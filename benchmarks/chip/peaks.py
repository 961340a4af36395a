"""Published peaks of the chips the benchmark runs on, keyed by
``jax.Device.device_kind``.  A device missing from the table is an error:
a roofline share against a guessed peak is not a measurement."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' system architecture page",
    },
}


def peak_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device kind {device_kind!r}; add it to "
            f"benchmarks/chip/peaks.py with its source (known: {sorted(PEAKS)})"
        ) from None
