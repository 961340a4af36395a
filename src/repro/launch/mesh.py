"""Production mesh builders.

Functions (never module-level constants) so importing this module never
touches jax device state.  Single pod: (data=16, model=16) = 256 chips of
TPU v5e.  Multi-pod: (pod=2, data=16, model=16) = 512 chips — the ``pod``
axis is outermost so only data-parallel gradient all-reduces cross the DCN
boundary (verified by the dry-run collective parse).
"""
from __future__ import annotations

import math

import jax

from repro.nn.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, have {len(devices)} — "
            "run under dryrun.py (XLA_FLAGS=--xla_force_host_platform_device_count=512)"
        )
    return make_mesh(shape, axes, devices=devices[:need])


def make_host_mesh():
    """Whatever this process actually has (tests / examples): (1,1) mesh."""
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))
