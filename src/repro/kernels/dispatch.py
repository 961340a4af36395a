"""Kernel backend dispatch: one module owning the process-global backend
selection for BOTH fused serving kernels (DESIGN.md §3, §9).

Packed matmul backends (``set_packed_backend`` / ``REPRO_PACKED_BACKEND``):

  'pallas'    — kernels.fixedpoint_matmul compiled for TPU: packed words
                stream HBM→VMEM and unpack next to the MXU dot.
  'interpret' — the same kernel under the Pallas interpreter (CI / CPU
                validation of the kernel path, slow).
  'unpack'    — dequantize-then-dot in plain XLA per call.  Exact, but the
                per-step dequantization makes packed serving ~4-5x slower
                than dense on CPU (kernel_bench decode_matmul entries).
  'dense'     — serve the exactly-dequantized float tree: ServeEngine
                densifies a packed artifact ONCE at construction (with a
                WARNING), so off-TPU ``--packed`` is never slower than
                float.  Direct ``packed_dense_apply`` calls under 'dense'
                fall back to the per-call unpack path (still exact).

Attention backends (``set_attention_backend`` / ``REPRO_ATTN_BACKEND``):

  'fused'           — kernels.paged_attention compiled for TPU: the
                      block-table gather runs inside the online-softmax
                      loop; nothing materializes the logical cache view.
  'fused-interpret' — the same kernel under the Pallas interpreter (CI
                      parity against the composed path on CPU).
  'composed'        — gather_logical → mask → dense attention in plain XLA:
                      the reference implementation the kernel is tested
                      against (models/attention.py).

Both default to 'auto': the fused Pallas path on TPU, the CPU-honest
fallback elsewhere ('dense' / 'composed').  ``ServeEngine`` pins the
resolved values at construction and restores the globals around every
jitted call, so a ``set_*_backend()`` after construction can never desync
a cached trace (DESIGN.md §4).

``shard_kernel`` is how every kernel call meets an ambient mesh: XLA cannot
partition a Mosaic kernel, so under a mesh of several devices the call
rides a ``jax.shard_map`` (DESIGN.md §12).
"""
from __future__ import annotations

import os

import jax
from jax.sharding import PartitionSpec as P

from repro.nn.sharding import current_mesh

PACKED_BACKENDS = ("auto", "pallas", "interpret", "unpack", "dense")
ATTN_BACKENDS = ("auto", "fused", "fused-interpret", "composed")

_packed_backend = os.environ.get("REPRO_PACKED_BACKEND", "auto")
_attn_backend = os.environ.get("REPRO_ATTN_BACKEND", "auto")


def set_packed_backend(name: str) -> None:
    """Select how Packed matmuls execute: auto|pallas|interpret|unpack|dense."""
    global _packed_backend
    if name not in PACKED_BACKENDS:
        raise ValueError(f"backend must be one of {PACKED_BACKENDS}, got {name!r}")
    _packed_backend = name


def get_packed_backend() -> str:
    return _packed_backend


def resolve_packed_backend() -> str:
    """'auto' → the fused Pallas kernel on TPU; 'dense' elsewhere (the
    unpack-then-dot path loses to dense matmuls off-TPU — the satellite
    regression kernel_bench documents, so auto never picks it)."""
    if _packed_backend != "auto":
        return _packed_backend
    return "pallas" if jax.default_backend() == "tpu" else "dense"


def set_attention_backend(name: str) -> None:
    """Select the paged-decode attention path: auto|fused|fused-interpret|composed."""
    global _attn_backend
    if name not in ATTN_BACKENDS:
        raise ValueError(f"backend must be one of {ATTN_BACKENDS}, got {name!r}")
    _attn_backend = name


def get_attention_backend() -> str:
    return _attn_backend


def resolve_attention_backend() -> str:
    if _attn_backend != "auto":
        return _attn_backend
    return "fused" if jax.default_backend() == "tpu" else "composed"


def shard_kernel(call, *args, in_specs=None, out_specs=P()):
    """``call(*args)`` for a Pallas kernel call under the ambient mesh.

    Off-mesh (or on one device) this is a plain call.  Under a mesh of
    several devices XLA refuses to partition a Mosaic kernel — even over
    replicated operands — so the call runs inside a ``jax.shard_map``:
    over ``in_specs``/``out_specs`` where the caller has a per-shard split,
    otherwise with every operand and the result replicated, so each device
    runs the whole call on whole operands."""
    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return call(*args)
    if in_specs is None:
        in_specs = tuple(P() for _ in args)
    return jax.shard_map(
        call, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )(*args)
