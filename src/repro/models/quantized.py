"""Quantized-execution dispatch: run model layers natively on ``Packed``
SYMOG serving artifacts (DESIGN.md §3).

``core.symog.pack_tree`` replaces every quantizable leaf with a
``core.packing.Packed`` (int8 words, 8/n_bits mantissas each, one integer
exponent f per layer — or per expert for MoE stacks).  The layer stack
detects those leaves *at its matmul call sites* and routes there instead of
densifying the whole tree up front, so the packed bytes are what lives in
(and streams from) device memory:

  'pallas'    — kernels.fixedpoint_matmul on TPU: packed words stream
                HBM→VMEM and unpack on the VPU next to the MXU dot — the
                8×/4× weight-bandwidth win at the decode hot spot.
  'interpret' — the same kernel under pallas interpret mode (CI / CPU
                validation of the kernel path, slow).
  'unpack'    — dequantize-then-dot in plain XLA.  Dequantization is exact
                (mantissa × power-of-two scale), so this path is
                bit-identical to serving the ``quantize_tree`` float params
                — tests assert token-exact generation on any backend.

  'dense'     — serve the exactly-dequantized float tree: the engine
                densifies the packed artifact ONCE at construction instead
                of unpacking per call (off-TPU the unpack path is 4-5x
                slower than dense — kernel_bench).  Direct calls under
                'dense' take the unpack path (still exact).

The default 'auto' resolves to 'pallas' on TPU and 'dense' elsewhere;
override with ``set_packed_backend()`` or ``REPRO_PACKED_BACKEND``.  The
backend state itself lives in ``repro.kernels.dispatch`` (one module owns
both the packed and the paged-attention backend selection); the names are
re-exported here for compatibility.

Dispatch rule (DESIGN.md §3): a leaf is servable-packed iff it is a
``Packed`` instance; everything else (norm scales, biases, routers, the
positional machinery) stays float and takes the ordinary path.  Weights
whose consumer is not a plain `x @ W` contraction (embedding gather, tied
read-out, MLA's absorbed einsums) dequantize on the fly via ``as_dense`` /
``packed_take`` — still 4×/8× smaller at rest, dequantized per use.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.packing import Packed, unpack, unpack_int, values_per_byte
from repro.core.quantizer import delta_from_f
from repro.kernels.dispatch import (
    PACKED_BACKENDS as BACKENDS,
    get_packed_backend,
    resolve_packed_backend as resolve_backend,
    set_packed_backend,
)
from repro.kernels.fixedpoint_matmul.ops import (
    fixedpoint_matmul,
    fixedpoint_matmul_experts,
)

__all__ = [
    "BACKENDS",
    "set_packed_backend",
    "get_packed_backend",
    "resolve_backend",
    "is_packed",
    "tree_has_packed",
    "as_dense",
    "unpack_params",
    "scan_ready",
    "packed_dense_apply",
    "packed_expert_einsum",
    "packed_take",
]


# ---------------------------------------------------------------------------
# predicates / conversions
# ---------------------------------------------------------------------------
def is_packed(leaf: Any) -> bool:
    return isinstance(leaf, Packed)


def tree_has_packed(tree: Any) -> bool:
    return any(
        is_packed(l)
        for l in jax.tree_util.tree_leaves(tree, is_leaf=is_packed)
    )


def as_dense(leaf: Any, dtype=None) -> jax.Array:
    """Dequantize a Packed leaf (exact); cast a float leaf.  For consumers
    that are not a plain right-matmul (absorbed MLA einsums, oracles)."""
    if is_packed(leaf):
        return unpack(leaf, dtype or jnp.float32)
    return leaf if dtype is None else leaf.astype(dtype)


def unpack_params(tree: Any, dtype=None) -> Any:
    """Densify every Packed leaf of a param tree (debug / paths that cannot
    consume packed weights yet, e.g. the shard_map expert-parallel MoE)."""
    return jax.tree_util.tree_map(
        lambda l: as_dense(l, dtype) if is_packed(l) else l,
        tree, is_leaf=is_packed,
    )


def scan_ready(tree: Any, count: int) -> Any:
    """Make a stacked (scan-grouped) param subtree sliceable by lax.scan /
    vmap: both slice the leading axis of EVERY leaf, and a Packed leaf whose
    exponent is a scalar (one Δ for the whole stack) has no axis to slice.
    Broadcast such f to (count,) — each scanned layer then carries its own
    (identical) exponent and Packed slices like any float leaf."""

    def fix(l):
        if is_packed(l) and jnp.ndim(l.f) == 0:
            return Packed(data=l.data, n_bits=l.n_bits,
                          f=jnp.broadcast_to(jnp.asarray(l.f), (count,)))
        return l

    return jax.tree_util.tree_map(fix, tree, is_leaf=is_packed)


# ---------------------------------------------------------------------------
# packed layer primitives
# ---------------------------------------------------------------------------
def packed_dense_apply(p, x, *, n_in: int = 1, compute_dtype=None) -> jax.Array:
    """``dense_apply`` for a dict whose 'kernel' is Packed.

    Contracts the last ``n_in`` dims of x with the first n_in dims of the
    (original-shape) kernel.  The words reshape straight into the kernel's
    (K, words) 2-D view with no repack; when the out dims are several
    (q/k/v: (D, H, hd) packed along hd) each hd row is its own packing
    row, which the kernel wrapper maps back (``row_values``).
    """
    pk: Packed = p["kernel"]
    bias = p.get("bias")
    if compute_dtype is not None:
        x = x.astype(compute_dtype)

    backend = resolve_backend()
    f = jnp.asarray(pk.f)
    if backend in ("unpack", "dense"):
        k = unpack(pk, x.dtype)
        lhs = tuple(range(x.ndim - n_in, x.ndim))
        rhs = tuple(range(n_in))
        y = jax.lax.dot_general(x, k, ((lhs, rhs), ((), ())))
        if bias is not None:
            y = y + bias.astype(y.dtype)
        return y
    if f.ndim != 0:
        # one exponent per leading slice belongs to expert stacks
        # (packed_expert_einsum) or to a scan-stacked group that was not
        # sliced per layer — neither reaches a plain dense layer.
        raise ValueError(
            f"packed dense kernel needs a scalar exponent, got f of shape {f.shape}"
        )

    in_dims = pk.shape[:n_in]
    out_dims = pk.shape[n_in:]
    K = int(math.prod(in_dims))
    N = int(math.prod(out_dims))
    per = values_per_byte(pk.n_bits)
    lead = x.shape[: x.ndim - n_in]
    x2 = x.reshape(*lead, K)
    w2 = pk.data.reshape(K, N // per)
    b2 = None if bias is None else bias.reshape(N)
    y = fixedpoint_matmul(
        x2, w2, f, b2, n_bits=pk.n_bits, n_out=N, row_values=pk.shape[-1],
        interpret=(backend == "interpret"), out_dtype=x.dtype,
    )
    return y.reshape(*lead, *out_dims)


def packed_expert_einsum(x, pk: Packed, *, compute_dtype=None) -> jax.Array:
    """einsum('ECK,EKN->ECN') against a per-expert Packed stack.

    Covers both MoE projections: gate/up (E,D,F) and down (E,F,D) — the
    contraction is always over the middle axis, packing over the last.
    ``pk.f`` is the per-expert exponent vector (one Δ per expert)."""
    if compute_dtype is not None:
        x = x.astype(compute_dtype)
    backend = resolve_backend()
    if backend in ("unpack", "dense"):
        return jnp.einsum("ECK,EKN->ECN", x, unpack(pk, x.dtype))
    return fixedpoint_matmul_experts(
        x, pk.data, jnp.asarray(pk.f), n_bits=pk.n_bits, n_out=pk.shape[-1],
        interpret=(backend == "interpret"), out_dtype=x.dtype,
    )


def packed_take(pk: Packed, ids, *, dtype=None) -> jax.Array:
    """Embedding lookup from a Packed (vocab, d) table: gather the packed
    *rows* (words pack along d, so a row gather never splits a packing
    chunk), then
    dequantize only the gathered (..., d/per) words — O(tokens·d) unpack
    work instead of O(vocab·d)."""
    dtype = dtype or jnp.float32
    f = jnp.asarray(pk.f)
    if f.ndim != 0:  # per-leading-dim f tables would gather scales too
        return jnp.take(unpack(pk, dtype), ids, axis=0)
    rows = jnp.take(pk.data, ids, axis=0)
    m = unpack_int(rows, pk.n_bits, pk.shape[-1]).astype(dtype)
    return m * delta_from_f(f).astype(dtype)
