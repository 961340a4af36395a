"""Public wrapper: packed fixed-point matmul for arbitrary (M, K, N).

``pack_weight`` quantizes a SYMOG-converged weight to packed mantissas;
``fixedpoint_matmul`` pads to the kernel's block grid and dispatches — with
optional fused bias add and bf16 activations (the epilogue real dense
layers need, DESIGN.md §3) — and maps the kernel's column order back to
the value order where a packed row is not a whole number of 128-word
chunks (``kernel_columns``).  ``fixedpoint_matmul_experts`` vmaps the
kernel over a leading expert dim with a per-expert exponent vector ``f`` —
the MoE-stack form (each expert is a "layer" in the paper's Δ-per-layer
sense).  Under a mesh of several devices the kernel runs per device on
whole operands (``dispatch.shard_kernel``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.packing import PLANE_WORDS, pack_int, values_per_byte
from repro.core.quantizer import delta_from_f, quantize_int
from repro.kernels.dispatch import shard_kernel
from repro.kernels.fixedpoint_matmul.kernel import fixedpoint_matmul_padded


def pack_weight(w: jax.Array, f, n_bits: int = 2) -> jax.Array:
    """(K, N) float weight -> (K, N·n_bits/8) int8 packed mantissas."""
    delta = delta_from_f(f)
    m = quantize_int(w, delta, n_bits)
    return pack_int(m, n_bits)


def _pad_to(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _as_compute(x):
    """Keep float activations in their wire dtype; promote ints to f32."""
    return x if jnp.issubdtype(x.dtype, jnp.floating) else x.astype(jnp.float32)


@functools.lru_cache(maxsize=None)
def kernel_columns(n_out: int, row_values: int, n_bits: int):
    """Kernel output column of every value column, or None when they agree.

    The packed leaf holds ``n_out // row_values`` rows of ``row_values``
    values along its last axis, each packed in core.packing's split-planes
    order; the kernel reads the rows back to back as one word axis and
    numbers its columns by 128-word chunks of THAT axis (kernel.py).  The
    two orders agree exactly when a row is a whole number of chunks."""
    per = values_per_byte(n_bits)
    row_words = row_values // per
    if row_words % PLANE_WORDS == 0:
        return None
    v = np.arange(row_values)
    chunk = v // (PLANE_WORDS * per)
    width = np.minimum(PLANE_WORDS, row_words - chunk * PLANE_WORDS)
    off = v - chunk * PLANE_WORDS * per
    plane, lane = off // width, off % width
    word = np.arange(n_out // row_values)[:, None] * row_words + (chunk * PLANE_WORDS + lane)
    cols = (word // PLANE_WORDS) * PLANE_WORDS * per + plane * PLANE_WORDS + word % PLANE_WORDS
    cols = cols.reshape(-1)
    cols.setflags(write=False)  # cached: every caller shares this array
    return cols


def _bias_to_kernel(bias, n_out, n_pad, cols):
    b = bias.reshape(n_out).astype(jnp.float32)
    if cols is None:
        return _pad_to(b, 0, n_pad).reshape(1, n_pad)
    return jnp.zeros((n_pad,), jnp.float32).at[cols].set(b).reshape(1, n_pad)


def _from_kernel(y, n_out, cols):
    return y[..., :n_out] if cols is None else jnp.take(y, cols, axis=-1)


def _blocks(M, K, bm, bk):
    """Row/contraction tiles: bm a multiple of 8 (or all of M), bk of 128."""
    return min(bm, -(-M // 8) * 8), min(bk, -(-K // 128) * 128)


@functools.partial(
    jax.jit,
    static_argnames=("n_bits", "n_out", "row_values", "bm", "bw", "bk", "interpret",
                     "out_dtype"),
)
def fixedpoint_matmul(x, packed_w, f, bias=None, *, n_bits: int = 2, n_out: int,
                      row_values: int = 0, bm: int = 128, bw: int = PLANE_WORDS,
                      bk: int = 128, interpret: bool = False, out_dtype=None) -> jax.Array:
    """y = x @ (unpack(packed_w)·2^{-f}) [+ bias].  x: (..., K) float.

    packed_w (K, n_out·n_bits/8) int8 holds ``n_out // row_values`` packed
    rows per input row (``row_values`` 0: one row of n_out values) — a
    kernel whose out dims were flattened, e.g. (D, H, hd) packed along hd."""
    per = values_per_byte(n_bits)
    lead = x.shape[:-1]
    K = x.shape[-1]
    x2 = _as_compute(x).reshape(-1, K)
    M = x2.shape[0]
    cols = kernel_columns(n_out, row_values or n_out, n_bits)

    bm_, bk_ = _blocks(M, K, bm, bk)
    x2 = _pad_to(_pad_to(x2, 0, bm_), 1, bk_)
    w2 = _pad_to(_pad_to(packed_w, 0, bk_), 1, bw)
    n_pad = w2.shape[1] * per

    if bias is None:
        b2 = jnp.zeros((1, n_pad), jnp.float32)
    else:
        b2 = _bias_to_kernel(bias, n_out, n_pad, cols)
    scale = delta_from_f(f).reshape(1, 1)
    run = functools.partial(fixedpoint_matmul_padded, n_bits=n_bits, bm=bm_, bw=bw, bk=bk_,
                            interpret=interpret)
    y = shard_kernel(run, x2, w2, scale, b2)
    y = _from_kernel(y[:M], n_out, cols).reshape(*lead, n_out)
    return y.astype(out_dtype) if out_dtype is not None else y


@functools.partial(
    jax.jit,
    static_argnames=("n_bits", "n_out", "bm", "bw", "bk", "interpret", "out_dtype"),
)
def fixedpoint_matmul_experts(x, packed_w, f, *, n_bits: int = 2, n_out: int,
                              bm: int = 128, bw: int = PLANE_WORDS, bk: int = 128,
                              interpret: bool = False, out_dtype=None) -> jax.Array:
    """Per-expert packed matmul: y[e] = x[e] @ (unpack(w[e])·2^{-f[e]}).

    x (E, C, K) float; packed_w (E, K, n_out·n_bits/8) int8; f (E,) int32.
    The expert dim rides a vmap over the padded kernel (one extra grid dim
    on TPU), so the per-expert scale stays a scalar inside each program.
    """
    E, C, K = x.shape
    x2 = _as_compute(x)
    cols = kernel_columns(n_out, n_out, n_bits)

    bm_, bk_ = _blocks(C, K, bm, bk)
    x2 = _pad_to(_pad_to(x2, 1, bm_), 2, bk_)
    w2 = _pad_to(_pad_to(packed_w, 1, bk_), 2, bw)

    scale = delta_from_f(f).reshape(E, 1, 1)
    run = functools.partial(fixedpoint_matmul_padded, n_bits=n_bits, bm=bm_, bw=bw,
                            bk=bk_, interpret=interpret)
    y = shard_kernel(jax.vmap(lambda xe, we, se: run(xe, we, se)), x2, w2, scale)
    y = _from_kernel(y[:, :C], n_out, cols)
    return y.astype(out_dtype) if out_dtype is not None else y
