"""Pallas kernel: fixed-point matmul with 2/4-bit packed weights.

    y (M,N) = x (M,K) @ (m (K,N) · 2^{-f}) + b (N)

``m`` is streamed from HBM as int8 words holding 8/n_bits mantissas each
(packed along N in the split-planes order of repro.core.packing).  Per
(bm, bw·per) output tile the kernel loops K-blocks: each 128-word chunk of
the (bk, bw) word block yields ``per`` (bk, 128) mantissa planes (shift +
arithmetic shift on the VPU), which concatenate along lanes to the
(bk, bw·per) operand of one MXU dot into an fp32 accumulator tile.  Every
block is (8, 128)-tiled and the unpack needs no minor-dim reshape.

The kernel's output columns follow the word layout of the 2-D (K, words)
view: chunk c of that view, plane i, lane j lands in column
``c·128·per + i·128 + j``.  For a packed axis that is a whole number of
chunks this IS the value order; ``ops.py`` maps any other layout back.

The power-of-two scale multiplies the tile ONCE on the last K step (the
TPU analogue of the paper's bit-shift dequantization — exponent add,
exact) and the bias rides the same epilogue, so a full dense layer is one
kernel launch.

Activations keep their dtype on the wire: bf16 x dots against bf16
mantissas (|m| ≤ 7 is exact in bf16) with an fp32 accumulator — the MXU
path real serving uses.

HBM traffic for weights: N·K·n_bits/8 bytes — 8× (2-bit) less than bf16.
Decode matvecs are weight-bandwidth-bound, so this is the serving win.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.packing import PLANE_WORDS, unpack_planes


def _kernel(scale_ref, bias_ref, x_ref, w_ref, o_ref, *, n_bits: int, nk: int):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]
    m = unpack_planes(w_ref[...], n_bits, x.dtype)  # (bk, bw·per) mantissas
    o_ref[...] += jnp.dot(x, m, preferred_element_type=jnp.float32)

    @pl.when(k_idx == nk - 1)
    def _finish():
        o_ref[...] = o_ref[...] * scale_ref[0, 0] + bias_ref[...].astype(jnp.float32)


def fixedpoint_matmul_padded(x, packed_w, scale, bias=None, *, n_bits: int,
                             bm: int, bw: int, bk: int, interpret: bool = False):
    """x (M,K) float; packed_w (K, W) int8 words; scale (1,1) f32; bias
    (1, W·per) float or None, in kernel column order.  Returns (M, W·per)
    f32 in kernel column order (module docstring).
    M % bm == K % bk == W % bw == 0 and bw % 128 == 0 (pad in ops.py)."""
    M, K = x.shape
    W = packed_w.shape[1]
    per = 8 // n_bits
    assert packed_w.shape[0] == K and W % bw == 0 and bw % PLANE_WORDS == 0, (
        packed_w.shape, K, bw)
    n_out, bn = W * per, bw * per
    if bias is None:
        bias = jnp.zeros((1, n_out), jnp.float32)
    nk = K // bk
    grid = (M // bm, W // bw, nk)
    return pl.pallas_call(
        functools.partial(_kernel, n_bits=n_bits, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bw), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, n_out), jnp.float32),
        interpret=interpret,
        name=f"fixedpoint_matmul_{n_bits}bit",
    )(scale, bias, x, packed_w)
