"""Pallas kernel: fused SYMOG optimizer update (paper Alg. 1 lines 15–17).

A naive jnp implementation of the SYMOG step reads/writes each O(params)
tensor ~6 times (quantize, error, scale-add, momentum, nesterov step,
clip).  The fusion does ONE read of (w, g, v) and ONE write of (w', v') —
the op is purely memory-bound, so this is a ~2.4× traffic reduction
(10 streams → 5, measured in tests/test_kernels.py via cost analysis).

Layout: inputs flattened/padded to (R, 128) f32; grid tiles R in blocks of
``BLOCK_ROWS`` (8·128-aligned for the VPU).  Scalars (Δ, λ_eff, η, μ) ride
in one (1, 4) VMEM block broadcast to every grid step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
BLOCK_ROWS = 256  # 256×128 f32 = 128 KiB per stream; 5 streams ≈ 640 KiB VMEM


def _kernel(scal_ref, w_ref, g_ref, v_ref, w_out_ref, v_out_ref, *, qmax: float):
    delta = scal_ref[0, 0]
    lam_eff = scal_ref[0, 1]
    lr = scal_ref[0, 2]
    mu = scal_ref[0, 3]

    w = w_ref[...]
    g = g_ref[...]
    v = v_ref[...]

    # quantize (round-half-even like the oracle) + clip to the mode grid
    m = jnp.clip(jnp.round(w / delta), -qmax, qmax)
    q = m * delta
    g_tot = g + lam_eff * (w - q)          # Eq. 4 gradient, pre-scaled
    v_new = mu * v + g_tot                 # momentum
    upd = g_tot + mu * v_new               # nesterov
    lim = delta * qmax
    w_new = jnp.clip(w - lr * upd, -lim, lim)  # §3.4 weight clipping

    w_out_ref[...] = w_new
    v_out_ref[...] = v_new


def symog_update_2d(w, g, v, scalars, *, n_bits: int, interpret: bool = False):
    """w/g/v: (R, 128) f32 with R % BLOCK_ROWS == 0; scalars: (1, 4) f32
    [Δ, λ_eff, η, μ].  Returns (w', v')."""
    R, C = w.shape
    assert C == LANE and R % BLOCK_ROWS == 0, (w.shape,)
    qmax = float(2 ** (n_bits - 1) - 1)
    grid = (R // BLOCK_ROWS,)
    blk = pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0))
    scal = pl.BlockSpec((1, 4), lambda i: (0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, qmax=qmax),
        grid=grid,
        in_specs=[scal, blk, blk, blk],
        out_specs=[blk, blk],
        out_shape=[
            jax.ShapeDtypeStruct((R, C), jnp.float32),
            jax.ShapeDtypeStruct((R, C), jnp.float32),
        ],
        interpret=interpret,
        name="symog_update",
    )(scalars, w, g, v)
