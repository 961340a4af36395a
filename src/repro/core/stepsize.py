"""Per-layer step-size initialization (paper Alg. 1, lines 2–5):

    f_l = argmin_{f ∈ ℤ}  || W_l - Q_N(W_l; 2^{-f}) ||²

An integer grid search over f — the objective is piecewise smooth in Δ but f
ranges over a handful of integers, so exhaustive search is exact and cheap
(vectorized over candidates, one pass over the weights per candidate).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.quantizer import delta_from_f, quantize

# f ∈ [F_MIN, F_MAX]: Δ from 2^4=16 down to 2^-16.  Pretrained nets have
# |w| ≲ 1, so the optimum lies well inside this window for any N ≤ 8.
F_MIN = -4
F_MAX = 16


def sse_for_f(w: jax.Array, f, n_bits: int) -> jax.Array:
    d = delta_from_f(f)
    err = w - quantize(w, d, n_bits)
    return jnp.sum(jnp.square(err.astype(jnp.float32)))


@functools.partial(jax.jit, static_argnames=("n_bits", "f_min", "f_max"))
def optimal_f(
    w: jax.Array, n_bits: int, f_min: int = F_MIN, f_max: int = F_MAX
) -> Tuple[jax.Array, jax.Array]:
    """Return (f*, Δ*=2^{-f*}) minimizing the quantization SSE of ``w``.

    Ties break toward the smaller f (larger Δ), matching the paper's
    preference for the coarsest step that achieves the minimum (more head
    room inside the clip interval).  Jitted, so the per-candidate errors
    reduce in one fused pass instead of materializing a candidates × w
    tensor (a full-size layer stack would not fit in device memory).
    """
    fs = jnp.arange(f_min, f_max + 1)
    sses = jax.vmap(lambda f: sse_for_f(w, f, n_bits))(fs)
    idx = jnp.argmin(sses)  # argmin returns first minimum -> smallest f
    f_star = fs[idx]
    return f_star, delta_from_f(f_star)
