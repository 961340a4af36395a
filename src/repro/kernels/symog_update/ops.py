"""Public wrapper: arbitrary-shape SYMOG fused update.

Flattens/pads the parameter to the kernel's (R, 128) layout, runs the
Pallas kernel, restores the original shape.  Compiles to Mosaic on TPU;
pass ``interpret=True`` to run the Pallas interpreter (CPU tests).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.symog_update.kernel import BLOCK_ROWS, LANE, symog_update_2d

_TILE = BLOCK_ROWS * LANE


@functools.partial(jax.jit, static_argnames=("n_bits", "interpret"))
def symog_update(w, g, v, *, delta, lam_eff, lr, mu, n_bits: int = 2,
                 interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Fused SYMOG step for one parameter tensor (any shape).

    Returns (w', v') with the semantics of ref.symog_update_ref.
    Scalars may be traced (schedules) — they ride in a (1,4) VMEM block.
    """
    shape, dtype = w.shape, w.dtype
    n = w.size
    pad = (-n) % _TILE

    def flat(x):
        x = x.reshape(-1).astype(jnp.float32)
        if pad:
            x = jnp.pad(x, (0, pad))
        return x.reshape(-1, LANE)

    scalars = jnp.stack(
        [jnp.asarray(delta, jnp.float32), jnp.asarray(lam_eff, jnp.float32),
         jnp.asarray(lr, jnp.float32), jnp.asarray(mu, jnp.float32)]
    ).reshape(1, 4)
    w2, v2 = symog_update_2d(flat(w), flat(g), flat(v), scalars,
                             n_bits=n_bits, interpret=interpret)

    def unflat(x):
        return x.reshape(-1)[:n].reshape(shape).astype(dtype)

    return unflat(w2), unflat(v2)
