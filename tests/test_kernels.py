"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import fixedpoint_matmul, pack_weight, symog_update
from repro.kernels.fixedpoint_matmul.ref import fixedpoint_matmul_ref
from repro.kernels.symog_update.ref import symog_update_ref


@pytest.mark.parametrize("shape", [(64,), (100,), (57, 33), (4, 5, 6), (300, 128)])
@pytest.mark.parametrize("n_bits", [2, 4])
def test_symog_update_matches_oracle(rng, shape, n_bits):
    k1, k2, k3 = jax.random.split(rng, 3)
    w = jax.random.normal(k1, shape) * 0.3
    g = jax.random.normal(k2, shape) * 0.05
    v = jax.random.normal(k3, shape) * 0.01
    kw = dict(delta=0.25, lam_eff=0.7, lr=0.01, mu=0.9, n_bits=n_bits)
    w1, v1 = symog_update(w, g, v, **kw, interpret=True)
    w2, v2 = symog_update_ref(w, g, v, **kw)
    np.testing.assert_allclose(w1, w2, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(v1, v2, rtol=1e-6, atol=1e-7)


def test_symog_update_traced_scalars(rng):
    """Schedules are traced — the kernel must accept traced Δ/λ/η."""
    w = jax.random.normal(rng, (128,)) * 0.3
    g = jnp.zeros_like(w)
    v = jnp.zeros_like(w)

    @jax.jit
    def step(w, g, v, lam):
        return symog_update(w, g, v, delta=0.5, lam_eff=lam, lr=0.1, mu=0.9, n_bits=2,
                            interpret=True)

    w1, _ = step(w, g, v, jnp.float32(2.0))
    w2, _ = symog_update_ref(w, g, v, delta=0.5, lam_eff=2.0, lr=0.1, mu=0.9, n_bits=2)
    np.testing.assert_allclose(w1, w2, rtol=1e-6)


def test_symog_update_equals_paper_semantics(rng):
    """Fused kernel == Alg.1 l.15-17 composed from repro.core pieces."""
    from repro import core

    w = jax.random.normal(rng, (64, 32)) * 0.4
    g = jax.random.normal(jax.random.fold_in(rng, 1), (64, 32)) * 0.1
    v = jnp.zeros_like(w)
    f, delta = core.optimal_f(w, 2)
    lam, lr, mu = 3.0, 0.02, 0.9
    lam_eff = lam * 2.0 / w.size
    w_k, v_k = symog_update(w, g, v, delta=delta, lam_eff=lam_eff, lr=lr, mu=mu, n_bits=2,
                            interpret=True)
    # reference composition: reg grad → momentum → nesterov → clip
    g_tot = g + lam * core.layer_reg_grad(w, delta, 2)
    v_ref = mu * v + g_tot
    w_ref = core.clip_to_range(w - lr * (g_tot + mu * v_ref), delta, 2)
    np.testing.assert_allclose(w_k, w_ref, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(v_k, v_ref, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("mkn", [(4, 32, 64), (130, 256, 200), (1, 128, 128), (64, 64, 96)])
@pytest.mark.parametrize("n_bits", [2, 4])
@pytest.mark.parametrize("f", [-1, 0, 3])
def test_fixedpoint_matmul_matches_oracle(rng, mkn, n_bits, f):
    M, K, N = mkn
    k1, k2 = jax.random.split(rng)
    w = jax.random.normal(k1, (K, N)) * 0.2
    x = jax.random.normal(k2, (M, K))
    pw = pack_weight(w, f, n_bits)
    y = fixedpoint_matmul(x, pw, f, n_bits=n_bits, n_out=N, interpret=True)
    y_ref = fixedpoint_matmul_ref(x, pw, f, n_bits=n_bits, n_out=N)
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)


def test_fixedpoint_matmul_batched_input(rng):
    """Leading batch dims are flattened/restored by the wrapper."""
    w = jax.random.normal(rng, (32, 48)) * 0.3
    x = jax.random.normal(jax.random.fold_in(rng, 1), (2, 3, 32))
    pw = pack_weight(w, 2, 2)
    y = fixedpoint_matmul(x, pw, 2, n_bits=2, n_out=48, interpret=True)
    assert y.shape == (2, 3, 48)
    y_ref = fixedpoint_matmul_ref(x.reshape(-1, 32), pw, 2, n_bits=2, n_out=48)
    np.testing.assert_allclose(y.reshape(-1, 48), y_ref, rtol=1e-5, atol=1e-5)


def test_fixedpoint_matmul_equals_float_quantized_matmul(rng):
    """The packed path is EXACT vs x @ Q(w): SYMOG mantissas are exact ints
    and the power-of-two scale is exact — no calibration loss (DESIGN §2)."""
    from repro import core

    w = jax.random.normal(rng, (64, 64)) * 0.2
    x = jax.random.normal(jax.random.fold_in(rng, 1), (8, 64))
    f = 2
    qw = core.quantize(w, core.delta_from_f(f), 2)
    y_float = x @ qw
    pw = pack_weight(w, f, 2)
    y_packed = fixedpoint_matmul(x, pw, f, n_bits=2, n_out=64, interpret=True)
    np.testing.assert_allclose(y_packed, y_float, rtol=1e-5, atol=1e-5)
