"""shard_map expert-parallel MoE == dense reference (8-device subprocess)."""
import os
import subprocess
import sys
import textwrap

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.models.moe import MoEConfig, moe_init, moe_apply_dense_ref, moe_apply
    from repro.models.moe_ep import moe_apply_ep
    from repro.nn.sharding import make_mesh

    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = MoEConfig(d_model=16, n_experts=8, top_k=2, d_ff_expert=8,
                    n_shared_experts=1, capacity_factor=8.0)
    key = jax.random.PRNGKey(0)
    p = moe_init(key, cfg)
    x = jax.random.normal(jax.random.fold_in(key, 1), (4, 6, 16)) * 0.5

    with jax.set_mesh(mesh):
        x_sh = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
        p_sh = jax.device_put(p, NamedSharding(mesh, P()))
        # expert leaves sharded over model
        for kname in ("gate_proj", "up_proj", "down_proj"):
            p_sh["experts"][kname]["kernel"] = jax.device_put(
                p["experts"][kname]["kernel"], NamedSharding(mesh, P("model", None, None)))

        @jax.jit
        def run(p, x):
            y, aux = moe_apply_ep(p, x, cfg=cfg, compute_dtype=jnp.float32,
                                  capacity_mult=8.0)
            return y, aux

        y_ep, aux = run(p_sh, x_sh)

        # gradients flow through the all_to_all routing
        @jax.jit
        def loss(p, x):
            y, _ = moe_apply_ep(p, x, cfg=cfg, compute_dtype=jnp.float32,
                                capacity_mult=8.0)
            return jnp.sum(y**2)
        g = jax.grad(loss)(p_sh, x_sh)
        gnorm = sum(float(jnp.abs(v).sum()) for v in jax.tree_util.tree_leaves(g))
        assert np.isfinite(gnorm) and gnorm > 0

    y_ref = moe_apply_dense_ref(p, x, cfg=cfg)
    err = float(jnp.max(jnp.abs(y_ep - y_ref)))
    print("MAX_ERR", err)
    assert err < 2e-4, err
    assert np.isfinite(float(aux["moe_aux_loss"]))
    print("OK")
""")


def test_moe_ep_matches_dense_ref():
    # the EP dispatch traces under jax.set_mesh over an all-Auto mesh
    # (repro.nn.sharding.make_mesh) with masked slot numbering, so
    # non-owned assignment partitions never consume send slots; the 1-D/2-D
    # EP output matches the dense reference within 2e-4.
    root = os.path.join(os.path.dirname(__file__), "..")
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True,
        timeout=600, env={**os.environ, "PYTHONPATH": "src"}, cwd=root,
    )
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert "OK" in r.stdout
