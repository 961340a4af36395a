"""Sharded multi-device serving (DESIGN.md §12) on a simulated CPU mesh.

The CI ``multidevice`` job runs pytest itself under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``; everywhere else
these tests skip (1 device).  Contracts:

  * greedy ``serve()`` on a (data, model) mesh is TOKEN-IDENTICAL to the
    single-device scheduler for fully-paged decoder archs, for both
    ``quantize_tree`` and ``pack_tree`` artifacts.  Bit-identity of the
    logits is NOT promised: model-axis contractions psum partial products,
    and float accumulation order differs (measured ~1e-6 relative on the
    reduced configs — far from the greedy argmax margins).  Temperature
    sampling can therefore flip near-ties; the identity bar is greedy;
  * quantized int4/int8 paged pools shard their KV-head axis over
    ``model`` when heads divide (per-device resident bytes drop), scale
    leaves and block tables stay replicated, and the token streams still
    match single-device;
  * ep-MoE archs (olmoe / deepseek family, ``moe_impl='ep'``) decode under
    continuous batching through the shard_map all_to_all dispatch instead
    of raising, and match the single-device dispatch-MoE streams.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import configs, core
from repro.models import init_lm, set_packed_backend
from repro.nn.sharding import make_mesh
from repro.serve import Request, ServeConfig, ServeEngine

pytestmark = [
    pytest.mark.multidevice,
    pytest.mark.skipif(jax.device_count() < 8, reason="needs 8 simulated devices"),
]

MAX_LEN = 32


@pytest.fixture(autouse=True)
def unpack_backend():
    set_packed_backend("unpack")
    yield
    set_packed_backend("auto")


def _requests(vocab):
    return [
        Request(tokens=np.arange(1, 6) % vocab, max_new_tokens=8),
        Request(tokens=np.arange(3, 12) % vocab, max_new_tokens=6),
        Request(tokens=np.array([7, 7, 2]) % vocab, max_new_tokens=8),
    ]


def _trees(cfg):
    params = init_lm(jax.random.PRNGKey(0), cfg)
    scfg = core.SymogConfig(n_bits=2, total_steps=1)
    st = core.symog_init(params, scfg)
    return core.quantize_tree(params, st, scfg), core.pack_tree(params, st, scfg)


def _tokens(eng, cfg, config=None):
    config = config or ServeConfig(n_slots=2, temperature=0.0)
    return [c.tokens for c in eng.serve(_requests(cfg.vocab_size), config)]


# ---------------------------------------------------------------------------
# fully-paged decoders: token-identical, qt and packed artifacts
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["internlm2-1.8b", "gemma2-27b", "granite-34b"])
def test_sharded_serve_token_identical(arch):
    cfg = configs.get_reduced(arch)
    qt, pt = _trees(cfg)
    ref = _tokens(ServeEngine(cfg, qt, max_len=MAX_LEN, compute_dtype=jnp.float32), cfg)
    mesh = make_mesh((2, 4), ("data", "model"))
    eng = ServeEngine(cfg, qt, max_len=MAX_LEN, compute_dtype=jnp.float32, mesh=mesh)
    assert eng.rules is not None and eng.model_shards() == 4
    assert _tokens(eng, cfg) == ref
    # the Packed int8-word artifact shards through the same rules (leaves
    # flatten as <param>/0 and match their parent path) and stays exact
    engp = ServeEngine(cfg, pt, max_len=MAX_LEN, compute_dtype=jnp.float32, mesh=mesh)
    assert _tokens(engp, cfg) == ref


def test_engine_pins_ambient_mesh_at_construction():
    cfg = configs.get_reduced("internlm2-1.8b")
    qt, _ = _trees(cfg)
    mesh = make_mesh((2, 4), ("data", "model"))
    with jax.set_mesh(mesh):
        eng = ServeEngine(cfg, qt, max_len=MAX_LEN, compute_dtype=jnp.float32)
    assert eng.mesh is mesh  # jax.set_mesh construction pins, like backends


# ---------------------------------------------------------------------------
# quantized pools: KV-head axis sharded, scales/tables replicated
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", ["int8_fp", "int4_fp"])
def test_quantized_pool_shards_kv_heads(dtype):
    from repro.models.lm import PAGED_CACHE_LEAVES, scan_groups

    cfg = dataclasses.replace(configs.get_reduced("internlm2-1.8b"), kv_cache_dtype=dtype)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    ref_eng = ServeEngine(cfg, params, max_len=MAX_LEN, compute_dtype=jnp.float32)
    ref = _tokens(ref_eng, cfg)

    # 2 model shards divide the 2 KV heads; 4 would not (replication fallback)
    mesh = make_mesh((4, 2), ("data", "model"))
    eng = ServeEngine(cfg, params, max_len=MAX_LEN, compute_dtype=jnp.float32, mesh=mesh)
    config = ServeConfig(n_slots=2, temperature=0.0)
    comps, sched = eng.serve(_requests(cfg.vocab_size), config, return_scheduler=True)
    assert [c.tokens for c in comps] == ref

    n_data, n_sharded, n_scale = 0, 0, 0
    for g in scan_groups(cfg):
        axis = 1 if g.stacked else 0
        for j in range(len(g.unit)):
            for name, leaf in sched.caches[g.name][f"sub{j}"].items():
                spec = leaf.sharding.spec
                if g.paged[j] and name in PAGED_CACHE_LEAVES:
                    n_data += 1
                    head_spec = spec[axis + 1] if len(spec) > axis + 1 else None
                    if head_spec == "model":
                        n_sharded += 1
                        # per-device slice holds K/m heads of every block
                        local = leaf.addressable_shards[0].data.shape
                        assert local[axis + 1] * 2 == leaf.shape[axis + 1]
                else:
                    n_scale += 1
                    # scale exponents are allocated replicated; after a
                    # decode step XLA propagation may co-shard them with the
                    # pool on their trailing KV-head axis, never elsewhere
                    assert all(s is None for s in spec[:-1]), (name, spec)
                    assert spec[-1] in (None, "model"), (name, spec)
    assert n_data and n_sharded == n_data  # every data pool leaf sharded
    assert n_scale  # scale siblings exist, head-axis-or-replicated
    assert all(s is None for s in sched._block_tables.sharding.spec)


def test_pool_replication_fallback_when_heads_do_not_divide():
    """KV heads that don't divide the model axis replicate (the same
    shape-aware fallback the param rules use) — and serving still matches."""
    from repro.nn.sharding import make_rules
    from repro.serve.sharding import pool_head_shards, pool_pspec

    mesh = make_mesh((2, 4), ("data", "model"))
    rules = make_rules(mesh, "dp_tp")
    assert pool_head_shards(rules, (9, 2, 16, 8), 0) == 1  # 2 heads, 4 shards
    assert pool_head_shards(rules, (9, 4, 16, 8), 0) == 4
    assert pool_head_shards(rules, (3, 9, 4, 16, 8), 1) == 4  # stacked
    assert pool_head_shards(rules, (9, 16, 7), 0) == 1  # MLA rank-space leaf
    assert tuple(pool_pspec(rules, (9, 4, 16, 8), 0)) == (None, "model", None, None)
    assert tuple(pool_pspec(rules, (9, 2, 16, 8), 0)) == ()


# ---------------------------------------------------------------------------
# ep-MoE: olmoe / deepseek decode under continuous batching on the mesh
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["olmoe-1b-7b", "deepseek-v3-671b"])
def test_ep_moe_decodes_under_scheduler(arch):
    cfg = dataclasses.replace(configs.get_reduced(arch), moe_impl="ep")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    ref_eng = ServeEngine(cfg, params, max_len=MAX_LEN, compute_dtype=jnp.float32)
    assert not ref_eng.capabilities()["ep_moe"]  # off-mesh: dispatch fallback
    ref = _tokens(ref_eng, cfg)

    mesh = make_mesh((2, 4), ("data", "model"))
    eng = ServeEngine(cfg, params, max_len=MAX_LEN, compute_dtype=jnp.float32, mesh=mesh)
    cap = eng.capabilities()["ep_moe"]
    assert bool(cap), cap.reason
    # token-identical here at reduced scale; the documented bound (§12) is
    # agreement up to float reduction order — EP's scatter-add combine and
    # the dispatch path accumulate in different orders (~1e-6 rel logits)
    assert _tokens(eng, cfg) == ref
