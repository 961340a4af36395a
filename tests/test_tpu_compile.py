"""Ahead-of-time compiles of the serving kernels for a described TPU v5e.

The TPU compiler compiles for a chip that is described, not attached: these
tests lower each Pallas kernel at the registry's real widths and compile it
for one chip of a ``v5e:2x2`` topology, so a block layout Mosaic refuses
fails here instead of on the chip.  Nothing runs; each case asserts that
the compiled program holds the kernel (``tpu_custom_call``).

The topology is described only inside the module fixture (never while a
module is imported): the TPU library admits one process at a time, and the
fixture skips where no topology can be described.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.packing import values_per_byte
from repro.kernels.fixedpoint_matmul.ops import fixedpoint_matmul, fixedpoint_matmul_experts
from repro.kernels.paged_attention.kernel import heads_per_step
from repro.kernels.paged_attention.ops import paged_attention, paged_attention_mla


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A described-topology compile can be written to the persistent cache
    but never read back without the chip: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("m", [8, 512])
@pytest.mark.parametrize("k,n", [(2048, 8192), (8192, 2048)])
@pytest.mark.parametrize("n_bits", [2, 4])
def test_fixedpoint_matmul_compiles(one_chip, m, k, n, n_bits):
    per = values_per_byte(n_bits)
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    fn = lambda x, w, f, b: fixedpoint_matmul(x, w, f, b, n_bits=n_bits, n_out=n)  # noqa: E731
    text = _compile_text(
        fn, sd((m, k), jnp.bfloat16), sd((k, n // per), jnp.int8), sd((), jnp.int32),
        sd((n,), jnp.float32),
    )
    assert "tpu_custom_call" in text


def test_fixedpoint_matmul_head_rows_compiles(one_chip):
    """q projection of internlm2: (2048, 16, 128) packed along head_dim, so
    every 32-word packing row is shorter than a 128-word chunk."""
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    fn = lambda x, w, f: fixedpoint_matmul(  # noqa: E731
        x, w, f, n_bits=2, n_out=16 * 128, row_values=128)
    text = _compile_text(fn, sd((8, 2048), jnp.bfloat16), sd((2048, 512), jnp.int8),
                         sd((), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k,n", [(2048, 1024), (1024, 2048)])
def test_fixedpoint_matmul_experts_compiles(one_chip, k, n):
    """olmoe-1b-7b expert stack: 64 experts, gate/up 2048->1024, down back."""
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    fn = lambda x, w, f: fixedpoint_matmul_experts(x, w, f, n_bits=2, n_out=n)  # noqa: E731
    text = _compile_text(fn, sd((64, 16, k), jnp.bfloat16), sd((64, k, n // 4), jnp.int8),
                         sd((64,), jnp.int32))
    assert "tpu_custom_call" in text


# (B, T, block, physical blocks, table blocks) by case: 16-token blocks at
# decode (T=1) and a short verify/tail window (T=4); then the internlm2-chat
# cell's 32-row decode and a 2048-token tail prefill on its 64-token pool
_PAGED_SHAPES = {
    1: (4, 1, 16, 129, 32),
    4: (4, 4, 16, 129, 32),
    "chat-decode": (32, 1, 64, 1537, 48),
    "chat-prefill": (1, 2048, 64, 1537, 48),
}


@pytest.mark.parametrize("t", list(_PAGED_SHAPES))
@pytest.mark.parametrize("pool", ["bf16", "int8", "int4"])
def test_paged_attention_compiles(one_chip, pool, t):
    """internlm2-1.8b heads: K=8 KV heads of G=2 queries, head_dim 128.  The
    chat shapes compile the fold of all 8 heads into a step (decode) and the
    one-head step that 4096 query rows leave (2048-token prefill)."""
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    K, G, hd = 8, 2, 128
    B, t, block, n_phys, max_blocks = _PAGED_SHAPES[t]
    q = sd((B, t, K, G, hd), jnp.bfloat16)
    tables, pos0 = sd((B, max_blocks), jnp.int32), sd((B,), jnp.int32)
    if pool == "bf16":
        kv = sd((n_phys, K, block, hd), jnp.bfloat16)
        fn = lambda q, k, v, bt, p: paged_attention(q, k, v, bt, p, scale=0.088)  # noqa: E731
        text = _compile_text(fn, q, kv, kv, tables, pos0)
    else:
        bits = 8 if pool == "int8" else 4
        kv = sd((n_phys, K, block, hd * bits // 8), jnp.int8)
        ex = sd((n_phys, K), jnp.int32)
        fn = lambda q, k, v, ke, ve, bt, p: paged_attention(  # noqa: E731
            q, k, v, bt, p, scale=0.088, k_scale_exp=ke, v_scale_exp=ve, kv_bits=bits)
        text = _compile_text(fn, q, kv, kv, ex, ex, tables, pos0)
    assert "tpu_custom_call" in text
    name = "paged_attention" if pool == "bf16" else f"paged_attention_{pool}"
    assert f"{name}_h{heads_per_step(K, t * G)}" in text  # the fold names the call


@pytest.mark.parametrize("pool", ["bf16", "int4"])
def test_paged_attention_mla_compiles(one_chip, pool):
    """deepseek-v3 absorbed decode: 128 heads over a rank-512 c_kv pool and
    a 64-wide rope pool."""
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    B, H, r, rope, block, n_phys, max_blocks = 2, 128, 512, 64, 16, 65, 16
    qe, qr = sd((B, 1, H, r), jnp.bfloat16), sd((B, 1, H, rope), jnp.bfloat16)
    tables, pos0 = sd((B, max_blocks), jnp.int32), sd((B,), jnp.int32)
    if pool == "bf16":
        fn = lambda qe, qr, c, kr, bt, p: paged_attention_mla(  # noqa: E731
            qe, qr, c, kr, bt, p, scale=0.07)
        text = _compile_text(fn, qe, qr, sd((n_phys, block, r), jnp.bfloat16),
                             sd((n_phys, block, rope), jnp.bfloat16), tables, pos0)
    else:
        ex = sd((n_phys,), jnp.int32)
        fn = lambda qe, qr, c, kr, ce, re, bt, p: paged_attention_mla(  # noqa: E731
            qe, qr, c, kr, bt, p, scale=0.07, ckv_scale_exp=ce, kr_scale_exp=re, kv_bits=4)
        text = _compile_text(fn, qe, qr, sd((n_phys, block, r // 2), jnp.int8),
                             sd((n_phys, block, rope // 2), jnp.int8), ex, ex, tables, pos0)
    assert "tpu_custom_call" in text


def test_kernels_compile_under_a_mesh(four_chips):
    """XLA cannot partition a Mosaic kernel, not even over replicated
    operands: under a 1x4 mesh both kernels must ride a shard_map
    (kernels.dispatch.shard_kernel) — column-sharded packed words included."""
    sd = lambda shape, dt, spec=P(): jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=NamedSharding(four_chips, spec))
    mm = lambda x, w, f: fixedpoint_matmul(x, w, f, n_bits=2, n_out=8192)  # noqa: E731
    kv = sd((129, 8, 16, 128), jnp.bfloat16, P(None, "model"))
    attn = lambda q, k, v, bt, p: paged_attention(q, k, v, bt, p, scale=0.088)  # noqa: E731
    with jax.set_mesh(four_chips):
        text = _compile_text(mm, sd((8, 2048), jnp.bfloat16),
                             sd((2048, 2048), jnp.int8, P(None, "model")), sd((), jnp.int32))
        assert "tpu_custom_call" in text
        text = _compile_text(attn, sd((4, 1, 8, 2, 128), jnp.bfloat16), kv, kv,
                             sd((4, 32), jnp.int32), sd((4,), jnp.int32))
        assert "tpu_custom_call" in text
