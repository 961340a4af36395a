"""Kernel microbenchmarks.

On this CPU container the Pallas kernels run in interpret mode (Python
loop — timing them is meaningless), so we report:
  * us/call of the jitted *semantic equivalents* (fused single-expression
    vs unfused multi-pass) on CPU — the fusion structure XLA sees;
  * the DERIVED traffic model for TPU (bytes in/out per element), which is
    what the kernel actually buys on hardware (DESIGN.md §2).
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks.common import emit, write_results_json
from repro import core


def _time(fn, *args, iters=20):
    """us per call, MIN over iters: the mean is inflated 2x+ by co-tenant
    noise on shared runners, which would flake the CI regression gate; the
    minimum estimates the achievable time."""
    warm = fn(*args)
    (warm[0] if isinstance(warm, tuple) else warm).block_until_ready()
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        (out[0] if isinstance(out, tuple) else out).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


_REF_STATE = {}


def _ref_us() -> float:
    """Reference-workload time (fixed 8x1024x1024 matmul), measured NOW.

    Every timed entry records the reference time taken adjacent to its own
    measurement: shared-runner noise regimes (co-tenant bursts, frequency
    scaling) last seconds, so entry and reference land in the same regime
    and the us/ref ratio the CI gate compares stays stable while absolute
    wall time swings 2x+ (measured on the dev container)."""
    if not _REF_STATE:
        key = jax.random.PRNGKey(42)
        _REF_STATE["x"] = jax.random.normal(key, (8, 1024))
        _REF_STATE["w"] = jax.random.normal(jax.random.fold_in(key, 1), (1024, 1024))
        _REF_STATE["fn"] = jax.jit(lambda a, b: a @ b)
    return _time(_REF_STATE["fn"], _REF_STATE["x"], _REF_STATE["w"])


def run(trace_path: str = "") -> None:
    key = jax.random.PRNGKey(0)
    n = 1 << 20  # 1M params
    w = jax.random.normal(key, (n,)) * 0.3
    g = jax.random.normal(jax.random.fold_in(key, 1), (n,)) * 0.05
    v = jnp.zeros_like(w)
    delta, lam, lr, mu = 0.25, 2.0, 0.01, 0.9

    @jax.jit
    def unfused(w, g, v):
        # Alg.1 l.15-17 as separate passes (materialized intermediates)
        q = core.quantize(w, delta, 2)
        rg = (2.0 / w.size) * (w - q)
        g_tot = g + lam * rg
        v2 = mu * v + g_tot
        w2 = w - lr * (g_tot + mu * v2)
        return core.clip_to_range(w2, delta, 2), v2

    @jax.jit
    def fused(w, g, v):
        # single expression — what kernels/symog_update implements on TPU
        q = jnp.clip(jnp.round(w / delta), -1, 1) * delta
        g_tot = g + (lam * 2.0 / w.size) * (w - q)
        v2 = mu * v + g_tot
        return jnp.clip(w - lr * (g_tot + mu * v2), -delta, delta), v2

    t_unfused = _time(unfused, w, g, v)
    r_unfused = _ref_us()
    t_fused = _time(fused, w, g, v)
    r_fused = _ref_us()
    emit("symog_update_unfused_1M", t_unfused, "jnp multi-pass (CPU)", ref_us=r_unfused)
    emit(
        "symog_update_fused_1M",
        t_fused,
        f"speedup_vs_unfused={t_unfused / t_fused:.2f}x",
        ref_us=r_fused,
    )
    # TPU traffic model: unfused ~10 streams (r/w per pass) vs fused 5
    emit(
        "symog_update_traffic_model",
        0.0,
        "fused=5 streams (r:w,g,v; w:w',v') vs naive>=10 -> >=2x HBM saving",
    )

    # fixed-point matmul: bytes per weight
    K, N = 2048, 2048
    wkn = jax.random.normal(key, (K, N)) * 0.2
    x = jax.random.normal(jax.random.fold_in(key, 2), (8, K))

    @jax.jit
    def dense(x, w):
        return x @ w

    t_dense = _time(dense, x, wkn)
    emit("matmul_dense_f32_8x2048x2048", t_dense, "baseline x@W (CPU)", ref_us=_ref_us())
    emit(
        "fixedpoint_matmul_traffic_model",
        0.0,
        f"weight_bytes: f32={K * N * 4}, bf16={K * N * 2}, packed2bit={K * N // 4}"
        " -> 8x less HBM than bf16 (decode is weight-bandwidth-bound)",
    )

    # correctness cross-check vs kernel oracle (tiny, interpret mode)
    from repro.kernels import fixedpoint_matmul, pack_weight

    pw = pack_weight(wkn[:256, :256], 2, 2)
    y = fixedpoint_matmul(x[:, :256], pw, 2, n_bits=2, n_out=256, interpret=True)
    qw = core.quantize(wkn[:256, :256], core.delta_from_f(2), 2)
    err = float(jnp.max(jnp.abs(y - x[:, :256] @ qw)))
    emit("fixedpoint_matmul_exactness", 0.0, f"max_abs_err_vs_quantized_float={err:.2e}")

    # ---- packed vs dense DECODE matmul (ServeEngine hot path) -------------
    # Decode is a (batch, K) x (K, N) matvec-batch: weight-bandwidth-bound,
    # so bytes moved is the first-order model (DESIGN.md §2).  Wall time
    # here is the CPU unpack-then-dot fallback (the packed path XLA runs
    # when no TPU is present); the Pallas kernel replaces it on hardware.
    for n_bits in (2, 4):
        pk = core.pack(wkn, 2, n_bits)

        @jax.jit
        def packed_decode(x, data=pk.data):
            p = core.Packed(data=data, n_bits=n_bits, f=jnp.asarray(2))
            return x @ core.unpack(p, jnp.float32)

        t_packed = _time(packed_decode, x)
        dense_bytes = K * N * 4 + 8 * K * 4 + 8 * N * 4
        packed_bytes = K * N * n_bits // 8 + 8 * K * 4 + 8 * N * 4
        emit(
            f"decode_matmul_packed{n_bits}bit_8x{K}x{N}",
            t_packed,
            f"bytes_moved={packed_bytes} vs dense_f32={dense_bytes} "
            f"({dense_bytes / packed_bytes:.1f}x less; CPU fallback "
            f"{t_packed / t_dense:.2f}x dense wall time)",
            ref_us=_ref_us(),
        )

    run_fused_kernel_bench()
    run_serve_bench()
    run_capacity_bench()
    run_sharded_capacity_bench()
    run_kv_quant_bench()
    run_prefix_cache_bench()
    run_speculative_bench()
    run_chunked_prefill_bench()
    run_telemetry_bench(trace_path=trace_path)


def run_fused_kernel_bench() -> None:
    """Fused decode kernels (DESIGN.md §9): interpret-mode parity plus the
    bytes-moved model compare_bench gates on.

    Wall time is meaningless here (interpret mode is a Python loop; the
    fused path only exists on TPU), but both gated numbers are
    deterministic: the kernel must agree with the composed oracle it
    replaces, and the traffic model — pool reads + block-table scalars for
    the attention kernel, packed weight words for the dequant-matmul
    epilogue, never the materialized logical view / dense weights — must
    not silently lose its advantage to an accounting or layout change."""
    from benchmarks.roofline import fixedpoint_matmul_bytes, paged_attention_bytes
    from repro.kernels import paged_attention
    from repro.kernels.paged_attention.ref import paged_attention_ref

    B, T, K, G, hd, block, max_blocks = 4, 1, 4, 2, 64, 16, 8
    n_blocks = B * max_blocks + 1
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    q = jax.random.normal(ks[0], (B, T, K, G, hd))
    k_pool = jax.random.normal(ks[1], (n_blocks, K, block, hd))
    v_pool = jax.random.normal(ks[2], (n_blocks, K, block, hd))
    perm = jax.random.permutation(ks[3], jnp.arange(1, n_blocks))[: B * max_blocks]
    bt = perm.reshape(B, max_blocks).astype(jnp.int32)
    pos0 = jax.random.randint(ks[4], (B,), 0, max_blocks * block).astype(jnp.int32)
    kw = dict(scale=hd**-0.5, window=48)
    y = paged_attention(q, k_pool, v_pool, bt, pos0, interpret=True, **kw)
    y_ref = paged_attention_ref(q, k_pool, v_pool, bt, pos0, **kw)
    err = float(jnp.max(jnp.abs(y - y_ref)))
    assert err < 1e-4, f"paged_attention interpret parity broke: {err}"
    pa = paged_attention_bytes(B=B, T=T, K=K, G=G, hd=hd, max_blocks=max_blocks, block=block)
    emit(
        "paged_attention_fused_decode",
        0.0,
        f"B{B} {K}kvx{G} hd{hd} pool {max_blocks}x{block} windowed: interpret "
        f"parity max_abs_err={err:.1e}; bytes/call fused={pa['fused']} vs "
        f"composed={pa['composed']} ({pa['ratio']:.1f}x less HBM — the "
        "(B,S,K,hd) logical view is never materialized)",
        composed_over_fused_bytes=round(pa["ratio"], 2),
    )
    # per-block SYMOG pools (DESIGN.md §11): quantize the SAME float pools
    # with first-position block calibration, then check the fused kernel
    # against the quantized ref oracle (must be exact to kernel tolerance)
    # and report the drift vs the bf16-pool answer (accuracy cost of the
    # bits, gated at serve level by run_kv_quant_bench)
    from repro.core.packing import pack_int
    from repro.models.attention import KV_QMAX, block_scale_exp, quantize_fixed

    def _quant_pool(pool, bits):
        qmax = KV_QMAX[bits]
        e = block_scale_exp(pool[:, :, 0], qmax)  # (n_blocks, K), head-major pool
        q = quantize_fixed(pool, e[..., None], qmax)
        return (pack_int(q, 4) if bits == 4 else q), e

    drifts = {}
    for bits in (8, 4):
        k_q, ke = _quant_pool(k_pool, bits)
        v_q, ve = _quant_pool(v_pool, bits)
        qkw = dict(k_scale_exp=ke, v_scale_exp=ve, kv_bits=bits, **kw)
        y_q = paged_attention(q, k_q, v_q, bt, pos0, interpret=True, **qkw)
        y_q_ref = paged_attention_ref(q, k_q, v_q, bt, pos0, **qkw)
        err_q = float(jnp.max(jnp.abs(y_q - y_q_ref)))
        assert err_q < 1e-4, f"int{bits} quantized-pool kernel parity broke: {err_q}"
        drifts[bits] = float(jnp.max(jnp.abs(y_q - y_ref)))
        pa_q = paged_attention_bytes(
            B=B, T=T, K=K, G=G, hd=hd, max_blocks=max_blocks, block=block, kv_bits=bits
        )
        emit(
            f"paged_attention_quantized_int{bits}",
            0.0,
            f"per-block int{bits} pool (first-token calibrated scales): "
            f"fused-vs-ref parity max_abs_err={err_q:.1e}; attention-out "
            f"drift vs bf16 pool {drifts[bits]:.2e} (report-only); bytes/call "
            f"fused={pa_q['fused']} vs composed={pa_q['composed']} "
            f"({pa_q['ratio']:.1f}x less HBM incl. the int32 scale stream)",
            composed_over_fused_bytes=round(pa_q["ratio"], 2),
        )

    fp = fixedpoint_matmul_bytes(M=8, K=2048, N=2048, n_bits=2)
    emit(
        "fixedpoint_matmul_fused_epilogue",
        0.0,
        f"8x2048x2048 2-bit: weight+activation bytes packed={fp['packed']} "
        f"vs bf16={fp['bf16']} f32={fp['f32']} "
        f"({fp['bf16_over_packed']:.1f}x less than bf16; in-kernel unpack, "
        "per-tile 2^-f epilogue)",
        bf16_over_packed_bytes=round(fp["bf16_over_packed"], 2),
    )


def run_serve_bench() -> None:
    """Ragged-decode throughput: continuous batching vs the static loop.

    Workload: requests with uniform prompts but heavy-tailed generation
    budgets — the shape where static batching burns the most bandwidth
    (every batch decodes to its slowest member while finished rows ride
    along).  The continuous scheduler evicts at each budget and refills the
    slot, so useful-token throughput is the honest comparison: both sides
    pay their prefills and produce exactly the same `useful` tokens.
    Measured for the float tree and the 2-bit pack_tree artifact.

    Runs a widened reduced config (d_model 256): at test scale (d_model 32)
    a decode step is dispatch-overhead-bound on CPU, and the scheduler's
    step-count advantage disappears into timer noise.
    """
    import dataclasses as _dc

    from repro import configs
    from repro.models.lm import init_lm
    from repro.serve import Request, ServeConfig, ServeEngine

    cfg = _dc.replace(
        configs.get_reduced("internlm2-1.8b"),
        d_model=256,
        n_heads=8,
        n_kv_heads=4,
        head_dim=32,
        d_ff=1024,
        vocab_size=2048,
    )
    params = init_lm(jax.random.PRNGKey(0), cfg)
    scfg = core.SymogConfig(n_bits=2, total_steps=1)
    sst = core.symog_init(params, scfg)
    packed = core.pack_tree(params, sst, scfg)

    slots, prompt_len, steps_max = 4, 8, 48
    budgets = [steps_max, 4, 6, 4] * 5  # heavy-tailed: one straggler per wave
    key = jax.random.PRNGKey(7)
    prompts = [
        np.asarray(jax.random.randint(jax.random.fold_in(key, i), (prompt_len,), 0, cfg.vocab_size))
        for i in range(len(budgets))
    ]
    reqs = [Request(tokens=p, max_new_tokens=b) for p, b in zip(prompts, budgets)]
    useful = sum(budgets)

    # committed floors (BENCH_serve.baseline.json): the float floor absorbs
    # the paged gather/dispatch overhead on CPU plus shared-runner noise;
    # packed (the serving artifact, bigger matmuls per step) keeps 1.5x
    floors = {"float": 1.2, "packed2bit": 1.3}
    cont_wall = {}
    for label, tree in (("float", params), ("packed2bit", packed)):
        eng = ServeEngine(cfg, tree, max_len=prompt_len + steps_max, compute_dtype=jnp.float32)

        def run_static():
            for lo in range(0, len(reqs), slots):
                chunk = reqs[lo : lo + slots]
                batch = {"tokens": jnp.asarray(np.stack([np.asarray(r.tokens) for r in chunk]))}
                out = eng.generate_static(batch, max(r.max_new_tokens for r in chunk))
                # sync before the timer stops: the continuous arm pays a
                # per-step host sync by construction, so the static arm must
                # not get away with measuring dispatch only
                jax.block_until_ready(out)

        def run_continuous():
            eng.serve(reqs, ServeConfig(n_slots=slots))

        def timed(fn):
            t0 = time.perf_counter()
            fn()
            return time.perf_counter() - t0

        run_static()  # warm both trace sets
        run_continuous()
        # INTERLEAVED median-of-5: a co-tenant burst spanning one arm's runs
        # would skew the gated speedup ratio; alternating S,C,S,C,... puts
        # both arms in the same noise regime, per-round PAIRED ratios keep
        # them there, and the median drops the burst rounds entirely (the
        # min-of-3 this replaces still let one lucky/unlucky pairing set
        # the gated number — the repeated floor re-commits of PR 3-4)
        n_rep = 5
        ts, tc = [], []
        for _ in range(n_rep):
            ts.append(timed(run_static))
            tc.append(timed(run_continuous))
        ratios = sorted(s / c for s, c in zip(ts, tc))
        speedup = ratios[n_rep // 2]
        t_static, t_cont = float(np.median(ts)), float(np.median(tc))
        r_static = r_cont = _ref_us()
        emit(
            f"serve_static_ragged_{label}",
            t_static * 1e6,
            f"{useful / t_static:.1f} useful tok/s "
            f"({len(reqs)} reqs x batches-of-{slots} to slowest member)",
            ref_us=r_static,
            repeats=n_rep,
            spread={"us_min": round(min(ts) * 1e6, 1), "us_max": round(max(ts) * 1e6, 1)},
        )
        emit(
            f"serve_continuous_ragged_{label}",
            t_cont * 1e6,
            f"{useful / t_cont:.1f} useful tok/s; median {speedup:.2f}x static "
            f"over {n_rep} paired rounds (target >= {floors[label]}x)",
            ref_us=r_cont,
            repeats=n_rep,
            spread={"speedup_min": round(ratios[0], 3), "speedup_max": round(ratios[-1], 3)},
            speedup_vs_static=round(speedup, 3),
        )
        cont_wall[label] = t_cont

    # off-TPU the packed artifact must not serve slower than the float tree:
    # the engine densifies it ONCE at construction ('dense' auto-backend)
    # instead of re-paying unpack-then-dot every matmul.  Floor 0.7 absorbs
    # runner noise; the pre-densify fallback sat near 0.5.
    pf = cont_wall["float"] / cont_wall["packed2bit"]
    emit(
        "serve_packed_over_float",
        0.0,
        f"continuous ragged wall: packed2bit {cont_wall['packed2bit']:.2f}s vs "
        f"float {cont_wall['float']:.2f}s -> float/packed {pf:.2f}x "
        "(floor 0.7; densify-once keeps the packed artifact at float speed "
        "where no fused dequant kernel exists)",
        packed_over_float=round(pf, 3),
    )


def run_capacity_bench() -> None:
    """Paged-pool capacity at an equal cache-HBM budget (DESIGN.md §6).

    The dense layout gives every slot a full max_len cache row, so a pool
    holding S_dense rows serves at most S_dense concurrent requests no
    matter how short they are.  The paged pool gets the SAME token budget
    (S_dense x ceil(max_len/block) blocks) but allocates per-block on
    demand, so a heavy-tailed workload (mostly short requests, a few
    stragglers) packs several requests into one dense row's worth of
    blocks.  Gated metric: peak concurrent live slots / S_dense >= 2x.
    """
    import dataclasses as _dc

    from repro import configs
    from repro.models.lm import init_lm
    from repro.serve import Request, ServeConfig, ServeEngine

    cfg = _dc.replace(
        configs.get_reduced("internlm2-1.8b"),
        d_model=256,
        n_heads=8,
        n_kv_heads=4,
        head_dim=32,
        d_ff=1024,
        vocab_size=2048,
    )
    params = init_lm(jax.random.PRNGKey(0), cfg)

    S_dense, block, prompt_len, steps_max = 4, 16, 8, 48
    max_len = prompt_len + steps_max
    max_blocks = -(-max_len // block)
    n_blocks = S_dense * max_blocks  # == the dense pool's HBM in tokens
    n_slots = 4 * S_dense  # paged: slots are cheap, blocks are the budget

    # heavy-tailed: mostly short requests (one block each), a straggler per
    # 8 that grows across block boundaries mid-decode
    key = jax.random.PRNGKey(7)
    budgets = ([4] * 7 + [40]) * 4
    reqs = [
        Request(
            tokens=np.asarray(
                jax.random.randint(jax.random.fold_in(key, i), (prompt_len,), 0, cfg.vocab_size)
            ),
            max_new_tokens=b,
        )
        for i, b in enumerate(budgets)
    ]

    eng = ServeEngine(cfg, params, max_len=max_len, compute_dtype=jnp.float32)
    serve_cfg = ServeConfig(n_slots=n_slots, block_size=block, n_blocks=n_blocks)
    eng.serve(reqs[:1], serve_cfg)  # warm the traces
    t0 = time.perf_counter()
    _, sched = eng.serve(reqs, serve_cfg, return_scheduler=True)
    dt = time.perf_counter() - t0
    peak = sched.stats["peak_live_slots"]
    ratio = peak / S_dense
    emit(
        "serve_paged_capacity",
        dt * 1e6,
        f"peak {peak} live slots on a {S_dense}-dense-slot HBM budget "
        f"({n_blocks} blocks of {block}; {sched.stats['preemptions']} "
        f"preemptions, {sched.stats['admission_traces']} admit traces) "
        f"-> {ratio:.1f}x dense capacity (target >= 2x)",
        ref_us=_ref_us(),
        capacity_ratio=round(ratio, 3),
    )

    # int4 arm (DESIGN.md §11): SAME byte budget — the bf16 pool's bytes for
    # S_dense dense rows — converted to packed-int4 blocks (0.5 B/element
    # plus one int32 exponent per (block, kv head, stream)), so the ratio
    # compounds paging on-demand with the 4-bit wordlength
    K, hd, L = cfg.n_kv_heads, cfg.head_dim, cfg.n_layers
    dense_bytes = S_dense * max_blocks * block * L * 2 * K * hd * 2  # bf16
    blk_bytes = L * (2 * K * hd * block // 2 + 2 * K * 4)  # int4 + scales
    n_blocks_q = dense_bytes // blk_bytes
    n_slots_q = min(52, n_blocks_q)
    cfg_q = _dc.replace(cfg, kv_cache_dtype="int4_fp")
    budgets_q = ([4] * 7 + [40]) * 8
    reqs_q = [
        Request(
            tokens=np.asarray(
                jax.random.randint(jax.random.fold_in(key, i), (prompt_len,), 0, cfg.vocab_size)
            ),
            max_new_tokens=b,
        )
        for i, b in enumerate(budgets_q)
    ]
    eng_q = ServeEngine(cfg_q, params, max_len=max_len, compute_dtype=jnp.float32)
    serve_cfg_q = ServeConfig(n_slots=n_slots_q, block_size=block, n_blocks=n_blocks_q)
    eng_q.serve(reqs_q[:1], serve_cfg_q)  # warm the traces
    t0 = time.perf_counter()
    _, sq = eng_q.serve(reqs_q, serve_cfg_q, return_scheduler=True)
    dt = time.perf_counter() - t0
    peak_q = sq.stats["peak_live_slots"]
    ratio_q = peak_q / S_dense
    emit(
        "serve_paged_capacity_int4",
        dt * 1e6,
        f"int4 pool: peak {peak_q} live slots on the SAME {S_dense}-dense-"
        f"slot bf16 byte budget ({n_blocks_q} packed blocks of {block} = "
        f"{n_blocks_q * blk_bytes} B vs {dense_bytes} B dense; "
        f"{sq.stats['preemptions']} preemptions) -> {ratio_q:.1f}x dense "
        "capacity (target >= 12x: ~4x bytes/token x on-demand paging)",
        ref_us=_ref_us(),
        capacity_ratio=round(ratio_q, 3),
    )


def run_sharded_capacity_bench() -> None:
    """Per-device resident pool bytes under the §12 mesh placement.

    Deterministic byte model, not a timing: ``pool_bytes_per_device``
    (the same accounting ``serve/sharding.py`` uses to place the pool)
    prices the int4 paged pool on a hypothetical 8-way model mesh vs
    single-device — data leaves shard their KV-head axis 8 ways, scale
    exponents stay replicated.  Gated metric ``pool_shard_ratio`` =
    single-device resident bytes / per-device resident bytes at 8 shards
    (floor 6.0: below 8 because the replicated scales don't shrink; a
    placement bug that silently replicates the pool would read 1.0).
    """
    import dataclasses as _dc

    from repro import configs
    from repro.models.lm import init_lm
    from repro.serve import ServeEngine
    from repro.serve.sharding import pool_bytes_per_device

    # 8 KV heads so the head axis divides an 8-way model mesh exactly
    cfg = _dc.replace(
        configs.get_reduced("internlm2-1.8b"),
        n_heads=8,
        n_kv_heads=8,
        head_dim=32,
        d_model=256,
        kv_cache_dtype="int4_fp",
    )
    eng = ServeEngine(cfg, init_lm(jax.random.PRNGKey(0), cfg), max_len=64)
    block, n_blocks = 16, 64
    total, single = pool_bytes_per_device(eng, block, n_blocks)
    _, per_dev = pool_bytes_per_device(eng, block, n_blocks, model_shards=8)
    ratio = single / per_dev
    emit(
        "serve_sharded_capacity",
        0.0,
        f"int4 pool {total} B total: {single} B/device unsharded vs "
        f"{per_dev} B/device on an 8-way model mesh -> {ratio:.2f}x "
        "headroom per device (floor 6.0; scales replicate, data shards)",
        pool_shard_ratio=round(ratio, 3),
    )


def run_kv_quant_bench() -> None:
    """Accuracy cost of the quantized paged KV pools (DESIGN.md §11).

    The model is first TRAINED (40 scan-compiled steps on a mod-V counting
    task, ~4s on the dev container): untrained random weights produce
    near-tie logits where ANY cache perturbation flips the greedy argmax
    and free-running streams diverge by compounding — that measures the
    workload's chaos, not the pool's fidelity.  A trained model has the
    confident logit gaps of every deployment target, which is the regime
    the near-lossless claim is about.

    The trained weights then serve the SAME greedy workload on a float, an
    int8 and an int4 block pool; the gated metric is per-token agreement
    of the int8 streams with the float-pool streams (committed floor 0.99
    — the serving half of the paper's fixed-point claim applied to the KV
    bytes).  int4 agreement rides along metrics-only (floor 0.0): 7
    quantization levels per block scale are below the paper's studied
    range and the capacity bench owns int4's value story.  Per-position
    logit drift is not observable through serve(), so the kernel-level
    attention-out drift entries (run_fused_kernel_bench) carry the
    report-only drift numbers."""
    import dataclasses as _dc

    from repro import configs
    from repro.models.lm import init_lm
    from repro.optim import adamw
    from repro.serve import Request, ServeConfig, ServeEngine
    from repro.train.trainer import init_train_state, make_train_step

    cfg = _dc.replace(
        configs.get_reduced("internlm2-1.8b"),
        d_model=128,
        n_heads=4,
        n_kv_heads=2,
        head_dim=32,
        d_ff=512,
        vocab_size=256,
    )
    params = init_lm(jax.random.PRNGKey(0), cfg)
    tx = adamw(weight_decay=0.0)
    step = make_train_step(cfg, tx, lambda s: 3e-3, compute_dtype=jnp.float32)
    state = init_train_state(params, tx)
    rng = np.random.default_rng(0)
    starts = rng.integers(0, cfg.vocab_size, size=(40, 8, 1))
    batches = (starts + np.arange(24)) % cfg.vocab_size

    @jax.jit
    def train_all(state, batches):
        def body(st, toks):
            st, m = step(st, {"tokens": toks})
            return st, m["ce"]

        return jax.lax.scan(body, state, batches)

    t0 = time.perf_counter()
    state, ces = train_all(state, jnp.asarray(batches, jnp.int32))
    jax.block_until_ready(state.params)
    t_train = time.perf_counter() - t0
    tparams = state.params

    slots, prompt_len, budget, n_req, block = 4, 8, 24, 12, 16
    prompts = [
        np.asarray((int(k) + np.arange(prompt_len)) % cfg.vocab_size)
        for k in rng.integers(0, cfg.vocab_size, n_req)
    ]
    reqs = [Request(tokens=p, max_new_tokens=budget) for p in prompts]
    serve_cfg = ServeConfig(n_slots=slots, block_size=block)
    streams = {}
    for kv in ("bf16", "int8_fp", "int4_fp"):
        eng = ServeEngine(
            _dc.replace(cfg, kv_cache_dtype=kv),
            tparams,
            max_len=prompt_len + budget,
            compute_dtype=jnp.float32,
        )
        comps = eng.serve(reqs, serve_cfg)
        streams[kv] = np.concatenate([np.asarray(c.tokens) for c in comps])

    def agree(kv):
        return float(np.mean(streams[kv] == streams["bf16"]))

    a8, a4 = agree("int8_fp"), agree("int4_fp")
    emit(
        "serve_kv_quant_agreement",
        0.0,
        f"greedy serve, {n_req} reqs x {budget} tokens, weights trained to "
        f"ce={float(ces[-1]):.2f} in {t_train:.1f}s: int8 pool agrees with "
        f"the float pool on {a8:.1%} of tokens (floor 0.99); int4 {a4:.1%} "
        "(metrics-only)",
        token_agreement_int8=round(a8, 4),
        token_agreement_int4=round(a4, 4),
    )


def _admit_seconds(sched, hits: bool = False) -> list:
    """Durations of a traced scheduler's ``admit`` spans: every one, or
    with ``hits`` only prefix hits (``start`` > 0)."""
    return [
        dur
        for kind, _, dur, args in sched.tracer.spans
        if kind == "admit" and (args["start"] > 0 or not hits)
    ]


def run_prefix_cache_bench() -> None:
    """Automatic prefix cache on a shared-system-prompt workload (§7).

    Every request repeats one 48-token system prompt (3 full blocks of 16)
    and appends a unique 8-token user tail — the canonical deployment shape
    (system prompts / few-shot headers amortized across traffic).  With the
    cache ON, request 1 prefills the whole 64-bucket prompt and every later
    request pins the 3 cached blocks and prefills only its 8-bucket tail.
    Gated metrics (floors in BENCH_serve.baseline.json):

      blocks_saved_frac      — fresh pool allocations saved vs the cache-off
                               run (committed floor 0.30; measured ~0.5);
      ttft_miss_over_hit_p50 — p50 admission wall time of cache-off (miss)
                               prefills over p50 of prefix-HIT admissions:
                               > 1.0 means hits reach their first token
                               faster than misses (the latency half of the
                               §7 claim; the 64-vs-8 bucket gap dominates).

    Admission wall times are the traced ``admit`` spans (dispatch until the
    first token is on the host); a span's ``start`` > 0 marks a hit.  Both
    arms trace, so the ratio carries no tracing skew.
    """
    import dataclasses as _dc

    from repro import configs
    from repro.models.lm import init_lm
    from repro.serve import Request, ServeConfig, ServeEngine, TelemetryConfig

    cfg = _dc.replace(
        configs.get_reduced("internlm2-1.8b"),
        d_model=256,
        n_heads=8,
        n_kv_heads=4,
        head_dim=32,
        d_ff=1024,
        vocab_size=2048,
    )
    params = init_lm(jax.random.PRNGKey(0), cfg)
    sys_len, tail_len, budget, n_req, block = 48, 8, 4, 16, 16
    max_len = sys_len + tail_len + budget + block  # headroom: no growth churn
    key = jax.random.PRNGKey(11)
    system = np.asarray(jax.random.randint(key, (sys_len,), 0, cfg.vocab_size))
    reqs = [
        Request(
            tokens=np.concatenate(
                [
                    system,
                    np.asarray(
                        jax.random.randint(
                            jax.random.fold_in(key, i), (tail_len,), 0, cfg.vocab_size
                        )
                    ),
                ]
            ),
            max_new_tokens=budget,
        )
        for i in range(n_req)
    ]

    eng = ServeEngine(cfg, params, max_len=max_len, compute_dtype=jnp.float32)
    cfg_off = ServeConfig(
        n_slots=n_req, block_size=block, telemetry=TelemetryConfig(trace=True)
    )
    cfg_on = _dc.replace(cfg_off, prefix_cache=True)
    eng.serve(reqs, cfg_off)  # warm miss traces
    eng.serve(reqs, cfg_on)  # warm prefix-hit traces
    # median-of-3 paired repeats: the ttft ratio mixes two runs' admission
    # timings, the noisiest gated number in this file (each serve() builds
    # a fresh scheduler+cache, so repeats are independent)
    n_rep, ratios, dts = 3, [], []
    saved = 0.0
    hits = alloc_on = alloc_off = 0
    for _ in range(n_rep):
        _, off = eng.serve(reqs, cfg_off, return_scheduler=True)
        t0 = time.perf_counter()
        _, on = eng.serve(reqs, cfg_on, return_scheduler=True)
        dts.append(time.perf_counter() - t0)
        # a silent eligibility/matching regression would crash the
        # percentile below with an opaque numpy error — fail with the story
        assert on.stats["prefix_hits"] > 0, "prefix-cache bench produced zero hits"
        saved = 1.0 - on.pool.total_allocs / off.pool.total_allocs  # deterministic
        hits = on.stats["prefix_hits"]
        alloc_on, alloc_off = on.pool.total_allocs, off.pool.total_allocs
        miss_p50 = float(np.percentile(_admit_seconds(off), 50))
        hit_p50 = float(np.percentile(_admit_seconds(on, hits=True), 50))
        ratios.append(miss_p50 / hit_p50)
    r_us = _ref_us()
    ratios.sort()
    ratio = ratios[n_rep // 2]
    emit(
        "serve_prefix_cache",
        float(np.median(dts)) * 1e6,
        f"{hits}/{n_req} hits on a shared {sys_len}-token "
        f"system prompt: {alloc_on} vs {alloc_off} "
        f"blocks allocated ({saved:.0%} saved, floor 30%); median ttft p50 "
        f"miss/hit {ratio:.2f}x over {n_rep} repeats (floor > 1x)",
        ref_us=r_us,
        repeats=n_rep,
        spread={"ratio_min": round(ratios[0], 3), "ratio_max": round(ratios[-1], 3)},
        blocks_saved_frac=round(saved, 3),
        ttft_miss_over_hit_p50=round(ratio, 3),
    )


def run_speculative_bench() -> None:
    """Self-speculative decoding on the paged scheduler (DESIGN.md §8).

    Target: the 2-bit ``quantize_tree`` params; draft: the ``pack_tree``
    of the SAME SYMOG state — the deployment pairing the paper motivates
    (one training run, one weight set, two artifacts).  On the unpack
    backend the packed artifact's logits are bit-equal to its
    quantize_tree twin, so every draft is accepted and the gated metric
    isolates the CONTROLLER: tokens committed per (row, verify round) —
    window bookkeeping, budget truncation, adaptive depth — where vanilla
    decode is pinned at 1.0 and a clean k=3 round commits 4.  Greedy on a
    fixed workload, so the number is deterministic (repeats recorded to
    prove it; the floor is regression protection against the controller
    silently degenerating to one token per round, not against noise).

    The float-target pairing (the artifacts genuinely disagree at random
    init; SYMOG training drives agreement toward the twin case) rides
    along UNGATED — its acceptance is a property of untrained weights,
    not of the serving stack.
    """
    import dataclasses as _dc

    from repro import configs
    from repro.models.lm import init_lm
    from repro.serve import Request, ServeConfig, ServeEngine, SpeculativeConfig

    cfg = _dc.replace(
        configs.get_reduced("internlm2-1.8b"),
        d_model=256,
        n_heads=8,
        n_kv_heads=4,
        head_dim=32,
        d_ff=1024,
        vocab_size=2048,
    )
    params = init_lm(jax.random.PRNGKey(0), cfg)
    scfg = core.SymogConfig(n_bits=2, total_steps=1)
    sst = core.symog_init(params, scfg)
    qt = core.quantize_tree(params, sst, scfg)
    packed = core.pack_tree(params, sst, scfg)

    slots, prompt_len, budget, n_req, k = 4, 8, 16, 8, 3
    key = jax.random.PRNGKey(9)
    reqs = [
        Request(
            tokens=np.asarray(
                jax.random.randint(jax.random.fold_in(key, i), (prompt_len,), 0, cfg.vocab_size)
            ),
            max_new_tokens=budget,
        )
        for i in range(n_req)
    ]
    eng = ServeEngine(cfg, qt, max_len=prompt_len + budget, compute_dtype=jnp.float32)
    spec = SpeculativeConfig(draft=packed, k=k)
    cfg_van = ServeConfig(n_slots=slots)
    cfg_spec = ServeConfig(n_slots=slots, speculative=spec)
    eng.serve(reqs, cfg_van)  # warm vanilla traces
    eng.serve(reqs, cfg_spec)  # warm draft/verify traces

    n_rep, accepted, dts, dts_vanilla = 3, [], [], []
    sched = None
    for _ in range(n_rep):
        t0 = time.perf_counter()
        _, van = eng.serve(reqs, cfg_van, return_scheduler=True)
        dts_vanilla.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        _, sched = eng.serve(reqs, cfg_spec, return_scheduler=True)
        dts.append(time.perf_counter() - t0)
        # a silent eligibility regression would bypass to vanilla decode and
        # divide by zero below — fail with the story instead
        assert sched.stats["spec_row_rounds"] > 0, "speculative bench ran zero verify rounds"
        accepted.append(sched.stats["spec_emitted"] / sched.stats["spec_row_rounds"])
    accepted.sort()
    apr = accepted[n_rep // 2]
    dt, dt_v = float(np.median(dts)), float(np.median(dts_vanilla))

    # ungated companion: the same controller against the FLOAT target,
    # where the 2-bit draft genuinely disagrees (untrained weights)
    eng_f = ServeEngine(cfg, params, max_len=prompt_len + budget, compute_dtype=jnp.float32)
    eng_f.serve(reqs[:1], cfg_spec)
    _, sf = eng_f.serve(reqs, cfg_spec, return_scheduler=True)
    assert sf.stats["spec_row_rounds"] > 0, "speculative bench ran zero verify rounds"
    apr_float = sf.stats["spec_emitted"] / sf.stats["spec_row_rounds"]

    emit(
        "serve_speculative",
        dt * 1e6,
        f"2-bit pack_tree draft vs its quantize_tree twin, k={k}: "
        f"{apr:.2f} tokens committed per row-round (floor 1.5; vanilla "
        f"decode = 1.0), {sched.stats['decode_steps']} rounds vs "
        f"{van.stats['decode_steps']} vanilla steps, wall {dt_v / dt:.2f}x "
        "vanilla on CPU (draft costs full compute here; on TPU it streams "
        f"2/16 of the target's weight bytes); float-target acceptance "
        f"{apr_float:.2f} ungated (untrained weights)",
        ref_us=_ref_us(),
        repeats=n_rep,
        spread={"apr_min": round(accepted[0], 3), "apr_max": round(accepted[-1], 3)},
        accepted_per_step=round(apr, 3),
    )


def run_chunked_prefill_bench() -> None:
    """Latency under load: p99 inter-token latency with a long-prompt
    adversary, one-shot admission vs chunked prefill (DESIGN.md §10).

    Workload: three short-prompt requests decoding steadily while one
    256-token adversary prompt arrives mid-stream.  One-shot admission runs
    the whole 256-bucket prefill inside a single scheduler step — every
    neighbor's next token waits behind it, which is exactly one giant ITL
    outlier (the p99).  Chunked admission (32-token chunks) spreads the
    same prefill FLOPs over 8 mixed prefill+decode steps, so no single step
    carries the whole prompt.  Total work is unchanged (bit-identical pool
    KV), so mean ITL barely moves — the tail is the whole story, hence the
    gated metric:

      itl_p99_ratio — p99(one-shot step wall) / p99(chunked step wall)
                      over the steps where at least one already-live slot
                      was decoding (committed floor 1.25 in
                      BENCH_serve.baseline.json; measured 1.6-2.1x on the
                      dev container).

    Median-of-3 paired ratios, same discipline as the other serve gates.
    """
    import dataclasses as _dc

    from repro import configs
    from repro.models.lm import init_lm
    from repro.serve import Request, Scheduler, ServeConfig, ServeEngine

    cfg = _dc.replace(
        configs.get_reduced("internlm2-1.8b"),
        d_model=256,
        n_heads=8,
        n_kv_heads=4,
        head_dim=32,
        d_ff=1024,
        vocab_size=2048,
    )
    params = init_lm(jax.random.PRNGKey(0), cfg)

    long_len, short_len, budget, chunk, block = 256, 8, 48, 32, 16
    max_len = long_len + block  # adversary decodes a few tokens, no growth churn
    key = jax.random.PRNGKey(5)
    shorts = [
        Request(
            tokens=np.asarray(
                jax.random.randint(jax.random.fold_in(key, i), (short_len,), 0, cfg.vocab_size)
            ),
            max_new_tokens=budget,
        )
        for i in range(3)
    ]
    adversary = Request(
        tokens=np.asarray(jax.random.randint(key, (long_len,), 0, cfg.vocab_size)),
        max_new_tokens=4,
        arrival=8,  # lands while the shorts are mid-decode
    )
    reqs = shorts + [adversary]
    eng = ServeEngine(cfg, params, max_len=max_len, compute_dtype=jnp.float32)

    def itl_samples(prefill_chunk):
        """Per-step wall times over the steps a live slot was decoding —
        each is one inter-token latency every live stream paid."""
        sched = Scheduler(
            eng, ServeConfig(n_slots=4, block_size=block, prefill_chunk=prefill_chunk)
        )
        for r in reqs:
            sched.submit(r)
        samples = []
        while True:
            decoding = sched._n_decoding() > 0
            t0 = time.perf_counter()
            more = sched.step()
            jax.block_until_ready(sched._tokens)
            if decoding:
                samples.append(time.perf_counter() - t0)
            if not more:
                break
        return np.asarray(samples)

    itl_samples(0)  # warm one-shot traces (incl. the 256-bucket prefill)
    itl_samples(chunk)  # warm the chunk-bucket prefix traces
    n_rep, ratios = 3, []
    one = chk = None
    for _ in range(n_rep):
        one, chk = itl_samples(0), itl_samples(chunk)
        ratios.append(float(np.percentile(one, 99)) / float(np.percentile(chk, 99)))
    ratios.sort()
    ratio = ratios[n_rep // 2]
    p99_one, p99_chk = float(np.percentile(one, 99)), float(np.percentile(chk, 99))
    emit(
        "serve_chunked_prefill_itl",
        p99_chk * 1e6,
        f"{long_len}-token adversary over {len(shorts)} decoding streams: "
        f"p99 ITL {p99_one * 1e3:.1f}ms one-shot vs {p99_chk * 1e3:.1f}ms "
        f"chunked ({chunk}/step) -> median {ratio:.2f}x tail cut over "
        f"{n_rep} paired rounds (mean moves "
        f"{float(np.mean(one)) / float(np.mean(chk)):.2f}x — same total work, "
        "different shape)",
        ref_us=_ref_us(),
        repeats=n_rep,
        spread={"ratio_min": round(ratios[0], 3), "ratio_max": round(ratios[-1], 3)},
        itl_p99_ratio=round(ratio, 3),
    )


def run_telemetry_bench(trace_path: str = "") -> None:
    """Telemetry overhead gate (DESIGN.md §13): the fully-instrumented
    serve path (metrics registry + step-span tracing ON) vs telemetry-off
    on the same ragged workload.

    The registry is always on (host-side integer adds inside a loop that
    already pays a device sync per step), and tracing adds one ring append
    per phase — the design budget is <= 5 % wall-time overhead, committed
    as the ``off_over_instrumented`` floor 0.95 in
    BENCH_serve.baseline.json (ratio = off wall / instrumented wall; 1.0
    means free, 0.95 means instrumented is at most ~5 % slower).
    Interleaved median-of-5 paired rounds, same discipline as the other
    serve gates.  The LAST instrumented round's span ring is exported as a
    Chrome trace_event JSON when ``trace_path`` is set — CI uploads it so
    every run leaves an openable Perfetto artifact.
    """
    import dataclasses as _dc

    from repro import configs
    from repro.models.lm import init_lm
    from repro.serve import Request, ServeConfig, ServeEngine, TelemetryConfig

    cfg = _dc.replace(
        configs.get_reduced("internlm2-1.8b"),
        d_model=256,
        n_heads=8,
        n_kv_heads=4,
        head_dim=32,
        d_ff=1024,
        vocab_size=2048,
    )
    params = init_lm(jax.random.PRNGKey(0), cfg)

    slots, prompt_len, steps_max = 4, 8, 48
    budgets = [steps_max, 4, 6, 4] * 3
    key = jax.random.PRNGKey(11)
    reqs = [
        Request(
            tokens=np.asarray(
                jax.random.randint(jax.random.fold_in(key, i), (prompt_len,), 0, cfg.vocab_size)
            ),
            max_new_tokens=b,
        )
        for i, b in enumerate(budgets)
    ]
    eng = ServeEngine(cfg, params, max_len=prompt_len + steps_max, compute_dtype=jnp.float32)
    cfg_off = ServeConfig(n_slots=slots)
    cfg_on = ServeConfig(n_slots=slots, telemetry=TelemetryConfig(trace=True))

    def serve(c):
        return eng.serve(reqs, c, return_scheduler=True)

    serve(cfg_off)  # telemetry never changes traces: one warmup covers both arms
    n_rep, t_off, t_on = 5, [], []
    sched = None
    for _ in range(n_rep):
        t0 = time.perf_counter()
        serve(cfg_off)
        t_off.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        _, sched = serve(cfg_on)
        t_on.append(time.perf_counter() - t0)
    ratios = sorted(o / i for o, i in zip(t_off, t_on))
    ratio = ratios[n_rep // 2]
    if trace_path:
        sched.tracer.export_chrome(trace_path)
    n_events = len(sched.tracer)
    emit(
        "serve_telemetry_overhead",
        float(np.median(t_on)) * 1e6,
        f"instrumented (registry + {n_events}-event span trace) "
        f"{float(np.median(t_on)):.2f}s vs off {float(np.median(t_off)):.2f}s -> "
        f"median off/instrumented {ratio:.2f}x over {n_rep} paired rounds "
        "(floor 0.95: the whole telemetry layer costs <= ~5% wall)",
        ref_us=_ref_us(),
        repeats=n_rep,
        spread={"ratio_min": round(ratios[0], 3), "ratio_max": round(ratios[-1], 3)},
        off_over_instrumented=round(ratio, 3),
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--json",
        default="",
        help="also write the emitted entries to this JSON path "
        "(CI: BENCH_serve.json artifact + regression gate)",
    )
    ap.add_argument(
        "--trace-json",
        default="",
        help="export the telemetry bench's instrumented-run span ring as a "
        "Chrome trace_event JSON to this path (CI: Perfetto artifact)",
    )
    args = ap.parse_args()
    run(trace_path=args.trace_json)
    if args.json:
        write_results_json(args.json)


if __name__ == "__main__":
    main()
