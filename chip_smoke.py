#!/usr/bin/env python3
"""Bring-up check: the packed serving path runs on a TPU and agrees with XLA.

    python chip_smoke.py [--seed 0]      # one chip
    python chip_smoke.py --chips 4       # the four-chip phase only

One chip (the default) builds internlm2-1.8b at its published widths from
random weights (``--seed``), packs it with SYMOG at 2 bits, drops the float
tree and serves 8 requests (prompts of 100-300 tokens, 32 greedy tokens
each) through ``ServeEngine.serve``, first on the bf16 paged pool and then
on the int8 one.  The same packed artifact, served by an engine built under
the plain-XLA ``unpack`` + ``composed`` backends, is the reference: the
kernel path's prefill logits must lie within twice the gap between that
reference in bf16 and in float32 (i.e. the kernels may be no less accurate
than XLA in bf16).  Greedy token agreement is printed, not gated: a random
model has near-tie logits.

``--chips 4`` serves the same internlm2 artifact on a 1x4 (data x model)
mesh against one chip, and olmoe-1b-7b with expert-parallel MoE on that
mesh against the dispatch MoE on one chip, and checks that every device
holds a share of the weights and of the KV pool.

Every line before the last is a report; none is a speed metric.  The last
line is ``{"ok": true, "device": {...}}``.  Without a TPU, or when any
check or phase fails, the script exits non-zero and prints no result line.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

MAX_LEN = 512
BLOCK = 16
EXPECT_BACKENDS = ("pallas", "fused")  # ServeEngine.backend, .attn_backend


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(*parts) -> None:
    print(*parts, flush=True)


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------
class CompileClock:
    """Seconds XLA spent compiling, summed from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0

        def listen(event, duration, **_):
            if "backend_compile" in event:
                self.seconds += duration

        jax.monitoring.register_event_duration_secs_listener(listen)


def packed_artifact(cfg, seed: int, *, shardings=None):
    """SYMOG 2-bit Packed tree of a randomly initialized ``cfg``; the float
    tree is dropped once packed.  ``shardings`` places the float init."""
    import jax

    from repro import core
    from repro.models import init_lm

    init = functools.partial(init_lm, cfg=cfg)
    init = jax.jit(init) if shardings is None else jax.jit(init, out_shardings=shardings)
    params = init(jax.random.PRNGKey(seed))
    scfg = core.SymogConfig(n_bits=2, total_steps=1)
    state = core.symog_init(params, scfg)
    packed = core.pack_tree(params, state, scfg)
    jax.block_until_ready(packed)
    del params
    return packed


def make_requests(vocab: int, seed: int, n: int, new_tokens: int):
    import numpy as np

    from repro.serve import Request

    rng = np.random.default_rng(seed)
    lens = rng.integers(100, 301, size=n)
    return [
        Request(tokens=rng.integers(0, vocab, size=int(t)).astype(np.int32),
                max_new_tokens=new_tokens)
        for t in lens
    ]


def serve(eng, reqs, label: str):
    """Greedy serve on 4 slots; every request must run to its length."""
    from repro.serve import ServeConfig

    cfg = ServeConfig(n_slots=4, block_size=BLOCK, temperature=0.0)
    t0 = time.perf_counter()
    comps, sched = eng.serve(reqs, cfg, return_scheduler=True)
    reasons = [c.finish_reason for c in comps]
    say(f"{label}: {len(comps)} requests, finish reasons {reasons}, "
        f"{time.perf_counter() - t0:.1f} s wall (compiles included)")
    check(all(r == "length" for r in reasons), f"{label}: a request did not finish by length")
    check(all(len(c.tokens) == r.max_new_tokens for c, r in zip(comps, reqs)),
          f"{label}: a request emitted the wrong token count")
    return [list(c.tokens) for c in comps], sched


def agreement(a, b) -> float:
    pairs = [(x, y) for ra, rb in zip(a, b) for x, y in zip(ra, rb)]
    return sum(x == y for x, y in pairs) / len(pairs)


def prefill_logits(eng, reqs, n: int = 2, t: int = 128):
    import jax.numpy as jnp
    import numpy as np

    toks = jnp.asarray(np.stack([np.asarray(r.tokens[:t]) for r in reqs[:n]]))
    logits, _ = eng.prefill({"tokens": toks})
    out = np.asarray(logits.astype(jnp.float32))
    check(bool(np.isfinite(out).all()), "prefill logits are not finite")
    return out


def reference_engine(cfg, params, compute_dtype):
    """An engine over the same artifact on the plain-XLA backends."""
    from repro.kernels import set_attention_backend, set_packed_backend
    from repro.serve import ServeEngine

    set_packed_backend("unpack")
    set_attention_backend("composed")
    try:
        return ServeEngine(cfg, params, max_len=MAX_LEN, compute_dtype=compute_dtype)
    finally:
        set_packed_backend("auto")
        set_attention_backend("auto")


def kernel_engine(cfg, params, mesh=None):
    from repro.serve import ServeEngine

    eng = ServeEngine(cfg, params, max_len=MAX_LEN, mesh=mesh)
    check((eng.backend, eng.attn_backend) == EXPECT_BACKENDS,
          f"engine backends {(eng.backend, eng.attn_backend)}, want {EXPECT_BACKENDS}")
    return eng


def custom_calls_in_decode(eng, sched) -> int:
    """tpu_custom_call count in the compiled paged decode step the
    scheduler ran (layer scans hold each kernel once)."""
    args = (eng.params, sched.caches, sched._tokens, sched._pos, sched._active,
            sched._seed0, sched._block_tables, sched._base_key, sched._temp)
    fns = eng.scheduler_fns(greedy=True, top_k=0)
    text = eng._with_backend(lambda *a: fns.decode_step.lower(*a).compile().as_text(), *args)
    return text.count("custom_call_target=\"tpu_custom_call\"")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def kernel_parity(cfg, seed: int, rel_tol: float = 2e-2) -> None:
    """Each serving kernel against its pure-jnp oracle at ``cfg``'s widths,
    oracles at float32 "highest" matmul precision.  A layout fault (wrong
    columns, head, block or exponent) is off by the output's own scale; the
    kernels' bf16 MXU passes stay orders of magnitude inside ``rel_tol``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import core
    from repro.kernels import fixedpoint_matmul, paged_attention
    from repro.kernels.paged_attention.ref import paged_attention_ref
    from repro.models.attention import KV_QMAX, block_scale_exp, quantize_fixed

    def report(name, got, want):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        err, scale = float(np.max(np.abs(got - want))), float(np.max(np.abs(want)))
        say(f"kernel parity {name}: max |diff| {err:.3g} of max |ref| {scale:.3g}")
        check(np.isfinite(got).all() and err <= rel_tol * scale, f"kernel parity {name}: {err}")

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    D, H, K, hd, F = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff
    x = jax.random.normal(next(keys), (8, D)).astype(jnp.bfloat16)
    # (D, F): one packing row per input row; (D, H, hd): one per head
    for name, out_dims in (("up_proj", (F,)), ("q_proj", (H, hd))):
        w = jax.random.normal(next(keys), (D,) + out_dims) * D**-0.5
        f, _ = core.optimal_f(w, 2)
        pk = core.pack(w, f, 2)
        n = int(np.prod(out_dims))
        got = fixedpoint_matmul(x, pk.data.reshape(D, -1), f, n_bits=2, n_out=n,
                                row_values=out_dims[-1])
        with jax.default_matmul_precision("highest"):
            want = x.astype(jnp.float32) @ core.unpack(pk).reshape(D, n)
        report(f"fixedpoint_matmul {name} 2-bit", got, want)

    B, G, block, max_blocks = 4, H // K, BLOCK, MAX_LEN // BLOCK
    n_phys = B * max_blocks + 1
    q = jax.random.normal(next(keys), (B, 1, K, G, hd)).astype(jnp.bfloat16)
    kv = [jax.random.normal(next(keys), (n_phys, K, block, hd)) for _ in range(2)]
    bt = jax.random.permutation(next(keys), jnp.arange(1, n_phys)).reshape(B, max_blocks)
    pos0 = jax.random.randint(next(keys), (B,), 0, MAX_LEN).astype(jnp.int32)
    kw = dict(scale=hd**-0.5)
    for pool in ("bf16", "int8", "int4"):
        if pool == "bf16":
            pools, extra = [p.astype(jnp.bfloat16) for p in kv], {}
        else:
            bits = 8 if pool == "int8" else 4
            pools, exps = [], []
            for p in kv:
                e = block_scale_exp(p[:, :, 0], KV_QMAX[bits])
                m = quantize_fixed(p, e[..., None], KV_QMAX[bits])
                pools.append(core.pack_int(m, 4) if bits == 4 else m)
                exps.append(e)
            extra = dict(k_scale_exp=exps[0], v_scale_exp=exps[1], kv_bits=bits)
        got = paged_attention(q, *pools, bt, pos0, **kw, **extra)
        with jax.default_matmul_precision("highest"):
            want = paged_attention_ref(q.astype(jnp.float32), *pools, bt, pos0, **kw, **extra)
        report(f"paged_attention {pool} pool", got, want)


def one_chip(get_config, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = get_config("internlm2-1.8b")
    kernel_parity(cfg, seed)
    t0 = time.perf_counter()
    packed = packed_artifact(cfg, seed)
    eng = kernel_engine(cfg, packed)
    say(f"built {cfg.name} (d_model {cfg.d_model}, {cfg.n_layers} layers, vocab "
        f"{cfg.vocab_size}) packed at 2 bits: {eng.weight_bytes()} weight bytes, "
        f"{time.perf_counter() - t0:.1f} s; backends {eng.backend}/{eng.attn_backend}")

    reqs = make_requests(cfg.vocab_size, seed, n=8, new_tokens=32)
    bf16_tokens, sched = serve(eng, reqs, "bf16 pool")
    n_calls = custom_calls_in_decode(eng, sched)
    say(f"tpu_custom_call in the compiled decode step: {n_calls}")
    check(n_calls > 0, "the decode step holds no Pallas kernel")
    del sched

    eng8 = kernel_engine(dataclasses.replace(cfg, kv_cache_dtype="int8_fp"), eng.params)
    int8_tokens, sched8 = serve(eng8, reqs, "int8 pool")
    del sched8, eng8
    say(f"int8-pool vs bf16-pool greedy token agreement: {agreement(int8_tokens, bf16_tokens):.4f}")

    ref16 = reference_engine(cfg, eng.params, jnp.bfloat16)
    ref32 = reference_engine(cfg, eng.params, jnp.float32)
    got = prefill_logits(eng, reqs)
    want = prefill_logits(ref16, reqs)
    with jax.default_matmul_precision("highest"):
        exact = prefill_logits(ref32, reqs)
    err = float(np.max(np.abs(got - want)))
    floor = float(np.max(np.abs(want - exact)))
    tol, scale = 2.0 * floor, float(np.max(np.abs(exact)))
    say(f"prefill logits, kernels vs XLA bf16: max |diff| {err:.6g}; tolerance {tol:.6g} "
        f"(2 x XLA bf16 vs float32 gap {floor:.6g}; max |logit| {scale:.6g})")
    check(err <= tol, f"kernel prefill logits off by {err} > {tol}")

    ref_tokens, _ = serve(ref16, reqs, "reference (unpack + composed) bf16 pool")
    say(f"kernel vs reference greedy token agreement: {agreement(bf16_tokens, ref_tokens):.4f}")
    stats = jax.devices()[0].memory_stats() or {}
    say(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}")


def device_bytes(tree) -> dict:
    """Bytes of ``tree``'s arrays held on each device id."""
    import jax

    out: dict = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for shard in leaf.addressable_shards:
            out[shard.device.id] = out.get(shard.device.id, 0) + shard.data.nbytes
    return out


def _mesh_vs_one(label, eng_mesh, eng_one, reqs):
    """Serve on the mesh and on one chip; report logits and tokens; check
    that every device of the mesh holds weights and KV pool."""
    import jax
    import numpy as np

    got = prefill_logits(eng_mesh, reqs)
    want = prefill_logits(eng_one, reqs)
    mesh_tokens, sched = serve(eng_mesh, reqs, f"{label} on the 1x4 mesh")
    one_tokens, _ = serve(eng_one, reqs, f"{label} on one chip")
    say(f"{label}: prefill logits mesh vs one chip max |diff| "
        f"{float(np.max(np.abs(got - want))):.6g}; greedy token agreement "
        f"{agreement(mesh_tokens, one_tokens):.4f}")
    weights, pool = device_bytes(eng_mesh.params), device_bytes(sched.caches)
    total = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(eng_mesh.params))
    ids = [d.id for d in eng_mesh.mesh.devices.flat]
    for d in jax.devices():
        stats = d.memory_stats() or {}
        say(f"{label}: device {d.id}: mesh weights {weights.get(d.id, 0)} of {total} B, "
            f"KV pool {pool.get(d.id, 0)} B, bytes_in_use {stats.get('bytes_in_use')}, "
            f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    check(all(weights.get(i, 0) > 0 and pool.get(i, 0) > 0 for i in ids),
          f"{label}: a mesh device holds no weights or no KV pool")
    check(max(weights.values()) < total, f"{label}: one device holds the whole weight tree")


def four_chips(get_config, seed: int) -> None:
    import gc

    import jax

    from repro.models import init_lm
    from repro.nn.sharding import make_mesh, make_rules, shardings_for_tree

    check(len(jax.devices()) == 4, f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    mesh = make_mesh((1, 4), ("data", "model"))
    one = jax.devices()[0]

    cfg = get_config("internlm2-1.8b")
    packed = packed_artifact(cfg, seed)
    reqs = make_requests(cfg.vocab_size, seed, n=4, new_tokens=16)
    _mesh_vs_one(cfg.name, kernel_engine(cfg, packed, mesh), kernel_engine(cfg, packed), reqs)
    del packed
    gc.collect()

    # olmoe: the float init only fits sharded over the four chips
    cfg = dataclasses.replace(get_config("olmoe-1b-7b"), moe_impl="ep")
    rules = make_rules(mesh, cfg.sharding_profile)
    shapes = jax.eval_shape(functools.partial(init_lm, cfg=cfg), jax.random.PRNGKey(seed))
    packed = packed_artifact(cfg, seed, shardings=shardings_for_tree(rules, shapes))
    eng_ep = kernel_engine(cfg, packed, mesh)
    check(bool(eng_ep.capabilities()["ep_moe"]), "expert-parallel MoE is not active on the mesh")
    packed_one = jax.device_put(packed, one)  # the whole packed tree on one chip
    eng_one = kernel_engine(dataclasses.replace(cfg, moe_impl="dispatch"), packed_one)
    reqs = make_requests(cfg.vocab_size, seed, n=4, new_tokens=16)
    _mesh_vs_one(f"{cfg.name} (ep vs dispatch)", eng_ep, eng_one, reqs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    try:
        from repro.configs import get_config
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is not beside this script: {e}",
              file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {devices[0].platform}); "
              "this check never runs on the CPU", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    say(f"devices: {len(devices)} x {devices[0].device_kind}; compile cache {cache} "
        f"({entries} entries at start)")
    clock = CompileClock()
    t0 = time.perf_counter()
    try:
        (four_chips if args.chips == 4 else one_chip)(get_config, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    say(f"compile seconds {clock.seconds:.1f}; total {time.perf_counter() - t0:.1f} s; "
        f"compile cache now {entries} entries")
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
