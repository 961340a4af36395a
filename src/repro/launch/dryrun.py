import os
# a CPU simulation: never take an accelerator, in this process or the
# per-cell children it starts (they inherit the environment)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell from
ShapeDtypeStructs only — proves the distribution config is coherent without
hardware.  Records memory_analysis / cost_analysis / collective bytes per
cell as JSON for the roofline report (benchmarks/roofline.py).

    python -m repro.launch.dryrun --arch gemma2-27b --shape train_4k
    python -m repro.launch.dryrun --arch gemma2-27b --shape train_4k --multi-pod
    python -m repro.launch.dryrun --all            # every cell, subprocess each

The XLA_FLAGS line above MUST precede any jax import: jax locks the device
count at first init.  Smoke tests / benches never import this module.
"""
import argparse
import json
import subprocess
import sys
import time
import traceback
from typing import Any, Dict

import numpy as np

import jax
import jax.numpy as jnp

from repro import core, optim
from repro.configs import ARCHS, SHAPES, cell_supported, get_config, input_specs
from repro.launch.hlo import collective_bytes
from repro.launch.jaxpr_cost import jaxpr_cost
from repro.launch.mesh import make_production_mesh
from repro.launch.shardings import (
    cache_shardings,
    data_shardings,
    param_shardings,
    replicated,
    state_shardings,
)
from repro.models.config import ModelConfig
from repro.models.lm import decode_lm, init_lm, prefill_lm
from repro.train import init_train_state, make_train_step

RESULTS_DIR = os.path.join(
    os.path.dirname(__file__), "..", "..", "..", "benchmarks", "results", "dryrun"
)

# TPU v5e constants (roofline denominators)
V5E = {"peak_flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9}

TRAIN_ACCUM = 8  # all train_4k cells are 1M tokens/step — grad accumulation


def _train_accum(cfg: "ModelConfig", multi_pod: bool) -> int:
    # deepseek-671b single-pod needs ×16: at ×8 the 7168-wide activations
    # put the per-device peak over 16 GiB HBM (memory_analysis, §Perf).
    # Multi-pod keeps ×8 — ×16 would make the microbatch (16 seqs) smaller
    # than the 32-way (pod,data) batch sharding, and memory halves anyway.
    return 16 if (cfg.name.startswith("deepseek") and not multi_pod) else TRAIN_ACCUM


def _lower_cell(arch: str, shape: str, multi_pod: bool, overrides=None,
                quantized: bool = False):
    import ast
    import dataclasses as _dc

    cfg = get_config(arch)
    if overrides:
        parsed = {}
        for k, v in overrides.items():
            try:
                parsed[k] = ast.literal_eval(v) if isinstance(v, str) else v
            except (ValueError, SyntaxError):
                parsed[k] = v
        cfg = _dc.replace(cfg, **parsed)
    if quantized:
        # packed 2-bit weights + fixed-point int8 KV cache (paper quantizer)
        cfg = _dc.replace(cfg, kv_cache_dtype="int8_fp")
    mesh = make_production_mesh(multi_pod=multi_pod)
    cell = SHAPES[shape]
    specs = input_specs(cfg, shape)

    with jax.set_mesh(mesh):
        if cell.kind == "train":
            # deepseek: bf16 momentum (optimizer-state compression) — fp32
            # momentum for 654B expert params alone is 10.2 GiB/chip
            mom_dtype = jnp.bfloat16 if cfg.name.startswith("deepseek") else jnp.float32
            tx = optim.sgd(momentum=0.9, nesterov=True, momentum_dtype=mom_dtype)
            scfg = core.SymogConfig(n_bits=2, total_steps=10_000)
            mb_sh = data_shardings(specs, mesh)

            def mb_constraint(mb):
                return jax.tree_util.tree_map(
                    lambda x, s: jax.lax.with_sharding_constraint(x, s), mb, mb_sh
                )

            batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
            act_pspec = jax.sharding.PartitionSpec(batch_axes, None, None)
            step = make_train_step(
                cfg, tx, core.constant(0.01), symog_cfg=scfg,
                accum_steps=_train_accum(cfg, multi_pod),
                mb_constraint=mb_constraint, act_pspec=act_pspec, cast_params=True,
            )
            state = jax.eval_shape(
                lambda: init_train_state(init_lm(jax.random.PRNGKey(0), cfg), tx, scfg)
            )
            state_sh = state_shardings(state, mesh, cfg.sharding_profile)
            batch_sh = data_shardings(specs, mesh)
            jf = jax.jit(step, in_shardings=(state_sh, batch_sh),
                         out_shardings=(state_sh, None), donate_argnums=0)
            fn, fargs = step, (state, specs)
            lowered = jf.lower(state, specs)

        elif cell.kind == "prefill":
            params = jax.eval_shape(
                lambda: init_lm(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16)
            )
            p_sh = param_shardings(params, cfg, mesh)
            batch_sh = data_shardings(specs, mesh)

            cache_len = cell.seq + (cfg.prefix_len if cfg.family == "vlm" else 0)
            batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
            act_pspec = jax.sharding.PartitionSpec(batch_axes, None, None)

            def prefill(p, b):
                return prefill_lm(p, b, cfg, max_len=cache_len, act_pspec=act_pspec)

            # pin the output cache shardings — left unspecified XLA may
            # materialize the (L,B,S,K,hd) caches unsharded (47 GiB/dev for
            # granite); found via memory_analysis in the baseline pass
            cache_struct = jax.eval_shape(prefill, params, specs)[1]
            out_sh = (None, cache_shardings(cache_struct, cfg, mesh))
            jf = jax.jit(prefill, in_shardings=(p_sh, batch_sh), out_shardings=out_sh)
            fn, fargs = prefill, (params, specs)
            lowered = jf.lower(params, specs)

        else:  # decode
            if quantized:
                # SYMOG-packed serving: quantizable weights live in HBM as
                # 2-bit-packed int8 words (8× less resident/read bytes than
                # bf16); dequantized on the fly (on TPU the fixedpoint_matmul
                # Pallas kernel fuses unpack+dot — see kernels/).
                scfg = core.SymogConfig(n_bits=2, total_steps=1)

                def make_packed():
                    p = init_lm(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16)
                    st = core.symog_init(p, scfg)
                    return core.pack_tree(p, st, scfg), st

                params, symog_state = jax.eval_shape(make_packed)

                def decode(p, c, tok, pos):
                    deq = jax.tree_util.tree_map(
                        lambda l: core.packing.unpack(l, jnp.bfloat16)
                        if isinstance(l, core.Packed) else l,
                        p, is_leaf=lambda l: isinstance(l, core.Packed),
                    )
                    return decode_lm(deq, c, tok, pos, cfg)
            else:
                params = jax.eval_shape(
                    lambda: init_lm(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16)
                )

                def decode(p, c, tok, pos):
                    return decode_lm(p, c, tok, pos, cfg)

            p_sh = param_shardings(params, cfg, mesh)
            caches = specs.pop("caches")
            c_sh = cache_shardings(caches, cfg, mesh)
            tok_sh = data_shardings({"tokens": specs["tokens"]}, mesh)["tokens"]

            jf = jax.jit(decode, in_shardings=(p_sh, c_sh, tok_sh, replicated(mesh)),
                         donate_argnums=1)
            fn, fargs = decode, (params, caches, specs["tokens"], specs["pos"])
            lowered = jf.lower(params, caches, specs["tokens"], specs["pos"])

    return cfg, mesh, lowered, fn, fargs


def _mem_dict(mem) -> Dict[str, Any]:
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes", "peak_memory_in_bytes"):
        v = getattr(mem, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def _model_flops(cfg: ModelConfig, shape: str) -> float:
    """6·N·D (train) / 2·N·D per generated token (serve), N = active params."""
    n_active = cfg.active_param_count()
    cell = SHAPES[shape]
    tokens = cell.batch * (cell.seq if cell.kind == "train" else 1)
    if cell.kind == "prefill":
        tokens = cell.batch * cell.seq
    mult = 6 if cell.kind == "train" else 2
    return float(mult) * n_active * tokens


def _tree_bytes(tree) -> int:
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            total += int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
    return total


def run_cell(arch: str, shape: str, multi_pod: bool, quantized: bool = False) -> Dict[str, Any]:
    cfg = get_config(arch)
    ok, reason = cell_supported(cfg, shape)
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": 512 if multi_pod else 256,
        "profile": cfg.sharding_profile,
        "quantized": quantized,
    }
    if not ok:
        rec.update(status="SKIP", reason=reason)
        return rec

    t0 = time.time()
    cfg, mesh, lowered, fn, fargs = _lower_cell(arch, shape, multi_pod, quantized=quantized)
    rec["lower_s"] = round(time.time() - t0, 1)

    if SHAPES[shape].kind == "decode":
        # decode reads every resident weight + the cache once per step —
        # the honest memory-term numerator for serving (on TPU the packed
        # path streams int8 words via kernels/fixedpoint_matmul)
        params_b = _tree_bytes(fargs[0])
        cache_b = _tree_bytes(fargs[1])
        rec["resident"] = {"params_bytes": params_b, "cache_bytes": cache_b}

    # logical (global, trip-count-exact) cost from the jaxpr
    t0 = time.time()
    with jax.set_mesh(mesh):  # model sharding constraints need the ambient mesh
        logical = jaxpr_cost(fn, *fargs)
    rec["trace_s"] = round(time.time() - t0, 1)
    rec["logical"] = logical

    t0 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 1)

    mem = compiled.memory_analysis()
    print(mem)  # required artifact: proves the program fits
    rec["memory"] = _mem_dict(mem)

    cost = compiled.cost_analysis()
    print({k: v for k, v in cost.items() if k in ("flops", "bytes accessed")})
    rec["cost_analysis_raw"] = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "note": "XLA counts while bodies once; see 'logical' for trip-exact",
    }

    text = compiled.as_text()
    rec["collectives"] = collective_bytes(text)

    chips = rec["chips"]
    flops_dev = logical["flops"] / chips
    bytes_dev = logical["dot_bytes"] / chips
    # per-device wire bytes at TPU dtypes (XLA-CPU promotes bf16 reduces to
    # f32 — "_promoted" reducers counted at bf16 width; raw kept alongside)
    coll_dev = rec["collectives"]["total_bytes_tpu"]
    model_flops = _model_flops(cfg, shape)
    rec["roofline"] = {
        "compute_s": flops_dev / V5E["peak_flops"],
        "memory_s": bytes_dev / V5E["hbm_bw"],
        "collective_s": coll_dev / V5E["ici_bw"],
        "collective_s_raw": rec["collectives"]["total_bytes"] / V5E["ici_bw"],
        "model_flops_total": model_flops,
        "model_flops_per_chip": model_flops / chips,
        "useful_flops_ratio": model_flops / logical["flops"] if logical["flops"] else 0.0,
    }
    if "resident" in rec:
        rec["roofline"]["memory_s_resident"] = (
            (rec["resident"]["params_bytes"] + rec["resident"]["cache_bytes"])
            / chips / V5E["hbm_bw"]
        )
    dom = max(("compute_s", "memory_s", "collective_s"), key=lambda k: rec["roofline"][k])
    rec["roofline"]["dominant"] = dom
    rec["status"] = "OK"
    return rec


def _result_path(arch: str, shape: str, multi_pod: bool, quantized: bool = False) -> str:
    d = os.path.join(os.path.abspath(RESULTS_DIR), "pod2" if multi_pod else "pod1")
    os.makedirs(d, exist_ok=True)
    suffix = "_q2" if quantized else ""
    return os.path.join(d, f"{arch}__{shape}{suffix}.json")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true", help="all cells via subprocesses")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--quantized", action="store_true",
                    help="decode with SYMOG 2-bit packed weights")
    ap.add_argument("--meshes", default="both", choices=("pod1", "pod2", "both"))
    args = ap.parse_args()

    if args.all:
        failures = []
        meshes = {"pod1": [False], "pod2": [True], "both": [False, True]}[args.meshes]
        for mp in meshes:
            for arch in ARCHS:
                for shape in SHAPES:
                    variants = [False]
                    if SHAPES[shape].kind == "decode":
                        variants.append(True)  # SYMOG-packed serving variant
                    for q in variants:
                        path = _result_path(arch, shape, mp, q)
                        if os.path.exists(path) and not args.force:
                            continue
                        cmd = [sys.executable, "-m", "repro.launch.dryrun",
                               "--arch", arch, "--shape", shape]
                        if mp:
                            cmd.append("--multi-pod")
                        if q:
                            cmd.append("--quantized")
                        print(f"[dryrun] {arch} × {shape}{' ×q2' if q else ''} × "
                              f"{'2x16x16' if mp else '16x16'}", flush=True)
                        r = subprocess.run(cmd, env={**os.environ})
                        if r.returncode != 0:
                            failures.append((arch, shape, mp, q))
        if failures:
            print("FAILURES:", failures)
            return 1
        print("dry-run matrix complete")
        return 0

    assert args.arch and args.shape, "--arch/--shape required without --all"
    path = _result_path(args.arch, args.shape, args.multi_pod, args.quantized)
    try:
        rec = run_cell(args.arch, args.shape, args.multi_pod, quantized=args.quantized)
    except Exception:
        rec = {
            "arch": args.arch, "shape": args.shape,
            "mesh": "2x16x16" if args.multi_pod else "16x16",
            "status": "ERROR", "error": traceback.format_exc(),
        }
        with open(path, "w") as f:
            json.dump(rec, f, indent=2)
        print(rec["error"], file=sys.stderr)
        return 1
    with open(path, "w") as f:
        json.dump(rec, f, indent=2)
    print(json.dumps({k: v for k, v in rec.items() if k != "error"}, indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
