"""Training launcher — the end-to-end driver (deliverable (b)).

    PYTHONPATH=src python -m repro.launch.train \
        --arch internlm2-1.8b --reduced --steps 300 --symog \
        --ckpt-dir /tmp/run1 [--resume] [--mesh 1x1]

Wires together: config registry → synthetic data (host-sharded,
checkpointable) → pjit train step (SYMOG on/off) → async checkpoints →
straggler monitor.  On this CPU container use ``--reduced``; on a real
cluster drop it and pass ``--mesh 16x16``.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro import core, optim
from repro.checkpoint import CheckpointManager
from repro.configs import ARCHS, get_config, get_reduced
from repro.data import SyntheticLM, SyntheticLMConfig
from repro.distributed import StepTimeMonitor
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.shardings import data_shardings, state_shardings
from repro.models.lm import init_lm
from repro.nn.sharding import make_mesh
from repro.train import init_train_state, make_train_step


def parse_mesh(spec: str):
    dims = tuple(int(x) for x in spec.split("x"))
    names = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
    return make_mesh(dims, names)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--symog", action="store_true", help="enable SYMOG QAT")
    ap.add_argument("--n-bits", type=int, default=2)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=20)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    mesh = parse_mesh(args.mesh)

    data = SyntheticLM(SyntheticLMConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq, global_batch=args.batch,
        seed=args.seed,
    ))

    tx = optim.chain(optim.clip_by_global_norm(1.0), optim.sgd(momentum=0.9, nesterov=True))
    lr_sched = core.linear_lr(args.lr, args.lr / 10, args.steps)
    symog_cfg = (
        core.SymogConfig(n_bits=args.n_bits, total_steps=args.steps)
        if args.symog else None
    )
    compute_dtype = jnp.float32 if args.reduced else jnp.bfloat16
    step_fn = make_train_step(cfg, tx, lr_sched, symog_cfg=symog_cfg,
                              accum_steps=args.accum, compute_dtype=compute_dtype)

    with jax.set_mesh(mesh):
        params = init_lm(jax.random.PRNGKey(args.seed), cfg)
        state = init_train_state(params, tx, symog_cfg)
        st_sh = state_shardings(jax.eval_shape(lambda: state), mesh, cfg.sharding_profile)
        state = jax.device_put(state, st_sh)
        batch_struct = jax.eval_shape(
            lambda: {"tokens": jnp.zeros((args.batch, args.seq), jnp.int32)}
        )
        b_sh = data_shardings(batch_struct, mesh)
        jstep = jax.jit(step_fn, in_shardings=(st_sh, b_sh),
                        out_shardings=(st_sh, None), donate_argnums=0)

        ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
        start = 0
        if ckpt and args.resume and ckpt.latest_step() is not None:
            state, meta, start = ckpt.restore(jax.eval_shape(lambda: state), shardings=st_sh)
            data.load_state_dict(meta["data"])
            print(f"resumed from step {start}")

        mon = StepTimeMonitor()
        for i in range(start, args.steps):
            batch = {k: jax.device_put(v, b_sh[k]) for k, v in next(data).items()}
            mon.start()
            state, metrics = jstep(state, batch)
            slow = mon.stop()
            if i % args.log_every == 0 or i == args.steps - 1:
                m = {k: float(v) for k, v in metrics.items()}
                print(f"step {i:5d} loss {m['loss']:.4f} gnorm {m['grad_norm']:.3f}"
                      + (f" λ {m['symog_lambda']:.1f}" if "symog_lambda" in m else "")
                      + (" [straggler]" if slow else ""), flush=True)
            if ckpt and (i + 1) % args.ckpt_every == 0:
                ckpt.save(i + 1, state, metadata={"data": data.state_dict()})
        if ckpt:
            ckpt.save(args.steps, state, metadata={"data": data.state_dict()}, blocking=True)

        if symog_cfg is not None:
            qm = core.quant_error_metrics(state.params, state.symog, symog_cfg)
            print(f"final rel quant error: {float(qm['rel_quant_error']):.2e} "
                  f"(ce floor {data.ce_floor():.3f})")
        print(f"straggler fraction: {mon.straggler_fraction():.3f}")


if __name__ == "__main__":
    main()
