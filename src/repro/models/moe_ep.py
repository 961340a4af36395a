"""Expert-parallel MoE via shard_map + all_to_all (§Perf iterations on the
MoE cells).

The pjit scatter/gather dispatch (moe.py) lets GSPMD realize the combine as
an all-reduce of the full (N·k, D) assignment tensor — measured at 2×2 TB
per step per device for olmoe train_4k (EXPERIMENTS.md §Perf).  Here the
routing is explicit:

  tokens: sharded over the batch (dp) axes, replicated over the EP axes'
  complement; experts: sharded over ``ep_axes`` (1-D: ('model',); 2-D for
  deepseek: ('data','model') — E=256 over 256 chips ⇒ ONE expert per chip,
  expert weights fully local, no FSDP re-gather per microbatch).

  per device:
    1. route locally (top-k); split assignments across the axes where the
       tokens are replicated (axis_index masking) — without this every
       model-copy ships identical payloads: ×16 wire/compute (measured);
    2. pack a (ep, C_send, D) send buffer (capacity per destination);
    3. all_to_all over ep_axes → received token payloads;
    4. scatter into (E_local, C_loc, D) per-expert buffers, run the FFNs;
    5. reverse all_to_all (same layout — outputs return to source slots);
    6. local combine (scatter-add × gate), psum over the replicated axes.

Wire bytes per device per layer ≈ 2·tokens_local·k·D·bytes — the all-to-all
minimum.  Gradients flow through all_to_all (transpose = reverse routing);
tests/test_moe_ep.py checks exact agreement with the dense reference for
both 1-D and 2-D EP meshes.

Serving additions (DESIGN.md §12): ``seq_len`` masks bucketed-prefill
padding out of capacity competition (mirroring moe.py), and ``dropless``
sizes both capacities at their worst-case bounds so no assignment can ever
drop — the row-independence invariant continuous batching needs.  Trace
under ``jax.set_mesh(mesh)`` (the engine's ``_with_backend`` enters it).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import act_fn
from repro.models.moe import MoEConfig, _route
from repro.models.quantized import tree_has_packed, unpack_params
from repro.nn.sharding import current_mesh


def _positions_for(dest: jax.Array, n_dest: int, cap: int, mask: Optional[jax.Array] = None):
    """dest (A,) int32 → (slot, keep): positions within each destination's
    capacity-bounded buffer (first-come priority).  ``mask`` excludes rows
    from BOTH the output (keep=False) and the slot numbering — a masked-out
    assignment (bucket padding, another copy's ownership partition) must
    not consume capacity that drops a real one."""
    onehot = jax.nn.one_hot(dest, n_dest, dtype=jnp.int32)  # (A, n_dest)
    if mask is not None:
        onehot = onehot * mask.astype(jnp.int32)[:, None]
    pos_all = jnp.cumsum(onehot, axis=0) - 1
    pos = jnp.take_along_axis(pos_all, dest[:, None], axis=1)[:, 0]
    keep = pos < cap
    if mask is not None:
        keep = keep & mask
    return jnp.minimum(jnp.maximum(pos, 0), cap - 1), keep


def moe_apply_ep(
    p,
    x,
    *,
    cfg: MoEConfig,
    compute_dtype=jnp.bfloat16,
    ep_axes=("model",),
    dp_axes=("pod", "data"),
    capacity_mult: float = 2.0,
    seq_len=None,
    dropless: bool = False,
) -> Tuple[jax.Array, Dict]:
    """x (B,T,D) global → (B,T,D).  Trace under ``jax.set_mesh(mesh)``.

    ``seq_len`` (traced scalar or None): bucketed-prefill valid length —
    positions >= seq_len are padding and are masked out of routing capacity
    (their output rows are junk, as in moe.py).  ``dropless``: size the
    send capacity at the ownership-partition worst case and the per-expert
    capacity at the one-assignment-per-token bound, so no assignment ever
    drops — decode rows stay independent of who shares the batch."""
    if tree_has_packed(p):
        # shard_map bodies below index raw kernels; densify Packed serving
        # leaves up front (exact) until the EP path grows a packed kernel.
        p = unpack_params(p, jnp.float32)
    mesh = current_mesh()
    if mesh is None:
        raise ValueError("moe_apply_ep must trace under an ambient mesh (jax.set_mesh)")
    ep_axes = tuple(a for a in ep_axes if a in mesh.axis_names)
    assert ep_axes, (mesh.axis_names,)
    # tokens ALWAYS shard over the batch axes (even when 'data' is also an
    # EP axis — 2-D EP); x is replicated only over the non-batch EP axes,
    # and assignments are partitioned across exactly those replicas.
    dp = tuple(a for a in dp_axes if a in mesh.axis_names)
    # shape-aware fallback (mirrors nn/sharding.pspec_for): drop batch axes
    # that don't divide B — serving admission prefills are a batch of ONE,
    # which replicates over 'data' and (when 'data' is an EP axis) folds it
    # into the assignment-ownership partition instead
    B = x.shape[0]
    while dp and B % math.prod(mesh.shape[a] for a in dp) != 0:
        dp = dp[:-1]
    repl_axes = tuple(a for a in ep_axes if a not in dp)
    ep_total = math.prod(mesh.shape[a] for a in ep_axes)
    msize = math.prod(mesh.shape[a] for a in repl_axes) if repl_axes else 1
    E, k = cfg.n_experts, cfg.top_k
    assert E % ep_total == 0, (E, ep_total)
    E_local = E // ep_total
    _, T, D = x.shape
    P = jax.sharding.PartitionSpec

    we = p["experts"]
    f = act_fn(cfg.act)

    in_specs = [
        P(dp if dp else None, None, None),  # x: batch over dp, repl over ep-complement
        P(),  # seq_len scalar
        P(),  # router
        P(ep_axes, None, None),  # gate_proj (E, D, F)
        P(ep_axes, None, None),  # up_proj
        P(ep_axes, None, None),  # down_proj
    ]
    shared_args = ()
    if cfg.n_shared_experts:
        sh = p["shared"]
        shared_args = (
            sh["gate_proj"]["kernel"],
            sh["up_proj"]["kernel"],
            sh["down_proj"]["kernel"],
        )
        in_specs += [P(None, "model"), P(None, "model"), P("model", None)]
    out_specs = (P(dp if dp else None, None, None), P(), P())

    def body(x_l, valid_len, router_w, gate_w, up_w, down_w, *shared_ws):
        Bl, Tl, _ = x_l.shape
        N = Bl * Tl
        xf = x_l.reshape(N, D)
        gates, idx, _, aux = _route({"router": {"kernel": router_w}}, xf, cfg)

        a_ids = idx.T.reshape(-1)  # (A=kN,) global expert
        A = a_ids.shape[0]
        token_ids = jnp.tile(jnp.arange(N, dtype=jnp.int32), (k,))
        g_flat = gates.T.reshape(-1).astype(jnp.float32)
        dest = a_ids // E_local  # destination device
        local_eid = a_ids % E_local

        # partition the (replicated) assignment set across the repl axes —
        # each copy routes a disjoint 1/msize of the assignments
        if msize > 1:
            midx = jnp.zeros((), jnp.int32)
            for a in repl_axes:
                midx = midx * mesh.shape[a] + jax.lax.axis_index(a)
            own = (jnp.arange(A, dtype=jnp.int32) % msize) == midx
        else:
            own = jnp.ones((A,), bool)
        # bucketed-prefill padding (positions >= seq_len) must not compete
        # for capacity — its junk output rows are masked the same way
        # moe.py masks the dispatch path
        token_valid = jnp.arange(Tl, dtype=jnp.int32) < valid_len
        token_valid = jnp.broadcast_to(token_valid[None, :], (Bl, Tl)).reshape(N)
        own = own & token_valid[token_ids]

        if dropless:
            # ownership is a strided 1/msize partition: at most ceil(A/msize)
            # assignments per copy, all of which could target one destination
            c_send = max(1, -(-A // msize))
        else:
            c_send = max(1, int(math.ceil(capacity_mult * A / (msize * ep_total))))
        slot, keep = _positions_for(dest, ep_total, c_send, mask=own)
        keepf = keep.astype(compute_dtype)

        xb = xf.astype(compute_dtype)
        send_x = jnp.zeros((ep_total, c_send, D), compute_dtype)
        send_x = send_x.at[dest, slot].add(xb[token_ids] * keepf[:, None])
        send_e = jnp.full((ep_total, c_send), -1, jnp.int32)
        send_e = send_e.at[dest, slot].max(jnp.where(keep, local_eid, -1))

        # ---- token payloads to expert owners ---------------------------------
        axis = ep_axes if len(ep_axes) > 1 else ep_axes[0]
        recv_x = jax.lax.all_to_all(send_x, axis, split_axis=0, concat_axis=0, tiled=True)
        recv_e = jax.lax.all_to_all(send_e, axis, split_axis=0, concat_axis=0, tiled=True)
        X = ep_total * c_send
        rx = recv_x.reshape(X, D)
        re_ = recv_e.reshape(X)

        # ---- per-local-expert buffers ----------------------------------------
        if dropless:
            # a token's top-k experts are distinct, so ONE expert sees at
            # most one assignment per global token: capacity B·T never drops
            c_loc = max(1, min(X, B * T))
        else:
            c_loc = max(1, int(math.ceil(capacity_mult * X / max(E_local, 1))))
        valid = re_ >= 0
        eslot, ekeep = _positions_for(jnp.where(valid, re_, 0), E_local, c_loc, mask=valid)
        ekeepf = ekeep.astype(compute_dtype)
        buf = jnp.zeros((E_local, c_loc, D), compute_dtype)
        buf = buf.at[jnp.where(valid, re_, 0), eslot].add(rx * ekeepf[:, None])

        h = jnp.einsum("eCD,eDF->eCF", buf, gate_w.astype(compute_dtype))
        u = jnp.einsum("eCD,eDF->eCF", buf, up_w.astype(compute_dtype))
        out_buf = jnp.einsum("eCF,eFD->eCD", f(h) * u, down_w.astype(compute_dtype))

        # ---- back to source layout --------------------------------------------
        y_rows = out_buf[jnp.where(valid, re_, 0), eslot] * ekeepf[:, None]
        back = jax.lax.all_to_all(
            y_rows.reshape(ep_total, c_send, D), axis, split_axis=0, concat_axis=0, tiled=True
        )
        y_send = back.reshape(ep_total, c_send, D)

        # ---- local combine + sum over the assignment partitions ---------------
        y_assign = y_send[dest, slot] * (g_flat.astype(compute_dtype) * keepf)[:, None]
        y = jnp.zeros((N, D), compute_dtype).at[token_ids].add(y_assign)

        # shared experts: TP-local partials folded into the same psum
        if shared_ws:
            sg, su, sd = (w.astype(compute_dtype) for w in shared_ws)
            gsh = jnp.einsum("ND,DF->NF", xb, sg)
            ush = jnp.einsum("ND,DF->NF", xb, su)
            y = y + jnp.einsum("NF,FD->ND", f(gsh) * ush, sd)

        psum_axes = tuple(dict.fromkeys(repl_axes + (("model",) if shared_ws else ())))
        if psum_axes and (msize > 1 or shared_ws):
            y = jax.lax.psum(y, psum_axes)

        all_axes = dp + tuple(a for a in ep_axes if a not in dp)
        aux = {kk: jax.lax.pmean(v, all_axes) for kk, v in aux.items()}
        return y.reshape(Bl, Tl, D), aux["moe_aux_loss"], aux["moe_z_loss"]

    y, aux_l, z_l = jax.shard_map(
        body, mesh=mesh, in_specs=tuple(in_specs), out_specs=out_specs, check_vma=False
    )(
        x,
        jnp.asarray(T if seq_len is None else seq_len, jnp.int32),
        p["router"]["kernel"],
        we["gate_proj"]["kernel"],
        we["up_proj"]["kernel"],
        we["down_proj"]["kernel"],
        *shared_args,
    )
    return y, {"moe_aux_loss": aux_l, "moe_z_loss": z_l}
