"""Self-speculative decoding: low-bit SYMOG draft, full-precision verify
(DESIGN.md §8).

SYMOG training yields the same weights at several fixed-point bit-widths,
so every served model ships with a free, distribution-matched cheap twin:
the low-bit ``pack_tree`` artifact.  This module spends that twin on
per-token decode latency.  Each scheduler step, the DRAFT (the packed
artifact, its own paged KV pool mirroring the target's block tables) runs
K cheap single-token decode steps to propose ``d_1..d_K``; the TARGET
(float or ``quantize_tree`` params) then scores all K proposals plus one
bonus position in ONE multi-token pass (``models/lm.py::
decode_verify_lm``): the K+1 fed tokens scatter their KV into the pool at
their global positions BEFORE the causal gather, so the returned logits
are exactly what K+1 sequential decode steps would have produced.

Acceptance:

  * greedy — accept the longest prefix of drafts matching the target's
    argmax chain; the first mismatch position commits the target's argmax
    instead.  Every committed token is the target's own greedy choice, so
    speculative serve() is TOKEN-IDENTICAL to ``generate_static`` — the
    draft only decides how many of those tokens arrive per step;
  * temperature/top-k — standard speculative rejection sampling: accept
    ``d_j`` with probability ``min(1, p(d_j)/q(d_j))`` (p/q the target/
    draft distributions under the SAME temperature and top-k filter), on
    rejection sample from ``norm(max(p - q, 0))``, and on full acceptance
    draw the bonus token from ``p_K``.  The committed stream is
    distributed exactly as vanilla sampling (not samplepath-identical to
    it); accept/residual draws are keyed by (request, position), so the
    stream is deterministic across admission order and batch composition.

Rollback is position bookkeeping alone: rejected positions keep stale KV
in both pools that the §6 position mask hides (kv_pos <= q_pos) until the
next round's scatter overwrites it, and per-request position counters roll
back on the host — no device revert pass.  The draft pool trails by one
entry after a fully-accepted round (the bonus token was never drafted), so
the draft phase runs K+1 steps: the extra step writes ``d_K``'s draft KV
and its output is discarded.

Per-request ADAPTIVE depth (GREEDY mode only): each request carries an
AIMD recommendation (grow by one on full acceptance, shrink to its
accepted count on rejection) and a round runs at the max over live rows —
rows that keep rejecting stop paying K sequential draft dispatches.
Greedy commits are the target's argmax chain at any depth, so the
batch-coupled depth is stream-neutral there; in SAMPLED mode the depth
decides which positions draw bonus vs accept/residual, so a neighbor's
recommendation would leak into this request's stream — sampled rounds
therefore always run at full ``k``.  Verify traces are memoized per depth
(<= K of them, like admission buckets).

Eligibility is structural and mirrors the prefix cache: only the
fully-paged tier (every cache leaf of every group in the block pool —
all-attention or MLA decoders) can roll a rejection back by position
bookkeeping.  Recurrent/SSD per-row state, conv windows, ring buffers and
encdec cross-kv advance irreversibly per step; MoE capacity competition
couples the K+1 in-flight tokens.  On those families the flag is accepted
and structurally inert — every step is a vanilla decode step
(``stats['spec_steps']`` stays 0; ``launch/serve.py`` warns).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.models.lm import decode_lm, decode_verify_lm
from repro.obs import NULL_SPAN
from repro.serve.config import ServeConfig
from repro.serve.engine import filter_logits
from repro.serve.scheduler import Scheduler, _sample_seed

# PRNG stream tags: draft proposals, accept uniforms and residual draws all
# fold the serve seed through distinct subkeys so no stream is reused
_DRAFT_TAG = 7901
_ACCEPT_TAG = 7907
_RESIDUAL_TAG = 7919


def speculative_eligible(engine) -> bool:
    """Would ``speculative`` actually speculate on this engine?  True on
    the fully-paged tier (all-attention or MLA decoders); elsewhere the
    flag is accepted but structurally inert (DESIGN.md §8) — launchers use
    this to warn instead of silently no-opping.  Delegates to
    ``engine.capabilities()`` — the one source of truth with reasons."""
    from repro.serve.config import capabilities

    return bool(capabilities(engine)["speculative"])


@dataclasses.dataclass
class SpeculativeConfig:
    """Speculation knobs for ``ServeEngine.serve(..., speculative=...)``.

    ``draft``: the draft artifact — a params tree of the SAME architecture
    (typically the 2-bit ``pack_tree``) or a ready ``ServeEngine`` wrapping
    one.  ``k``: max draft tokens per verify round (the verify scores k+1
    positions).  ``adaptive``: per-request AIMD depth adaptation — honored
    in greedy mode only (sampled rounds always run at full ``k``: a
    batch-coupled depth would break sampled-stream determinism across
    batch composition; module docstring); when off every round runs at
    full depth ``k``."""

    draft: Any
    k: int = 4
    adaptive: bool = True


class SpeculativeFns:
    """Jitted draft/verify traces for one (greedy, top_k) sampling config.
    Owned by the TARGET engine (``ServeEngine.speculative_fns`` memo) so
    serve() calls share compilations; draft params ride in as arguments
    (the packed treedef compiles its own variant once).

    ``draft_step`` is a single-token self-decode on the draft pool that
    additionally returns the draft's (filtered) next-token distribution
    when sampling.  ``verify_step(k)`` returns the depth-k verify trace:
    one ``decode_verify_lm`` pass over the target pool plus the in-trace
    acceptance rule — the host downloads only (tokens, accepted counts)
    per round."""

    def __init__(self, engine, *, greedy: bool, top_k: int):
        self._eng = engine
        self._greedy = greedy
        self._top_k = top_k
        cfg, cd = engine.cfg, engine.compute_dtype

        def _draft_step(params, caches, tokens, pos, active, seed0, block_tables, key, temperature):
            logits, caches = decode_lm(
                params,
                caches,
                tokens[:, None],
                pos,
                cfg,
                compute_dtype=cd,
                active=active,
                block_tables=block_tables,
            )
            lg = logits[:, -1, :].astype(jnp.float32)
            new_pos = pos + active.astype(jnp.int32)
            if greedy:
                nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                return nxt, new_pos, caches
            scaled = filter_logits(lg, temperature, top_k)
            probs = jax.nn.softmax(scaled, axis=-1)
            keys = jax.vmap(lambda s: jax.random.fold_in(key, s))(seed0 + pos)
            nxt = jax.vmap(jax.random.categorical)(keys, scaled).astype(jnp.int32)
            return nxt, probs, new_pos, caches

        self.draft_step = jax.jit(_draft_step, donate_argnums=(1,))
        self._verifies: Dict[int, Any] = {}
        self.verify_compiles = 0

    def verify_step(self, k: int):
        """The depth-k verify trace, compiled on first use and memoized —
        adaptive depth costs at most ``draft_k`` trace shapes."""
        k = int(k)
        if k not in self._verifies:
            self._verifies[k] = jax.jit(self._build_verify(k), donate_argnums=(1,))
            self.verify_compiles += 1
        return self._verifies[k]

    def _build_verify(self, k: int):
        eng, greedy, top_k = self._eng, self._greedy, self._top_k
        cfg, cd, max_len = eng.cfg, eng.compute_dtype, eng.max_len
        T = k + 1

        def _accept_greedy(lg, draft_toks, valid):
            tgt = jnp.argmax(lg, axis=-1).astype(jnp.int32)  # (B, T)
            ok = (draft_toks == tgt[:, :-1]) & valid[:, 1:]
            m = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)
            return tgt, m

        def _accept_sampled(lg, draft_toks, draft_probs, valid, pos, seed0, key, temperature):
            B = lg.shape[0]
            p = jax.nn.softmax(filter_logits(lg, temperature, top_k), axis=-1)  # (B,T,V)
            d = draft_toks
            p_d = jnp.take_along_axis(p[:, :k], d[..., None], axis=-1)[..., 0]  # (B,k)
            q_d = jnp.take_along_axis(draft_probs, d[..., None], axis=-1)[..., 0]
            # accept d_j w.p. min(1, p/q); uniforms keyed per (request,
            # position) — deterministic across batch composition, and an
            # exact draft (p == q) always accepts (u < 1)
            seeds = seed0[:, None] + pos[:, None] + jnp.arange(k, dtype=jnp.int32)[None]
            acc_key = jax.random.fold_in(key, _ACCEPT_TAG)
            u = jax.vmap(jax.vmap(lambda s: jax.random.uniform(jax.random.fold_in(acc_key, s))))(
                seeds
            )
            ratio = jnp.where(q_d > 0, p_d / jnp.maximum(q_d, 1e-20), 0.0)
            ok = (u < ratio) & valid[:, 1:]
            m = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)  # (B,)
            # residual at the rejection index: norm(max(p_m - q_m, 0)); q is
            # zero-padded at index k so a full accept's bonus draw is p_k.
            # A position whose accept test never RAN (capacity-blocked by
            # the valid mask at the cache boundary, not coin-rejected) must
            # also draw from the FULL target distribution: subtracting q
            # there would ban every token the draft over-weights from being
            # the request's final token — zero q wherever the test was
            # masked, so those indices get bonus semantics too
            q_pad = jnp.concatenate([draft_probs, jnp.zeros_like(p[:, :1])], axis=1)
            tested = jnp.concatenate([valid[:, 1:], jnp.zeros((B, 1), bool)], axis=1)
            q_pad = q_pad * tested[..., None]
            p_m = jnp.take_along_axis(p, m[:, None, None], axis=1)[:, 0]  # (B,V)
            q_m = jnp.take_along_axis(q_pad, m[:, None, None], axis=1)[:, 0]
            res = jnp.maximum(p_m - q_m, 0.0)
            res = jnp.where(jnp.sum(res, axis=-1, keepdims=True) > 0, res, p_m)
            res_key = jax.random.fold_in(key, _RESIDUAL_TAG)
            res_tok = jax.vmap(
                lambda r, s: jax.random.categorical(
                    jax.random.fold_in(res_key, s), jnp.log(r + 1e-30)
                )
            )(res, seed0 + pos + m).astype(jnp.int32)
            d_pad = jnp.concatenate([d, jnp.zeros((B, 1), jnp.int32)], axis=1)
            at_m = jnp.arange(T, dtype=jnp.int32)[None] == m[:, None]
            return jnp.where(at_m, res_tok[:, None], d_pad), m

        if greedy:

            def _verify(params, caches, last_tok, draft_toks, pos, active, seed0, bt, key, temp):
                tokens = jnp.concatenate([last_tok[:, None], draft_toks], axis=1)
                positions = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
                valid = positions <= max_len - 1
                logits, caches = decode_verify_lm(
                    params, caches, tokens, pos, cfg,
                    compute_dtype=cd, active=active, valid=valid, block_tables=bt,
                )
                out, m = _accept_greedy(logits.astype(jnp.float32), draft_toks, valid)
                return out, m, caches

            return _verify

        def _verify(
            params, caches, last_tok, draft_toks, draft_probs, pos, active, seed0, bt, key, temp
        ):
            tokens = jnp.concatenate([last_tok[:, None], draft_toks], axis=1)
            positions = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
            valid = positions <= max_len - 1
            logits, caches = decode_verify_lm(
                params, caches, tokens, pos, cfg,
                compute_dtype=cd, active=active, valid=valid, block_tables=bt,
            )
            out, m = _accept_sampled(
                logits.astype(jnp.float32), draft_toks, draft_probs, valid, pos, seed0, key, temp
            )
            return out, m, caches

        return _verify


class SpeculativeScheduler(Scheduler):
    """Continuous-batching scheduler with a draft-K/verify-K+1 speculation
    controller on the fully-paged tier (module docstring; DESIGN.md §8).

    The draft owns a SECOND cache pool of identical geometry; the single
    ``BlockPool`` and the per-slot block tables drive both (allocation,
    growth, eviction, preemption and the trash-block redirect are shared),
    so the §6 invariants hold for the pair by construction.  Off the
    eligible tier every step defers to the vanilla ``Scheduler.step``."""

    def __init__(self, engine, config: Optional[ServeConfig] = None, **legacy):
        # the prefix_cache / prefill_chunk conflicts are rejected at
        # ServeConfig construction (its __post_init__), not here
        if isinstance(config, int):  # legacy positional n_slots
            legacy["n_slots"] = config
            config = None
        if legacy:
            config = ServeConfig(**legacy)  # super().__init__ would re-warn; build once
        config = (config or ServeConfig()).resolve(engine)
        if config.speculative is None:
            raise ValueError("SpeculativeScheduler needs config.speculative (a SpeculativeConfig)")
        speculative = config.speculative
        super().__init__(engine, config)
        self.spec_cfg = speculative
        self.draft_k = max(1, int(speculative.k))
        # batch-coupled depth adaptation is GREEDY-ONLY: greedy commits are
        # the target's argmax chain at ANY depth, but in sampled mode the
        # round depth decides which positions draw bonus vs accept/residual,
        # so a neighbor row's AIMD recommendation would leak into this
        # request's stream and break the batch-composition determinism
        # contract — sampled rounds always run at full draft_k
        self._adaptive = bool(speculative.adaptive) and self.temperature <= 0.0
        # spec_* count work PERFORMED, like the base class's tokens_emitted:
        # a preempted request's discarded rounds stay counted here and are
        # re-counted by its replay, while Completion.spec_steps/spec_tokens
        # describe only the delivered stream (the final pass) — the two
        # views reconcile exactly when nothing was preempted
        self.stats.update(
            {
                "spec_steps": 0,  # scheduler rounds that ran draft+verify
                "spec_row_rounds": 0,  # (live row, round) pairs — the §8 denominator
                "spec_drafted": 0,
                "spec_accepted": 0,
                "spec_emitted": 0,
                "verify_trace_compiles": 0,  # depth-k verify traces built this run
            }
        )
        self.spec_fns: Optional[SpeculativeFns] = None
        self.draft_eng = None
        self.draft_caches = None
        self._adaptive_k: Dict[int, int] = {}  # slot -> AIMD depth recommendation
        self._slot_spec: Dict[int, Tuple[int, int]] = {}  # slot -> (rounds, tokens)
        if not speculative_eligible(engine):
            return  # structurally inert: every step() is a vanilla decode
        from repro.serve.engine import ServeEngine

        draft = speculative.draft
        if not isinstance(draft, ServeEngine):
            draft = ServeEngine(
                engine.cfg, draft, max_len=engine.max_len, compute_dtype=engine.compute_dtype
            )
        if draft.cfg != engine.cfg:
            raise ValueError("draft must share the target's architecture (cache shapes mirror)")
        if draft.max_len != engine.max_len:
            raise ValueError(
                f"draft max_len={draft.max_len} != target max_len={engine.max_len}"
            )
        self.draft_eng = draft
        self.spec_fns = engine.speculative_fns(greedy=self.temperature <= 0.0, top_k=self.top_k)
        self._verify_compiles0 = self.spec_fns.verify_compiles
        self.draft_caches = self._init_caches()  # same geometry: cfg and dtypes match

    # ------------------------------------------------------------------
    # admission / teardown hooks
    # ------------------------------------------------------------------
    def _admit_one(self, slot, idx, prompt, budget, req, blocks, start=0):
        super()._admit_one(slot, idx, prompt, budget, req, blocks, start)
        if self.spec_fns is None or self._slots[slot] is None:
            # ineligible tier, or the request finished AT admission (budget
            # 1 / instant EOS: table row already zeroed, nothing to draft)
            return
        # mirror the admission prefill into the DRAFT pool: the same
        # bucketed trace (shared prep via _admit_batch, so target and draft
        # can't diverge) with draft params/caches and the slot's live table
        # row; the sampled token is discarded — the first committed token
        # always comes from the TARGET's admission (lossless)
        bucket, batch = self._admit_batch(prompt, req)
        admit = self._fns.admit_step(bucket, self.block_size)
        _, self.draft_caches = self.draft_eng._with_backend(
            admit,
            self.draft_eng.params,
            batch,
            jnp.int32(prompt.shape[0]),
            self.draft_caches,
            self._block_tables[slot],
            jnp.int32(slot),
            jnp.int32(_sample_seed(idx, 0)),
            self._base_key,
            self._temp,
        )
        self._adaptive_k[slot] = self.draft_k

    def _release(self, slot):
        self._adaptive_k.pop(slot, None)
        self._slot_spec.pop(slot, None)
        return super()._release(slot)

    def _finish(self, slot, reason):
        state = self._slots[slot]
        rounds, toks = self._slot_spec.get(slot, (0, 0))
        super()._finish(slot, reason)
        comp = self._completions[state.index]
        comp.spec_steps, comp.spec_tokens = rounds, toks

    # ------------------------------------------------------------------
    # the speculative loop
    # ------------------------------------------------------------------
    def _depth(self) -> int:
        """This round's draft depth: max of the live rows' adaptive
        recommendations (rows that keep rejecting stop forcing K draft
        dispatches on the batch), full ``draft_k`` when adaptation is off
        or the mode is sampled (see ``self._adaptive``)."""
        if not self._adaptive:
            return self.draft_k
        ks = [
            self._adaptive_k.get(s, self.draft_k)
            for s in range(self.n_slots)
            if self._slots[s] is not None
        ]
        return max(1, min(self.draft_k, max(ks))) if ks else self.draft_k

    def step(self) -> bool:
        if self.spec_fns is None:
            return super().step()
        if self._profile is not None:
            self._profile.on_step()
        with self._step_span() as span:
            # growth runs twice: existing rows reserve their draft windows
            # before admission spends blocks (the §6 step-order rule), and a
            # second pass covers freshly admitted rows' windows — under
            # pressure it may preempt the youngest (correct: replay is exact)
            self._grow_tables(horizon=self._depth())
            self._admit()
            depth = self._depth()
            self._grow_tables(horizon=depth)
            self._count_step(span)
            if self._n_live == 0:
                if not self._queue:
                    self._sync_gauges()
                    return False
                self.step_count += 1
                self.stats["idle_steps"] += 1
                self._sync_gauges()
                return True
            self._spec_round(depth)
            self._sync_gauges()
            return bool(self._n_live or self._queue)

    def _spec_round(self, k: int) -> None:
        fns, eng = self.spec_fns, self.eng
        greedy = self.temperature <= 0.0
        draft_key = jax.random.fold_in(self._base_key, _DRAFT_TAG)
        n = self.step_count
        tr = self.tracer
        t0 = time.perf_counter()
        span = tr.span("verify", step=n, k=k, n_live=self._n_live) if tr.enabled else NULL_SPAN
        span.__enter__()
        # draft phase: k+1 single-token self-decode steps on the draft pool
        # (chained on device, no host sync).  The (k+1)-th step only writes
        # d_k's draft KV so a fully-accepted round leaves no hole for the
        # next round's drafting; its proposal is discarded.
        cur, dpos = self._tokens, self._pos
        d_toks, d_probs = [], []
        for i in range(k + 1):
            out = self.draft_eng._with_backend(
                fns.draft_step,
                self.draft_eng.params,
                self.draft_caches,
                cur,
                dpos,
                self._active,
                self._seed0,
                self._block_tables,
                draft_key,
                self._temp,
            )
            if greedy:
                cur, dpos, self.draft_caches = out
            else:
                cur, probs, dpos, self.draft_caches = out
                if i < k:
                    d_probs.append(probs)
            if i < k:
                d_toks.append(cur)
        draft_toks = jnp.stack(d_toks, axis=1)  # (B, k)

        verify = fns.verify_step(k)
        args = [eng.params, self.caches, self._tokens, draft_toks]
        if not greedy:
            args.append(jnp.stack(d_probs, axis=1))  # (B, k, V) draft dists
        out_t, m_t, self.caches = eng._with_backend(
            verify,
            *args,
            self._pos,
            self._active,
            self._seed0,
            self._block_tables,
            self._base_key,
            self._temp,
        )
        out_np = np.asarray(out_t)  # the round's one host sync
        m_np = np.asarray(m_t)
        span.__exit__(None, None, None)
        dt = time.perf_counter() - t0
        self.step_count += 1
        self.stats["decode_steps"] += 1
        self.stats["spec_steps"] += 1
        self.stats["spec_drafted"] += k * self._n_live
        self.stats["verify_trace_compiles"] = fns.verify_compiles - self._verify_compiles0
        self._observe_step_time(dt)

        with self._emit_span(n):
            for s in range(self.n_slots):
                state = self._slots[s]
                if state is None:
                    continue
                accepted = int(m_np[s])
                # commits: accepted drafts then the verify's correction/bonus
                # token, truncated by the row's budget and an in-stream EOS
                ncommit = min(accepted + 1, state.budget - len(state.out))
                toks = [int(t) for t in out_np[s, :ncommit]]
                if state.eos_id >= 0 and state.eos_id in toks:
                    toks = toks[: toks.index(state.eos_id) + 1]
                    ncommit = len(toks)
                state.out.extend(toks)
                state.pos += ncommit
                self._emit_tokens(state)
                self.stats["tokens_emitted"] += ncommit
                self._h_accept.observe(ncommit)
                per_tok = dt / max(1, ncommit)  # this row's per-token wall time view
                for _ in range(ncommit):
                    self._h_itl.observe(per_tok)
                self.stats["spec_accepted"] += min(accepted, ncommit)
                self.stats["spec_emitted"] += ncommit
                self.stats["spec_row_rounds"] += 1
                rounds, committed = self._slot_spec.get(s, (0, 0))
                self._slot_spec[s] = (rounds + 1, committed + ncommit)
                if self._adaptive:
                    # AIMD: one deeper after a clean round, shrink to what was
                    # accepted (floor 1) after a rejection
                    grown = min(self.draft_k, k + 1)
                    self._adaptive_k[s] = grown if accepted >= k else max(1, accepted)
                if toks[-1] == state.eos_id:
                    self._finish(s, "eos")
                elif len(state.out) >= state.budget:
                    self._finish(s, "length")

        # rollback: rejected positions keep stale KV the position mask
        # hides; the device mirrors are refreshed from the host's committed
        # counts (per-row, so one vector upload each for tokens and pos)
        tok_np = np.zeros(self.n_slots, np.int32)
        pos_np = np.zeros(self.n_slots, np.int32)
        for s, state in enumerate(self._slots):
            if state is not None:
                tok_np[s] = state.out[-1]
                pos_np[s] = state.pos
        self._tokens = jnp.asarray(tok_np)
        self._pos = jnp.asarray(pos_np)
