"""The scheduler's step spans (DESIGN.md §13): what each span covers,
how they nest, the counters they carry, and their profiler annotations.

The contracts: a one-shot ``admit`` span lasts until the first sampled
token is on the host, so it holds the prefill's device time; every child
span (grow, admit, chunk, decode, verify, emit) lies inside the ``step``
span of the same step number; the step's counters are the scheduler's
state once its growth, admissions and chunks are in; with tracing off
nothing is recorded and no profiler annotation opens; with tracing on a
``jax.profiler`` trace holds one host event per recorded span, found by
the benchmark's own trace reader.
"""
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.chip import xplane
from repro import configs, core
from repro.models import init_lm, set_packed_backend
from repro.serve import (
    Request,
    ServeConfig,
    ServeEngine,
    SpeculativeConfig,
    TelemetryConfig,
)
from repro.serve.scheduler import Scheduler

MAX_LEN = 24
KINDS = ("step", "grow", "admit", "decode", "emit")
_ENGINES = {}


@pytest.fixture
def unpack_backend():
    set_packed_backend("unpack")
    yield
    set_packed_backend("auto")


def _engine():
    """(qt engine, packed draft tree), cached: traces are the cost here."""
    if not _ENGINES:
        cfg = configs.get_reduced("internlm2-1.8b")
        params = init_lm(jax.random.PRNGKey(0), cfg)
        scfg = core.SymogConfig(n_bits=2, total_steps=1)
        st = core.symog_init(params, scfg)
        qt = core.quantize_tree(params, st, scfg)
        _ENGINES["eng"] = (
            ServeEngine(cfg, qt, max_len=MAX_LEN, compute_dtype=jnp.float32),
            core.pack_tree(params, st, scfg),
        )
    return _ENGINES["eng"]


def _requests(cfg, lens=(3, 6, 4, 5, 7), budgets=(5, 3, 6, 4, 4)):
    key = jax.random.PRNGKey(3)
    return [
        Request(
            tokens=np.asarray(
                jax.random.randint(jax.random.fold_in(key, i), (n,), 0, cfg.vocab_size)
            ),
            max_new_tokens=b,
        )
        for i, (n, b) in enumerate(zip(lens, budgets))
    ]


def _traced(**kw):
    return ServeConfig(
        n_slots=2, block_size=4, n_blocks=6, telemetry=TelemetryConfig(trace=True), **kw
    )


def _serve(config, reqs=None):
    eng, _ = _engine()
    return eng.serve(reqs or _requests(eng.cfg), config, return_scheduler=True)


def _busy(tok):
    """Tens of milliseconds of device work that the token depends on."""
    a = jnp.full((1024, 1024), 1e-3, jnp.float32) + tok.astype(jnp.float32)
    for _ in range(6):
        a = jnp.tanh(a @ a)
    return a.sum()


def _held(tok):
    time.sleep(0.06)
    return tok


@pytest.mark.parametrize("slowed_by", ["host callback", "device work"])
def test_admit_span_covers_the_prefill(unpack_backend, monkeypatch, slowed_by):
    """Every admission program is slowed after the prefill, by a 60-ms host
    callback or by device work the first token depends on; the admit span,
    which closes only once the first token is on the host, must hold that
    time.  On the CPU a program with a host callback runs inside its
    dispatch, so only the device work tells a span that ends at the
    download from one that ends at the dispatch."""
    eng, _ = _engine()
    fns = eng.scheduler_fns(greedy=True, top_k=0)
    real, slow = fns.admit_step, {}
    if slowed_by == "host callback":
        floor = 0.06
    else:
        busy = jax.jit(_busy)
        busy(jnp.int32(0)).block_until_ready()
        times = []
        for _ in range(3):
            t = time.perf_counter()
            busy(jnp.int32(0)).block_until_ready()
            times.append(time.perf_counter() - t)
        floor = 0.5 * min(times)

    def slowed(first):
        if slowed_by == "host callback":
            spec = jax.ShapeDtypeStruct(first.shape, first.dtype)
            return jax.pure_callback(_held, spec, first)
        return first + (_busy(first) > 1e30).astype(first.dtype)

    def slow_admit_step(bucket, block_size):
        key = (bucket, block_size)
        if key not in slow:
            admit = real(bucket, block_size)

            def run(*args):
                first, caches = admit(*args)
                return slowed(first), caches

            slow[key] = jax.jit(run, donate_argnums=(3,))
        return slow[key]

    monkeypatch.setattr(fns, "admit_step", slow_admit_step)
    reqs = _requests(eng.cfg, lens=(3, 6), budgets=(2, 2))
    _serve(_traced(), reqs)  # compile the slowed programs outside the measured spans
    comps, sched = _serve(_traced(), reqs)
    admits = [(dur, args) for kind, _, dur, args in sched.tracer.spans if kind == "admit"]
    assert len(admits) == 2
    assert all(dur >= floor for dur, _ in admits), (admits, floor)
    assert [args["bucket"] for _, args in admits] == [4, 8]
    assert all(args["start"] == 0 for _, args in admits)
    monkeypatch.undo()
    off = _serve(ServeConfig(n_slots=2, block_size=4, n_blocks=6), reqs)[0]
    assert [c.tokens for c in comps] == [c.tokens for c in off]  # the slowing changed nothing


def _nested(sched, child_kinds):
    steps = {}
    for kind, t, dur, args in sched.tracer.spans:
        if kind == "step":
            assert args["step"] not in steps  # one step span per step number
            steps[args["step"]] = (t, t + dur)
    seen = set()
    for kind, t, dur, args in sched.tracer.spans:
        if kind == "step":
            continue
        assert kind in child_kinds, kind
        lo, hi = steps[args["step"]]
        assert lo <= t and t + dur <= hi, (kind, args)
        seen.add(kind)
    return seen


@pytest.mark.parametrize(
    "mode, kinds",
    [
        ("one-shot", {"grow", "admit", "decode", "emit"}),
        ("chunked", {"grow", "chunk", "decode", "emit"}),
        ("speculative", {"grow", "admit", "verify", "emit"}),
    ],
)
def test_children_lie_inside_their_step(unpack_backend, mode, kinds):
    _, packed = _engine()
    extra = {
        "one-shot": {},
        "chunked": {"prefill_chunk": 2},
        "speculative": {"speculative": SpeculativeConfig(draft=packed, k=2)},
    }[mode]
    comps, sched = _serve(_traced(**extra))
    assert _nested(sched, kinds) == kinds
    # one step span per step() call, and every call of a drain counts a step
    n_steps = sum(1 for s in sched.tracer.spans if s[0] == "step")
    counted = sum(sched.stats[k] for k in ("decode_steps", "idle_steps", "prefill_only_steps"))
    assert n_steps == counted == sched.step_count
    assert all(c.finish_reason in ("eos", "length") for c in comps)


def test_step_counters_match_the_scheduler(unpack_backend, monkeypatch):
    """Each step's counters equal the scheduler's state at the point they
    are read, computed here from the slots themselves; the child spans'
    counts add up to the run's totals."""
    expected = []
    count_step = Scheduler._count_step

    def checked(self, span):
        count_step(self, span)
        live = [st for st in self._slots if st is not None]
        expected.append(
            {
                "step": self.step_count,
                "live": len(live),
                "decoding": sum(1 for st in live if not st.prefilling),
                "queued": len(self._queue),
                "blocks_live": sum(len(st.blocks) for st in live),
            }
        )

    monkeypatch.setattr(Scheduler, "_count_step", checked)
    _, sched = _serve(_traced())
    steps = [args for kind, _, _, args in sched.tracer.spans if kind == "step"]
    assert steps == expected
    assert max(a["blocks_live"] for a in steps) == sched.pool.peak_live
    spans = sched.tracer.spans

    def total(kind, key):
        return sum(args[key] for k, _, _, args in spans if k == kind)

    n_admit = sum(1 for s in spans if s[0] == "admit")
    assert total("emit", "tokens") + n_admit == sched.stats["tokens_emitted"]
    assert total("emit", "finished") == sched.stats["evictions"]
    assert total("grow", "new_blocks") + sum(
        a["prompt"] // 4 + 1 for k, _, _, a in spans if k == "admit"
    ) == sched.pool.total_allocs
    by_step = {a["step"]: a for a in steps}
    for kind, _, _, args in spans:
        if kind == "decode":
            assert args["n_decode"] == by_step[args["step"]]["decoding"]
        if kind == "emit":
            assert args["tokens"] == by_step[args["step"]]["decoding"]


def _host_events(directory):
    return xplane.load(xplane.find_xplane(str(directory))).host


def test_null_tracer_records_and_annotates_nothing(unpack_backend, tmp_path):
    _serve(ServeConfig(n_slots=2, block_size=4, n_blocks=6))  # warm the programs
    with jax.profiler.trace(str(tmp_path)):
        _, sched = _serve(ServeConfig(n_slots=2, block_size=4, n_blocks=6))
    assert sched.tracer.enabled is False and len(sched.tracer) == 0
    names = {e.name for e in _host_events(tmp_path)}
    assert not names & {*KINDS, "chunk", "verify", "serve_step"}


def test_spans_are_host_events_of_a_profiler_trace(unpack_backend, tmp_path):
    _serve(_traced())  # warm the programs
    with jax.profiler.trace(str(tmp_path)):
        _, sched = _serve(_traced())
    events = _host_events(tmp_path)
    for kind in KINDS:
        n_spans = sum(1 for s in sched.tracer.spans if s[0] == kind)
        assert n_spans > 0
        assert sum(1 for e in events if e.name == kind) == n_spans, kind
    n_steps = sum(1 for s in sched.tracer.spans if s[0] == "step")
    assert sum(1 for e in events if e.name == "serve_step") == n_steps


def test_profile_window_carries_the_spans(unpack_backend, tmp_path):
    """The operator's own capture (``TelemetryConfig.profile_dir``) holds
    the scheduler's spans when tracing is on."""
    _serve(_traced())  # warm the programs
    tele = TelemetryConfig(trace=True, profile_dir=str(tmp_path), profile_steps=4)
    _serve(ServeConfig(n_slots=2, block_size=4, n_blocks=6, telemetry=tele))
    names = [e.name for e in _host_events(tmp_path)]
    for kind in ("serve_step", "step", "grow", "decode", "emit"):
        assert kind in names, kind
