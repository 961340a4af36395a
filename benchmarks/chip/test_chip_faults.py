"""A whole benchmark run on the CPU at a tiny size, past the harness's look
for a chip: sound, it is correct; with the timed path broken underneath,
or with the float8 control in the program's place, ``correct`` comes out
false.  A serving cell on one chip can have these faults: a decode step
that returns its state unchanged, half of the batch left out, a token
altered where it is produced.  (It has no exchange between chips.)"""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import compare, harness, manifest

HERE = Path(__file__).parent
SEED = 2**31 + 77


@pytest.fixture(scope="module")
def session():
    bench = manifest.load_manifest()
    cell = manifest.Cell(
        "internlm2-chat", 1, json.loads((HERE / "testdata" / "tiny-internlm2.json").read_text()),
        json.loads((HERE / "testdata" / "tiny-faults.json").read_text()),
        bench["end_to_end"], bench["per_layer"])
    return harness.Session(cell, require_tpu=False)


def verdict(session):
    run = harness.serve(session, SEED, 1.0, False)
    checks = compare.check(run, SEED)
    return run, checks, all(c["ok"] for c in checks.values())


def test_sound_run_is_correct_and_the_control_is_not(session):
    run, checks, ok = verdict(session)
    assert ok, checks
    got = compare.gaps(run, SEED, compare.sample(run, SEED), control=True)
    limit = session.cell.traffic["check"]["logit_gap"]
    assert got["logit_gap"] <= limit < got["control_gap"]


def _break_decode(monkeypatch, fault):
    from repro.serve import engine

    init = engine.SchedulerFns.__init__

    def patched(self, *a, **kw):
        init(self, *a, **kw)
        step = self.decode_step

        def broken(params, caches, tokens, pos, active, *rest):
            if fault == "half_batch":  # the upper half of the slots is not computed
                keep = jnp.arange(active.shape[0]) < active.shape[0] // 2
                nxt, new_pos, new = step(params, caches, tokens, pos, active & keep, *rest)
                return jnp.where(keep, nxt, tokens), pos + active.astype(jnp.int32), new
            if fault == "stale_state":  # the step's cache writes are lost
                scratch = jax.tree_util.tree_map(jnp.copy, caches)
                nxt, new_pos, _ = step(params, scratch, tokens, pos, active, *rest)
                return nxt, new_pos, caches
            nxt, new_pos, new = step(params, caches, tokens, pos, active, *rest)
            return (nxt + 1) % 256, new_pos, new  # "token": altered where produced

        self.decode_step = broken

    monkeypatch.setattr(engine.SchedulerFns, "__init__", patched)


@pytest.mark.parametrize("fault", ["stale_state", "half_batch", "token"])
def test_broken_decode_is_not_correct(session, monkeypatch, fault):
    _break_decode(monkeypatch, fault)
    _, checks, ok = verdict(session)
    assert not ok, checks
    assert not checks["logit_gap"]["ok"]
