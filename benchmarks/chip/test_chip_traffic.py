"""The open-loop generator: deterministic from the seed, the same work for
every seed, the stated rate, clipped lengths and Zipf topic skew."""
import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import generator

HERE = Path(__file__).parent


def mix(name):
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["chat", "topics", "longdoc"])
def test_same_seed_same_schedule(name):
    a = generator.plan(mix(name), 30, 2**31 + 5, 50304)
    b = generator.plan(mix(name), 30, 2**31 + 5, 50304)
    assert [(r.due_s, r.max_new_tokens, r.topic) for r in a] == [
        (r.due_s, r.max_new_tokens, r.topic) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("name", ["chat", "topics", "longdoc"])
def test_every_seed_offers_the_same_work(name):
    a = generator.plan(mix(name), 30, 11, 50304)
    b = generator.plan(mix(name), 30, 2**40 + 3, 50304)
    key = lambda p: sorted(len(r.prompt) for r in p)  # noqa: E731
    assert key(a) == key(b)
    assert sorted(r.max_new_tokens for r in a) == sorted(r.max_new_tokens for r in b)
    assert Counter(r.topic for r in a) == Counter(r.topic for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]  # another order
    assert [r.due_s for r in a] != [r.due_s for r in b]  # another burst pattern
    # and the same work in each phase, so in the window too
    m = mix(name)
    for lo, hi in ((0.0, m["warm_s"]), (m["warm_s"], m["warm_s"] + 30)):
        pa = [r for r in a if lo <= r.due_s < hi]
        pb = [r for r in b if lo <= r.due_s < hi]
        assert len(pa) == len(pb) > 0
        assert sorted(len(r.prompt) for r in pa) == sorted(len(r.prompt) for r in pb)
        assert sorted(r.max_new_tokens for r in pa) == sorted(r.max_new_tokens for r in pb)
        assert Counter(r.topic for r in pa) == Counter(r.topic for r in pb)
        # each phase's gaps, the last one up to the phase's close
        gaps = lambda q: sorted(np.diff([r.due_s for r in q] + [hi]))  # noqa: E731
        assert np.allclose(gaps(pa), gaps(pb))


def test_rate_and_count():
    m = mix("chat")
    p = generator.plan(m, 30, 1, 92544)
    n_warm, n_win = round(m["rate_per_s"] * m["warm_s"]), round(m["rate_per_s"] * 30)
    assert len(p) == n_warm + n_win == generator.request_count(m, 30)
    due = np.asarray([r.due_s for r in p])
    assert np.all(np.diff(due) > 0)
    assert np.sum(due < m["warm_s"]) == n_warm
    assert due[0] == 0.0 and due[n_warm] == pytest.approx(m["warm_s"])
    assert due[-1] < m["warm_s"] + 30
    gaps = np.diff(due)
    assert gaps.mean() == pytest.approx(1 / m["rate_per_s"], rel=0.1)
    # exponential: the spread of the gaps equals their mean
    assert gaps.std() == pytest.approx(gaps.mean(), rel=0.15)


@pytest.mark.parametrize("name", ["chat", "topics", "longdoc"])
def test_lengths_clipped_with_stated_median(name):
    m = mix(name)
    p = generator.plan(m, 30, 3, 50304)
    lens = np.asarray([len(r.prompt) for r in p])
    outs = np.asarray([r.max_new_tokens for r in p])
    assert lens.min() >= m["prompt"]["min"] and lens.max() <= m["prompt"]["max"]
    assert outs.min() >= m["output"]["min"] and outs.max() <= m["output"]["max"]
    assert np.median(lens) == pytest.approx(m["prompt"]["median"], rel=0.05)
    assert np.median(outs) == pytest.approx(m["output"]["median"], rel=0.05)


def test_topic_skew_and_slices():
    m = mix("topics")
    vocab = 50304
    p = generator.plan(m, 30, 5, vocab)
    counts = Counter(r.topic for r in p)
    want = sum(generator.zipf_counts(8, m["zipf_s"], n)
               for n in generator.phase_counts(m, 30))
    assert [counts[t] for t in range(8)] == list(want)
    assert want[0] > 2 * want[3] > 0  # popularity falls as 1 / rank^1.2
    for r in p:
        s = generator.topic_slice(r.topic, 8, vocab)
        assert r.prompt.min() >= s.start and r.prompt.max() < s.stop


def test_prefill_buckets_follow_chunking():
    assert generator.prefill_buckets([300, 32], 0, 3072) == [32, 512]
    # 1030 = 512 + 512 + 6: chunks of 512 and a final tail of 6
    assert generator.prefill_buckets([1030], 512, 9216) == [8, 512]
    assert generator.prefill_buckets([1024, 100], 512, 9216) == [128, 512]
