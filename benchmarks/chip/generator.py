"""Open-loop request schedules from a traffic file and a seed.

One general generator reads every mix.  A schedule has two phases, the
warm traffic (the mix's ``warm_s``) and the measured window.  Each phase
holds a fixed set of requests: its count, the multiset of inter-arrival
gaps, prompt lengths, output lengths and topics follow from the mix and the
phase's length alone.  ``--seed`` draws the order of each of these within
the phase, independently, and the prompt token ids.  So every seed offers
the window the same work with its own burst pattern, and two runs differ by
what the order does, not by how much was offered.

The gaps are the ``n`` mid-quantiles of an exponential distribution (a
Poisson process's inter-arrival law), scaled to fill the phase; the lengths
are the ``n`` mid-quantiles of a lognormal clipped to its range; topics
follow a Zipf popularity over contiguous vocabulary slices.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass
class PlannedRequest:
    index: int  # position in the schedule (due order)
    due_s: float  # seconds after the schedule starts
    prompt: np.ndarray  # int32 token ids
    max_new_tokens: int
    topic: int  # -1: the whole vocabulary


def phase_counts(traffic: dict, seconds: float) -> tuple:
    """Requests due in the warm phase and in the measured window."""
    rate = traffic["rate_per_s"]
    return int(round(rate * traffic["warm_s"])), max(1, int(round(rate * seconds)))


def request_count(traffic: dict, seconds: float) -> int:
    """Requests due over the warm phase and the measured window."""
    return sum(phase_counts(traffic, seconds))


def _mid_quantiles(n: int) -> np.ndarray:
    return (np.arange(n, dtype=np.float64) + 0.5) / n


def lognormal_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` mid-quantiles of lognormal(log(median), sigma), clipped and
    rounded to whole tokens."""
    z = np.asarray([NormalDist().inv_cdf(q) for q in _mid_quantiles(n)])
    raw = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(n: int, length: float) -> np.ndarray:
    """``n`` mid-quantiles of an exponential, scaled so they sum to ``length``."""
    gaps = -np.log1p(-_mid_quantiles(n))
    return gaps * length / gaps.sum()


def zipf_counts(n_topics: int, s: float, n: int) -> np.ndarray:
    """Requests per topic under Zipf(s) popularity, largest remainder."""
    p = 1.0 / np.arange(1, n_topics + 1, dtype=np.float64) ** s
    want = p / p.sum() * n
    counts = np.floor(want).astype(np.int64)
    for i in np.argsort(-(want - counts))[: n - counts.sum()]:
        counts[i] += 1
    return counts


def topic_slice(topic: int, n_topics: int, vocab: int) -> range:
    """The contiguous vocabulary slice of ``topic`` (the last takes the rest)."""
    width = vocab // n_topics
    lo = topic * width
    return range(lo, vocab if topic == n_topics - 1 else lo + width)


def plan(traffic: dict, seconds: float, seed: int, vocab: int) -> List[PlannedRequest]:
    """The whole schedule of one run, in due order: the warm phase's
    requests due in [0, warm_s), the window's in [warm_s, warm_s + seconds)."""
    rng = np.random.default_rng([seed % 2**32, seed // 2**32, 0x7AF1C])
    n_topics = int(traffic.get("topics", 0))
    out = []
    start = 0.0
    for n, length in zip(phase_counts(traffic, seconds), (traffic["warm_s"], seconds)):
        if not n:
            start += length
            continue
        gaps = rng.permutation(exponential_gaps(n, length))
        prompts = rng.permutation(lognormal_lengths(traffic["prompt"], n))
        outputs = rng.permutation(lognormal_lengths(traffic["output"], n))
        if n_topics:
            counts = zipf_counts(n_topics, traffic["zipf_s"], n)
            topics = rng.permutation(np.repeat(np.arange(n_topics), counts))
        else:
            topics = np.full(n, -1)
        # the first request is due as the phase opens, the last one gap
        # before it closes
        due = start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        for j in range(n):
            t = int(topics[j])
            ids = topic_slice(t, n_topics, vocab) if t >= 0 else range(vocab)
            tokens = rng.integers(ids.start, ids.stop, size=int(prompts[j]), dtype=np.int64)
            out.append(PlannedRequest(len(out), float(due[j]), tokens.astype(np.int32),
                                      int(outputs[j]), t))
        start += length
    return out


def prefill_buckets(prompt_lens, chunk: int, max_len: int) -> List[int]:
    """Power-of-two prefill buckets that admitting these prompts compiles:
    one per one-shot prompt, and with chunked prefill one per chunk and per
    final tail.  Mirrors the scheduler's bucketing rule (``_bucket``)."""
    def bucket(t):
        b = 1
        while b < t:
            b <<= 1
        return min(b, max_len)

    out = set()
    for lp in prompt_lens:
        lp = int(lp)
        if chunk and lp > chunk:
            out.add(bucket(chunk))
            out.add(bucket(lp - (lp - 1) // chunk * chunk))
        else:
            out.add(bucket(lp))
    return sorted(out)
