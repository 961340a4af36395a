"""What decides ``correct``: the served tokens against the float32
reference.

Once the window has closed and the program's state is freed, a sample of
the requests the program finished, drawn from the seed and always holding
the longest, is run through the reference teacher-forced on its prompt and
served tokens.  At every served token the gap is the reference's best
logit minus the reference's logit of the token the program served: 0 where
the program chose the reference's argmax, small where it chose among near
ties, large where the program computed something else.  The widest gap
over the sample is held to the cell's limit.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def sample(run, seed: int) -> List:
    """Requests to compare: the longest finished one, then the other
    finished ones in an order drawn from the seed, then (where those hold
    too few served tokens) requests still in flight at the close, whose
    tokens so far were served all the same, until the cell's token count
    is reached."""
    done = [r for r in run.requests if r.finish in ("length", "eos") and r.tokens]
    live = [r for r in run.requests if not r.finish and r.tokens]
    if not done and not live:
        return []
    want = run.traffic["check"]["tokens"]
    longest = max(done or live, key=lambda r: r.prompt_len + len(r.tokens))
    rng = np.random.default_rng([seed % 2**32, seed // 2**32, 0xC0C0])
    rest = [r for r in done if r is not longest]
    rest = [rest[j] for j in rng.permutation(len(rest))]
    others = [r for r in live if r is not longest]
    rest += [others[j] for j in rng.permutation(len(others))]
    picked, n = [longest], len(longest.tokens)
    for r in rest:
        if n >= want:
            break
        picked.append(r)
        n += len(r.tokens)
    return picked


def gaps(run, seed: int, picked, control: bool = False) -> Dict[str, float]:
    """Widest gap of the served tokens (and of the float8 control's first
    choices) over ``picked``."""
    from benchmarks.chip.reference import Reference

    ref = Reference(run.spec, run.server["kv_pool"], run.server["block_size"], seed)
    worst, worst_ctl, n = 0.0, 0.0, 0
    for r in picked:
        served = np.asarray(r.tokens, np.int32)
        seq = np.concatenate([r.plan.prompt, served[:-1]])
        out = ref.score(seq, r.prompt_len - 1, served, control=control)
        worst = max(worst, float(np.max(out[0] - out[1])))
        if control:
            worst_ctl = max(worst_ctl, float(np.max(out[0] - out[2])))
        n += len(served)
    res = {"logit_gap": worst, "tokens": n}
    if control:
        res["control_gap"] = worst_ctl
    return res


def check(run, seed: int) -> Dict[str, dict]:
    """The numbers compared, each with its limit and the verdict."""
    lim = run.traffic["check"]
    picked = sample(run, seed)
    got = gaps(run, seed, picked) if picked else {"logit_gap": None, "tokens": 0}
    gap = got["logit_gap"]
    return {
        "logit_gap": {"value": gap, "limit": lim["logit_gap"], "op": "<=",
                      "ok": gap is not None and gap <= lim["logit_gap"]},
        "checked_tokens": {"value": got["tokens"], "limit": lim["tokens"], "op": ">=",
                           "ok": got["tokens"] >= lim["tokens"]},
    }
