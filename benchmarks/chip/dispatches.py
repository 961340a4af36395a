"""The dispatches the traced sub-window ran, with what each carried:
decode steps with each live row's real context, one-shot admissions with
their prompt tokens, chunks with their offset and tokens."""
from __future__ import annotations

import dataclasses
from typing import List, Tuple


@dataclasses.dataclass
class Dispatch:
    kind: str  # 'decode' | 'admit' | 'chunk'
    rows: List[Tuple[int, int]]  # (start position, tokens) per row
    head_rows: int  # rows whose logits the dispatch computes

    @property
    def tokens(self) -> int:
        return sum(t for _, t in self.rows)


def in_profile(run) -> List[Dispatch]:
    p = run.profile
    lo, hi = p["t0"], p["t1"]
    by_index = {r.index: r for r in run.requests}
    out = []
    for s in run.steps:
        if s.t0 < lo or s.t1 > hi:
            continue
        rows = [(by_index[i].prompt_len + k - 1, 1) for i, k in s.delivered
                if k >= 1 and i in by_index]
        if rows:
            out.append(Dispatch("decode", rows, len(rows)))
    for kind, t, dur, args in run.spans:
        if t < lo or t + dur > hi:
            continue
        if kind == "admit":
            out.append(Dispatch("admit", [(0, int(args["prompt"]))], 1))
        elif kind == "chunk":
            out.append(Dispatch("chunk", [(int(args["done"]), int(args["tail"]))], 1))
    return out
