"""Operation and byte counts against hand-worked numbers, counting the
rows a dispatch really carried and never its padding."""
import json
from pathlib import Path

import pytest

from benchmarks.chip import counts, dispatches

HERE = Path(__file__).parent


def spec(name, layers=1):
    s = json.loads((HERE / "configs" / f"{name}.json").read_text())
    s["num_hidden_layers"] = layers
    return s


def test_internlm2_layer_packed_matmuls_at_three_rows():
    calls = counts.fixedpoint_matmul_calls(spec("internlm2-1.8b"), tokens=3, head_rows=3)
    # q, k, v, o, gate, up, down, then the untied head
    assert calls == [
        (25_165_824, 1_048_576 + 24_576),  # 2048 -> 16 x 128
        (12_582_912, 524_288 + 18_432),  # 2048 -> 8 x 128
        (12_582_912, 524_288 + 18_432),
        (25_165_824, 1_048_576 + 24_576),  # 16 x 128 -> 2048
        (100_663_296, 4_194_304 + 61_440),  # 2048 -> 8192
        (100_663_296, 4_194_304 + 61_440),
        (100_663_296, 4_194_304 + 61_440),  # 8192 -> 2048
        (1_137_180_672, 47_382_528 + 567_552),  # head: 2048 -> 92544
    ]
    assert sum(o for o, _ in calls[:7]) == 6 * 62_914_560  # 2 x rows x layer weights


def test_olmoe_expert_layer_counts_routed_rows():
    calls = counts.fixedpoint_matmul_calls(spec("olmoe-1b-7b"), tokens=4, head_rows=4)
    attn = (33_554_432, 1_048_576 + 32_768)
    expert = (134_217_728, 33_554_432 + 196_608)  # 32 routed rows, all 64 experts read
    head = (824_180_736, 25_755_648 + 418_816)  # untied head: 2048 -> 50304
    assert calls == [attn] * 4 + [expert] * 3 + [head]


def test_paged_attention_decode_and_tail_prefill():
    s = spec("internlm2-1.8b")
    kw = dict(n_heads=16, n_kv_heads=8, head_dim=128, block=16)
    # one decode row at context 100 on the int8 pool: 7 blocks of exponents
    assert counts.paged_attention([(99, 1)], pool="int8_fp", **kw) == (
        819_200, 204_800 + 448 + 8_192)
    # a 4-token tail prefill at offset 0 sees 1 + 2 + 3 + 4 keys
    assert counts.paged_attention([(0, 4)], pool="int8_fp", **kw) == (81_920, 8_192 + 64 + 32_768)
    assert counts.paged_attention([(99, 1)], pool="bf16", **kw) == (
        819_200, 409_600 + 8_192)
    assert counts.paged_attention_calls(s, [(99, 1)], "int8_fp", 16) == [(819_200, 213_440)]


def test_least_seconds_takes_the_binding_roof():
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.least_seconds([(1000.0, 1.0), (1.0, 100.0)], peak) == pytest.approx(20.0)


class _Req:
    def __init__(self, index, prompt_len):
        self.index, self._lp = index, prompt_len

    @property
    def prompt_len(self):
        return self._lp


class _Step:
    def __init__(self, t0, t1, delivered):
        self.number, self.t0, self.t1, self.delivered = 0, t0, t1, delivered


class _Run:
    pass


def test_dispatches_carry_live_rows_not_slots():
    run = _Run()
    run.requests = [_Req(0, 100), _Req(1, 20), _Req(2, 7)]
    # a step that admitted request 2 (token 0) and decoded rows 0 and 1
    run.steps = [_Step(1.0, 2.0, [(2, 0), (0, 5), (1, 1)]), _Step(9.0, 9.5, [(0, 6)])]
    run.spans = [("admit", 1.1, 0.1, {"req": 2, "prompt": 7}),
                 ("chunk", 1.3, 0.1, {"req": 3, "done": 512, "tail": 64})]
    run.profile = {"t0": 0.5, "t1": 3.0}
    got = {d.kind: d for d in dispatches.in_profile(run)}
    assert got["decode"].rows == [(104, 1), (20, 1)]  # contexts 105 and 21
    assert got["decode"].tokens == 2 and got["decode"].head_rows == 2
    assert got["admit"].rows == [(0, 7)] and got["chunk"].rows == [(512, 64)]


def test_decode_model_flops():
    s = spec("internlm2-1.8b", layers=24)
    per_row = 2 * (24 * 62_914_560 + 2048 * 92544)
    att = 4 * 16 * 128 * 24
    assert counts.decode_model_flops(s, [10, 30]) == 2 * per_row + att * 40
