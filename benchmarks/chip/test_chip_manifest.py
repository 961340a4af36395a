"""Every cell, traffic mix, configuration and metric of BENCHMARK.json
loads by name, and the file keeps the benchmark's format.  A new cell,
mix or metric is files and entries only: this loader never changes."""
import json
import re
from pathlib import Path

import pytest

from benchmarks.chip import generator, manifest

ROOT = Path(__file__).resolve().parents[2]
BENCH = manifest.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert BENCH["command"][1] == "benchmarks/chip/run.py"
    assert (ROOT / BENCH["command"][1]).is_file()
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_cell_loads_by_name(w):
    cell = manifest.cell(w["name"])
    assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
    assert 1 <= len(w["why"]) <= 200
    assert cell.spec["name"] == w["config"]
    server = cell.traffic["server"]
    assert set(server) == {"n_slots", "kv_pool", "block_size", "n_blocks", "max_len",
                           "prefill_chunk", "prefix_cache"}
    plan = generator.plan(cell.traffic, BENCH["run_seconds"], 1, cell.spec["vocab_size"])
    longest = max(len(r.prompt) + r.max_new_tokens for r in plan)
    assert longest <= server["max_len"]
    assert set(cell.traffic["check"]) == {"tokens", "logit_gap"}
    # every cell reports set-up, another end-to-end metric and a per-layer one
    assert "setup_s" in {m.name for m in cell.metrics(False)}
    assert len(cell.metrics(False)) >= 2 and cell.metrics(True)


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_every_configuration_file(c):
    spec = json.loads((ROOT / c["file"]).read_text())
    assert c["file"].startswith("benchmarks/chip/") and spec["name"] == c["name"]
    assert spec["source"] == c["source"] and sorted(spec["reduced"]) == sorted(c["reduced"])
    for key in c["reduced"]:
        assert not re.search(r"(size|_dim|_rank|heads|experts_per_tok)$", key), key
    assert any(w["config"] == c["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("m", BENCH["end_to_end"] + BENCH["per_layer"], ids=lambda m: m["name"])
def test_every_metric_has_a_reader(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert callable(manifest.load_reader(m["name"]))
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(m.get("workloads", cells)) <= cells
    if "bound" in m:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")


def test_names_are_unique_and_setup_is_bounded_at_a_quarter():
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[key]]
        assert len(names) == len(set(names))
    setup = {m["name"]: m for m in BENCH["end_to_end"]}["setup_s"]
    assert setup["bound"] == 0.25
    assert len(json.dumps(BENCH)) < 64 * 1024
