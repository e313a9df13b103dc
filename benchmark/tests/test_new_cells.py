"""The cells PR 27 added, end to end on the CPU at the tiny presets, through
the same runners, generators, check and per-layer readers as a chip run; and
the control on the new configuration at the small preset. (`test_rehearsal.py`
keeps its own table of the first three cells.)"""
import json
import os

import pytest

from conftest import ROOT

CELLS = {
    "mistral7b-long-prefill": {"gap_p95_ms", "setup_s"},
    "kanana2-longctx-decode": {"serve_tok_s", "setup_s"},
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_walks_through(rehearse, cell):
    result, out = rehearse(cell, "--trace", "0", seconds=3)
    assert result["correct"] is True, out
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == CELLS[cell]
    last = out.strip().splitlines()[-1]
    assert set(json.loads(last[len("REHEARSAL "):])["would_report"]) == CELLS[cell]
    assert "compare served_token_mean_gap" in out


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_traced_walk_reads_the_counters(rehearse, cell):
    result, _ = rehearse(cell, "--trace", "1", seconds=3)
    assert result["correct"] is True
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert result["metrics"], "no per-layer metric was read"
    for name in result["metrics"]:
        assert cell in per_layer[name]["workloads"]
        assert per_layer[name]["source"] != "device_trace"
    if cell == "kanana2-longctx-decode":
        assert result["metrics"]["moe_load_max_over_mean.serve"]["value"] >= 1


def test_every_new_entry_has_its_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    here = os.path.join(ROOT, "benchmark")
    for w in bench["workloads"]:
        for mix in (w["traffic"], "rehearsal-" + w["traffic"]):
            assert os.path.exists(os.path.join(here, "traffic", mix + ".json"))
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert set(m["workloads"]) <= cells
        stem = m["name"].rpartition(".")[0]
        assert os.path.exists(os.path.join(here, "layer_metrics", stem + ".py"))


@pytest.mark.parametrize("control,correct", [(None, True), ("ref-int8", False)])
def test_control_on_the_new_configuration(bench_run, preset_bench, capsys,
                                          control, correct):
    extra = ("--control", control) if control else ()
    result = bench_run.main([
        "--rehearse", "--bench-file", preset_bench("small"), "--workload",
        "kanana2-longctx-decode", "--seed", "3000000029", "--seconds", "4",
        *extra])
    out = capsys.readouterr().out
    assert result["correct"] is correct, out
    assert result["failed"] == 0, "the control fails the comparison, not the run"
