"""The command end to end on the CPU at the tiny presets, all three cells:
the same runners, generators, check and per-layer readers as a chip run.
Counts only; the result line is never printed."""
import json
import os

import pytest

from conftest import ROOT

CELLS = {
    "mistral7b-chat-open": {"gap_p95_ms", "setup_s"},
    "yi6b-train-4k": {"train_tok_s", "setup_s"},
    "mistral7b-batch-decode": {"serve_tok_s", "setup_s"},
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_walks_through(rehearse, cell):
    result, out = rehearse(cell, "--trace", "0")
    assert result["correct"] is True, out
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == CELLS[cell]
    last = out.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL "), "a rehearsal prints no result line"
    assert set(json.loads(last[len("REHEARSAL "):])["would_report"]) == CELLS[cell]
    assert "compare " in out, "every number compared is printed beside its limit"


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_traced_walk_reads_the_counters(rehearse, cell):
    """`--trace 1` reports per-layer metrics; on the CPU only those that
    need no device trace find something to read."""
    result, _ = rehearse(cell, "--trace", "1")
    assert result["correct"] is True
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert result["metrics"], "no per-layer metric was read"
    for name in result["metrics"]:
        assert cell in per_layer[name]["workloads"]
        assert per_layer[name]["source"] != "device_trace"


def test_same_seed_same_first_losses(rehearse):
    _, a = rehearse("yi6b-train-4k")
    _, b = rehearse("yi6b-train-4k")
    pick = lambda out: [l for l in out.splitlines() if "first 3 steps" in l][0].split("losses")[1]  # noqa: E731
    assert pick(a) == pick(b)


def test_no_chip_no_result(bench_run, capsys):
    """Without `--rehearse` the command measures, and on a CPU it refuses:
    exit code 3 and no result line."""
    rc = bench_run.main(["--workload", "yi6b-train-4k", "--seed", "1",
                         "--seconds", "1"])
    assert rc == 3
    assert "{" not in capsys.readouterr().out
