"""The cells PR 38 added, end to end on the CPU at the tiny presets, through
the same runners, generators, check and per-layer readers as a chip run; the
broken path of the new runner; and that the new costs never count more than
the kernel must read. (`test_new_cells.py` keeps PR 27's table.)"""
import json
import os

import numpy as np
import pytest

from conftest import ROOT, load

CELLS = {
    "commandaplus-mixedctx-decode": {"serve_tok_s", "setup_s"},
    "kanana2-docqa-open": {"gap_p95_ms", "setup_s"},
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
    result, out = rehearse(cell, "--trace", "1", seconds=3)
    assert result["correct"] is True, out
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert result["metrics"], "no per-layer metric was read"
    for name in result["metrics"]:
        assert cell in per_layer[name]["workloads"]
        assert per_layer[name]["source"] != "device_trace"
    if cell == "commandaplus-mixedctx-decode":
        m = result["metrics"]
        # the tiny preset holds experts 2-5 of a router 8 wide
        assert 20 < m["moe_held_assignment_share.cmdaplus"]["value"] < 80
        assert m["window_blocks_released_per_step.cmdaplus"]["value"] > 0
        assert 0 < m["window_blocks_peak_share.cmdaplus"]["value"] <= 100
        assert "released behind the window" in out


def test_every_new_entry_has_its_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    here = os.path.join(ROOT, "benchmark")
    run = load("run.py", "benchmark_run_pr38")
    every = {w["name"] for w in bench["workloads"]}
    moved = {e["name"]: set(e.get("workloads", every))
             for e in bench["end_to_end"]}
    for m in bench["per_layer"]:
        if set(m["workloads"]) & set(CELLS):
            assert os.path.exists(run._reader(m["name"]))
            # every cell of an entry reports the metric the entry moves
            assert set(m["workloads"]) <= moved[m["moves"]], m["name"]
    for w in bench["workloads"]:
        if w["name"] in CELLS:
            assert len(w["why"]) <= 200
            for mix in (w["traffic"], "rehearsal-" + w["traffic"]):
                assert os.path.exists(
                    os.path.join(here, "traffic", mix + ".json"))


def test_a_token_altered_in_the_tail_is_not_correct(bench_run, preset_bench,
                                                    capsys, monkeypatch):
    """The timed path itself, broken: the sampler's token is replaced where
    the step's tail picks it, so what is served is not what the model
    chose. The comparison has to see it."""
    from paddle_tpu.ops import sampling

    real = sampling.step_tail

    def tail(logits, lanes, temperature):
        out = real(logits, lanes, temperature)
        return out.at[0].set((out[0] + 1) % logits.shape[-1])

    monkeypatch.setattr(sampling, "step_tail", tail)
    result = bench_run.main([
        "--rehearse", "--bench-file", preset_bench("tiny"), "--workload",
        "commandaplus-mixedctx-decode", "--seed", "3000000038",
        "--seconds", "3"])
    out = capsys.readouterr().out
    assert result["correct"] is False, out
    assert result["failed"] == 0, "the comparison fails, not the run"


@pytest.mark.parametrize("kv_len,q_len", [(1, 1), (100, 1), (4096, 1),
                                          (4097, 1), (30000, 1), (5000, 512),
                                          (600, 512), (40960, 512)])
def test_costs_never_count_more_than_the_kernel_reads(kv_len, q_len):
    """`ragged_attention_bytes` of a sliding layer is counted over the keys
    its queries can see, and those all lie in pages the kernel fetches: the
    pages from the first query's oldest visible position to the last
    query's own."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "command-a-plus-05-2026-serve.json")) as f:
        cfg = json.load(f)
    costs = load("costs_cohere2_moe.py", "costs_c2_test")
    w, bs = cfg["sliding_window"], cfg["deployment"]["block_size"]
    row = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * 2
    qo = q_len * 2 * cfg["num_attention_heads"] * cfg["head_dim"] * 2
    first = max(0, kv_len - q_len - w + 1) // bs          # the kernel's page0
    pages = -(-kv_len // bs) - first
    counted = costs.layer_attention_bytes(cfg, costs.SLIDING, [kv_len], [q_len])
    assert counted - qo <= pages * bs * row
    assert counted - qo <= (w + q_len - 1) * row
    full = costs.layer_attention_bytes(cfg, costs.FULL, [kv_len], [q_len])
    assert full - qo == kv_len * row
    # the mean layer x depth is the sum over the kinds
    mean = costs.ragged_attention_bytes(cfg, [kv_len], [q_len])
    n = costs.layers(cfg)
    assert np.isclose(mean * cfg["num_hidden_layers"],
                      n[costs.SLIDING] * counted + n[costs.FULL] * full)
