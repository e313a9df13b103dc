"""The cell PR 45 added, end to end on the CPU at the tiny preset, through
the same runner, generator, check and per-layer readers as a chip run; the
broken path of the new runner; and the new costs' arithmetic. (The earlier
cells' tables: `test_rehearsal.py`, `test_new_cells.py`,
`test_new_cells_pr38.py`.)"""
import json
import os

import pytest

from conftest import ROOT, load

CELL = "brumby14b-longgen-decode"
REPORTS = {"serve_tok_s", "setup_s"}


def test_cell_walks_through(rehearse):
    result, out = rehearse(CELL, "--trace", "0", seconds=3)
    assert result["correct"] is True, out
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == REPORTS
    last = out.strip().splitlines()[-1]
    assert set(json.loads(last[len("REHEARSAL "):])["would_report"]) == REPORTS
    assert "compare served_token_mean_gap" in out
    assert "compare moved_state.restarts: 0" in out


def test_traced_walk_reads_the_counters(rehearse):
    result, out = rehearse(CELL, "--trace", "1", seconds=3)
    assert result["correct"] is True, out
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    m = result["metrics"]
    assert m, "no per-layer metric was read"
    for name in m:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["source"] != "device_trace"
    # the state group's slots are the cell's "blocks": 4 lanes and the guard
    assert m["kv_blocks_peak_share.brumby"]["value"] == 100.0
    assert m["decode_lanes_mean.brumby"]["value"] > 0
    assert "lanes started from zero" in out


def test_every_new_entry_has_its_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    run = load("run.py", "benchmark_run_pr45")
    every = {w["name"] for w in bench["workloads"]}
    moved = {e["name"]: set(e.get("workloads", every))
             for e in bench["end_to_end"]}
    mine = [m for m in bench["per_layer"] if CELL in m["workloads"]]
    assert len(mine) == 22 and all(m["name"].endswith(".brumby") for m in mine)
    for m in mine:
        assert os.path.exists(run._reader(m["name"]))
        assert set(m["workloads"]) <= moved[m["moves"]], m["name"]
    (w,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(w["why"]) <= 200 and w["chips"] == 1
    for mix in (w["traffic"], "rehearsal-" + w["traffic"]):
        assert os.path.exists(os.path.join(ROOT, "benchmark", "traffic",
                                           mix + ".json"))


def test_the_configuration_keeps_the_published_keys():
    """Every number of the catalog's `config` under the same key; `reduced`
    is depth alone and names no width."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "brumby-14b-base-serve.json")) as f:
        cfg = json.load(f)
    published = dict(
        attention_bias=False, head_dim=128, hidden_act="silu",
        hidden_size=5120, intermediate_size=17408,
        max_position_embeddings=32768, max_window_layers=40,
        model_type="brumby", num_attention_heads=40, num_hidden_layers=40,
        num_key_value_heads=8, rms_norm_eps=1e-06, rope_scaling=None,
        rope_theta=1000000, sliding_window=None, tie_word_embeddings=False,
        use_sliding_window=False, vocab_size=151936)
    differs = {k for k, v in published.items() if cfg.get(k, "absent") != v}
    assert differs == set(cfg["reduced"]) == {"num_hidden_layers"}
    assert cfg["reduced"]["num_hidden_layers"]["run"] \
        == cfg["num_hidden_layers"] >= 4
    for key in ("retention_degree", "gate", "qk_norm_and_rope",
                "retention_scale", "retention_eps", "state_dtype",
                "feature_axis", "gate_bias"):
        assert key in cfg["assumed"], key
    dep = cfg["deployment"]
    assert dep["state_slots"] == dep["lanes"] + 1
    assert dep["feature_dim_run"] == 65 * 128 >= 128 * 129 // 2


def test_a_token_altered_in_the_tail_is_not_correct(bench_run, preset_bench,
                                                    capsys, monkeypatch):
    """The timed path itself, broken: the sampler's token is replaced where
    the step's tail picks it. The comparison has to see it."""
    from paddle_tpu.ops import sampling

    real = sampling.step_tail

    def tail(logits, lanes, temperature):
        out = real(logits, lanes, temperature)
        return out.at[0].set((out[0] + 1) % logits.shape[-1])

    monkeypatch.setattr(sampling, "step_tail", tail)
    result = bench_run.main([
        "--rehearse", "--bench-file", preset_bench("tiny"), "--workload",
        CELL, "--seed", "3000000045", "--seconds", "3"])
    out = capsys.readouterr().out
    assert result["correct"] is False, out
    assert result["failed"] == 0, "the comparison fails, not the run"


def test_a_state_that_forgets_is_not_correct(bench_run, preset_bench, capsys,
                                             monkeypatch):
    """The new mechanism itself, broken: the update kernel's twin drops the
    carried state (every token starts afresh). Tokens are still sampled and
    nothing fails; the comparison has to see it."""
    from paddle_tpu.ops.pallas import power_retention as pr

    real = pr.power_retention_update_ref

    def forgetful(*a, fresh, **kw):
        return real(*a, fresh=fresh | True, **kw)

    monkeypatch.setattr(pr, "power_retention_update_ref", forgetful)
    result = bench_run.main([
        "--rehearse", "--bench-file", preset_bench("tiny"), "--workload",
        CELL, "--seed", "3000000045", "--seconds", "3"])
    out = capsys.readouterr().out
    assert result["correct"] is False, out
    assert result["failed"] == 0


@pytest.mark.parametrize("control,correct", [(None, True),
                                             ("ref-int8", False)])
def test_control_on_the_new_configuration(bench_run, preset_bench, capsys,
                                          control, correct):
    """The control at the small preset (hidden 256, head size 32, vocabulary
    4,096; its limit set from CPU readings as the cell's from chip readings):
    a sound run is correct, the reference on an int8 grid in the program's
    place is not."""
    extra = ("--control", control) if control else ()
    result = bench_run.main([
        "--rehearse", "--bench-file", preset_bench("small"), "--workload",
        CELL, "--seed", "3000000029", "--seconds", "3", *extra])
    out = capsys.readouterr().out
    assert result["correct"] is correct, out
    assert result["failed"] == 0, "the control fails the comparison, not the run"


@pytest.mark.parametrize("lanes", [0, 1, 32])
def test_costs_count_a_state_and_never_a_context(lanes):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "brumby-14b-base-serve.json")) as f:
        cfg = json.load(f)
    costs = load("costs_brumby.py", "costs_brumby_test")
    per_seq_layer = 8 * 8320 * 129 * 4
    assert costs.state_bytes(cfg) == per_seq_layer
    assert costs.features(cfg) == 8256
    assert costs.retention_update_bytes(cfg, lanes) \
        == lanes * 6 * 2 * per_seq_layer
    short = costs.ragged_attention_bytes(cfg, [10] * lanes, [1] * lanes)
    long = costs.ragged_attention_bytes(cfg, [30000] * lanes, [1] * lanes)
    assert short == long == lanes * 2 * per_seq_layer
    assert costs.traced["update_bytes"] == 2 * lanes * 6 * 2 * per_seq_layer
    # a token's FLOPs: the ISSUE's 101 MFLOP a layer, beside 660 of matmuls
    assert 100e6 < costs.retention_token_flops(cfg) < 104e6
    assert 655e6 < 2 * costs.matmul_params(cfg) / 6 < 665e6
    assert costs.serve_flops(cfg, 32, 32, 0) == costs.serve_flops(
        cfg, 32, 32, 10 ** 9)
    one = costs.retention_chunk_flops(cfg, [128])
    assert one > 128 * costs.retention_token_flops(cfg)
    assert costs.retention_chunk_bytes(cfg, [128, 5]) > 4 * per_seq_layer
