"""The two cells PR 47 added, end to end on the CPU at the tiny preset,
through the same runners, generator, checks and per-layer readers as a chip
run; the broken paths of the new runner (a token altered in the tail, a
selection that is not the carried one); the control at the small preset; the
new configuration's published keys; and the new costs' arithmetic. (The
earlier cells' tables: `test_rehearsal.py`, `test_new_cells.py`,
`test_new_cells_pr38.py`, `test_new_cells_pr45.py`.)"""
import json
import os

import pytest

from conftest import ROOT, load

GLM, DOCQA = "glm52-longctx-decode", "brumby14b-docqa-open"
REPORTS = {GLM: {"serve_tok_s", "setup_s"}, DOCQA: {"gap_p95_ms", "setup_s"}}
ENTRIES = {GLM: (25, ".glm52"), DOCQA: (10, ".brumbyqa")}


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _glm_config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "glm-5.2-serve.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", [GLM, DOCQA])
def test_cell_walks_through(rehearse, cell):
    result, out = rehearse(cell, "--trace", "0", seconds=3)
    assert result["correct"] is True, out
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == REPORTS[cell]
    last = out.strip().splitlines()[-1]
    assert set(json.loads(last[len("REHEARSAL "):])["would_report"]) \
        == REPORTS[cell]
    assert "compare served_token_mean_gap" in out
    if cell == GLM:
        assert "compare decode_selection_miss_worst" in out
        assert "compare decode_attention_error_worst" in out
        assert "decode_witness_missing" not in out


@pytest.mark.parametrize("cell", [GLM, DOCQA])
def test_traced_walk_reads_the_counters(rehearse, cell):
    result, out = rehearse(cell, "--trace", "1", seconds=3)
    assert result["correct"] is True, out
    per_layer = {m["name"]: m for m in _bench()["per_layer"]}
    m = result["metrics"]
    assert m, "no per-layer metric was read"
    for name in m:
        assert per_layer[name]["workloads"] == [cell]
        assert per_layer[name]["source"] != "device_trace"
    if cell == GLM:
        # contexts 84-240 against the preset's index_topk of 16: the
        # selections hold a small share of what they were picked from
        assert 5 < m["dsa_selected_share.glm52"]["value"] < 40
        # experts 2-5 of a router of eight: half the assignments, about
        assert 30 < m["moe_held_assignment_share.glm52"]["value"] < 70
        assert m["decode_lanes_mean.glm52"]["value"] > 0
    else:
        assert m["prefill_token_share.brumbyqa"]["value"] > 50


@pytest.mark.parametrize("cell", [GLM, DOCQA])
def test_every_new_entry_has_its_files(cell):
    bench = _bench()
    run = load("run.py", "benchmark_run_pr47")
    every = {w["name"] for w in bench["workloads"]}
    moved = {e["name"]: set(e.get("workloads", every))
             for e in bench["end_to_end"]}
    count, suffix = ENTRIES[cell]
    mine = [m for m in bench["per_layer"] if cell in m["workloads"]]
    assert len(mine) == count and all(m["name"].endswith(suffix)
                                      for m in mine)
    assert len(bench["per_layer"]) == 89 + 35 <= 128
    for m in mine:
        assert os.path.exists(run._reader(m["name"]))
        assert set(m["workloads"]) <= moved[m["moves"]], m["name"]
    (w,) = [w for w in bench["workloads"] if w["name"] == cell]
    assert len(w["why"]) <= 200 and w["chips"] == 1
    for mix in (w["traffic"], "rehearsal-" + w["traffic"]):
        assert os.path.exists(os.path.join(ROOT, "benchmark", "traffic",
                                           mix + ".json"))


def test_the_open_cell_runs_at_four_fifths_of_its_recorded_knee():
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "brumby-docqa-open.json")) as f:
        mix = json.load(f)
    knee = mix["knee"]
    rates = [row["rate_per_s"] for row in knee["sweep"]]
    assert knee["knee_rate_per_s"] in rates and len(rates) >= 6
    assert mix["arrivals"]["rate_per_s"] == pytest.approx(
        0.8 * knee["knee_rate_per_s"])
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "docqa-open.json")) as f:
        kanana = json.load(f)
    assert (mix["prompt_len"], mix["output_len"], mix["drain_limit_s"]) == (
        kanana["prompt_len"], kanana["output_len"], kanana["drain_limit_s"])


def test_the_configuration_keeps_the_published_keys():
    """Every number of the catalog's `config` under the same key; `reduced`
    names depth, the leading dense layers, the experts held, the vocabulary
    and the MTP module, and no width; the two published lists are whole and
    `layers_run` reads the run's kinds off them."""
    cfg = _glm_config()
    published = dict(
        attention_bias=False, ep_size=1, first_k_dense_replace=3,
        head_dim=192, hidden_act="silu", hidden_size=6144,
        index_head_dim=128, index_n_heads=32,
        index_share_for_mtp_iteration=True, index_skip_topk_offset=3,
        index_topk=2048, index_topk_freq=4, index_topk_pattern=None,
        indexer_rope_interleave=True, intermediate_size=12288,
        kv_lora_rank=512, max_position_embeddings=1048576,
        model_type="glm_moe_dsa", moe_intermediate_size=2048,
        moe_layer_freq=1, n_group=1, n_routed_experts=256,
        n_shared_experts=1, norm_topk_prob=True, num_attention_heads=64,
        num_experts_per_tok=8, num_hidden_layers=78, num_key_value_heads=64,
        num_nextn_predict_layers=1, q_lora_rank=2048, qk_head_dim=256,
        qk_nope_head_dim=192, qk_rope_head_dim=64, rms_norm_eps=1e-05,
        rope_interleave=True, routed_scaling_factor=2.5,
        scoring_func="sigmoid", tie_word_embeddings=False, topk_group=1,
        topk_method="noaux_tc", v_head_dim=256, vocab_size=154880,
        rope_parameters={"rope_theta": 8000000, "rope_type": "default"})
    differs = {k for k, v in published.items() if cfg.get(k, "absent") != v}
    assert differs == set(cfg["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"}
    (entry,) = [c for c in _bench()["configs"] if c["name"] == cfg["name"]]
    assert set(entry["reduced"]) == differs
    period = ["shared", "shared", "shared", "full"]
    assert cfg["indexer_types"] == ["full"] * 3 + period * 18 + period[:3]
    assert cfg["mlp_layer_types"] == ["dense"] * 3 + ["sparse"] * 75
    run = cfg["layers_run"]
    assert len(run) == cfg["num_hidden_layers"] == 5
    assert [cfg["indexer_types"][i] for i in run] == ["full"] + period
    assert [cfg["mlp_layer_types"][i] for i in run] == ["dense"] \
        + ["sparse"] * 4
    assert cfg["reduced"]["n_routed_experts"]["held"] == [0, 16]
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    for key in ("indexer_equations", "indexer_hadamard_and_fp8",
                "indexer_rope", "indexer_k_norm", "topk_ties",
                "shared_layers"):
        assert key in cfg["assumed"], key
    dep = cfg["deployment"]
    assert dep["chips_sharing_a_layer"] * 16 == published["n_routed_experts"]
    assert dep["blocks"] * dep["block_size"] > 32 * 6144 + 466725


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
        GLM, "--seed", "3000000047", "--seconds", "3"])
    out = capsys.readouterr().out
    assert result["correct"] is False, out
    assert result["failed"] == 0, "the comparison fails, not the run"


def test_a_selection_that_is_not_carried_is_not_correct(
        bench_run, preset_bench, capsys, monkeypatch):
    """The new mechanism itself, broken as ISSUE 47's third control breaks it
    on the chip: a `shared` layer attends the most recent `index_topk`
    positions in place of the selection it was handed. Tokens are still
    sampled and nothing fails; the comparison has to see it on the window's
    DECODE rows, and both of the replayed rows' readings name it: the
    selection attention was given, and what attention made of it."""
    import jax.numpy as jnp

    from paddle_tpu.inference import glm_moe_dsa_runner as gr
    from paddle_tpu.models import glm_moe_dsa as glm

    seen = {}
    real_meta, real_layer = gr.ragged_metadata, glm.decoder_layer

    def meta(q, kv, t):
        lane, pos = real_meta(q, kv, t)
        seen["pos"] = pos
        return lane, pos

    def recent(x, p, cfg, kind, cos, sin, index, attend, carried, live,
               rowwise=glm.dsv3.whole):
        if kind == glm.SHARED:
            idx, n = carried
            last = seen["pos"][:, None] - jnp.arange(
                idx.shape[1], dtype=jnp.int32)[None, :]
            carried = (jnp.maximum(last, 0).astype(jnp.int32), n)
        return real_layer(x, p, cfg, kind, cos, sin, index, attend, carried,
                          live, rowwise)

    monkeypatch.setattr(gr, "ragged_metadata", meta)
    monkeypatch.setattr(glm, "decoder_layer", recent)
    result = bench_run.main([
        "--rehearse", "--bench-file", preset_bench("tiny"), "--workload",
        GLM, "--seed", "3000000047", "--seconds", "3"])
    out = capsys.readouterr().out
    assert result["correct"] is False, out
    assert result["failed"] == 0
    for name in ("decode_selection_miss_worst",
                 "decode_attention_error_worst"):
        line = [ln for ln in out.splitlines()
                if ln.startswith("compare " + name)]
        assert line and line[0].endswith("NOT OK"), out


@pytest.mark.parametrize("control,correct", [(None, True),
                                             ("ref-int8", False)])
def test_control_on_the_new_configuration(bench_run, preset_bench, capsys,
                                          control, correct):
    """The control at the small preset (hidden 256, 8 heads, index_topk 64,
    vocabulary 4,096; its limit set from CPU readings as the cell's from
    chip readings): a sound run is correct, the reference on an int8 grid in
    the program's place is not."""
    extra = ("--control", control) if control else ()
    result = bench_run.main([
        "--rehearse", "--bench-file", preset_bench("small"), "--workload",
        GLM, "--seed", "3000000029", "--seconds", "3", *extra])
    out = capsys.readouterr().out
    assert result["correct"] is correct, out
    assert result["failed"] == 0, "the control fails the comparison, not the run"


def test_costs_count_a_selection_and_an_index_over_the_context():
    cfg = _glm_config()
    costs = load("costs_glm_moe_dsa.py", "costs_glm_moe_dsa_test")
    assert costs.full_layers(cfg) == 2 and costs.held_share(cfg) == 1 / 16
    assert costs.latent_row_bytes(cfg) == 1152
    # a decode lane's attention: 2,048 rows whatever the context
    near = costs.sparse_attn_bytes(cfg, [4096], [1])
    far = costs.sparse_attn_bytes(cfg, [50000], [1])
    assert near == far == 2048 * 1152 + 64 * (2 * 512 + 64) * 2
    assert costs.sparse_attn_bytes(cfg, [1000], [1]) < near
    assert costs.sparse_attn_flops(cfg, [50000], [1]) \
        == 2048 * 2.0 * 64 * (2 * 512 + 64)
    # a chunk's rows each hold their own set
    assert costs.selected(cfg, 2100, 100) == sum(
        min(2048, t + 1) for t in range(2000, 2100))
    # the index scores follow the context: 256 B of keys a position
    assert costs.index_score_bytes(cfg, [50000], [1]) \
        - costs.index_score_bytes(cfg, [4096], [1]) \
        == (50000 - 4096) * (256 + 4)
    before = dict(costs.traced)
    mean = costs.ragged_attention_bytes(cfg, [20000] * 32, [1] * 32)
    assert mean * 5 == pytest.approx(
        5 * 32 * near + 2 * costs.index_score_bytes(cfg, [20000] * 32,
                                                    [1] * 32))
    assert costs.traced["sparse_bytes"] - before["sparse_bytes"] \
        == 5 * 32 * near
    assert costs.traced["index_bytes"] > before["index_bytes"]
    # my count of the parameters a token meets on this chip (ISSUE 47):
    # attention 165.0 M x 5, two indexers, the dense SwiGLU, and in four
    # layers the router, the shared expert and half a routed expert: 1,303 M
    active = costs.active_params(cfg)
    assert 1.29e9 < active < 1.32e9
    assert costs.serve_flops(cfg, 32, 32, 0) < costs.serve_flops(
        cfg, 32, 32, 32 * 20000)
    assert costs.expert_bytes(cfg, 10, 16) > 10 * 3 * 6144 * 2048 * 2
