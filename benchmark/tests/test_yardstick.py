"""The yardstick's own arithmetic: traffic that is the same work in another
order under every seed, the peaks table, FLOPs and bytes."""
import json
import os
import sys

import numpy as np
import pytest

from conftest import BENCH, load

costs = load("costs.py", "benchmark_costs_t")
stream = load("generators/request_stream.py", "benchmark_stream_t")


def _mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_every_seed_offers_the_same_work_in_another_order():
    mix = _mix("chat-open")
    a = stream.make(mix, 1, 51, 32768)
    b = stream.make(mix, 2**31 + 7, 51, 32768)
    shape = lambda p: [(r["due"], len(r["prompt"]), r["max_new_tokens"])  # noqa: E731
                       for r in p["requests"]]
    assert len(a["requests"]) == round(mix["arrivals"]["rate_per_s"] * 51)
    assert stream.make(mix, 1, 51, 32768)["requests"] == a["requests"]
    # the same prompt lengths, answer lengths and gaps between arrivals ...
    for col in (1, 2):
        assert sorted(s[col] for s in shape(a)) == sorted(s[col] for s in shape(b))
    gaps = lambda p: {round(g, 9) for g in np.diff([r["due"] for r in p["requests"]])}  # noqa: E731
    assert len(gaps(a) ^ gaps(b)) <= 4     # all but what follows each first arrival
    assert a["requests"][-1]["due"] == pytest.approx(b["requests"][-1]["due"])
    # ... in another order, with other token ids
    assert shape(a) != shape(b)
    assert a["requests"][0]["prompt"] != b["requests"][0]["prompt"]
    lens = sorted(len(r["prompt"]) for r in a["requests"])
    assert mix["prompt_len"]["min"] <= lens[0] and lens[-1] <= mix["prompt_len"]["max"]
    assert all(0 <= r["due"] < 51 for r in a["requests"])


def test_backlog_never_drains():
    plan = stream.make(_mix("backlog-decode"), 3, 51, 32768)
    assert plan["mode"] == "backlog" and plan["keep_queued"] == 32
    # 32 lanes x 51 s cannot finish this many 96-token answers at any
    # speed the chip's bytes allow (about 4,500 tokens/s)
    assert sum(r["max_new_tokens"] for r in plan["requests"]) > 51 * 5000


def test_train_flops_a_token():
    cfg = _config("yi-6b-train")
    assert costs.matmul_params(cfg) == pytest.approx(954.2e6, rel=1e-3)
    assert costs.train_flops_per_token(cfg, 4096) == pytest.approx(6.53e9, rel=2e-3)


def test_attention_bytes():
    cfg = _config("mistral-7b-v0.3-serve")
    # one lane, 100 tokens of context, one query row: K and V at 4 KB a
    # token (2 x 8 heads x 128 x 2 B), q and o at 8 KB a row each
    assert costs.ragged_attention_bytes(cfg, [100, 0], [1, 0]) == 100 * 4096 + 16384


def test_unknown_device_is_an_error():
    assert costs.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        costs.peaks("TPU v9")


def test_serve_flops():
    """By hand from the published widths. Mistral, depth 12: a layer
    multiplies 2 x 4096^2 + 2 x 4096 x 1024 + 3 x 4096 x 14336 parameters
    (+ two norms), the head 32768 x 4096; one decoded row over a context
    of 100 tokens. Kanana, depth 7: attention 26,345,472 a layer, the dense
    layer 3 x 2048 x 6144, an expert layer the router and 6 + 2 experts of
    3 x 2048 x 768; a pair 2 x 32 heads x (2 x 512 + 64) a layer."""
    cfg = _config("mistral-7b-v0.3-serve")
    body = 12 * (33_554_432 + 8_388_608 + 176_160_768 + 8_192) + 4_096
    assert costs.serve_flops(cfg, 1, 1, 100) == \
        2 * (body + 134_217_728) + 4 * 12 * 4096 * 100
    # a prompt of 64 rows prefilled: one sampled row, 64 x 65 / 2 pairs
    assert costs.serve_flops(cfg, 64, 1, 2080) == \
        2 * (64 * body + 134_217_728) + 4 * 12 * 4096 * 2080
    sys.modules.setdefault("costs", costs)       # as run.py has imported it
    mla = load("costs_deepseek_v3.py", "benchmark_costs_dsv3_t")
    cfg = _config("kanana-2-30b-a3b-serve")
    assert mla.active_params(cfg) == 7 * 26_345_472 + 37_748_736 + 6 * 38_010_880
    assert mla.serve_flops(cfg, 1, 1, 8000) == \
        2 * (450_232_320 + 262_668_288) + 7 * 69_632 * 8000


def test_step_mfu_reads_flops_over_window_and_peak():
    read = load("layer_metrics/step_mfu.py", "benchmark_step_mfu_t").read
    rec = {"served_flops": 1.97e14, "window_s": 10.0,
           "peaks": {"bf16_flops_per_s": 1.97e14}}
    assert read(rec) == pytest.approx(10.0)
    assert read(dict(rec, peaks=None)) is None       # a rehearsal: no device
    assert read(dict(rec, served_flops=0.0)) is None
