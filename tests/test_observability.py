"""Observability layer: compile/retrace causes, cost cards, per-request
timelines, flight recorder, typed monitor surface, baseline store +
bench_diff gate — and the zero-overhead-when-disabled contract.
"""
import importlib.util
import json
import os

import sys

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.observability as obs
from paddle_tpu.core import dispatch
from paddle_tpu.framework import monitor
from paddle_tpu.observability.baseline import (BaselineStore,
                                               compare_reports)
from paddle_tpu.serving import (MLPLMEngine, RequestStatus, ServingFrontend,
                                ServingMetrics)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _obs_clean():
    """Every test starts disabled with empty recorders and leaves the
    process the same way (observability state is global)."""
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def _mlp_frontend(**kw):
    cfg = dict(vocab_size=64, hidden=16, max_batch_size=4, num_blocks=48,
               block_size=4, max_blocks_per_seq=8)
    cfg.update(kw)
    return ServingFrontend(MLPLMEngine(**cfg))


# ---------------------------------------------------------------------------
# retrace-cause attribution (ISSUE 7 satellite)
# ---------------------------------------------------------------------------

def test_dtype_retrace_cause_names_field():
    dispatch.register_op("obs_t_dtype", lambda x, y: x + y)
    obs.enable()
    af = paddle.to_tensor(np.ones((13, 11), np.float32))
    ai = paddle.to_tensor(np.ones((13, 11), np.int32))
    dispatch.apply("obs_t_dtype", [af, af])
    dispatch.apply("obs_t_dtype", [ai, ai])
    causes = [c for c in obs.retrace_causes() if c["name"] == "obs_t_dtype"]
    assert causes, obs.retrace_causes()
    assert "dtype" in causes[-1]["cause"]
    assert "int32" in causes[-1]["cause"]
    # the changed slot is named, not just "something changed"
    assert "arg0" in causes[-1]["cause"]


def test_shape_retrace_cause_names_field():
    dispatch.register_op("obs_t_shape", lambda x: x * 2.0)
    obs.enable()
    dispatch.apply("obs_t_shape", [paddle.to_tensor(np.ones((13, 11),
                                                            np.float32))])
    dispatch.apply("obs_t_shape", [paddle.to_tensor(np.ones((13, 22),
                                                            np.float32))])
    causes = [c for c in obs.retrace_causes() if c["name"] == "obs_t_shape"]
    assert causes and "shape" in causes[-1]["cause"]
    assert "(13, 11)" in causes[-1]["cause"] \
        and "(13, 22)" in causes[-1]["cause"]


def test_static_arg_retrace_cause_names_field():
    dispatch.register_op("obs_t_static", lambda x, *, k=1.0: x * k)
    obs.enable()
    t = paddle.to_tensor(np.ones((13, 11), np.float32))
    dispatch.apply("obs_t_static", [t], {"k": 2.0})
    dispatch.apply("obs_t_static", [t], {"k": 3.0})
    causes = [c for c in obs.retrace_causes()
              if c["name"] == "obs_t_static"]
    assert causes, obs.retrace_causes()
    assert "static_arg k" in causes[-1]["cause"]
    assert "2.0" in causes[-1]["cause"] and "3.0" in causes[-1]["cause"]


def test_compile_wall_time_recorded():
    dispatch.register_op("obs_t_wall", lambda x: x + 1.0)
    obs.enable()
    t = paddle.to_tensor(np.ones((7, 5), np.float32))
    dispatch.apply("obs_t_wall", [t])
    # the one record, made by JAX's own events; the dispatch layer put
    # its op, kind and structure key on it
    recs = [r for r in obs.compiles() if r.op == "obs_t_wall"]
    assert len(recs) == 1 and recs[0].kind == "fwd"
    assert recs[0].key[0] == "obs_t_wall"
    assert recs[0].backend_s > 0 and recs[0].wall_s > recs[0].backend_s
    # second call: cache hit, no new record
    dispatch.apply("obs_t_wall", [t])
    assert len([r for r in obs.compiles() if r.op == "obs_t_wall"]) == 1


def test_first_trace_is_not_a_retrace_cause():
    """The first-ever trace of each serving phase bumps the trace-time
    counter but is a compile, not a retrace — no cause may be counted."""
    ServingMetrics.reset_monitor()
    obs.enable()
    fe = _mlp_frontend()
    fe.submit([1, 2, 3], max_new_tokens=3)
    fe.run_until_idle()
    for phase in ("prefill", "decode"):
        assert monitor.get(f"serving.{phase}_retrace_causes.other") == 0
    assert not [c for c in obs.retrace_causes()
                if c["name"].startswith("serve.")]


def test_ragged_no_prompt_length_retrace_and_shape_cause_attribution():
    """Prompt length no longer retraces ANYTHING — the bucket executable
    family collapsed into one ragged program — and when the dispatch
    shape genuinely changes (a different packed-token budget), the
    retrace-cause tracing still names the changed shape."""
    obs.enable()
    fe = _mlp_frontend()
    rng = np.random.default_rng(0)
    fe.submit(rng.integers(1, 64, 3).tolist(), max_new_tokens=2)
    fe.run_until_idle()
    base = monitor.get("serving.decode_retraces")
    fe.submit(rng.integers(1, 64, 9).tolist(), max_new_tokens=2)
    fe.submit(rng.integers(1, 64, 17).tolist(), max_new_tokens=2)
    fe.run_until_idle()
    assert monitor.get("serving.decode_retraces") == base
    assert not [c for c in obs.retrace_causes()
                if c["name"].startswith("serve.")]
    # a REAL shape change — a frontend with a different chunk budget, so
    # a different packed buffer — is still attributed with a why
    fe2 = ServingFrontend(MLPLMEngine(vocab_size=64, hidden=16,
                                      max_batch_size=4, num_blocks=48,
                                      block_size=4, max_blocks_per_seq=8),
                          prefill_chunk_tokens=8)
    fe2.submit(rng.integers(1, 64, 3).tolist(), max_new_tokens=2)
    fe2.run_until_idle()
    causes = [c for c in obs.retrace_causes()
              if c["name"] == "serve.decode"]
    assert causes and "shape" in causes[-1]["cause"], obs.retrace_causes()


# ---------------------------------------------------------------------------
# zero overhead while disabled (ISSUE 7 satellite + acceptance)
# ---------------------------------------------------------------------------

def test_disabled_no_spans_no_cost_analysis_no_records():
    assert not obs.enabled()
    ca_before = monitor.get("observability.cost_analyses")
    fe = _mlp_frontend()
    rng = np.random.default_rng(0)
    hs = [fe.submit(rng.integers(1, 64, n).tolist(), max_new_tokens=3)
          for n in (3, 6, 9)]
    fe.run_until_idle()
    assert all(h.status is RequestStatus.FINISHED for h in hs)
    # no span allocation, no cost_analysis call, no signature built: the
    # compile record is JAX's own and always on, and nothing of the
    # callers' (op, structure key, cause) is on it
    assert obs.events() == []
    assert monitor.get("observability.cost_analyses") == ca_before
    assert obs.compiles(), "the record does not wait for enable()"
    assert all(r.op is None and r.key is None and r.cause is None
               for r in obs.compiles())
    assert obs.retrace_causes() == []
    assert hs[0].timeline() == []


# ---------------------------------------------------------------------------
# timelines, flight recorder, cost cards, profiler sections
# ---------------------------------------------------------------------------

def test_request_timeline_lifecycle_and_chrome_tracks(tmp_path):
    import paddle_tpu.profiler as profiler

    obs.enable()
    prof = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU])
    prof.start()
    fe = _mlp_frontend()
    rng = np.random.default_rng(1)
    hs = [fe.submit(rng.integers(1, 64, n).tolist(), max_new_tokens=4)
          for n in (3, 7)]
    fe.run_until_idle()
    prof.stop()
    names = [e["name"] for e in hs[0].timeline()]
    for needed in ("queued", "admitted", "prefill", "decode"):
        assert needed in names, names
    assert names[-1].startswith("terminal:finished")
    # decode events carry tokens-committed
    dec = [e for e in hs[0].timeline() if e["name"] == "decode"]
    assert all(e["meta"]["tokens"] == 1 for e in dec)

    p = str(tmp_path / "trace.json")
    prof.export(p)
    ev = [e for e in json.load(open(p))["traceEvents"]
          if e.get("pid") == "serving" and e.get("ph") != "M"]
    tids = {e["tid"] for e in ev}
    assert 0 in tids and len(tids) >= 3   # engine track + 2 request tracks
    assert all(e["args"]["req_id"] is not None
               for e in ev if e["tid"] != 0)
    assert all(e["ts"] >= 0 for e in ev)  # one clock base for all tracks
    # the export must not have mutated the ring's stored meta dicts
    assert all("req_id" not in e["meta"] for e in dec)
    # a later export with observability DISABLED must not leak the stale
    # serving ring into an unrelated trace
    obs.disable()
    p2 = str(tmp_path / "trace2.json")
    prof.export(p2)
    assert not [e for e in json.load(open(p2))["traceEvents"]
                if e.get("pid") == "serving"]


def test_flight_recorder_dumps_on_injected_fault(tmp_path):
    from paddle_tpu.resilience import faults

    obs.enable()
    obs.timeline.configure(flight_dir=str(tmp_path))
    fe = _mlp_frontend()
    rng = np.random.default_rng(0)
    faults.inject("serve.decode", after_n=1, times=1)
    try:
        hs = [fe.submit(rng.integers(1, 64, 4).tolist(), max_new_tokens=4)
              for _ in range(2)]
        fe.run_until_idle()
    finally:
        faults.clear()
    assert all(h.status is RequestStatus.FINISHED for h in hs)
    flights = [f for f in os.listdir(tmp_path) if f.startswith("flight_")]
    assert flights
    lines = [json.loads(ln)
             for ln in open(tmp_path / sorted(flights)[0])]
    assert lines[0]["flight_recorder"] and lines[0]["reason"].startswith(
        "step_fault")
    assert any(e.get("name") == "queued" for e in lines[1:])


def test_engine_cost_cards_cached_and_summary_sections():
    import paddle_tpu.profiler as profiler

    obs.enable()
    fe = _mlp_frontend()
    rng = np.random.default_rng(0)
    prof = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU])
    prof.start()
    fe.submit(rng.integers(1, 64, 5).tolist(), max_new_tokens=4)
    fe.run_until_idle()
    prof.stop()
    rows = {r["name"]: r for r in obs.cost_book().rows()}
    assert rows["serve.decode"]["flops_per_call"] > 0
    assert rows["serve.decode"]["calls"] >= 1
    assert rows["serve.decode"]["achieved_gflops"] is not None
    # one cost_analysis per phase card, not one per dispatch
    ca = monitor.get("observability.cost_analyses")
    fe.submit(rng.integers(1, 64, 5).tolist(), max_new_tokens=4)
    fe.run_until_idle()
    assert monitor.get("observability.cost_analyses") == ca
    s = prof.summary()
    assert "Compiles:" in s
    assert "Executable" in s and "serve.decode" in s


def test_failed_engine_card_is_tombstoned_not_retried():
    """A broken/missing cost_card_args hook must cost ONE attempt, not a
    lower().compile() try per dispatch."""
    from paddle_tpu.observability import costs

    calls = {"n": 0}

    class BrokenHook:
        def cost_card_args(self, phase):
            calls["n"] += 1
            raise RuntimeError("broken hook")

    eng = BrokenHook()
    for _ in range(5):
        assert not costs.ensure_engine_card("serve.broken", eng, "decode",
                                            ())
    assert calls["n"] == 1
    assert not costs.ensure_engine_card("serve.nohook", object(), "decode",
                                        ())


def test_cost_card_for_plain_jit():
    from paddle_tpu.observability import costs

    import jax.numpy as jnp

    card = costs.card_for_jit(lambda x, y: x @ y,
                              jnp.ones((64, 64), jnp.float32),
                              jnp.ones((64, 64), jnp.float32))
    assert card.flops and card.flops >= 2 * 64 ** 3 * 0.9
    assert card.bytes_accessed and card.argument_bytes == 2 * 64 * 64 * 4


# ---------------------------------------------------------------------------
# typed monitor surface (gauges / histograms / snapshot / prometheus)
# ---------------------------------------------------------------------------

def test_monitor_gauge_histogram_snapshot_prometheus():
    monitor.set_gauge("obs_t.depth", 7)
    monitor.inc("obs_t.events", 3)
    monitor.observe("obs_t.lat", 0.02, buckets=(0.01, 0.1, 1.0))
    monitor.observe("obs_t.lat", 0.5, buckets=(0.01, 0.1, 1.0))
    snap = monitor.snapshot("obs_t.")
    assert snap["obs_t.depth"] == 7 and snap["obs_t.events"] == 3
    assert snap["obs_t.lat_bucket_le_0.1"] == 1
    assert snap["obs_t.lat_bucket_le_1"] == 2
    assert snap["obs_t.lat_bucket_le_inf"] == 2
    assert snap["obs_t.lat_count"] == 2
    assert abs(snap["obs_t.lat_sum"] - 0.52) < 1e-9
    # scalar-only slice drops the histogram expansion
    scalars = monitor.snapshot("obs_t.", include_histograms=False)
    assert "obs_t.lat_count" not in scalars and "obs_t.depth" in scalars
    text = monitor.render_prometheus("obs_t.")
    assert "# TYPE obs_t_depth gauge" in text
    assert "# TYPE obs_t_events counter" in text
    assert '# TYPE obs_t_lat histogram' in text
    assert 'obs_t_lat_bucket{le="+Inf"} 2' in text
    # bucket bounds are frozen: re-registering with different bounds is
    # an error, never a silent sample misroute
    with pytest.raises(ValueError):
        monitor.observe("obs_t.lat", 0.1, buckets=(0.5, 5.0))
    monitor.observe("obs_t.lat", 0.1, buckets=(0.01, 0.1, 1.0))  # same: ok
    monitor.reset_prefix("obs_t.")
    assert monitor.snapshot("obs_t.")["obs_t.lat_count"] == 0


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        f"_tool_{name}", os.path.join(_REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_metrics_dump_tool_renders(capsys):
    # in-process (no subprocess spawn in tier-1): main() is the CLI body
    rc = _load_tool("metrics_dump").main(
        ["--format", "prom", "--prefix", "zed.",
         "--exec", "from paddle_tpu.framework import monitor; "
                   "monitor.inc('zed.x', 5)"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "# TYPE zed_x counter" in out and "zed_x 5" in out


# ---------------------------------------------------------------------------
# baseline store + bench_diff regression gate
# ---------------------------------------------------------------------------

def _report(platform="cpu", value=100.0, **extras):
    return {"scenario": "serving_throughput", "platform": platform,
            "metric": "serving_throughput", "value": value,
            "extras": {"ttft_p99_ms": 5.0, **extras}}


def test_baseline_platform_rules(tmp_path):
    store = BaselineStore(str(tmp_path))
    ok, _ = store.update(_report("cpu", 100.0))
    assert ok
    # same platform: last-good moves
    ok, _ = store.update(_report("cpu", 120.0))
    assert ok and store.load("serving_throughput")["value"] == 120.0
    # tpu upgrades over cpu
    ok, _ = store.update(_report("tpu", 900.0))
    assert ok and store.load("serving_throughput")["platform"] == "tpu"
    # cpu fallback can NEVER overwrite the tpu baseline
    ok, reason = store.update(_report("cpu", 5000.0))
    assert not ok and "refusing" in reason
    assert store.load("serving_throughput")["value"] == 900.0


def test_compare_reports_directions(tmp_path):
    base = _report("cpu", 100.0)
    # 4% down on higher-better: pass
    r = compare_reports(_report("cpu", 96.0), base)
    assert r["ok"] and not r["skipped"]
    # 6% down: regression
    r = compare_reports(_report("cpu", 94.0), base)
    assert not r["ok"]
    assert any(c["regression"] and c["metric"] == "value"
               for c in r["checks"])
    # lower-better metric (ttft p99) regresses when it RISES
    worse_ttft = _report("cpu", 100.0)
    worse_ttft["extras"]["ttft_p99_ms"] = 5.6
    r = compare_reports(worse_ttft, base)
    assert not r["ok"]
    assert any(c["metric"] == "extras.ttft_p99_ms" and c["regression"]
               for c in r["checks"])
    # platform mismatch is a skip, not a silent pass/fail
    r = compare_reports(_report("tpu", 10.0), base)
    assert r["skipped"] and r["ok"]


def test_bench_diff_cli_gate(tmp_path, capsys):
    bench_diff = _load_tool("bench_diff")
    store = BaselineStore(str(tmp_path / "bl"))
    assert store.update(_report("cpu", 200.0))[0]
    run_p = tmp_path / "run.json"

    def rc_for(rep, bl_dir="bl"):
        run_p.write_text(json.dumps(rep))
        rc = bench_diff.main([str(run_p), "--baseline-dir",
                              str(tmp_path / bl_dir)])
        return rc, capsys.readouterr().out

    rc, out = rc_for(_report("cpu", 200.0))
    assert rc == 0, out
    rc, out = rc_for(_report("cpu", 180.0))   # -10%: fail
    assert rc == 1, out
    assert json.loads(out)["checks"][0]["regression"]
    # missing baseline is a distinct error, not a pass
    rc, _out = rc_for(_report("cpu", 180.0), bl_dir="empty")
    assert rc == 2
    # platform mismatch: explicit skip (0), exit 3 under --strict-platform
    run_p.write_text(json.dumps(_report("tpu", 999.0)))
    assert bench_diff.main([str(run_p), "--baseline-dir",
                            str(tmp_path / "bl")]) == 0
    assert bench_diff.main([str(run_p), "--baseline-dir",
                            str(tmp_path / "bl"),
                            "--strict-platform"]) == 3


def test_bench_baseline_is_last_good_not_last_run(tmp_path, monkeypatch,
                                                  capsys):
    """bench must not store a regressed run as the new baseline — that
    would let `bench.py && bench_diff.py` compare a run against itself."""
    monkeypatch.setenv("BENCH_BASELINE_DIR", str(tmp_path))
    spec = importlib.util.spec_from_file_location(
        "_bench2", os.path.join(_REPO, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    store = BaselineStore(str(tmp_path))
    assert store.update(_report("cpu", 200.0))[0]
    bench._emit_report(_report("cpu", 150.0), "serving_throughput")
    capsys.readouterr()
    assert store.load("serving_throughput")["value"] == 200.0  # kept
    # sub-gate (-2.5%) regressions must not compound into a downward
    # ratchet: anything worse than the baseline keeps it
    bench._emit_report(_report("cpu", 195.0), "serving_throughput")
    capsys.readouterr()
    assert store.load("serving_throughput")["value"] == 200.0  # kept
    bench._emit_report(_report("cpu", 210.0), "serving_throughput")
    capsys.readouterr()
    assert store.load("serving_throughput")["value"] == 210.0  # moved


def test_bench_scenario_registry():
    """The registry owns every scenario with a budget; the dispatcher
    resolves back-compat spellings."""
    spec = importlib.util.spec_from_file_location(
        "_bench", os.path.join(_REPO, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    assert set(bench.SCENARIOS) >= {"train_mfu", "serving_throughput",
                                    "serving_spec"}
    for name in bench.SCENARIOS:
        assert bench._scenario_budget_s(name) > 0
