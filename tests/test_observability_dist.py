"""Distributed & memory observability (ISSUE 9): collective tracing on
the 8-device CPU mesh, disabled-path zero overhead, overlap accounting,
HLO comm census, comm-watchdog forensics, KV fragmentation + guard-aware
utilization, OOM flight dumps, mesh-aware aggregation + straggler
attribution, CostCard memory fields.
"""
import json
import os

import numpy as np
import pytest

import paddle_tpu.distributed as dist
import paddle_tpu.observability as obs
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.framework import monitor
from paddle_tpu.inference.cache import (BlockCacheManager, KVCacheExhausted)
from paddle_tpu.observability import comms, memory


@pytest.fixture(autouse=True)
def _obs_clean():
    """Every test starts disabled with empty recorders/counters and
    leaves the process the same way (observability state is global)."""
    obs.disable()
    obs.reset()
    monitor.reset_prefix("comm.")
    monitor.reset_prefix("mesh.")
    memory.configure(min_dump_interval_s=0.0)
    yield
    obs.disable()
    obs.reset()
    monitor.reset_prefix("comm.")
    monitor.reset_prefix("mesh.")
    comms.configure(flight_dir="profiler_log")
    memory.configure(flight_dir="profiler_log", min_dump_interval_s=30.0)


# ---------------------------------------------------------------------------
# collective tracing on the 8-device mesh
# ---------------------------------------------------------------------------

def test_all_reduce_trace_records_kind_bytes_group(rng):
    obs.enable()
    t = Tensor(np.ones((8, 16), np.float32))
    dist.scatter(t)                       # stack over the 8-device group
    comms.reset()                         # trace the all_reduce alone
    monitor.reset_prefix("comm.")
    dist.all_reduce(t)
    recs = comms.records()
    assert len(recs) == 1
    r = recs[0]
    assert r.kind == "all_reduce"
    assert r.nranks == 8
    assert r.group == 0
    # per-rank payload of the [8, 16] f32 stack
    assert r.nbytes == 16 * 4
    assert r.wall_s > 0
    snap = monitor.snapshot("comm.", include_histograms=False)
    assert snap["comm.all_reduce.calls"] == 1
    assert snap["comm.all_reduce.bytes"] == 64
    assert snap["comm.all_reduce.wall_ms"] > 0
    assert "comm.all_reduce.algbw_gbs" in snap
    # algbw follows the bytes*(n-1)/n / wall convention
    assert r.algbw_gbs == pytest.approx(
        64 * 7 / 8 / r.wall_s / 1e9, rel=1e-3)


def test_every_collective_kind_traced(rng):
    obs.enable()
    g = 8
    t = Tensor(np.ones((g, 4), np.float32))
    dist.scatter(t)
    dist.all_reduce(t)
    dist.all_gather(None, t)
    dist.broadcast(t, src=0)
    dist.reduce(t, dst=0)
    lst = [Tensor(np.full((2,), float(i), np.float32)) for i in range(g)]
    out = Tensor(np.zeros((g, 2), np.float32))
    dist.reduce_scatter(out, lst)
    dist.alltoall(None, lst)
    from paddle_tpu.distributed.communication.collective import (barrier,
                                                                 p2p_shift,
                                                                 recv, send)

    p2p_shift(t, 1)
    send(t, dst=1)
    r2 = Tensor(np.zeros_like(t._data))
    recv(r2, src=0)
    barrier()
    snap = monitor.snapshot("comm.", include_histograms=False)
    for kind in ("scatter", "all_reduce", "all_gather", "broadcast",
                 "reduce", "reduce_scatter", "alltoall", "ppermute",
                 "send_recv", "barrier"):
        assert snap.get(f"comm.{kind}.calls", 0) >= 1, (kind, snap)
        if kind != "barrier":
            assert snap.get(f"comm.{kind}.bytes", 0) > 0, (kind, snap)


def test_disabled_path_records_nothing(rng):
    assert not obs.enabled()
    t = Tensor(np.ones((8, 4), np.float32))
    dist.scatter(t)
    dist.all_reduce(t)
    dist.all_gather(None, t)
    assert comms.records() == []
    assert comms.totals() == {}
    # counter KEYS may linger from other tests (registration is sticky);
    # none may have moved
    assert all(v == 0 for v in monitor.snapshot(
        "comm.", include_histograms=False).values())


# ---------------------------------------------------------------------------
# overlap accounting
# ---------------------------------------------------------------------------

def test_overlap_report_arithmetic():
    r = comms.overlap_report(0.010, 0.0025)
    assert r["step_ms"] == 10.0
    assert r["exposed_ms"] == 2.5
    assert r["comm_exposed_fraction"] == pytest.approx(0.25)
    assert r["overlap_efficiency"] == pytest.approx(0.75)
    # comm wall clamps at the step wall (overlapped async comm can
    # exceed it; exposure cannot)
    r = comms.overlap_report(0.010, 0.040)
    assert r["exposed_ms"] == 10.0
    assert r["overlap_efficiency"] == 0.0
    # degenerate zero-length step
    r = comms.overlap_report(0.0, 0.0)
    assert r["comm_exposed_fraction"] == 0.0
    # ideal compute time from FLOPs + peak
    r = comms.overlap_report(0.010, 0.001, flops=4e9, peak_flops=1e12)
    assert r["ideal_compute_ms"] == 4.0
    assert r["compute_fraction_ideal"] == pytest.approx(0.4)
    # gauges published for the bench gate
    snap = monitor.snapshot("comm.", include_histograms=False)
    assert snap["comm.exposed_ms_per_step"] == 1.0
    assert snap["comm.overlap_efficiency"] == 0.9


def test_step_overlap_window_counts_only_inner_comm(rng):
    obs.enable()
    t = Tensor(np.ones((8, 8), np.float32))
    dist.scatter(t)
    dist.all_reduce(t)          # outside the window
    with comms.step_overlap("probe_step") as box:
        dist.all_reduce(t)      # inside
    assert box["label"] == "probe_step"
    assert box["comm_calls"] == 1
    assert box["comm_ms"] > 0
    assert box["step_ms"] >= box["exposed_ms"] > 0


# ---------------------------------------------------------------------------
# HLO comm census (compiled-program comm volume)
# ---------------------------------------------------------------------------

def test_hlo_comm_census_synthetic():
    hlo = "\n".join([
        "%ar.1 = f32[8,64]{1,0} all-reduce(%x), replica_groups={}",
        "%ag = bf16[16,32]{1,0} all-gather(%y), dimensions={0}",
        "%cp = f32[4]{0} collective-permute(%z)",
        "%ars = f32[2,2]{1,0} all-reduce-start(%w)",
        "%ard = f32[2,2]{1,0} all-reduce-done(%ars)",
        # async tuple form: (operand, destination) — only the
        # destination payload may count, or the async compilation of the
        # same collective reports ~2x its synchronous form
        "%ags = (f32[4]{0}, f32[32]{0}) all-gather-start(%v)",
        "%agd = f32[32]{0} all-gather-done(%ags)",
        "%add = f32[8,64]{1,0} add(%a, %b)",
    ])
    c = comms.hlo_comm_census(hlo)
    assert c["all_reduce"]["ops"] == 2          # start counted, done not
    assert c["all_reduce"]["bytes"] == 8 * 64 * 4 + 2 * 2 * 4
    assert c["all_gather"] == {"ops": 2,
                               "bytes": 16 * 32 * 2 + 32 * 4}
    assert c["ppermute"] == {"ops": 1, "bytes": 16}
    assert "add" not in str(c)


def test_hlo_comm_census_real_psum():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:8]), ("g",))
    fn = jax.shard_map(lambda x: jax.lax.psum(x, "g"), mesh=mesh,
                   in_specs=P("g"), out_specs=P())
    compiled = jax.jit(fn).lower(jnp.ones((8, 32), jnp.float32)).compile()
    census = comms.hlo_comm_census(compiled.as_text())
    assert census.get("all_reduce", {}).get("ops", 0) >= 1, census
    assert census["all_reduce"]["bytes"] > 0


# ---------------------------------------------------------------------------
# comm watchdog forensics (satellite)
# ---------------------------------------------------------------------------

def test_watchdog_trip_zero_sleep(tmp_path):
    from paddle_tpu.distributed.communication.watchdog import CommWatchdog

    comms.configure(flight_dir=str(tmp_path))
    now = [1000.0]
    trips0 = monitor.get("comm.watchdog_trips")
    wd = CommWatchdog("all_reduce", timeout=5.0, action="log",
                      meta={"bytes": 4096, "group": 3},
                      clock=lambda: now[0],
                      wait=lambda _t: False)       # "timed out" instantly
    wd.started_at = now[0]
    now[0] += 7.0
    wd._watch()                                    # synchronous, no thread
    assert monitor.get("comm.watchdog_trips") == trips0 + 1
    dumps = [f for f in os.listdir(tmp_path)
             if f.startswith("flight_comm_watchdog_all_reduce")]
    assert dumps, os.listdir(tmp_path)
    header = json.loads(open(tmp_path / dumps[0]).readline())
    assert header["reason"] == "comm_watchdog_all_reduce"
    col = header["collective"]
    assert col["kind"] == "all_reduce"
    assert col["bytes"] == 4096 and col["group"] == 3
    assert col["elapsed_s"] == 7.0 and col["timeout_s"] == 5.0


def test_watchdog_no_trip_when_finished():
    from paddle_tpu.distributed.communication.watchdog import CommWatchdog

    trips0 = monitor.get("comm.watchdog_trips")
    wd = CommWatchdog("barrier", timeout=5.0, action="log",
                      wait=lambda _t: True)        # finished in time
    wd.started_at = 0.0
    wd._watch()
    assert monitor.get("comm.watchdog_trips") == trips0


# ---------------------------------------------------------------------------
# KV utilization / fragmentation (satellite)
# ---------------------------------------------------------------------------

def test_utilization_excludes_guard_blocks():
    mgr = BlockCacheManager(num_blocks=8, block_size=4,
                            max_blocks_per_seq=8)
    mgr.allocate(-1, 1)                   # guard lease (scheduler pad)
    assert mgr.utilization() == 0.0       # guard is overhead, not load
    mgr.allocate(1, 8)                    # 2 blocks of the 7 usable
    assert mgr.utilization() == pytest.approx(2 / 7)
    mgr.free(1)
    assert mgr.utilization() == 0.0


def test_fragmentation_breakdown():
    mgr = BlockCacheManager(num_blocks=16, block_size=4,
                            max_blocks_per_seq=8)
    mgr.allocate(-1, 1)
    mgr.allocate(1, 10)                   # 3 blocks, 10 tokens
    mgr.allocate(2, 4)                    # 1 block
    mgr.allocate(3, 5)                    # 2 blocks
    mgr.free(2)                           # hole between seq 1 and seq 3
    f = mgr.fragmentation()
    assert f["guard_blocks"] == 1
    assert f["leased_blocks"] == 5
    assert f["per_seq"][1] == {"leased_blocks": 3, "used_blocks": 3,
                               "tokens": 10}
    assert f["per_seq"][3]["leased_blocks"] == 2
    assert -1 not in f["per_seq"]
    # ids 7..15 free at the top + seq 2's returned block 4 -> largest
    # contiguous run 9 of 10 free
    assert f["free_blocks"] == 10
    assert f["largest_free_run"] == 9
    assert f["free_fragmentation_ratio"] == pytest.approx(1 - 9 / 10,
                                                          abs=1e-4)
    # 15 tokens in 5 leased blocks of 4 -> internal frag 1 - 15/20
    assert f["internal_frag_ratio"] == pytest.approx(0.25)
    assert f["utilization"] == pytest.approx(5 / 15, abs=1e-4)


def test_fragmentation_clean_pool():
    mgr = BlockCacheManager(num_blocks=4, block_size=4,
                            max_blocks_per_seq=4)
    f = mgr.fragmentation()
    assert f["free_blocks"] == 4 and f["largest_free_run"] == 4
    assert f["free_fragmentation_ratio"] == 0.0
    assert f["internal_frag_ratio"] == 0.0 and f["per_seq"] == {}


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------

def test_oom_flight_dump_on_injected_exhaustion(tmp_path):
    from paddle_tpu.resilience import faults
    from paddle_tpu.serving import MLPLMEngine, RequestStatus, \
        ServingFrontend

    obs.enable()
    memory.configure(flight_dir=str(tmp_path), min_dump_interval_s=0.0)
    fe = ServingFrontend(MLPLMEngine(
        vocab_size=64, hidden=16, max_batch_size=2, num_blocks=24,
        block_size=4, max_blocks_per_seq=8))
    rng = np.random.default_rng(0)
    # the injected KVCacheExhausted fires on a single-token grow — the
    # "real pressure" branch that preempts and must dump forensics first.
    # Twice: with a round in flight the first one only makes the launch
    # settle that round and start over; pressure that is still there on
    # committed state is what dumps and preempts
    faults.inject("serve.cache", after_n=4, times=2,
                  exc=KVCacheExhausted(1, 0, 24))
    try:
        hs = [fe.submit(rng.integers(1, 64, 5).tolist(), max_new_tokens=8)
              for _ in range(2)]
        fe.run_until_idle(max_steps=500)
    finally:
        faults.clear()
    assert all(h.status.terminal for h in hs)
    dumps = [f for f in os.listdir(tmp_path)
             if f.startswith("flight_oom_kv_exhausted")]
    assert dumps, os.listdir(tmp_path)
    lines = [json.loads(ln) for ln in open(tmp_path / sorted(dumps)[0])]
    assert lines[0]["reason"] == "oom_kv_exhausted"
    body = lines[1]
    assert body["memory"]["kv"], body        # the KV map snapshot
    kv = body["memory"]["kv"][0]
    assert {"free_blocks", "per_seq", "largest_free_run"} <= set(kv)
    assert body["memory"]["devices"]
    assert body["live_requests"] is not None
    assert body["extra"]["need"] == 1
    assert monitor.get("observability.oom_dumps") >= 1


def test_oom_dump_rate_limited(tmp_path):
    memory.configure(flight_dir=str(tmp_path), min_dump_interval_s=3600.0)
    memory.reset()
    assert memory.dump_oom("kv_exhausted") is not None
    assert memory.dump_oom("kv_exhausted") is None     # limited
    assert memory.dump_oom("kv_exhausted", force=True) is not None


# ---------------------------------------------------------------------------
# mesh-aware aggregation + straggler attribution
# ---------------------------------------------------------------------------

def test_aggregate_mesh_straggler_with_slow_host():
    snaps = [{"serving.tokens": 10, "mesh.step_wall_ms": 5.0},
             {"serving.tokens": 12, "mesh.step_wall_ms": 5.5},
             {"serving.tokens": 9, "mesh.step_wall_ms": 16.5},   # slow
             {"serving.tokens": 11, "mesh.step_wall_ms": 5.2}]
    agg = monitor.aggregate_mesh(snapshots=snaps)
    assert agg["hosts"] == 4
    assert agg["straggler_host"] == 2
    assert agg["straggler_step_wall_ms"] == 16.5
    assert agg["step_wall_spread_pct"] == pytest.approx(230.0)
    assert agg["sum"]["serving.tokens"] == 42
    # published for scrapers + the "Mesh:" profiler section
    snap = monitor.snapshot("mesh.")
    assert snap["mesh.straggler_host"] == 2
    assert snap["mesh.step_wall_spread_pct"] == pytest.approx(230.0)
    assert snap["mesh.step_wall_spread_count"] == 4


def test_aggregate_mesh_gathers_via_collective():
    monitor.set_gauge("mesh.step_wall_ms", 7.0)
    monitor.inc("obs_dist.agg_probe", 3)
    agg = monitor.aggregate_mesh()
    # single-controller: the emulated gather would return N identical
    # copies of this process, so aggregation must report ONE host with
    # true (not N-fold) counter sums
    assert agg["hosts"] == 1
    assert agg["per_host_step_wall_ms"] == [7.0]
    assert agg["step_wall_spread_pct"] == 0.0
    assert agg["sum"]["obs_dist.agg_probe"] == 3
    monitor.reset("obs_dist.agg_probe")


def test_mesh_section_requires_an_aggregation():
    """init_parallel_env sets mesh.hosts unconditionally; the profiler
    "Mesh:" section must stay empty until aggregate_mesh actually ran."""
    import paddle_tpu.profiler as profiler

    monitor.set_gauge("mesh.hosts", 4)          # topology gauge alone
    assert profiler.Profiler._mesh_summary_lines() == []
    monitor.aggregate_mesh(snapshots=[{"mesh.step_wall_ms": 2.0}])
    lines = profiler.Profiler._mesh_summary_lines()
    assert lines and any("Mesh:" in ln for ln in lines)


def test_metrics_dump_mesh_flag(capsys):
    import importlib.util

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "_tool_md", os.path.join(repo, "tools", "metrics_dump.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rc = mod.main(["--mesh", "--exec",
                   "from paddle_tpu.framework import monitor; "
                   "monitor.set_gauge('mesh.step_wall_ms', 3.0)"])
    out = capsys.readouterr().out
    assert rc == 0
    agg = json.loads(out)
    assert agg["hosts"] >= 1 and "per_host_step_wall_ms" in agg


# ---------------------------------------------------------------------------
# CostCard memory fields + memory snapshots
# ---------------------------------------------------------------------------

def test_cost_card_memory_fields_and_report():
    import jax.numpy as jnp

    from paddle_tpu.observability import costs

    card = costs.card_for_jit(lambda x, y: x @ y,
                              jnp.ones((64, 64), jnp.float32),
                              jnp.ones((64, 64), jnp.float32))
    assert card.argument_bytes == 2 * 64 * 64 * 4
    assert card.output_bytes == 64 * 64 * 4
    assert card.peak_bytes == (card.argument_bytes + card.output_bytes
                               + card.temp_bytes)
    d = card.as_dict()
    for k in ("argument_bytes", "output_bytes", "temp_bytes",
              "peak_bytes"):
        assert k in d
    costs.cost_book().register("obs_dist.matmul", card)
    rows = {r["name"]: r for r in costs.cost_book().rows()}
    assert rows["obs_dist.matmul"]["peak_bytes"] == card.peak_bytes
    rep = memory.memory_report()
    names = [r["name"] for r in rep["top_executables_by_peak_bytes"]]
    assert "obs_dist.matmul" in names


def test_device_memory_snapshot_gauges():
    rows = memory.device_memory_snapshot()
    assert len(rows) >= 1
    for r in rows:
        assert r["live_bytes"] >= 0 and r["peak_bytes"] >= 0
    snap = monitor.snapshot("mem.", include_histograms=False)
    assert any(k.endswith(".live_bytes") for k in snap)


# ---------------------------------------------------------------------------
# chrome comms track + profiler sections
# ---------------------------------------------------------------------------

def test_comms_chrome_track_correlated_with_steps(tmp_path, rng):
    import paddle_tpu.profiler as profiler

    obs.enable()
    prof = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU])
    prof.start()
    t = Tensor(np.ones((8, 8), np.float32))
    dist.scatter(t)
    with comms.step_overlap("obs_dist_step"):
        dist.all_reduce(t)
    prof.stop()
    p = str(tmp_path / "trace.json")
    prof.export(p)
    ev = [e for e in json.load(open(p))["traceEvents"]
          if e.get("pid") == "comms" and e.get("ph") != "M"]
    assert ev, "no comms track in chrome export"
    steps = [e for e in ev if e["cat"] == "step"]
    colls = [e for e in ev if e["cat"] == "comm"]
    assert any(e["name"] == "obs_dist_step" for e in steps)
    ar = [e for e in colls if e["name"] == "all_reduce"]
    assert ar and ar[-1]["args"]["bytes"] > 0
    assert all(e["ts"] >= 0 for e in ev)    # shared clock base
    # the all_reduce inside the window lands inside the step span
    st = next(e for e in steps if e["name"] == "obs_dist_step")
    assert st["ts"] <= ar[-1]["ts"] <= st["ts"] + st["dur"]
    # disabled export leaks nothing
    obs.disable()
    p2 = str(tmp_path / "trace2.json")
    prof.export(p2)
    assert not [e for e in json.load(open(p2))["traceEvents"]
                if e.get("pid") == "comms"]


def test_profiler_comms_section(rng):
    import paddle_tpu.profiler as profiler

    obs.enable()
    prof = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU])
    prof.start()
    with profiler.RecordEvent("obs_dist_host_span"):
        t = Tensor(np.ones((8, 4), np.float32))
        dist.scatter(t)
        dist.all_reduce(t)
    prof.stop()
    s = prof.summary()
    assert "Comms:" in s and "all_reduce" in s
