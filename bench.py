"""Benchmark: Llama train-step throughput on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extras"}.
Metric: model FLOPs utilisation (MFU) of a bf16 Llama train step
(fwd+bwd+AdamW), the BASELINE.md config-3 metric measured on the smallest
representative slice (one chip): true 7B layer shapes (hidden 4096,
intermediate 11008, 32 heads, seq 2048) with layer count/remat fitted to the
chip's HBM. vs_baseline = MFU / 0.45 (the north-star >=45% MFU target).

A scenario runs in THIS process on the TPU JAX finds, or on the CPU when the
caller set `JAX_PLATFORMS=cpu`; with neither it fails (`_scenario_setup`).
There is no probe child, no CPU fall-back and no carried-forward result: a
run that could not measure prints nothing and exits non-zero.

- `extras.pallas_custom_calls` counts tpu_custom_call sites in the lowered
  step HLO — proof the Pallas kernels (not the jnp fallback) are engaged;
- `extras.flash_microbench` times the Pallas flash-attention fwd+bwd against
  the XLA sdpa composite on the measured shape;
- RESOURCE_EXHAUSTED falls back through smaller configs; any other error,
  in any sub-measurement, ends the run.
"""
from __future__ import annotations

import json
import os
import sys
import time

# peak dense bf16 FLOPs per chip by PJRT device_kind (public spec sheets)
_PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
    "TPU7x": 2307e12,
}


# ---------------------------------------------------------------------------
# Scenario registry + regression-gate plumbing (ROADMAP item 5)
# ---------------------------------------------------------------------------
# Every scenario is independently runnable (`python bench.py <name>`),
# independently budgeted, and emits ONE JSON line tagged with `scenario`
# and `platform`. Successful runs update the per-scenario last-good
# baseline under profiler_log/baselines/ (a CPU run can never
# overwrite a TPU baseline — enforced by the store); `tools/bench_diff.py`
# gates any run against its stored baseline (>5 % regression fails).

SCENARIOS = {}
_scenario_t0 = None


def scenario(name, budget_s):
    """Register a bench scenario with its wall-clock budget (seconds;
    `BENCH_BUDGET_<NAME>_S` overrides)."""

    def deco(fn):
        SCENARIOS[name] = (fn, budget_s)
        return fn

    return deco


def _scenario_budget_s(name):
    _fn, default = SCENARIOS[name]
    return float(os.environ.get(f"BENCH_BUDGET_{name.upper()}_S", default))


def _emit_report(report, scenario_name):
    """Print the scenario's ONE JSON line (stdout stays a single line —
    the artifact contract) and update the last-good baseline. Baselines
    only move on successful, fresh, same-or-better-platform runs."""
    report["scenario"] = scenario_name
    if "platform" not in report:
        import jax

        # the REAL backend string (cpu/gpu/tpu): a GPU run must not
        # masquerade as TPU in the baseline store
        report["platform"] = jax.devices()[0].platform
    if _scenario_t0 is not None:
        budget = _scenario_budget_s(scenario_name)
        wall = round(time.time() - _scenario_t0, 1)
        report.setdefault("extras", {})["scenario_wall_s"] = wall
        report["extras"]["scenario_budget_s"] = budget
        if wall > budget:
            report["extras"]["budget_exceeded"] = True
    print(json.dumps(report))
    from paddle_tpu.observability import baseline as bl

    store = bl.BaselineStore(os.environ.get("BENCH_BASELINE_DIR"))
    # last-GOOD, not last-run: the baseline only moves when this run
    # is at least as good as it on EVERY gated metric (gate_pct=0).
    # A within-5% tolerance update would let ten consecutive 4%
    # regressions each become 'last-good' and compound to 33% with
    # bench_diff never firing; a worse-than-baseline run keeps the
    # stored one and is left for tools/bench_diff.py to fail.
    prev = store.load(scenario_name)
    if prev is not None and prev.get("platform") == report.get(
            "platform"):
        gate = bl.compare_reports(report, prev, gate_pct=0.0)
        if not gate["ok"]:
            bad = [c["metric"] for c in gate["checks"]
                   if c["regression"]]
            print(f"[bench] baseline[{scenario_name}]: kept last-good "
                  f"— this run is worse on {bad} (gate it with "
                  f"tools/bench_diff.py)", file=sys.stderr)
            return
    saved, reason = store.update(report)
    print(f"[bench] baseline[{scenario_name}]: {reason}",
          file=sys.stderr)


def _scenario_setup(scenario):
    """Initialise JAX in this process and name the device the scenario
    runs on: the TPU, or the CPU when the caller — nobody else — set
    `JAX_PLATFORMS=cpu`. Anything else is an error, never a fall-back.
    Returns the device record for the scenario's extras."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit(
            f"bench.py {scenario}: JAX found no TPU (platform "
            f"{dev.platform!r}); set JAX_PLATFORMS=cpu to run this "
            "scenario on the CPU on purpose")
    from paddle_tpu.framework import compile_cache

    compile_cache.configure()
    return {"scenario": scenario, "platform": dev.platform,
            "device_kind": dev.device_kind,
            "device_count": len(jax.devices())}


def _peak_flops(device) -> float:
    """Peak dense bf16 FLOP/s of `device`; a `device_kind` the table does
    not hold is an error (a CPU has no entry: it has no MFU)."""
    kind = device.device_kind
    # longest (most specific) prefix match: "TPU v5 lite" must hit the 197T
    # v5e entry, not the 459T "TPU v5" (v5p) one
    match = max((k for k in _PEAK_FLOPS
                 if kind.lower().startswith(k.lower())),
                key=len, default=None)
    if match is None:
        raise KeyError(f"no peak FLOP/s on record for device_kind {kind!r}")
    return _PEAK_FLOPS[match]


def _count_pallas_calls(jitted_step, *args) -> int:
    return jitted_step.lower(*args).as_text().count("tpu_custom_call")


def _eager_microbench():
    """Eager per-op dispatch cost (SURVEY §7.3 hard-part #1): µs/op for
    cache-hit dispatch with grad off/on, warm-backward µs/op, and the
    eager-vs-compiled train-step ratio on llama_tiny. The reference keeps this
    path native (`phi/core/kernel_factory.cc:270`); here it is a Python dict
    lookup + jitted-executable call, so it must be measured, not assumed."""
    import time

    import jax
    import numpy as np

    import paddle_tpu as paddle

    out = {}
    a = paddle.to_tensor(np.ones((1024, 1024), np.float32))
    b = paddle.to_tensor(np.ones((1024, 1024), np.float32))
    s = paddle.to_tensor(np.ones((8, 8), np.float32))
    t = paddle.to_tensor(np.ones((8, 8), np.float32))
    for x in (a, b, s, t):
        x.stop_gradient = True

    def us_per_op(op, x, y, n):
        op(x, y)._data.block_until_ready()  # warm the executable cache
        t0 = time.perf_counter()
        for _ in range(n):
            r = op(x, y)
        r._data.block_until_ready()
        return (time.perf_counter() - t0) / n * 1e6

    mul = lambda x, y: x * y  # noqa: E731
    mm = lambda x, y: x @ y  # noqa: E731
    out["nograd_tiny_add_us"] = round(us_per_op(lambda x, y: x + y, s, t, 2000), 1)
    out["nograd_1k_matmul_us"] = round(us_per_op(mm, a, b, 200), 1)
    a.stop_gradient = s.stop_gradient = False
    out["grad_tiny_add_us"] = round(us_per_op(lambda x, y: x + y, s, t, 2000), 1)
    out["grad_tiny_mul_us"] = round(us_per_op(mul, s, t, 2000), 1)
    out["grad_tiny_matmul_us"] = round(us_per_op(mm, s, t, 2000), 1)
    out["grad_1k_matmul_us"] = round(us_per_op(mm, a, b, 200), 1)
    out["dispatch_ops_per_sec"] = round(1e6 / out["grad_tiny_mul_us"])

    # warm backward: 100-op chain, second run (first pays one-time jit traces)
    def chain_backward():
        s.clear_gradient()
        w = s
        for _ in range(100):
            w = w * t
        loss = w.sum()
        t0 = time.perf_counter()
        loss.backward()
        s._grad._data.block_until_ready()
        return (time.perf_counter() - t0) / 101 * 1e6

    chain_backward()
    out["backward_us_per_op"] = round(chain_backward(), 1)

    # eager vs compiled train step on llama_tiny
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.jit import functional_call, state_arrays
    from paddle_tpu.models import llama_tiny

    model = llama_tiny(seq=128)
    model.train()
    rng = np.random.default_rng(0)
    V = model.config.vocab_size
    ids_np = rng.integers(0, V, (2, 128))
    lab_np = rng.integers(0, V, (2, 128))
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    # pre-staged device tensors: both legs measure fwd+bwd+AdamW only, no
    # per-step host->device transfer on either side
    ids_t, lab_t = paddle.to_tensor(ids_np), paddle.to_tensor(lab_np)

    def eager_step():
        loss, _ = model(ids_t, labels=lab_t)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    def time_steps(n):
        eager_step()  # warm executable caches
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(n):
                loss = eager_step()
            loss._data.block_until_ready()
            jax.block_until_ready([p._data for p in model.parameters()])
            best = min(best, (time.perf_counter() - t0) / n * 1e3)
        return best

    eager_ms = time_steps(5)

    # lazy op-batching eager mode (core/lazy.py): same user-visible loop,
    # ops fused into one region executable + one fused fwd+grad program
    from paddle_tpu.core import lazy as lazy_mode
    from paddle_tpu.framework import monitor as _monitor

    prev_lazy = lazy_mode.set_lazy_mode(True)
    try:
        _monitor.reset("lazy.fused_ops")
        _monitor.reset("lazy.flushes")
        lazy_ms = time_steps(8)
        flushes = max(1, _monitor.get("lazy.flushes"))
        out["lazy_ops_per_flush"] = round(
            _monitor.get("lazy.fused_ops") / flushes, 1)
        out["lazy_max_region_ops"] = _monitor.get("lazy.max_region_ops")
    finally:
        lazy_mode.set_lazy_mode(prev_lazy)

    params = state_arrays(model)
    m_st = {k: jax.numpy.zeros_like(v) for k, v in params.items()}
    v_st = {k: jax.numpy.zeros_like(v) for k, v in params.items()}

    def compiled_step(params, m_st, v_st, step, ids, labels):
        def loss_fn(p):
            loss, _ = functional_call(model, p, Tensor(ids),
                                      labels=Tensor(labels))
            return loss._data

        loss, grads = jax.value_and_grad(loss_fn)(params)
        # the same AdamW update the eager leg's optimizer performs
        b1, b2, lr, eps, wd = 0.9, 0.999, 1e-4, 1e-8, 0.01
        new_p, new_m, new_v = {}, {}, {}
        for k in params:
            g = grads[k]
            new_m[k] = b1 * m_st[k] + (1 - b1) * g
            new_v[k] = b2 * v_st[k] + (1 - b2) * g * g
            mhat = new_m[k] / (1 - b1 ** step)
            vhat = new_v[k] / (1 - b2 ** step)
            new_p[k] = params[k] - lr * (
                mhat / (jax.numpy.sqrt(vhat) + eps) + wd * params[k])
        return loss, new_p, new_m, new_v

    jstep = jax.jit(compiled_step)

    def step_fn(params, ids, labels):
        nonlocal m_st, v_st, _step
        _step += 1.0
        loss, params, m_st, v_st = jstep(params, m_st, v_st, _step, ids,
                                         labels)
        return loss, params

    _step = 0.0
    ids_j, lab_j = jax.numpy.asarray(ids_np), jax.numpy.asarray(lab_np)
    loss, params = step_fn(params, ids_j, lab_j)
    jax.block_until_ready(loss)
    compiled_ms = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(5):
            loss, params = step_fn(params, ids_j, lab_j)
        jax.block_until_ready(loss)
        jax.block_until_ready(jax.tree.leaves(params))
        compiled_ms = min(compiled_ms, (time.perf_counter() - t0) / 5 * 1e3)
    out["llama_tiny_eager_step_ms"] = round(eager_ms, 2)
    out["llama_tiny_lazy_step_ms"] = round(lazy_ms, 2)
    out["llama_tiny_compiled_step_ms"] = round(compiled_ms, 2)
    # headline ratio is measured with lazy mode ON (the shipped eager fast
    # path); the immediate-dispatch ratio is kept for comparison
    out["eager_vs_compiled_ratio"] = round(
        lazy_ms / max(compiled_ms, 1e-9), 2)
    out["eager_vs_compiled_ratio_immediate"] = round(
        eager_ms / max(compiled_ms, 1e-9), 2)
    return out


def _drive_poisson(fe, arrivals, submit_one):
    """Open-loop Poisson driver shared by the throughput and overload
    scenarios: submit each request at its arrival offset (sleeping only
    when the engine is idle AND nothing is due), stepping the scheduler
    otherwise, until every arrival is in and the frontend drains.
    Returns (handles, wall_s)."""
    handles = []
    n = len(arrivals)
    t0 = time.perf_counter()
    i = 0
    while i < n or not fe.scheduler.idle:
        now = time.perf_counter() - t0
        while i < n and arrivals[i] <= now:
            handles.append(submit_one(i))
            i += 1
        if fe.scheduler.idle and i < n:
            time.sleep(max(0.0, arrivals[i] - (time.perf_counter() - t0)))
            continue
        fe.step()
    return handles, time.perf_counter() - t0


@scenario("serving_throughput", 420)
def serving_throughput_main():
    """`python bench.py serving_throughput` — continuous-batching serving
    under a Poisson arrival trace (open-loop). CPU-runnable; on TPU the
    same harness exercises the real paged-attention decode kernel.

    Prints ONE JSON line: tok/s generated, p50/p99/mean TTFT, batch
    occupancy, KV utilization, preemptions, and the decode retrace count
    after warmup (must be 0 — the zero-recompile steady state); extras
    also carry an `overload` sub-report (4x-capacity Poisson burst with
    admission control: shed/admit counts, shed-rejection latency, and
    admitted-TTFT degradation vs the 1x burst on the same stack)."""
    device = _scenario_setup("serving_throughput")
    import jax
    import numpy as np

    from paddle_tpu.framework import monitor
    from paddle_tpu.inference import LlamaInferenceEngine
    from paddle_tpu.models import llama_tiny
    from paddle_tpu.serving import RequestStatus, ServingFrontend

    on_tpu = jax.devices()[0].platform != "cpu"
    model = llama_tiny(vocab=128, layers=2, hidden=64, heads=4, seq=256)
    model.eval()

    def build_engine():
        return LlamaInferenceEngine(
            model, max_batch_size=8, num_blocks=128, block_size=8,
            max_blocks_per_seq=16,
            **({"dtype": "bfloat16"} if on_tpu else {}))

    engine = build_engine()
    fe = ServingFrontend(engine)
    rng = np.random.default_rng(0)

    # warmup: cover the prefill buckets + the decode shape
    for n in (3, 7, 14, 27):
        fe.submit(rng.integers(1, 128, n).tolist(), max_new_tokens=2)
    fe.run_until_idle(max_steps=500)
    monitor.reset("serving.decode_retraces")
    # warmup requests paid the compiles; their latencies/occupancy are not
    # the trace's, and counters are deltas from here
    fe.metrics.reset_window()
    base_tokens = monitor.get("serving.tokens_generated")
    base_steps = monitor.get("serving.decode_steps")

    # Poisson arrival trace: open-loop, mean inter-arrival 15 ms
    n_requests, mean_gap_s = 64, 0.015
    gaps = rng.exponential(mean_gap_s, n_requests)
    arrivals = np.cumsum(gaps)
    specs = [(rng.integers(2, 28), int(rng.integers(4, 12)))
             for _ in range(n_requests)]
    def submit_one(i):
        plen, gen = specs[i]
        return fe.submit(rng.integers(1, 128, plen).tolist(),
                         max_new_tokens=gen)

    handles, wall = _drive_poisson(fe, arrivals, submit_one)

    done = sum(h.status is RequestStatus.FINISHED for h in handles)
    tokens = monitor.get("serving.tokens_generated") - base_tokens \
        + len(handles)  # + the prefill-sampled first tokens
    s = fe.summary()
    tok_s = tokens / wall
    ttfts = sorted(t for t in (h.ttft_ms() for h in handles)
                   if t is not None)
    extras = {
        "requests": n_requests, "completed": done,
        "wall_s": round(wall, 2),
        "ttft_p50_ms": s["serving.ttft_p50_ms"],
        "ttft_p99_ms": s["serving.ttft_p99_ms"],
        "ttft_mean_ms": round(float(np.mean(ttfts)), 3) if ttfts else None,
        "tpot_mean_ms": s["serving.tpot_mean_ms"],
        "batch_occupancy_avg_pct": s["serving.batch_occupancy_avg_pct"],
        "kv_utilization_peak_pct": s["serving.kv_utilization_peak_pct"],
        "preemptions": s.get("serving.preemptions", 0),
        "decode_steps": monitor.get("serving.decode_steps") - base_steps,
        "decode_retraces_after_warmup":
            monitor.get("serving.decode_retraces"),
        "poisson_mean_gap_ms": mean_gap_s * 1e3,
        "device": device,
        "device": jax.devices()[0].device_kind or "cpu",
    }
    extras["overload"] = _overload_bench(build_engine, tok_s,
                                         float(np.mean([g for _, g in specs])))
    # XLA cost-based utilization (observability layer): the decode
    # executable's compiler-reported FLOPs, lowered AFTER every retrace
    # assertion above was collected (lowering re-traces → the counters
    # tick once more, which must not look like a steady-state recompile)
    try:
        from paddle_tpu.observability import costs as _costs
        from paddle_tpu.ops.sampling import step_args

        # the serving decode program is the ragged step: lower it at the
        # scheduler's packed shapes (T = lanes + chunk budget)
        fn, leading = engine.cost_card_args("decode")
        B = engine.max_batch_size
        T = fe.scheduler.ragged_tokens
        card = _costs.card_from_lowered(fn, *leading, *step_args(
            np.zeros((T,), np.int32),
            np.ones((B,), np.int32), np.ones((B,), np.int32),
            np.zeros((B, engine.manager.max_blocks_per_seq), np.int32)))
        if card.flops:
            dsteps = max(extras["decode_steps"], 1)
            extras["decode_cost"] = {
                "flops_per_step": card.flops,
                "bytes_accessed_per_step": card.bytes_accessed,
                "achieved_flops": round(card.flops * dsteps / wall, 1),
                "pct_of_peak": round(card.flops * dsteps / wall
                                     / _peak_flops(jax.devices()[0]) * 100,
                                     4) if on_tpu else "not measured",
            }
    except Exception as e:
        extras["decode_cost"] = f"{type(e).__name__}: {str(e)[:120]}"
    _emit_report({
        "metric": "serving_throughput",
        "value": round(tok_s, 1),
        "unit": f"tok/s (llama_tiny, {done}/{n_requests} done, "
                f"p50 TTFT {extras['ttft_p50_ms']} ms)",
        "vs_baseline": None,
        "extras": extras,
    }, "serving_throughput")


def _overload_bench(build_engine, capacity_tok_s, mean_gen_tokens):
    """4x-capacity Poisson burst against the admission-controlled stack.

    The acceptance contract (ISSUE 6): overload must degrade to FAST shed
    rejections, not collapsed TTFT — admitted-request p99 TTFT under the
    4x burst stays < 2x an unloaded (0.5x) baseline ON THE SAME
    admission-controlled frontend, and shed requests are rejected in
    < 5 ms. Both runs share one engine/frontend (drained between bursts)
    so the comparison isolates load, not compile or cache state.

    Capacity is MEASURED full-batch closed-loop throughput (a saturation
    run of `lanes` concurrent requests, host loop and prefills included):
    the open-loop phase's tok/s runs at partial occupancy and would
    understate the 4x point, while raw `lanes / dispatch_TPOT` ignores
    per-step host overhead and would overstate it — either error makes
    the burst multipliers meaningless."""
    import numpy as np

    from paddle_tpu.serving import (AdmissionConfig, RequestStatus,
                                    ServingFrontend, ServingMetrics)

    ServingMetrics.reset_monitor()
    # Tightest queue watermark: admit only into an empty queue. Under
    # saturation a slot frees roughly every step and each queue position
    # costs ~a step of TTFT (measured: ~6 ms/position on CPU — qh=3
    # degraded admitted p99 ~3x), so for a latency-isolation bench the
    # queue IS the degradation; shed instead. Throughput-leaning
    # deployments raise the watermark and trade TTFT for goodput
    # (docs/SERVING.md "watermark tuning").
    # chunk budget sized to the burst's whole per-step admission load
    # (8 slots x <=20-token prompts): this is a latency-isolation bench,
    # so TTFT must not queue behind the chunk budget — the TPOT side of
    # that trade-off has its own scenario (serving_mixed)
    fe = ServingFrontend(
        build_engine(),
        admission=AdmissionConfig(queue_high=1, queue_low=0,
                                  kv_high=0.95, kv_low=0.8),
        prefill_chunk_tokens=160)
    rng = np.random.default_rng(7)
    # compile coverage before any timing. One request at a time: this
    # frontend sheds on queue depth, so submitting the four bucket
    # sizes back-to-back sheds the later ones and leaves their prefill
    # buckets uncompiled — the first burst request to hit one then pays
    # the whole compile (~600 ms on CPU) mid-burst, stalling the loop
    # and latching the shed watermark over everything behind it.
    for n in (3, 7, 14, 27):
        fe.submit(rng.integers(1, 128, n).tolist(), max_new_tokens=2)
        fe.run_until_idle(max_steps=500)
    # saturation phase: the bucket pass above yields ~1 decode dispatch
    # per request (max_new_tokens=2 — prefill samples the first token),
    # so the TPOT window would hold mostly the compile outlier. A
    # full-batch closed-loop run both fills the median window with
    # steady-state dispatch times (the deadline-shed estimate) and
    # measures TRUE end-to-end capacity — host loop, sampling, and
    # prefill overhead included, which raw `lanes / dispatch_TPOT`
    # overstates several-fold (that mistake made the "0.5x baseline"
    # itself saturate). step() after each submit keeps the queue under
    # the shed watermark (slots are free, so each admits immediately).
    lanes = len(fe.scheduler.slots)
    sat_gen = 12
    t_sat = time.perf_counter()
    for _ in range(lanes):
        fe.submit(rng.integers(1, 128, 14).tolist(),
                  max_new_tokens=sat_gen)
        fe.step()
    fe.run_until_idle(max_steps=500)
    sat_tok_s = lanes * sat_gen / (time.perf_counter() - t_sat)

    def burst(load_x, n_requests, deadline_s, capacity_rps):
        fe.metrics.reset_window()
        gaps = rng.exponential(1.0 / (load_x * capacity_rps), n_requests)
        arrivals = np.cumsum(gaps)
        handles, _wall = _drive_poisson(
            fe, arrivals,
            lambda _i: fe.submit(
                rng.integers(1, 128, int(rng.integers(2, 20))).tolist(),
                max_new_tokens=int(rng.integers(4, 12)),
                timeout_s=deadline_s))
        non_terminal = sum(not h.finished for h in handles)
        shed = [h for h in handles if h.status is RequestStatus.SHED]
        admitted = [h for h in handles if h.status is not RequestStatus.SHED]
        ttfts = sorted(t for t in (h.ttft_ms() for h in admitted)
                       if t is not None)
        shed_ms = sorted((h._req.t_finish - h._req.t_submit) * 1e3
                         for h in shed)
        pct = lambda xs, q: (  # noqa: E731
            round(float(np.percentile(xs, q)), 3) if xs else None)
        return {
            "requests": n_requests, "admitted": len(admitted),
            "shed": len(shed),
            "non_terminal": non_terminal,
            "finished": sum(h.status is RequestStatus.FINISHED
                            for h in handles),
            "timed_out": sum(h.status is RequestStatus.TIMED_OUT
                             for h in handles),
            "admitted_ttft_p50_ms": pct(ttfts, 50),
            "admitted_ttft_p99_ms": pct(ttfts, 99),
            "shed_reject_p99_ms": pct(shed_ms, 99),
        }

    # generous completion deadline: ~mean_gen steps of decode + slack; the
    # deadline-aware shed uses the measured TPOT against it
    tpot0 = fe.scheduler.tpot_estimate() or 0.005
    deadline_s = max(0.05, 24 * tpot0 * 3)
    full_capacity_rps = sat_tok_s / max(mean_gen_tokens, 1.0)
    # Three paired (0.5x, 4x) trials, degradation gated on the MEDIAN:
    # p99 over the ~100 admitted requests of one burst is close to a
    # max-statistic on a shared CPU — a single GC pause or scheduler
    # hiccup in either burst would flip a single-shot gate either way.
    # The burst sizes (256/512) keep each trial's p99 interpolated
    # rather than literal-max.
    trials = []
    for _ in range(3):
        base = burst(0.5, 256, deadline_s, full_capacity_rps)
        over = burst(4.0, 512, deadline_s, full_capacity_rps)
        trials.append((base, over))
    degs = [round(o["admitted_ttft_p99_ms"] / b["admitted_ttft_p99_ms"], 2)
            for b, o in trials
            if b["admitted_ttft_p99_ms"] and o["admitted_ttft_p99_ms"]]
    base, over = trials[-1]
    report = {
        "burst_x": 4.0,
        "baseline_x": 0.5,
        "tpot_est_ms": round(tpot0 * 1e3, 2),
        "full_capacity_rps": round(full_capacity_rps, 1),
        "saturated_tok_s": round(sat_tok_s, 1),
        "open_loop_tok_s": round(capacity_tok_s, 1),
        "baseline_1x": base,
        "overload_4x": over,
        "shed_by_reason": ServingMetrics.shed_by_reason(),
        "ttft_degradation_trials_x": degs,
        "ttft_degradation_x": (round(float(np.median(degs)), 2)
                               if degs else None),
    }
    # hard in-run checks — an overload regression must fail the bench,
    # not print a healthy-looking report
    for b, o in trials:
        assert o["shed"] > 0, "4x burst shed nothing: admission control dead"
        assert o["shed_reject_p99_ms"] is not None \
            and o["shed_reject_p99_ms"] < 5.0, \
            f"shed rejection too slow: {o['shed_reject_p99_ms']} ms"
        # the terminal-status contract under load: nothing left hanging
        # after the drain, in either burst
        assert b["non_terminal"] == 0 and o["non_terminal"] == 0, \
            f"requests left non-terminal after drain: " \
            f"baseline={b['non_terminal']} overload={o['non_terminal']}"
    if report["ttft_degradation_x"] is not None:
        assert report["ttft_degradation_x"] < 2.0, \
            f"admitted p99 TTFT degraded {report['ttft_degradation_x']}x " \
            f"(median of {degs}) under the 4x burst (bar: < 2x)"
    return report


@scenario("serving_spec", 420)
def serving_spec_main():
    """`python bench.py serving_throughput --spec` — speculative decoding
    (n-gram prompt-lookup proposer + batched multi-token verify) against
    the plain one-token-per-step decode, on a repetition-heavy CLOSED-loop
    trace (prompts repeat a short phrase; greedy continuations of the tiny
    model fall into cycles, the workload prompt-lookup is built for).

    Prints ONE JSON line whose value is the tok/s SPEEDUP of the
    speculative run over the non-speculative baseline (same engine config,
    same trace, greedy); extras carry both throughputs, acceptance-rate
    metrics, tokens/lane-step, retrace counters, and a token-for-token
    greedy parity check. Each mode runs twice and keeps the faster wall
    clock (the two runs are token-identical; timing is the only noise)."""
    device = _scenario_setup("serving_spec")
    import jax
    import numpy as np

    from paddle_tpu.framework import monitor
    from paddle_tpu.inference import LlamaInferenceEngine
    from paddle_tpu.models import llama_tiny
    from paddle_tpu.serving import (NGramProposer, RequestStatus,
                                    ServingFrontend, ServingMetrics,
                                    SpecDecodeConfig)

    on_tpu = jax.devices()[0].platform != "cpu"
    spec_k = int(os.environ.get("BENCH_SPEC_K", "4"))
    # seeded weights: the measured speedup depends on the draft acceptance
    # rate, which depends on the model's greedy cycles — pin a seed whose
    # greedy rollouts actually fall into repetition (what this trace is
    # MEANT to measure) so the speedup is reproducible run-to-run
    import paddle_tpu as paddle
    paddle.seed(int(os.environ.get("BENCH_SPEC_MODEL_SEED", "6")))
    model = llama_tiny(vocab=128, layers=2, hidden=64, heads=4, seq=256)
    model.eval()

    def build_engine():
        return LlamaInferenceEngine(
            model, max_batch_size=8, num_blocks=256, block_size=8,
            max_blocks_per_seq=16,
            **({"dtype": "bfloat16"} if on_tpu else {}))

    def trace(rng):
        reqs = []
        for _ in range(24):
            phrase = rng.integers(1, 128, int(rng.integers(3, 6))).tolist()
            reqs.append(((phrase * 8)[:int(rng.integers(12, 25))], 96))
        return reqs

    def run(spec):
        ServingMetrics.reset_monitor()
        fe = ServingFrontend(build_engine(), spec=spec)
        rng = np.random.default_rng(0)
        for n in (3, 7, 14, 27):   # cover prefill buckets + decode shapes
            fe.submit(rng.integers(1, 128, n).tolist(), max_new_tokens=3)
        fe.run_until_idle(max_steps=500)
        fe.metrics.reset_window()
        for c in ("serving.decode_retraces", "serving.verify_retraces",
                  "serving.sample_retraces"):
            monitor.reset(c)
        base_tok = monitor.get("serving.tokens_generated")
        hs = [fe.submit(p, max_new_tokens=g)
              for p, g in trace(np.random.default_rng(1))]
        t0 = time.perf_counter()
        fe.run_until_idle(max_steps=8000)
        wall = time.perf_counter() - t0
        assert all(h.status is RequestStatus.FINISHED for h in hs), \
            [h.status for h in hs]
        return {
            "tok_s": (monitor.get("serving.tokens_generated")
                      - base_tok) / wall,
            "tokens": [h.tokens for h in hs],
            "decode_retraces": monitor.get("serving.decode_retraces"),
            "verify_retraces": monitor.get("serving.verify_retraces"),
            "sample_retraces": monitor.get("serving.sample_retraces"),
            "acceptance_pct": monitor.get("serving.spec_acceptance_pct"),
            "tokens_per_lane_step":
                monitor.get("serving.spec_tokens_per_lane_step"),
            "proposed": monitor.get("serving.spec_proposed_tokens"),
            "accepted": monitor.get("serving.spec_accepted_tokens"),
        }

    spec_cfg = SpecDecodeConfig(NGramProposer(), num_draft_tokens=spec_k)
    base = max((run(None) for _ in range(2)), key=lambda r: r["tok_s"])
    spec = max((run(spec_cfg) for _ in range(2)), key=lambda r: r["tok_s"])
    parity = all(a == b for a, b in zip(base["tokens"], spec["tokens"]))
    # hard in-run checks: a parity or steady-state-recompile regression
    # must fail the bench, not print a healthy-looking speedup
    assert parity, "speculative greedy parity violated vs plain decode"
    for c in ("decode_retraces", "verify_retraces", "sample_retraces"):
        assert spec[c] == 0, f"steady-state {c} = {spec[c]}"
    speedup = spec["tok_s"] / base["tok_s"]
    extras = {
        "num_draft_tokens": spec_k,
        "base_tok_s": round(base["tok_s"], 1),
        "spec_tok_s": round(spec["tok_s"], 1),
        "spec_acceptance_pct": spec["acceptance_pct"],
        "spec_tokens_per_lane_step": spec["tokens_per_lane_step"],
        "spec_proposed_tokens": spec["proposed"],
        "spec_accepted_tokens": spec["accepted"],
        "greedy_parity": parity,
        "decode_retraces_after_warmup": spec["decode_retraces"],
        "verify_retraces_after_warmup": spec["verify_retraces"],
        "sample_retraces_after_warmup": spec["sample_retraces"],
        "device": device,
        "device": jax.devices()[0].device_kind or "cpu",
    }
    _emit_report({
        "metric": "serving_throughput_spec",
        "value": round(speedup, 2),
        "unit": f"x tok/s vs non-speculative ({extras['spec_tok_s']} vs "
                f"{extras['base_tok_s']} tok/s, "
                f"{extras['spec_acceptance_pct']}% drafts accepted)",
        "vs_baseline": round(speedup / 1.3, 2),  # >=1.3x is the bar
        "extras": extras,
    }, "serving_spec")


@scenario("serving_mixed", 420)
def serving_mixed_main():
    """`python bench.py serving_mixed` — the chunked-prefill acceptance
    instrument (ISSUE 10): decode traffic keeps flowing while a 4k+-token
    prompt arrives mid-stream. Decode TPOT p99 during the long prompt's
    prefill must stay < 1.5x the no-prefill steady state (per-step wall
    over live decode lanes == per-token latency: every live lane commits
    exactly one token per ragged round); a monolithic-prefill baseline
    (chunk budget >= the whole prompt, i.e. the pre-ISSUE-10 dispatch
    shape) runs the same trace for contrast and shows the stall. Also
    asserted in-run: zero ragged retraces across the measured phases —
    the steady state holds ONE prompt-length-independent executable."""
    device = _scenario_setup("serving_mixed")
    import jax
    import numpy as np

    from paddle_tpu.framework import monitor
    from paddle_tpu.inference import LlamaInferenceEngine
    from paddle_tpu.models import llama_tiny
    from paddle_tpu.serving import (RequestStatus, ServingFrontend,
                                    ServingMetrics)

    on_tpu = jax.devices()[0].platform != "cpu"
    long_len = int(os.environ.get("BENCH_MIXED_PROMPT", "4096"))
    chunk = int(os.environ.get("BENCH_MIXED_CHUNK", "64"))
    model = llama_tiny(vocab=128, layers=2, hidden=64, heads=4,
                      seq=long_len + 512)
    model.eval()

    def build_engine():
        return LlamaInferenceEngine(
            model, max_batch_size=8, block_size=8,
            num_blocks=long_len // 8 + 192,
            max_blocks_per_seq=long_len // 8 + 32,
            **({"dtype": "bfloat16"} if on_tpu else {}))

    rng = np.random.default_rng(0)

    def run_phases(chunk_tokens):
        """One engine, three phases: warmup -> steady decode window ->
        the same decode lanes with the long prompt prefilling. Returns
        per-step wall samples for both windows + decode token counts."""
        ServingMetrics.reset_monitor()
        fe = ServingFrontend(build_engine(),
                             prefill_chunk_tokens=chunk_tokens)
        # warmup: compile the ragged step + drain
        for n in (3, 17):
            fe.submit(rng.integers(1, 128, n).tolist(), max_new_tokens=2)
        fe.run_until_idle(max_steps=500)
        monitor.reset("serving.ragged_retraces")
        # six long-lived decode lanes
        lanes = [fe.submit(rng.integers(1, 128, 12).tolist(),
                           max_new_tokens=10 ** 6) for _ in range(6)]
        for _ in range(4):
            fe.step()                       # prompts in, lanes decoding
        steady = []
        for _ in range(60):
            t0 = time.perf_counter()
            fe.step()
            steady.append(time.perf_counter() - t0)
        tok_mark = monitor.get("serving.tokens_generated")
        long_req = fe.submit(rng.integers(1, 128, long_len).tolist(),
                             max_new_tokens=4)
        during = []
        t_mix = time.perf_counter()
        while long_req._req.prefilling or not long_req._req._prefill_ctx.size:
            t0 = time.perf_counter()
            fe.step()
            during.append(time.perf_counter() - t0)
            if len(during) > 4 * (long_len // chunk_tokens + 8):
                raise RuntimeError("long prompt prefill never completed")
        mix_wall = time.perf_counter() - t_mix
        mixed_tokens = monitor.get("serving.tokens_generated") - tok_mark
        retraces = monitor.get("serving.ragged_retraces")
        for h in lanes:
            fe.cancel(h)
        fe.run_until_idle(max_steps=2000)
        assert long_req.status is RequestStatus.FINISHED, long_req
        return steady, during, mixed_tokens, mix_wall, retraces

    p99 = lambda xs: float(np.percentile(np.asarray(xs), 99))  # noqa: E731

    steady, during, mixed_tokens, mix_wall, retraces = run_phases(chunk)
    chunked = {
        "steady_tpot_p99_ms": round(p99(steady) * 1e3, 3),
        "prefill_tpot_p99_ms": round(p99(during) * 1e3, 3),
        "prefill_steps": len(during),
        "decode_tok_s_during_prefill": round(mixed_tokens / mix_wall, 1),
        "ragged_retraces": retraces,
    }
    chunked["tpot_degradation_x"] = round(
        chunked["prefill_tpot_p99_ms"] / chunked["steady_tpot_p99_ms"], 3)
    # hard in-run checks: the acceptance contract
    assert chunked["tpot_degradation_x"] < 1.5, \
        f"chunked prefill stalls decode: {chunked['tpot_degradation_x']}x"
    assert retraces == 0, \
        f"ragged step retraced {retraces}x mid-serving (prompt-length " \
        f"shaped executables are back)"
    extras = {
        "long_prompt_tokens": long_len,
        "prefill_chunk_tokens": chunk,
        "chunked": chunked,
        "tpot_p99_during_prefill_ms": chunked["prefill_tpot_p99_ms"],
        "tpot_degradation_x": chunked["tpot_degradation_x"],
        "device": device,
        "device": jax.devices()[0].device_kind or "cpu",
    }
    _emit_report({
        "metric": "serving_mixed_decode_tok_s",
        "value": chunked["decode_tok_s_during_prefill"],
        "unit": f"decode tok/s while a {long_len}-token prompt prefills "
                f"(TPOT p99 {chunked['prefill_tpot_p99_ms']} ms = "
                f"{chunked['tpot_degradation_x']}x steady)",
        "vs_baseline": None,
        "extras": extras,
    }, "serving_mixed")


@scenario("serving_shared_prefix", 420)
def serving_shared_prefix_main():
    """`python bench.py serving_shared_prefix` — the shared-prefix radix
    caching acceptance instrument (ROADMAP item 1): an 80 %-shared-prefix
    Poisson trace (the shape of real system-prompt traffic) runs twice on
    identical stacks — radix cache ON vs OFF — and the cached run must
    show >3x TTFT p99 on the shared requests and >1.5x aggregate tok/s
    (prefill work is the dominant cost the cache removes). Also asserted
    in-run: zero steady-state ragged retraces (block sharing is pure
    host bookkeeping — the executable never changes), eviction pressure
    actually exercised (the pool is sized so unique suffixes force LRU
    eviction of unpinned tree nodes), and zero leaked / double-freed
    blocks afterwards (`kv_leaked_blocks` + refcount consistency audit
    including the tree's leases). Run SOLO outside the tier-1 window
    (ROADMAP note)."""
    device = _scenario_setup("serving_shared_prefix")
    import jax
    import numpy as np

    from paddle_tpu.framework import monitor
    from paddle_tpu.inference import LlamaInferenceEngine
    from paddle_tpu.models import llama_tiny
    from paddle_tpu.serving import (RequestStatus, ServingFrontend,
                                    ServingMetrics)

    on_tpu = jax.devices()[0].platform != "cpu"
    prefix_len = int(os.environ.get("BENCH_PREFIX_LEN", "192"))
    n_requests = int(os.environ.get("BENCH_PREFIX_REQUESTS", "40"))
    mean_gap_s = 0.03
    model = llama_tiny(vocab=128, layers=2, hidden=64, heads=4,
                       seq=prefix_len + 160)
    model.eval()
    rng = np.random.default_rng(0)
    shared = rng.integers(1, 128, prefix_len).tolist()
    # the trace: 80 % shared-prefix + unique suffix, 20 % fully cold
    specs = []
    for i in range(n_requests):
        sfx = rng.integers(8, 17)
        if rng.random() < 0.8:
            specs.append((True, shared + rng.integers(
                1, 128, sfx).tolist()))
        else:
            specs.append((False, rng.integers(
                1, 128, prefix_len + sfx).tolist()))
    gaps = rng.exponential(mean_gap_s, n_requests)
    arrivals = np.cumsum(gaps)

    def build_engine():
        # pool sized so the tree (shared path + unique published
        # suffixes + the cold requests' full paths) outgrows it over
        # the trace: LRU eviction pressure is part of the contract
        return LlamaInferenceEngine(
            model, max_batch_size=8, block_size=8,
            num_blocks=int(os.environ.get("BENCH_PREFIX_BLOCKS", "256")),
            max_blocks_per_seq=(prefix_len + 160) // 8,
            **({"dtype": "bfloat16"} if on_tpu else {}))

    def run_trace(prefix_cache: bool):
        ServingMetrics.reset_monitor()
        fe = ServingFrontend(build_engine(), prefix_cache=prefix_cache,
                             prefill_chunk_tokens=32)
        # warmup: compile the ragged step at the packed shape AND seed
        # the cache with the shared prefix (steady-state serving has the
        # system prompt resident; the cold 20 % and the unique suffixes
        # still measure the miss path), then drain
        for n in (3, 17):
            fe.submit(rng.integers(1, 128, n).tolist(), max_new_tokens=2)
        fe.submit(shared, max_new_tokens=2)
        fe.run_until_idle(max_steps=1000)
        monitor.reset("serving.ragged_retraces")
        fe.metrics.reset_window()
        base_tokens = monitor.get("serving.tokens_generated")
        tree = fe.scheduler.prefix_cache
        stats0 = tree.stats() if tree is not None else None

        def submit_one(i):
            return fe.submit(specs[i][1], max_new_tokens=4)

        handles, wall = _drive_poisson(fe, arrivals, submit_one)
        done = sum(h.status is RequestStatus.FINISHED for h in handles)
        tokens = monitor.get("serving.tokens_generated") - base_tokens \
            + done  # + the prefill-sampled first tokens
        shared_ttfts = sorted(
            h.ttft_ms() for (is_shared, _), h in zip(specs, handles)
            if is_shared and h.ttft_ms() is not None)
        p99 = lambda xs: round(float(  # noqa: E731
            np.percentile(np.asarray(xs), 99)), 3)
        sched = fe.scheduler
        leaked = sched.kv_leaked_blocks()
        prefix = None
        if tree is not None:
            # double-free / refcount audit with the tree's own leases
            sched.engine.manager.check_consistency(
                external=tree.block_ref_counts())
            prefix = tree.stats()
            d_hits = prefix["hits"] - stats0["hits"]
            d_miss = prefix["misses"] - stats0["misses"]
            prefix["trace_hit_rate"] = round(
                d_hits / max(d_hits + d_miss, 1), 4)
            prefix["trace_evictions"] = prefix["evictions"] \
                - stats0["evictions"]
        return {
            "tok_s": round(tokens / wall, 1),
            "wall_s": round(wall, 2),
            "completed": done,
            "ttft_shared_p99_ms": p99(shared_ttfts),
            "ttft_shared_p50_ms": round(float(np.percentile(
                np.asarray(shared_ttfts), 50)), 3),
            "ragged_retraces": monitor.get("serving.ragged_retraces"),
            "leaked_blocks": leaked,
            "preemptions": monitor.get("serving.preemptions"),
            "prefix": prefix,
        }

    cached = run_trace(prefix_cache=True)
    cold = run_trace(prefix_cache=False)
    ttft_speedup = round(
        cold["ttft_shared_p99_ms"] / cached["ttft_shared_p99_ms"], 2)
    tok_speedup = round(cached["tok_s"] / cold["tok_s"], 2)

    # hard in-run checks: the acceptance contract (ISSUE 12)
    assert cached["completed"] == n_requests and \
        cold["completed"] == n_requests, (cached, cold)
    assert ttft_speedup > 3.0, \
        f"shared-prefix TTFT p99 speedup {ttft_speedup}x <= 3x " \
        f"(cached {cached['ttft_shared_p99_ms']} ms vs cold " \
        f"{cold['ttft_shared_p99_ms']} ms)"
    assert tok_speedup > 1.5, \
        f"tok/s speedup {tok_speedup}x <= 1.5x " \
        f"(cached {cached['tok_s']} vs cold {cold['tok_s']})"
    assert cached["ragged_retraces"] == 0 and \
        cold["ragged_retraces"] == 0, \
        "ragged step retraced mid-trace: block sharing must be pure " \
        "host bookkeeping"
    assert cached["leaked_blocks"] == 0 and cold["leaked_blocks"] == 0, \
        (cached["leaked_blocks"], cold["leaked_blocks"])
    assert cached["prefix"]["trace_evictions"] > 0, \
        "pool never pressured the tree: eviction path unexercised " \
        f"({cached['prefix']})"
    assert cached["prefix"]["trace_hit_rate"] > 0.6, cached["prefix"]
    assert cached["prefix"]["cow_copies"] > 0, \
        f"no divergent append ever COWed ({cached['prefix']})"

    extras = {
        "requests": n_requests,
        "shared_prefix_tokens": prefix_len,
        "shared_fraction": 0.8,
        "poisson_mean_gap_ms": mean_gap_s * 1e3,
        "cached": cached,
        "cold": cold,
        "ttft_shared_p99_ms": cached["ttft_shared_p99_ms"],
        "ttft_speedup_x": ttft_speedup,
        "tok_s_speedup_x": tok_speedup,
        "device": device,
        "device": jax.devices()[0].device_kind or "cpu",
    }
    _emit_report({
        "metric": "serving_shared_prefix_tok_s",
        "value": cached["tok_s"],
        "unit": f"tok/s on the 80% shared-prefix trace "
                f"({tok_speedup}x vs no cache; shared TTFT p99 "
                f"{cached['ttft_shared_p99_ms']} ms = 1/{ttft_speedup} "
                f"of cold; hit rate "
                f"{cached['prefix']['trace_hit_rate']})",
        "vs_baseline": None,
        "extras": extras,
    }, "serving_shared_prefix")


@scenario("serving_quant", 420)
def serving_quant_main():
    """`python bench.py serving_quant` — the quantized-serving capacity
    instrument (ROADMAP item 4, ISSUE 14): int8 weight-only gemms +
    int8 paged KV (per-slot scale planes, quantize-on-write, in-kernel
    dequant) against the full-precision stack.

    The capacity contract: size the quantized pool at the SAME KV HBM
    byte budget as the baseline (`bytes_per_block` halves-or-better, so
    the block count roughly doubles) and drive an identical closed-loop
    burst — the quantized stack must admit >= 2x the concurrent
    sequences (>= 1.7x on TPU, where the bf16 baseline is already half
    of f32 and the scale planes' overhead is honestly counted) with
    tok/s and TTFT p99 no worse than the baseline at its 1x
    concurrency. Also asserted in-run: teacher-forced greedy top-1
    agreement >= 99 % (tie-aware, `serving.quant.greedy_agreement`),
    spec==plain token parity ON the quantized stack, zero ragged/sample
    retraces after warmup, zero leaked blocks + pool consistency.
    Gated via BaselineStore/bench_diff on tok/s, the concurrency ratio,
    and TTFT p99. Run SOLO outside the tier-1 window (ROADMAP note)."""
    device = _scenario_setup("serving_quant")
    import jax
    import numpy as np

    from paddle_tpu.framework import monitor
    from paddle_tpu.inference import LlamaInferenceEngine
    from paddle_tpu.models import llama_tiny
    from paddle_tpu.serving import (NGramProposer, RequestStatus,
                                    ServingFrontend, ServingMetrics,
                                    SpecDecodeConfig, greedy_agreement,
                                    quantize_engine)

    on_tpu = jax.devices()[0].platform != "cpu"
    model = llama_tiny(vocab=128, layers=2, hidden=64, heads=4, seq=256)
    model.eval()
    rng = np.random.default_rng(0)
    n_requests = int(os.environ.get("BENCH_QUANT_REQUESTS", "24"))
    lanes, base_blocks, bs = 16, 24, 8
    prompts = [rng.integers(1, 128, 24).tolist() for _ in range(n_requests)]

    def build(kv_bits=16, wbits=None, num_blocks=base_blocks):
        eng = LlamaInferenceEngine(
            model, max_batch_size=lanes, num_blocks=num_blocks,
            block_size=bs, max_blocks_per_seq=8, kv_bits=kv_bits,
            **({"dtype": "bfloat16"} if on_tpu else {}))
        if wbits is not None:
            quantize_engine(eng, wbits)
        return eng

    # equal KV HBM bytes: the quantized pool gets however many blocks
    # the baseline's byte budget buys at its (smaller) bytes_per_block —
    # the 2x-sequences-per-HBM-byte claim, with the scale planes'
    # overhead counted against it. kv_quant.kv_bytes_per_block owns the
    # formula (the engines register the SAME numbers on their managers,
    # which run_burst reads back for the report/audit)
    from paddle_tpu.inference import kv_quant

    mcfg = model.config
    geom = dict(kv_heads=mcfg.num_key_value_heads, block_size=bs,
                head_dim=mcfg.head_dim, dtype_bytes=2 if on_tpu else 4,
                num_layers=mcfg.num_hidden_layers)
    bpb_base = kv_quant.kv_bytes_per_block(kv_bits=16, **geom)
    bpb_q = kv_quant.kv_bytes_per_block(kv_bits=8, **geom)
    quant_blocks = (base_blocks * bpb_base) // bpb_q

    def run_burst(engine):
        ServingMetrics.reset_monitor()
        fe = ServingFrontend(engine, prefill_chunk_tokens=32)
        for n in (3, 17):      # warm the ragged executable + sampler
            fe.submit(rng.integers(1, 128, n).tolist(), max_new_tokens=2)
        fe.run_until_idle(max_steps=500)
        monitor.reset("serving.ragged_retraces")
        monitor.reset("serving.sample_retraces")
        fe.metrics.reset_window()
        base_tokens = monitor.get("serving.tokens_generated")
        handles = [fe.submit(p, max_new_tokens=8) for p in prompts]
        peak = 0
        t0 = time.perf_counter()
        while not fe.scheduler.idle:
            fe.step()
            peak = max(peak, fe.scheduler.num_running)
        wall = time.perf_counter() - t0
        done = sum(h.status is RequestStatus.FINISHED for h in handles)
        tokens = monitor.get("serving.tokens_generated") - base_tokens \
            + done  # + the prefill-sampled first tokens
        ttfts = sorted(t for t in (h.ttft_ms() for h in handles)
                       if t is not None)
        mgr = fe.scheduler.engine.manager
        leaked = fe.scheduler.kv_leaked_blocks()
        mgr.check_consistency()
        return {
            "tok_s": round(tokens / wall, 1),
            "wall_s": round(wall, 2),
            "completed": done,
            "peak_concurrency": peak,
            "ttft_p99_ms": round(float(np.percentile(
                np.asarray(ttfts), 99)), 3),
            "ttft_p50_ms": round(float(np.percentile(
                np.asarray(ttfts), 50)), 3),
            "ragged_retraces": monitor.get("serving.ragged_retraces"),
            "sample_retraces": monitor.get("serving.sample_retraces"),
            "leaked_blocks": leaked,
            "num_blocks": mgr.num_blocks,
            "bytes_per_block": mgr.bytes_per_block,
            "pool_bytes": mgr.bytes_per_block * mgr.num_blocks,
            "kv_bits": mgr.kv_bits,
            "preemptions": monitor.get("serving.preemptions"),
        }, [h.tokens for h in handles]

    base, _ = run_burst(build())
    quant, _ = run_burst(build(kv_bits=8, wbits=8, num_blocks=quant_blocks))

    # spec==plain parity ON the quantized stack: same engine config,
    # speculative vs plain decode, bitwise token streams
    def run_tokens(spec):
        fe = ServingFrontend(
            build(kv_bits=8, wbits=8, num_blocks=quant_blocks),
            spec=SpecDecodeConfig(NGramProposer(), num_draft_tokens=3)
            if spec else None)
        hs = [fe.submit(p, max_new_tokens=8) for p in prompts[:8]]
        fe.run_until_idle(max_steps=2000)
        assert all(h.status is RequestStatus.FINISHED for h in hs)
        return [h.tokens for h in hs]

    spec_toks = run_tokens(spec=True)
    plain_toks = run_tokens(spec=False)

    # teacher-forced greedy agreement, quantized vs full precision
    agreement = greedy_agreement(
        build(kv_bits=8, wbits=8), build(), prompts[:8])

    concurrency_x = round(quant["peak_concurrency"]
                          / max(base["peak_concurrency"], 1), 2)
    tok_s_x = round(quant["tok_s"] / base["tok_s"], 2)
    ttft_p99_x = round(quant["ttft_p99_ms"] / base["ttft_p99_ms"], 2)

    # hard in-run checks: the acceptance contract (ISSUE 14)
    assert base["completed"] == n_requests and \
        quant["completed"] == n_requests, (base, quant)
    # the formula this scenario sized pools with IS what the engines
    # registered on their managers (one source: kv_bytes_per_block)
    assert base["bytes_per_block"] == bpb_base and \
        quant["bytes_per_block"] == bpb_q, (base, quant, bpb_base, bpb_q)
    assert quant["pool_bytes"] <= base["pool_bytes"], (quant, base)
    conc_bar = 1.7 if on_tpu else 2.0
    assert concurrency_x >= conc_bar, \
        f"admitted concurrency {concurrency_x}x < {conc_bar}x " \
        f"(quant peak {quant['peak_concurrency']} vs base " \
        f"{base['peak_concurrency']} at equal pool bytes)"
    assert tok_s_x >= 0.95, \
        f"quantized tok/s {quant['tok_s']} < 0.95x baseline {base['tok_s']}"
    assert ttft_p99_x <= 1.1, \
        f"quantized TTFT p99 {quant['ttft_p99_ms']} ms worse than " \
        f"1.1x baseline {base['ttft_p99_ms']} ms"
    assert agreement["agreement_tie_aware"] >= 0.99, agreement
    assert spec_toks == plain_toks, \
        "spec==plain token parity broke under quantization"
    assert quant["ragged_retraces"] == 0 and \
        quant["sample_retraces"] == 0, quant
    assert quant["leaked_blocks"] == 0 and base["leaked_blocks"] == 0

    extras = {
        "requests": n_requests,
        "lanes": lanes,
        "base": base,
        "quant": quant,
        "concurrency_x": concurrency_x,
        "tok_s_x": tok_s_x,
        "ttft_p99_ms": quant["ttft_p99_ms"],
        "ttft_p99_x": ttft_p99_x,
        "agreement": {k: round(v, 4) for k, v in agreement.items()},
        "spec_plain_parity": True,
        "quant_mode": {"wbits": 8, "kv_bits": 8},
        "device": device,
        "device": jax.devices()[0].device_kind or "cpu",
    }
    _emit_report({
        "metric": "serving_quant_tok_s",
        "value": quant["tok_s"],
        "unit": f"tok/s int8(w)+int8(KV) at {concurrency_x}x admitted "
                f"concurrency, equal pool bytes (TTFT p99 "
                f"{quant['ttft_p99_ms']} ms = {ttft_p99_x}x base; "
                f"tie-aware agreement "
                f"{extras['agreement']['agreement_tie_aware']})",
        "vs_baseline": None,
        "extras": extras,
    }, "serving_quant")


@scenario("serving_lora", 420)
def serving_lora_main():
    """`python bench.py serving_lora` — the multi-tenant LoRA serving
    instrument (ROADMAP item 4, ISSUE 18): a Poisson mix over 36 tenant
    adapters on ONE ragged engine (`serving.lora.attach_adapters` —
    paged adapter pool + per-lane batched-gather low-rank epilogues).

    The density contract, all asserted in-run: the 36-adapter mix
    sustains >= 80 % of the single-model (no-LoRA) tok/s on the same
    burst; ZERO ragged/sample/switch retraces after warmup — adapter
    identity is data riding the ragged metadata, so any adapter mix
    shares one executable; per-adapter token parity — a tenant's stream
    on the shared engine is bitwise the stream a DEDICATED
    single-adapter engine produces; zero leaked blocks, adapter-pool
    refcount books clean, every request terminal. Gated via
    BaselineStore/bench_diff on tok/s. Run SOLO outside the tier-1
    window (ROADMAP note)."""
    device = _scenario_setup("serving_lora")
    import jax
    import numpy as np

    from paddle_tpu.framework import monitor
    from paddle_tpu.serving import (MLPLMEngine, RequestStatus,
                                    ServingFrontend, ServingMetrics,
                                    attach_adapters)
    from paddle_tpu.serving.lora import random_adapter

    n_adapters = int(os.environ.get("BENCH_LORA_ADAPTERS", "36"))
    n_requests = 2 * n_adapters
    pool_slots = n_adapters + 4      # steady state: whole set resident
    ranks = [2, 3, 4, 6, 8]          # heterogeneous, bucket-padded
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 256, 12).tolist() for _ in range(n_requests)]
    # open-loop Poisson arrivals (deterministic): fast enough that the
    # batch stays packed — the density claim is about a FULL engine
    arrivals = np.cumsum(rng.exponential(0.002, n_requests)).tolist()

    def build():
        return MLPLMEngine(vocab_size=256, hidden=32, max_batch_size=8,
                           num_blocks=192, block_size=8,
                           max_blocks_per_seq=8)

    def build_lora():
        eng = attach_adapters(build(), pool_slots=pool_slots,
                              rank_buckets=(2, 4, 8))
        for i in range(n_adapters):
            eng.adapter_pool.register(
                f"ad{i}", random_adapter(eng, rank=ranks[i % len(ranks)],
                                         seed=i))
        return eng

    def run_burst(engine, adapter_of):
        """Drive the Poisson burst; `adapter_of(i)` names request i's
        adapter (None = base model / baseline engine)."""
        ServingMetrics.reset_monitor()
        fe = ServingFrontend(engine, prefill_chunk_tokens=32)
        pool = getattr(engine, "adapter_pool", None)
        if pool is not None:
            # pre-warm residency: every adapter uploads once here (the
            # slot-scatter executables compile now), so the TIMED mix
            # below serves pure hits — the steady state being measured
            for i in range(n_adapters):
                pool.lease(f"ad{i}")
                pool.release(f"ad{i}")
        for n in (3, 17):      # warm the ragged executable + sampler
            fe.submit(rng.integers(1, 256, n).tolist(), max_new_tokens=2,
                      adapter="ad0" if pool is not None else None)
        fe.run_until_idle(max_steps=500)
        monitor.reset("serving.ragged_retraces")
        monitor.reset("serving.sample_retraces")
        monitor.reset("serving.lora.switch_retraces")
        fe.metrics.reset_window()
        base_tokens = monitor.get("serving.tokens_generated")

        def submit_one(i):
            return fe.submit(prompts[i], max_new_tokens=8,
                             adapter=adapter_of(i))
        handles, wall = _drive_poisson(fe, arrivals, submit_one)
        done = sum(h.status is RequestStatus.FINISHED for h in handles)
        tokens = monitor.get("serving.tokens_generated") - base_tokens \
            + done  # + the prefill-sampled first tokens
        ttfts = sorted(t for t in (h.ttft_ms() for h in handles)
                       if t is not None)
        leaked = fe.scheduler.kv_leaked_blocks()
        fe.scheduler.engine.manager.check_consistency()
        out = {
            "tok_s": round(tokens / wall, 1),
            "wall_s": round(wall, 2),
            "completed": done,
            "ttft_p99_ms": round(float(np.percentile(
                np.asarray(ttfts), 99)), 3),
            "ttft_p50_ms": round(float(np.percentile(
                np.asarray(ttfts), 50)), 3),
            "ragged_retraces": monitor.get("serving.ragged_retraces"),
            "sample_retraces": monitor.get("serving.sample_retraces"),
            "switch_retraces": monitor.get(
                "serving.lora.switch_retraces"),
            "miss_loads_timed": monitor.get("serving.lora.miss_loads")
            - (n_adapters if pool is not None else 0),
            "leaked_blocks": leaked,
            "preemptions": monitor.get("serving.preemptions"),
        }
        if pool is not None:
            pool.check_consistency()
            out["pool"] = pool.stats()
            assert pool.leases() == 0, out["pool"]
        return out

    mix = run_burst(build_lora(), lambda i: f"ad{i % n_adapters}")
    base = run_burst(build(), lambda i: None)

    # per-adapter token parity: the shared multi-adapter engine must
    # give each tenant bitwise the stream of a DEDICATED engine serving
    # only that adapter (same base weights — MLPLMEngine init is
    # seed-deterministic; same greedy sampling)
    parity_adapters = ["ad0", "ad7", "ad23"][:min(3, n_adapters)]
    parity_prompt = prompts[0]

    def greedy_tokens(engine, adapter, n_lanes_busy=1):
        fe = ServingFrontend(engine, prefill_chunk_tokens=32)
        hs = [fe.submit(parity_prompt, max_new_tokens=8, adapter=adapter)
              for _ in range(n_lanes_busy)]
        fe.run_until_idle(max_steps=2000)
        assert all(h.status is RequestStatus.FINISHED for h in hs), \
            [(h.status, h._req.finish_reason) for h in hs]
        return [h.tokens for h in hs]

    shared = build_lora()
    parity = {}
    for name in parity_adapters:
        dedicated = attach_adapters(build(), pool_slots=2,
                                    rank_buckets=(2, 4, 8))
        i = int(name[2:])
        dedicated.adapter_pool.register(
            name, random_adapter(dedicated, rank=ranks[i % len(ranks)],
                                 seed=i))
        ded_toks = greedy_tokens(dedicated, name)[0]
        # on the SHARED engine the same request runs in a mixed batch:
        # two other tenants occupy neighbor lanes concurrently
        others = [a for a in parity_adapters if a != name][:2]
        fe = ServingFrontend(shared, prefill_chunk_tokens=32)
        hs = [fe.submit(parity_prompt, max_new_tokens=8, adapter=a)
              for a in [name] + others]
        fe.run_until_idle(max_steps=2000)
        assert all(h.status is RequestStatus.FINISHED for h in hs)
        parity[name] = (hs[0].tokens == ded_toks)
        assert parity[name], \
            f"{name}: shared {hs[0].tokens} != dedicated {ded_toks}"

    tok_s_x = round(mix["tok_s"] / base["tok_s"], 3)
    # hard in-run checks: the acceptance contract (ISSUE 18)
    assert n_adapters >= 32, n_adapters
    assert mix["completed"] == n_requests and \
        base["completed"] == n_requests, (mix, base)
    assert tok_s_x >= 0.8, \
        f"{n_adapters}-adapter mix tok/s {mix['tok_s']} < 0.8x " \
        f"single-model {base['tok_s']}"
    assert mix["ragged_retraces"] == 0 and mix["sample_retraces"] == 0 \
        and mix["switch_retraces"] == 0, mix
    assert mix["miss_loads_timed"] == 0, mix   # whole set stayed resident
    assert mix["leaked_blocks"] == 0 and base["leaked_blocks"] == 0
    assert mix["pool"]["resident_adapters"] == n_adapters, mix["pool"]

    extras = {
        "adapters": n_adapters,
        "requests": n_requests,
        "pool_slots": pool_slots,
        "rank_buckets": [2, 4, 8],
        "ranks": ranks,
        "mix": mix,
        "single_model": base,
        "tok_s_x": tok_s_x,
        "parity": parity,
        "device": device,
        "device": jax.devices()[0].device_kind or "cpu",
    }
    _emit_report({
        "metric": "serving_lora_tok_s",
        "value": mix["tok_s"],
        "unit": f"tok/s over a {n_adapters}-adapter Poisson mix "
                f"({tok_s_x}x single-model; switch retraces "
                f"{mix['switch_retraces']}, per-adapter parity "
                f"{all(parity.values())})",
        "vs_baseline": None,
        "extras": extras,
    }, "serving_lora")


@scenario("serving_fleet", 420)
def serving_fleet_main():
    """`python bench.py serving_fleet` — the multi-replica ROUTER scaling
    instrument (ROADMAP item 5 / fleet serving): aggregate tok/s and p99
    TTFT for the same request burst served by 1, 2, and 4 `FleetRouter`
    replicas, with the scaling ratios as the gated contract.

    What it measures: the fleet CONTROL PLANE. Each replica's engine
    carries a simulated per-dispatch device-latency floor
    (`BENCH_FLEET_STEP_LATENCY_MS`, GIL-released, emulating the
    accelerator wall a real per-chip replica spends its step in), so a
    2-core CI box measures what production cares about — whether the
    router's placement, membership, and drain bookkeeping serialize
    replica progress. Near-linear scaling (>=1.7x at 2, >=3x at 4)
    holds only while the router's per-step host work stays a small
    fraction of the replica step; a regression here means fleet
    dispatch got heavier, exactly what the gate should catch.

    Run SOLO, outside the tier-1 window (the 870 s box truncates).
    """
    device = _scenario_setup("serving_fleet")
    import jax
    import numpy as np

    from paddle_tpu.framework import monitor
    from paddle_tpu.serving import (FleetRouter, MLPLMEngine,
                                    RequestStatus, ServingMetrics)

    lat_ms = float(os.environ.get("BENCH_FLEET_STEP_LATENCY_MS", "100"))
    n_req = int(os.environ.get("BENCH_FLEET_REQUESTS", "64"))
    max_new = int(os.environ.get("BENCH_FLEET_MAX_NEW", "8"))
    counts = [int(c) for c in os.environ.get(
        "BENCH_FLEET_REPLICAS", "1,2,4").split(",")]
    min_scale = {2: float(os.environ.get("BENCH_FLEET_MIN_SCALE_2X", "1.7")),
                 4: float(os.environ.get("BENCH_FLEET_MIN_SCALE_4X", "3.0"))}

    class _DeviceLatencyEngine:
        """MLP engine whose ragged dispatch takes a FIXED wall time:
        compute runs for real (synced), then a deadline-corrected sleep
        (GIL-released) tops the dispatch up to `latency_s` — the
        fixed-shape-executable timing profile of a real accelerator
        step. Replica "device time" therefore overlaps across threads
        exactly the way per-chip replicas overlap, and compute/dispatch
        jitter is absorbed into the floor instead of compounding with
        thread-scheduler noise."""

        def __init__(self, inner, latency_s):
            self._inner = inner
            self._lat = latency_s

        def __getattr__(self, name):
            return getattr(self._inner, name)

        def sampled_step(self, *args):
            t0 = time.perf_counter()
            out = self._inner.sampled_step(*args)
            jax.block_until_ready(out)
            time.sleep(max(0.0, self._lat
                           - (time.perf_counter() - t0)))
            return out

        def respawn(self):
            return _DeviceLatencyEngine(self._inner.respawn(), self._lat)

    def factory():
        return _DeviceLatencyEngine(
            MLPLMEngine(vocab_size=256, hidden=32, max_batch_size=8,
                        num_blocks=160, block_size=4, max_blocks_per_seq=8,
                        seed=0), lat_ms / 1e3)

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 256, int(rng.integers(4, 10))).tolist()
               for _ in range(n_req)]

    trials = int(os.environ.get("BENCH_FLEET_TRIALS", "3"))

    def burst(router, n):
        """One measured burst on a warm router; returns the trial dict."""
        hs = [router.submit(p, max_new_tokens=max_new) for p in prompts]
        t0 = time.perf_counter()
        steps = router.run_until_idle()
        wall = time.perf_counter() - t0
        bad = [h for h in hs if h.status is not RequestStatus.FINISHED]
        assert not bad, f"fleet[{n}]: non-finished requests {bad[:3]}"
        fs = router.fleet_summary()
        assert fs["counters"].get("fleet.replica_deaths", 0) == 0 \
            and fs["counters"].get("fleet.relocations", 0) == 0, \
            f"fleet[{n}]: clean run saw deaths/relocations {fs}"
        toks = sum(len(h.tokens) for h in hs)
        ttfts = [h.ttft_ms() for h in hs if h.ttft_ms() is not None]
        return {
            "replicas": n,
            "tok_s": round(toks / wall, 1),
            "wall_s": round(wall, 2),
            "steps": steps,
            "tokens": toks,
            "ttft_p50_ms": round(float(np.percentile(ttfts, 50)), 1),
            "ttft_p99_ms": round(float(np.percentile(ttfts, 99)), 1),
            "straggler_spread_pct": fs["step_wall_spread_pct"],
        }

    # PAIRED trials (the PR 6 overload-bench convention): each trial
    # measures EVERY replica count back-to-back on pre-warmed routers,
    # so a slow-box epoch hits the trial's baseline and its fleet runs
    # alike and cancels out of the ratio; the gated scaling is the
    # MEDIAN paired ratio. Unpaired best-of-N still let a lucky
    # 1-replica trial divide an unlucky 4-replica trial (observed ±10%
    # interference on a contended 2-core box -> spurious ratio misses).
    ServingMetrics.reset_monitor()
    monitor.reset_prefix("fleet.")
    routers = {}
    try:
        for n in counts:
            # relaxed membership cadence: at a 100 ms step, the default
            # heartbeat-every-8-steps file lock/write lands mid-burst
            # often enough for a slow disk to show up in the walls
            router = FleetRouter(factory, num_replicas=n, parallel=True,
                                 heartbeat_every=64, sweep_every=512)
            routers[n] = router
            for p in prompts[:2 * n]:   # warm executables + step pool
                router.submit(p, max_new_tokens=2)
            router.run_until_idle()
        trial_runs = [{n: burst(routers[n], n) for n in counts}
                      for _ in range(trials)]
    finally:
        for router in routers.values():
            router.close()
    ratios = {n: sorted(t[n]["tok_s"] / t[counts[0]]["tok_s"]
                        for t in trial_runs) for n in counts}
    scaling = {n: round(ratios[n][len(ratios[n]) // 2], 2)
               for n in counts}        # median paired ratio
    # per-count report: the best trial (capability), scaling from pairs
    runs = {n: max((t[n] for t in trial_runs),
                   key=lambda r: r["tok_s"]) for n in counts}
    for n, bar in min_scale.items():
        if n in runs:
            assert scaling[n] >= bar, \
                f"fleet scaling at {n} replicas {scaling[n]}x < {bar}x " \
                f"(paired-trial median; router host work is " \
                f"serializing replica steps)"
    top = max(counts)
    extras = {
        "runs": {str(n): runs[n] for n in counts},
        "scaling_2x": scaling.get(2),
        "scaling_4x": scaling.get(4),
        "ttft_p99_ms": runs[top]["ttft_p99_ms"],
        "simulated_step_latency_ms": lat_ms,
        "requests": n_req,
        "device": device,
        "device": jax.devices()[0].device_kind or "cpu",
    }
    _emit_report({
        "metric": "serving_fleet_tok_s",
        "value": runs[top]["tok_s"],
        "unit": f"fleet tok/s at {top} replicas "
                f"(scaling 1->{top}: {scaling[top]}x, "
                f"p99 TTFT {runs[top]['ttft_p99_ms']} ms, "
                f"{lat_ms} ms simulated device step)",
        "vs_baseline": None,
        "extras": extras,
    }, "serving_fleet")


@scenario("serving_tp", 420)
def serving_tp_main():
    """`python bench.py serving_tp` — TP-sharded serving (ISSUE 16):
    tok/s scaling at tp=1/2/4 on the 8-virtual-device CPU mesh at FIXED
    per-request work, the overlap-vs-sequential exposed-comm A/B, and
    the sharded decode program's HLO collective census.

    What it measures: the TP CONTROL + COLLECTIVE plane. Each engine
    carries a simulated per-dispatch device-latency floor (the
    `serving_fleet` convention): the single-chip floor is L and the
    tp-degree-t floor is L/t — the fixed-shape profile of a decode step
    whose gemm and KV bytes split t ways — so a 2-core CI box measures
    what production cares about: whether the sharded dispatch, the
    shard_map program, and the scheduler's replicated bookkeeping eat
    the per-chip win. Scaling holds only while the host-side step work
    stays a small fraction of the per-chip step; the exposed-ms A/B is
    real (the sequential mode's host logit assembly IS the exposed leg
    the in-program tiled psums + device all-gather delete).

    In-run contracts (acceptance, ISSUE 16): tp=1 token parity (greedy
    AND stochastic through the full scheduler), tp=4 scaling >= 2.5x,
    exposed_ms(overlap) strictly < exposed_ms(sequential), zero ragged/
    sample retraces in steady state. CPU mesh by design, like
    `dryrun_multichip`. Run SOLO (the 870 s tier-1 box truncates)."""
    probe = {"ok": False, "scenario": "serving_tp",
             "skipped_reason": "cpu_mesh_by_design"}
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import paddle_tpu.observability as obs
    from paddle_tpu.framework import monitor
    from paddle_tpu.observability import comms
    from paddle_tpu.serving import (MLPLMEngine, RequestStatus,
                                    ServingFrontend, shard_engine)

    assert jax.device_count() >= 8, \
        f"virtual CPU mesh failed to form ({jax.device_count()} devices)"

    lat_ms = float(os.environ.get("BENCH_TP_STEP_LATENCY_MS", "40"))
    n_req = int(os.environ.get("BENCH_TP_REQUESTS", "48"))
    max_new = int(os.environ.get("BENCH_TP_MAX_NEW", "8"))
    trials = int(os.environ.get("BENCH_TP_TRIALS", "3"))
    min_scale4 = float(os.environ.get("BENCH_TP_MIN_SCALE_4X", "2.5"))
    tiles = int(os.environ.get("BENCH_TP_OVERLAP_TILES", "3"))
    kw = dict(vocab_size=128, hidden=32, max_batch_size=8, num_blocks=160,
              block_size=4, max_blocks_per_seq=8, seed=0)

    class _LatencyFloor:
        """Fixed-wall ragged dispatch (the `serving_fleet`
        `_DeviceLatencyEngine` convention): compute runs for real
        (synced), a deadline-corrected GIL-released sleep tops the
        dispatch up to `latency_s`. The floor scales 1/tp — fixed
        per-request work split over the mesh."""

        def __init__(self, inner, latency_s):
            self._inner = inner
            self._lat = latency_s

        def __getattr__(self, name):
            return getattr(self._inner, name)

        def sampled_step(self, *args):
            t0 = time.perf_counter()
            out = self._inner.sampled_step(*args)
            jax.block_until_ready(out)
            time.sleep(max(0.0, self._lat - (time.perf_counter() - t0)))
            return out

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 128, int(rng.integers(4, 10))).tolist()
               for _ in range(n_req)]

    # ---- tp=1 token parity, greedy AND stochastic, full scheduler ----
    def tokens_of(engine):
        fe = ServingFrontend(engine)
        hs = [fe.submit(p, max_new_tokens=max_new,
                        temperature=(0.8 if i % 2 else 0.0), seed=i)
              for i, p in enumerate(prompts[:12])]
        fe.run_until_idle(max_steps=4000)
        assert all(h.status is RequestStatus.FINISHED for h in hs)
        return [list(h.tokens) for h in hs]

    parity_ok = tokens_of(MLPLMEngine(**kw)) == tokens_of(
        shard_engine(MLPLMEngine(**kw), tp=1, overlap=True,
                     overlap_tiles=tiles))
    assert parity_ok, "tp=1 sharded engine diverged from single-chip " \
        "tokens through the scheduler (bitwise contract)"

    # ---- tok/s scaling at fixed per-request work ----
    def build(tp):
        if tp == 1:
            return _LatencyFloor(MLPLMEngine(**kw), lat_ms / 1e3)
        eng = shard_engine(MLPLMEngine(**kw), tp=tp, overlap=True,
                           overlap_tiles=tiles)
        return _LatencyFloor(eng, lat_ms / 1e3 / tp)

    fes = {tp: ServingFrontend(build(tp)) for tp in (1, 2, 4)}
    for fe in fes.values():                      # pay the compiles
        for p in prompts[:8]:
            fe.submit(p, max_new_tokens=2)
        fe.run_until_idle(max_steps=2000)
    for c in ("serving.decode_retraces", "serving.ragged_retraces",
              "serving.sample_retraces"):
        monitor.reset(c)

    def burst(fe):
        hs = [fe.submit(p, max_new_tokens=max_new) for p in prompts]
        t0 = time.perf_counter()
        fe.run_until_idle(max_steps=20000)
        wall = time.perf_counter() - t0
        assert all(h.status is RequestStatus.FINISHED for h in hs)
        return round(sum(len(h.tokens) for h in hs) / wall, 1)

    # PAIRED trials (serving_fleet convention): every tp degree runs
    # back-to-back inside one trial so slow-box epochs cancel out of the
    # ratio; the gated scaling is the median paired ratio
    trial_runs = [{tp: burst(fes[tp]) for tp in (1, 2, 4)}
                  for _ in range(trials)]
    ratios = {tp: sorted(t[tp] / t[1] for t in trial_runs)
              for tp in (2, 4)}
    scaling = {tp: round(r[len(r) // 2], 2) for tp, r in ratios.items()}
    tok_s = {tp: max(t[tp] for t in trial_runs) for tp in (1, 2, 4)}
    retraces = {c: monitor.get(c) for c in
                ("serving.decode_retraces", "serving.ragged_retraces",
                 "serving.sample_retraces")}
    assert not any(retraces.values()), \
        f"steady-state recompiles under TP: {retraces}"
    assert scaling[4] >= min_scale4, \
        f"tp=4 scaling {scaling[4]}x < {min_scale4}x (sharded dispatch " \
        f"or replicated bookkeeping is eating the per-chip win)"

    # ---- exposed-comm A/B: tiled-psum overlap vs sequential ----
    # bigger vocab so the sequential mode's host logit assembly (its
    # exposed leg) is well above timer noise
    kw_ab = dict(kw, vocab_size=2048)
    ab_engines = {
        "overlap": shard_engine(MLPLMEngine(**kw_ab), tp=2, overlap=True,
                                overlap_tiles=tiles),
        "sequential": shard_engine(MLPLMEngine(**kw_ab), tp=2,
                                   overlap=False),
    }

    def ab_args(step):
        q = np.array([1, 1, 1, 1, 2, 0, 0, 0], np.int32)
        kv = np.array([3 + step, 2 + step, 1 + step, 4 + step, 2, 0, 0, 0],
                      np.int32)
        toks = (np.arange(16, dtype=np.int32) * 5 + step) % 128
        tables = np.arange(64, dtype=np.int32).reshape(8, 8)
        return toks, q, kv, tables

    exposed = {}
    obs.enable()
    try:
        obs.reset()
        for mode, eng in ab_engines.items():
            eng.ragged_step(*ab_args(0))         # warm the executable
            samples = []
            for step in range(8):
                eng.ragged_step(*ab_args(step + 1))
                samples.append(monitor.get("comm.exposed_ms_per_step"))
            samples.sort()
            exposed[mode] = samples[len(samples) // 2]
    finally:
        obs.disable()
    assert exposed["overlap"] < exposed["sequential"], \
        f"overlapped decode exposes {exposed['overlap']} ms/step, not " \
        f"strictly below the sequential baseline " \
        f"{exposed['sequential']} ms/step"

    # ---- compiled census + per-chip cost card (lowering re-traces, so
    # this runs AFTER the retrace assertion collected its counters) ----
    extras = {
        "tok_s": {str(tp): tok_s[tp] for tp in (1, 2, 4)},
        "scaling_tp2": scaling[2],
        "scaling_tp4": scaling[4],
        "exposed_ms_per_step": exposed["overlap"],
        "exposed_ms_per_step_sequential": exposed["sequential"],
        "retraces_after_warmup": retraces,
        "tp1_token_parity": parity_ok,
        "simulated_step_latency_ms": lat_ms,
        "requests": n_req,
        "tp_summary": ab_engines["overlap"].tp_summary(),
        "probe": probe,
    }
    try:
        from paddle_tpu.observability import costs as _costs
        from paddle_tpu.ops.sampling import step_args

        eng = ab_engines["overlap"]
        fn, lead = eng.cost_card_args("ragged")
        args = (*lead, *step_args(*ab_args(0)))
        extras["hlo_collectives"] = comms.hlo_comm_census(
            fn.lower(*args).compile().as_text())
        card = _costs.card_from_lowered(fn, *args)
        if card.flops:
            extras["decode_cost_per_chip"] = {
                "flops_per_step": card.flops,
                "bytes_accessed_per_step": card.bytes_accessed}
    except Exception as e:  # census is evidence, not the contract
        extras["hlo_collectives"] = f"{type(e).__name__}: {str(e)[:120]}"
    _emit_report({
        "metric": "serving_tp_tok_s",
        "value": tok_s[4],
        "unit": f"tok/s at tp=4 (scaling 1->4: {scaling[4]}x, 1->2: "
                f"{scaling[2]}x, exposed {exposed['overlap']} vs "
                f"{exposed['sequential']} ms/step seq, {lat_ms} ms "
                f"simulated single-chip step)",
        "vs_baseline": None,
        "extras": extras,
    }, "serving_tp")


@scenario("serving_disagg", 420)
def serving_disagg_main():
    """`python bench.py serving_disagg` — the disaggregated-serving
    acceptance instrument (ISSUE 17): 2 prefill + 2 decode replicas vs 4
    colocated replicas on the SAME deterministic trace (steady decode
    lanes, then a long-prompt storm).

    What it measures: the tier isolation the architecture buys. Each
    replica's engine carries a simulated device-latency profile — a
    fixed decode-step floor plus a per-prefill-token surcharge
    (deadline-corrected GIL-released sleep, the `serving_fleet`
    convention) — so a CPU CI box reproduces the interference physics:
    a replica whose ragged round carries prefill chunks stretches every
    decode lane sharing that round. Colocated, the storm lands on every
    replica and steady-lane TPOT inflates toward the `serving_mixed`
    floor (>= 1.10x asserted — without the contrast the headline is
    meaningless). Disaggregated, the decode tier never sees a prompt
    chunk and its storm-window TPOT must hold <= 1.02x steady.

    Decode TPOT is measured per REPLICA step wall (a running lane
    commits exactly one token per its replica's round), so the
    synchronous router driver's barrier doesn't leak the prefill tier's
    wall into the decode tier's number. Fleet efficiency is gated as
    tokens per device-busy-second (the device-time a fleet actually
    pays for): disaggregation packs the decode tier denser, so it must
    be >= the colocated run's. Also asserted in-run: zero ragged
    retraces on BOTH tiers across the measured windows, and every
    steady lane finishing on the decode tier with bitwise-identical
    streams across the two configs.

    Run SOLO, outside the tier-1 window (the 870 s box truncates).
    """
    device = _scenario_setup("serving_disagg")
    import jax
    import numpy as np

    from paddle_tpu.framework import monitor
    from paddle_tpu.serving import (DisaggRouter, FleetRouter,
                                    HandoffState, MLPLMEngine,
                                    RequestStatus, ServingMetrics)

    decode_ms = float(os.environ.get("BENCH_DISAGG_DECODE_MS", "25"))
    prefill_tok_ms = float(
        os.environ.get("BENCH_DISAGG_PREFILL_TOK_MS", "0.5"))
    storm_len = int(os.environ.get("BENCH_DISAGG_STORM_PROMPT", "192"))
    chunk = int(os.environ.get("BENCH_DISAGG_CHUNK", "32"))
    n_lanes = int(os.environ.get("BENCH_DISAGG_LANES", "8"))
    n_storm = int(os.environ.get("BENCH_DISAGG_STORM", "8"))
    # long enough that every steady lane outlives the whole storm
    # window — the TPOT samples must come from RUNNING decode lanes
    steady_new = int(os.environ.get("BENCH_DISAGG_MAX_NEW", "96"))
    max_tpot_x = float(os.environ.get("BENCH_DISAGG_MAX_TPOT_X", "1.02"))
    min_colo_x = float(os.environ.get("BENCH_DISAGG_MIN_COLO_X", "1.10"))

    class _InterferenceEngine:
        """MLP engine whose ragged dispatch walls like a real chip:
        `decode_s` floor per round, plus `tok_s` per prefill token in
        the round (lanes with q > 1). Decode-only rounds stay at the
        floor; prefill-carrying rounds stretch — the interference the
        disaggregation is supposed to remove. `busy_s` accumulates the
        device-busy wall this replica actually spent."""

        def __init__(self, inner, decode_s, tok_s):
            self._inner = inner
            self._decode_s = decode_s
            self._tok_s = tok_s
            self.busy_s = 0.0
            self.walls_ms = []          # per-dispatch device wall

        def __getattr__(self, name):
            return getattr(self._inner, name)

        def sampled_step(self, tokens, lanes, tables, temperature):
            t0 = time.perf_counter()
            out = self._inner.sampled_step(tokens, lanes, tables,
                                           temperature)
            jax.block_until_ready(out)
            compute = time.perf_counter() - t0
            q = np.asarray(lanes)[:, 0]           # q_lens
            target = self._decode_s + self._tok_s * int(q[q > 1].sum())
            time.sleep(max(0.0, target - compute))
            # the DEVICE wall is the simulated profile (or the real
            # compute when it spills past it) — sleep overshoot under
            # host thread contention is emulator noise, not serving
            # behavior, and must not leak into the TPOT samples
            wall = max(target, compute)
            self.busy_s += wall
            self.walls_ms.append(wall * 1e3)
            return out

        def respawn(self):
            e = _InterferenceEngine(self._inner.respawn(),
                                    self._decode_s, self._tok_s)
            e.busy_s = self.busy_s
            return e

    def make_factory(pool):
        def factory():
            e = _InterferenceEngine(
                MLPLMEngine(vocab_size=256, hidden=32, max_batch_size=8,
                            num_blocks=320, block_size=4,
                            max_blocks_per_seq=64, seed=0),
                decode_ms / 1e3, prefill_tok_ms / 1e3)
            pool.append(e)
            return e
        return factory

    rng = np.random.default_rng(0)
    lane_ps = [rng.integers(1, 256, 12).tolist() for _ in range(n_lanes)]
    storm_ps = [rng.integers(1, 256, storm_len).tolist()
                for _ in range(n_storm)]
    fkw = dict(prefill_chunk_tokens=chunk)

    def run_config(router, engines, lanes_on):
        """The shared trace on a warm router. Returns (tpot dict,
        token-streams, tokens); device-busy is read by the caller."""
        # warm EVERY replica's executables + (disagg) the handoff
        # gather/scatter pair; least-loaded placement spreads these
        for p in (lane_ps * 2)[:2 * len(router.replicas)]:
            router.submit(p, max_new_tokens=2)
        router.run_until_idle()
        monitor.reset("serving.ragged_retraces")
        lanes = [router.submit(p, max_new_tokens=steady_new)
                 for p in lane_ps]
        # settle: prefills done, (disagg) every lane handed off — the
        # measured windows see pure steady-state decode placement
        for _ in range(400):
            if all(len(h._req.generated) >= 2 and h._replica is not None
                   and lanes_on(h) for h in lanes):
                break
            router.step()
        else:
            raise RuntimeError("steady lanes never settled")
        # decode TPOT = the DEVICE dispatch wall of the replicas hosting
        # the steady lanes (a running lane commits one token per its
        # replica's dispatch): spawn order == factory-call order, so
        # replicas zip with the engine pool
        eng_by_id = {rep.replica_id: e
                     for rep, e in zip(router.replicas, engines)}
        hosts = [eng_by_id[h._replica.replica_id]
                 for h in lanes if h._replica is not None]
        hosts = list({id(e): e for e in hosts}.values())

        def window(until):
            marks = [len(e.walls_ms) for e in hosts]
            for _ in range(2000):
                if until():
                    break
                router.step()
            else:
                raise RuntimeError("measurement window never completed")
            return [w for e, m in zip(hosts, marks)
                    for w in e.walls_ms[m:]]

        rounds = iter(range(20))
        steady = window(lambda: next(rounds, None) is None)
        storm = [router.submit(p, max_new_tokens=2) for p in storm_ps]
        # a request's _prefill_ctx only materializes when first
        # scheduled (the serving_mixed guard): unscheduled != done
        still_prefilling = lambda h: not h.status.terminal and (  # noqa: E731
            h._req.prefilling or not h._req._prefill_ctx.size)
        during = window(
            lambda: not any(still_prefilling(h) for h in storm))
        router.run_until_idle()
        hs = lanes + storm
        bad = [h for h in hs if h.status is not RequestStatus.FINISHED]
        assert not bad, f"non-finished requests: {bad[:3]}"
        assert len(during) >= 8, \
            f"storm window produced {len(during)} decode-lane TPOT " \
            f"samples: lanes died before the storm, nothing was measured"
        p99 = lambda xs: float(np.percentile(np.asarray(xs), 99))  # noqa: E731
        tpot = {
            "steady_tpot_p99_ms": round(p99(steady), 3),
            "storm_tpot_p99_ms": round(p99(during), 3),
            "tpot_degradation_x": round(p99(during) / p99(steady), 3),
            "storm_rounds": len(during),
        }
        return tpot, [h.tokens for h in lanes], sum(
            len(h.tokens) for h in hs)

    results = {}
    for mode in ("disagg", "colocated"):
        ServingMetrics.reset_monitor()
        monitor.reset_prefix("fleet.")
        engines = []
        if mode == "disagg":
            router = DisaggRouter(make_factory(engines), num_prefill=2,
                                  num_decode=2, parallel=True,
                                  heartbeat_every=64, sweep_every=512,
                                  frontend_kwargs=fkw)
            decode_tier = set(router.fleet_summary()["tiers"]["decode"])
            lanes_on = lambda h: (h._replica.replica_id  # noqa: E731
                                  in decode_tier)
        else:
            router = FleetRouter(make_factory(engines), num_replicas=4,
                                 parallel=True, heartbeat_every=64,
                                 sweep_every=512, frontend_kwargs=fkw)
            lanes_on = lambda h: True  # noqa: E731
        try:
            tpot, streams, toks = run_config(router, engines, lanes_on)
            retraces = monitor.get("serving.ragged_retraces")
            fs = router.fleet_summary()
            if mode == "disagg":
                assert fs["counters"].get("fleet.handoffs", 0) > 0, \
                    "disagg run moved no sessions prefill->decode"
                assert fs["counters"].get(
                    "fleet.handoff_fallbacks", 0) == 0, \
                    f"clean run fell back to re-prefill: {fs['counters']}"
            assert retraces == 0, \
                f"{mode}: {retraces} ragged retraces in steady state"
            results[mode] = {
                **tpot,
                "tok_per_device_s": round(
                    toks / sum(e.busy_s for e in engines), 1),
                "tokens": toks,
                "handoffs": fs["counters"].get("fleet.handoffs", 0),
                "streams": streams,
            }
        finally:
            router.close()

    dis, colo = results["disagg"], results["colocated"]
    # identical trace, identical greedy streams: disaggregation must be
    # invisible in the tokens
    assert dis.pop("streams") == colo.pop("streams"), \
        "steady-lane streams differ between disagg and colocated"
    assert colo["tpot_degradation_x"] >= min_colo_x, \
        f"colocated floor {colo['tpot_degradation_x']}x < {min_colo_x}x: " \
        f"the storm shows no interference, the contrast is meaningless"
    assert dis["tpot_degradation_x"] <= max_tpot_x, \
        f"decode-tier TPOT degraded {dis['tpot_degradation_x']}x > " \
        f"{max_tpot_x}x under the prefill storm: the tier is not isolated"
    assert dis["tok_per_device_s"] >= colo["tok_per_device_s"], \
        f"disagg fleet efficiency {dis['tok_per_device_s']} tok/device-s " \
        f"< colocated {colo['tok_per_device_s']}: specialization is " \
        f"wasting the fleet"
    extras = {
        "disagg": dis,
        "colocated": colo,
        "tpot_degradation_x": dis["tpot_degradation_x"],
        "colocated_tpot_degradation_x": colo["tpot_degradation_x"],
        "simulated_decode_step_ms": decode_ms,
        "simulated_prefill_tok_ms": prefill_tok_ms,
        "storm_prompt_tokens": storm_len,
        "prefill_chunk_tokens": chunk,
        "device": device,
        "device": jax.devices()[0].device_kind or "cpu",
    }
    _emit_report({
        "metric": "serving_disagg_tok_s",
        "value": dis["tok_per_device_s"],
        "unit": f"fleet tok per device-busy-s, 2 prefill + 2 decode "
                f"(decode TPOT under storm {dis['tpot_degradation_x']}x "
                f"steady vs {colo['tpot_degradation_x']}x colocated; "
                f"{decode_ms} ms simulated decode step)",
        "vs_baseline": None,
        "extras": extras,
    }, "serving_disagg")


@scenario("dryrun_multichip", 300)
def dryrun_multichip_main():
    """`python bench.py dryrun_multichip` — the 8-virtual-device CPU mesh
    dryrun with observability ON (ISSUE 9): per-collective-kind
    byte/wall/algbw counters, per-path comm-volume + exposure reports
    (dp/mp/sp train step, pp pipeline, ep MoE, sep ring attention), the
    HLO collective census of the GSPMD step, a per-device memory + KV
    fragmentation snapshot, and the mesh aggregation snapshot.

    CPU by design: the dryrun validates sharding + observability
    semantics, never the chip (same rationale as `_force_cpu_platform`).
    Gated metrics (`tools/bench_diff.py`): exposed_ms_per_step must not
    grow, traced algbw must not collapse."""
    probe = {"ok": False, "scenario": "dryrun_multichip",
             "skipped_reason": "cpu_mesh_by_design"}
    os.environ["JAX_PLATFORMS"] = "cpu"
    n = int(os.environ.get("BENCH_DRYRUN_DEVICES", "8"))
    import __graft_entry__ as ge  # sibling module; forces n CPU devices

    import paddle_tpu.observability as obs
    from paddle_tpu.framework import monitor
    from paddle_tpu.observability import comms, memory

    obs.enable()
    obs.reset()
    monitor.reset_prefix("comm.")
    import contextlib

    with contextlib.redirect_stdout(sys.stderr):
        # the dryrun's progress prints belong to the driver's artifact;
        # bench stdout stays ONE JSON line
        report = ge.dryrun_multichip(n)
    assert report is not None and report.get("paths"), \
        "dryrun produced no observability report"
    # hard in-run checks: the acceptance contract, not a hopeful print
    snap = monitor.snapshot("comm.", include_histograms=False)
    assert snap.get("comm.all_reduce.bytes", 0) > 0, snap
    assert report["train_step_hlo_collectives"].get("all_reduce", {}) \
        .get("ops", 0) > 0, report["train_step_hlo_collectives"]
    paths = report["paths"]
    exposed_ms = round(sum(p.get("exposed_ms", 0.0)
                           for p in paths.values()) / len(paths), 3)
    # KV fragmentation PROBE: a small paged pool with a guard lease and
    # a freed hole, built here — it demonstrates the fragmentation
    # instrument in the artifact, it is NOT serving-side state (the
    # dryrun has no KV cache); tagged synthetic so nobody chases its
    # constant numbers
    from paddle_tpu.inference.cache import BlockCacheManager

    mgr = BlockCacheManager(num_blocks=32, block_size=4,
                            max_blocks_per_seq=8)
    mgr.allocate(-1, 1)                     # guard (excluded from util)
    for sid, toks in ((1, 10), (2, 12), (3, 17)):
        mgr.allocate(sid, toks)
    mgr.free(2)                             # punch a hole in the free list
    frag = dict(mgr.fragmentation(), synthetic_probe=True)
    extras = {
        "devices": n,
        "exposed_ms_per_step": exposed_ms,
        "algbw_gbs": report["algbw_gbs"],
        "paths": paths,
        "train_step_hlo_collectives": report["train_step_hlo_collectives"],
        "comm_counters": snap,
        "mesh": report["mesh"],
        "device_memory": memory.device_memory_snapshot(),
        "kv_fragmentation_probe": frag,
        "probe": probe,
    }
    overlap_eff = [p.get("overlap_efficiency") for p in paths.values()]
    _emit_report({
        "metric": "dryrun_multichip_comms",
        "value": exposed_ms,
        "unit": f"exposed comm ms/step (mean over {len(paths)} mesh "
                f"paths, overlap eff "
                f"{round(sum(overlap_eff) / len(overlap_eff), 3)}, "
                f"algbw {report['algbw_gbs']} GB/s)",
        "vs_baseline": None,
        "extras": extras,
    }, "dryrun_multichip")


@scenario("train_elastic", 300)
def train_elastic_main():
    """`python bench.py train_elastic` — elastic-training recovery wall
    (ISSUE 15): a supervised sharded train job on the 8-virtual-device
    CPU mesh loses its busiest pod to an armed ``train.step`` kill
    mid-step; the supervisor fences the epoch, re-forms 8 -> 7 under
    quorum, reshards the latest checkpoint onto the surviving mesh, and
    resumes. The gated value is the MIN (over independent trials)
    recovery wall-clock from the injected kill to the FIRST post-resume
    train step — detect + fence + quorum + rebuild/recompile + reshard.
    Min, not median: the wall is ONE XLA recompile at the new world
    size, and on the contended 2-core box the median swings ~2x with
    scheduler interference while the least-contended trial tracks the
    actual cost the code determines (the dryrun convention, one level
    stricter).

    CPU by design (same rationale as `dryrun_multichip`: this validates
    the recovery loop's semantics and wall, never the chip). In-run hard
    asserts: exactly one reform per trial, post-resume losses
    token-for-token equal an unkilled world-7 run from the restored
    step, `elastic.recovery_ms` published, zero quarantined dirs."""
    probe = {"ok": False, "scenario": "train_elastic",
             "skipped_reason": "cpu_mesh_by_design"}
    os.environ["JAX_PLATFORMS"] = "cpu"
    n = int(os.environ.get("BENCH_ELASTIC_DEVICES", "8"))
    import __graft_entry__ as ge

    ge._force_cpu_platform(n)
    import tempfile

    from paddle_tpu.distributed.elastic import (ElasticManager,
                                                MembershipStore)
    from paddle_tpu.framework import monitor
    from paddle_tpu.resilience import (CheckpointManager,
                                       ElasticTrainSupervisor,
                                       make_emulated_trainable, faults)

    steps = int(os.environ.get("BENCH_ELASTIC_STEPS", "12"))
    reps = int(os.environ.get("BENCH_ELASTIC_REPS", "5"))
    kill_at = steps // 2
    pods = [f"pod{i}" for i in range(n)]
    recoveries, trials = [], []
    for rep in range(reps):
        work = tempfile.mkdtemp(prefix=f"bench_train_elastic_{rep}_")
        store = MembershipStore(os.path.join(work, "members.json"),
                                ttl=1000.0)
        mgr = ElasticManager(store, min_nodes=1, max_nodes=n,
                             stabilize_s=0.0, sleep=lambda s: None)
        ckpt = CheckpointManager(os.path.join(work, "ckpt"),
                                 keep_last_n=steps + 1)
        sup = ElasticTrainSupervisor(
            make_emulated_trainable(), mgr, ckpt, pods, min_world=2,
            save_every=1, quorum_deadline_s=5.0)
        sup.start()
        faults.inject("train.step", after_n=kill_at, times=1,
                      action="flag")
        try:
            losses = sup.run(steps)
        finally:
            sup.close()
            faults.clear()
        assert sup.reforms == 1, sup.reforms
        assert len(sup.world) == n - 1, sup.world
        assert sup.last_recovery_ms is not None
        assert monitor.get("elastic.recovery_ms") == sup.last_recovery_ms
        restored = sup.last_restored_step
        # parity: an unkilled world-(n-1) run from the restored
        # checkpoint must produce token-for-token the same losses
        ref_tr = make_emulated_trainable()(sup.world)
        ckpt.load(os.path.join(ckpt.root, f"step_{restored:06d}"),
                  state_dict=ref_tr.state_dict(),
                  placements=ref_tr.placements())
        mism = [i for i in range(restored + 1, steps)
                if repr(ref_tr.step(i)) != repr(losses[i])]
        assert not mism, f"post-resume losses diverged at steps {mism}"
        assert not [d for d in os.listdir(ckpt.root)
                    if d.startswith("QUARANTINED-")]
        recoveries.append(sup.last_recovery_ms)
        trials.append({"recovery_ms": sup.last_recovery_ms,
                       "restored_step": restored,
                       "replayed_steps": steps - restored - 1})
    recovery_ms = min(recoveries)
    extras = {
        "devices": n, "steps": steps, "kill_at": kill_at,
        "world": f"{n}->{n - 1}", "trials": trials, "reps": reps,
        "recovery_ms_median": sorted(recoveries)[len(recoveries) // 2],
        "parity": "bitwise", "probe": probe,
    }
    _emit_report({
        "metric": "train_elastic_recovery_ms",
        "value": recovery_ms,
        "unit": f"ms kill->first post-resume step (min of {reps}, "
                f"world {n}->{n - 1}, reshard-on-load)",
        "vs_baseline": None,
        "extras": extras,
    }, "train_elastic")


def build_train_step(model):
    """The measured train step over `model`: `functional_call` +
    `jax.value_and_grad` + a hand-written AdamW, meant to run under ONE
    donated `jax.jit` (`donate_argnums=(0, 1, 2)`). Returns
    (train_step, bf16 params, f32 m_state, f32 v_state). `chip_smoke.py`
    takes its few steps through this same function."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.jit import functional_call, state_arrays

    params = {k: v.astype(jnp.bfloat16)
              for k, v in state_arrays(model).items()}
    m_state = {k: jnp.zeros(v.shape, jnp.float32) for k, v in params.items()}
    v_state = {k: jnp.zeros(v.shape, jnp.float32) for k, v in params.items()}

    def train_step(params, m_state, v_state, step, ids, labels):
        def loss_fn(p):
            loss, _ = functional_call(model, p, Tensor(ids),
                                      labels=Tensor(labels))
            return loss._data.astype(jnp.float32)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        b1, b2, lr, eps, wd = 0.9, 0.95, 3e-4, 1e-8, 0.1
        new_p, new_m, new_v = {}, {}, {}
        with jax.named_scope("adamw"):
            for k in params:
                g = grads[k].astype(jnp.float32)
                new_m[k] = b1 * m_state[k] + (1 - b1) * g
                new_v[k] = b2 * v_state[k] + (1 - b2) * g * g
                mhat = new_m[k] / (1 - b1 ** step)
                vhat = new_v[k] / (1 - b2 ** step)
                pf = params[k].astype(jnp.float32)
                pf = pf - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * pf)
                new_p[k] = pf.astype(params[k].dtype)
        return loss, new_p, new_m, new_v

    return train_step, params, m_state, v_state


@scenario("train_mfu", 900)
def train_mfu_main():
    extras = {"device": _scenario_setup("train_mfu")}

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    # a CPU has no peak on record, hence no MFU: the scenario still runs
    # there (the caller asked for it) and reports the figure as unmeasured
    peak = _peak_flops(dev) if on_tpu else None
    # Configs in preference order: (layers, batch, remat). Remat-off wins
    # ~5 MFU points when activations fit (measured on v5e 16G); fall through
    # on RESOURCE_EXHAUSTED.
    if on_tpu:
        # Layer count / remat fitted to the chip's HBM (state is ~10 B/param:
        # bf16 p + f32 m,v; one 7B layer is 202.6M params -> ~2 GB + grads).
        hbm = int(dev.memory_stats()["bytes_limit"])
        extras["hbm_bytes"] = hbm
        if hbm >= 90 << 30:       # v5p class
            tries = [(16, 4, False), (24, 4, True), (8, 2, False),
                     (4, 2, True)]
        elif hbm >= 28 << 30:     # v6e class
            tries = [(6, 2, False), (8, 2, True), (4, 2, True),
                     (2, 2, False)]
        else:                     # v5e 16G
            tries = [(2, 2, False), (4, 2, True), (2, 2, True),
                     (1, 2, True)]
        seq, steps = 2048, 10
        base_cfg = dict(vocab_size=32000, hidden_size=4096,
                        intermediate_size=11008, num_attention_heads=32,
                        max_position_embeddings=2048)
    else:
        tries = [(2, 2, False)]
        seq, steps = 128, 3
        base_cfg = dict(vocab_size=256, hidden_size=64,
                        intermediate_size=172, num_attention_heads=4,
                        max_position_embeddings=128)

    def build(n_layers, batch, remat):
        cfg = LlamaConfig(num_hidden_layers=n_layers, **base_cfg)
        model = LlamaForCausalLM(cfg)
        model.train()
        model.llama.remat = remat
        return (model,) + build_train_step(model)

    rng = np.random.default_rng(0)

    def run_config(n_layers, batch, remat, count_pallas=False,
                   breakdown=False):
        """Measure one (layers, batch, remat) config; returns
        (model, dt_seconds, loss, breakdown_dict|None, CostCard|None).
        Raises on OOM. The step executable is compiled AOT
        (`lower().compile()`) so the SAME executable yields both the
        timing and the compiler's cost_analysis — no second compile, and
        the reported FLOPs are exactly what ran."""
        model, train_step, params, m_state, v_state = build(
            n_layers, batch, remat)
        ids = jnp.asarray(rng.integers(0, base_cfg["vocab_size"],
                                       (batch, seq)))
        labels = jnp.asarray(rng.integers(0, base_cfg["vocab_size"],
                                          (batch, seq)))
        bd = None
        if breakdown:
            # profiler-style step decomposition: time fwd-only, fwd+bwd, and
            # the full step as separate jitted programs; bwd/opt come out by
            # subtraction (BASELINE.md protocol "step time breakdown").
            from paddle_tpu.core.tensor import Tensor
            from paddle_tpu.jit import functional_call

            def fwd_only(params, ids, labels):
                loss, _ = functional_call(model, params, Tensor(ids),
                                          labels=Tensor(labels))
                return loss._data.astype(jnp.float32)

            def fwd_bwd(params, ids, labels):
                return jax.value_and_grad(
                    lambda p: fwd_only(p, ids, labels))(params)

            def timeit(fn, *args, reps=5):
                r = fn(*args)
                jax.block_until_ready(r)
                t0 = time.perf_counter()
                for _ in range(reps):
                    r = fn(*args)
                jax.block_until_ready(r)
                return (time.perf_counter() - t0) / reps * 1e3

            fwd_ms = timeit(jax.jit(fwd_only), params, ids, labels)
            fwdbwd_ms = timeit(jax.jit(fwd_bwd), params, ids, labels)
            bd = {"fwd_ms": round(fwd_ms, 1),
                  "bwd_ms": round(fwdbwd_ms - fwd_ms, 1)}
        step_fn = jax.jit(train_step, donate_argnums=(0, 1, 2))
        if count_pallas:
            extras["pallas_custom_calls"] = _count_pallas_calls(
                step_fn, params, m_state, v_state, 1.0, ids, labels)
        from paddle_tpu.observability.costs import CostCard

        step_call = step_fn.lower(params, m_state, v_state, 1.0, ids,
                                  labels).compile()
        card = CostCard.from_compiled(step_call)
        loss, params, m_state, v_state = step_call(
            params, m_state, v_state, 1.0, ids, labels)
        jax.block_until_ready(loss)
        import paddle_tpu.observability as _obs
        from paddle_tpu.observability import comms as _comms

        # observability ON for the measured window: the overlap yardstick
        # must see host-blocking eager collectives a (future multichip)
        # step issues — with tracing off it would report perfect overlap
        # no matter what. The loop body is one compiled call, so tracing
        # adds nothing to the measured steps today.
        obs_was_on = _obs.enabled()
        _obs.enable()
        comm_mark = _comms.mark()
        t0 = time.perf_counter()
        for i in range(steps):
            loss, params, m_state, v_state = step_call(
                params, m_state, v_state, float(i + 2), ids, labels)
        jax.block_until_ready(loss)
        dt = (time.perf_counter() - t0) / steps
        extras["_comm_s_per_step"] = _comms.wall_since(comm_mark) / steps
        if not obs_was_on:
            _obs.disable()
        if bd is not None:
            # by-subtraction estimate across two separately compiled programs
            # (the full step is donated/fused differently): clamp at 0 and
            # mark the method so a near-zero optimizer share reads as such.
            bd["opt_ms_by_subtraction"] = round(max(0.0, dt * 1e3 - fwdbwd_ms), 1)
            bd["step_ms"] = round(dt * 1e3, 1)
        return model, dt, float(loss), bd, card

    result = None
    for (n_layers, batch, remat) in tries:
        try:
            model, dt, loss_val, bd, card = run_config(
                n_layers, batch, remat, count_pallas=on_tpu, breakdown=on_tpu)
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            # does not fit this chip's HBM: try the next smaller config
            extras.setdefault("config_fallbacks", []).append(
                {"config": [n_layers, batch, remat],
                 "error": f"{type(e).__name__}: {str(e)[:200]}"})
            import gc

            gc.collect()
            continue
        if bd:
            extras["step_breakdown_ms"] = bd
        result = (model, n_layers, batch, remat, dt, loss_val, card)
        break

    if result is None:
        raise SystemExit("bench.py train_mfu: no config fits this device: "
                         + json.dumps(extras["config_fallbacks"]))

    model, n_layers, batch, remat, dt, loss_v, card = result
    tokens_per_sec = batch * seq / dt
    # Headline MFU from the compiler's own cost model (what XLA actually
    # compiled — remat recompute included), with the hand-coded
    # PaLM-appendix formula kept as a cross-check; >10 % divergence is
    # reported, not hidden (ISSUE 7 acceptance).
    legacy_flops_per_step = model.flops_per_token(seq) * batch * seq
    mfu = None
    if peak is None:
        extras["mfu_accounting"] = {
            "source": "not measured",
            "note": f"no peak FLOP/s for platform {dev.platform!r}",
            "legacy_flops_per_step": legacy_flops_per_step,
        }
    elif card.flops:
        mfu_legacy = legacy_flops_per_step / dt / peak
        mfu = card.flops / dt / peak
        divergence_pct = round(
            (legacy_flops_per_step - card.flops) / card.flops * 100.0, 2)
        extras["mfu_accounting"] = {
            "source": "xla_cost_analysis",
            "xla_flops_per_step": card.flops,
            "legacy_flops_per_step": legacy_flops_per_step,
            "flop_divergence_pct": divergence_pct,
            "divergence_exceeds_10pct": abs(divergence_pct) > 10.0,
            "mfu_legacy_formula": round(float(mfu_legacy), 4),
            "bytes_accessed_per_step": card.bytes_accessed,
            "peak_bytes": card.peak_bytes,
        }
    else:
        mfu = legacy_flops_per_step / dt / peak
        extras["mfu_accounting"] = {
            "source": "legacy_formula",
            "note": "cost_analysis reports no flops on this backend",
            "legacy_flops_per_step": legacy_flops_per_step,
        }
    # comm/compute overlap yardstick (ISSUE 9): exposed-comm ms/step from
    # the collective trace vs the measured step wall. Single-chip steps
    # issue no collectives, so exposed stays 0 and efficiency 1.0 — the
    # gauge every future multichip (T3-style) train config must keep high.
    from paddle_tpu.observability import comms as _comms

    extras["overlap"] = _comms.overlap_report(
        dt, extras.pop("_comm_s_per_step", 0.0),
        flops=card.flops, peak_flops=peak)
    import gc

    gc.collect()  # release the training state before further measurements

    # Remat-on / deeper-model companion measurement: the remat-on number is
    # what predicts large-pod behavior where activations cannot be held
    # (round-3 VERDICT weak-item 2). Measured only when the headline config
    # ran remat-off.
    if on_tpu and not remat:
        remat_tries = ([(24, 4, True), (16, 4, True)] if extras.get(
            "hbm_bytes", 0) >= 90 << 30 else [(8, 2, True), (4, 2, True)])
        for (rl, rb, _) in remat_tries:
            try:
                rmodel, rdt, rloss, _bd, rcard = run_config(rl, rb, True)
            except jax.errors.JaxRuntimeError as e:
                if "RESOURCE_EXHAUSTED" not in str(e):
                    raise
                extras.setdefault("remat_fallbacks", []).append(
                    {"config": [rl, rb],
                     "error": f"{type(e).__name__}: {str(e)[:160]}"})
                gc.collect()
                continue
            rtps = rb * seq / rdt
            rflops = rcard.flops or rtps * rmodel.flops_per_token(seq) * rdt
            extras["remat_on_mfu"] = {
                "mfu": round(float(rflops / rdt / peak), 4), "layers": rl,
                "batch": rb, "tokens_per_sec": round(rtps),
                "loss": round(rloss, 3)}
            del rmodel
            gc.collect()
            break

    # Eager dispatch microbench (round-3 VERDICT weak-item 1)
    extras["eager_dispatch"] = _eager_microbench()
    gc.collect()

    # flash-vs-sdpa microbench on the measured attention shape
    if on_tpu:
        from paddle_tpu.ops.pallas import flash_attention as fa

        q = jnp.asarray(rng.normal(size=(batch, 32, seq, 128)),
                        jnp.bfloat16)

        def flash_loss(q, k, v):
            return fa.flash_attention_bhsd(
                q, k, v, causal=True).astype(jnp.float32).sum()

        def sdpa_loss(q, k, v):
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                           preferred_element_type=jnp.float32)
            s = s / np.sqrt(128)
            mask = jnp.tril(jnp.ones((seq, seq), bool))
            s = jnp.where(mask, s, -1e30)
            p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
            return jnp.einsum("bhqk,bhkd->bhqd", p, v).astype(
                jnp.float32).sum()

        def timed(fn):
            g = jax.jit(jax.grad(fn, argnums=(0, 1, 2)))
            jax.block_until_ready(g(q, q, q))
            t0 = time.perf_counter()
            for _ in range(5):
                out = g(q, q, q)
            jax.block_until_ready(out)
            return (time.perf_counter() - t0) / 5 * 1e3

        extras["flash_microbench_ms"] = {
            "pallas_flash_fwdbwd": round(timed(flash_loss), 2),
            "xla_sdpa_fwdbwd": round(timed(sdpa_loss), 2)}

    extras.pop("_comm_s_per_step", None)   # companion run_config leftovers
    report = {
        "metric": "llama_train_mfu_1chip",
        "value": None if mfu is None else round(float(mfu), 4),
        "unit": f"MFU{'' if on_tpu else ' not measured'} "
                f"(tok/s={tokens_per_sec:.0f}, loss={loss_v:.3f}, "
                f"L={n_layers} h={model.config.hidden_size} seq={seq} "
                f"b={batch} "
                f"remat={'on' if remat else 'off'}, "
                f"{dev.platform} {dev.device_kind})",
        "vs_baseline": None if mfu is None else round(float(mfu) / 0.45, 4),
        "extras": extras,
        "platform": dev.platform,
    }
    _emit_report(report, "train_mfu")


def main():
    """Back-compat alias: `python bench.py` runs the train-MFU scenario."""
    train_mfu_main()


def _dispatch(argv):
    global _scenario_t0
    if "--list" in argv:
        for name in sorted(SCENARIOS):
            print(f"{name}  (budget {_scenario_budget_s(name):.0f}s)")
        return
    name = argv[0] if argv and not argv[0].startswith("-") else "train_mfu"
    # back-compat spelling: `serving_throughput --spec` is the
    # serving_spec scenario
    if name == "serving_throughput" and "--spec" in argv[1:]:
        name = "serving_spec"
    if name not in SCENARIOS:
        print(f"unknown scenario {name!r}; available: "
              + ", ".join(sorted(SCENARIOS)), file=sys.stderr)
        raise SystemExit(2)
    _scenario_t0 = time.time()
    SCENARIOS[name][0]()


if __name__ == "__main__":
    _dispatch(sys.argv[1:])
