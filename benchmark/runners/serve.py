"""Runner `serve`: a Llama-architecture model through the program's normal
serving path, `LlamaForCausalLM` -> `LlamaInferenceEngine` ->
`ServingFrontend`, driven by one thread in one process.

The yardstick is the benchmark's own: it stamps a request when it was DUE
(not when it got submitted), stamps tokens after each `fe.step()`, and
counts through a `ServingMetrics` hook. From the program it takes the
frontend, request handles and the monitor's counters, nothing else.
"""
from __future__ import annotations

import gc
import time

import numpy as np


def _percentile(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q))


def build(job):
    """The model with the seed's weights (`program.llama`), stacked into the
    engine and dropped; the frontend over it, and the counting hook."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.inference.llama_runner import LlamaInferenceEngine
    from paddle_tpu.serving import ServingFrontend
    from paddle_tpu.serving.metrics import ServingMetrics

    cfg, dep = job["config"], job["config"]["deployment"]
    t = time.perf_counter()
    model = job["check"].load("program.py").llama(
        cfg, job["check"].weights, job["check"].ref.param_shapes(cfg),
        job["seed"], jnp.bfloat16, train=False,
        fake_int8=job["control"] == "weights-int8")
    print(f"    model and weights {time.perf_counter() - t:.1f} s", flush=True)
    t = time.perf_counter()
    blocks_per_seq = dep["context_tokens"] // dep["block_size"]
    engine = LlamaInferenceEngine(
        model, max_batch_size=dep["lanes"],
        num_blocks=dep["lanes"] * blocks_per_seq + 1,
        block_size=dep["block_size"], max_blocks_per_seq=blocks_per_seq,
        dtype="bfloat16")
    del model
    gc.collect()
    jax.block_until_ready(engine.params)
    print(f"    engine {time.perf_counter() - t:.1f} s", flush=True)

    class Hook(ServingMetrics):
        """Sums what the monitor only keeps as last-value gauges."""
        counting = False
        steps = prefill_tokens = decode_lanes = 0

        def on_ragged_step(self, prefill_tokens, decode_lanes):
            super().on_ragged_step(prefill_tokens, decode_lanes)
            if self.counting:
                self.steps += 1
                self.prefill_tokens += prefill_tokens
                self.decode_lanes += decode_lanes

    hook = Hook()
    fe = ServingFrontend(engine, metrics=hook,
                         prefill_chunk_tokens=dep["prefill_chunk_tokens"],
                         prefix_cache=bool(dep.get("prefix_cache", False)))
    return fe, hook


class _Req:
    __slots__ = ("spec", "handle", "due", "t_submit", "t_admit", "stamps",
                 "in_window")

    def __init__(self, spec):
        self.spec, self.handle = spec, None
        self.due = spec["due"]
        self.t_submit = self.t_admit = None
        self.stamps = []
        self.in_window = False


def run(job):
    fe, hook = build(job)
    warm_up(fe, job["config"]["deployment"])
    return drive(job, fe, hook)


def warm_up(fe, dep):
    """One request longer than a chunk compiles the engine's one program,
    `jit__ragged_fn` (the step with the NaN screen, the row gather and the
    sampler as its tail), at the one shape every round has; nothing else is
    ever used."""
    from paddle_tpu.serving import RequestStatus

    t = time.perf_counter()
    warm = fe.submit(list(range(1, dep["prefill_chunk_tokens"] + 8)),
                     max_new_tokens=3)
    fe.run_until_idle()
    if warm.status is not RequestStatus.FINISHED or len(warm.tokens) != 3:
        raise SystemExit(f"warm-up request ended {warm!r}")
    print(f"    warm-up {time.perf_counter() - t:.1f} s", flush=True)


def drive(job, fe, hook):
    """Set-up of the traffic, the window, and what decides `correct`."""
    import jax

    from paddle_tpu.framework import monitor
    from paddle_tpu.serving import RequestStatus

    cfg, dep, seconds = job["config"], job["config"]["deployment"], job["seconds"]
    span = job["span"]
    mgr = fe.scheduler.engine.manager
    lanes = dep["lanes"]
    watched = ("serving.ragged_retraces", "serving.step_faults",
               "serving.isolated_faults", "serving.engine_restarts")
    before = {c: monitor.get(c) or 0 for c in watched}
    # the round in flight's counters (PR 43), over the window alone: a
    # counter never bumped is not in the registry and reads 0
    rounds = ("serving.step.programs", "serving.step.overlapped",
              "serving.step.wasted_lanes")

    plan = job["generator"].make(job["traffic"], job["seed"], seconds,
                                 cfg["vocab_size"])
    pending = [_Req(s) for s in plan["requests"]]
    pending.reverse()                          # pop() takes the next one
    live, done = [], []
    backlog = plan["mode"] == "backlog"
    step_ms, kv_peak, bytes_per_step, trace_steps = [], 0, [], 0
    queue_depth = []                           # (seconds, requests waiting)

    paused = [0.0]

    def clk():
        """The window's clock: the host's, less the time spent writing the
        trace out (a traced run stops the profiler inside the window)."""
        return time.perf_counter() - paused[0]

    def stop_trace():
        """The profiler's stop, and the first program after it (`settled`:
        the step that followed a chat-open trace took 5.96 s at 25.6
        requests/s, PR 34, and its backlog was the traced run's TTFT), both
        outside the window's clock."""
        nonlocal tracing
        t = time.perf_counter()
        jax.profiler.stop_trace()
        settled(0).block_until_ready()
        took = time.perf_counter() - t
        paused[0] += took
        tracing = span.on = False
        print(f"    trace stopped and written in {took:.1f} s, outside the "
              f"window's clock", flush=True)

    def submit(r, now):
        with span("submit"):
            r.t_submit = now
            r.handle = fe.submit(r.spec["prompt"],
                                 max_new_tokens=r.spec["max_new_tokens"])
        (done if r.handle.finished else live).append(r)

    def poll(now):
        with span("poll"):
            still = []
            for r in live:
                h = r.handle
                n = len(h.tokens)
                if r.t_admit is None and (
                        n or h.status is not RequestStatus.QUEUED):
                    r.t_admit = now
                while len(r.stamps) < n:
                    r.stamps.append(now)
                (done if h.finished else still).append(r)
            live[:] = still

    def queued():
        return sum(r.handle.status is RequestStatus.QUEUED for r in live)

    def step():
        nonlocal kv_peak
        t_a = clk()
        with span("fe.step"):
            fe.step()
        t_b = clk()
        kv_peak = max(kv_peak, mgr.num_blocks - mgr.free_blocks)
        return t_a, t_b

    if backlog:
        # fill the lanes before the window opens (set-up): the window is
        # about decode with every lane busy, not about getting there
        for _ in range(dep["lanes"] * 64):
            while pending and queued() < plan["keep_queued"]:
                submit(pending.pop(), clk())
            _, t_b = step()
            poll(t_b)
            if sum(bool(r.stamps) for r in live) >= lanes:
                break
        else:
            raise SystemExit("the lanes never all decoded")

    tracing = False
    if job["trace"]:
        settled = jax.jit(lambda x: x + 1)
        settled(0).block_until_ready()         # compiled in set-up
        jax.profiler.start_trace(job["trace_dir"])
        tracing = span.on = True
    # the interpreter's full garbage collections inside the window: each
    # stops the host for as long as the process has objects to walk
    gc_ms, gc_began = [], [0.0]

    def on_gc(phase, info):
        if info["generation"] == 2:
            if phase == "start":
                gc_began[0] = time.perf_counter()
            else:
                gc_ms.append((time.perf_counter() - gc_began[0]) * 1e3)

    gc.callbacks.append(on_gc)
    hook.counting = True
    rounds_before = {c: monitor.get(c) or 0 for c in rounds}
    t0 = clk()
    job["window_started"](t0)
    for r in live:
        r.in_window = True
    while True:
        now = clk() - t0
        if tracing and (now >= job["trace_seconds"] or now >= seconds):
            stop_trace()
        if backlog:
            if now >= seconds:
                break
            while pending and queued() < plan["keep_queued"]:
                r = pending.pop()
                r.in_window = True
                submit(r, clk())
        else:
            while pending and pending[-1].due <= now and now < seconds:
                r = pending.pop()
                r.in_window = True
                submit(r, clk())
            if now >= seconds:
                waiting = [r for r in live if r.in_window and not r.stamps]
                if not waiting or now >= seconds + plan["drain_limit_s"]:
                    break
            elif not live:
                # nothing to serve until the next arrival is due
                wait = pending[-1].due - now if pending else seconds - now
                with span("wait"):
                    time.sleep(min(max(wait, 0.0), 0.05))
                continue
        t_a, t_b = step()
        poll(t_b)
        if t_b - t0 <= seconds:
            step_ms.append((t_b - t_a) * 1e3)
            queue_depth.append((t_b - t0, queued()))
        if tracing:
            trace_steps += 1
            running = [r for r in live if r.t_admit is not None]
            bytes_per_step.append(job["costs"].ragged_attention_bytes(
                cfg, [len(r.spec["prompt"]) + len(r.stamps) for r in running],
                [1] * len(running)) * cfg["num_hidden_layers"])
    hook.counting = False
    closed = clk() - t0
    rounds_moved = {c.rpartition(".")[2]: (monitor.get(c) or 0)
                    - rounds_before[c] for c in rounds}
    gc.callbacks.remove(on_gc)
    if tracing:
        stop_trace()
    stats = jax.devices()[0].memory_stats() or {}

    # ---- the window's own numbers -------------------------------------------
    everyone = [r for r in done + live if r.in_window
                and (not backlog or r.t_admit is not None)]
    rel = lambda t: t - t0                                    # noqa: E731
    e2e, rec = {}, {}
    failed = 0
    for r in everyone:
        h = r.handle
        bad_end = h.finished and (
            h.status is not RequestStatus.FINISHED
            or len(h.tokens) != r.spec["max_new_tokens"])
        if bad_end or (not backlog and not r.stamps):
            failed += 1
    if backlog:
        in_win = [s for r in everyone for s in r.stamps if 0 <= rel(s)]
        produced = len(in_win)
        e2e["serve_tok_s"] = produced / closed
        rec["tokens_in_window"] = produced
    else:
        limit_ms = plan["drain_limit_s"] * 1e3
        ttft = [(rel(r.stamps[0]) - r.due) * 1e3 if r.stamps else limit_ms
                for r in everyone]
        gaps = [(b - a) * 1e3 for r in everyone
                for a, b in zip(r.stamps, r.stamps[1:])
                if 0 <= rel(a) and rel(b) <= seconds]
        # no TTFT statistic is end to end yet: over one window's requests
        # it swings with their order (PERF.md); the log keeps them
        print(f"    first tokens, ms: p50 {_percentile(ttft, 50):.1f}, p90 "
              f"{_percentile(ttft, 90):.1f}, slowest "
              f"{[round(x) for x in sorted(ttft)[-8:]]}", flush=True)
        e2e["gap_p95_ms"] = _percentile(gaps, 95) if gaps else limit_ms
        if gaps:
            print(f"    gaps between tokens, ms: {len(gaps)}, " + ", ".join(
                f"p{q} {_percentile(gaps, q):.1f}"
                for q in (50, 75, 90, 93, 95, 97, 99)), flush=True)
        rec.update(
            ttft_ms=ttft, gaps_ms=gaps,
            late_ms=[(rel(r.t_submit) - r.due) * 1e3 for r in everyone],
            queue_wait_ms=[(rel(r.t_admit) - r.due) * 1e3
                           for r in everyone if r.t_admit is not None])
    # what the window processed, for `step_mfu`: a decoded token attends
    # its whole context, a prompt prefilled in the window every causal pair
    # (a prompt that straddles an edge of the window is counted whole or
    # not at all, by where its first token falls)
    rows = sampled = pairs = 0
    for r in done + live:
        p = len(r.spec["prompt"])
        for i, s in enumerate(r.stamps):
            if rel(s) < 0:
                continue
            sampled += 1
            if i:
                rows, pairs = rows + 1, pairs + p + i
            elif rel(r.t_submit) >= 0:
                rows, pairs = rows + p, pairs + p * (p + 1) // 2
    rec["served_flops"] = job["costs"].serve_flops(cfg, rows, sampled, pairs)
    moved = {c: (monitor.get(c) or 0) - before[c] for c in watched}
    rec.update(
        step_ms=step_ms, window_s=closed, lanes=lanes, gc_ms=gc_ms,
        queue_depth=queue_depth,
        hook_steps=hook.steps, prefill_tokens=hook.prefill_tokens,
        decode_lanes=hook.decode_lanes, rounds=rounds_moved,
        kv_blocks_peak=kv_peak, kv_blocks=mgr.num_blocks,
        trace_steps=trace_steps, attn_bytes_traced=float(sum(bytes_per_step)),
        span_names=("submit", "fe.step", "poll", "wait"),
        memory=stats)
    finished = sum(r.handle.status is RequestStatus.FINISHED
                   for r in everyone)
    # a stalled step (the host's neighbours, the runtime) shows here first
    slowest = sorted(zip(step_ms, (at for at, _ in queue_depth)))[-5:]
    print("    slowest steps, ms (ending at s): " + ", ".join(
        f"{ms:.0f} ({at:.1f})" for ms, at in slowest), flush=True)
    print(f"    full garbage collections in the window: {len(gc_ms)}, ms: "
          f"{[round(x) for x in gc_ms]}", flush=True)
    print(f"    window {closed:.2f} s: {len(everyone)} requests attempted, "
          f"{finished} finished, {failed} failed, {len(step_ms)} steps, "
          f"counters moved {moved}", flush=True)

    # ---- correct -------------------------------------------------------------
    compared = job["check"].Compared()
    compared.add("requests_failed", failed, 0)
    for c, d in moved.items():
        compared.add(c.replace("serving.", "moved_"), d, 0)
    served = [r for r in done + live if r.stamps]
    if job.get("no_reference"):
        # the knee sweep alone: many windows over one build, judged on the
        # window's own counts; leave the engine empty for the next
        for r in live:
            fe.cancel(r.handle)
        fe.run_until_idle()
    elif served:
        # finished requests first (the longest always among them), then
        # others drawn from the seed; every one is judged on the tokens
        # it was served, complete or not
        rng = np.random.default_rng([int(job["seed"]), 7])
        size = lambda r: len(r.spec["prompt"]) + len(r.handle.tokens)  # noqa: E731
        pool = [r for r in served if r.handle.finished] or served
        longest = max(pool, key=size)
        rest = [r for r in pool if r is not longest]
        take = min(job["config"]["check"]["sample_requests"] - 1, len(rest))
        picked = [longest] + [rest[i] for i in
                              rng.choice(len(rest), take, replace=False)]
        t = time.perf_counter()
        gap, mean_gap, n_tok = job["check"].served_gap(
            cfg, job["seed"], "bfloat16",
            [(r.spec["prompt"], r.handle.tokens) for r in picked],
            pad_to=128,
            control=job["control"] if job["control"] == "ref-int8" else None)
        print(f"    reference over {len(picked)} requests, {n_tok} served "
              f"tokens, {time.perf_counter() - t:.1f} s", flush=True)
        compared.add("served_token_widest_gap", gap,
                     job["config"]["check"]["served_gap_limit"])
        compared.add("served_token_mean_gap", mean_gap,
                     job["config"]["check"]["served_mean_gap_limit"])
    else:
        compared.add("served_tokens_to_check_missing", 1, 0)
    return {"compared": compared, "attempted": len(everyone),
            "failed": failed, "end_to_end": e2e, "record": rec}
