"""Runner `train`: the program's one compiled train step,
`bench.build_train_step` (`functional_call` + `value_and_grad` + AdamW under
one donated `jax.jit`), over `LlamaForCausalLM` with bf16 parameters and
float32 moments, kernels on.

Set-up builds ONE object, the jitted step with its state, drives it from the
seed through its first steps by the window's own call and feed, compares
those with the reference, and hands the same object to the window.
"""
from __future__ import annotations

import gc
import time

import numpy as np

WARM_STEPS = 3


def run(job):
    import jax
    import jax.numpy as jnp

    import bench

    cfg, traffic, check = job["config"], job["traffic"], job["check"]
    opt, limits = cfg["optimizer"], cfg["check"]
    batch_of = job["generator"].make(traffic, job["seed"], cfg["vocab_size"])
    follow = int(limits["reference_losses"])
    shapes = check.ref.param_shapes(cfg)

    # ---- the reference first, before any of the program's state exists -------
    t = time.perf_counter()
    want = check.reference_train(
        cfg, opt, job["seed"], jnp.bfloat16,
        [batch_of(i) for i in range(1, follow + 1)])
    low = None
    if job["control"] == "ref-int8":
        low = check.reference_train(
            cfg, opt, job["seed"], jnp.bfloat16,
            [batch_of(i) for i in range(1, follow + 1)], quant="int8")
    gc.collect()
    ref_s = time.perf_counter() - t
    job["not_setup"](ref_s)
    peak_ref = (jax.devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use")
    print(f"    reference, one step and {follow} losses: {ref_s:.1f} s, losses "
          f"{want['loss']}, device peak after it {peak_ref}", flush=True)

    # ---- the program ---------------------------------------------------------
    t = time.perf_counter()
    model = check.load("program.py").llama(
        cfg, check.weights, shapes, job["seed"], jnp.bfloat16, train=True)
    train_step, *state = bench.build_train_step(model)
    step_fn = jax.jit(train_step, donate_argnums=(0, 1, 2))
    print(f"    model, weights, state {time.perf_counter() - t:.1f} s",
          flush=True)

    losses = []

    def drive(i, state):
        """One step as the window makes it: the feed, the call, the wait."""
        with span("make_batch"):
            ids, labels = batch_of(i)
        with span("train_step"):
            loss, *state = step_fn(*state, float(i), ids, labels)
            loss = float(jax.block_until_ready(loss))
        losses.append(loss)
        return state

    span = job["span"]

    diff_norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32)))))
    got = {}
    t = time.perf_counter()
    for i in range(1, WARM_STEPS + 1):
        state = drive(i, state)
        if i == 1:
            # the first gradient as the optimizer got it: m1 = (1 - b1) g1
            got["grad_norm"] = {k: float(v) / (1 - opt["beta1"]) for k, v
                                in check.tree_norms(state[1]).items()}
            got["delta_norm"] = {
                k: float(diff_norm(state[0][k], check.weights.make_leaf(
                    job["seed"], k, shapes[k][0], shapes[k][1],
                    jnp.bfloat16)))
                for k in sorted(state[0])}
    got["loss"] = losses[:follow]
    print(f"    first {WARM_STEPS} steps {time.perf_counter() - t:.1f} s, "
          f"losses {losses}", flush=True)
    if low is not None:
        got = low         # the control: the int8 reference in the program's place

    # ---- the window ------------------------------------------------------------
    step_ms = []
    if job["trace"]:
        jax.profiler.start_trace(job["trace_dir"])
        span.on = True
    t0 = time.perf_counter()
    job["window_started"](t0)
    i = WARM_STEPS
    while True:
        t_a = time.perf_counter()
        if t_a - t0 >= job["seconds"]:
            break
        if span.on and i - WARM_STEPS >= job["trace_steps"]:
            jax.profiler.stop_trace()
            span.on = False
            t0 += time.perf_counter() - t_a   # writing the trace out is
            continue                          # no part of the window
        i += 1
        state = drive(i, state)
        step_ms.append((time.perf_counter() - t_a) * 1e3)
    closed = time.perf_counter() - t0
    if span.on:
        jax.profiler.stop_trace()
        span.on = False
    stats = jax.devices()[0].memory_stats() or {}
    steps = len(step_ms)
    tokens = steps * traffic["batch"] * traffic["seq"]

    compared = check.Compared()
    check.compare_train(compared, got, want, limits)
    window_losses = losses[WARM_STEPS:]
    compared.add("window_losses_not_finite",
                 int(not np.isfinite(window_losses).all()), 0)
    compared.add("window_steps_missing", int(steps == 0), 0)
    print(f"    window {closed:.2f} s: {steps} steps, loss "
          f"{window_losses[0] if steps else None} -> "
          f"{window_losses[-1] if steps else None}", flush=True)
    return {
        "compared": compared, "attempted": steps,
        "failed": int(steps - int(np.isfinite(window_losses).sum())),
        "end_to_end": {"train_tok_s": tokens / closed},
        "record": {"step_ms": step_ms, "window_s": closed,
                   "tokens": tokens, "tokens_per_step":
                   traffic["batch"] * traffic["seq"],
                   "flops_per_token": job["costs"].train_flops_per_token(
                       cfg, traffic["seq"]),
                   "trace_steps": min(steps, job["trace_steps"]),
                   "span_names": ("make_batch", "train_step"),
                   "memory": stats, "memory_peak_after_reference": peak_ref},
    }
