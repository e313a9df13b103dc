"""Runner `serve_cohere2_moe`: a Cohere2-MoE model (Command A+) through the
program's normal serving path, `Cohere2MoeForCausalLM` ->
`Cohere2MoeInferenceEngine` -> `ServingFrontend`.

Only the build is this file's. The warm-up, the traffic's set-up, the window,
the stamps, the counters and the comparison are `runners/serve.py`'s own
`warm_up` and `drive`, loaded by path and given a job whose `check`
(`check_cohere2_moe.py`) and `costs` (`costs_cohere2_moe.py`) answer for this
architecture, as `serve_deepseek_v3.py` does it. What this file adds to the
record: the engine's expert-load counters over the window, the attention's
bytes of the traced steps by layer kind, and the WINDOW group's
blocks (in use when the window opens, at most, and when it closes; released
behind the window), read off the cache manager by the counting hook.

The configuration's `reduced.num_experts` is the chip's share: the model is
built with the router's published width and is told which experts it holds.
Before the reference runs the engine's pools are dropped: nothing compared
lives in them, and the reference needs the room (check_cohere2_moe.py).
"""
from __future__ import annotations

import gc
import time
import types


def build(job, check):
    """The model holding the seed's weights (made on the device in one
    call, taken by the model and the engine by reference) and the engine."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.inference.cohere2_moe_runner import \
        Cohere2MoeInferenceEngine
    from paddle_tpu.models.cohere2_moe import (Cohere2MoeConfig,
                                               Cohere2MoeForCausalLM,
                                               param_shapes)

    cfg, dep = job["config"], job["config"]["deployment"]
    t = time.perf_counter()
    width, first, count = check.ref.share(cfg)
    config = Cohere2MoeConfig.from_hf(dict(cfg, num_experts=width),
                                      held_experts=(first, count))
    shapes = check.ref.param_shapes(cfg)
    if {k: tuple(s) for k, (s, _) in shapes.items()} != \
            {k: tuple(s) for k, (s, _) in param_shapes(config).items()}:
        raise SystemExit("the program's parameters are not the reference's")
    made = check.weights.make_all(
        job["seed"], shapes, jnp.bfloat16,
        fake_int8=job["control"] == "weights-int8")
    model = Cohere2MoeForCausalLM(config, weights=made)
    del made
    jax.block_until_ready(model.weight_tree())
    print(f"    model and weights {time.perf_counter() - t:.1f} s", flush=True)
    t = time.perf_counter()
    engine = Cohere2MoeInferenceEngine(
        model, max_batch_size=dep["lanes"], num_blocks=dep["full_blocks"],
        block_size=dep["block_size"],
        max_blocks_per_seq=dep["context_tokens"] // dep["block_size"],
        window_blocks=dep["window_blocks"])
    del model
    gc.collect()
    jax.block_until_ready(engine.pools)
    print(f"    engine {time.perf_counter() - t:.1f} s", flush=True)
    return engine


def run(job):
    base = job["check"]
    serve = base.load("runners/serve.py")
    check = base.load("check_cohere2_moe.py")
    costs = base.load("costs_cohere2_moe.py")
    engine = build(job, check)

    from paddle_tpu.framework import monitor
    from paddle_tpu.serving import ServingFrontend
    from paddle_tpu.serving.metrics import ServingMetrics

    mgr = engine.manager
    windowed = [g for g in range(1, mgr.n_groups) if mgr.group_window(g)]

    class Hook(ServingMetrics):
        """`serve.py`'s counting hook, and the window group's blocks in use
        at every counted step's end."""
        counting = False
        steps = prefill_tokens = decode_lanes = 0
        window_in_use = []

        def on_ragged_step(self, prefill_tokens, decode_lanes):
            super().on_ragged_step(prefill_tokens, decode_lanes)
            if self.counting:
                self.steps += 1
                self.prefill_tokens += prefill_tokens
                self.decode_lanes += decode_lanes
                if windowed:
                    g = windowed[0]
                    self.window_in_use.append(
                        mgr.num_blocks_of(g) - mgr.free_blocks_of(g))

    dep = job["config"]["deployment"]
    hook = Hook()
    fe = ServingFrontend(engine, metrics=hook,
                         prefill_chunk_tokens=dep["prefill_chunk_tokens"],
                         prefix_cache=bool(dep.get("prefix_cache", False)))
    serve.warm_up(fe, dep)

    at_open = {}
    opened = job["window_started"]
    released = "serving.kv.window_blocks_released"

    def window_started(t):
        at_open["load"] = engine.expert_load()
        at_open["released"] = monitor.get(released) or 0
        opened(t)

    def served_gap(*args, **kw):
        # nothing the comparison reads lives in the pools, and the
        # reference needs their room: the window is over, drop them
        engine.pools = None
        gc.collect()
        return check.served_gap(*args, **kw)

    judge = types.SimpleNamespace(Compared=check.Compared,
                                  served_gap=served_gap)
    out = serve.drive(dict(job, check=judge, costs=costs,
                           window_started=window_started), fe, hook)
    closed = engine.expert_load()
    rec = out["record"]
    rec.update(
        expert_load={k: closed[k] - at_open["load"][k]
                     for k in ("tokens", "touched", "steps")},
        held_experts=closed["held"],
        window_attn_bytes_traced=costs.traced["window_bytes"],
        full_attn_bytes_traced=costs.traced["full_bytes"])
    if windowed and hook.window_in_use:
        used = hook.window_in_use
        rec.update(
            window_blocks=mgr.num_blocks_of(windowed[0]),
            window_blocks_peak=max(used),
            window_blocks_released=(monitor.get(released) or 0)
            - at_open["released"])
        print(f"    window group: {used[0]} blocks in use at the window's "
              f"first step, at most {max(used)}, {used[-1]} at its last, of "
              f"{rec['window_blocks']}; {rec['window_blocks_released']} "
              f"released behind the window in {len(used)} steps; the full "
              f"group's peak {rec['kv_blocks_peak']} of {rec['kv_blocks']}",
              flush=True)
    return out
