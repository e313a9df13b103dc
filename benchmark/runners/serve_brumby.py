"""Runner `serve_brumby`: a Brumby model (power retention) through the
program's normal serving path, `BrumbyForCausalLM` -> `BrumbyInferenceEngine`
-> `ServingFrontend`.

Only the build is this file's. The warm-up, the traffic's set-up, the window,
the stamps, the counters and the comparison are `runners/serve.py`'s own
`warm_up` and `drive`, loaded by path and given a job whose `check`
(`check_brumby.py`) and `costs` (`costs_brumby.py`) answer for this
architecture, as `serve_deepseek_v3.py` and `serve_cohere2_moe.py` do it.
What this file adds to the record: the bytes the one-token state update had
to move in the traced steps, and the state group's own counters (lanes started
from zero, lanes restarted) over the window.

The engine has no KV pool: `drive`'s `kv_blocks` are the state group's slots
(33: one a lane and the scheduler's guard). Before the reference runs the
engine's state is dropped: nothing compared lives in it, and the reference
needs the room (check_brumby.py).
"""
from __future__ import annotations

import gc
import time
import types


def build(job, check):
    """The model holding the seed's weights (made on the device in one
    call, the gate biases from their own draws, all taken by the model and
    the engine by reference) and the engine."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.inference.brumby_runner import BrumbyInferenceEngine
    from paddle_tpu.models.brumby import (BrumbyConfig, BrumbyForCausalLM,
                                          param_shapes)
    from paddle_tpu.ops.pallas.power_retention import feature_dim

    cfg, dep = job["config"], job["config"]["deployment"]
    t = time.perf_counter()
    config = BrumbyConfig.from_hf(cfg)
    shapes = check.ref.param_shapes(cfg)
    if {k: (tuple(s), kind) for k, (s, kind) in shapes.items()} != \
            {k: (tuple(s), kind) for k, (s, kind)
             in param_shapes(config).items()}:
        raise SystemExit("the program's parameters are not the reference's")
    if feature_dim(config.head_dim) != dep["feature_dim_run"]:
        raise SystemExit("the state's feature axis is not the one the "
                         "configuration states")
    drawn = {k: v for k, v in shapes.items() if v[1] != "bias"}
    made = check.weights.make_all(
        job["seed"], drawn, jnp.bfloat16,
        fake_int8=job["control"] == "weights-int8")
    for name, (shape, kind) in shapes.items():
        if kind == "bias":
            made[name] = check.leaf(job["seed"], name, shape, kind, "float32")
    model = BrumbyForCausalLM(config, weights=made)
    del made
    jax.block_until_ready(model.weight_tree())
    print(f"    model and weights {time.perf_counter() - t:.1f} s", flush=True)
    t = time.perf_counter()
    engine = BrumbyInferenceEngine(
        model, max_batch_size=dep["lanes"], slots=dep["state_slots"] - 1,
        context_tokens=dep["context_tokens"])
    del model
    gc.collect()
    jax.block_until_ready(engine.state)
    print(f"    engine {time.perf_counter() - t:.1f} s; a sequence's state "
          f"{engine.state_bytes_per_seq() / 1e6:.1f} MB", flush=True)
    return engine


def run(job):
    base = job["check"]
    serve = base.load("runners/serve.py")
    check = base.load("check_brumby.py")
    costs = base.load("costs_brumby.py")
    engine = build(job, check)

    from paddle_tpu.framework import monitor
    from paddle_tpu.serving import ServingFrontend
    from paddle_tpu.serving.metrics import ServingMetrics

    class Hook(ServingMetrics):
        """`serve.py`'s counting hook."""
        counting = False
        steps = prefill_tokens = decode_lanes = 0

        def on_ragged_step(self, prefill_tokens, decode_lanes):
            super().on_ragged_step(prefill_tokens, decode_lanes)
            if self.counting:
                self.steps += 1
                self.prefill_tokens += prefill_tokens
                self.decode_lanes += decode_lanes

    dep = job["config"]["deployment"]
    hook = Hook()
    fe = ServingFrontend(engine, metrics=hook,
                         prefill_chunk_tokens=dep["prefill_chunk_tokens"])
    serve.warm_up(fe, dep)

    at_open = {}
    opened = job["window_started"]

    def window_started(t):
        at_open["resets"] = engine.state_resets()
        at_open["restarts"] = monitor.get("serving.state.restarts") or 0
        opened(t)

    def served_gap(*args, **kw):
        # nothing the comparison reads lives in the state, and the
        # reference needs its room: the window is over, drop it
        engine.state = None
        gc.collect()
        return check.served_gap(*args, **kw)

    closed = {}

    def compared():
        """`Compared`, made by `drive` once the window has closed: the
        state group's counters are read then, before the state is dropped."""
        closed["resets"] = engine.state_resets() - at_open["resets"]
        closed["restarts"] = (monitor.get("serving.state.restarts") or 0) \
            - at_open["restarts"]
        out = check.Compared()
        out.add("moved_state.restarts", closed["restarts"], 0)
        return out

    judge = types.SimpleNamespace(Compared=compared, served_gap=served_gap)
    out = serve.drive(dict(job, check=judge, costs=costs,
                           window_started=window_started), fe, hook)
    out["record"].update(
        retention_update_bytes_traced=costs.traced["update_bytes"],
        state_resets=closed["resets"], state_restarts=closed["restarts"],
        state_bytes_per_seq=monitor.get("serving.state.bytes_per_seq"))
    print(f"    state group: {out['record']['kv_blocks_peak']} of "
          f"{out['record']['kv_blocks']} slots in use at most (the guard's "
          f"among them), {closed['resets']} lanes started from zero and "
          f"{closed['restarts']} restarted in the window", flush=True)
    return out
