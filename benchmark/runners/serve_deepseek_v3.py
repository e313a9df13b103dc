"""Runner `serve_deepseek_v3`: a DeepSeek-V3-architecture model through the
program's normal serving path, `DeepseekV3ForCausalLM` ->
`DeepseekV3InferenceEngine` -> `ServingFrontend`.

Only the build is this file's. The warm-up, the traffic's set-up, the window,
the stamps, the counters and the comparison are `runners/serve.py`'s own
`warm_up` and `drive`, loaded by path and given a job whose `check`
(`check_deepseek_v3.py`: `served_gap` over this architecture's reference) and
`costs` (`costs_deepseek_v3.py`: `ragged_attention_bytes` of a latent cache)
answer for this architecture. So a Kanana cell is stamped, counted and
judged by the code the Mistral cells are. What this file adds to the record:
the engine's expert-load counters, read when the window opens (in the
`window_started` callback) and after `drive` returns (their difference is
the window's), and the attention FLOPs of the traced steps.
"""
from __future__ import annotations

import gc
import time


def build(job, check):
    """The model holding the seed's weights (made on the device in one
    call, taken by the model and the engine by reference), the engine, the
    frontend and `serve.py`'s counting hook."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.inference.deepseek_v3_runner import \
        DeepseekV3InferenceEngine
    from paddle_tpu.models.deepseek_v3 import (DeepseekV3Config,
                                               DeepseekV3ForCausalLM,
                                               param_shapes)

    cfg, dep = job["config"], job["config"]["deployment"]
    t = time.perf_counter()
    config = DeepseekV3Config.from_hf(cfg)
    shapes = check.ref.param_shapes(cfg)
    if {k: tuple(s) for k, (s, _) in shapes.items()} != \
            {k: tuple(s) for k, (s, _) in param_shapes(config).items()}:
        raise SystemExit("the program's parameters are not the reference's")
    made = check.weights.make_all(
        job["seed"], shapes, jnp.bfloat16,
        fake_int8=job["control"] == "weights-int8")
    model = DeepseekV3ForCausalLM(config, weights=made)
    del made
    jax.block_until_ready(model.weight_tree())
    print(f"    model and weights {time.perf_counter() - t:.1f} s", flush=True)
    t = time.perf_counter()
    blocks_per_seq = dep["context_tokens"] // dep["block_size"]
    engine = DeepseekV3InferenceEngine(
        model, max_batch_size=dep["lanes"],
        num_blocks=dep["lanes"] * blocks_per_seq + 1,
        block_size=dep["block_size"], max_blocks_per_seq=blocks_per_seq)
    del model
    gc.collect()
    jax.block_until_ready(engine.pool)
    print(f"    engine {time.perf_counter() - t:.1f} s", flush=True)
    return engine


def run(job):
    base = job["check"]
    serve = base.load("runners/serve.py")
    check = base.load("check_deepseek_v3.py")
    costs = base.load("costs_deepseek_v3.py")
    engine = build(job, check)

    # serve.py's hook class is local to its build(); the same counting is
    # three lines
    from paddle_tpu.serving import ServingFrontend
    from paddle_tpu.serving.metrics import ServingMetrics

    class Hook(ServingMetrics):
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
                         prefill_chunk_tokens=dep["prefill_chunk_tokens"],
                         prefix_cache=bool(dep.get("prefix_cache", False)))
    serve.warm_up(fe, dep)

    load = {}
    opened = job["window_started"]

    def window_started(t):
        load["open"] = engine.expert_load()
        opened(t)

    out = serve.drive(dict(job, check=check, costs=costs,
                           window_started=window_started), fe, hook)
    closed = engine.expert_load()
    out["record"].update(
        expert_load={k: closed[k] - load["open"][k]
                     for k in ("tokens", "touched", "steps")},
        attn_flops_traced=costs.traced["flops"]
        * job["config"]["num_hidden_layers"])
    return out
