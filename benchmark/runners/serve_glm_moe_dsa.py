"""Runner `serve_glm_moe_dsa`: a GLM-MoE-DSA model (GLM-5.2) through the
program's normal serving path, `GlmMoeDsaForCausalLM` ->
`GlmMoeDsaInferenceEngine` -> `ServingFrontend`.

Only the build is this file's. The warm-up, the traffic's set-up, the window,
the stamps, the counters and the comparison are `runners/serve.py`'s own
`warm_up` and `drive`, loaded by path and given a job whose `check`
(`check_glm_moe_dsa.py`) and `costs` (`costs_glm_moe_dsa.py`) answer for this
architecture, as `serve_deepseek_v3.py` and `serve_cohere2_moe.py` do it. What
this file adds to the record: the engine's expert-load and selection-load
counters over the window, the bytes and FLOPs of the traced steps' sparse
attention and index scores, apart, and two more comparisons for `correct`,
made on DECODE rows of the window: after it closes, decode positions spread
over what each sampled request was served (its last fed position among them)
are replayed through the engine's `attention_witness`, over the caches the
window's rounds wrote, and held against the reference there
(`check_glm_moe_dsa.decode_witness_gap`): `decode_selection_miss_worst`, the
share of the reference's selection the program's attention was not given, and
`decode_attention_error_worst`, how far the attention sub-block's output lies
from the reference's. At the benchmark's random weights the served tokens'
gaps hardly see WHICH 2,048 rows were attended (a wrong selection read
`correct` by them alone: my chip run, PR 47, PERF.md section 2); these do.
Which requests the reference judges is this file's too (`fitting_sample`:
those that fit `check.width`, so that a run ends inside the driver's limit),
and the programs that only the check needs are compiled beside the set-up
(`compile_ahead`).

The configuration's `reduced.n_routed_experts` is the chip's share: the model
is built with the router's published width and is told which experts it
holds; `layers_run` names the published layers the run's depth keeps, and the
model gets their `indexer_types`. Before the reference runs the engine's pools
and weights are dropped: nothing compared lives in them, and the reference
has the chip (check_glm_moe_dsa.py).
"""
from __future__ import annotations

import gc
import threading
import time
import types

import numpy as np


def build(job, check):
    """The model holding the seed's weights (made on the device in one
    call, taken by the model and the engine by reference) and the engine."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.inference.glm_moe_dsa_runner import \
        GlmMoeDsaInferenceEngine
    from paddle_tpu.models.glm_moe_dsa import (GlmMoeDsaConfig,
                                               GlmMoeDsaForCausalLM,
                                               param_shapes)

    cfg, dep = job["config"], job["config"]["deployment"]
    t = time.perf_counter()
    width, first, count = check.ref.share(cfg)
    config = GlmMoeDsaConfig.from_hf(
        dict(cfg, n_routed_experts=width,
             indexer_types=list(check.ref.layer_kinds(cfg))),
        held_experts=(first, count))
    shapes = check.ref.param_shapes(cfg)
    if {k: tuple(s) for k, (s, _) in shapes.items()} != \
            {k: tuple(s) for k, (s, _) in param_shapes(config).items()}:
        raise SystemExit("the program's parameters are not the reference's")
    made = check.weights.make_all(
        job["seed"], shapes, jnp.bfloat16,
        fake_int8=job["control"] == "weights-int8")
    model = GlmMoeDsaForCausalLM(config, weights=made)
    del made
    jax.block_until_ready(model.weight_tree())
    print(f"    model and weights {time.perf_counter() - t:.1f} s", flush=True)
    t = time.perf_counter()
    engine = GlmMoeDsaInferenceEngine(
        model, max_batch_size=dep["lanes"], num_blocks=dep["blocks"],
        block_size=dep["block_size"],
        max_blocks_per_seq=dep["context_tokens"] // dep["block_size"])
    del model
    gc.collect()
    jax.block_until_ready(engine.pools)
    print(f"    engine {time.perf_counter() - t:.1f} s", flush=True)
    return engine


def compile_ahead(engine, check, cfg):
    """The programs a run needs only AFTER its window, compiled while the
    traffic's set-up keeps the device busy: the reference's (`check.
    compile_ahead`) and the engine's witness, lowered and compiled from
    shapes in a thread of their own: the calls after the window find them
    made (the process keeps a program by its shapes, and the persistent
    cache by its text). Where the cache is empty some 55 s of compiling
    would otherwise follow the window, and some 15 s of tracing where it is
    not (PERF.md section 2). The thread is started after the warm-up, so the
    serving thread lowers nothing beside it, and it is done long before
    the lanes are full. A rehearsal (its compile cache is off, its set-up
    short) compiles where it calls."""
    import jax

    if not jax.config.jax_enable_compilation_cache:
        return None
    lanes = engine.max_batch_size
    shape = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)   # noqa: E731
    ints = lambda *s: jax.ShapeDtypeStruct(s, np.int32)        # noqa: E731
    witness, state = engine.cost_card_args("witness")
    state = jax.tree.map(shape, state)   # now: a step donates the arrays

    def work():
        t = time.perf_counter()
        try:
            check.compile_ahead(cfg, lanes // cfg["check"]["sample_requests"])
            witness.lower(*state, ints(lanes), ints(lanes),
                          ints(lanes, engine.manager.table_width)).compile()
        except Exception as e:      # the calls after the window compile
            print(f"    compiling ahead failed: {e!r}", flush=True)
        print(f"    compiled ahead in {time.perf_counter() - t:.1f} s, "
              "beside the set-up", flush=True)

    thread = threading.Thread(target=work, daemon=True)
    thread.start()
    return thread


def fitting_sample(fe, cfg, seed):
    """The requests the reference judges, `[(prompt ids, served ids)]`:
    of the requests that hold a lane and fit the configuration's
    `check.width` (prompt + served tokens), the longest and `check.
    sample_requests - 1` others drawn from the seed: the harness's own rule
    (`runners/serve.py` `drive`) over the requests that fit. The harness's
    draw always holds the longest request of all, and the float32 reference
    of a 55 k-token sequence alone takes some 90 s on the chip (its
    attention is quadratic; PERF.md section 2): with the set-up's prefill of
    470 k tokens a run would not end inside the driver's limit. So the
    sample's width is bounded, and every sample is padded to that one
    width, whose programs compile once and are the same in every run."""
    size = lambda r: len(r.prompt) + len(r.generated)        # noqa: E731
    pool = [r for r in fe.scheduler.slots if r is not None
            and len(r.generated) >= 2 and size(r) <= cfg["check"]["width"]]
    if not pool:
        return []
    longest = max(pool, key=size)
    rest = [r for r in pool if r is not longest]
    take = min(cfg["check"]["sample_requests"] - 1, len(rest))
    rng = np.random.default_rng([int(seed), 47])
    picked = [longest] + [rest[i] for i in
                          rng.choice(len(rest), take, replace=False)]
    print(f"    sampled: {len(picked)} of the {len(pool)} requests that fit "
          f"{cfg['check']['width']} positions, "
          f"{[size(r) for r in picked]} long", flush=True)
    return [(r.prompt.tolist(), list(r.generated)) for r in picked]


def decode_witness(fe, engine, samples):
    """The sampled requests' decode rows, replayed: a sample a dict as
    `check_glm_moe_dsa.decode_witness_gap` takes it, or None where the
    request holds no lane any more or was served under two tokens. The
    engine's lanes are shared out among the samples; a sample's positions
    are spread over its served tokens that were fed back (all but the
    last), the last of them always among them."""
    mgr = engine.manager
    lanes = engine.max_batch_size
    live = [r for r in fe.scheduler.slots if r is not None]
    tokens = np.zeros((lanes,), np.int32)
    lens = np.zeros((lanes,), np.int32)
    tables = np.zeros((lanes, mgr.table_width), np.int32)
    asked, lane = [], 0
    for prompt, served in samples:
        req = next((r for r in live if len(r.prompt) == len(prompt)
                    and (r.prompt == np.asarray(prompt)).all()), None)
        if req is None or len(served) < 2:
            asked.append(None)
            continue
        at = len(prompt) + np.unique(np.linspace(
            0, len(served) - 2, lanes // len(samples)).astype(int))
        ids = list(prompt) + list(served)
        mine = slice(lane, lane + len(at))
        tokens[mine], lens[mine] = [ids[p] for p in at], at + 1
        tables[mine] = mgr.block_table_array([req.seq_id])[0]
        asked.append({"at": at, "lanes": mine})
        lane += len(at)
    if lane:
        got = engine.attention_witness(tokens, lens, tables)
    return [a and dict(at=a["at"], **{k: got[k][:, a["lanes"]] for k in got})
            for a in asked]


def run(job):
    base = job["check"]
    serve = base.load("runners/serve.py")
    check = base.load("check_glm_moe_dsa.py")
    costs = base.load("costs_glm_moe_dsa.py")
    engine = build(job, check)

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
                         prefill_chunk_tokens=dep["prefill_chunk_tokens"],
                         prefix_cache=bool(dep.get("prefix_cache", False)))
    serve.warm_up(fe, dep)
    ahead = compile_ahead(engine, check, job["config"])

    at_open, closed = {}, {}
    opened = job["window_started"]

    def window_started(t):
        if ahead is not None and ahead.is_alive():
            print("    STILL COMPILING AHEAD as the window opens: the host "
                  "is shared with it", flush=True)
        at_open["load"] = engine.expert_load()
        at_open["selection"] = engine.selection_load()
        opened(t)

    def served_gap(cfg, seed, dtype, samples, **kw):
        # the counters are read and the decode rows replayed first; then
        # nothing the comparison reads lives in the pools or the weights:
        # the window is over, drop them
        closed["load"] = engine.expert_load()
        closed["selection"] = engine.selection_load()
        if ahead is not None:
            ahead.join()
        samples = fitting_sample(fe, cfg, seed)
        if not samples:
            closed["compared"].add("reference_sample_missing", 1, 0)
            return 0.0, 0.0, 0
        t = time.perf_counter()
        witness = decode_witness(fe, engine, samples)
        print(f"    decode rows replayed {time.perf_counter() - t:.1f} s",
              flush=True)
        engine.pools = engine.params = None
        gc.collect()
        found = {}
        out = check.served_gap(cfg, seed, dtype, samples, witness=witness,
                               found=found, **kw)
        gap = found.get("decode_witness")
        if gap is None:
            closed["compared"].add("decode_witness_missing", 1, 0)
        else:
            for name, value in zip(("selection_miss", "attention_error"), gap):
                closed["compared"].add(f"decode_{name}_worst", value,
                                       cfg["check"][f"decode_{name}_limit"])
        return out

    def compared():
        closed["compared"] = check.Compared()
        return closed["compared"]

    judge = types.SimpleNamespace(Compared=compared, served_gap=served_gap)
    out = serve.drive(dict(job, check=judge, costs=costs,
                           window_started=window_started), fe, hook)
    if "load" not in closed:           # no reference ran (the knee sweep)
        closed["load"] = engine.expert_load()
        closed["selection"] = engine.selection_load()
    closed.pop("compared", None)
    load, sel = closed["load"], closed["selection"]
    out["record"].update(
        expert_load={k: load[k] - at_open["load"][k]
                     for k in ("tokens", "touched", "steps")},
        held_experts=load["held"],
        selection_load={k: sel[k] - at_open["selection"][k] for k in sel},
        sparse_attn_bytes_traced=costs.traced["sparse_bytes"],
        sparse_attn_flops_traced=costs.traced["sparse_flops"],
        index_score_bytes_traced=costs.traced["index_bytes"])
    return out
