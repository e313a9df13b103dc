"""A builder's own traced SET-UP of the Brumby cell (no benchmark cell times
its prefill yet: PERF.md section 7 queues one), on the chip:

1. the two power-retention kernels against their plain `jnp` twins at the
   published head size (128) on a small state, on the device itself (what
   the Pallas interpreter on a CPU cannot show: Mosaic's own arithmetic);
2. the cell's engine, built by the benchmark's own runner from `--seed`,
   prefills `--prompts` one at a time under the profiler; the device time of
   the kernel `power_retention_chunk` is held against the least time the
   chip could take for `benchmark/costs_brumby.retention_chunk_flops` and
   `_bytes` of the same chunks, and the step's time by region is printed.

    python3 tools/brumby_prefill_trace.py --seed 3000045001 [--prompts 4096,1000]

Prints one JSON line, `CHUNK_TRACE {...}`. It measures only on a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path[:0] = [ROOT, BENCH]


def kernels_against_twins():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas import power_retention as pr

    rng = np.random.default_rng(45)
    L, slots, KV, G, d, B, T = 2, 5, 2, 5, 128, 4, 300
    o = pr.n_offsets(d)
    S = jnp.asarray(rng.normal(size=(L, slots, KV, o, d, d)), jnp.float32)
    z = jnp.asarray(np.abs(rng.normal(size=(L, slots, KV, o, d))) + 8,
                    jnp.float32)

    def rows(n):
        return (jnp.asarray(rng.normal(size=(n, KV, G, d)), jnp.float32) * 0.1,
                jnp.asarray(rng.normal(size=(n, KV, d)), jnp.bfloat16),
                jnp.asarray(rng.normal(size=(n, KV, d)), jnp.bfloat16),
                jnp.asarray(-np.abs(rng.normal(size=(n, KV))) * 0.01,
                            jnp.float32))

    out = {}
    slot = jnp.asarray([3, 1, 0, 2], jnp.int32)
    for name, live, fresh in (("update", [1, 0, 1, 1], [0, 0, 1, 0]),
                              ("update_none", [0, 0, 0, 0], [0, 0, 0, 0])):
        kw = dict(layer=1, slot=slot, live=jnp.asarray(live, bool),
                  fresh=jnp.asarray(fresh, bool), eps=1e-6)
        lanes = rows(B)
        want = jax.jit(lambda *a: pr.power_retention_update_ref(*a, **kw))(
            *lanes, S, z)
        got = jax.jit(lambda *a: pr.power_retention_update(*a, **kw))(
            *lanes, S, z)
        out[name] = [float(jnp.abs(g - w).max() / (jnp.abs(w).max() + 1e-9))
                     for g, w in zip(got, want)]
    q_lens = [1, 200, 0, 60]
    lane_of = np.full((T,), -1, np.int32)
    lane_of[:sum(q_lens)] = np.repeat(np.arange(B), q_lens)
    kw = dict(layer=0, slot=slot, live=jnp.asarray([0, 1, 0, 1], bool),
              fresh=jnp.asarray([0, 1, 0, 0], bool),
              tok_lane=jnp.asarray(lane_of), eps=1e-6)
    packed = rows(T)
    want = jax.jit(lambda *a: pr.power_retention_chunk_ref(*a, **kw))(
        *packed, S, z)
    got = jax.jit(lambda *a: pr.power_retention_chunk(*a, **kw))(
        *packed, S, z)
    out["chunk"] = [float(jnp.abs(g - w).max() / (jnp.abs(w).max() + 1e-9))
                    for g, w in zip(got, want)]
    print(f"    kernels against their twins at head size {d}, worst "
          f"|got - want| / max |want| of (y, S, z): {out}", flush=True)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--prompts", default="4096,1000")
    ap.add_argument("--workload", default="brumby14b-longgen-decode")
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("this measures on a TPU", file=sys.stderr)
        return 3
    import numpy as np

    import check
    import costs as base_costs
    import program_trace
    import trace_reduce

    run = check.load("run.py", "benchmark_run_tool")
    _bench, cell, cfg, _traffic = run.load_cell(
        os.path.join(ROOT, "BENCHMARK.json"), args.workload)
    result = {"kernels": kernels_against_twins()}
    mine = check.load("check_brumby.py")
    costs = check.load("costs_brumby.py")
    runner = check.load("runners/serve_brumby.py")
    serve = check.load("runners/serve.py")
    engine = runner.build({"config": cfg, "seed": args.seed, "control": None},
                          mine)

    from paddle_tpu.serving import ServingFrontend

    dep = cfg["deployment"]
    chunk = dep["prefill_chunk_tokens"]
    fe = ServingFrontend(engine, prefill_chunk_tokens=chunk)
    serve.warm_up(fe, dep)
    trace_dir = os.path.join(ROOT, ".bench_trace", "brumby-prefill")
    shutil.rmtree(trace_dir, ignore_errors=True)
    rng = np.random.default_rng(args.seed)
    lens = [int(n) for n in args.prompts.split(",")]
    jax.profiler.start_trace(trace_dir)
    for n in lens:
        h = fe.submit(rng.integers(1, cfg["vocab_size"], n).tolist(),
                      max_new_tokens=2)
        fe.run_until_idle()
        assert len(h.tokens) == 2, h
    jax.profiler.stop_trace()
    tr = trace_reduce.reduce(trace_reduce.find_xplane(trace_dir))
    pt = program_trace.ProgramTrace(trace_reduce.find_xplane(trace_dir))
    kernel = pt.op_seconds(tr["ops"], program_trace.has("power_retention_chunk"))
    chunks = [min(chunk, n - at) for n in lens for at in range(0, n, chunk)]
    peaks = base_costs.peaks(jax.devices()[0].device_kind)
    layers = cfg["num_hidden_layers"]
    flops = layers * costs.retention_chunk_flops(cfg, chunks)
    moved = layers * costs.retention_chunk_bytes(cfg, chunks)
    least = max(flops / peaks["bf16_flops_per_s"],
                moved / peaks["hbm_bytes_per_s"])
    regions = sorted(pt.by_region(tr["ops"]).items(), key=lambda kv: -kv[1])
    result.update(
        prompts=lens, chunk_steps=len(chunks), busy_s=tr["busy_s"],
        window_s=tr["window_s"], chunk_kernel_s=kernel,
        chunk_flops=flops, chunk_bytes=moved,
        chunk_roofline_pct=100.0 * least / kernel if kernel else None,
        chunk_share_pct=100.0 * kernel / tr["busy_s"],
        by_region_pct={k: round(100.0 * v / tr["busy_s"], 2)
                       for k, v in regions[:14]})
    print("CHUNK_TRACE " + json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
