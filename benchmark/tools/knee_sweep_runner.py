#!/usr/bin/env python3
"""`knee_sweep.py` for a cell whose configuration names another runner than
`serve` (an architecture with a build of its own): the same sweep, one build,
one window a rate, the same columns, through the runner's own `build` and
`runners/serve.py`'s `warm_up` and `drive`. `knee_sweep.py` builds through
`serve.build`, which knows the Llama family alone.

    python3 benchmark/tools/knee_sweep_runner.py --workload kanana2-docqa-open \\
        --rates 1.6,2.4,3.2,4.0,4.8 --seconds 40 --seed 7
"""
import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

# runner -> (the module that decides `correct` for it, its costs)
MODULES = {"serve_deepseek_v3": ("check_deepseek_v3.py", "costs_deepseek_v3.py"),
           "serve_cohere2_moe": ("check_cohere2_moe.py", "costs_cohere2_moe.py")}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--bench-file", default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args()
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import check

    _, _, config, traffic = check.load("run.py").load_cell(
        args.bench_file, args.workload)
    if jax.devices()[0].platform != "tpu" and not config.get("rehearsal"):
        raise SystemExit("the knee is a property of the chip; no TPU here")
    serve = check.load("runners/serve.py")
    runner = check.load(f"runners/{config['runner']}.py")
    own_check, own_costs = (check.load(m) for m in MODULES[config["runner"]])
    job = {"config": config, "seed": args.seed, "seconds": args.seconds,
           "trace": False, "control": None, "check": own_check,
           "costs": own_costs,
           "generator": check.load(f"generators/{traffic['generator']}.py"),
           "span": check.load("trace_reduce.py").Spans(),
           "window_started": lambda t: None, "no_reference": True}
    from paddle_tpu.serving import ServingFrontend
    from paddle_tpu.serving.metrics import ServingMetrics

    class Hook(ServingMetrics):
        counting = False
        steps = prefill_tokens = decode_lanes = 0

    dep = config["deployment"]
    hook = Hook()
    fe = ServingFrontend(runner.build(job, own_check), metrics=hook,
                         prefill_chunk_tokens=dep["prefill_chunk_tokens"],
                         prefix_cache=bool(dep.get("prefix_cache", False)))
    serve.warm_up(fe, dep)
    print("rate_per_s attempted failed ttft_p50_ms ttft_p90_ms gap_p95_ms "
          "queue_mean_first_half queue_mean_second_half queue_at_end "
          "drain_s step_ms_p50", flush=True)
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = json.loads(json.dumps(traffic))
        mix["arrivals"]["rate_per_s"] = rate
        out = serve.drive(dict(job, traffic=mix), fe, hook)
        rec = out["record"]
        q = np.asarray(rec["queue_depth"], float)
        half = q[:, 0] < args.seconds / 2
        print(rate, out["attempted"], out["failed"],
              round(float(np.percentile(rec["ttft_ms"], 50)), 1),
              round(float(np.percentile(rec["ttft_ms"], 90)), 1),
              round(out["end_to_end"]["gap_p95_ms"], 1),
              round(float(q[half, 1].mean()), 2),
              round(float(q[~half, 1].mean()), 2), int(q[-1, 1]),
              round(rec["window_s"] - args.seconds, 2),
              round(float(np.percentile(rec["step_ms"], 50)), 1), flush=True)


if __name__ == "__main__":
    main()
