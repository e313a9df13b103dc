#!/usr/bin/env python3
"""Find the knee of an open-loop cell once, on the chip: the highest of a few
fixed rates at which the queue does not grow over the window. One build, one
window a rate (`drain_s` is how long after the window the last request due in
it waited for its first token: the backlog sits in the lanes, not in the
frontend's queue). The cell's mix then fixes its rate at 0.8 of the knee; no run of
the benchmark ever searches.

    python3 benchmark/tools/knee_sweep.py --workload mistral7b-chat-open \\
        --rates 1.2,1.6,2.0,2.4,2.8 --seconds 40 --seed 7
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
    import costs

    _, _, config, traffic = check.load("run.py").load_cell(
        args.bench_file, args.workload)
    if jax.devices()[0].platform != "tpu" and not config.get("rehearsal"):
        raise SystemExit("the knee is a property of the chip; no TPU here")
    serve = check.load("runners/serve.py")
    job = {"config": config, "seed": args.seed, "seconds": args.seconds,
           "trace": False, "control": None, "check": check, "costs": costs,
           "generator": check.load(f"generators/{traffic['generator']}.py"),
           "span": check.load("trace_reduce.py").Spans(),
           "window_started": lambda t: None, "no_reference": True}
    fe, hook = serve.build(job)
    serve.warm_up(fe, config["deployment"])
    print("rate_per_s attempted failed ttft_p50_ms ttft_p90_ms gap_p95_ms "
          "queue_mean_first_half queue_mean_second_half queue_at_end "
          "drain_s step_ms_p50", flush=True)
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = json.loads(json.dumps(traffic))
        mix["arrivals"]["rate_per_s"] = rate
        hook.steps = hook.prefill_tokens = hook.decode_lanes = 0
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
