#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of `BENCHMARK.json`: set-up (build, seeded weights,
warm-up of the cell's own shapes; all of it counted as `setup_s`), a measured
window of `--seconds`, the comparison that decides `correct`, and as the last
line of stdout one JSON object. `--trace 0` reports the cell's end-to-end
metrics; `--trace 1` traces the start of the window and reports its per-layer
metrics. Everything that belongs to one configuration, traffic mix, runner,
generator or per-layer metric is a file found by name (benchmark/README.md).

It measures only on a TPU and fails without one. `--rehearse` (CPU, tiny
presets of `benchmark/tests/`) walks the same code and prints counts, never
the result line.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import shutil     # noqa: E402
import sys        # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_SECONDS = 8.0      # serving: the traced start of the window
TRACE_STEPS = 10         # training: the traced first steps of the window


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("ref-int8", "weights-int8"),
                    help="run the control instead: `correct` must come out "
                         "false (benchmark/README.md)")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU walk-through of a tiny preset; counts only")
    ap.add_argument("--bench-file", default=os.path.join(ROOT, "BENCHMARK.json"))
    return ap.parse_args(argv)


def load_cell(bench_file, name):
    """The benchmark file, and the cell `name` with its configuration and
    its traffic mix, each from the file its name finds."""
    with open(bench_file) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in {bench_file}; it has "
                         f"{[w['name'] for w in bench['workloads']]}")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return bench, cell, config, traffic


def _reports(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def _reader(name):
    """A per-layer metric's reader: `layer_metrics/<name>.py`, or the file
    of the name up to its last dot, so that `step_ms_p50.gap`, `.serve`
    and `.train` are read by the one `step_ms_p50.py`."""
    for stem in (name, name.rpartition(".")[0]):
        path = os.path.join(HERE, "layer_metrics", stem + ".py")
        if stem and os.path.exists(path):
            return path
    raise SystemExit(f"no reader for the per-layer metric {name!r} under "
                     "benchmark/layer_metrics/")


def main(argv=None):
    args = _args(argv)
    bench, cell, config, traffic = load_cell(args.bench_file, args.workload)

    # the compile cache: where the caller says, else a fixed path in the
    # checkout; every program is kept, however quickly it compiled (a
    # rehearsal's CPU programs are not worth keeping)
    import jax

    if args.rehearse:
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir",
                              os.path.join(ROOT, ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    devices = jax.devices()
    platform = devices[0].platform
    if args.rehearse:
        if platform != "cpu":
            raise SystemExit("--rehearse is the CPU walk-through; run it "
                             "with JAX_PLATFORMS=cpu")
    elif platform != "tpu" or len(devices) < cell["chips"]:
        print(f"this cell measures on {cell['chips']} TPU chip(s); JAX "
              f"found {len(devices)} x {platform}", file=sys.stderr)
        return 3

    sys.path.insert(0, ROOT)            # the program under test
    sys.path.insert(0, HERE)
    import check
    import costs
    import trace_reduce

    if args.rehearse and not config.get("rehearsal"):
        raise SystemExit("--rehearse runs only a preset marked 'rehearsal'")
    peaks = None if args.rehearse else costs.peaks(devices[0].device_kind)
    trace_dir = os.path.join(ROOT, ".bench_trace", cell["name"])
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)

    marks = {"not_setup": 0.0}
    job = {
        "config": config, "traffic": traffic, "seed": args.seed,
        "seconds": args.seconds, "trace": bool(args.trace),
        "trace_dir": trace_dir, "trace_seconds": TRACE_SECONDS,
        "trace_steps": TRACE_STEPS, "control": args.control,
        "check": check, "costs": costs, "span": trace_reduce.Spans(),
        "generator": check.load(f"generators/{traffic['generator']}.py"),
        "window_started": lambda t: marks.__setitem__("window", t),
        "not_setup": lambda s: marks.__setitem__(
            "not_setup", marks["not_setup"] + s),
    }
    print(f"[{cell['name']}] seed {args.seed}, {args.seconds:g} s, "
          f"{len(devices)} x {devices[0].device_kind}", flush=True)
    out = check.load(f"runners/{config['runner']}.py").run(job)
    setup_s = marks["window"] - _T0 - marks["not_setup"]

    compared = out["compared"]
    compared.print()
    correct = compared.ok
    rec = out["record"]
    rec.update(peaks=peaks, config=config, traffic=traffic,
               is_pallas=trace_reduce.is_pallas)
    end_to_end = dict(out["end_to_end"], setup_s=setup_s)
    mem = rec.get("memory") or {}
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": mem.get("peak_bytes_in_use")}
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": {}, "device": device}

    if args.trace and not args.rehearse:
        tr = trace_reduce.reduce(trace_reduce.find_xplane(trace_dir),
                                 rec.get("span_names", ()))
        rec["trace"] = tr
        device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
        result["breakdown"] = {"device_ops": trace_reduce.top_ops(tr),
                               "idle_gaps": trace_reduce.idle_by_span(tr)}
    if args.trace:
        for m in bench["per_layer"]:
            if _reports(m, cell["name"]):
                value = check.load(_reader(m["name"])).read(rec)
                if value is not None:
                    result["metrics"][m["name"]] = {"value": value,
                                                    "unit": m["unit"]}
    else:
        for m in bench["end_to_end"]:
            if _reports(m, cell["name"]):
                result["metrics"][m["name"]] = {
                    "value": end_to_end[m["name"]], "unit": m["unit"]}

    if args.rehearse:
        # counts only: a CPU's times are never written under a device
        # metric's name, and the result line is never printed
        print("REHEARSAL " + json.dumps(
            {"correct": correct, "attempted": out["attempted"],
             "failed": out["failed"],
             "would_report": sorted(result["metrics"])}), flush=True)
        return result
    # each number compared beside its limit: the result's last key, and the
    # last lines of standard error (what the driver keeps of a run at fault)
    result["compared"] = compared.as_dict()
    print(json.dumps(result), flush=True)
    compared.print(sys.stderr)
    return result


if __name__ == "__main__":
    r = main()
    sys.exit(r if isinstance(r, int) else 0)
