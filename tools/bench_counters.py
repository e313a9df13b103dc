"""One run of the UNEDITED benchmark in this process, then the program's own
counters of the same process, printed after the benchmark's result line.

A counter that no per-layer metric of `BENCHMARK.json` reads yet (the round
in flight's `serving.step.forced_settles`, a state group's
`serving.state.*`; `serving.step.overlapped` and `wasted_lanes` were read
this way until PR 44 gave them `round_overlap_share.*` and
`wasted_lanes_per_step.*`) is read this way in a builder's chip runs, on
either side of a comparison: `benchmark/run.py` runs under `runpy` with
its own arguments, and the `framework.monitor` registry it filled is
printed as one `COUNTERS {...}` line. Nothing under `benchmark/` is
touched and the result line is the benchmark's own.

Usage:
    python3 tools/bench_counters.py <root of a checkout> [--prefix P ...] \
        --workload <cell> --seed <n> --seconds <s> --trace <0|1>

`<root>` may be another checkout (the parent's `git archive`): the counters
a program lacks read 0. `--prefix` (repeatable, default `serving.step.`
plus the fault, retrace and preemption counters) picks what is printed.
"""
from __future__ import annotations

import json
import os
import runpy
import sys

_DEFAULT = ("serving.step.", "serving.ragged_retraces",
            "serving.logits_retraces", "serving.preemptions", "serving.step_faults",
            "serving.engine_restarts")


def main(argv) -> int:
    root = os.path.abspath(argv[0])
    rest, prefixes = [], []
    it = iter(argv[1:])
    for a in it:
        if a == "--prefix":
            prefixes.append(next(it))
        else:
            rest.append(a)
    sys.argv = [os.path.join(root, "benchmark", "run.py")] + rest
    os.chdir(root)
    sys.path[:0] = [root, os.path.join(root, "benchmark")]
    try:
        runpy.run_path(sys.argv[0], run_name="__main__")
        code = 0
    except SystemExit as e:
        code = e.code or 0
    from paddle_tpu.framework import monitor   # the checkout's, filled by now

    wanted = tuple(prefixes) or _DEFAULT
    seen = {k: v for k, v in monitor.snapshot(include_histograms=False).items()
            if k.startswith(wanted)}
    print("COUNTERS " + json.dumps(seen), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
