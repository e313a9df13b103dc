"""Scheduler. Median wall of the traced `sched.step`s that hold a
`sched.admit_one` or a `sched.finish`, less the median of those that hold
neither: what admitting or finishing a request adds to a step."""
import statistics

import program_trace


def read(rec):
    pt = program_trace.of(rec)
    if pt is None:
        return None
    marked, plain = [], []
    for s in pt.steps:
        (marked if s.holds("sched.admit_one", "sched.finish")
         else plain).append(s.wall)
    if not marked or not plain:
        return None
    print(f"    admit_step_extra_ms: {len(marked)} steps admit or finish, "
          f"{len(plain)} do neither", flush=True)
    return 1e3 * (statistics.median(marked) - statistics.median(plain))
