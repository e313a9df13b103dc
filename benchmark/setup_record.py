"""What the program recorded of a set-up, for the `setup_*_s` readers: the
compile record that JAX's own events feed
(`paddle_tpu.observability.compile_trace`: one record a top-level compile,
with its seconds tracing, lowering and in `backend_compile`, which is the
compile or the read from the persistent cache), the stamps of the phases that
are not JAX's (`startup.import`, `engine.build`), and the monitor's values
(`startup.import_s`, `engine.build_s`, `serving.step.wall_s`).

`of(rec)` returns `None` where the program keeps no such record (a program
older than the record), and prints the whole record to the log once a
process: a reader sums the cell's own step program by name, and the log shows
what that left out (the reference's programs, the weights', the eager ones).
"""
from __future__ import annotations

# the one compiled step a cell's window drives, by JAX's name for it: the
# three engines' step ends in `ops/sampling.with_tail`'s `_ragged_fn`; the
# train runner jits `bench.build_train_step`'s `train_step`. `correct` holds
# `moved_ragged_retraces` to 0, so no record of these names is the window's.
STEP_PROGRAM = {"train": "train_step"}
SERVING_STEP = "_ragged_fn"
_LOG_FLOOR_S = 0.010

_printed = False


class Setup:
    """The records since the newest engine was built (the run's own, where
    one process makes several runs), `step` those of the cell's program."""

    def __init__(self, monitor, records, stamps, program):
        self.monitor, self.stamps, self.program = monitor, stamps, program
        build = stamps.get("engine.build")
        self.records = [r for r in records
                        if build is None or r.start >= build[0]]
        self.step = [r for r in self.records if r.name == program]

    def value(self, name):
        """A monitor value, `None` where the program never set it."""
        return self.monitor.get(name) or None

    def inside(self, stamp):
        """Seconds of the records that lie inside the phase `stamp`."""
        began, ended = self.stamps[stamp]
        return sum(r.wall_s for r in self.records
                   if began <= r.start and r.end <= ended)

    def step_sum(self, field):
        if not self.step:
            return None
        return float(sum(getattr(r, field) for r in self.step))


def of(rec):
    global _printed
    try:
        from paddle_tpu.framework import monitor
        from paddle_tpu.observability import compile_trace
        stamps, records = compile_trace.stamps(), compile_trace.compiles()
    except (ImportError, AttributeError):
        return None
    program = STEP_PROGRAM.get((rec.get("config") or {}).get("runner"),
                               SERVING_STEP)
    found = Setup(monitor, records, stamps, program)
    if not _printed:
        _printed = True
        _print(found, records)
    return found


def _print(found, records):
    print(f"    set-up: import {found.value('startup.import_s')} s, engine "
          f"build {found.value('engine.build_s')} s, step wall "
          f"{found.value('serving.step.wall_s')} s; {len(records)} programs "
          f"compiled, the cell's step program is {found.program!r}",
          flush=True)
    small = [r for r in records if r.wall_s < _LOG_FLOOR_S]
    for r in records:
        if r.wall_s >= _LOG_FLOOR_S:
            print(f"      {r.name}: trace {r.trace_s:.3f} s, lowering "
                  f"{r.lower_s:.3f} s, backend {r.backend_s:.3f} s, cache "
                  f"{r.cache}" + (" (retrace)" if r.is_retrace else ""),
                  flush=True)
    if small:
        print(f"      and {len(small)} programs under {_LOG_FLOOR_S * 1e3:g} "
              f"ms each: trace {sum(r.trace_s for r in small):.3f} s, "
              f"lowering {sum(r.lower_s for r in small):.3f} s, backend "
              f"{sum(r.backend_s for r in small):.3f} s, "
              f"{sum(r.cache == 'hit' for r in small)} cache hits, "
              f"{sum(r.cache == 'miss' for r in small)} misses", flush=True)
