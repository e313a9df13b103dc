"""Device. Device-idle time inside the `sched.step` spans and outside their
waiting spans, in ms a step: the idle that the scheduler's Python causes.
The rest of `host_ms_per_step` is launch and fetch latency."""
import program_trace


def read(rec):
    pt = program_trace.of(rec)
    if pt is None or not pt.steps:
        return None
    idle = sum(pt.idle_outside_waiting_s(s) for s in pt.steps)
    return 1e3 * idle / len(pt.steps)
