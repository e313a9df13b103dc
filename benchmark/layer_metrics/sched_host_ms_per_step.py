"""Scheduler. Per traced `sched.step` span: its wall less its waiting spans
(`sched.dispatch`, the round's one launch, and `sched.sample`, its one fetch:
where the host waits for the device): the scheduler's own Python, in ms a step."""
import program_trace


def read(rec):
    pt = program_trace.of(rec)
    if pt is None or not pt.steps:
        return None
    own = sum(s.wall - pt.waiting_s(s) for s in pt.steps)
    return 1e3 * own / len(pt.steps)
