"""Scheduler. From a request's `sched.admit_one` to its `sched.first_token`
(the same `req` id): the wait in the lane, prefill included, which the queue
does not show. Median over the requests with both edges in the traced
window; the count goes to the log."""
import statistics

import program_trace


def read(rec):
    pt = program_trace.of(rec)
    if pt is None:
        return None
    admitted = {}
    for s in pt.named("sched.admit_one"):
        admitted.setdefault(s.ids.get("req"), s.start)   # the first admission
    waits = [s.start - admitted[s.ids.get("req")]
             for s in pt.named("sched.first_token")
             if s.ids.get("req") in admitted]
    if not waits:
        return None
    print(f"    lane_wait_p50_ms: {len(waits)} requests admitted and first "
          f"served inside the trace, of {len(admitted)} admitted", flush=True)
    return 1e3 * statistics.median(waits)
