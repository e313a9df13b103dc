"""Set-up. Seconds the scheduler spent stepping BEFORE the window opened: the
warm-up's steps and, in a backlog cell, the prefill steps that fill the lanes.
The scheduler's step wall over the whole run (monitor `serving.step.wall_s`,
one `inc` a `Scheduler.step`), less the window's own steps (`step_ms`, the
benchmark's stamps), less the step program's compile records (the first step
traces, lowers and compiles it): good to a step or two, since the step that
crosses the window's end is in the first sum and not in the second."""
import setup_record


def read(rec):
    found = setup_record.of(rec)
    wall = found and found.value("serving.step.wall_s")
    if not wall or "step_ms" not in rec:
        return None
    return (wall - sum(rec["step_ms"]) / 1e3
            - sum(r.wall_s for r in found.step))
