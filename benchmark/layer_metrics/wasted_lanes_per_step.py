"""Scheduler. Lanes of a settled round that belonged to requests which had
left their slots since its launch (monitor counter
`serving.step.wasted_lanes`, PR 43: computed, never read), over the window's
rounds (`serving.step.programs`). No cell's traffic ends on EOS, cancels or
preempts, so every cell reads 0 today; a count, not a share of a peak."""


def read(rec):
    rounds = rec.get("rounds")
    if not rounds or not rounds["programs"]:
        return None
    return rounds["wasted_lanes"] / rounds["programs"]
