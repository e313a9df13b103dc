"""Scheduler. Mean decode lanes a step over the window's steps, through the
`on_ragged_step` hook."""


def read(rec):
    steps = rec.get("hook_steps", 0)
    return rec["decode_lanes"] / steps if steps else None
