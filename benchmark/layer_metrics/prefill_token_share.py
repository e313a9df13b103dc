"""Scheduler. Prefill tokens over all packed tokens (prefill tokens + decode
lanes), summed over the window's steps through the `on_ragged_step` hook."""


def read(rec):
    total = rec.get("prefill_tokens", 0) + rec.get("decode_lanes", 0)
    return 100.0 * rec["prefill_tokens"] / total if total else None
