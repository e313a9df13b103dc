"""Frontend. 90th percentile of the time from the instant a request was due
to its first token (a request without one counts the drain limit). What a
chat user feels most, and not an end-to-end metric yet: over one window's 82
requests it swings with their order far beyond any bound (PERF.md)."""
import numpy as np


def read(rec):
    return float(np.percentile(rec["ttft_ms"], 90)) if rec.get("ttft_ms") else None
