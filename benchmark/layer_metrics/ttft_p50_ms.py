"""Frontend. Median time from the instant a request was due to its first
token, by the benchmark's own stamps (a request without one counts the
drain limit)."""
import numpy as np


def read(rec):
    return float(np.percentile(rec["ttft_ms"], 50)) if rec.get("ttft_ms") else None
