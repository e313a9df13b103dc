"""Scheduler. Median time from a request's due time to the end of the first
step after which it holds a lane (its handle left QUEUED)."""
import numpy as np


def read(rec):
    waits = rec.get("queue_wait_ms")
    return float(np.percentile(waits, 50)) if waits else None
