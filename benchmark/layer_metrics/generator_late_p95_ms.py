"""Frontend. How late the load generator ran: submit time less due time,
95th percentile. The loop is one thread, so a long `fe.step()` makes the
next arrivals late; TTFT counts from the due time all the same."""
import numpy as np


def read(rec):
    return float(np.percentile(rec["late_ms"], 95)) if rec.get("late_ms") else None
