"""Engine step, train step. Median host-clock time of one step over the
window's steps. A serving step is `fe.step()`, which ends in a host fetch of
the sampled tokens (dispatch + device + fetch); a training step runs from
the feed to `block_until_ready` on the loss."""
import numpy as np


def read(rec):
    return float(np.percentile(rec["step_ms"], 50)) if rec.get("step_ms") else None
