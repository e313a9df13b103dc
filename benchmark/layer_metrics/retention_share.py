"""Engine step. Device time of everything under the scope `llama.retention`
(the gate, the one-token update, the chunked form) over the device's busy
time in the traced steps: the share of a step that is the layer's own and
not the weights' matmuls."""
import check


def read(rec):
    return check.load("layer_metrics/retention_update_share.py").read(
        rec, "llama.retention")
