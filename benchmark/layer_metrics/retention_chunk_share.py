"""Kernels. Device time of the operations under the scope
`llama.retention_chunk` (the chunked form of power retention over the prefill
lanes: the kernel `power_retention_chunk` and what surrounds its call) over
the device's busy time in the traced steps."""
import check


def read(rec):
    return check.load("layer_metrics/retention_update_share.py").read(
        rec, "llama.retention_chunk")
