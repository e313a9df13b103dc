"""Kernels. Device time of the operations under the scope
`llama.retention_update` (the one-token state update and read of the decode
lanes: the kernel `power_retention_update` and the gathers and the division
around its call) over the device's busy time in the traced steps."""
import program_trace

SCOPE = "llama.retention_update"


def read(rec, scope=SCOPE):
    pt = program_trace.of(rec)
    if pt is None or not pt.op_seconds(rec["trace"]["ops"],
                                       program_trace.has(scope)):
        return None         # a program without the layer: nothing to read
    return program_trace.share(rec, program_trace.has(scope))
