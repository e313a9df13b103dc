"""Engine step. Device time of the operations under the scope `llama.moe`
(an expert layer's feed-forward: router, dispatch, routed experts, shared
experts, combine) over the device's busy time in the traced steps."""
import program_trace


def read(rec):
    return program_trace.share(rec, program_trace.has("llama.moe"))
