"""Engine step. Device time of the operations under the scopes
`llama.moe_router`, `llama.moe_dispatch` and `llama.moe_combine` (everything
of an expert layer that is not an expert's matmul: scores and top-k, the
sort by expert and the gather, the gather back and the weighted sum) over
the device's busy time in the traced steps."""
import program_trace


def read(rec):
    return program_trace.share(rec, program_trace.has(
        "llama.moe_router", "llama.moe_dispatch", "llama.moe_combine"))
