"""Engine step. Device time of the operations under the scope
`llama.kv_write` (the write of a step's K and V into the paged pool) over
the device's busy time in the traced steps."""
import program_trace


def read(rec):
    return program_trace.share(rec, program_trace.has("llama.kv_write"))
