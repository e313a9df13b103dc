"""Kernels. Device time of the kernel `paged_attention_ragged` where it runs
under the scope `llama.attn_window` (a sliding-window layer's attention: the
page walk starts behind the window) over the device's busy time in the traced
steps. Beside `ragged_attn_share`, which is the kernel's time in layers of
both kinds."""
import program_trace

KERNEL = "paged_attention_ragged"


def under(scope_name):
    """A matcher: the kernel's operations under the region `scope_name`."""
    return lambda scope: {scope_name, KERNEL} <= set(
        program_trace.tokens(scope))


def read(rec):
    pt = program_trace.of(rec)
    if pt is None or not pt.op_seconds(rec["trace"]["ops"],
                                       under("llama.attn_window")):
        return None         # a program without window layers: nothing to read
    return program_trace.share(rec, under("llama.attn_window"))
