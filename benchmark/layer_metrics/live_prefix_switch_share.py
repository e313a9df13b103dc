"""Engine step. Device time of the operations that lie directly under a
layer's scope (`llama.layer` the innermost region of their path, no kernel)
over the device's busy time in the traced steps: in the two MoE engines what
`inference/live_prefix.py`'s switches themselves cost, the cut of a segment's
row arguments to the live prefix and the zero pad of its row outputs back to
the packed buffer. A program without the switches has next to nothing there.
The compiler's own copies of a switch's operands carry no scope path: they
stay in `unscoped_device_share`."""
import program_trace


def read(rec):
    return program_trace.share(
        rec, lambda scope: program_trace.region(scope) == "llama.layer"
        and not program_trace.kernel(scope))
