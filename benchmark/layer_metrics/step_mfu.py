"""Engine step. Model FLOP/s utilisation of a serving window: the FLOPs the
model needs for the rows the window processed (`costs.serve_flops`, or the
architecture's own: projections 2 FLOPs a parameter a row, the head for
sampled rows only, attention by each row's own context; tallied by the
runner from its stamps once the window has closed) over the window's
seconds and the chip's published bf16 peak. It is the whole step's share of
the peak beside the kernels' rooflines: a kernel taken off the path leaves
its roofline silent, and this still bounds the gain."""


def read(rec):
    if not rec.get("served_flops") or not rec.get("peaks"):
        return None
    return (100.0 * rec["served_flops"] / rec["window_s"]
            / rec["peaks"]["bf16_flops_per_s"])
