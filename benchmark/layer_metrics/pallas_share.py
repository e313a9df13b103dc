"""Kernels. Device time in Pallas custom calls over the device's busy time
in the traced steps. In the train step these are flash forward and backward,
`rms_norm` and `fused_rope`; the lowered ragged serving step holds exactly
one kind, the paged attention kernel, so there this is that kernel's share."""


def read(rec):
    tr = rec.get("trace")
    if not tr:
        return None
    kernel = sum(v for k, v in tr["ops"].items() if rec["is_pallas"](k))
    return 100.0 * kernel / tr["busy_s"] if kernel else None
