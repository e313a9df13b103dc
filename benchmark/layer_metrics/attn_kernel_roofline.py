"""Kernels. The least time the chip could take for the bytes the algorithm
needs in the traced steps (K and V of the live contexts once a step and
layer, q in and o out: `costs.ragged_attention_bytes`) at the published
HBM rate, over the kernel's device time. Bytes-bound: at one query row a
lane the FLOPs are a hundredth of what the bytes allow."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not rec.get("attn_bytes_traced") or not rec.get("peaks"):
        return None
    kernel = sum(v for k, v in tr["ops"].items() if rec["is_pallas"](k))
    if not kernel:
        return None
    least = rec["attn_bytes_traced"] / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / kernel
