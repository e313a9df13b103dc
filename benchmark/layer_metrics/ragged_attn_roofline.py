"""Kernels. The least time the chip could take for the bytes the algorithm
needs in the traced steps (K and V of the live contexts once a step and
layer, q in and o out: `costs.ragged_attention_bytes`) at the published
HBM rate, over the device time of the kernel `paged_attention_ragged`, told
by its name. Bytes-bound: at one query row a lane the FLOPs are a hundredth
of what the bytes allow."""
import program_trace


def read(rec):
    pt = program_trace.of(rec)
    if pt is None or not rec.get("attn_bytes_traced") or not rec.get("peaks"):
        return None
    kernel = pt.op_seconds(rec["trace"]["ops"],
                           program_trace.has("paged_attention_ragged"))
    if not kernel:
        return None
    least = rec["attn_bytes_traced"] / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / kernel
