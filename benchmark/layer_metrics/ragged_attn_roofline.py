"""Kernels. `attn_kernel_roofline`'s arithmetic (the bytes the algorithm
needs in the traced steps at the published HBM rate; bytes-bound) over the
device time of the kernel `paged_attention_ragged` told by its name, not by
its being the step's only Pallas call."""
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
