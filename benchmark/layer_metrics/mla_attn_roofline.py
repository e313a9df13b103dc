"""Kernels. The least time the chip could take for what the kernel
`paged_attention_mla` had to do in the traced steps (the larger of the bytes
it needs at the published HBM rate and the FLOPs it needs at the published
bf16 peak; `costs_deepseek_v3.py`, tallied by the runner a traced step) over
the kernel's device time, told by its name."""
import program_trace


def read(rec):
    pt = program_trace.of(rec)
    if pt is None or not rec.get("attn_bytes_traced") or not rec.get("peaks"):
        return None
    kernel = pt.op_seconds(rec["trace"]["ops"],
                           program_trace.has("paged_attention_mla"))
    if not kernel:
        return None
    least = max(
        rec["attn_bytes_traced"] / rec["peaks"]["hbm_bytes_per_s"],
        rec.get("attn_flops_traced", 0.0) / rec["peaks"]["bf16_flops_per_s"])
    return 100.0 * least / kernel
