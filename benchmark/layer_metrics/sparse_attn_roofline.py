"""Kernels. The least time the chip could take for what the sparse attention
of the traced steps needs (the larger of the selected rows' bytes at the
published HBM rate and the FLOPs over the selected positions at the published
bf16 peak; `costs_glm_moe_dsa.py`, tallied by the runner a traced step) over
the device time of everything under the scope `llama.attn_sparse`: the gather
that reads the selected rows out of the pool AND the kernel
`mla_sparse_attention` that attends over them (the kernel's time alone would
leave the pool's read out of the denominator)."""
import program_trace


def read(rec):
    pt = program_trace.of(rec)
    if pt is None or not rec.get("sparse_attn_bytes_traced") \
            or not rec.get("peaks"):
        return None
    spent = pt.op_seconds(rec["trace"]["ops"],
                          program_trace.has("llama.attn_sparse"))
    if not spent:
        return None
    least = max(
        rec["sparse_attn_bytes_traced"] / rec["peaks"]["hbm_bytes_per_s"],
        rec.get("sparse_attn_flops_traced", 0.0)
        / rec["peaks"]["bf16_flops_per_s"])
    return 100.0 * least / spent
