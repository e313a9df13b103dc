"""Kernels. The least time the chip could take for the bytes the index-score
calls of the traced steps need (every live lane's index keys once a `full`
layer, the query rows in and the causal scores out:
`costs_glm_moe_dsa.index_score_bytes`, tallied by the runner a traced step)
at the published HBM rate, over the device time of the kernel
`dsa_index_scores`, told by its name. Bytes-bound at decode: a lane's one
query does `2 x 32` FLOPs a byte of keys it reads."""
import program_trace


def read(rec):
    pt = program_trace.of(rec)
    if pt is None or not rec.get("index_score_bytes_traced") \
            or not rec.get("peaks"):
        return None
    kernel = pt.op_seconds(rec["trace"]["ops"],
                           program_trace.has("dsa_index_scores"))
    if not kernel:
        return None
    return (100.0 * rec["index_score_bytes_traced"]
            / rec["peaks"]["hbm_bytes_per_s"] / kernel)
