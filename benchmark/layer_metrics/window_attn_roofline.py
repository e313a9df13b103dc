"""Kernels. The least time the chip could take for the bytes the SLIDING
layers' attention needs in the traced steps (K and V of what a window reaches
back from each lane's queries, `min(context, window + q_len - 1)` rows a lane,
once a step and sliding layer, q in and o out:
`costs_cohere2_moe.layer_attention_bytes`) at the published HBM rate, over the
device time of the kernel `paged_attention_ragged` under the scope
`llama.attn_window`. Bytes-bound at decode. Counted over the context instead
of the window it would read above 100 % on a correct kernel."""
import check

import program_trace


def read(rec, scope="llama.attn_window", key="window_attn_bytes_traced"):
    pt = program_trace.of(rec)
    if pt is None or not rec.get(key) or not rec.get("peaks"):
        return None
    under = check.load("layer_metrics/window_attn_share.py").under
    kernel = pt.op_seconds(rec["trace"]["ops"], under(scope))
    if not kernel:
        return None
    return 100.0 * rec[key] / rec["peaks"]["hbm_bytes_per_s"] / kernel
