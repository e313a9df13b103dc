"""Kernels. `window_attn_roofline`'s twin for the FULL-attention layers: the
least time for K and V of every lane's whole context once a step and full
layer, q in and o out, at the published HBM rate, over the device time of the
kernel `paged_attention_ragged` under the scope `llama.attn_full`."""
import check


def read(rec):
    return check.load("layer_metrics/window_attn_roofline.py").read(
        rec, "llama.attn_full", "full_attn_bytes_traced")
