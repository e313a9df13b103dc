"""Engine step. Device time of the operations under the scope `llama.dsa_topk`
(the selection itself: `lax.top_k` over each live row's causal scores, a tile
of rows at a time) over the device's busy time in the traced steps."""
import check


def read(rec):
    return check.load("layer_metrics/dsa_index_share.py").read(
        rec, "llama.dsa_topk")
