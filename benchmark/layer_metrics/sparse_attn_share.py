"""Kernels. Device time of the operations under the scope `llama.attn_sparse`
(the gather of each live row's selected latent rows through the block table
and the kernel `mla_sparse_attention` over them) over the device's busy time
in the traced steps."""
import check


def read(rec):
    return check.load("layer_metrics/dsa_index_share.py").read(
        rec, "llama.attn_sparse")
