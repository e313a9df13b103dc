"""Engine step. Device time of everything under the scope `llama.dsa_index`
(a `full` layer's indexer: its projections, the index pool's write, the
kernel `dsa_index_scores`, the selection) over the device's busy time in the
traced steps: what a step pays to know where to attend."""
import check

SCOPE = "llama.dsa_index"


def read(rec, scope=SCOPE):
    # a scope's share of the device's busy time, None where the program has
    # no such scope: the reader the Brumby scopes already have
    return check.load("layer_metrics/retention_update_share.py").read(
        rec, scope)
