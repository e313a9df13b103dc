"""The yardstick's arithmetic for a Cohere2-MoE configuration (Command A+):
the operations and bytes its mechanisms need, computed from shapes. Kept with
the benchmark (see `costs.py`, whose `peaks` it shares).

Two things differ from a dense model's count. A SLIDING layer's query sees
at most `sliding_window` keys, so the bytes and the pairs of such a layer are
counted over the window and not over the context: counted over the context,
a correct kernel would read above 100 % of its roofline. And a token meets,
of the experts its router chose, only those this chip HOLDS (`reduced.
num_experts`: `held` of a router `published` wide): the chosen experts that
live on other chips are no work of this one.
"""
from __future__ import annotations

import costs as base            # the benchmark's own; already imported

peaks = base.peaks
SLIDING, FULL = "sliding_attention", "full_attention"
# what the calls of `ragged_attention_bytes` added up to: `runners/serve.py`'s
# `drive` asks once a traced step, for the whole model's bytes alone; the
# per-kind readers (`window_attn_roofline`, `full_attn_roofline`) read the
# split here
traced = {"window_bytes": 0.0, "full_bytes": 0.0}


def layers(cfg: dict) -> dict:
    """{layer kind: how many of the run's layers are of it}."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    return {k: kinds.count(k) for k in (SLIDING, FULL)}


def held_share(cfg: dict) -> float:
    """The share of a router's assignments that fall on an expert held
    here, if its choice is even over the experts."""
    cut = (cfg.get("reduced") or {}).get("num_experts")
    return cut["held"][1] / cut["published"] if cut else 1.0


def seen(cfg: dict, kind: str, kv_len: int, q_len: int) -> int:
    """Keys the `q_len` last queries of a context `kv_len` long see between
    them in a layer of `kind`: every one, or those a window reaches back
    from the first query to the last."""
    if kind == SLIDING:
        return min(kv_len, cfg["sliding_window"] + q_len - 1)
    return kv_len


def layer_attention_bytes(cfg: dict, kind: str, kv_lens, q_lens,
                          dtype_bytes: int = 2) -> float:
    """Bytes one paged-attention call of a layer of `kind` has to move:
    K and V of the keys its queries see, once, each query row in and each
    output row out. What the kernel actually reads (whole pages, a page
    group's dead pages re-fetched) is its own affair and is never less."""
    kvh, nh, d = (cfg["num_key_value_heads"], cfg["num_attention_heads"],
                  cfg["head_dim"])
    live = [(k, q) for k, q in zip(kv_lens, q_lens) if q > 0]
    return float(sum(seen(cfg, kind, k, q) for k, q in live)
                 * 2 * kvh * d * dtype_bytes
                 + sum(q for _, q in live) * 2 * nh * d * dtype_bytes)


def ragged_attention_bytes(cfg: dict, kv_lens, q_lens,
                           dtype_bytes: int = 2) -> float:
    """The MEAN layer's bytes of one step's attention: `drive` multiplies by
    `num_hidden_layers`, and the product is the sliding layers' bytes over
    their windows plus the full layers' over their contexts."""
    n = layers(cfg)
    by_kind = {k: n[k] * layer_attention_bytes(cfg, k, kv_lens, q_lens,
                                               dtype_bytes) for k in n}
    traced["window_bytes"] += by_kind[SLIDING]
    traced["full_bytes"] += by_kind[FULL]
    return sum(by_kind.values()) / cfg["num_hidden_layers"]


def pair_flops(cfg: dict) -> float:
    """FLOPs of one (query token, key) pair in one layer: every head's
    score and its update, `head_dim` wide each."""
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]


def expert_bytes(cfg: dict, experts_touched: float, rows: float,
                 dtype_bytes: int = 2) -> float:
    """Bytes the held routed experts of ONE layer call have to move: the
    three matrices of every held expert touched, once, and each row routed
    to a held expert in and out (hidden wide) with its intermediate
    (written and read)."""
    h, im = cfg["hidden_size"], cfg["intermediate_size"]
    return float(experts_touched * 3 * h * im * dtype_bytes
                 + rows * (2 * h + 2 * im) * dtype_bytes)


def active_params(cfg: dict) -> float:
    """Parameters one token is multiplied by in the run's layers ON THIS
    CHIP: the attention projections, the router, the shared experts, and of
    its `num_experts_per_tok` routed experts the share that is held here."""
    h, nh, kvh, d = (cfg["hidden_size"], cfg["num_attention_heads"],
                     cfg["num_key_value_heads"], cfg["head_dim"])
    im = cfg["intermediate_size"]
    cut = (cfg.get("reduced") or {}).get("num_experts")
    router = h * (cut["published"] if cut else cfg["num_experts"])
    experts = cfg["num_shared_experts"] \
        + cfg["num_experts_per_tok"] * held_share(cfg)
    return cfg["num_hidden_layers"] * (
        2 * h * nh * d + 2 * h * kvh * d + router + experts * 3 * h * im)


def serve_flops(cfg: dict, tokens: float, sampled: float, pairs: float) -> float:
    """`costs.serve_flops` for this architecture. `pairs` are (query,
    context) pairs over whole contexts, which is what a FULL layer computes.
    A sliding layer computes at most `sliding_window` of a context; how the
    pairs split over contexts is not handed over, so its pairs are counted
    at the LEAST they can be for contexts up to the deployment's
    `context_tokens` (all of them in the longest contexts): `pairs x window
    / context_tokens`. The share reads low by that, never high."""
    n = layers(cfg)
    reach = min(1.0, cfg["sliding_window"]
                / cfg["deployment"]["context_tokens"])
    return (2.0 * (tokens * active_params(cfg)
                   + sampled * cfg["vocab_size"] * cfg["hidden_size"])
            + pair_flops(cfg) * pairs * (n[FULL] + n[SLIDING] * reach))
