"""The yardstick's arithmetic for a DeepSeek-V3-architecture configuration:
the operations and bytes its two new mechanisms need, computed from shapes.
Kept with the benchmark (see `costs.py`, whose `peaks` it shares).
"""
from __future__ import annotations

import costs as base            # the benchmark's own; already imported

peaks = base.peaks
# FLOPs of the calls whose bytes `ragged_attention_bytes` was asked for:
# `runners/serve.py`'s `drive` asks once a traced step, for its bytes alone
traced = {"flops": 0.0}


def latent_row_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    """One token's cache row of one layer: `[c | k_rope]`."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * dtype_bytes


def ragged_attention_bytes(cfg: dict, kv_lens, q_lens,
                           dtype_bytes: int = 2) -> float:
    """Bytes one absorbed-MLA paged-attention call has to move for ONE
    layer: every live lane's latent rows once (a row is key and value), each
    query row in (`heads x (rank + rope)`) and each output row out (`heads x
    rank`). What the kernel actually reads (rows padded to whole lane tiles)
    is its own affair."""
    nh, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    q_row = nh * (2 * rank + cfg["qk_rope_head_dim"]) * dtype_bytes
    live = [(k, q) for k, q in zip(kv_lens, q_lens) if q > 0]
    traced["flops"] += ragged_attention_flops(cfg, kv_lens, q_lens)
    return float(sum(k for k, _ in live) * latent_row_bytes(cfg, dtype_bytes)
                 + sum(q for _, q in live) * q_row)


def pair_flops(cfg: dict) -> float:
    """FLOPs of one (query token, context token) pair in one layer, absorbed
    form: every head's score over the row's whole width and its update over
    the value columns."""
    return 2.0 * cfg["num_attention_heads"] * (
        2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def ragged_attention_flops(cfg: dict, kv_lens, q_lens) -> float:
    """FLOPs of the same call: `pair_flops` per query token and context
    token. A chunk's later tokens see more context than its first; the
    count takes each query token's own causal context."""
    pairs = sum(q * k - q * (q - 1) / 2.0
                for k, q in zip(kv_lens, q_lens) if q > 0)
    return pair_flops(cfg) * pairs


def expert_bytes(cfg: dict, experts_touched: float, rows: float,
                 dtype_bytes: int = 2) -> float:
    """Bytes the routed experts of ONE layer call have to move: the three
    matrices of every expert touched, once, and each routed row in and out
    (hidden wide) with its intermediate (written and read)."""
    h, im = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return float(experts_touched * 3 * h * im * dtype_bytes
                 + rows * (2 * h + 2 * im) * dtype_bytes)


def active_params(cfg: dict) -> int:
    """Parameters one token is multiplied by in the layers: the attention
    projections, and a dense SwiGLU or the router, the shared experts and
    the `num_experts_per_tok` routed experts it is sent to."""
    h, nh, rank = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    rd, nope, vd = (cfg["qk_rope_head_dim"], cfg["qk_nope_head_dim"],
                    cfg["v_head_dim"])
    attn = (h * nh * (nope + rd) + h * (rank + rd) + rank * nh * (nope + vd)
            + nh * vd * h)
    dense = cfg["first_k_dense_replace"]
    experts = cfg["num_experts_per_tok"] + cfg["n_shared_experts"]
    moe = h * cfg["n_routed_experts"] + experts * 3 * h * cfg["moe_intermediate_size"]
    return (cfg["num_hidden_layers"] * attn + dense * 3 * h * cfg["intermediate_size"]
            + (cfg["num_hidden_layers"] - dense) * moe)


def serve_flops(cfg: dict, tokens: float, sampled: float, pairs: float) -> float:
    """`costs.serve_flops` for this architecture: a token meets only the
    experts it is routed to, and a (query, context) pair costs what
    `ragged_attention_flops` counts for it (the absorbed form)."""
    return (2.0 * (tokens * active_params(cfg)
                   + sampled * cfg["vocab_size"] * cfg["hidden_size"])
            + cfg["num_hidden_layers"] * pair_flops(cfg) * pairs)
