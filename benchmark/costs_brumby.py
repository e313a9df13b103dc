"""The yardstick's arithmetic for a Brumby configuration (power retention):
the operations and bytes its mechanisms need, computed from shapes. Kept with
the benchmark (see `costs.py`, whose `peaks` it shares).

What differs from an attention model's count: nothing here grows with the
context. A token costs one update and one read of its sequence's state, `D x
(head_dim + 1)` numbers a KV head and layer with `D = head_dim (head_dim + 1)
/ 2` the distinct degree-2 products (8,256 at head_dim 128); a decode step
has to move every live lane's state in and out once, whatever the lane's
length. FLOPs are counted at the algorithm's `D`; bytes at the state AS IT
LIES on the device (`deployment.feature_dim_run`, 8,320: the 64 padded
features are 0.8 % and are real traffic a kernel cannot avoid).
"""
from __future__ import annotations

import costs as base            # the benchmark's own; already imported

peaks = base.peaks
# what the calls of `ragged_attention_bytes` added up to: `runners/serve.py`'s
# `drive` asks once a traced step; `retention_update_roofline` reads it here
traced = {"update_bytes": 0.0}


def features(cfg: dict) -> int:
    """`D`: the distinct products `x_i x_j`, `i <= j`."""
    d = cfg["head_dim"]
    return d * (d + 1) // 2


def state_bytes(cfg: dict, layers: int = 1) -> int:
    """Bytes of ONE sequence's state in `layers` layers as it lies on the
    device: `S` and `z` of every KV head, float32, the feature axis as run."""
    run = cfg["deployment"]["feature_dim_run"]
    return (layers * cfg["num_key_value_heads"] * run
            * (cfg["head_dim"] + 1) * 4)


def retention_update_bytes(cfg: dict, live_lanes: int) -> float:
    """Bytes the one-token update of a step has to move over ALL the run's
    layers: every live lane's state read once and written once. (Its q, k,
    v and y rows are a few KB a lane and are left out: the share reads
    low by that, never high.)"""
    return float(live_lanes * 2 * state_bytes(cfg, cfg["num_hidden_layers"]))


def retention_token_flops(cfg: dict) -> float:
    """FLOPs of one token in one layer's retention, recurrent form: the
    state's update (every KV head: `D x (head_dim + 1)` multiply-adds) and
    its read (every query head: the same)."""
    heads = cfg["num_key_value_heads"] + cfg["num_attention_heads"]
    return 2.0 * features(cfg) * (cfg["head_dim"] + 1) * heads


def retention_chunk_flops(cfg: dict, chunk_lens) -> float:
    """FLOPs the chunked form needs for ONE layer over lanes that hold
    `chunk_lens` tokens each: inside a chunk the attention form (a score and
    a weighted value, `head_dim` wide each, a query head and causal pair),
    the carried state's part of every query, and the state's own update."""
    nh, kvh, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    per_row = 2.0 * features(cfg) * (d + 1) * (nh + kvh)
    return float(sum(4.0 * nh * d * n * (n + 1) / 2 + per_row * n
                     for n in chunk_lens))


def retention_chunk_bytes(cfg: dict, chunk_lens, dtype_bytes: int = 2) -> float:
    """Bytes the chunked form has to move for ONE layer: each chunk lane's
    state in and out once, and its rows (q in, y out, k, v)."""
    nh, kvh, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    row = (2 * nh + 2 * kvh) * d * dtype_bytes
    return float(sum(2 * state_bytes(cfg) + n * row for n in chunk_lens))


def ragged_attention_bytes(cfg: dict, kv_lens, q_lens,
                           dtype_bytes: int = 2) -> float:
    """What `drive` asks a traced step: the MEAN layer's bytes of the step's
    context mechanism (`drive` multiplies by `num_hidden_layers`). Here it
    does not depend on `kv_lens`: a live lane's state, in and out."""
    live = sum(1 for q in q_lens if q > 0)
    moved = retention_update_bytes(cfg, live)
    traced["update_bytes"] += moved
    return moved / cfg["num_hidden_layers"]


def matmul_params(cfg: dict) -> int:
    """Parameters one row is multiplied by in the run's layers: q, k, v, o,
    the gate and the SwiGLU's three."""
    h, nh, kvh, d = (cfg["hidden_size"], cfg["num_attention_heads"],
                     cfg["num_key_value_heads"], cfg["head_dim"])
    layer = 2 * h * nh * d + 2 * h * kvh * d + h * kvh \
        + 3 * h * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * layer


def serve_flops(cfg: dict, tokens: float, sampled: float, pairs: float) -> float:
    """`costs.serve_flops` for this architecture: `tokens` rows through
    every layer's projections and retention, `sampled` rows through the
    untied head. `pairs` (query, context) are no work of this model: a token
    costs the same at any length (prefill inside a chunk does compute pairs;
    counted in the recurrent form it reads low, never high)."""
    del pairs
    return (2.0 * (tokens * matmul_params(cfg)
                   + sampled * cfg["vocab_size"] * cfg["hidden_size"])
            + tokens * cfg["num_hidden_layers"] * retention_token_flops(cfg))
