"""The yardstick's arithmetic: the chip's published peaks, and the operations
and bytes an algorithm needs, computed from shapes. Kept with the benchmark so
that no PR that claims a gain can change how its gain is counted.
"""
from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """Published peaks of the device; one that is not in the table is an
    error, never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"benchmark/peaks.json (has {sorted(table)})")
    return table[device_kind]


def matmul_params(cfg: dict) -> int:
    """Parameters that are multiplied: every layer's seven projections and
    the output head. The embedding is a gather (it counts only when tied,
    because it then is the head)."""
    h, inter = cfg["hidden_size"], cfg["intermediate_size"]
    d = h // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * d
    layer = 2 * h * h + 2 * h * kv + 3 * h * inter + 2 * h   # + two norms
    return (cfg["num_hidden_layers"] * layer + h
            + cfg["vocab_size"] * h)


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Model FLOPs per trained token, forward and backward, PaLM-appendix
    accounting: 6 N + 12 L H T (the repo's own `flops_per_token`,
    `models/llama.py`, copied). Recomputed operations do not count."""
    return (6.0 * matmul_params(cfg)
            + 12.0 * cfg["num_hidden_layers"] * cfg["hidden_size"] * seq)


def ragged_attention_bytes(cfg: dict, kv_lens, q_lens,
                           dtype_bytes: int = 2) -> float:
    """Bytes one ragged paged-attention call has to move for ONE layer: K
    and V of every live lane's context once, each query row in and each
    output row out. What the kernel actually reads (every page up to the
    table's width, once per packed token) is its own affair."""
    d = cfg["hidden_size"] // cfg["num_attention_heads"]
    kv_row = 2 * cfg["num_key_value_heads"] * d * dtype_bytes
    q_row = 2 * cfg["num_attention_heads"] * d * dtype_bytes   # q in, o out
    live = [(k, q) for k, q in zip(kv_lens, q_lens) if q > 0]
    return float(sum(k for k, _ in live) * kv_row
                 + sum(q for _, q in live) * q_row)


def serve_flops(cfg: dict, tokens: float, sampled: float, pairs: float) -> float:
    """Model FLOPs a serving window needs for what it processed: `tokens`
    rows (prompt or decoded) through every layer's projections, `sampled`
    rows through the head (a prompt's other rows need no logits, whatever
    the program computes for them), 2 FLOPs a multiplied parameter; and
    attention's score and update, 4 x heads x head size a layer for each of
    the `pairs` (query token, token of its own causal context)."""
    h = cfg["hidden_size"]
    head = cfg["vocab_size"] * h
    return (2.0 * (tokens * (matmul_params(cfg) - head) + sampled * head)
            + 4.0 * cfg["num_hidden_layers"] * h * pairs)
