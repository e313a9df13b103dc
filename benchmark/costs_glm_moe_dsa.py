"""The yardstick's arithmetic for a GLM-MoE-DSA configuration (GLM-5.2): the
operations and bytes its mechanisms need, computed from shapes. Kept with the
benchmark (see `costs.py`, whose `peaks` it shares).

What differs from a DeepSeek-V3 configuration's count: a query attends its
SELECTION, `min(index_topk, context)` positions, so attention's bytes and pairs
are counted over the selection and not over the context (counted over the
context, a correct kernel would read above 100 % of its roofline); what does
grow with the context is the indexer, in the `full` layers alone: every live
position's index key read once a lane, and `index_n_heads x index_head_dim`
multiply-adds a (query, position) pair. And a token meets, of the experts its
router chose, only those this chip HOLDS (`reduced.n_routed_experts`).
"""
from __future__ import annotations

import costs as base            # the benchmark's own; already imported

peaks = base.peaks
FULL = "full"
# what the calls of `ragged_attention_bytes` added up to: `runners/serve.py`'s
# `drive` asks once a traced step, for the whole model's bytes alone; the
# readers `sparse_attn_roofline` and `dsa_index_roofline` read the split here
traced = {"sparse_bytes": 0.0, "sparse_flops": 0.0, "index_bytes": 0.0}


def layer_kinds(cfg: dict):
    run = cfg.get("layers_run") or range(cfg["num_hidden_layers"])
    return tuple(cfg["indexer_types"][i] for i in run)


def full_layers(cfg: dict) -> int:
    return layer_kinds(cfg).count(FULL)


def held_share(cfg: dict) -> float:
    """The share of a router's assignments that fall on an expert held
    here, if its choice is even over the experts."""
    cut = (cfg.get("reduced") or {}).get("n_routed_experts")
    return cut["held"][1] / cut["published"] if cut else 1.0


def latent_row_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    """One token's cache row of one layer: `[c | k_rope]`."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * dtype_bytes


def selected(cfg: dict, kv_len: int, q_len: int) -> float:
    """Selected positions summed over the `q_len` last queries of a context
    `kv_len` long: query at position t picks `min(index_topk, t + 1)`."""
    k = cfg["index_topk"]
    return float(sum(min(k, t + 1) for t in range(kv_len - q_len, kv_len)))


def sparse_attn_bytes(cfg: dict, kv_lens, q_lens, dtype_bytes: int = 2) -> float:
    """Bytes ONE layer's sparse attention has to move: every query row's
    selected latent rows once (a row is key and value; each query has its own
    set), the query row in (`heads x (rank + rope)`) and the output row out
    (`heads x rank`). What the gather writes and the kernel reads again (the
    gathered copy), and rows padded to whole lane tiles, are the program's own
    affair."""
    nh, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    q_row = nh * (2 * rank + cfg["qk_rope_head_dim"]) * dtype_bytes
    live = [(k, q) for k, q in zip(kv_lens, q_lens) if q > 0]
    return float(sum(selected(cfg, k, q) for k, q in live)
                 * latent_row_bytes(cfg, dtype_bytes)
                 + sum(q for _, q in live) * q_row)


def pair_flops(cfg: dict) -> float:
    """FLOPs of one (query token, selected position) pair in one layer,
    absorbed form: every head's score over the row's whole width and its
    update over the value columns."""
    return 2.0 * cfg["num_attention_heads"] * (
        2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def sparse_attn_flops(cfg: dict, kv_lens, q_lens) -> float:
    """FLOPs of the same call: `pair_flops` a query and selected position."""
    return pair_flops(cfg) * sum(selected(cfg, k, q)
                                 for k, q in zip(kv_lens, q_lens) if q > 0)


def index_pair_flops(cfg: dict) -> float:
    """FLOPs of one (query token, causal position) pair in one `full` layer's
    indexer: every index head's dot product, `index_head_dim` wide."""
    return 2.0 * cfg["index_n_heads"] * cfg["index_head_dim"]


def index_score_bytes(cfg: dict, kv_lens, q_lens, dtype_bytes: int = 2) -> float:
    """Bytes ONE `full` layer's index-score call has to move: every live
    lane's index keys once, each query row in (`index_n_heads x
    index_head_dim`, and its head weights in float32) and its scores out
    (float32, a causal position each)."""
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    live = [(k, q) for k, q in zip(kv_lens, q_lens) if q > 0]
    pairs = sum(q * k - q * (q - 1) / 2.0 for k, q in live)
    return float(sum(k for k, _ in live) * di * dtype_bytes
                 + sum(q for _, q in live) * hi * (di * dtype_bytes + 4)
                 + pairs * 4)


def ragged_attention_bytes(cfg: dict, kv_lens, q_lens,
                           dtype_bytes: int = 2) -> float:
    """What `drive` asks a traced step: the MEAN layer's bytes of the step's
    context mechanisms (`drive` multiplies by `num_hidden_layers`): every
    layer's sparse attention and the `full` layers' index scores."""
    layers = cfg["num_hidden_layers"]
    sparse = layers * sparse_attn_bytes(cfg, kv_lens, q_lens, dtype_bytes)
    index = full_layers(cfg) * index_score_bytes(cfg, kv_lens, q_lens,
                                                 dtype_bytes)
    traced["sparse_bytes"] += sparse
    traced["sparse_flops"] += layers * sparse_attn_flops(cfg, kv_lens, q_lens)
    traced["index_bytes"] += index
    return (sparse + index) / layers


def expert_bytes(cfg: dict, experts_touched: float, rows: float,
                 dtype_bytes: int = 2) -> float:
    """Bytes the held routed experts of ONE layer call have to move: the
    three matrices of every held expert touched, once, and each row routed
    to a held expert in and out (hidden wide) with its intermediate
    (written and read)."""
    h, im = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return float(experts_touched * 3 * h * im * dtype_bytes
                 + rows * (2 * h + 2 * im) * dtype_bytes)


def active_params(cfg: dict) -> float:
    """Parameters one token is multiplied by in the run's layers ON THIS
    CHIP: the attention projections (the q-LoRA's two, `kv_a`, `kv_b`, `o`),
    a `full` layer's indexer, and a dense SwiGLU or the router, the shared
    expert and, of its `num_experts_per_tok` routed experts, the share that
    is held here."""
    h, nh, rank = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    rd, nope, vd = (cfg["qk_rope_head_dim"], cfg["qk_nope_head_dim"],
                    cfg["v_head_dim"])
    ql = cfg["q_lora_rank"]
    attn = (h * ql + ql * nh * (nope + rd) + h * (rank + rd)
            + rank * nh * (nope + vd) + nh * vd * h)
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    indexer = ql * hi * di + h * di + h * hi
    dense = cfg["first_k_dense_replace"]
    cut = (cfg.get("reduced") or {}).get("n_routed_experts")
    router = h * (cut["published"] if cut else cfg["n_routed_experts"])
    experts = cfg["n_shared_experts"] \
        + cfg["num_experts_per_tok"] * held_share(cfg)
    moe = router + experts * 3 * h * cfg["moe_intermediate_size"]
    return (cfg["num_hidden_layers"] * attn + full_layers(cfg) * indexer
            + dense * 3 * h * cfg["intermediate_size"]
            + (cfg["num_hidden_layers"] - dense) * moe)


def serve_flops(cfg: dict, tokens: float, sampled: float, pairs: float) -> float:
    """`costs.serve_flops` for this architecture. `pairs` are (query,
    context) pairs over whole contexts: that is what a `full` layer's INDEXER
    computes. Attention computes at most `index_topk` of a context; how the
    pairs split over contexts is not handed over, so attention's pairs are
    counted at the LEAST they can be for contexts up to the deployment's
    `context_tokens` (all of them in the longest contexts): `pairs x
    index_topk / context_tokens`. The share reads low by that, never high."""
    reach = min(1.0, cfg["index_topk"] / cfg["deployment"]["context_tokens"])
    return (2.0 * (tokens * active_params(cfg)
                   + sampled * cfg["vocab_size"] * cfg["hidden_size"])
            + pairs * (cfg["num_hidden_layers"] * pair_flops(cfg) * reach
                       + full_layers(cfg) * index_pair_flops(cfg)))
