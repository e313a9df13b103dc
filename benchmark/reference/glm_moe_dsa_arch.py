"""Plain reference of the GLM-MoE-DSA architecture (`model_type:
glm_moe_dsa`; here GLM-5.2): float32 `jax.numpy`, matmuls at the `highest`
precision, no kernels, no cache, no batching, no grouped matmul. It imports
nothing of the program under test and takes nothing the program has made:
its weights come from `benchmark/weights.py` and the seed, and its routing and
its selection are its own.

Per token row x at position t, RMSNorm before each sub-block and the residual
after (the `config` keys name every shape; the indexer is the published
DeepSeek-V3.2-Exp one, `inference/model.py` `Indexer`):

- MLA with a q-LoRA, in the EXPANDED form: `c_q = RMSNorm(x W_qa)`, `q = c_q
  W_qb -> [heads, nope + rope]`; `a = x W_kva`, `c = RMSNorm(a[:rank])`,
  `k_rope = a[rank:]` shared by all heads; RoPE on `q_rope`, `k_rope` (pairs
  (x0, x1), (x2, x3), ... de-interleaved, then rotate-half); `[k_nope | v] =
  c W_kvb` by head (`v_head_dim` need not be `qk_nope_head_dim`); softmax of
  `q k^T (nope + rope)^-0.5` OVER THE SELECTED POSITIONS `S_t` ONLY, times
  `v`; `W_o`.
- A `full` layer's indexer: `q_I = c_q W_Iq -> [index_n_heads,
  index_head_dim]`; `k_I = LayerNorm(x W_Ik)` (weight and bias, eps 1e-6);
  RoPE on the FIRST `qk_rope_head_dim` numbers of each `q_I` head and of
  `k_I`; `w = x W_Iw`; `I[t, s] = heads^-0.5 dim^-0.5 sum_h w[t, h]
  ReLU(q_I[t, h] . k_I[s])` for `s <= t`; `S_t` = the `min(index_topk, t +
  1)` positions of largest `I[t, .]`, ties to the lower position.
- A `shared` layer has no indexer and uses `S_t` of the nearest `full` layer
  before it (`index_topk_freq`).
- Expert layers (all but the first `first_k_dense_replace`): `s = sigmoid(x
  W_g)`; top `num_experts_per_tok` of `s + bias`; weights `s[chosen] / (sum
  + 1e-20) * routed_scaling_factor`; the chosen experts' SwiGLUs combined by
  weight, plus one shared SwiGLU on every token. Given a SHARE (`reduced.
  n_routed_experts`: `held` of a router `published` wide) only the held
  experts' part is computed; an assignment to an absent expert adds nothing.
- Final RMSNorm, untied output head.

Departures (the configuration's `assumed` repeats them): the published
inference code's Hadamard rotation of `q_I`, `k_I` (orthogonal: every `q_I .
k_I` is unchanged) and its FP8 index cache are left out; the MTP module is
not part of the main forward.

Layout, memory and time, none about mathematics: linear weights `[in, out]`,
the held experts stacked `[held, in, out]`; attention a block of queries and a
group of heads at a time; a row's selection is kept as a BIT MASK over the
positions (`[S, S / 32]` uint32: 380 MB at 55 k positions where indices would
take as much and a boolean matrix 3 GB), made from the row's k-th largest
score with the tie rule above, and read again by every head group and by the
`shared` layers after; the query blocks go in `CAUSAL_PARTS` runs, each over
the keys up to its own last position (a later key is in no selection: nine
sixteenths of the products of every query with every key); each held expert is applied only to the rows routed to
it (this file's own argsort, blocks of `ROW_BLOCK`).

`quant="int8"` is the control of `benchmark/README.md`: every projection's
operands on a symmetric int8 grid. It exists to be refused.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256      # queries attended at a time
INDEX_BLOCK = 64       # queries scored by the indexer at a time (x 32 heads)
HEAD_GROUP = 8         # heads attended at a time
CAUSAL_PARTS = 8       # runs of query blocks, each over the keys up to its end
ROW_BLOCK = 128        # sorted rows multiplied by one expert at a time
MOE_ROWS = 1024        # rows of a sequence routed at a time
FULL, SHARED = "full", "shared"
K_NORM_EPS = 1e-6

ATTENTION = ("self_attn.q_a_proj.weight", "self_attn.q_a_layernorm.weight",
             "self_attn.q_b_proj.weight",
             "self_attn.kv_a_proj_with_mqa.weight",
             "self_attn.kv_a_layernorm.weight", "self_attn.kv_b_proj.weight",
             "self_attn.o_proj.weight")
INDEXER = ("self_attn.indexer.wq_b.weight", "self_attn.indexer.wk.weight",
           "self_attn.indexer.k_norm.weight", "self_attn.indexer.k_norm.bias",
           "self_attn.indexer.weights_proj.weight")


def share(cfg: dict):
    """`(router width, first held expert, held experts)`: the published
    count and the chip's share where `reduced` cuts `n_routed_experts`,
    else all of them."""
    cut = (cfg.get("reduced") or {}).get("n_routed_experts")
    if cut:
        return int(cut["published"]), int(cut["held"][0]), int(cut["held"][1])
    return cfg["n_routed_experts"], 0, cfg["n_routed_experts"]


def layer_kinds(cfg: dict):
    """The run's layers' indexer types: of the published layers
    `layers_run` where the configuration cuts the depth, else the first
    `num_hidden_layers`."""
    types = cfg["indexer_types"]
    run = cfg.get("layers_run") or range(cfg["num_hidden_layers"])
    return tuple(types[i] for i in run)


def rope_theta(cfg: dict) -> float:
    return float((cfg.get("rope_parameters") or {}).get(
        "rope_theta", cfg.get("rope_theta", 10000.0)))


def layer_shapes(cfg: dict, i: int) -> dict:
    h, nh, ql = cfg["hidden_size"], cfg["num_attention_heads"], cfg["q_lora_rank"]
    rank, rd = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, vd = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    out = {
        "input_layernorm.weight": ((h,), "norm"),
        "self_attn.q_a_proj.weight": ((h, ql), "matrix"),
        "self_attn.q_a_layernorm.weight": ((ql,), "norm"),
        "self_attn.q_b_proj.weight": ((ql, nh * (nope + rd)), "matrix"),
        "self_attn.kv_a_proj_with_mqa.weight": ((h, rank + rd), "matrix"),
        "self_attn.kv_a_layernorm.weight": ((rank,), "norm"),
        "self_attn.kv_b_proj.weight": ((rank, nh * (nope + vd)), "matrix"),
        "self_attn.o_proj.weight": ((nh * vd, h), "matrix"),
        "post_attention_layernorm.weight": ((h,), "norm"),
    }
    if layer_kinds(cfg)[i] == FULL:
        hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
        out.update({
            "self_attn.indexer.wq_b.weight": ((ql, hi * di), "matrix"),
            "self_attn.indexer.wk.weight": ((h, di), "matrix"),
            "self_attn.indexer.k_norm.weight": ((di,), "norm"),
            "self_attn.indexer.k_norm.bias": ((di,), "matrix"),
            "self_attn.indexer.weights_proj.weight": ((h, hi), "matrix"),
        })
    if i < cfg["first_k_dense_replace"]:
        inter = cfg["intermediate_size"]
        out.update({"mlp.gate_proj.weight": ((h, inter), "matrix"),
                    "mlp.up_proj.weight": ((h, inter), "matrix"),
                    "mlp.down_proj.weight": ((inter, h), "matrix")})
        return out
    width, _, held = share(cfg)
    im = cfg["moe_intermediate_size"]
    sh = cfg["n_shared_experts"] * im
    out.update({
        "mlp.gate.weight": ((h, width), "matrix"),
        "mlp.gate.e_score_correction_bias": ((width,), "matrix"),
        "mlp.experts.gate_proj.weight": ((held, h, im), "matrix"),
        "mlp.experts.up_proj.weight": ((held, h, im), "matrix"),
        "mlp.experts.down_proj.weight": ((held, im, h), "matrix"),
        "mlp.shared_experts.gate_proj.weight": ((h, sh), "matrix"),
        "mlp.shared_experts.up_proj.weight": ((h, sh), "matrix"),
        "mlp.shared_experts.down_proj.weight": ((sh, h), "matrix"),
    })
    return out


def param_shapes(cfg: dict) -> dict:
    """name -> (shape, kind) for every weight; the HuggingFace names."""
    h = cfg["hidden_size"]
    out = {"model.embed_tokens.weight": ((cfg["vocab_size"], h), "matrix")}
    for i in range(cfg["num_hidden_layers"]):
        for k, v in layer_shapes(cfg, i).items():
            out[f"model.layers.{i}.{k}"] = v
    out["model.norm.weight"] = ((h,), "norm")
    out["lm_head.weight"] = ((h, cfg["vocab_size"]), "matrix")
    return out


def _block(n, want):
    """The largest divisor of n that is no more than `want`."""
    return next(b for b in range(min(n, want), 0, -1) if n % b == 0)


def _int8_grid(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def matmul(x, w, quant=None):
    """x [S, K] @ w [K, N], float32 `highest`."""
    x, w = x.astype(F32), w.astype(F32)
    if quant == "int8":
        x, w = _int8_grid(x, -1), _int8_grid(w, 0)
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.matmul(x, w, precision=HI)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w.astype(F32) + b.astype(F32)


def rope_tables(cfg, seq):
    d = cfg["qk_rope_head_dim"]
    inv = 1.0 / rope_theta(cfg) ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.outer(np.arange(seq, dtype=np.float64), inv)
    return jnp.asarray(np.cos(ang), F32), jnp.asarray(np.sin(ang), F32)


def _rotate(x, cos, sin, interleave):
    """x [S, heads, D] at positions 0..S-1."""
    if interleave:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


# --- the selection -----------------------------------------------------------------

def _pack(mask):
    """bool [R, S] (S a multiple of 32) -> uint32 [R, S / 32]."""
    r, s = mask.shape
    bits = mask.reshape(r, s // 32, 32).astype(jnp.uint32)
    return jnp.sum(bits << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                   dtype=jnp.uint32)


def _unpack(packed):
    """uint32 [R, S / 32] -> bool [R, S]."""
    r = packed.shape[0]
    bits = (packed[:, :, None] >> jnp.arange(32, dtype=jnp.uint32)) & 1
    return bits.reshape(r, -1).astype(bool)


def pick(scores, qpos, k):
    """bool [R, S]: of row r's causal positions (`<= qpos[r]`), the `min(k,
    qpos[r] + 1)` of largest score, ties to the lower position. From the
    row's k-th largest causal score: everything above it, and of its equals
    the first that still fit."""
    s = scores.shape[1]
    causal = jnp.arange(s)[None, :] <= qpos[:, None]
    masked = jnp.where(causal, scores, -jnp.inf)
    kth = jax.lax.top_k(masked, min(k, s))[0][:, -1:]
    above = causal & (masked > kth)
    equal = causal & (masked == kth)
    room = min(k, s) - jnp.sum(above, axis=-1, keepdims=True)
    return above | (equal & (jnp.cumsum(equal, axis=-1) <= room))


def index_selection(x, c_q, p, cfg, cos, sin, quant=None):
    """A `full` layer's indexer on one sequence: normed rows x [S, H] and
    their c_q [S, q_lora_rank] -> the rows' selections, packed [S, S / 32]."""
    s = x.shape[0]
    hi, di, rd = cfg["index_n_heads"], cfg["index_head_dim"], \
        cfg["qk_rope_head_dim"]
    il = cfg.get("indexer_rope_interleave", True)
    q = matmul(c_q, p["self_attn.indexer.wq_b.weight"], quant).reshape(s, hi, di)
    k = layer_norm(matmul(x, p["self_attn.indexer.wk.weight"], quant),
                   p["self_attn.indexer.k_norm.weight"],
                   p["self_attn.indexer.k_norm.bias"], K_NORM_EPS)
    q = jnp.concatenate([_rotate(q[..., :rd], cos, sin, il), q[..., rd:]], -1)
    k = jnp.concatenate([_rotate(k[:, None, :rd], cos, sin, il)[:, 0],
                         k[:, rd:]], -1)
    w = matmul(x, p["self_attn.indexer.weights_proj.weight"], quant) \
        * (hi ** -0.5 * di ** -0.5)
    blk = _block(s, INDEX_BLOCK)

    def block(args):
        qb, wb, qpos = args
        dots = jnp.einsum("qhd,sd->qhs", qb, k, precision=HI)
        scores = jnp.sum(jax.nn.relu(dots) * wb[:, :, None], axis=1)
        return _pack(pick(scores, qpos, cfg["index_topk"]))

    return jax.lax.map(block, (q.reshape(s // blk, blk, hi, di),
                               w.reshape(s // blk, blk, hi),
                               jnp.arange(s).reshape(s // blk, blk))
                       ).reshape(s, s // 32)


# --- the blocks ------------------------------------------------------------------------

def attention(x, p, cfg, kind, cos, sin, carried, quant=None):
    """The attention sub-block on one sequence x [S, H] (already normed):
    `(output [S, H], the selection it attended over)`. `carried`: the
    selection of the `full` layer before (packed), which a `shared` layer
    uses and a `full` layer replaces with its own."""
    s = x.shape[0]
    nh, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rd, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                    cfg["v_head_dim"])
    il = cfg.get("rope_interleave", True)
    c_q = rms_norm(matmul(x, p["self_attn.q_a_proj.weight"], quant),
                   p["self_attn.q_a_layernorm.weight"], cfg["rms_norm_eps"])
    if kind == FULL:
        carried = index_selection(x, c_q, p, cfg, cos, sin, quant)
    a = matmul(x, p["self_attn.kv_a_proj_with_mqa.weight"], quant)
    c = rms_norm(a[:, :rank], p["self_attn.kv_a_layernorm.weight"],
                 cfg["rms_norm_eps"])
    k_rope = _rotate(a[:, None, rank:], cos, sin, il)            # [S, 1, rd]
    blk, hg = _block(s, QUERY_BLOCK), _block(nh, HEAD_GROUP)
    # the query blocks in `parts` runs, each over the keys up to its own end
    # (a selection's bit words are whole only where a block is whole 32s)
    parts = _block(s // blk, CAUSAL_PARTS) if blk % 32 == 0 else 1
    per = s // blk // parts

    def heads(w):
        """The head group's columns of q_b_proj and of kv_b_proj."""
        w_q, w_kvb = w
        q = matmul(c_q, w_q, quant).reshape(s, hg, nope + rd)
        q = jnp.concatenate(
            [q[..., :nope], _rotate(q[..., nope:], cos, sin, il)], axis=-1)
        kv = matmul(c, w_kvb, quant).reshape(s, hg, nope + vd)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, (s, hg, rd))], axis=-1)
        v = kv[..., nope:]

        def part(first, last):
            """Query blocks [first, last) over the keys up to the last of
            their own positions: no later one is in a selection."""
            upto = last * blk
            kp, vp = k[:upto], v[:upto]

            def block(args):
                qb, chosen = args                                # [blk, hg, D]
                sc = jnp.einsum("qhd,khd->hqk", qb, kp, precision=HI) \
                    * (nope + rd) ** -0.5
                sc = jnp.where(_unpack(chosen)[None], sc, -jnp.inf)
                return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1),
                                  vp, precision=HI)

            return jax.lax.map(block, (qs[first:last],
                                       sel[first:last, :, :upto // 32]))

        qs = q.reshape(s // blk, blk, hg, nope + rd)
        sel = carried.reshape(s // blk, blk, s // 32)
        return jnp.concatenate([part(j * per, (j + 1) * per)
                                for j in range(parts)]).reshape(s, hg * vd)

    by_group = lambda w, d: jnp.moveaxis(                        # noqa: E731
        w.reshape(w.shape[0], nh // hg, hg * d), 1, 0)
    # the output projection a head group at a time, summed: all heads'
    # outputs side by side are [S, heads x v] (3.6 GB at 55 k positions)
    w_o = p["self_attn.o_proj.weight"].reshape(nh // hg, hg * vd, -1)

    def group(out, w):
        return out + matmul(heads(w[:2]), w[2], quant), None

    out, _ = jax.lax.scan(group, jnp.zeros((s, w_o.shape[-1]), F32), (
        by_group(p["self_attn.q_b_proj.weight"], nope + rd),
        by_group(p["self_attn.kv_b_proj.weight"], nope + vd), w_o))
    return out, carried


def swiglu(x, gate_w, up_w, down_w, quant=None):
    return matmul(jax.nn.silu(matmul(x, gate_w, quant))
                  * matmul(x, up_w, quant), down_w, quant)


def swiglu_rows(x, gate_w, up_w, down_w, quant=None):
    """`swiglu` on x [S, H], `MOE_ROWS` rows at a time (the dense layer's
    intermediate is 2.7 GB at 55 k positions)."""
    s, h = x.shape
    rows = _block(s, MOE_ROWS)
    return jax.lax.map(lambda xb: swiglu(xb, gate_w, up_w, down_w, quant),
                       x.reshape(s // rows, rows, h)).reshape(s, h)


def route(x, p, cfg):
    """x [S, H] -> (experts [S, k] of the router's whole width, weights)."""
    s = jax.nn.sigmoid(matmul(x, p["mlp.gate.weight"]))
    _, experts = jax.lax.top_k(
        s + p["mlp.gate.e_score_correction_bias"].astype(F32),
        cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, experts, axis=-1)
    if cfg.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return experts, w * cfg["routed_scaling_factor"]


def routed_moe(x, p, cfg, quant=None):
    """The held routed experts on x [S, H], `MOE_ROWS` rows at a time."""
    s, h = x.shape
    rows = _block(s, MOE_ROWS)
    return jax.lax.map(lambda xb: _routed_rows(xb, p, cfg, quant),
                       x.reshape(s // rows, rows, h)).reshape(s, h)


def _routed_rows(x, p, cfg, quant=None):
    """Rows x [S, H]: each held expert applied only to the rows routed to
    it; an assignment to an expert that is not held adds nothing."""
    s, h = x.shape
    k = cfg["num_experts_per_tok"]
    _, lo, e = share(cfg)
    experts, weights = route(x, p, cfg)
    flat = experts.reshape(s * k)
    held = (flat >= lo) & (flat < lo + e)
    flat = jnp.where(held, flat - lo, e)             # the absent sort last
    order = jnp.argsort(flat)                        # sorted row -> flat row
    count = jnp.sum(flat[:, None] == jnp.arange(e)[None, :], axis=0)
    start = jnp.cumsum(count) - count                # first sorted row
    nblk = -(-count // ROW_BLOCK)                    # blocks of each expert
    first = jnp.cumsum(nblk) - nblk                  # its first block
    blocks = -(-s * k // ROW_BLOCK) + e              # no more than these
    xs = jnp.concatenate([jnp.take(x, order // k, axis=0),
                          jnp.zeros((ROW_BLOCK, h), F32)])

    def block(j):
        ex = jnp.clip(jnp.searchsorted(first + nblk, j, side="right"),
                      0, e - 1)
        row0 = start[ex] + (j - first[ex]) * ROW_BLOCK
        rows = row0 + jnp.arange(ROW_BLOCK)
        mine = (j < first[ex] + nblk[ex]) & (rows < start[ex] + count[ex])
        xb = jax.lax.dynamic_slice_in_dim(
            xs, jnp.minimum(row0, s * k), ROW_BLOCK, axis=0)
        yb = swiglu(xb, p["mlp.experts.gate_proj.weight"][ex],
                    p["mlp.experts.up_proj.weight"][ex],
                    p["mlp.experts.down_proj.weight"][ex], quant)
        return jnp.where(mine, rows, s * k), yb

    rows, ys = jax.lax.map(block, jnp.arange(blocks))
    y = jnp.zeros((s * k, h), F32).at[rows.reshape(-1)].set(
        ys.reshape(-1, h), mode="drop")              # sorted order
    back = jnp.zeros((s * k,), jnp.int32).at[order].set(
        jnp.arange(s * k, dtype=jnp.int32))
    y = jnp.take(y, back, axis=0).reshape(s, k, h)
    return jnp.sum(y * weights[..., None], axis=1)


def every_expert_moe(x, p, cfg, quant=None):
    """The held experts' part as the equations read: every held expert on
    every row, times its weight (zero where not chosen). Small sizes only."""
    _, lo, e = share(cfg)
    experts, weights = route(x, p, cfg)
    out = jnp.zeros_like(x)
    for ex in range(e):
        w = jnp.sum(jnp.where(experts == lo + ex, weights, 0.0), axis=-1)
        out = out + w[:, None] * swiglu(
            x, p["mlp.experts.gate_proj.weight"][ex],
            p["mlp.experts.up_proj.weight"][ex],
            p["mlp.experts.down_proj.weight"][ex], quant)
    return out


def shared_expert(x, p, quant=None):
    return swiglu_rows(x, p["mlp.shared_experts.gate_proj.weight"],
                  p["mlp.shared_experts.up_proj.weight"],
                  p["mlp.shared_experts.down_proj.weight"], quant)


def bf16_flips(a, p, cfg):
    """The share of rows of `a` [S, H] whose set of chosen experts changes
    when `a` is rounded to bfloat16 before the router."""
    exact, _ = route(a, p, cfg)
    rounded, _ = route(a.astype(jnp.bfloat16).astype(F32), p, cfg)
    return jnp.mean(jnp.any(jnp.sort(exact, -1) != jnp.sort(rounded, -1), -1))


def layer(x, p, cfg, kind, cos, sin, carried, quant=None, moe=routed_moe,
          flips=False):
    """One decoder layer on one sequence x [S, H]; `p` names the layer's
    weights without the `model.layers.<i>.` prefix. Returns `(x, the
    selection it attended over)`, and with `flips` `bf16_flips` of the
    expert layer's input (0 for a dense layer) as a third."""
    eps = cfg["rms_norm_eps"]
    o, carried = attention(rms_norm(x, p["input_layernorm.weight"], eps), p,
                           cfg, kind, cos, sin, carried, quant)
    x = x + o
    a = rms_norm(x, p["post_attention_layernorm.weight"], eps)
    if "mlp.gate.weight" not in p:
        out = x + swiglu_rows(a, p["mlp.gate_proj.weight"],
                              p["mlp.up_proj.weight"],
                              p["mlp.down_proj.weight"], quant)
        flipped = jnp.zeros((), F32)
    else:
        out = x + moe(a, p, cfg, quant) + shared_expert(a, p, quant)
        flipped = bf16_flips(a, p, cfg) if flips else None
    return (out, carried, flipped) if flips else (out, carried)


def layer_params(params, i):
    pre = f"model.layers.{i}."
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def head_logits(x, norm_w, head_w, cfg, quant=None):
    return matmul(rms_norm(x, norm_w, cfg["rms_norm_eps"]), head_w, quant)


def forward(params, ids, cfg, quant=None, moe=routed_moe, reselect=None):
    """ids [S] (S a multiple of 32) -> logits [S, V]: the full forward, no
    cache. `reselect(i, carried) -> selection`, for tests alone: what a
    `shared` layer attends over in place of the carried selection."""
    s = ids.shape[0]
    cos, sin = rope_tables(cfg, s)
    x = jnp.take(params["model.embed_tokens.weight"], ids, axis=0).astype(F32)
    carried = None
    for i, kind in enumerate(layer_kinds(cfg)):
        if kind == SHARED and reselect is not None:
            carried = reselect(i, carried)
        x, carried = layer(x, layer_params(params, i), cfg, kind, cos, sin,
                           carried, quant, moe)
    return head_logits(x, params["model.norm.weight"],
                       params["lm_head.weight"], cfg, quant)
