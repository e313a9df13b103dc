"""Plain reference of the Cohere2-MoE architecture (`model_type:
cohere2_moe`; here Command A+, `command-a-plus-05-2026`): float32
`jax.numpy`, matmuls at the `highest` precision, no kernels, no cache, no
batching, no grouped matmul. It imports nothing of the program under test
and takes nothing the program has made: its weights come from
`benchmark/weights.py` and the seed, and its routing is its own.

The mathematics, per token row x of layer l (the published `config.json`
keys; where the config leaves a reading open, the configuration's file lists
it under `assumed`):

- `h = LayerNorm(x) = (x - mean) / sqrt(var + layer_norm_eps) * w_l`: mean-
  centred, weight only. ONE norm a layer feeds attention AND the
  feed-forward (`use_parallel_block`).
- `q = h Wq` (heads x head_dim), `k = h Wk`, `v = h Wv` (kv heads x
  head_dim; query head i reads kv head `i // (heads / kv heads)`), no bias,
  no QK norm. A `sliding_attention` layer turns q and k by RoPE over the
  interleaved pairs `(2i, 2i+1)` (`rope_gptj`), theta `rope_theta`, all
  dimensions (`rotary_pct` 1), and query i sees key j iff `i -
  sliding_window < j <= i`. A `full_attention` layer has NO position
  embedding and sees `j <= i`. `a = softmax(q k^T / sqrt(head_dim)) v`,
  `attn = a Wo`.
- On the same h: `s = sigmoid(h Wr)` (`num_experts` wide); the
  `num_experts_per_tok` largest; `w_e = s_e / sum of the chosen`
  (`norm_topk_prob`); `routed = sum_chosen w_e E_e(h)`, `E(h) = (silu(h Wg)
  * (h Wu)) Wd`; `shared = (1 / num_shared_experts) sum_j S_j(h)`, each S_j a
  SwiGLU of width `intermediate_size` (`average`); `ffn = routed + shared`.
- `x <- x + attn + ffn`. After the last layer `logits = logit_scale *
  LayerNorm(x) E^T` with the tied embedding E.

THE SHARE (`share(cfg)`): a configuration cut to one chip of a deployment
holds experts `first .. first + count - 1` of every layer. The router keeps
its published width and its top-k; `routed` sums over the chosen experts
that are HELD, with the weights the full choice gave them; what the absent
experts would have added is left out, and that partial result goes on to
the next layer, here exactly as in the program. With every expert held it
is the whole layer.

Departures, all about layout, memory and time, none about mathematics:
linear weights are `[in, out]`; the held routed experts are stacked on a
leading axis (`mlp.experts.gate_proj.weight [held, in, out]`) and the
shared experts' matrices lie side by side (`mlp.shared_experts.
gate_proj.weight [H, shared * I]`; expert j is columns `j * I .. (j + 1) *
I`), the layout the program shares and states; attention runs one kv head's
query heads and a block of queries at a time, and a sliding layer's block
looks only at the stretch of keys its window can reach (`dense_attention`
is the form that looks at every key, for `benchmark/tests/`); each held
expert is applied only to the rows routed to it (rows sorted by expert with
this file's own argsort, walked in blocks of `ROW_BLOCK`; `every_expert_moe`
is the form without that); bf16-valued weights are upcast where they are
used, so a 40,960-token sequence fits beside the program's weights.

`quant="int8"` is the control of `benchmark/README.md`: every projection's
operands on a symmetric int8 grid (weights per output channel, activations
per row). It exists to be refused.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 128      # queries attended at a time
ROW_BLOCK = 128        # sorted rows multiplied by one expert at a time
MOE_ROWS = 1024        # rows of a sequence through the feed-forward at a time
SLIDING, FULL = "sliding_attention", "full_attention"


def share(cfg: dict):
    """`(router width, first held expert, held experts)`: the published
    count and the chip's share where `reduced` cuts `num_experts`, else all
    of them."""
    cut = (cfg.get("reduced") or {}).get("num_experts")
    if cut:
        return int(cut["published"]), int(cut["held"][0]), int(cut["held"][1])
    return cfg["num_experts"], 0, cfg["num_experts"]


def layer_kinds(cfg: dict):
    return tuple(cfg["layer_types"][:cfg["num_hidden_layers"]])


def layer_shapes(cfg: dict) -> dict:
    h, nh, kvh, d = (cfg["hidden_size"], cfg["num_attention_heads"],
                     cfg["num_key_value_heads"], cfg["head_dim"])
    im = cfg["intermediate_size"]
    width, _, held = share(cfg)
    sh = cfg["num_shared_experts"] * im
    return {
        "input_layernorm.weight": ((h,), "norm"),
        "self_attn.q_proj.weight": ((h, nh * d), "matrix"),
        "self_attn.k_proj.weight": ((h, kvh * d), "matrix"),
        "self_attn.v_proj.weight": ((h, kvh * d), "matrix"),
        "self_attn.o_proj.weight": ((nh * d, h), "matrix"),
        "mlp.gate.weight": ((h, width), "matrix"),
        "mlp.experts.gate_proj.weight": ((held, h, im), "matrix"),
        "mlp.experts.up_proj.weight": ((held, h, im), "matrix"),
        "mlp.experts.down_proj.weight": ((held, im, h), "matrix"),
        "mlp.shared_experts.gate_proj.weight": ((h, sh), "matrix"),
        "mlp.shared_experts.up_proj.weight": ((h, sh), "matrix"),
        "mlp.shared_experts.down_proj.weight": ((sh, h), "matrix"),
    }


ATTENTION = tuple(f"self_attn.{n}_proj.weight" for n in "qkvo")
ROUTED = ("mlp.gate.weight",) + tuple(
    f"mlp.experts.{n}_proj.weight" for n in ("gate", "up", "down"))
SHARED = tuple(f"mlp.shared_experts.{n}_proj.weight"
               for n in ("gate", "up", "down"))


def param_shapes(cfg: dict) -> dict:
    """name -> (shape, kind) for every weight; the HuggingFace names. The
    embedding is tied: there is no output head of its own."""
    h = cfg["hidden_size"]
    out = {"model.embed_tokens.weight": ((cfg["vocab_size"], h), "matrix")}
    for i in range(cfg["num_hidden_layers"]):
        for k, v in layer_shapes(cfg).items():
            out[f"model.layers.{i}.{k}"] = v
    out["model.norm.weight"] = ((h,), "norm")
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


def layer_norm(x, w, eps):
    xc = x - jnp.mean(x, -1, keepdims=True)
    return xc * jax.lax.rsqrt(jnp.mean(xc * xc, -1, keepdims=True) + eps) \
        * w.astype(F32)


def rope_tables(cfg, seq):
    d = cfg["head_dim"]
    inv = 1.0 / cfg["rope_theta"] ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.outer(np.arange(seq, dtype=np.float64), inv)
    return jnp.asarray(np.cos(ang), F32), jnp.asarray(np.sin(ang), F32)


def _rotate_pairs(x, cos, sin):
    """x [S, heads, D] at positions 0..S-1: the pair (x[2i], x[2i+1]) turned
    by the position's angle i."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1).reshape(x.shape)


def _sees(kind, cfg, qpos, kpos):
    """[queries, keys]: may the query at `qpos` see the key at `kpos`."""
    see = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] >= 0)
    if kind == SLIDING:
        see &= kpos[None, :] > qpos[:, None] - cfg["sliding_window"]
    return see


def attention(h, p, cfg, kind, cos, sin, quant=None):
    """The attention sub-block on one sequence's normed rows h [S, H] in a
    layer of `kind`: one kv head's query heads and `QUERY_BLOCK` queries at
    a time; a sliding layer's block takes only the keys from `window`
    (rounded up to blocks) before its first query to its last."""
    s = h.shape[0]
    nh, kvh, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    g = nh // kvh
    k = matmul(h, p["self_attn.k_proj.weight"], quant).reshape(s, kvh, d)
    v = matmul(h, p["self_attn.v_proj.weight"], quant).reshape(s, kvh, d)
    if kind == SLIDING:
        k = _rotate_pairs(k, cos, sin)
    blk = _block(s, QUERY_BLOCK)
    back = -(-cfg["sliding_window"] // blk) * blk if kind == SLIDING else s
    reach = back < s           # else every block takes every key
    if reach:
        k = jnp.pad(k, ((back, 0), (0, 0), (0, 0)))
        v = jnp.pad(v, ((back, 0), (0, 0), (0, 0)))

    def kv_head(acc, w):
        w_q, w_o, k_h, v_h = w                 # [H, g*d] [g*d, H] [S', d] x2
        q = matmul(h, w_q, quant).reshape(s, g, d)
        if kind == SLIDING:
            q = _rotate_pairs(q, cos, sin)

        def block(args):
            qb, q0 = args                                        # [blk, g, d]
            if reach:
                kb = jax.lax.dynamic_slice_in_dim(k_h, q0, back + blk, 0)
                vb = jax.lax.dynamic_slice_in_dim(v_h, q0, back + blk, 0)
                kpos = q0 - back + jnp.arange(back + blk)
            else:
                kb, vb, kpos = k_h, v_h, jnp.arange(s)
            sc = jnp.einsum("qgd,kd->gqk", qb, kb, precision=HI) * d ** -0.5
            see = _sees(kind, cfg, q0 + jnp.arange(blk), kpos)
            return jnp.einsum(
                "gqk,kd->qgd",
                jax.nn.softmax(jnp.where(see[None], sc, -jnp.inf), axis=-1),
                vb, precision=HI)

        o = jax.lax.map(block, (q.reshape(s // blk, blk, g, d),
                                jnp.arange(0, s, blk))).reshape(s, g * d)
        return acc + matmul(o, w_o, quant), None

    out, _ = jax.lax.scan(kv_head, jnp.zeros((s, cfg["hidden_size"]), F32), (
        jnp.moveaxis(p["self_attn.q_proj.weight"].reshape(-1, kvh, g * d), 1, 0),
        p["self_attn.o_proj.weight"].reshape(kvh, g * d, -1),
        jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
    return out


def dense_attention(h, p, cfg, kind, cos, sin, quant=None):
    """The same sub-block as the equations read: every head, every query and
    every key at once. For small sizes only."""
    s = h.shape[0]
    nh, kvh, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q = matmul(h, p["self_attn.q_proj.weight"], quant).reshape(s, nh, d)
    k = matmul(h, p["self_attn.k_proj.weight"], quant).reshape(s, kvh, d)
    v = matmul(h, p["self_attn.v_proj.weight"], quant).reshape(s, kvh, d)
    if kind == SLIDING:
        q, k = _rotate_pairs(q, cos, sin), _rotate_pairs(k, cos, sin)
    k, v = (jnp.repeat(a, nh // kvh, axis=1) for a in (k, v))
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) * d ** -0.5
    see = _sees(kind, cfg, jnp.arange(s), jnp.arange(s))
    a = jnp.einsum("hqk,khd->qhd",
                   jax.nn.softmax(jnp.where(see[None], sc, -jnp.inf), -1), v,
                   precision=HI)
    return matmul(a.reshape(s, nh * d), p["self_attn.o_proj.weight"], quant)


def swiglu(x, gate_w, up_w, down_w, quant=None):
    return matmul(jax.nn.silu(matmul(x, gate_w, quant))
                  * matmul(x, up_w, quant), down_w, quant)


def route(h, p, cfg):
    """h [S, H] -> (experts [S, k] over the ROUTER's width, weights [S, k])."""
    s = jax.nn.sigmoid(matmul(h, p["mlp.gate.weight"]))
    w, experts = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return experts, w


def _by_rows(fn, h):
    s, width = h.shape
    rows = _block(s, MOE_ROWS)
    return jax.lax.map(fn, h.reshape(s // rows, rows, width)).reshape(s, width)


def routed_moe(h, p, cfg, quant=None):
    """The held experts' part of the routed sum on h [S, H], `MOE_ROWS`
    rows at a time."""
    return _by_rows(lambda hb: _routed_rows(hb, p, cfg, quant), h)


def _routed_rows(h, p, cfg, quant=None):
    """Rows h [S, H]: each held expert applied only to the rows routed to
    it; an assignment to an expert that is not held adds nothing."""
    s, width = h.shape
    k = cfg["num_experts_per_tok"]
    _, lo, e = share(cfg)
    experts, weights = route(h, p, cfg)
    flat = experts.reshape(s * k)
    held = (flat >= lo) & (flat < lo + e)
    flat = jnp.where(held, flat - lo, e)             # the absent sort last
    order = jnp.argsort(flat)                        # sorted row -> flat row
    count = jnp.sum(flat[:, None] == jnp.arange(e)[None, :], axis=0)
    start = jnp.cumsum(count) - count                # first sorted row
    nblk = -(-count // ROW_BLOCK)                    # blocks of each expert
    first = jnp.cumsum(nblk) - nblk                  # its first block
    blocks = -(-s * k // ROW_BLOCK) + e              # no more than these
    xs = jnp.concatenate([jnp.take(h, order // k, axis=0),
                          jnp.zeros((ROW_BLOCK, width), F32)])

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
    y = jnp.zeros((s * k, width), F32).at[rows.reshape(-1)].set(
        ys.reshape(-1, width), mode="drop")          # sorted order
    back = jnp.zeros((s * k,), jnp.int32).at[order].set(
        jnp.arange(s * k, dtype=jnp.int32))
    y = jnp.take(y, back, axis=0).reshape(s, k, width)
    return jnp.sum(y * weights[..., None], axis=1)


def every_expert_moe(h, p, cfg, quant=None):
    """The held experts' part as the equations read: every held expert on
    every row, times its weight (zero where not chosen). Small sizes only."""
    _, lo, e = share(cfg)
    experts, weights = route(h, p, cfg)
    out = jnp.zeros_like(h)
    for ex in range(e):
        w = jnp.sum(jnp.where(experts == lo + ex, weights, 0.0), axis=-1)
        out = out + w[:, None] * swiglu(
            h, p["mlp.experts.gate_proj.weight"][ex],
            p["mlp.experts.up_proj.weight"][ex],
            p["mlp.experts.down_proj.weight"][ex], quant)
    return out


def shared_experts(h, p, cfg, quant=None):
    """The mean of the shared experts' SwiGLUs on h [S, H], expert by
    expert, `MOE_ROWS` rows at a time."""
    n, im = cfg["num_shared_experts"], cfg["intermediate_size"]
    gate, up, down = (p[k] for k in SHARED)

    def rows(hb):
        out = jnp.zeros_like(hb)
        for j in range(n):
            cols = slice(j * im, (j + 1) * im)
            out = out + swiglu(hb, gate[:, cols], up[:, cols], down[cols],
                               quant)
        return out / n

    return _by_rows(rows, h)


def bf16_flips(h, p, cfg):
    """The share of rows of `h` [S, H] whose set of chosen experts changes
    when `h` is rounded to bfloat16 before the router: how often the
    smallest perturbation a bfloat16 program makes flips a choice at a
    near-tie."""
    exact, _ = route(h, p, cfg)
    rounded, _ = route(h.astype(jnp.bfloat16).astype(F32), p, cfg)
    return jnp.mean(jnp.any(jnp.sort(exact, -1) != jnp.sort(rounded, -1), -1))


def layer(x, p, cfg, kind, cos, sin, quant=None, moe=routed_moe,
          attend=attention):
    """One decoder layer of `kind` on one sequence x [S, H]; `p` names the
    layer's weights without the `model.layers.<i>.` prefix."""
    h = layer_norm(x, p["input_layernorm.weight"], cfg["layer_norm_eps"])
    return x + attend(h, p, cfg, kind, cos, sin, quant) \
        + moe(h, p, cfg, quant) + shared_experts(h, p, cfg, quant)


def layer_params(params, i):
    pre = f"model.layers.{i}."
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def head_logits(x, norm_w, embed, cfg, quant=None):
    """Final LayerNorm and the tied head; `embed` [rows of the vocabulary,
    H], all of them or a slice."""
    return cfg.get("logit_scale", 1) * matmul(
        layer_norm(x, norm_w, cfg["layer_norm_eps"]), embed.T, quant)


def forward(params, ids, cfg, quant=None, moe=routed_moe, attend=attention):
    """ids [S] -> logits [S, V]: the full forward, no cache."""
    cos, sin = rope_tables(cfg, ids.shape[0])
    x = jnp.take(params["model.embed_tokens.weight"], ids, axis=0).astype(F32)
    for i, kind in enumerate(layer_kinds(cfg)):
        x = layer(x, layer_params(params, i), cfg, kind, cos, sin, quant, moe,
                  attend)
    return head_logits(x, params["model.norm.weight"],
                       params["model.embed_tokens.weight"], cfg, quant)
