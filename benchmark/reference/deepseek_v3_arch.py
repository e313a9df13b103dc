"""Plain reference of the DeepSeek-V3 architecture (`model_type:
deepseek_v3`; here Kanana-2-30B-A3B): float32 `jax.numpy`, matmuls at the
`highest` precision, no kernels, no cache, no grouped matmul. It imports
nothing of the program under test and takes nothing the program has made:
its weights come from `benchmark/weights.py` and the seed, and its routing
is its own.

Follows the published equations (DeepSeek-V3 technical report, sections
2.1.1 and 2.1.2; the HuggingFace `modeling_deepseek_v3.py`), per token row x,
RMSNorm before each sub-block and the residual after:

- MLA without `q_lora_rank`, in the EXPANDED form: `q = x W_q -> [heads,
  nope + rope]`; `a = x W_kva`, `c = RMSNorm(a[:rank])`, `k_rope =
  a[rank:]` shared by all heads; RoPE on `q_rope` and `k_rope`
  (`rope_interleave`: the pairs (x0, x1), (x2, x3), ... are de-interleaved,
  then rotated in the rotate-half form); `[k_nope | v] = c W_kvb` by head;
  `k = [k_nope | k_rope]`; causal softmax of `q k^T (nope + rope)^-0.5`
  times `v`; `W_o`.
- Expert layers (all but the first `first_k_dense_replace`): `s =
  sigmoid(x W_g)`; the top `num_experts_per_tok` of `s + bias` (`n_group` 1:
  no group limit); weights `s[chosen] / (sum + 1e-20) *
  routed_scaling_factor`; the chosen experts' SwiGLUs combined by weight,
  plus one shared SwiGLU of width `n_shared_experts *
  moe_intermediate_size` on every token. The first layers: a dense SwiGLU.
- Final RMSNorm, untied output head.

Departures, all about layout, memory and time, none about mathematics:
linear weights are `[in, out]` and the routed experts are stacked on a
leading axis (`mlp.experts.gate_proj.weight [E, in, out]`, ...), the layout
the program shares and states; attention runs a block of queries at a time
and a group of heads at a time (`jax.lax.map`), and the routed experts a
slice of the sequence at a time; each expert is applied only to the rows routed to it
(applying all 128 to every row multiplies the work by 21): the rows are
sorted by expert with this file's own argsort and walked in blocks of
`ROW_BLOCK`, each block multiplied by the one expert it belongs to; expert
weights stay in the bf16 they are made in (they are bf16-valued) and are
upcast an expert at a time, so the reference fits in the 3 GB left beside
the program. `every_expert_moe` is the form without any of this, for
`benchmark/tests/`.

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
QUERY_BLOCK = 256      # queries attended at a time
HEAD_GROUP = 8         # heads attended at a time
ROW_BLOCK = 128        # sorted rows multiplied by one expert at a time
MOE_ROWS = 1024        # rows of a sequence routed at a time


def layer_shapes(cfg: dict, i: int) -> dict:
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    rank, rd = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, vd = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    out = {
        "input_layernorm.weight": ((h,), "norm"),
        "self_attn.q_proj.weight": ((h, nh * (nope + rd)), "matrix"),
        "self_attn.kv_a_proj_with_mqa.weight": ((h, rank + rd), "matrix"),
        "self_attn.kv_a_layernorm.weight": ((rank,), "norm"),
        "self_attn.kv_b_proj.weight": ((rank, nh * (nope + vd)), "matrix"),
        "self_attn.o_proj.weight": ((nh * vd, h), "matrix"),
        "post_attention_layernorm.weight": ((h,), "norm"),
    }
    if i < cfg["first_k_dense_replace"]:
        inter = cfg["intermediate_size"]
        out.update({"mlp.gate_proj.weight": ((h, inter), "matrix"),
                    "mlp.up_proj.weight": ((h, inter), "matrix"),
                    "mlp.down_proj.weight": ((inter, h), "matrix")})
        return out
    e, im = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    sh = cfg["n_shared_experts"] * im
    out.update({
        "mlp.gate.weight": ((h, e), "matrix"),
        "mlp.gate.e_score_correction_bias": ((e,), "matrix"),
        "mlp.experts.gate_proj.weight": ((e, h, im), "matrix"),
        "mlp.experts.up_proj.weight": ((e, h, im), "matrix"),
        "mlp.experts.down_proj.weight": ((e, im, h), "matrix"),
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


def rope_tables(cfg, seq):
    d = cfg["qk_rope_head_dim"]
    inv = 1.0 / cfg["rope_theta"] ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.outer(np.arange(seq, dtype=np.float64), inv)
    return jnp.asarray(np.cos(ang), F32), jnp.asarray(np.sin(ang), F32)


def _rotate(x, cos, sin, interleave):
    """x [S, heads, D] at positions 0..S-1."""
    if interleave:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(x, p, cfg, cos, sin, quant=None):
    """The MLA sub-block on one sequence x [S, H] (already normed), expanded
    form, `HEAD_GROUP` heads and a block of queries at a time."""
    s = x.shape[0]
    nh, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rd, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                    cfg["v_head_dim"])
    il = cfg["rope_interleave"]
    a = matmul(x, p["self_attn.kv_a_proj_with_mqa.weight"], quant)
    c = rms_norm(a[:, :rank], p["self_attn.kv_a_layernorm.weight"],
                 cfg["rms_norm_eps"])
    k_rope = _rotate(a[:, None, rank:], cos, sin, il)            # [S, 1, rd]
    blk, hg = _block(s, QUERY_BLOCK), _block(nh, HEAD_GROUP)
    kpos = jnp.arange(s)

    def heads(w):
        """The head group's columns of q_proj and of kv_b_proj."""
        w_q, w_kvb = w
        q = matmul(x, w_q, quant).reshape(s, hg, nope + rd)
        q = jnp.concatenate(
            [q[..., :nope], _rotate(q[..., nope:], cos, sin, il)], axis=-1)
        kv = matmul(c, w_kvb, quant).reshape(s, hg, nope + vd)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, (s, hg, rd))], axis=-1)
        v = kv[..., nope:]

        def block(args):
            qb, qpos = args                                      # [blk, hg, D]
            sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) \
                * (nope + rd) ** -0.5
            sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc,
                           -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v,
                              precision=HI)

        return jax.lax.map(block, (q.reshape(s // blk, blk, hg, nope + rd),
                                   kpos.reshape(s // blk, blk))
                           ).reshape(s, hg * vd)

    by_group = lambda w, d: jnp.moveaxis(                        # noqa: E731
        w.reshape(w.shape[0], nh // hg, hg * d), 1, 0)
    o = jax.lax.map(heads, (
        by_group(p["self_attn.q_proj.weight"], nope + rd),
        by_group(p["self_attn.kv_b_proj.weight"], nope + vd)))   # [G, S, hg*vd]
    o = jnp.moveaxis(o, 0, 1).reshape(s, nh * vd)
    return matmul(o, p["self_attn.o_proj.weight"], quant)


def swiglu(x, gate_w, up_w, down_w, quant=None):
    return matmul(jax.nn.silu(matmul(x, gate_w, quant))
                  * matmul(x, up_w, quant), down_w, quant)


def route(x, p, cfg):
    """x [S, H] -> (experts [S, k], weights [S, k])."""
    s = jax.nn.sigmoid(matmul(x, p["mlp.gate.weight"]))
    _, experts = jax.lax.top_k(
        s + p["mlp.gate.e_score_correction_bias"].astype(F32),
        cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, experts, axis=-1)
    if cfg.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return experts, w * cfg["routed_scaling_factor"]


def routed_moe(x, p, cfg, quant=None):
    """The routed experts on x [S, H], `MOE_ROWS` rows at a time."""
    s, h = x.shape
    rows = _block(s, MOE_ROWS)
    return jax.lax.map(lambda xb: _routed_rows(xb, p, cfg, quant),
                       x.reshape(s // rows, rows, h)).reshape(s, h)


def _routed_rows(x, p, cfg, quant=None):
    """The routed experts on rows x [S, H], each applied only to its rows."""
    s, h = x.shape
    e, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    experts, weights = route(x, p, cfg)
    flat = experts.reshape(s * k)
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
    """The routed experts as the equations read: every expert on every row,
    times its weight (zero where not chosen). For small sizes only."""
    experts, weights = route(x, p, cfg)
    out = jnp.zeros_like(x)
    for ex in range(cfg["n_routed_experts"]):
        w = jnp.sum(jnp.where(experts == ex, weights, 0.0), axis=-1)
        out = out + w[:, None] * swiglu(
            x, p["mlp.experts.gate_proj.weight"][ex],
            p["mlp.experts.up_proj.weight"][ex],
            p["mlp.experts.down_proj.weight"][ex], quant)
    return out


def bf16_flips(a, p, cfg):
    """The share of rows of `a` [S, H] whose set of chosen experts changes
    when `a` is rounded to bfloat16 before the router: how often the
    smallest perturbation a bfloat16 program makes flips a choice at a
    near-tie. A program's hidden state differs from the reference's by more
    than one rounding, so it flips at least this often."""
    exact, _ = route(a, p, cfg)
    rounded, _ = route(a.astype(jnp.bfloat16).astype(F32), p, cfg)
    return jnp.mean(jnp.any(jnp.sort(exact, -1) != jnp.sort(rounded, -1), -1))


def layer(x, p, cfg, cos, sin, quant=None, moe=routed_moe, flips=False):
    """One decoder layer on one sequence x [S, H]; `p` names the layer's
    weights without the `model.layers.<i>.` prefix. `flips`: return `(x,
    bf16_flips of the expert layer's input)` (0 for a dense layer)."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(rms_norm(x, p["input_layernorm.weight"], eps), p, cfg,
                      cos, sin, quant)
    a = rms_norm(x, p["post_attention_layernorm.weight"], eps)
    if "mlp.gate.weight" not in p:
        out = x + swiglu(a, p["mlp.gate_proj.weight"],
                         p["mlp.up_proj.weight"], p["mlp.down_proj.weight"],
                         quant)
        return (out, jnp.zeros((), F32)) if flips else out
    out = x + moe(a, p, cfg, quant) + swiglu(
        a, p["mlp.shared_experts.gate_proj.weight"],
        p["mlp.shared_experts.up_proj.weight"],
        p["mlp.shared_experts.down_proj.weight"], quant)
    return (out, bf16_flips(a, p, cfg)) if flips else out


def layer_params(params, i):
    pre = f"model.layers.{i}."
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def head_logits(x, norm_w, head_w, cfg, quant=None):
    return matmul(rms_norm(x, norm_w, cfg["rms_norm_eps"]), head_w, quant)


def forward(params, ids, cfg, quant=None, moe=routed_moe):
    """ids [S] -> logits [S, V]: the full forward, no cache."""
    cos, sin = rope_tables(cfg, ids.shape[0])
    x = jnp.take(params["model.embed_tokens.weight"], ids, axis=0).astype(F32)
    for i in range(cfg["num_hidden_layers"]):
        x = layer(x, layer_params(params, i), cfg, cos, sin, quant, moe)
    return head_logits(x, params["model.norm.weight"],
                       params["lm_head.weight"], cfg, quant)
