"""Plain reference of the Brumby architecture (`model_type: brumby`; here
Brumby-14B-Base): float32 `jax.numpy`, matmuls at the `highest` precision,
no kernels, no state, no batching. It imports nothing of the program under
test and takes nothing the program has made: its weights come from
`benchmark/weights.py` and the seed.

The model is the Qwen3-shaped dense decoder (RMSNorm, q/k/v projections
without bias, per-head RMSNorm of q and k, RoPE over split halves, SwiGLU,
untied head) whose attention is replaced by POWER RETENTION of degree 2
(Gelada et al., "Scaling Context Requires Rethinking Attention",
arXiv:2507.04239). The retention layer is computed here in its ATTENTION
FORM, which needs no state and no feature map: per KV head, with `a_t =
logsigmoid(h_t . w_g + b_g)` the log-gate and `G` its running sum,

    y_i = sum_{j<=i} w_ij v_j / (sum_{j<=i} w_ij + eps),
    w_ij = exp(G_i - G_j) (q_i . k_j)^2 s^2,

the query heads of a group sharing their KV head's `k`, `v` and `a`. The
recurrent form the published description gives (`S_t = e^{a_t} S_{t-1} +
phi(k_t) v_t^T`, `z_t` likewise, `y_t = phi(q_t)^T S_t / (phi(q_t)^T z_t +
eps)`, `phi` the symmetric degree-2 embedding) is the same numbers, since
`phi(q) . phi(k) = (q . k)^2`; the program runs that one, and the tests hold
the two together.

What the published `config.json` does not say is listed under `assumed` in
the configuration's file, and each is one line here: the degree (the square
in `w_ij`), the gate (`log_gate`), that q/k norm and RoPE stay (`_qk`), `s`
(`_scale`), `eps` (`_eps`).

Departures, all about memory and time, none about mathematics: linear
weights are `[in, out]`; retention runs one KV head's query heads and a
block of `QUERY_BLOCK` queries at a time against every key under the causal
mask (`dense_retention` is the form that takes all heads and queries at
once, for `benchmark/tests/`); the SwiGLU runs `MLP_ROWS` rows at a time;
bf16-valued weights are upcast where they are used, so that a 22k-token
sample fits beside the program's weights.

`quant="int8"` is the control of `benchmark/README.md`: every projection's
operands on a symmetric int8 grid (weights per output channel, activations
per row). It exists to be refused.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256      # queries attended at a time
MLP_ROWS = 2048        # rows of a sequence through the SwiGLU at a time


def layer_shapes(cfg: dict) -> dict:
    h, nh, kvh, d = (cfg["hidden_size"], cfg["num_attention_heads"],
                     cfg["num_key_value_heads"], cfg["head_dim"])
    im = cfg["intermediate_size"]
    return {
        "input_layernorm.weight": ((h,), "norm"),
        "self_attn.q_proj.weight": ((h, nh * d), "matrix"),
        "self_attn.k_proj.weight": ((h, kvh * d), "matrix"),
        "self_attn.v_proj.weight": ((h, kvh * d), "matrix"),
        "self_attn.o_proj.weight": ((nh * d, h), "matrix"),
        "self_attn.q_norm.weight": ((d,), "norm"),
        "self_attn.k_norm.weight": ((d,), "norm"),
        "self_attn.g_proj.weight": ((h, kvh), "matrix"),
        "self_attn.g_proj.bias": ((kvh,), "bias"),
        "post_attention_layernorm.weight": ((h,), "norm"),
        "mlp.gate_proj.weight": ((h, im), "matrix"),
        "mlp.up_proj.weight": ((h, im), "matrix"),
        "mlp.down_proj.weight": ((im, h), "matrix"),
    }


RETENTION = ("input_layernorm.weight",) + tuple(
    f"self_attn.{n}" for n in ("q_proj.weight", "k_proj.weight",
                               "v_proj.weight", "o_proj.weight",
                               "q_norm.weight", "k_norm.weight",
                               "g_proj.weight", "g_proj.bias"))
MLP = ("post_attention_layernorm.weight",) + tuple(
    f"mlp.{n}_proj.weight" for n in ("gate", "up", "down"))


def param_shapes(cfg: dict) -> dict:
    """name -> (shape, kind) for every weight; the HuggingFace Qwen3 names,
    with `self_attn.g_proj` for the gate. A leaf of kind "bias" is not a
    draw of `benchmark/weights.py` itself: `gate_bias` makes it of one."""
    h = cfg["hidden_size"]
    out = {"model.embed_tokens.weight": ((cfg["vocab_size"], h), "matrix")}
    for i in range(cfg["num_hidden_layers"]):
        for k, v in layer_shapes(cfg).items():
            out[f"model.layers.{i}.{k}"] = v
    out["model.norm.weight"] = ((h,), "norm")
    out["lm_head.weight"] = ((h, cfg["vocab_size"]), "matrix")
    return out


def gate_bias(draw, std=0.02, shortest=64.0, longest=4096.0):
    """A layer's gate biases from a N(0, std^2) draw, one a KV head: the
    draw's quantile places the head's HALF-LIFE (at a zero gate input)
    log-uniformly between `shortest` and `longest` tokens, and the bias is
    where `sigmoid(b) = 2 ** (-1 / half-life)`. With a zero bias a random
    gate keeps half of its state a token: nothing would ever be carried
    across a chunk, and the comparison would not see the state at all."""
    u = 0.5 * (1.0 + jax.scipy.special.erf(
        jnp.asarray(draw, F32) / (std * math.sqrt(2.0))))
    half_life = shortest * (longest / shortest) ** u
    keep = jnp.exp2(-1.0 / half_life)
    return jnp.log(keep) - jnp.log1p(-keep)


def _scale(cfg):
    return cfg.get("retention_scale") or cfg["head_dim"] ** -0.5


def _eps(cfg):
    return cfg.get("retention_eps", 1e-6)


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
    d = cfg["head_dim"]
    inv = 1.0 / cfg["rope_theta"] ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.outer(np.arange(seq, dtype=np.float64), inv)
    return jnp.asarray(np.cos(ang), F32), jnp.asarray(np.sin(ang), F32)


def _rotate_halves(x, cos, sin):
    """x [S, heads, D] at positions 0..S-1: the pair (x[i], x[i + D/2])
    turned by the position's angle i (the Qwen3 layout)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def log_gate(h, p):
    """`a = logsigmoid(h w_g + b_g)` [S, kv heads]; never on the int8 grid
    (it is no projection of the block's: a scalar a head)."""
    return jax.nn.log_sigmoid(
        matmul(h, p["self_attn.g_proj.weight"])
        + p["self_attn.g_proj.bias"].astype(F32))


def _qk(x, w, norm_w, cfg, cos, sin, heads, quant):
    """A projection to `heads` x head_dim, its per-head RMSNorm, its RoPE."""
    y = matmul(x, w, quant).reshape(x.shape[0], heads, cfg["head_dim"])
    return _rotate_halves(rms_norm(y, norm_w, cfg["rms_norm_eps"]), cos, sin)


def retention(h, p, cfg, cos, sin, quant=None):
    """The retention sub-block on one sequence's normed rows h [S, H]: one
    KV head's query heads and `QUERY_BLOCK` queries at a time, every key
    under the causal mask."""
    s = h.shape[0]
    nh, kvh, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    g = nh // kvh
    k = _qk(h, p["self_attn.k_proj.weight"], p["self_attn.k_norm.weight"],
            cfg, cos, sin, kvh, quant)
    v = matmul(h, p["self_attn.v_proj.weight"], quant).reshape(s, kvh, d)
    big_g = jnp.cumsum(log_gate(h, p), axis=0)                    # [S, kvh]
    blk = _block(s, QUERY_BLOCK)
    scale2, eps = _scale(cfg) ** 2, _eps(cfg)
    kpos = jnp.arange(s)

    def kv_head(acc, w):
        w_q, w_o, k_h, v_h, g_h = w         # [H, g*d] [g*d, H] [S, d] x2 [S]
        q = _qk(h, w_q, p["self_attn.q_norm.weight"], cfg, cos, sin, g, quant)

        def block(args):
            qb, q0 = args                                        # [blk, g, d]
            qpos = q0 + jnp.arange(blk)
            gq = jax.lax.dynamic_slice_in_dim(g_h, q0, blk)
            sc = jnp.einsum("qgd,kd->gqk", qb, k_h, precision=HI)
            see = kpos[None, :] <= qpos[:, None]
            decay = jnp.exp(jnp.where(see, gq[:, None] - g_h[None, :],
                                      -jnp.inf))
            wgt = sc * sc * scale2 * decay[None]                 # degree 2
            num = jnp.einsum("gqk,kd->qgd", wgt, v_h, precision=HI)
            den = jnp.sum(wgt, axis=-1).T[..., None]             # [blk, g, 1]
            return num / (den + eps)

        o = jax.lax.map(block, (q.reshape(s // blk, blk, g, d),
                                jnp.arange(0, s, blk))).reshape(s, g * d)
        return acc + matmul(o, w_o, quant), None

    out, _ = jax.lax.scan(kv_head, jnp.zeros((s, cfg["hidden_size"]), F32), (
        jnp.moveaxis(p["self_attn.q_proj.weight"].reshape(-1, kvh, g * d), 1, 0),
        p["self_attn.o_proj.weight"].reshape(kvh, g * d, -1),
        jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0), big_g.T))
    return out


def dense_retention(h, p, cfg, cos, sin, quant=None):
    """The same sub-block as the equations read: every head, every query and
    every key at once. For small sizes only."""
    s = h.shape[0]
    nh, kvh, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q = _qk(h, p["self_attn.q_proj.weight"], p["self_attn.q_norm.weight"],
            cfg, cos, sin, nh, quant)
    k = _qk(h, p["self_attn.k_proj.weight"], p["self_attn.k_norm.weight"],
            cfg, cos, sin, kvh, quant)
    v = matmul(h, p["self_attn.v_proj.weight"], quant).reshape(s, kvh, d)
    big_g = jnp.cumsum(log_gate(h, p), axis=0)                    # [S, kvh]
    k, v, big_g = (jnp.repeat(x, nh // kvh, axis=1) for x in (k, v, big_g))
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HI)
    see = jnp.tril(jnp.ones((s, s), bool))
    decay = jnp.exp(jnp.where(see[None], big_g.T[:, :, None]
                              - big_g.T[:, None, :], -jnp.inf))
    wgt = sc * sc * _scale(cfg) ** 2 * decay
    y = jnp.einsum("hqk,khd->qhd", wgt, v, precision=HI) \
        / (jnp.sum(wgt, -1).T[..., None] + _eps(cfg))
    return matmul(y.reshape(s, nh * d), p["self_attn.o_proj.weight"], quant)


def mlp(h, p, cfg, quant=None):
    """The SwiGLU on normed rows h [S, H], `MLP_ROWS` rows at a time."""
    s, width = h.shape
    rows = _block(s, MLP_ROWS)

    def some(hb):
        return matmul(jax.nn.silu(matmul(hb, p["mlp.gate_proj.weight"], quant))
                      * matmul(hb, p["mlp.up_proj.weight"], quant),
                      p["mlp.down_proj.weight"], quant)

    return jax.lax.map(some, h.reshape(s // rows, rows, width)).reshape(
        s, width)


def retention_block(x, p, cfg, cos, sin, quant=None, retain=retention):
    """`x + retention(RMSNorm(x))`; `p` names the layer's weights without
    the `model.layers.<i>.` prefix."""
    h = rms_norm(x, p["input_layernorm.weight"], cfg["rms_norm_eps"])
    return x + retain(h, p, cfg, cos, sin, quant)


def mlp_block(x, p, cfg, quant=None):
    h = rms_norm(x, p["post_attention_layernorm.weight"], cfg["rms_norm_eps"])
    return x + mlp(h, p, cfg, quant)


def layer_params(params, i):
    pre = f"model.layers.{i}."
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def head_logits(x, norm_w, head_w, cfg, quant=None):
    """Final RMSNorm and the untied head `[H, V]`."""
    return matmul(rms_norm(x, norm_w, cfg["rms_norm_eps"]), head_w, quant)


def forward(params, ids, cfg, quant=None, retain=retention):
    """ids [S] -> logits [S, V]: the full forward, no state."""
    cos, sin = rope_tables(cfg, ids.shape[0])
    x = jnp.take(params["model.embed_tokens.weight"], ids, axis=0).astype(F32)
    for i in range(cfg["num_hidden_layers"]):
        p = layer_params(params, i)
        x = mlp_block(retention_block(x, p, cfg, cos, sin, quant, retain), p,
                      cfg, quant)
    return head_logits(x, params["model.norm.weight"],
                       params["lm_head.weight"], cfg, quant)
