"""Plain reference of the Llama-architecture decoder (Mistral, Yi): float32
`jax.numpy`, matmuls at the `highest` precision, no kernels, no cache, no
batching tricks. It imports nothing of the program under test and takes
nothing the program has made: its weights come from `benchmark/weights.py`
and the seed.

Follows the published description (Touvron et al. 2023; Jiang et al. 2023):
pre-norm residual blocks, RMSNorm, rotary embedding on split halves
(the HuggingFace `rotate_half` convention), grouped-query attention with a
causal mask, SwiGLU, untied output head. Linear weights are `[in, out]`.

Departures, all about memory and none about mathematics: attention runs one
KV group at a time and the output head with its cross-entropy one block of
rows at a time (`jax.lax.map` over `jax.checkpoint`), so that the float32
reference fits beside nothing else on one 16 GB chip at 4096 tokens.

`quant="int8"` is the control of `benchmark/README.md` ("How correct is
decided"): every projection's operands are rounded to a symmetric int8 grid
(weights per output channel, activations and incoming gradients per row),
the precision one step under the bfloat16 the configurations state, and the
one the chip's 393 TOP/s would tempt a later PR with. It exists to be
refused.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def param_shapes(cfg: dict) -> dict:
    """name -> (shape, kind) for every weight of the architecture; kind is
    "matrix" or "norm". Names are the HuggingFace ones under the program's
    `llama.` prefix, so a runner can hand the same leaf to both sides."""
    h, inter = cfg["hidden_size"], cfg["intermediate_size"]
    d = h // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * d
    out = {"llama.embed_tokens.weight": ((cfg["vocab_size"], h), "matrix")}
    for i in range(cfg["num_hidden_layers"]):
        for name, leaf in layer_shapes(h, inter, kv).items():
            out[f"llama.layers.{i}.{name}"] = leaf
    out["llama.norm.weight"] = ((h,), "norm")
    out["lm_head.weight"] = ((h, cfg["vocab_size"]), "matrix")
    return out


def layer_shapes(h, inter, kv):
    return {
        "self_attn.q_proj.weight": ((h, h), "matrix"),
        "self_attn.k_proj.weight": ((h, kv), "matrix"),
        "self_attn.v_proj.weight": ((h, kv), "matrix"),
        "self_attn.o_proj.weight": ((h, h), "matrix"),
        "mlp.gate_proj.weight": ((h, inter), "matrix"),
        "mlp.up_proj.weight": ((h, inter), "matrix"),
        "mlp.down_proj.weight": ((inter, h), "matrix"),
        "input_layernorm.weight": ((h,), "norm"),
        "post_attention_layernorm.weight": ((h,), "norm"),
    }


def _int8_grid(x, axis):
    """Round to a symmetric int8 grid along `axis` (127 steps to the largest
    magnitude there)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _mm(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


@jax.custom_vjp
def _matmul_int8(x, w):
    """x [S, K] @ w [K, N] as an int8 pipeline computes it: activations on a
    per-row grid, weights on a per-output-channel grid, and in the backward
    pass the incoming gradient on a per-row grid too."""
    return _mm(_int8_grid(x, -1), _int8_grid(w, 0))


def _matmul_int8_fwd(x, w):
    xq, wq = _int8_grid(x, -1), _int8_grid(w, 0)
    return _mm(xq, wq), (xq, wq)


def _matmul_int8_bwd(res, dy):
    xq, wq = res
    dq = _int8_grid(dy, -1)
    return _mm(dq, wq.T), _mm(xq.T, dq)


_matmul_int8.defvjp(_matmul_int8_fwd, _matmul_int8_bwd)


def matmul(x, w, quant=None):
    if quant == "int8":
        return _matmul_int8(x, w)
    if quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return _mm(x, w)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope_tables(cfg, seq):
    d = cfg["hidden_size"] // cfg["num_attention_heads"]
    inv = 1.0 / cfg["rope_theta"] ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.outer(np.arange(seq, dtype=np.float64), inv)
    return jnp.asarray(np.cos(ang), F32), jnp.asarray(np.sin(ang), F32)


def _rotate(x, cos, sin):
    """x [S, heads, D]; pairs are (x[..., :D/2], x[..., D/2:])."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attend_group(args):
    """One KV head and the G query heads that share it, full causal
    softmax over the sequence: q [S, G, D], k and v [S, D]."""
    q, k, v = args
    s, d = k.shape
    hi = jax.lax.Precision.HIGHEST
    scores = jnp.einsum("qgd,kd->gqk", q, k, precision=hi) / math.sqrt(d)
    mask = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return jnp.einsum("gqk,kd->qgd", probs, v, precision=hi)


def layer(x, p, cfg, cos, sin, quant=None):
    """One decoder layer on one sequence, x [S, H]."""
    s, h = x.shape
    nh, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, eps = h // nh, cfg["rms_norm_eps"]
    a = rms_norm(x, p["input_layernorm.weight"], eps)
    q = matmul(a, p["self_attn.q_proj.weight"], quant).reshape(s, nh, d)
    k = matmul(a, p["self_attn.k_proj.weight"], quant).reshape(s, kvh, d)
    v = matmul(a, p["self_attn.v_proj.weight"], quant).reshape(s, kvh, d)
    q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
    groups = (q.reshape(s, kvh, nh // kvh, d).transpose(1, 0, 2, 3),
              k.transpose(1, 0, 2), v.transpose(1, 0, 2))
    o = jax.lax.map(jax.checkpoint(_attend_group), groups)  # [KVH,S,G,D]
    o = o.transpose(1, 0, 2, 3).reshape(s, h)
    x = x + matmul(o, p["self_attn.o_proj.weight"], quant)
    a = rms_norm(x, p["post_attention_layernorm.weight"], eps)
    gate = matmul(a, p["mlp.gate_proj.weight"], quant)
    up = matmul(a, p["mlp.up_proj.weight"], quant)
    return x + matmul(jax.nn.silu(gate) * up, p["mlp.down_proj.weight"],
                      quant)


def layer_params(params, i):
    pre = f"llama.layers.{i}."
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def hidden(params, ids, cfg, quant=None):
    """ids [S] -> the last layer's output before the final norm, [S, H]."""
    cos, sin = rope_tables(cfg, ids.shape[0])
    x = jnp.take(params["llama.embed_tokens.weight"], ids, axis=0)
    body = jax.checkpoint(lambda x, p: layer(x, p, cfg, cos, sin, quant))
    for i in range(cfg["num_hidden_layers"]):
        x = body(x, layer_params(params, i))
    return x


def head_logits(x, norm_w, head_w, cfg, quant=None):
    return matmul(rms_norm(x, norm_w, cfg["rms_norm_eps"]), head_w, quant)


def forward(params, ids, cfg, quant=None):
    """ids [S] -> logits [S, V]: the full forward, no cache."""
    return head_logits(hidden(params, ids, cfg, quant),
                       params["llama.norm.weight"], params["lm_head.weight"],
                       cfg, quant)


def loss(params, ids, labels, cfg, quant=None, row_block=1024):
    """Mean next-token cross-entropy of `labels` [S] under `forward`,
    the head taken `row_block` rows at a time."""
    x = hidden(params, ids, cfg, quant)
    s = x.shape[0]
    blk = math.gcd(s, row_block)

    def block_nll(args):
        xb, lb = args
        logits = head_logits(xb, params["llama.norm.weight"],
                             params["lm_head.weight"], cfg, quant)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, lb[:, None], axis=-1)[:, 0]

    nll = jax.lax.map(jax.checkpoint(block_nll),
                      (x.reshape(s // blk, blk, -1),
                       labels.reshape(s // blk, blk)))
    return jnp.mean(nll)


def adamw_leaf(p, g, m, v, step, opt):
    """The decoupled-weight-decay Adam update of one leaf, float32."""
    b1, b2 = opt["beta1"], opt["beta2"]
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mhat, vhat = m / (1 - b1 ** step), v / (1 - b2 ** step)
    p = p - opt["lr"] * (mhat / (jnp.sqrt(vhat) + opt["eps"])
                         + opt["weight_decay"] * p)
    return p, m, v
