"""Cohere2-MoE causal LM (`model_type: cohere2_moe`; Command A+): sliding-
window layers with RoPE beside full layers without any position embedding,
a PARALLEL attention + feed-forward block under one LayerNorm, and a
sigmoid-routed mixture of experts with averaged shared experts.

Written ONCE, as functions over a weight pytree (HuggingFace Cohere2 names,
linear weights `[in, out]`, the routed experts stacked on a leading axis).
The serving engine (`inference/cohere2_moe_runner.py`) runs `decoder_layer`
with an `attend` that writes and reads its two paged pools;
`Cohere2MoeForCausalLM` is a thin holder of the pytree whose `forward` runs
the same `decoder_layer` with a dense `attend` over the rows in flight.

Equations, per token row x of layer l (`layer_types[l]`):

- `h = LayerNorm(x) = (x - mean) / sqrt(var + eps) * w_l`, float32
  statistics, weight only (no bias). ONE norm feeds both sub-blocks.
- Attention: `q = h Wq` (heads x head_dim), `k = h Wk`, `v = h Wv` (kv heads
  x head_dim), no bias, no QK norm. `sliding_attention`: RoPE on q and k
  over interleaved pairs `(2i, 2i+1)` (`rope_gptj`), all `head_dim`
  dimensions (`rotary_pct` 1), and query i sees key j iff `i - window < j
  <= i`. `full_attention`: NO position embedding, `j <= i`. `a =
  softmax(q k^T / sqrt(head_dim)) v`, `attn = a Wo`.
- Feed-forward on the same h: `s = sigmoid(h Wr)` in float32 (`highest`);
  the `num_experts_per_tok` largest; `w_e = s_e / sum of the chosen`
  (`norm_topk_prob`; no bias, no scaling factor: the config has neither);
  `routed = sum_chosen w_e E_e(h)`, `E(h) = (silu(h Wg) * (h Wu)) Wd`;
  `shared = mean over the num_shared_experts shared SwiGLUs` (`average`);
  `ffn = routed + shared`.
- `x <- x + attn + ffn` (`use_parallel_block`). After the last layer
  `logits = logit_scale * LayerNorm(x) E^T` with the tied embedding.

The expert layer is TOLD which experts it holds (`held_experts = (first,
count)`, the chip's share under expert parallelism): the router keeps its
`num_experts` outputs and its top-k, the stacked matrices are the held
experts' alone, and the routed sum is the held experts' part
(`models/deepseek_v3.moe_dispatch / moe_experts / moe_combine`, the one
dropless expert layer both
architectures run: it stays where it was written because the Kanana path
imports it from there and moving it would change that file for no gain).

Layout only: the shared experts' matrices lie side by side
(`mlp.shared_experts.gate_proj.weight [H, n_shared * I]`, down `[n_shared *
I, H]`), so their mean is one SwiGLU of that width times `1 / n_shared`.

Device regions keep the one family of names docs/OBSERVABILITY.md lists
(`llama.*`, `llama.moe*`), with `llama.attn_window` / `llama.attn_full`
around the attention of the two layer types.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..nn.parameter import Parameter
from .deepseek_v3 import (_mm, expert_rows, layer_params, moe_combine,
                          moe_dispatch, moe_experts, whole)

__all__ = ["Cohere2MoeConfig", "Cohere2MoeForCausalLM", "param_shapes",
           "init_params", "decoder_layer", "model_forward", "rope_tables"]

_scope = jax.named_scope
SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class Cohere2MoeConfig:
    """The published `config.json` keys this architecture reads, and the
    share of each expert layer held here. Frozen and hashable: it is a
    static argument of the compiled step."""
    vocab_size: int = 262144
    hidden_size: int = 4096
    intermediate_size: int = 4096
    num_hidden_layers: int = 32
    num_attention_heads: int = 128
    num_key_value_heads: int = 8
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 4
    norm_topk_prob: bool = True
    layer_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    sliding_window: int = 4096
    logit_scale: float = 1.0
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL) * 8
    max_position_embeddings: int = 200000
    # experts `first .. first + count - 1` of every layer live here
    held_experts: Optional[Tuple[int, int]] = None

    # what `deepseek_v3.dispatch` asks a config for
    @property
    def n_routed_experts(self) -> int:
        return self.num_experts

    @property
    def held(self) -> Tuple[int, int]:
        return self.held_experts or (0, self.num_experts)

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types) if t == kind)

    @classmethod
    def from_hf(cls, cfg: dict, held_experts=None) -> "Cohere2MoeConfig":
        """From a published `config.json`. What this implementation does
        not compute is refused here rather than silently dropped."""
        refused = {
            "attention_bias": bool(cfg.get("attention_bias", False)),
            "use_qk_norm": bool(cfg.get("use_qk_norm", False)),
            "use_parallel_block": not cfg.get("use_parallel_block", True),
            "use_gated_activation": not cfg.get("use_gated_activation", True),
            "hidden_act": cfg.get("hidden_act", "silu") != "silu",
            "expert_selection_fn":
                cfg.get("expert_selection_fn", "sigmoid") != "sigmoid",
            "shared_expert_combination_strategy": cfg.get(
                "shared_expert_combination_strategy", "average") != "average",
            "first_k_dense_replace": cfg.get("first_k_dense_replace", 0) != 0,
            "position_embedding_type":
                cfg.get("position_embedding_type", "rope_gptj") != "rope_gptj",
            "rotary_pct": cfg.get("rotary_pct", 1) != 1,
            "tie_word_embeddings": not cfg.get("tie_word_embeddings", True),
            "rope_parameters": (cfg.get("rope_parameters") or {}).get(
                "rope_type", "default") != "default",
        }
        bad = sorted(k for k, v in refused.items() if v)
        if bad:
            raise ValueError(
                f"cohere2_moe: config keys {bad} ask for a mechanism this "
                "implementation does not have")
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in cfg.items() if k in names and v is not None}
        depth = kw.get("num_hidden_layers", cls.num_hidden_layers)
        types = tuple(kw.get("layer_types", cls.layer_types))[:depth]
        if len(types) != depth or set(types) - {SLIDING, FULL}:
            raise ValueError(f"cohere2_moe: layer_types {types} do not name "
                             f"{depth} sliding or full layers")
        kw["layer_types"] = types
        if held_experts is not None:
            first, count = held_experts
            if not 0 <= first < first + count <= kw.get("num_experts",
                                                        cls.num_experts):
                raise ValueError(f"cohere2_moe: held experts {held_experts} "
                                 "are not the router's")
            kw["held_experts"] = (int(first), int(count))
        return cls(**kw)


# --- the weight pytree ---------------------------------------------------------

def layer_shapes(cfg: Cohere2MoeConfig) -> Dict[str, Tuple[tuple, str]]:
    """name -> (shape, "matrix" | "norm") of one layer's weights."""
    h, nh, kvh, d = (cfg.hidden_size, cfg.num_attention_heads,
                     cfg.num_key_value_heads, cfg.head_dim)
    im, held = cfg.intermediate_size, cfg.held[1]
    sh = cfg.num_shared_experts * im
    return {
        "input_layernorm.weight": ((h,), "norm"),
        "self_attn.q_proj.weight": ((h, nh * d), "matrix"),
        "self_attn.k_proj.weight": ((h, kvh * d), "matrix"),
        "self_attn.v_proj.weight": ((h, kvh * d), "matrix"),
        "self_attn.o_proj.weight": ((nh * d, h), "matrix"),
        "mlp.gate.weight": ((h, cfg.num_experts), "matrix"),
        "mlp.experts.gate_proj.weight": ((held, h, im), "matrix"),
        "mlp.experts.up_proj.weight": ((held, h, im), "matrix"),
        "mlp.experts.down_proj.weight": ((held, im, h), "matrix"),
        "mlp.shared_experts.gate_proj.weight": ((h, sh), "matrix"),
        "mlp.shared_experts.up_proj.weight": ((h, sh), "matrix"),
        "mlp.shared_experts.down_proj.weight": ((sh, h), "matrix"),
    }


def param_shapes(cfg: Cohere2MoeConfig) -> Dict[str, Tuple[tuple, str]]:
    """name -> (shape, kind) of the whole pytree (tied: no output head)."""
    out = {"model.embed_tokens.weight":
           ((cfg.vocab_size, cfg.hidden_size), "matrix")}
    for i in range(cfg.num_hidden_layers):
        for k, v in layer_shapes(cfg).items():
            out[f"model.layers.{i}.{k}"] = v
    out["model.norm.weight"] = ((cfg.hidden_size,), "norm")
    return out


def init_params(cfg: Cohere2MoeConfig, seed: int = 0, dtype=jnp.float32,
                std: float = 0.02) -> Dict[str, jax.Array]:
    """A pytree drawn on the device: matrices N(0, std^2), gains 1."""
    key = jax.random.key(seed)
    out = {}
    for n, (name, (shape, kind)) in enumerate(sorted(param_shapes(cfg).items())):
        if kind == "norm":
            out[name] = jnp.ones(shape, dtype)
        else:
            out[name] = (jax.random.normal(jax.random.fold_in(key, n), shape,
                                           jnp.float32) * std).astype(dtype)
    return out


# --- the blocks ------------------------------------------------------------------

def layer_norm(x, w, eps):
    """Mean-centred, weight only, float32 statistics."""
    xf = x.astype(jnp.float32)
    xc = xf - jnp.mean(xf, axis=-1, keepdims=True)
    y = xc * jax.lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def rope_tables(cfg: Cohere2MoeConfig, positions: int):
    """cos, sin `[positions, head_dim / 2]` float32, from float64 angles."""
    d = cfg.head_dim
    inv = 1.0 / cfg.rope_theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.outer(np.arange(positions, dtype=np.float64), inv)
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def rope_pairs(x, cos, sin):
    """`rope_gptj`: x `[T, heads, D]`, cos/sin `[T, D / 2]` at the tokens'
    positions; the pair `(x[2i], x[2i+1])` turns by angle i, in place (the
    layout stays the published one)."""
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    c, s = cos[:, None, :], sin[:, None, :]
    out = jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def qkv(h, p, cfg: Cohere2MoeConfig, kind: str, cos, sin):
    """The attention sub-block before its context, on normed rows `h [T,
    H]` of a layer of `kind`: `(q [T, heads, D], k, v [T, kv heads, D])`,
    q and k turned where the layer has a position embedding. They go to
    the layer's `attend(q, k, v) -> [T, heads, D]`, which owns the context:
    it stores this step's k and v and answers each query over what its
    layer type lets it see; `o_proj` takes its answer on. Everything in the
    sub-block but `attend` maps a row to a row."""
    t = h.shape[0]
    nh, kvh, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    with _scope("llama.qkv"):
        q = _mm(h, p["self_attn.q_proj.weight"]).reshape(t, nh, d)
        k = _mm(h, p["self_attn.k_proj.weight"]).reshape(t, kvh, d)
        v = _mm(h, p["self_attn.v_proj.weight"]).reshape(t, kvh, d)
    if kind == SLIDING:
        with _scope("llama.rope"):
            q, k = rope_pairs(q, cos, sin), rope_pairs(k, cos, sin)
    return q, k, v


def o_proj(a, p, cfg: Cohere2MoeConfig, dtype):
    """The attention sub-block after its context: `a [T, heads, D]` -> `[T,
    H]` in `dtype`."""
    with _scope("llama.o_proj"):
        return _mm(a.reshape(a.shape[0], cfg.num_attention_heads
                             * cfg.head_dim).astype(dtype),
                   p["self_attn.o_proj.weight"])


def route(h, p, cfg: Cohere2MoeConfig):
    """The router on rows `h [T, H]`: `(experts [T, k] int32, weights [T, k]
    float32)`, float32 at the `highest` matmul precision (a choice flips at
    a near-tie, and the router's own arithmetic should not be what flips
    it: `deepseek_v3.route`)."""
    logits = jnp.dot(h.astype(jnp.float32),
                     p["mlp.gate.weight"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    w, experts = jax.lax.top_k(s, cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return experts.astype(jnp.int32), w


def decoder_layer(x, p, cfg: Cohere2MoeConfig, kind: str, cos, sin, attend,
                  live, rowwise: Callable = None):
    """One decoder layer of `kind` on rows `x [T, H]`: `(x, tokens_per_expert
    [num_experts])`.

    A layer is *segment -> `attend` and the experts -> segment*, and a
    segment maps a row to a row: `rowwise(segment)` may run it over fewer
    rows than `T` (the serving step's live prefix,
    `inference/live_prefix.py`; None: `whole(T)`). `attend`, which owns the
    context, and the experts' grouped matmuls, whose cost follows the
    experts touched, always take the whole packed buffer; the block is
    parallel, so neither waits for the other. An `attend` that is a kernel
    which takes and leaves the buffer as it is carries `attend.pack`, its
    row-wise `prepare`, applied by the first segment to the rows it made,
    and `attend.unpack`, what the second applies to its rows of the
    kernel's own buffer (`deepseek_v3.decoder_layer`); the experts' rows go
    the same way."""
    t = x.shape[0]
    m = t * cfg.num_experts_per_tok
    rowwise = rowwise or whole(t)
    pack, unpack = (getattr(attend, a, None) for a in ("pack", "unpack"))
    with _scope("llama.layer"):
        def before(x, cos, sin, live):
            with _scope("llama.rms_norm"):
                h = layer_norm(x, p["input_layernorm.weight"],
                               cfg.layer_norm_eps)
            q, k, v = qkv(h, p, cfg, kind, cos, sin)
            (xs, *rest), counts = moe_dispatch(h, p, cfg, live, cfg.held,
                                               route)
            xs = expert_rows(xs, p["mlp.experts.gate_proj.weight"], m)
            return (h, (pack(q) if pack else q, k, v), xs, *rest), counts

        def after(x, h, a, y, order, keep, weights):
            ffn = moe_combine(h, y, order, keep, weights, p,
                              cfg.num_shared_experts)
            a = unpack(a) if unpack else a
            return x + o_proj(a, p, cfg, x.dtype) + ffn, None

        (h, (q, k, v), xs, order, keep, weights), (mine, sizes) = rowwise(
            before)(x, cos, sin, live)
        x, _ = rowwise(after)(x, h, attend(q, k, v),
                              moe_experts(xs, mine, p, rowwise, m), order,
                              keep, weights)
        return x, sizes


def head(x, params, cfg: Cohere2MoeConfig):
    """Final LayerNorm and the tied head: float32 logits `[T, V]`."""
    with _scope("llama.rms_norm"):
        x = layer_norm(x, params["model.norm.weight"], cfg.layer_norm_eps)
    with _scope("llama.head"):
        logits = jnp.einsum(
            "tk,vk->tv", x, params["model.embed_tokens.weight"].astype(x.dtype),
            preferred_element_type=jnp.float32)
        return logits if cfg.logit_scale == 1 else logits * cfg.logit_scale


def dense_attend(cfg: Cohere2MoeConfig, kind: str):
    """`attend` for one whole sequence in flight and no cache."""
    def attend(q, k, v):
        t, kvh = k.shape[:2]
        g = q.shape[1] // kvh
        qg = q.reshape(t, kvh, g, -1).astype(jnp.float32)
        s = jnp.einsum("thgd,shd->hgts", qg, k.astype(jnp.float32)) \
            * cfg.head_dim ** -0.5
        i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
        see = j <= i
        if kind == SLIDING:
            see &= j > i - cfg.sliding_window
        a = jnp.einsum("hgts,shd->thgd",
                       jax.nn.softmax(jnp.where(see, s, -jnp.inf), axis=-1),
                       v.astype(jnp.float32))
        return a.reshape(q.shape)
    return attend


def model_forward(params, ids, cfg: Cohere2MoeConfig):
    """ids `[S]` -> float32 logits `[S, V]`: one sequence, no cache."""
    s = ids.shape[0]
    cos, sin = rope_tables(cfg, s)
    with _scope("llama.embed"):
        x = jnp.take(params["model.embed_tokens.weight"], ids, axis=0)
    live = jnp.ones((s,), bool)
    for i, kind in enumerate(cfg.layer_types):
        x, _ = decoder_layer(x, layer_params(params, i), cfg, kind, cos, sin,
                             dense_attend(cfg, kind), live)
    return head(x, params, cfg)


class Cohere2MoeForCausalLM(nn.Layer):
    """A thin holder of the weight pytree: every leaf is a `Parameter` under
    its HuggingFace name, and `forward` is `model_forward`. `weights`
    (name -> array, shapes as `param_shapes` gives them) are taken as they
    are, without a copy; without them the pytree is drawn on the device."""

    def __init__(self, config: Cohere2MoeConfig,
                 weights: Optional[Dict[str, jax.Array]] = None,
                 dtype=jnp.float32, seed: int = 0):
        super().__init__()
        self.config = config
        if weights is None:
            weights = init_params(config, seed, dtype)
        want = {k: tuple(s) for k, (s, _) in param_shapes(config).items()}
        have = {k: tuple(v.shape) for k, v in weights.items()}
        if have != want:
            raise ValueError(
                "cohere2_moe: the weights are not this configuration's: "
                f"{sorted(set(have.items()) ^ set(want.items()))[:8]}")
        for name, w in weights.items():
            self.add_parameter(name, Parameter(w, trainable=False, name=name))

    def weight_tree(self) -> Dict[str, jax.Array]:
        """name -> array, by reference."""
        return {k: p._data for k, p in self._parameters.items()}

    def forward(self, input_ids):
        ids = getattr(input_ids, "_data", input_ids)
        ids = jnp.asarray(ids, jnp.int32)
        if ids.ndim == 1:
            return model_forward(self.weight_tree(), ids, self.config)
        return jax.vmap(lambda r: model_forward(self.weight_tree(), r,
                                                self.config))(ids)
