"""Brumby causal LM (`model_type: brumby`; Brumby-14B-Base): the Qwen3-shaped
dense decoder whose attention is replaced by POWER RETENTION of degree 2
(Gelada et al., arXiv:2507.04239), a layer that keeps one fixed-size state a
KV head and sequence instead of keys and values a token.

Written ONCE, as functions over a weight pytree (HuggingFace Qwen3 names,
linear weights `[in, out]`). The serving engine (`inference/brumby_runner.py`)
runs `decoder_layer` with a `retain` that reads and writes its state slots
through the two kernels of `ops/pallas/power_retention.py`;
`BrumbyForCausalLM` is a thin holder of the pytree whose `forward` runs the
same `decoder_layer` with the attention form over the rows in flight.

Equations, per token row x of a layer:

- `h = RMSNorm(x)`; `q = h Wq` (heads x head_dim), `k = h Wk`, `v = h Wv` (kv
  heads x head_dim), no bias; per-head `RMSNorm` of q and k over `head_dim`
  (`q_norm`, `k_norm`), then RoPE on both (split halves), as in the Qwen3
  block the model was retrained from.
- the log-gate, one scalar a KV head: `a = logsigmoid(h Wg + b_g)` in
  float32, `Wg [hidden, kv heads]`.
- retention, per KV head, its query heads reading one state: with `G` the
  running sum of `a`, `y_i = sum_{j<=i} w_ij v_j / (sum_{j<=i} w_ij + eps)`,
  `w_ij = exp(G_i - G_j) (q_i . k_j)^2 s^2`; equally the recurrence `S_t =
  e^{a_t} S_{t-1} + phi(k_t) v_t^T`, `z_t = e^{a_t} z_{t-1} + phi(k_t)`, `y_t
  = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)` (`retention_recurrent`), and the
  chunked mix of the two (`retention_chunk`): `ops/pallas/power_retention.py`
  has all three and the layout of `phi`.
- `x <- x + y Wo`; `x <- x + SwiGLU(RMSNorm(x))`; after the last layer
  `logits = RMSNorm(x) W_head` (untied).

Not keys of the published config (`assumed` in the benchmark's file): the
degree, the gate's shape, that q/k norm and RoPE stay, `s`, `eps`, and the
float32 state. Each is a field or a line here.

The norm, the rotation and the SwiGLU are the ones `models/deepseek_v3.py`
wrote for the Llama-shaped block as functions; the rotary table is
`models/llama.py`'s. Device regions keep the one family of names
docs/OBSERVABILITY.md lists (`llama.*`), with `llama.retention` around the
layer's own part.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..nn.parameter import Parameter
from ..ops.pallas import power_retention as pr
from .deepseek_v3 import _mm, layer_params, rms_norm, rope, swiglu
from .llama import _rope_cache

__all__ = ["BrumbyConfig", "BrumbyForCausalLM", "param_shapes", "init_params",
           "decoder_layer", "model_forward", "retention_recurrent",
           "retention_chunk", "half_life_bias", "state_shapes"]

_scope = jax.named_scope
retention_chunk = pr.chunk_form


@dataclasses.dataclass(frozen=True)
class BrumbyConfig:
    """The published `config.json` keys this architecture reads, then what
    the config does not say (the assumed values). Frozen and hashable: it is
    a static argument of the compiled step."""
    vocab_size: int = 151936
    hidden_size: int = 5120
    intermediate_size: int = 17408
    num_hidden_layers: int = 40
    num_attention_heads: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 32768
    retention_degree: int = 2
    retention_scale: Optional[float] = None      # None: head_dim ** -0.5
    retention_eps: float = 1e-6

    @property
    def scale(self) -> float:
        return self.head_dim ** -0.5 if self.retention_scale is None \
            else self.retention_scale

    @classmethod
    def from_hf(cls, cfg: dict) -> "BrumbyConfig":
        """From a published `config.json`. What this implementation does
        not compute is refused here rather than silently dropped."""
        refused = {
            "attention_bias": bool(cfg.get("attention_bias", False)),
            "hidden_act": cfg.get("hidden_act", "silu") != "silu",
            "rope_scaling": cfg.get("rope_scaling") is not None,
            "use_sliding_window": bool(cfg.get("use_sliding_window", False)),
            "tie_word_embeddings": bool(cfg.get("tie_word_embeddings", False)),
            "retention_degree": cfg.get("retention_degree", 2) != 2,
        }
        bad = sorted(k for k, v in refused.items() if v)
        if bad:
            raise ValueError(f"brumby: config keys {bad} ask for a mechanism "
                             "this implementation does not have")
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in cfg.items()
                      if k in names and v is not None})


# --- the weight pytree ---------------------------------------------------------

def layer_shapes(cfg: BrumbyConfig) -> Dict[str, Tuple[tuple, str]]:
    """name -> (shape, "matrix" | "norm" | "bias") of one layer's weights."""
    h, nh, kvh, d = (cfg.hidden_size, cfg.num_attention_heads,
                     cfg.num_key_value_heads, cfg.head_dim)
    im = cfg.intermediate_size
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


def param_shapes(cfg: BrumbyConfig) -> Dict[str, Tuple[tuple, str]]:
    """name -> (shape, kind) of the whole pytree (the head is untied)."""
    out = {"model.embed_tokens.weight":
           ((cfg.vocab_size, cfg.hidden_size), "matrix")}
    for i in range(cfg.num_hidden_layers):
        for k, v in layer_shapes(cfg).items():
            out[f"model.layers.{i}.{k}"] = v
    out["model.norm.weight"] = ((cfg.hidden_size,), "norm")
    out["lm_head.weight"] = ((cfg.hidden_size, cfg.vocab_size), "matrix")
    return out


def half_life_bias(half_lives) -> np.ndarray:
    """The gate bias at which a head forgets half its state in `half_lives`
    tokens (with a zero gate input): `sigmoid(b) = 2 ** (-1 / h)`."""
    keep = np.exp2(-1.0 / np.asarray(half_lives, np.float64))
    return np.log(keep / (1.0 - keep)).astype(np.float32)


def init_params(cfg: BrumbyConfig, seed: int = 0, dtype=jnp.float32,
                std: float = 0.02) -> Dict[str, jax.Array]:
    """A pytree drawn on the device: matrices N(0, std^2), gains 1, the gate
    biases (float32) at half-lives spaced evenly in the logarithm from 64 to
    4,096 tokens over a layer's KV heads: with a zero bias a random gate
    forgets in one token, and no state would ever be carried."""
    key = jax.random.key(seed)
    bias = half_life_bias(np.geomspace(64, 4096, cfg.num_key_value_heads))
    out = {}
    for n, (name, (shape, kind)) in enumerate(sorted(param_shapes(cfg).items())):
        if kind == "norm":
            out[name] = jnp.ones(shape, dtype)
        elif kind == "bias":
            out[name] = jnp.asarray(bias)
        else:
            out[name] = (jax.random.normal(jax.random.fold_in(key, n), shape,
                                           jnp.float32) * std).astype(dtype)
    return out


def state_shapes(cfg: BrumbyConfig, slots: int):
    """`(S, z)` shapes of the retention state, `slots` sequences a layer."""
    kvh, d, o = cfg.num_key_value_heads, cfg.head_dim, pr.n_offsets(cfg.head_dim)
    lead = (cfg.num_hidden_layers, slots, kvh, o)
    return lead + (d, d), lead + (d,)


# --- the blocks ------------------------------------------------------------------

def retention_recurrent(q, k, v, a, S, z, eps):
    """The recurrent form over one sequence and one KV head, token by token:
    q `[n, G, d]` (scaled), k, v `[n, d]`, a `[n]` from the state `S, z`.
    Returns `(y [n, G, d], S, z)`."""
    def step(state, row):
        y, S, z = pr.recurrent_step(*row, *state, eps)
        return (S, z), y
    (S, z), y = jax.lax.scan(step, (S, z), (q, k, v, a))
    return y, S, z


def log_gate(h, p):
    """`a = logsigmoid(h Wg + b_g)`, float32 `[T, kv heads]`: it is summed
    over thousands of tokens before it is exponentiated, so its own
    arithmetic is not what should move it."""
    pre = jnp.dot(h.astype(jnp.float32),
                  p["self_attn.g_proj.weight"].astype(jnp.float32),
                  precision=jax.lax.Precision.HIGHEST)
    return jax.nn.log_sigmoid(pre + p["self_attn.g_proj.bias"].astype(
        jnp.float32))


def decoder_layer(x, p, cfg: BrumbyConfig, cos, sin, retain: Callable):
    """One decoder layer on rows `x [T, H]`. `retain(q [T, KV, G, d] (scaled),
    k, v [T, KV, d], a [T, KV]) -> [T, KV, G, d]` owns the context: the
    engine's reads and writes its state slots, `dense_retain` is the
    attention form over one whole sequence."""
    t = x.shape[0]
    nh, kvh, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    with _scope("llama.layer"):
        with _scope("llama.rms_norm"):
            h = rms_norm(x, p["input_layernorm.weight"], cfg.rms_norm_eps)
        with _scope("llama.qkv"):
            q = _mm(h, p["self_attn.q_proj.weight"]).reshape(t, nh, d)
            k = _mm(h, p["self_attn.k_proj.weight"]).reshape(t, kvh, d)
            v = _mm(h, p["self_attn.v_proj.weight"]).reshape(t, kvh, d)
        with _scope("llama.rms_norm"):
            q = rms_norm(q, p["self_attn.q_norm.weight"], cfg.rms_norm_eps)
            k = rms_norm(k, p["self_attn.k_norm.weight"], cfg.rms_norm_eps)
        with _scope("llama.rope"):
            q, k = rope(q, cos, sin, False), rope(k, cos, sin, False)
        with _scope("llama.retention"):
            with _scope("llama.retention_gate"):
                a = log_gate(h, p)
            y = retain(q.reshape(t, kvh, nh // kvh, d).astype(jnp.float32)
                       * cfg.scale, k, v, a)
        with _scope("llama.o_proj"):
            x = x + _mm(y.reshape(t, nh * d).astype(x.dtype),
                        p["self_attn.o_proj.weight"])
        with _scope("llama.rms_norm"):
            h = rms_norm(x, p["post_attention_layernorm.weight"],
                         cfg.rms_norm_eps)
        with _scope("llama.mlp"):
            return x + swiglu(h, p["mlp.gate_proj.weight"],
                              p["mlp.up_proj.weight"],
                              p["mlp.down_proj.weight"])


def head(x, params, cfg: BrumbyConfig):
    """Final norm and the untied output head: float32 logits `[T, V]`."""
    with _scope("llama.rms_norm"):
        x = rms_norm(x, params["model.norm.weight"], cfg.rms_norm_eps)
    with _scope("llama.head"):
        return jnp.einsum("tk,kn->tn", x,
                          params["lm_head.weight"].astype(x.dtype),
                          preferred_element_type=jnp.float32)


def rope_tables(cfg: BrumbyConfig):
    """cos, sin `[max positions, head_dim / 2]` float32 (`models/llama.py`'s
    table at this model's theta)."""
    cos, sin = _rope_cache(cfg)
    return jnp.asarray(cos), jnp.asarray(sin)


def dense_retain(cfg: BrumbyConfig):
    """`retain` for one whole sequence in flight and no state: the chunked
    form from a zero state, which is the attention form."""
    S, z = (jnp.zeros(s[2:], jnp.float32) for s in state_shapes(cfg, 1))

    def retain(q, k, v, a):
        heads = jax.vmap(lambda q, k, v, a, S, z: retention_chunk(
            q, k, v, a, S, z, cfg.retention_eps)[0], in_axes=(1, 1, 1, 1, 0, 0),
            out_axes=1)
        return heads(q, k, v, a, S, z)
    return retain


def model_forward(params, ids, cfg: BrumbyConfig):
    """ids `[S]` -> float32 logits `[S, V]`: one sequence, no state kept."""
    s = ids.shape[0]
    cos, sin = (t[:s] for t in rope_tables(cfg))
    with _scope("llama.embed"):
        x = jnp.take(params["model.embed_tokens.weight"], ids, axis=0)
    retain = dense_retain(cfg)
    for i in range(cfg.num_hidden_layers):
        x = decoder_layer(x, layer_params(params, i), cfg, cos, sin, retain)
    return head(x, params, cfg)


class BrumbyForCausalLM(nn.Layer):
    """A thin holder of the weight pytree: every leaf is a `Parameter` under
    its HuggingFace name, and `forward` is `model_forward`. `weights` (name
    -> array, shapes as `param_shapes` gives them) are taken as they are,
    without a copy; without them the pytree is drawn on the device."""

    def __init__(self, config: BrumbyConfig,
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
                "brumby: the weights are not this configuration's: "
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
