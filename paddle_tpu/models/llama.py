"""Llama-family causal LM — the flagship model (BASELINE config 3).

Mirrors the reference's CI Llama workload
(`test/auto_parallel/hybrid_strategy/semi_auto_llama.py:31-48`: hidden 4096,
intermediate 11008, 32 heads, seq 2048) built from this framework's layers:
RMSNorm + rotary attention (GQA) + SwiGLU MLP. Attention rides
`F.scaled_dot_product_attention` (Pallas flash path on TPU when available).

TPU-first choices: bf16 weights with f32 RMSNorm accumulation, static shapes
throughout, rotary cache precomputed as buffers, no data-dependent control flow —
the whole step compiles to one XLA program via `paddle_tpu.jit.functional_call`.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import numpy as np

from .. import nn
from ..core import dispatch
from ..core.tensor import Tensor
from ..nn import functional as F

__all__ = ["LlamaConfig", "LlamaAttention", "LlamaMLP", "LlamaDecoderLayer",
           "LlamaModel", "LlamaForCausalLM", "llama_tiny", "llama_7b_shaped"]


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: Optional[int] = None  # GQA; None -> MHA
    max_position_embeddings: int = 2048
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    dtype: str = "float32"

    def __post_init__(self):
        if self.num_key_value_heads is None:
            self.num_key_value_heads = self.num_attention_heads

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads


def _rope_cache(config: LlamaConfig):
    dim = config.head_dim
    inv_freq = 1.0 / (config.rope_theta **
                      (np.arange(0, dim, 2, dtype=np.float64) / dim))
    t = np.arange(config.max_position_embeddings, dtype=np.float64)
    freqs = np.outer(t, inv_freq)  # [T, dim/2]
    return np.cos(freqs).astype("float32"), np.sin(freqs).astype("float32")


def _apply_rope_fn(q, k, cos, sin, offset):
    """q/k: [B, S, H, D]; cos/sin: [T, D/2]. Rotates pairs (x[..., :D/2], x[..., D/2:])."""
    import jax.numpy as jnp

    s = q.shape[1]
    c = jnp.expand_dims(cos[offset:offset + s], (0, 2))  # [1, S, 1, D/2]
    si = jnp.expand_dims(sin[offset:offset + s], (0, 2))
    c = c.astype(q.dtype)
    si = si.astype(q.dtype)

    def rot(x):
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * c - x2 * si, x2 * c + x1 * si], axis=-1)

    return rot(q), rot(k)


dispatch.register_op("fused_rope", _apply_rope_fn, multi_out=True)


def _scope(name: str):
    """A program region's `jax.named_scope`. Trace-time only: the name
    lands in the scope path of every op lowered under it, which is how a
    device trace is read by region. The names are a contract, the same in
    serving (`inference/llama_runner.py`): docs/OBSERVABILITY.md."""
    import jax

    return jax.named_scope(name)


def fused_rotary_position_embedding(q, k, cos, sin, offset=0):
    """Analog of `incubate.nn.functional.fused_rotary_position_embedding`
    (reference kernel `phi/kernels/fusion/gpu/fused_rope_kernel.cu`)."""
    return dispatch.apply("fused_rope", [q, k, cos, sin],
                          {"offset": int(offset)})


class LlamaAttention(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        h = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = config.head_dim
        self.q_proj = nn.Linear(h, self.num_heads * self.head_dim,
                                bias_attr=False)
        self.k_proj = nn.Linear(h, self.num_kv_heads * self.head_dim,
                                bias_attr=False)
        self.v_proj = nn.Linear(h, self.num_kv_heads * self.head_dim,
                                bias_attr=False)
        self.o_proj = nn.Linear(self.num_heads * self.head_dim, h,
                                bias_attr=False)
        cos, sin = _rope_cache(config)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)

    def forward(self, x, position_offset=0, kv_cache=None):
        from ..ops import manipulation as M

        b, s = x.shape[0], x.shape[1]
        with _scope("llama.qkv"):
            q = M.reshape(self.q_proj(x),
                          [b, s, self.num_heads, self.head_dim])
            k = M.reshape(self.k_proj(x),
                          [b, s, self.num_kv_heads, self.head_dim])
            v = M.reshape(self.v_proj(x),
                          [b, s, self.num_kv_heads, self.head_dim])
        with _scope("llama.rope"):
            q, k = fused_rotary_position_embedding(q, k, self.rope_cos,
                                                   self.rope_sin,
                                                   offset=position_offset)
        new_cache = None
        if kv_cache is not None:
            pk, pv = kv_cache
            if pk is not None:
                k = M.concat([pk, k], axis=1)
                v = M.concat([pv, v], axis=1)
            new_cache = (k, v)
        # GQA K/V stay un-repeated: the Pallas flash path groups natively;
        # the sdpa fallback expands inside _sdpa_fn.
        causal = kv_cache is None or q.shape[1] > 1
        with _scope("llama.attn"):
            out = F.scaled_dot_product_attention(q, k, v, is_causal=causal)
            out = M.reshape(out, [b, s, self.num_heads * self.head_dim])
        with _scope("llama.o_proj"):
            out = self.o_proj(out)
        if kv_cache is not None:
            return out, new_cache
        return out


class LlamaMLP(nn.Layer):
    """SwiGLU MLP (reference fused path: `incubate.nn.functional.swiglu`)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        h, inter = config.hidden_size, config.intermediate_size
        self.gate_proj = nn.Linear(h, inter, bias_attr=False)
        self.up_proj = nn.Linear(h, inter, bias_attr=False)
        self.down_proj = nn.Linear(inter, h, bias_attr=False)

    def forward(self, x):
        from ..ops.activation import swiglu

        return self.down_proj(swiglu(self.gate_proj(x), self.up_proj(x)))


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.self_attn = LlamaAttention(config)
        self.mlp = LlamaMLP(config)
        self.input_layernorm = nn.RMSNorm(config.hidden_size,
                                          epsilon=config.rms_norm_eps)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size,
                                                   epsilon=config.rms_norm_eps)

    def forward(self, x, position_offset=0, kv_cache=None):
        residual = x
        with _scope("llama.rms_norm"):
            h = self.input_layernorm(x)
        if kv_cache is not None:
            attn, new_cache = self.self_attn(h, position_offset, kv_cache)
        else:
            attn = self.self_attn(h, position_offset)
        with _scope("llama.o_proj"):
            x = residual + attn
        with _scope("llama.rms_norm"):
            h = self.post_attention_layernorm(x)
        with _scope("llama.mlp"):
            x = x + self.mlp(h)
        if kv_cache is not None:
            return x, new_cache
        return x


class LlamaModel(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size, config.hidden_size)
        self.layers = nn.LayerList(
            [LlamaDecoderLayer(config) for _ in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        # When True and running under jax tracing (graph mode), each decoder
        # layer is wrapped in jax.checkpoint so activations are rematerialised
        # in backward — the HBM/FLOPs trade that lets full 7B layer shapes
        # train on one chip (SURVEY.md §7.1; ref analog: fleet recompute).
        self.remat = False

    def forward(self, input_ids, position_offset=0, kv_caches=None):
        with _scope("llama.embed"):
            x = self.embed_tokens(input_ids)
        new_caches = []
        use_remat = (self.remat and kv_caches is None
                     and dispatch._is_tracer(x._data))
        for i, layer in enumerate(self.layers):
            with _scope("llama.layer"):
                if kv_caches is not None:
                    x, c = layer(x, position_offset, kv_caches[i])
                    new_caches.append(c)
                elif use_remat:
                    import jax

                    def _call(xa, _layer=layer):
                        return _layer(Tensor(xa), position_offset)._data

                    x = Tensor(jax.checkpoint(_call)(x._data),
                               stop_gradient=x.stop_gradient)
                else:
                    x = layer(x, position_offset)
        with _scope("llama.rms_norm"):
            x = self.norm(x)
        if kv_caches is not None:
            return x, new_caches
        return x


class LlamaForCausalLM(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                     bias_attr=False)

    def forward(self, input_ids, labels=None, position_offset=0,
                kv_caches=None):
        if kv_caches is not None:
            hidden, caches = self.llama(input_ids, position_offset, kv_caches)
        else:
            hidden = self.llama(input_ids, position_offset)
        with _scope("llama.head"):
            if self.lm_head is None:
                from ..ops import linalg

                logits = linalg.matmul(hidden,
                                       self.llama.embed_tokens.weight,
                                       transpose_y=True)
            else:
                logits = self.lm_head(hidden)
        if labels is not None:
            from ..ops import manipulation as M

            with _scope("llama.loss"):
                loss = F.cross_entropy(
                    M.reshape(logits, [-1, self.config.vocab_size]),
                    M.reshape(labels, [-1]))
            return loss, logits
        if kv_caches is not None:
            return logits, caches
        return logits

    def pipeline_parts(self):
        """Decompose for the compiled pipeline (`scan_pipeline` /
        `pipeline_train_step` / auto-parallel Engine pp): returns
        ``(first_fn, first_params, block_fn, layer_params, last_fn,
        last_params)`` where `block_fn(params, x)` runs ONE decoder layer
        functionally (identical math to eager forward via functional_call)
        and `layer_params` is the per-layer param-dict list. Embedding and
        norm+head stay outside the pipeline stages (replicated), matching
        the homogeneous-stage contract."""
        import jax
        import jax.numpy as jnp

        from ..jit.functional import buffer_arrays, functional_call, state_arrays

        template = self.llama.layers[0]
        buffers = dict(buffer_arrays(template))
        layer_params = [dict(sorted(state_arrays(l).items()))
                        for l in self.llama.layers]

        def block_fn(params, x):
            out = functional_call(template, params, Tensor(x),
                                  buffers=buffers)
            return out._data

        first_params = {"embed": self.llama.embed_tokens.weight._data}

        def first_fn(p, ids):
            return jnp.take(p["embed"], ids, axis=0)

        tied = self.lm_head is None
        norm_layer = self.llama.norm
        last_params = {"norm": self.llama.norm.weight._data,
                       "head": (first_params["embed"] if tied
                                else self.lm_head.weight._data)}

        def last_fn(p, x):
            # reuse nn.RMSNorm via functional_call so the pipelined math
            # cannot drift from the eager model's
            h = functional_call(norm_layer, {"weight": p["norm"]},
                                Tensor(x))._data
            if tied:
                return jnp.einsum("...h,vh->...v", h, p["head"])
            return jnp.einsum("...h,hv->...v", h, p["head"])

        # NOTE tied embeddings: first_params["embed"] and last_params["head"]
        # are independent leaves to value_and_grad — the tied weight's total
        # gradient is g_first["embed"] + g_last["head"].T-free sum (both are
        # [V, H]); callers (Engine pp path) must combine them.
        return (first_fn, first_params, block_fn, layer_params, last_fn,
                last_params)

    def pipeline_block_modules(self):
        """The per-block modules behind pipeline_parts() (Engine uses their
        DistMeta annotations to shard the stacked pipeline weights)."""
        return list(self.llama.layers)

    def flops_per_token(self, seq_len: int) -> float:
        """Model FLOPs per trained token (fwd+bwd), PaLM-appendix accounting:
        6*N_params + 12*L*H*Q*T attention term."""
        c = self.config
        # 6N counts matmul'd params only: the embedding lookup is a gather,
        # not a matmul. With tied embeddings the same weight IS matmul'd as
        # the output projection, so it stays in the count.
        n_params = sum(int(np.prod(p.shape))
                       for name, p in self.named_parameters()
                       if c.tie_word_embeddings or "embed_tokens" not in name)
        attn = 12 * c.num_hidden_layers * c.hidden_size * seq_len
        return 6 * n_params + attn


def llama_tiny(vocab=256, layers=2, hidden=64, heads=4, seq=64, **kw):
    return LlamaForCausalLM(LlamaConfig(
        vocab_size=vocab, hidden_size=hidden, intermediate_size=hidden * 3,
        num_hidden_layers=layers, num_attention_heads=heads,
        max_position_embeddings=seq, **kw))


def llama_7b_shaped(num_layers=2, **kw):
    """The reference CI config (semi_auto_llama.py:31-48) — 7B shapes, N layers."""
    return LlamaForCausalLM(LlamaConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=11008,
        num_hidden_layers=num_layers, num_attention_heads=32,
        max_position_embeddings=2048, **kw))
