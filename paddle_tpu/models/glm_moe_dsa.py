"""GLM-MoE-DSA causal LM (`model_type: glm_moe_dsa`): the DeepSeek-V3 block
(latent attention, a sigmoid-routed mixture of experts with a shared expert)
with a q-LoRA and LEARNED SPARSE ATTENTION: a token attends only the
`index_topk` positions a small indexer scores highest, and a layer without an
indexer of its own reuses the set of the nearest layer before it that has one.

Written as `models/deepseek_v3.py` is, over the same weight pytree (HuggingFace
names, linear weights `[in, out]`, the HELD routed experts stacked `[held, in,
out]`), and out of its blocks: `mla_query` (given the queries this file's
q-LoRA makes), `mla_output`, `moe_dispatch(held=)`, `moe_experts`,
`moe_combine`, `rope`, `rms_norm`, `swiglu`. What is this file's: the
indexer, the layer types, the layer that carries a selection, and the dense
selecting context of the thin model holder. The serving engine
(`inference/glm_moe_dsa_runner.py`) runs `decoder_layer` with an `index` and
an `attend` over its two paged pools.

Equations, per token row x at position t (published `glm_moe_dsa` keys; the
indexer is the published DeepSeek-V3.2 one):

- MLA as `deepseek_v3.py` states it, with `c_q = RMSNorm(x W_qa)`, `q = c_q
  W_qb`, `v_head_dim != qk_nope_head_dim`, scale `(nope + rope)^-0.5`.
- A `full` layer's indexer: `q_I = c_q W_Iq -> [index_n_heads,
  index_head_dim]`; `k_I = LayerNorm(x W_Ik)` (weight and bias, eps 1e-6);
  RoPE (the attention's tables, interleaved pairs) on the FIRST
  `qk_rope_head_dim` numbers of every `q_I` head and of `k_I`; `w = x W_Iw`;
  `I[t, s] = heads^-0.5 dim^-0.5 sum_h w[t, h] ReLU(q_I[t, h] . k_I[s])`
  for `s <= t`, float32; `S_t` = the `min(index_topk, t + 1)` positions of
  largest `I[t, .]`, ties to the lower position (`lax.top_k`'s order).
- A `shared` layer has no indexer weights and uses the `S_t` of the nearest
  `full` layer before it. Every layer's attention is the softmax over `S_t`
  alone; for `t < index_topk` that is plain causal attention.
- Expert layers: `deepseek_v3.py`'s, told which experts are held
  (`held_experts = (first, count)` of a router `n_routed_experts` wide).

Device regions keep the one family of names (`llama.*`): `llama.dsa_index`
around the indexer, `llama.dsa_index_q` its projections, and the engine's
`llama.dsa_index_write`, `llama.dsa_index_scores`, `llama.dsa_topk`,
`llama.attn_sparse` (docs/OBSERVABILITY.md).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..nn.parameter import Parameter
from . import deepseek_v3 as dsv3

__all__ = ["GlmMoeDsaConfig", "GlmMoeDsaForCausalLM", "param_shapes",
           "init_params", "decoder_layer", "model_forward", "index_inputs",
           "index_scores", "select", "FULL", "SHARED"]

_scope = jax.named_scope
FULL, SHARED = "full", "shared"
K_NORM_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class GlmMoeDsaConfig(dsv3.DeepseekV3Config):
    """The published `config.json` keys this architecture reads, beside
    `DeepseekV3Config`'s, and the chip's share of each expert layer. Frozen
    and hashable: it is a static argument of the compiled step."""
    q_lora_rank: int = 2048
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    indexer_types: Tuple[str, ...] = ()
    indexer_rope_interleave: bool = True
    rms_norm_eps: float = 1e-5
    held_experts: Optional[Tuple[int, int]] = None

    @property
    def held(self) -> Tuple[int, int]:
        return self.held_experts or (0, self.n_routed_experts)

    @property
    def full_layers(self) -> Tuple[int, ...]:
        """The layers that carry an indexer, in order: a `full` layer's
        place here is its layer index in the indexer's cache."""
        return tuple(i for i, k in enumerate(self.indexer_types)
                     if k == FULL)

    @classmethod
    def from_hf(cls, cfg: dict, held_experts=None) -> "GlmMoeDsaConfig":
        """From a published `config.json`. What this implementation does
        not compute is refused here rather than silently dropped."""
        types = tuple(cfg.get("indexer_types") or ())
        types = types[:cfg["num_hidden_layers"]]
        refused = {
            "q_lora_rank": cfg.get("q_lora_rank") is None,
            "rope_scaling": cfg.get("rope_scaling") is not None,
            "n_group": cfg.get("n_group", 1) != 1,
            "topk_group": cfg.get("topk_group", 1) != 1,
            "scoring_func": cfg.get("scoring_func", "sigmoid") != "sigmoid",
            "attention_bias": bool(cfg.get("attention_bias", False)),
            "tie_word_embeddings": bool(cfg.get("tie_word_embeddings", False)),
            "moe_layer_freq": cfg.get("moe_layer_freq", 1) != 1,
            "hidden_act": cfg.get("hidden_act", "silu") != "silu",
            "indexer_types": (len(types) != cfg["num_hidden_layers"]
                              or not types or types[0] != FULL
                              or set(types) - {FULL, SHARED}),
            "index_topk_pattern": cfg.get("index_topk_pattern") is not None,
        }
        bad = sorted(k for k, v in refused.items() if v)
        if bad:
            raise ValueError(
                f"glm_moe_dsa: config keys {bad} ask for a mechanism this "
                "implementation does not have")
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in cfg.items() if k in names}
        kw["indexer_types"] = types
        rope = cfg.get("rope_parameters") or {}
        if "rope_theta" in rope:
            kw["rope_theta"] = rope["rope_theta"]
        if held_experts is not None:
            first, count = held_experts
            if not (0 <= first and count > 0
                    and first + count <= kw["n_routed_experts"]):
                raise ValueError(f"glm_moe_dsa: held experts {held_experts} "
                                 f"of {kw['n_routed_experts']}")
            kw["held_experts"] = (int(first), int(count))
        return cls(**kw)


# --- the weight pytree ---------------------------------------------------------

def layer_shapes(cfg: GlmMoeDsaConfig, i: int) -> Dict[str, Tuple[tuple, str]]:
    """name -> (shape, "matrix" | "norm") of layer `i`'s weights."""
    h, nh, ql = cfg.hidden_size, cfg.num_attention_heads, cfg.q_lora_rank
    out = dsv3.layer_shapes(cfg, i)
    del out["self_attn.q_proj.weight"]
    out.update({
        "self_attn.q_a_proj.weight": ((h, ql), "matrix"),
        "self_attn.q_a_layernorm.weight": ((ql,), "norm"),
        "self_attn.q_b_proj.weight": ((ql, nh * cfg.qk_head_dim), "matrix"),
    })
    if cfg.indexer_types[i] == FULL:
        hi, di = cfg.index_n_heads, cfg.index_head_dim
        out.update({
            "self_attn.indexer.wq_b.weight": ((ql, hi * di), "matrix"),
            "self_attn.indexer.wk.weight": ((h, di), "matrix"),
            "self_attn.indexer.k_norm.weight": ((di,), "norm"),
            "self_attn.indexer.k_norm.bias": ((di,), "matrix"),
            "self_attn.indexer.weights_proj.weight": ((h, hi), "matrix"),
        })
    if "mlp.experts.gate_proj.weight" in out:
        held, im = cfg.held[1], cfg.moe_intermediate_size
        out.update({
            "mlp.experts.gate_proj.weight": ((held, h, im), "matrix"),
            "mlp.experts.up_proj.weight": ((held, h, im), "matrix"),
            "mlp.experts.down_proj.weight": ((held, im, h), "matrix"),
        })
    return out


def param_shapes(cfg: GlmMoeDsaConfig) -> Dict[str, Tuple[tuple, str]]:
    """name -> (shape, kind) of the whole pytree."""
    out = {"model.embed_tokens.weight":
           ((cfg.vocab_size, cfg.hidden_size), "matrix")}
    for i in range(cfg.num_hidden_layers):
        for k, v in layer_shapes(cfg, i).items():
            out[f"model.layers.{i}.{k}"] = v
    out["model.norm.weight"] = ((cfg.hidden_size,), "norm")
    out["lm_head.weight"] = ((cfg.hidden_size, cfg.vocab_size), "matrix")
    return out


def init_params(cfg: GlmMoeDsaConfig, seed: int = 0, dtype=jnp.float32,
                std: float = 0.02) -> Dict[str, jax.Array]:
    """A pytree drawn on the device: matrices N(0, std^2), gains 1."""
    return dsv3.draw_params(param_shapes(cfg), seed, dtype, std)


# --- the indexer ------------------------------------------------------------------

def _layer_norm(x, w, b, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def _rope_head(x, cos, sin, rd, interleave):
    """RoPE on the first `rd` numbers of the last axis."""
    return jnp.concatenate(
        [dsv3.rope(x[..., :rd], cos, sin, interleave), x[..., rd:]], axis=-1)


def index_inputs(h, c_q, p, cfg: GlmMoeDsaConfig, cos, sin):
    """A `full` layer's indexer up to its context, on normed rows `h [T, H]`
    and their `c_q [T, q_lora_rank]`: `(q_i [T, heads, dim], k_i [T, dim],
    w [T, heads] float32)`, the queries, this step's index-cache rows and
    the head weights with both scales folded in. A row to a row."""
    t = h.shape[0]
    hi, di, rd = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    il = cfg.indexer_rope_interleave
    with _scope("llama.dsa_index_q"):
        q = dsv3._mm(c_q, p["self_attn.indexer.wq_b.weight"]).reshape(t, hi, di)
        k = _layer_norm(dsv3._mm(h, p["self_attn.indexer.wk.weight"]),
                        p["self_attn.indexer.k_norm.weight"],
                        p["self_attn.indexer.k_norm.bias"], K_NORM_EPS)
        q = _rope_head(q, cos, sin, rd, il)
        k = _rope_head(k, cos, sin, rd, il)
        w = dsv3._mm(h, p["self_attn.indexer.weights_proj.weight"]) \
            .astype(jnp.float32) * (hi ** -0.5 * di ** -0.5)
    return q, k, w


def index_scores(q_i, w, k_i):
    """`I [T, S]` float32 of queries `q_i [T, heads, dim]`, head weights `w
    [T, heads]` against keys `k_i [S, dim]`: `sum_h w ReLU(q . k)`, the
    products accumulated in float32. No mask."""
    s = jnp.einsum("thd,sd->ths", q_i, k_i.astype(q_i.dtype),
                   preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s) * w[:, :, None], axis=1)


def select(scores, pos, k: int):
    """The selection of rows whose scores over positions `0 .. S - 1` are
    `scores [T, S]` float32 and whose own positions are `pos [T]` (negative:
    a guard row): `(idx [T, k] int32, n [T] int32)`, the `n = min(k, pos +
    1)` causal positions of largest score first in `idx`, ties to the lower
    position; what follows them in `idx` is not a selection."""
    s = scores.shape[1]
    k = min(k, s)
    causal = jnp.arange(s, dtype=jnp.int32)[None, :] <= pos[:, None]
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), k)
    return idx.astype(jnp.int32), jnp.clip(pos + 1, 0, k).astype(jnp.int32)


# --- the layer ----------------------------------------------------------------------

def decoder_layer(x, p, cfg: GlmMoeDsaConfig, kind: str, cos, sin, index,
                  attend, carried, live, rowwise: Callable = None):
    """One decoder layer on rows `x [T, H]` (`p`: the layer's weights by
    their names under `model.layers.<i>.`). Returns `(x, tokens_per_expert
    [E] | None, selection)`.

    `index(q_i, k_i, w) -> selection` and `attend(q_abs, rows, selection)
    -> o_lat [T, heads, rank]` own the context: `index` (a `full` layer's)
    stores the index rows `k_i` and picks each row's positions, `attend`
    stores `rows` and answers each query over its row's selection alone. A
    `shared` layer hands `carried`, the selection of the `full` layer
    before it, to `attend` and on. What a selection is made of is theirs.
    The rest is `deepseek_v3.decoder_layer`: row-wise segments that
    `rowwise` may run over fewer rows, and the held experts' grouped
    matmuls. An `index` that scores with a kernel which takes the packed
    buffers as they are carries `index.pack(q_i, w) -> (q_i, w)`, that
    kernel's row-wise `prepare`, applied by the query segment to the rows
    it made."""
    eps = cfg.rms_norm_eps
    t = x.shape[0]
    m = t * cfg.num_experts_per_tok
    rowwise = rowwise or dsv3.whole(t)
    pack = getattr(index, "pack", None)
    with _scope("llama.layer"):
        def query(x, cos, sin):
            with _scope("llama.rms_norm"):
                h = dsv3.rms_norm(x, p["input_layernorm.weight"], eps)
            with _scope("llama.mla_q"):
                c_q = dsv3.rms_norm(
                    dsv3._mm(h, p["self_attn.q_a_proj.weight"]),
                    p["self_attn.q_a_layernorm.weight"], eps)
                q = dsv3._mm(c_q, p["self_attn.q_b_proj.weight"])
            q_abs, rows = dsv3.mla_query(h, p, cfg, cos, sin, q)
            if kind != FULL:
                return (q_abs, rows), None
            with _scope("llama.dsa_index"):
                q_i, k_i, w = index_inputs(h, c_q, p, cfg, cos, sin)
                if pack:
                    q_i, w = pack(q_i, w)
            return (q_abs, rows, q_i, k_i, w), None

        def attended(x, o_lat):
            x = x + dsv3.mla_output(o_lat, p, cfg, x.dtype)
            with _scope("llama.rms_norm"):
                return x, dsv3.rms_norm(
                    x, p["post_attention_layernorm.weight"], eps)

        def dense(x, o_lat):
            x, h = attended(x, o_lat)
            with _scope("llama.mlp"):
                return x + dsv3.swiglu(h, p["mlp.gate_proj.weight"],
                                       p["mlp.up_proj.weight"],
                                       p["mlp.down_proj.weight"]), None

        def routed(x, o_lat, live):
            x, h = attended(x, o_lat)
            (xs, *rest), counts = dsv3.moe_dispatch(h, p, cfg, live, cfg.held)
            xs = dsv3.expert_rows(xs, p["mlp.experts.gate_proj.weight"], m)
            return (x, h, xs, *rest), counts

        def combined(x, h, y, order, keep, weights):
            return x + dsv3.moe_combine(h, y, order, keep, weights, p), None

        (q_abs, rows, *indexer), _ = rowwise(query)(x, cos, sin)
        if kind == FULL:
            with _scope("llama.dsa_index"):
                carried = index(*indexer)
        o_lat = attend(q_abs, rows, carried)
        if "mlp.gate.weight" not in p:
            x, _ = rowwise(dense)(x, o_lat)
            return x, None, carried
        (x, h, xs, order, keep, weights), (mine, sizes) = rowwise(routed)(
            x, o_lat, live)
        x, _ = rowwise(combined)(
            x, h, dsv3.moe_experts(xs, mine, p, rowwise, m), order, keep,
            weights)
        return x, sizes, carried


head = dsv3.head


def dense_context(cfg: GlmMoeDsaConfig):
    """`(index, attend)` for one whole sequence in flight and no cache:
    token t scores rows 0..t and attends the rows it selects."""
    def index(q_i, k_i, w):
        t = k_i.shape[0]
        return select(index_scores(q_i, w, k_i),
                      jnp.arange(t, dtype=jnp.int32), cfg.index_topk)

    def attend(q_abs, rows, selection):
        idx, n = selection
        got = jnp.take(rows.astype(jnp.float32), idx, axis=0)   # [T, k, D]
        s = jnp.einsum("thd,tkd->thk", q_abs.astype(jnp.float32),
                       got) * cfg.qk_head_dim ** -0.5
        chosen = jnp.arange(idx.shape[1])[None, :] < n[:, None]
        s = jnp.where(chosen[:, None, :], s, -jnp.inf)
        return jnp.einsum("thk,tkc->thc", jax.nn.softmax(s, axis=-1),
                          got[..., :cfg.kv_lora_rank])
    return index, attend


def model_forward(params, ids, cfg: GlmMoeDsaConfig):
    """ids `[S]` -> float32 logits `[S, V]`: one sequence, no cache."""
    s = ids.shape[0]
    cos, sin = dsv3.rope_tables(cfg, s)
    with _scope("llama.embed"):
        x = jnp.take(params["model.embed_tokens.weight"], ids, axis=0)
    live = jnp.ones((s,), bool)
    index, attend = dense_context(cfg)
    carried = None
    for i, kind in enumerate(cfg.indexer_types):
        x, _, carried = decoder_layer(x, dsv3.layer_params(params, i), cfg,
                                      kind, cos, sin, index, attend, carried,
                                      live)
    return head(x, params, cfg)


class GlmMoeDsaForCausalLM(nn.Layer):
    """The thin holder of the weight pytree, as `DeepseekV3ForCausalLM` is
    one: every leaf a `Parameter` under its HuggingFace name, `forward` is
    `model_forward`. `weights` (shapes as `param_shapes` gives them) are
    taken as they are, without a copy; without them the pytree is drawn on
    the device."""

    def __init__(self, config: GlmMoeDsaConfig,
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
                "glm_moe_dsa: the weights are not this configuration's: "
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
