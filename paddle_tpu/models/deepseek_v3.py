"""DeepSeek-V3-architecture causal LM (`model_type: deepseek_v3`): latent
attention (MLA) and a sigmoid-routed mixture of experts with shared experts.

Written ONCE, as functions over a weight pytree (HuggingFace parameter names,
linear weights `[in, out]`, the routed experts stacked on a leading axis:
`mlp.experts.{gate,up,down}_proj.weight [E, in, out]`). The serving engine
(`inference/deepseek_v3_runner.py`) runs `decoder_layer` with an `attend`
that writes and reads its paged latent cache; `DeepseekV3ForCausalLM` is a
thin holder of the pytree whose `forward` runs the same `decoder_layer` with
a dense causal `attend` over the rows in flight. Nothing here is imported by
`paddle_tpu` or by the Llama serving path.

Equations, per token row x (published DeepSeek-V3 ones; `q_lora_rank` null:
`models/glm_moe_dsa.py` makes its q-LoRA's queries and hands them in):

- MLA: `q = x W_q -> [heads, nope + rope]`; `a = x W_kva -> [rank + rope]`,
  `c = RMSNorm(a[:rank])`, `k_rope = a[rank:]` shared by every head; RoPE on
  `q_rope`, `k_rope` (`rope_interleave`: pairs de-interleaved, then the
  rotate-half form). Served in the ABSORBED form: `W_kvb` split by head into
  `W_UK, W_UV`; `q_lat = q_nope W_UK^T`; score `(q_lat . c + q_rope . k_rope)
  * (nope + rope)^-0.5`; `o_lat = softmax . c`; `o = o_lat W_UV`. The cache
  row of a token is `[c | rotated k_rope]`: key, and in its first `rank`
  columns value.
- Expert layer: `s = sigmoid(x W_g)` in float32; top-k of `s + bias`
  (`n_group` 1: no group limit); weights `s[chosen] / (sum + 1e-20) *
  routed_scaling_factor`; routed SwiGLU experts without capacity or drops
  (tokens sorted by expert, a grouped matmul) plus one shared SwiGLU of
  width `n_shared_experts * moe_intermediate_size` on every token. The first
  `first_k_dense_replace` layers carry a dense SwiGLU instead.

Device regions keep the one family of names `benchmark/program_trace.py` and
docs/OBSERVABILITY.md read (`llama.*`), with `llama.mla_*` and `llama.moe*`
for what is new.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..nn.parameter import Parameter
from ..ops.pallas import _support, grouped_matmul

__all__ = ["DeepseekV3Config", "DeepseekV3ForCausalLM", "param_shapes",
           "init_params", "decoder_layer", "model_forward", "rope_tables"]

_scope = jax.named_scope


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    """The published `config.json` keys this architecture reads. Frozen and
    hashable: it is a static argument of the compiled step."""
    vocab_size: int = 129280
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    num_attention_heads: int = 128
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    first_k_dense_replace: int = 3
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_interleave: bool = True
    max_position_embeddings: int = 4096

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """Numbers in one token's cache row of one layer: `[c | k_rope]`."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @classmethod
    def from_hf(cls, cfg: dict) -> "DeepseekV3Config":
        """From a published `config.json`. What this implementation does
        not compute is refused here rather than silently dropped."""
        refused = {
            "q_lora_rank": cfg.get("q_lora_rank") is not None,
            "rope_scaling": cfg.get("rope_scaling") is not None,
            "n_group": cfg.get("n_group", 1) != 1,
            "topk_group": cfg.get("topk_group", 1) != 1,
            "scoring_func": cfg.get("scoring_func", "sigmoid") != "sigmoid",
            "attention_bias": bool(cfg.get("attention_bias", False)),
            "tie_word_embeddings": bool(cfg.get("tie_word_embeddings", False)),
            "moe_layer_freq": cfg.get("moe_layer_freq", 1) != 1,
            "hidden_act": cfg.get("hidden_act", "silu") != "silu",
        }
        bad = sorted(k for k, v in refused.items() if v)
        if bad:
            raise ValueError(
                f"deepseek_v3: config keys {bad} ask for a mechanism this "
                "implementation does not have")
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in cfg.items() if k in names})


# --- the weight pytree ---------------------------------------------------------

def layer_shapes(cfg: DeepseekV3Config, i: int) -> Dict[str, Tuple[tuple, str]]:
    """name -> (shape, "matrix" | "norm") of layer `i`'s weights."""
    h, nh = cfg.hidden_size, cfg.num_attention_heads
    out = {
        "input_layernorm.weight": ((h,), "norm"),
        "self_attn.q_proj.weight": ((h, nh * cfg.qk_head_dim), "matrix"),
        "self_attn.kv_a_proj_with_mqa.weight": ((h, cfg.latent_dim), "matrix"),
        "self_attn.kv_a_layernorm.weight": ((cfg.kv_lora_rank,), "norm"),
        "self_attn.kv_b_proj.weight": (
            (cfg.kv_lora_rank, nh * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            "matrix"),
        "self_attn.o_proj.weight": ((nh * cfg.v_head_dim, h), "matrix"),
        "post_attention_layernorm.weight": ((h,), "norm"),
    }
    if i < cfg.first_k_dense_replace:
        inter = cfg.intermediate_size
        out.update({
            "mlp.gate_proj.weight": ((h, inter), "matrix"),
            "mlp.up_proj.weight": ((h, inter), "matrix"),
            "mlp.down_proj.weight": ((inter, h), "matrix"),
        })
        return out
    e, im = cfg.n_routed_experts, cfg.moe_intermediate_size
    sh = cfg.n_shared_experts * im
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


def param_shapes(cfg: DeepseekV3Config) -> Dict[str, Tuple[tuple, str]]:
    """name -> (shape, kind) of the whole pytree."""
    out = {"model.embed_tokens.weight":
           ((cfg.vocab_size, cfg.hidden_size), "matrix")}
    for i in range(cfg.num_hidden_layers):
        for k, v in layer_shapes(cfg, i).items():
            out[f"model.layers.{i}.{k}"] = v
    out["model.norm.weight"] = ((cfg.hidden_size,), "norm")
    out["lm_head.weight"] = ((cfg.hidden_size, cfg.vocab_size), "matrix")
    return out


def draw_params(shapes: Dict[str, Tuple[tuple, str]], seed: int, dtype,
                std: float) -> Dict[str, jax.Array]:
    """A pytree of `shapes` (name -> (shape, kind)) drawn on the device:
    matrices N(0, std^2), gains 1."""
    key = jax.random.key(seed)
    out = {}
    for n, (name, (shape, kind)) in enumerate(sorted(shapes.items())):
        if kind == "norm":
            out[name] = jnp.ones(shape, dtype)
        else:
            out[name] = (jax.random.normal(jax.random.fold_in(key, n), shape,
                                           jnp.float32) * std).astype(dtype)
    return out


def init_params(cfg: DeepseekV3Config, seed: int = 0, dtype=jnp.float32,
                std: float = 0.02) -> Dict[str, jax.Array]:
    """`draw_params` over this configuration's `param_shapes`."""
    return draw_params(param_shapes(cfg), seed, dtype, std)


def layer_params(params: Dict[str, jax.Array], i: int) -> Dict[str, jax.Array]:
    pre = f"model.layers.{i}."
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


# --- the blocks ------------------------------------------------------------------

def rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def rope_tables(cfg: DeepseekV3Config, positions: int):
    """cos, sin `[positions, rope/2]` float32, from float64 angles."""
    d = cfg.qk_rope_head_dim
    inv = 1.0 / cfg.rope_theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.outer(np.arange(positions, dtype=np.float64), inv)
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def rope(x, cos, sin, interleave: bool):
    """x `[T, ..., rope]`, cos/sin `[T, rope/2]` at the tokens' positions.
    `interleave`: the published layout pairs (x0, x1), (x2, x3), ...; they
    are de-interleaved to (first half, second half) and stay so (q and k
    alike, so their products do not change)."""
    if interleave:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    shape = (cos.shape[0],) + (1,) * (x.ndim - 2) + (cos.shape[1],)
    c, s = cos.reshape(shape), sin.reshape(shape)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


def _mm(x, w):
    return jnp.einsum("...k,kn->...n", x, w.astype(x.dtype))


def swiglu(x, gate_w, up_w, down_w):
    g, u = _mm(x, gate_w), _mm(x, up_w)
    return _mm(jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u, down_w)


def _w_kvb(p, cfg: DeepseekV3Config):
    """`W_kvb` split by head: `[rank, heads, nope + v]`."""
    return p["self_attn.kv_b_proj.weight"].reshape(
        cfg.kv_lora_rank, cfg.num_attention_heads,
        cfg.qk_nope_head_dim + cfg.v_head_dim)


def mla_query(x, p, cfg: DeepseekV3Config, cos, sin, q=None):
    """The attention sub-block before its context, on normed rows `x [T,
    H]`: `(q_abs [T, heads, rank + rope], rows [T, rank + rope])`, the
    absorbed queries and this step's cache rows `[c | rotated k_rope]`.
    They go to the layer's `attend(q_abs, rows) -> o_lat [T, heads, rank]`,
    which owns the context: it stores `rows` and answers each query with
    `softmax(q . rows^T * scale) . rows[:, :rank]` over its token's causal
    context; `mla_output` takes `o_lat` on. Everything in the sub-block but
    `attend` maps a row to a row. `q [T, heads * (nope + rope)]`: the
    queries where the caller has projected them (an architecture with a
    q-LoRA makes them from its `c_q`); else `x W_q`."""
    t = x.shape[0]
    nh, nope, rd = cfg.num_attention_heads, cfg.qk_nope_head_dim, \
        cfg.qk_rope_head_dim
    rank = cfg.kv_lora_rank
    with _scope("llama.mla_q"):
        if q is None:
            q = _mm(x, p["self_attn.q_proj.weight"])
        q = q.reshape(t, nh, nope + rd)
        q_nope, q_rope = q[..., :nope], q[..., nope:]
    with _scope("llama.mla_kv_a"):
        a = _mm(x, p["self_attn.kv_a_proj_with_mqa.weight"])
        c = rms_norm(a[:, :rank], p["self_attn.kv_a_layernorm.weight"],
                     cfg.rms_norm_eps)
        k_rope = a[:, rank:]
    with _scope("llama.rope"):
        q_rope = rope(q_rope, cos, sin, cfg.rope_interleave)
        k_rope = rope(k_rope, cos, sin, cfg.rope_interleave)
    with _scope("llama.mla_absorb"):
        q_lat = jnp.einsum("thn,chn->thc", q_nope,
                           _w_kvb(p, cfg)[..., :nope].astype(x.dtype))
        q_abs = jnp.concatenate([q_lat, q_rope], axis=-1)
    return q_abs, jnp.concatenate([c, k_rope], axis=-1)


def mla_output(o_lat, p, cfg: DeepseekV3Config, dtype):
    """The attention sub-block after its context: `o_lat [T, heads, rank]`
    -> the block's output `[T, H]` in `dtype` (before the residual)."""
    t = o_lat.shape[0]
    with _scope("llama.mla_absorb"):
        o = jnp.einsum(
            "thc,chv->thv", o_lat.astype(dtype),
            _w_kvb(p, cfg)[..., cfg.qk_nope_head_dim:].astype(dtype))
    with _scope("llama.o_proj"):
        return _mm(o.reshape(t, cfg.num_attention_heads * cfg.v_head_dim),
                   p["self_attn.o_proj.weight"])


def route(x, p, cfg: DeepseekV3Config):
    """The router on rows `x [T, H]`: `(experts [T, k] int32, weights [T, k]
    float32)`. Scores, the choice and the weights are float32 at the
    `highest` matmul precision: a choice flips at a near-tie, and the
    router's own arithmetic should not be what flips it."""
    k = cfg.num_experts_per_tok
    logits = jnp.dot(x.astype(jnp.float32),
                     p["mlp.gate.weight"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    bias = p["mlp.gate.e_score_correction_bias"].astype(jnp.float32)
    _, experts = jax.lax.top_k(s + bias, k)
    w = jnp.take_along_axis(s, experts, axis=-1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), w * cfg.routed_scaling_factor


def dispatch(x, experts, live, cfg, held: Optional[Tuple[int, int]] = None):
    """The routed experts' rows, sorted by expert. `x [T, H]`, `experts [T,
    k]`, `live [T]` bool (a row that is not live reaches no expert).
    Returns `(xs [T*k, H], order [T*k], keep [T, k])`, the rows of `x`
    sorted by the expert they go to, `order[s]` the assignment (row * k +
    choice) at sorted place `s`, `keep` the assignments that reach an
    expert held here; and `(mine [count], sizes [E])`, the rows of each
    HELD expert and of each of the router's experts. Dead rows and
    assignments to an absent expert sort last.

    `held = (first, count)`: the layer is told which experts it holds (the
    chip's share under expert parallelism). The router keeps its
    `cfg.n_routed_experts` outputs and its choice; an assignment to an
    expert that is not held is what a dead row is. `sizes` counts held and
    absent alike. None: every expert is held."""
    t, k = experts.shape
    e = cfg.n_routed_experts
    first, count = (0, e) if held is None else held
    with _scope("llama.moe_dispatch"):
        flat = jnp.where(jnp.repeat(live, k), experts.reshape(t * k),
                         jnp.int32(e))                  # dead rows sort last
        sizes = jnp.zeros((e + 1,), jnp.int32).at[flat].add(1)[:e]
        keep = live[:, None]
        if (first, count) == (0, e):
            mine = sizes
        else:
            here = (flat >= first) & (flat < first + count)      # [T*k]
            flat = jnp.where(here, flat - first, jnp.int32(count))
            mine = sizes[first:first + count]
            keep = keep & here.reshape(t, k)
        order = jnp.argsort(flat).astype(jnp.int32)     # [T*k] sorted -> flat
        xs = jnp.take(x, order // k, axis=0)            # [T*k, H]
    return (xs, order, jnp.broadcast_to(keep, (t, k))), (mine, sizes)


def expert_rows(a, w, m: int):
    """Rows `a` (all or the first of `m` sorted assignments) on their way
    to a grouped matmul over `w [E, in, out]`: the kernel's `prepare`
    (its dtype, and the spare rows that make `m` whole row tiles: a
    `Packed`, which a `rowwise` places) where `expert_ffn` will take the
    kernel, else `a`."""
    if grouped_matmul.supported(w.shape, w.dtype):
        return grouped_matmul.prepare(a, w, m)
    return a


def expert_ffn(xs, mine, p, rowwise: Callable = None, m: int = None):
    """The experts' SwiGLUs on rows sorted by expert: three grouped matmuls
    (the kernel `moe_grouped_matmul`, `ops/pallas/grouped_matmul.py`, which
    takes a buffer of whole row tiles in the weights' dtype, `expert_rows`
    placed, as it is; off the TPU `jax.lax.ragged_dot`). `xs [M, H]` or
    that buffer, `mine [count]` rows an expert, their sum at most M; an
    expert with no row is not computed and its matrices are not read, rows
    past the sum cost nothing and hold whatever. The product between the
    matmuls maps a row to a row: a segment of its own, which `rowwise` may
    run over fewer rows (`m`: the assignments of the whole packed buffer,
    where `xs` has spare rows past them). Returns `[M, H]` float32, or the
    buffer's rows. Its cost follows the experts touched, not M."""
    with _scope("llama.moe_experts"):
        def rd(a, w):
            if grouped_matmul.supported(w.shape, w.dtype):
                return grouped_matmul.grouped_matmul(a, w, mine)
            return jax.lax.ragged_dot(a, w.astype(a.dtype), mine,
                                      preferred_element_type=jnp.float32)

        down = p["mlp.experts.down_proj.weight"]

        def gated(g, u):
            act = (jax.nn.silu(g) * u).astype(xs.dtype)
            return expert_rows(act, down, m or act.shape[0]), None

        g = rd(xs, p["mlp.experts.gate_proj.weight"])
        u = rd(xs, p["mlp.experts.up_proj.weight"])
        act, _ = (rowwise or whole(g.shape[0]))(gated)(g, u)
        return rd(act, down)                            # [M, H] float32


def combine(y, order, keep, weights, dtype):
    """Each token's k answers, combined by weight: `y [T*k, H]` sorted as
    `order` says, `keep`, `weights [T, k]` -> `[T, H]` in `dtype`."""
    t, k = weights.shape
    with _scope("llama.moe_combine"):
        back = jnp.zeros((t * k,), jnp.int32).at[order].set(
            jnp.arange(t * k, dtype=jnp.int32))         # flat -> sorted
        y = jnp.take(y, back, axis=0).reshape(t, k, -1)
        # rows past the groups' sum are never written by the grouped
        # matmul: select, never multiply, what a dead row holds
        w = jnp.where(keep, weights, 0.0)[..., None]
        out = jnp.sum(jnp.where(keep[..., None], y, 0.0) * w, axis=1)
    return out.astype(dtype)


def moe_dispatch(x, p, cfg, live, held=None, router: Callable = None):
    """An expert layer's feed-forward up to its experts, on normed rows `x
    [T, H]`: the router (`route`, or the architecture's own) and
    `dispatch`. `((xs, order, keep, weights), (mine, tokens_per_expert))`."""
    with _scope("llama.moe"):
        with _scope("llama.moe_router"):
            experts, weights = (router or route)(x, p, cfg)
        sorted_rows, counts = dispatch(x, experts, live, cfg, held)
    return sorted_rows + (weights,), counts


def moe_experts(xs, mine, p, rowwise: Callable = None, m: int = None):
    """The experts themselves (`expert_ffn`) under the layer's scope."""
    with _scope("llama.moe"):
        return expert_ffn(xs, mine, p, rowwise, m)


def moe_combine(x, y, order, keep, weights, p, mean_of: int = 1):
    """An expert layer's feed-forward after its experts: `combine`, plus the
    shared experts' SwiGLU on `x` (their matrices side by side: their sum,
    or with `mean_of` their mean). `[T, H]`."""
    with _scope("llama.moe"):
        out = combine(y, order, keep, weights, x.dtype)
        with _scope("llama.moe_shared"):
            shared = swiglu(x, p["mlp.shared_experts.gate_proj.weight"],
                            p["mlp.shared_experts.up_proj.weight"],
                            p["mlp.shared_experts.down_proj.weight"])
            if mean_of != 1:
                shared = (shared.astype(jnp.float32)
                          / mean_of).astype(out.dtype)
            return out + shared


def whole(t: int) -> Callable:
    """`rowwise` where every row is live (no cache, a verify window):
    `wrap(fn)` runs `fn` over all `t` token slots of its row arguments
    (less the spare rows a kernel left after them) and places a `Packed`
    row output in its kernel's buffer (`_support.place`)."""
    def wrap(fn):
        def on_all(*rows):
            outs, others = fn(*jax.tree.map(
                lambda a: a[:a.shape[0] // t * t], rows))
            return _support.place(outs, t, t), others
        return on_all
    return wrap


def decoder_layer(x, p, cfg: DeepseekV3Config, cos, sin, attend, live,
                  rowwise: Callable = None):
    """One decoder layer on rows `x [T, H]` (`p`: the layer's weights by
    their names under `model.layers.<i>.`). Returns `(x, tokens_per_expert
    [E] | None)`; None for a dense layer.

    A layer is *segment -> `attend` -> segment* (an expert layer's second
    segment again *-> experts -> segment*, the experts' own matmuls around
    one more), and a segment maps a row to a row: `rowwise(segment)` may
    run it over fewer rows than `T` (the serving step's live prefix,
    `inference/live_prefix.py`; None: `whole(T)`). `attend`, which owns the
    context, and the experts' grouped matmuls, whose cost follows the
    experts touched, always take the whole packed buffer: an `attend` that
    is a kernel which takes the buffer as it is carries `attend.pack`, the
    kernel's row-wise `prepare`, which the query segment applies to the
    rows it made (`attend` then returns the kernel's own buffer, and the
    next segment reads its rows of it); the experts' rows go the same way
    (`expert_rows`)."""
    t = x.shape[0]
    m = t * cfg.num_experts_per_tok
    rowwise = rowwise or whole(t)
    pack = getattr(attend, "pack", None)
    with _scope("llama.layer"):
        def query(x, cos, sin):
            with _scope("llama.rms_norm"):
                h = rms_norm(x, p["input_layernorm.weight"],
                             cfg.rms_norm_eps)
            q_abs, rows = mla_query(h, p, cfg, cos, sin)
            return (pack(q_abs) if pack else q_abs, rows), None

        def attended(x, o_lat):
            x = x + mla_output(o_lat, p, cfg, x.dtype)
            with _scope("llama.rms_norm"):
                return x, rms_norm(x, p["post_attention_layernorm.weight"],
                                   cfg.rms_norm_eps)

        def dense(x, o_lat):
            x, h = attended(x, o_lat)
            with _scope("llama.mlp"):
                return x + swiglu(h, p["mlp.gate_proj.weight"],
                                  p["mlp.up_proj.weight"],
                                  p["mlp.down_proj.weight"]), None

        def routed(x, o_lat, live):
            x, h = attended(x, o_lat)
            (xs, *rest), counts = moe_dispatch(h, p, cfg, live)
            xs = expert_rows(xs, p["mlp.experts.gate_proj.weight"], m)
            return (x, h, xs, *rest), counts

        def combined(x, h, y, order, keep, weights):
            return x + moe_combine(h, y, order, keep, weights, p), None

        (q_abs, rows), _ = rowwise(query)(x, cos, sin)
        o_lat = attend(q_abs, rows)
        if "mlp.gate.weight" not in p:
            return rowwise(dense)(x, o_lat)
        (x, h, xs, order, keep, weights), (mine, sizes) = rowwise(routed)(
            x, o_lat, live)
        x, _ = rowwise(combined)(x, h, moe_experts(xs, mine, p, rowwise, m),
                                 order, keep, weights)
        return x, sizes


def head(x, params, cfg: DeepseekV3Config):
    """Final norm and the untied output head: float32 logits `[T, V]`."""
    with _scope("llama.rms_norm"):
        x = rms_norm(x, params["model.norm.weight"], cfg.rms_norm_eps)
    with _scope("llama.head"):
        # float32 straight from the accumulator: rounding the logits to
        # bf16 first costs nothing less and ties the top of the vocabulary
        return jnp.einsum("tk,kn->tn", x,
                          params["lm_head.weight"].astype(x.dtype),
                          preferred_element_type=jnp.float32)


def dense_causal_attend(cfg: DeepseekV3Config):
    """`attend` for one whole sequence in flight and no cache: token i sees
    rows 0..i."""
    def attend(q_abs, rows):
        t = rows.shape[0]
        s = jnp.einsum("thd,sd->hts", q_abs.astype(jnp.float32),
                       rows.astype(jnp.float32)) * cfg.qk_head_dim ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        return jnp.einsum("hts,sc->thc", jax.nn.softmax(s, axis=-1),
                          rows[:, :cfg.kv_lora_rank].astype(jnp.float32))
    return attend


def model_forward(params, ids, cfg: DeepseekV3Config):
    """ids `[S]` -> float32 logits `[S, V]`: one sequence, no cache."""
    s = ids.shape[0]
    cos, sin = rope_tables(cfg, s)
    with _scope("llama.embed"):
        x = jnp.take(params["model.embed_tokens.weight"], ids, axis=0)
    live = jnp.ones((s,), bool)
    attend = dense_causal_attend(cfg)
    for i in range(cfg.num_hidden_layers):
        x, _ = decoder_layer(x, layer_params(params, i), cfg, cos, sin,
                             attend, live)
    return head(x, params, cfg)


class DeepseekV3ForCausalLM(nn.Layer):
    """A thin holder of the weight pytree: every leaf is a `Parameter` under
    its HuggingFace name, and `forward` is `model_forward`. `weights`
    (name -> array, shapes as `param_shapes` gives them) are taken as they
    are, without a copy; without them the pytree is drawn on the device."""

    def __init__(self, config: DeepseekV3Config,
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
                "deepseek_v3: the weights are not this configuration's: "
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
