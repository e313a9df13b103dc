"""Fused multi-transformer inference engine for the in-tree Llama.

The serving analog of the reference's `fused_multi_transformer` decode stack
(`paddle/phi/kernels/fusion/gpu/fused_multi_transformer_kernel.cu` + the
block-cache variant `block_multi_head_attention_kernel.cu`, python surface
`incubate.nn.functional.fused_multi_transformer`): the whole L-layer decoder
runs as ONE compiled XLA program per phase — weights stacked on a leading
layer axis and the layer body scanned with `lax.scan`, so the program size is
O(1) in depth and XLA pipelines HBM weight streaming with MXU compute.

TPU-first choices:
- paged KV cache ([L, num_blocks, kv_heads, block_size, D]) with the Pallas
  kernels (`ops/pallas/paged_attention.py`); block tables are host
  bookkeeping (`inference/cache.py`).
- every step jitted with the pools DONATED, and the pool stays where it
  is: the layer scan streams the WEIGHTS as its `xs`, while the whole pool
  tuple (`(k, v)`, with `(k_scale, v_scale)` when the KV is int8) is its
  carry and the layer index one more scanned operand. The step writes
  each live token's rows at `[layer, block, :, offset, :]` and its kernel
  reads pages at `[layer, block]`, so no layer's pool is ever sliced out
  of, stacked back into, reshaped or copied by the loop
  (tests/test_inference.py pins the compiled step's temporaries under one
  layer's pool).
- ONE compiled step a round: the `EngineCore` surface and the three
  programs are the shell's (`inference/step_engine.StepEngine`) over this
  file's `_ragged_stack` and `_head`, so a serving round is one program
  and one fetch and makes no `[T, V]` array. What a pool is made of is
  known where it is allocated and where its migration header is written,
  nowhere else.
- static shapes everywhere: batch and max_blocks fixed at engine build.
"""
from __future__ import annotations

import functools
import time

from ..models.llama import LlamaForCausalLM
from . import kv_migrate
from .cache import BlockCacheManager
from .generate import GenerationConfig
from .step_engine import StepEngine

__all__ = ["LlamaInferenceEngine", "GenerationConfig"]


def _stack_llama_params(model: LlamaForCausalLM, dtype=None):
    """Stack per-layer weights on a leading L axis (the fused-MT layout),
    each tensor cast to `dtype` BEFORE it is stacked: casting the stacked
    copy instead holds a second full-precision model on the device, and
    at real widths that build peak — not the engine — sets the depth a
    chip can hold."""
    import jax.numpy as jnp

    layers = model.llama.layers
    get = lambda t: t._data if dtype is None else t._data.astype(dtype)

    def stack(fn):
        return jnp.stack([fn(l) for l in layers])

    params = {
        "ln1": stack(lambda l: get(l.input_layernorm.weight)),
        "qkv_w": stack(lambda l: jnp.concatenate(
            [get(l.self_attn.q_proj.weight), get(l.self_attn.k_proj.weight),
             get(l.self_attn.v_proj.weight)], axis=1)),
        "o_w": stack(lambda l: get(l.self_attn.o_proj.weight)),
        "ln2": stack(lambda l: get(l.post_attention_layernorm.weight)),
        "gate_up_w": stack(lambda l: jnp.concatenate(
            [get(l.mlp.gate_proj.weight), get(l.mlp.up_proj.weight)], axis=1)),
        "down_w": stack(lambda l: get(l.mlp.down_proj.weight)),
        "embed": get(model.llama.embed_tokens.weight),
        "final_norm": get(model.llama.norm.weight),
        "rope_cos": get(layers[0].self_attn.rope_cos),
        "rope_sin": get(layers[0].self_attn.rope_sin),
    }
    if model.lm_head is not None:
        params["lm_head"] = get(model.lm_head.weight)
    return params


_QUANT_KEYS = ("qkv_w", "o_w", "gate_up_w", "down_w")


def _quantize_stacked(params, algo: str):
    """Weight-only-quantize the stacked [L, K, N] projection weights:
    -> {"q": int8/fp8 [L, N, K], "s": f32 [L, N]} per key (per-layer,
    per-out-channel scales; int4 packs two nibbles per byte into
    {"q4": [L, N, K//2], "s": [L, N]}), via the shared
    `nn.quant.per_channel_quantize` / `pack_int4` formulas."""
    import jax.numpy as jnp

    from ..nn.quant import pack_int4, per_channel_quantize

    if algo not in ("int8", "int4", "fp8"):
        raise ValueError(
            f"weight_only must be 'int8', 'int4' or 'fp8', got {algo}")
    wq_algo = {"int8": "weight_only_int8", "int4": "weight_only_int4",
               "fp8": "fp8"}[algo]
    out = dict(params)
    for key in _QUANT_KEYS:
        w = jnp.swapaxes(params[key].astype(jnp.float32), 1, 2)  # [L, N, K]
        q, scale = per_channel_quantize(w, wq_algo)
        out[key] = {"q4": pack_int4(q), "s": scale} if algo == "int4" \
            else {"q": q, "s": scale}
    return out


def _mm(x, w):
    """x [..., K] @ layer weight: dense [K, N] array (einsum),
    weight-only-quantized {"q": [N, K], "s": [N]} / int4-packed
    {"q4": [N, K//2], "s": [N]} via the shared `nn.quant.dequant_matmul`
    (Pallas dequant-in-kernel gemm on aligned TPU shapes), or a
    multi-LoRA epilogue dict {"w", "la", "lb", "ids"} that recursively
    wraps any of the former (`serving/lora.py`)."""
    import jax.numpy as jnp

    if not isinstance(w, dict):
        return jnp.einsum("...k,kn->...n", x, w.astype(x.dtype))
    if "la" in w:
        from ..serving.lora import lora_mm

        return lora_mm(x, w, _mm)
    from ..nn.quant import dequant_matmul

    if "q4" in w:
        return dequant_matmul(x, w["q4"], w["s"], "int4")
    return dequant_matmul(x, w["q"], w["s"])


def _rms(x, w, eps):
    import jax
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _rope_half(x, cos, sin):
    """Split-half rotation matching `models.llama._apply_rope_fn`."""
    import jax.numpy as jnp

    c = cos.astype(x.dtype)
    s = sin.astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


class LlamaInferenceEngine(kv_migrate.PagedPools, StepEngine):
    """Batch inference over LlamaForCausalLM with a paged KV cache: the
    shell's surface (`StepEngine`) over `_ragged_stack` and `_head`, COW
    copy and KV migration over the pool tuple (`kv_migrate.PagedPools`)."""

    def __init__(self, model: LlamaForCausalLM, max_batch_size: int = 8,
                 num_blocks: int = 256, block_size: int = 16,
                 max_blocks_per_seq: int = 16, dtype=None,
                 weight_only: str | None = None, kv_bits: int = 16):
        """`weight_only='int8'|'int4'|'fp8'` stores the projection
        weights quantized per-channel and dequantizes inside the gemm —
        the decode-bandwidth path of the reference's cutlass int8/fp8
        kernels (`phi/kernels/fusion/cutlass/gemm_epilogue/`); int4
        packs two nibbles per byte (`nn.quant.pack_int4`).

        `kv_bits=8` stores the paged KV pool as int8 with per-slot f32
        scale planes (`inference/kv_quant.py`): quantize-on-write in the
        ragged scatter, dequantize inside the attention kernel — bf16 KV
        never round-trips HBM, so the same HBM budget holds ~2x the
        blocks."""
        import jax.numpy as jnp

        began = time.time()     # `engine.build_s`: this line to the last
        cfg = model.config
        self.config = cfg
        self.block_size = block_size
        self.max_batch_size = max_batch_size
        self.manager = BlockCacheManager(num_blocks, block_size,
                                         max_blocks_per_seq)
        self.params = _stack_llama_params(model, dtype)
        self.weight_only = weight_only
        if weight_only is not None:
            self.params = _quantize_stacked(self.params, weight_only)
        cdtype = self.params["embed"].dtype
        L = cfg.num_hidden_layers
        kvh, d = cfg.num_key_value_heads, cfg.head_dim
        self.kv_bits = int(kv_bits)
        if self.kv_bits not in (8, 16):
            raise ValueError(f"kv_bits must be 8 or 16, got {kv_bits}")
        # the pool tuple: K and V, then the per-slot scale planes of an
        # int8 pool; every step, copy and migration takes it whole
        shape = (L, num_blocks, kvh, block_size, d)
        if self.kv_bits == 8:
            self.pools = (jnp.zeros(shape, jnp.int8),
                          jnp.zeros(shape, jnp.int8),
                          jnp.zeros(shape[:-1], jnp.float32),
                          jnp.zeros(shape[:-1], jnp.float32))
        else:
            self.pools = (jnp.zeros(shape, cdtype), jnp.zeros(shape, cdtype))
        self._slab_names = ("k", "v", "k_scale", "v_scale")[:len(self.pools)]
        # KV byte geometry: published on the manager so fragmentation()
        # and OOM forensics report bytes_per_block/kv_bits — capacity
        # claims audit from telemetry, not inference
        from . import kv_quant

        self._kv_geom = dict(kv_heads=kvh, block_size=block_size,
                             head_dim=d, kv_bits=self.kv_bits,
                             dtype_bytes=jnp.dtype(cdtype).itemsize,
                             num_layers=L)
        self.manager.set_kv_geometry(
            kv_quant.kv_bytes_per_block(**self._kv_geom), self.kv_bits)

        # COW copy and KV migration over the block axis (axis 1, all
        # layers at once): `kv_migrate.PagedPools`
        self._build_block_ops(1)
        self._mig_header = {
            "version": kv_migrate.PAYLOAD_VERSION, "engine": "llama",
            "block_size": block_size,
            "max_blocks_per_seq": max_blocks_per_seq,
            "kv_bits": self.kv_bits, "tp": 1, "num_layers": L,
            "kv_heads": kvh, "head_dim": d,
            "dtype": str(self.pools[0].dtype),
        }
        self._build_programs(*(
            functools.partial(fn, cfg=_StaticCfg(cfg))
            for fn in (_ragged_stack, _head)), began=began)

    def kv_bytes_per_token(self) -> float:
        """HBM bytes one cached token costs across K+V and all layers
        (int8 pools include their scale-plane overhead) — the
        `serving.kv_bytes_per_token` gauge and the capacity-math input
        (docs/SERVING.md "Quantized serving")."""
        from . import kv_quant

        return kv_quant.kv_bytes_per_token(**self._kv_geom)

    def quant_info(self) -> dict:
        """Quantization mode surface the serving metrics publish
        (`serving.quant.{wbits,kv_bits}`): weight bits (16 = native
        dtype), KV bits, and the per-token KV byte cost."""
        wb = {"int8": 8, "int4": 4, "fp8": 8}.get(self.weight_only, 16)
        return {"wbits": wb, "kv_bits": self.kv_bits,
                "kv_bytes_per_token": self.kv_bytes_per_token()}


class _StaticCfg:
    """Hashable static config for jit closure."""

    def __init__(self, cfg):
        self.num_heads = cfg.num_attention_heads
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = cfg.head_dim
        self.hidden = cfg.hidden_size
        self.inter = cfg.intermediate_size
        self.eps = cfg.rms_norm_eps
        self.tie = cfg.tie_word_embeddings

    def __hash__(self):
        return hash(tuple(sorted(self.__dict__.items())))

    def __eq__(self, o):
        return self.__dict__ == o.__dict__


def _layer_body(x, layer_in, pools, layer, *, cfg, positions, tables,
                ctx_lens, ragged_meta):
    """Decoder layer `layer` (a traced int32 scalar) on the packed tokens
    x [1, T, H], with its weights `layer_in` and the WHOLE pool tuple
    `pools` = (k_cache, v_cache) [L, NB, KVH, BS, D]; returns (x, pools),
    the pool written at `layer` and nowhere else.

    `ragged_meta` = (tok_lane, tok_pos) maps every packed token to its
    lane and absolute position, `ctx_lens` is per-lane kv_lens — ONE
    fixed-shape program for every batch composition (prefill chunks,
    decode lanes and verify windows alike).

    `pools` = (k_cache, v_cache, k_scale, v_scale), the per-slot scale
    planes [L, NB, KVH, BS] beside the caches, marks an int8 quantized KV
    pool (`inference/kv_quant.py`): writes quantize, attention
    dequantizes in-kernel, and all four come back.

    The layer's pool is never taken out: the write and the kernel index
    the whole pool at `layer`."""
    import jax
    import jax.numpy as jnp

    from ..ops.pallas import paged_attention as pk

    ln1, qkv_w, o_w, ln2, gu_w, down_w, cos, sin = layer_in
    b, s, hdim = x.shape
    nh, kvh, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    # one scope per region, the names `models/llama.py` gives the same
    # regions of the training step (docs/OBSERVABILITY.md)
    scope = jax.named_scope
    with scope("llama.rms_norm"):
        h1 = _rms(x, ln1, cfg.eps)
    with scope("llama.qkv"):
        qkv = _mm(h1, qkv_w)
        q = qkv[..., :nh * d].reshape(b, s, nh, d)
        k = qkv[..., nh * d:(nh + kvh) * d].reshape(b, s, kvh, d)
        v = qkv[..., (nh + kvh) * d:].reshape(b, s, kvh, d)
    with scope("llama.rope"):
        # rope at absolute positions (positions: [B, S])
        c = jnp.take(cos, positions, axis=0)[:, :, None, :]  # [B,S,1,D/2]
        si = jnp.take(sin, positions, axis=0)[:, :, None, :]
        q = _rope_half(q, c, si)
        k = _rope_half(k, c, si)

    tok_lane, tok_pos = ragged_meta
    with scope("llama.kv_write"):
        pools = pk.write_kv_to_cache_ragged(
            k[0], v[0], *pools[:2], tables, tok_lane, tok_pos,
            *pools[2:], layer=layer)
    with scope("llama.attn"):
        qr = q[0]                                     # [T, NH, D]
        kc, vc = pools[:2]
        kernel = pk.paged_attention_ragged if pk.ragged_supported(
            (s, nh, d), qr.dtype, kc.shape, kc.dtype,
            tables.shape[1]) else pk.paged_attention_ragged_ref
        attn = kernel(qr, kc, vc, tables, ctx_lens, tok_lane, tok_pos,
                      **dict(zip(("k_scale", "v_scale"), pools[2:])),
                      layer=layer)
        attn = attn.reshape(1, s, nh * d).astype(x.dtype)
    tp = getattr(cfg, "tp", None)
    if tp is not None:
        # TP-sharded ragged step (serving/tp.py): o_w/down_w are
        # row-parallel shards, so their gemms produce partial sums
        # reduced over the mesh axis — tiled, so tile k's psum
        # overlaps tile k+1's compute (distributed/tp_overlap.py)
        from ..distributed.tp_overlap import row_parallel_matmul

        with scope("llama.o_proj"):
            x = x + row_parallel_matmul(attn, o_w, axis_name=tp.axis,
                                        ntiles=tp.tiles, mm=_mm)
    else:
        with scope("llama.o_proj"):
            x = x + _mm(attn, o_w)
    with scope("llama.rms_norm"):
        h2 = _rms(x, ln2, cfg.eps)
    with scope("llama.mlp"):
        gu = _mm(h2, gu_w)
        g, u = jnp.split(gu, 2, axis=-1)
        act = jax.nn.silu(g.astype(jnp.float32)).astype(g.dtype) * u
        if tp is not None:
            x = x + row_parallel_matmul(act, down_w, axis_name=tp.axis,
                                        ntiles=tp.tiles, mm=_mm)
        else:
            x = x + _mm(act, down_w)
    return x, pools


def _run_stack(params, pools, x, positions, tables, ctx_lens, cfg,
               ragged_meta):
    """The decoder stack as one rolled `lax.scan`: the stacked weights and
    the layer index are its `xs`, the activations AND the whole KV pool
    tuple its carry, so the (donated) pool is written where it lies and
    never becomes a per-layer `xs` slice or a stacked `ys`. Returns
    (x [1, T, H] before the final norm, pools)."""
    import jax
    import jax.numpy as jnp

    cos, sin = params["rope_cos"], params["rope_sin"]

    @jax.named_scope("llama.layer")
    def body(carry, layer_xs):
        x, pools = carry
        *weights, layer = layer_xs
        x, pools = _layer_body(
            x, (*weights, cos, sin), pools, layer, cfg=cfg,
            positions=positions, tables=tables, ctx_lens=ctx_lens,
            ragged_meta=ragged_meta)
        return (x, pools), None

    xs = (params["ln1"], params["qkv_w"], params["o_w"], params["ln2"],
          params["gate_up_w"], params["down_w"],
          jnp.arange(pools[0].shape[0], dtype=jnp.int32))
    (x, pools), _ = jax.lax.scan(body, (x, pools), xs)
    return x, pools


def _head(state, x, lane, *, cfg):
    """The `head` of `ops/sampling.with_tail`: the final norm and the
    output matmul over the rows `x` [N, H] it is given (a round's `B`
    sampled rows, or all `T`), as float32 logits [N, V]. `state[0]` is the
    params; a row's `lane` changes nothing here."""
    import jax
    import jax.numpy as jnp

    params = state[0]
    with jax.named_scope("llama.rms_norm"):
        x = _rms(x, params["final_norm"], cfg.eps)
    head = params.get("lm_head")
    with jax.named_scope("llama.head"):
        if head is None:
            logits = jnp.einsum("sh,vh->sv", x,
                                params["embed"].astype(x.dtype))
        elif isinstance(head, dict):
            # weight-only-quantized head (serving/quant.py): the vocab
            # gemm is the largest single matmul of a decode step
            logits = _mm(x, head)
        else:
            logits = jnp.einsum("sh,hv->sv", x, head.astype(x.dtype))
        tp = getattr(cfg, "tp", None)
        if tp is not None and tp.gather_logits and head is not None:
            # column-parallel head (tied heads stay replicated): each
            # shard holds a contiguous vocab slice; gathering in-program
            # keeps the fused sampler device-side on replicated [..., V]
            # logits
            from ..distributed.tp_overlap import gather_columns

            logits = gather_columns(logits, tp.axis)
    return logits.astype(jnp.float32)


def _ragged_stack(params, pools, tokens, q_lens, kv_lens, tables, *, cfg):
    """Shared body of the ragged and verify entry points: packed tokens
    [T] + per-lane (q_len, kv_len) metadata through the decoder stack.
    Returns (hidden [T, H] before the final norm, pools): the `stack` of
    `ops/sampling.with_tail`, whose `head` is `_head`."""
    import jax
    import jax.numpy as jnp

    from ..ops.pallas import paged_attention as pk

    t = tokens.shape[0]
    tok_lane, tok_pos = pk.ragged_metadata(q_lens, kv_lens, t)
    with jax.named_scope("llama.embed"):
        x = jnp.take(params["embed"], tokens[None, :], axis=0)  # [1, T, H]
    positions = jnp.maximum(tok_pos, 0)[None, :]             # [1, T]
    x, pools = _run_stack(
        params, pools, x, positions, tables, kv_lens.astype(jnp.int32),
        cfg, ragged_meta=(tok_lane, tok_pos))
    return x[0], pools                                       # [T, H]
