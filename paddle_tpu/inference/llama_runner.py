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
- every step jitted with the caches DONATED, and the pool stays where it
  is: the layer scan streams the WEIGHTS as its `xs`, while the whole pool
  is its carry and the layer index one more scanned operand. The ragged
  step writes each live token's rows at `[layer, block, :, offset, :]` and
  its kernel reads pages at `[layer, block]`, so no layer's pool is ever
  sliced out of, stacked back into, reshaped or copied by the loop
  (tests/test_inference.py pins the compiled step's temporaries under one
  layer's pool). The legacy modes take their layer out and put it back.
- static shapes everywhere: batch and max_blocks fixed at engine build.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import numpy as np

from ..models.llama import LlamaForCausalLM
from . import kv_migrate
from .cache import BlockCacheManager

__all__ = ["LlamaInferenceEngine", "GenerationConfig"]


class GenerationConfig:
    def __init__(self, max_new_tokens: int = 32, do_sample: bool = False,
                 temperature: float = 1.0, top_p: float = 1.0,
                 top_k: int = 0, eos_token_id: Optional[int] = None,
                 seed: int = 0):
        self.max_new_tokens = max_new_tokens
        self.do_sample = do_sample
        self.temperature = temperature
        self.top_p = top_p
        self.top_k = top_k
        self.eos_token_id = eos_token_id
        self.seed = seed


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


class LlamaInferenceEngine:
    """Batch inference over LlamaForCausalLM with a paged KV cache.

    `prefill` and `decode_step` are each one jitted program; `generate` runs
    the host-side loop (sampling + block-table bookkeeping).
    """

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
        blocks. Quantized-KV engines serve through the ragged path
        (`ragged_step`/`verify_step`, the scheduler's only dispatches);
        the legacy `prefill`/`decode_step`/`generate` entry points raise."""
        import jax
        import jax.numpy as jnp

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
        if self.kv_bits == 8:
            self.k_cache = jnp.zeros((L, num_blocks, kvh, block_size, d),
                                     jnp.int8)
            self.v_cache = jnp.zeros((L, num_blocks, kvh, block_size, d),
                                     jnp.int8)
            self.k_scale = jnp.zeros((L, num_blocks, kvh, block_size),
                                     jnp.float32)
            self.v_scale = jnp.zeros((L, num_blocks, kvh, block_size),
                                     jnp.float32)
        else:
            self.k_cache = jnp.zeros((L, num_blocks, kvh, block_size, d),
                                     cdtype)
            self.v_cache = jnp.zeros((L, num_blocks, kvh, block_size, d),
                                     cdtype)
            self.k_scale = self.v_scale = None
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

        def step(fn, donate):
            # a bare partial has no name and the XLA module would be
            # `jit__unknown`; with the function's it is `jit__ragged_fn`,
            # which is how a profile's "XLA Modules" line tells the steps
            bound = functools.partial(fn, cfg=_StaticCfg(cfg))
            bound.__name__ = fn.__name__
            return jax.jit(bound, donate_argnums=donate)

        self._prefill = step(_prefill_fn, (1, 2))
        self._decode = step(_decode_fn, (1, 2))
        if self.kv_bits == 8:
            self._verify = step(_verify_q_fn, (1, 2, 3, 4))
            self._ragged = step(_ragged_q_fn, (1, 2, 3, 4))
            # COW copy moves the int8 block AND its scale rows in ONE
            # donated executable — q + scale can never tear apart
            self._copy_block_q = jax.jit(
                lambda k, v, ks, vs, s, d: (
                    k.at[:, d].set(k[:, s]), v.at[:, d].set(v[:, s]),
                    ks.at[:, d].set(ks[:, s]), vs.at[:, d].set(vs[:, s])),
                donate_argnums=(0, 1, 2, 3))
        else:
            self._verify = step(_verify_fn, (1, 2))
            self._ragged = step(_ragged_fn, (1, 2))
        # COW device copy (prefix caching, `BlockCacheManager` hook):
        # copies one physical block's K and V across every layer in one
        # donated executable; src/dst trace as int32 scalars, so COWs
        # never recompile
        self._copy_block = jax.jit(
            lambda k, v, s, d: (k.at[:, d].set(k[:, s]),
                                v.at[:, d].set(v[:, s])),
            donate_argnums=(0, 1))
        # KV migration (inference/kv_migrate.py): fixed-shape gather/
        # scatter over [max_blocks_per_seq] padded index vectors on the
        # block axis (axis 1, all layers at once). Gather NOT donated —
        # the source pool lives on; scatter donates the destination
        # pools. Int8 pools move K/V and BOTH scale planes in the same
        # executable so quantized state never tears apart in flight.
        if self.kv_bits == 8:
            self._kv_gather = jax.jit(
                lambda k, v, ks, vs, i: (k[:, i], v[:, i], ks[:, i],
                                         vs[:, i]))
            self._kv_scatter = jax.jit(
                lambda k, v, ks, vs, i, sk, sv, sks, svs: (
                    k.at[:, i].set(sk), v.at[:, i].set(sv),
                    ks.at[:, i].set(sks), vs.at[:, i].set(svs)),
                donate_argnums=(0, 1, 2, 3))
        else:
            self._kv_gather = jax.jit(
                lambda k, v, i: (k[:, i], v[:, i]))
            self._kv_scatter = jax.jit(
                lambda k, v, i, sk, sv: (k.at[:, i].set(sk),
                                         v.at[:, i].set(sv)),
                donate_argnums=(0, 1))
        self._mig_header = {
            "version": kv_migrate.PAYLOAD_VERSION, "engine": "llama",
            "block_size": block_size,
            "max_blocks_per_seq": max_blocks_per_seq,
            "kv_bits": self.kv_bits, "tp": 1, "num_layers": L,
            "kv_heads": kvh, "head_dim": d,
            "dtype": str(self.k_cache.dtype),
        }

    def extract_kv_blocks(self, seq_id: int) -> kv_migrate.KVBlockPayload:
        """Export `seq_id`'s committed KV blocks across all layers as ONE
        device gather (disaggregated handoff / KV-shipping relocation,
        ISSUE 17). The source pools are untouched — extraction is a
        copy; indices pad to the fixed `max_blocks_per_seq` shape so
        every sequence length rides one compiled executable."""
        mgr = self.manager
        blocks = mgr.blocks_of(seq_id)
        if not blocks:
            raise kv_migrate.KVMigrationError(
                f"sequence {seq_id} holds no KV blocks on this engine")
        idx = kv_migrate.pad_block_indices(blocks, mgr.max_blocks_per_seq)
        header = dict(self._mig_header, num_blocks=len(blocks),
                      num_tokens=mgr.seq_len(seq_id))
        if self.kv_bits == 8:
            sk, sv, sks, svs = self._kv_gather(
                self.k_cache, self.v_cache, self.k_scale, self.v_scale,
                idx)
            return kv_migrate.KVBlockPayload(
                header, {"k": sk, "v": sv, "k_scale": sks,
                         "v_scale": svs})
        sk, sv = self._kv_gather(self.k_cache, self.v_cache, idx)
        return kv_migrate.KVBlockPayload(header, {"k": sk, "v": sv})

    def inject_kv_blocks(self, seq_id: int,
                         payload: kv_migrate.KVBlockPayload) -> None:
        """Import a migrated payload under `seq_id`: typed header
        validation BEFORE any allocation, the manager's typed capacity
        errors propagate from `allocate`, one donated scatter writes
        every layer; any post-allocation failure frees the blocks so a
        failed inject never leaks. Payload slabs are not donated (one
        payload can stream to several workers)."""
        mgr = self.manager
        kv_migrate.check_header(payload.header, self._mig_header)
        blocks = mgr.allocate(seq_id, payload.num_tokens)
        try:
            if len(blocks) != payload.num_blocks:
                raise kv_migrate.KVMigrationError(
                    f"payload carries {payload.num_blocks} blocks but "
                    f"{payload.num_tokens} tokens allocate "
                    f"{len(blocks)} here")
            idx = kv_migrate.pad_block_indices(blocks,
                                               mgr.max_blocks_per_seq)
            if self.kv_bits == 8:
                (self.k_cache, self.v_cache, self.k_scale,
                 self.v_scale) = self._kv_scatter(
                    self.k_cache, self.v_cache, self.k_scale,
                    self.v_scale, idx, payload.slabs["k"],
                    payload.slabs["v"], payload.slabs["k_scale"],
                    payload.slabs["v_scale"])
            else:
                self.k_cache, self.v_cache = self._kv_scatter(
                    self.k_cache, self.v_cache, idx,
                    payload.slabs["k"], payload.slabs["v"])
        except Exception:
            mgr.free(seq_id)
            raise

    def cost_card_args(self, phase: str):
        """Observability hook (`observability.costs.ensure_engine_card`):
        the jitted executable behind `phase` plus the leading arguments
        the scheduler never sees (stacked params + paged KV). Lowered —
        never executed — for `cost_analysis()`: compiler-reported FLOPs
        per dispatch. The serving scheduler's "decode" phase is the
        ragged step (its only decode program); the legacy single-token
        executable stays reachable as "decode_legacy" for microbenches."""
        fn = {"prefill": self._prefill, "decode": self._ragged,
              "ragged": self._ragged, "decode_legacy": self._decode,
              "verify": self._verify}[phase]
        if self.kv_bits == 8:
            if phase not in ("decode", "ragged", "verify"):
                # the legacy executables pair f32/bf16 writes with the
                # int8 pool — a program this engine can never legally
                # run must not get a cost card (the caller tombstones)
                raise KeyError(
                    f"{phase!r} has no executable on a kv_bits=8 engine")
            return fn, (self.params, self.k_cache, self.v_cache,
                        self.k_scale, self.v_scale)
        return fn, (self.params, self.k_cache, self.v_cache)

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

    def _require_full_kv(self, entry: str):
        if self.kv_bits != 16:
            raise RuntimeError(
                f"{entry} is a legacy full-precision entry point; a "
                f"kv_bits={self.kv_bits} engine serves through "
                "ragged_step/verify_step (the scheduler's only dispatches)")

    # ---- public API (the serving EngineCore surface) ----
    def prefill(self, input_ids: np.ndarray, block_tables: np.ndarray,
                lens: Optional[np.ndarray] = None):
        """input_ids [B, S] int32; returns next-token logits [B, V].

        `lens` [B] gives the true prompt length per row when `input_ids` is
        right-padded (the serving scheduler pads prompts to a small set of
        bucket lengths so prefill compiles O(log S) programs, not one per
        prompt length); logits are gathered at position `lens-1`. Padded
        positions do write (garbage) KV into the sequence's own padded
        block allocation — callers trim via `BlockCacheManager.trim`, and
        decode overwrites position `lens` onward, so the garbage is never
        attended to."""
        self._require_full_kv("prefill")
        b, s = np.asarray(input_ids).shape
        if lens is None:
            lens = np.full((b,), s, np.int32)
        # exact-dtype numpy straight into the jit: the C++ dispatch path
        # transfers args far cheaper than per-arg jnp.asarray device_put
        # calls (the serving decode hot loop pays this 4x per step)
        logits, self.k_cache, self.v_cache = self._prefill(
            self.params, self.k_cache, self.v_cache,
            np.asarray(input_ids, np.int32),
            np.asarray(block_tables, np.int32),
            np.asarray(lens, np.int32))
        return logits

    def decode_step(self, tokens: np.ndarray, context_lens: np.ndarray,
                    block_tables: np.ndarray):
        """tokens [B] int32 (newest token per seq, already counted in
        context_lens); returns logits [B, V]."""
        self._require_full_kv("decode_step")
        logits, self.k_cache, self.v_cache = self._decode(
            self.params, self.k_cache, self.v_cache,
            np.asarray(tokens, np.int32),
            np.asarray(context_lens, np.int32),
            np.asarray(block_tables, np.int32))
        return logits

    def ragged_step(self, tokens: np.ndarray, q_lens: np.ndarray,
                    kv_lens: np.ndarray, block_tables: np.ndarray):
        """ONE fixed-shape step over a packed ragged batch — the serving
        scheduler's only decode-path program (chunked prefill + decode
        lanes fused; see docs/SERVING.md "Ragged batching").

        tokens [T] int32: packed lane-major query tokens; lane i owns
        slots [sum(q_lens[:i]), sum(q_lens[:i]) + q_lens[i]), its token j
        landing at position `kv_lens[i] - q_lens[i] + j` (kv_lens counts
        the cache INCLUDING this step's tokens; q_lens[i] == 0 marks an
        empty lane). Returns logits [T, V]; rows at guard slots past
        sum(q_lens) are meaningless and must be ignored (their KV writes
        are dropped, their attention output is forced to zero).
        Shape-stable in everything but T, which the scheduler fixes at
        `max_batch_size + prefill_chunk_tokens` — one compiled
        executable regardless of batch composition or prompt length."""
        if self.kv_bits == 8:
            (logits, self.k_cache, self.v_cache, self.k_scale,
             self.v_scale) = self._ragged(
                self.params, self.k_cache, self.v_cache, self.k_scale,
                self.v_scale, np.asarray(tokens, np.int32),
                np.asarray(q_lens, np.int32),
                np.asarray(kv_lens, np.int32),
                np.asarray(block_tables, np.int32))
            return logits
        logits, self.k_cache, self.v_cache = self._ragged(
            self.params, self.k_cache, self.v_cache,
            np.asarray(tokens, np.int32),
            np.asarray(q_lens, np.int32),
            np.asarray(kv_lens, np.int32),
            np.asarray(block_tables, np.int32))
        return logits

    def verify_step(self, tokens: np.ndarray, context_lens: np.ndarray,
                    block_tables: np.ndarray):
        """Batched multi-token verify pass (speculative decoding).

        tokens [B, S] int32 — per row, the pending last committed token
        followed by S-1 draft tokens; `context_lens` [B] counts the cache
        INCLUDING all S of them, so token i is written at position
        `context_lens - S + i` and attends causally up to itself (same
        fixed shape every step: zero recompiles once traced). Returns
        logits [B, S, V]: row i is the distribution for the token AFTER
        tokens[:, i] — rows 0..S-2 verify the drafts, row S-1 samples the
        bonus token when every draft is accepted."""
        if self.kv_bits == 8:
            (logits, self.k_cache, self.v_cache, self.k_scale,
             self.v_scale) = self._verify(
                self.params, self.k_cache, self.v_cache, self.k_scale,
                self.v_scale, np.asarray(tokens, np.int32),
                np.asarray(context_lens, np.int32),
                np.asarray(block_tables, np.int32))
            return logits
        logits, self.k_cache, self.v_cache = self._verify(
            self.params, self.k_cache, self.v_cache,
            np.asarray(tokens, np.int32),
            np.asarray(context_lens, np.int32),
            np.asarray(block_tables, np.int32))
        return logits

    def copy_kv_block(self, src: int, dst: int) -> None:
        """Copy one physical KV block, all layers (`BlockCacheManager`
        COW hook — the scheduler wires it when prefix caching is on).
        Int8 pools move the block's scale rows in the same donated
        executable — q and scale stay atomic under COW."""
        if self.kv_bits == 8:
            (self.k_cache, self.v_cache, self.k_scale,
             self.v_scale) = self._copy_block_q(
                self.k_cache, self.v_cache, self.k_scale, self.v_scale,
                np.int32(src), np.int32(dst))
            return
        self.k_cache, self.v_cache = self._copy_block(
            self.k_cache, self.v_cache, np.int32(src), np.int32(dst))

    def generate(self, input_ids, generation_config: GenerationConfig = None,
                 **kw) -> np.ndarray:
        """Greedy/sampling generation. input_ids: [B, S] (equal-length
        prompts; ragged batches go through per-sequence prefill calls).
        Returns [B, S + max_new_tokens]."""
        # guard BEFORE any allocation: raising from prefill() below
        # would leave the just-leased blocks permanently held
        self._require_full_kv("generate")
        gc = generation_config or GenerationConfig(**kw)
        ids = np.asarray(input_ids, np.int32)
        if ids.ndim == 1:
            ids = ids[None]
        b, s = ids.shape
        assert b <= self.max_batch_size
        seq_ids = list(range(b))
        for sid in seq_ids:
            self.manager.allocate(sid, s)
        tables = self.manager.block_table_array(seq_ids)
        logits = np.asarray(self.prefill(ids, tables))
        rng = np.random.default_rng(gc.seed)
        out = [ids]
        done = np.zeros(b, bool)
        last = self._pick(logits, gc, rng)
        for _ in range(gc.max_new_tokens):
            out.append(last[:, None])
            if gc.eos_token_id is not None:
                done |= last == gc.eos_token_id
                if done.all():
                    break
            for sid in seq_ids:
                self.manager.append_token(sid)
            tables = self.manager.block_table_array(seq_ids)
            lens = np.asarray([self.manager.seq_len(sid) for sid in seq_ids],
                              np.int32)
            logits = np.asarray(self.decode_step(last, lens, tables))
            last = self._pick(logits, gc, rng)
        for sid in seq_ids:
            self.manager.free(sid)
        return np.concatenate(out, axis=1)

    @staticmethod
    def _pick(logits: np.ndarray, gc: GenerationConfig, rng) -> np.ndarray:
        if not gc.do_sample:
            return np.argmax(logits, axis=-1).astype(np.int32)
        x = logits.astype(np.float64) / max(gc.temperature, 1e-6)
        if gc.top_k:
            kth = np.partition(x, -gc.top_k, axis=-1)[:, -gc.top_k][:, None]
            x = np.where(x < kth, -np.inf, x)
        p = np.exp(x - x.max(axis=-1, keepdims=True))
        p /= p.sum(axis=-1, keepdims=True)
        if gc.top_p < 1.0:
            order = np.argsort(-p, axis=-1)
            ps = np.take_along_axis(p, order, -1)
            cum = np.cumsum(ps, axis=-1)
            keep = cum - ps < gc.top_p   # always keep the top token
            ps = np.where(keep, ps, 0.0)
            ps /= ps.sum(axis=-1, keepdims=True)
            picked = np.stack([rng.choice(ps.shape[1], p=ps[i])
                               for i in range(ps.shape[0])])
            return np.take_along_axis(order, picked[:, None], -1)[:, 0].astype(
                np.int32)
        return np.stack([rng.choice(p.shape[1], p=p[i])
                         for i in range(p.shape[0])]).astype(np.int32)


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
                ctx_lens, mode, ragged_meta=None):
    """Decoder layer `layer` (a traced int32 scalar) on [B, S, H], with its
    weights `layer_in` and the WHOLE pool `pools` = (k_cache, v_cache)
    [L, NB, KVH, BS, D]; returns (x, pools), the pool written at `layer`
    and nowhere else.

    `mode`: "prefill" (dense causal SDPA over the in-flight tokens),
    "decode" (single-query paged attention), "verify" (S-query causal
    paged attention — the speculative multi-token verify pass), or
    "ragged" (packed mixed prefill-chunk/decode/verify tokens: x is
    [1, T, H], `ragged_meta` = (tok_lane, tok_pos) maps every packed
    token to its lane and absolute position, ctx_lens is per-lane
    kv_lens — ONE fixed-shape program for every batch composition).

    `pools` = (k_cache, v_cache, k_scale, v_scale), the per-slot scale
    planes [L, NB, KVH, BS] beside the caches, marks an int8 quantized KV
    pool (`inference/kv_quant.py`, ragged mode only): writes quantize,
    attention dequantizes in-kernel, and all four come back.

    Ragged mode never takes the layer's pool out: the write and the
    kernel index the whole pool at `layer`. The legacy modes slice their
    layer out and update it back, which is what they always cost."""
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

    if mode == "ragged":
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

    with scope("llama.kv_write"):
        start = positions[:, 0].astype(jnp.int32)
        kc, vc = (jax.lax.dynamic_index_in_dim(p, layer, 0, keepdims=False)
                  for p in pools)
        kc, vc = pk.write_kv_to_cache(k, v, kc, vc, tables, start)
        pools = tuple(jax.lax.dynamic_update_index_in_dim(p, c, layer, 0)
                      for p, c in zip(pools, (kc, vc)))

    with scope("llama.attn"):
        if mode == "decode":
            qd = q.reshape(b, nh, d)
            if pk.supported((b, nh, d), qd.dtype):
                attn = pk.paged_attention(qd, kc, vc, tables, ctx_lens)
            else:
                attn = pk.paged_attention_ref(qd, kc, vc, tables, ctx_lens)
            attn = attn.reshape(b, s, nh * d)
        elif mode == "verify":
            if pk.verify_supported((b, s, nh, d), q.dtype):
                attn = pk.paged_attention_verify(q, kc, vc, tables,
                                                 ctx_lens)
            else:
                attn = pk.paged_attention_verify_ref(q, kc, vc, tables,
                                                     ctx_lens)
            attn = attn.reshape(b, s, nh * d)
        else:
            kk, vv = k, v
            if kvh != nh:
                kk = jnp.repeat(kk, nh // kvh, axis=2)
                vv = jnp.repeat(vv, nh // kvh, axis=2)
            from ..nn.functional.attention import _sdpa_fn

            attn = _sdpa_fn(q, kk, vv, None, True, None, False)
            attn = attn.reshape(b, s, nh * d)
    with scope("llama.o_proj"):
        x = x + _mm(attn, o_w)

    with scope("llama.rms_norm"):
        h2 = _rms(x, ln2, cfg.eps)
    with scope("llama.mlp"):
        gu = _mm(h2, gu_w)
        g, u = jnp.split(gu, 2, axis=-1)
        act = jax.nn.silu(g.astype(jnp.float32)).astype(g.dtype) * u
        x = x + _mm(act, down_w)
    return x, pools


def _run_stack(params, k_cache, v_cache, x, positions, tables, ctx_lens,
               cfg, mode, ragged_meta=None, k_scale=None, v_scale=None):
    """The decoder stack as one rolled `lax.scan`: the stacked weights and
    the layer index are its `xs`, the activations AND the whole KV pool
    its carry, so the (donated) pool is written where it lies and never
    becomes a per-layer `xs` slice or a stacked `ys`. Returns (logits,
    k_cache, v_cache[, k_scale, v_scale when the pool is int8])."""
    import jax
    import jax.numpy as jnp

    cos, sin = params["rope_cos"], params["rope_sin"]
    pools = (k_cache, v_cache)
    if k_scale is not None:
        pools += (k_scale, v_scale)

    @jax.named_scope("llama.layer")
    def body(carry, layer_xs):
        x, pools = carry
        *weights, layer = layer_xs
        x, pools = _layer_body(
            x, (*weights, cos, sin), pools, layer, cfg=cfg,
            positions=positions, tables=tables, ctx_lens=ctx_lens,
            mode=mode, ragged_meta=ragged_meta)
        return (x, pools), None

    xs = (params["ln1"], params["qkv_w"], params["o_w"], params["ln2"],
          params["gate_up_w"], params["down_w"],
          jnp.arange(k_cache.shape[0], dtype=jnp.int32))
    (x, pools), _ = jax.lax.scan(body, (x, pools), xs)
    with jax.named_scope("llama.rms_norm"):
        x = _rms(x, params["final_norm"], cfg.eps)
    head = params.get("lm_head")
    with jax.named_scope("llama.head"):
        if head is None:
            logits = jnp.einsum("bsh,vh->bsv", x,
                                params["embed"].astype(x.dtype))
        elif isinstance(head, dict):
            # weight-only-quantized head (serving/quant.py): the vocab
            # gemm is the largest single matmul of a decode step
            logits = _mm(x, head)
        else:
            logits = jnp.einsum("bsh,hv->bsv", x, head.astype(x.dtype))
        tp = getattr(cfg, "tp", None)
        if tp is not None and tp.gather_logits and head is not None:
            # column-parallel head (tied heads stay replicated): each
            # shard holds a contiguous vocab slice; gathering in-program
            # keeps the fused sampler device-side on replicated [..., V]
            # logits
            from ..distributed.tp_overlap import gather_columns

            logits = gather_columns(logits, tp.axis)
    return (logits,) + pools


def _prefill_fn(params, k_cache, v_cache, input_ids, tables, lens, *, cfg):
    import jax
    import jax.numpy as jnp

    from ..framework import monitor

    # Trace-time side effect: bumps once per (re)trace, never at run time —
    # the serving tests assert this stays flat after warmup.
    monitor.inc("serving.prefill_retraces")
    b, s = input_ids.shape
    with jax.named_scope("llama.embed"):
        x = jnp.take(params["embed"], input_ids, axis=0)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    ctx = jnp.full((b,), s, jnp.int32)
    logits, nk, nv = _run_stack(params, k_cache, v_cache, x, positions,
                                tables, ctx, cfg, mode="prefill")
    idx = jnp.clip(lens - 1, 0, s - 1)
    last = jnp.take_along_axis(
        logits, idx[:, None, None].astype(jnp.int32), axis=1)[:, 0, :]
    return last.astype(jnp.float32), nk, nv


def _decode_fn(params, k_cache, v_cache, tokens, ctx_lens, tables, *, cfg):
    import jax
    import jax.numpy as jnp

    from ..framework import monitor

    monitor.inc("serving.decode_retraces")  # trace-time only (see prefill)
    b = tokens.shape[0]
    with jax.named_scope("llama.embed"):
        x = jnp.take(params["embed"], tokens[:, None], axis=0)
    positions = (ctx_lens - 1)[:, None].astype(jnp.int32)   # [B, 1]
    logits, nk, nv = _run_stack(params, k_cache, v_cache, x, positions,
                                tables, ctx_lens.astype(jnp.int32), cfg,
                                mode="decode")
    return logits[:, -1, :].astype(jnp.float32), nk, nv


def _ragged_stack(params, k_cache, v_cache, tokens, q_lens, kv_lens,
                  tables, cfg, k_scale=None, v_scale=None):
    """Shared body of the ragged and verify entry points: packed tokens
    [T] + per-lane (q_len, kv_len) metadata through the decoder stack in
    ragged mode. Returns (logits [T, V], new_k, new_v[, new_ks, new_vs
    when the KV pool is int8-quantized])."""
    import jax
    import jax.numpy as jnp

    from ..ops.pallas import paged_attention as pk

    t = tokens.shape[0]
    tok_lane, tok_pos = pk.ragged_metadata(q_lens, kv_lens, t)
    with jax.named_scope("llama.embed"):
        x = jnp.take(params["embed"], tokens[None, :], axis=0)  # [1, T, H]
    positions = jnp.maximum(tok_pos, 0)[None, :]             # [1, T]
    out = _run_stack(
        params, k_cache, v_cache, x, positions, tables,
        kv_lens.astype(jnp.int32), cfg, mode="ragged",
        ragged_meta=(tok_lane, tok_pos), k_scale=k_scale, v_scale=v_scale)
    logits, rest = out[0], out[1:]
    return (logits[0].astype(jnp.float32),) + rest           # [T, V]


def _ragged_fn(params, k_cache, v_cache, tokens, q_lens, kv_lens, tables,
               *, cfg):
    from ..framework import monitor

    # Trace-time side effects (see prefill): the ragged step IS the
    # serving decode program, so it owns the decode_retraces counter the
    # zero-recompile suite asserts on; ragged_retraces additionally pins
    # "ONE executable regardless of batch composition / prompt length".
    monitor.inc("serving.decode_retraces")
    monitor.inc("serving.ragged_retraces")
    return _ragged_stack(params, k_cache, v_cache, tokens, q_lens,
                         kv_lens, tables, cfg)


def _ragged_q_fn(params, k_cache, v_cache, k_scale, v_scale, tokens,
                 q_lens, kv_lens, tables, *, cfg):
    """The int8-KV serving decode program (`kv_bits=8`): same packed
    ragged step, with the pool's scale planes donated alongside the
    caches — quantize-on-write and in-kernel dequant, one executable."""
    from ..framework import monitor

    monitor.inc("serving.decode_retraces")  # trace-time (see _ragged_fn)
    monitor.inc("serving.ragged_retraces")
    return _ragged_stack(params, k_cache, v_cache, tokens, q_lens,
                         kv_lens, tables, cfg, k_scale=k_scale,
                         v_scale=v_scale)


def _verify_fn(params, k_cache, v_cache, tokens, ctx_lens, tables, *, cfg):
    """Speculative verify as a special case of the ragged step: every
    lane contributes a fixed q_len == S window, so the packed buffer is
    just tokens.reshape(B*S) and the logits fold back to [B, S, V]."""
    import jax.numpy as jnp

    from ..framework import monitor

    monitor.inc("serving.verify_retraces")  # trace-time only (see prefill)
    b, s = tokens.shape
    q_lens = jnp.full((b,), s, jnp.int32)
    logits, nk, nv = _ragged_stack(params, k_cache, v_cache,
                                   tokens.reshape(b * s),
                                   q_lens, ctx_lens.astype(jnp.int32),
                                   tables, cfg)
    return logits.reshape(b, s, -1), nk, nv                  # [B, S, V]


def _verify_q_fn(params, k_cache, v_cache, k_scale, v_scale, tokens,
                 ctx_lens, tables, *, cfg):
    """Verify over an int8-quantized KV pool (rides the quantized
    ragged stack exactly as `_verify_fn` rides the plain one)."""
    import jax.numpy as jnp

    from ..framework import monitor

    monitor.inc("serving.verify_retraces")  # trace-time only
    b, s = tokens.shape
    q_lens = jnp.full((b,), s, jnp.int32)
    logits, nk, nv, nks, nvs = _ragged_stack(
        params, k_cache, v_cache, tokens.reshape(b * s), q_lens,
        ctx_lens.astype(jnp.int32), tables, cfg, k_scale=k_scale,
        v_scale=v_scale)
    return logits.reshape(b, s, -1), nk, nv, nks, nvs        # [B, S, V]
