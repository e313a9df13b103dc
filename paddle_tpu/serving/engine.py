"""EngineCore — the model-agnostic serving engine protocol.

Generalizes `inference.llama_runner.LlamaInferenceEngine` into the contract
the continuous-batching scheduler programs against. An engine owns stacked
model params and `self.pools`, one tuple of paged KV(-like) arrays, and
exposes ONE compiled program a round:

- `sampled_step(tokens [T], lanes [B, 6], block_tables [B, MAXB],
  temperature [B])` — one fixed-shape step over a packed ragged batch
  (prefill chunks and decode lanes alike; the scheduler pads empty lanes
  with `q_len` 0) that ENDS in the NaN screen, the gather of each lane's
  last hidden row, the output head over those `B` rows and the sampler
  (`ops/sampling.with_tail`), returning `sampled [2, B]` on the device: a
  scheduler round is this one program and one fetch of `sampled`, and no
  `[T, V]` array is made. The engine keeps that `sampled` as
  `last_sampled` and hands it to its next step, where a token
  `ops/sampling.fed_token(b)` reads lane `b`'s out of it: the scheduler
  launches a round before it has fetched the one before
  (docs/SERVING.md "A round in flight");
- `ragged_step(tokens [T], q_lens [B], kv_lens [B], block_tables)` is the
  same stack with the head over every row, `[T, V]` logits: a second
  executable (`ops/sampling.all_rows`) that no round runs, compiled when
  a prober first calls it (`ops/sampling.ragged_step`);
- `verify_step(tokens [B, S], context_lens [B], block_tables)` is the
  stack's `q_len == S` case (speculative decoding), and
  `generate(input_ids)` a host loop over `ragged_step`
  (`inference/generate.py`).

Every engine class is state + a stack + a head under ONE shell,
`inference.step_engine.StepEngine`, which writes this surface once.

Beside it: `copy_kv_block(src, dst)` (the manager's COW hook) and
`extract_kv_blocks(seq_id)` / `inject_kv_blocks(seq_id, payload)` (KV
migration), each one donated executable over the whole pool tuple
(`inference.kv_migrate.PagedPools`).

Every compiled entry is shape-stable so the serving steady state never
recompiles (the Ragged-Paged-Attention shape discipline, PAPERS.md).
The round's program bumps `serving.decode_retraces` and
`serving.ragged_retraces`, the all-rows one `serving.logits_retraces`
(`ops/sampling`), an engine's verify `serving.verify_retraces`, each at
TRACE time inside the jitted function, so tests can assert exactly that.

Failure contract (docs/SERVING.md "Failure semantics"): an engine may
raise from any entry point — the scheduler's typed fault boundary
(`serving/fault_tolerance.py`) attributes the failure (raise
`EngineStepError(phase, seq_ids=...)` to name the poisoned lane(s)
directly; any other exception is attributed by per-lane probe replays),
fails only the culpable request(s), and replays the survivors. Engines
whose device state can be corrupted should be paired with an
`engine_factory` (e.g. `MLPLMEngine.respawn`) so the watchdog can
rebuild them.

`MLPLMEngine` is the second, deliberately tiny implementation: a bag-of-
embeddings MLP language model whose "KV" cache stores per-token embeddings
in the same paged layout. It exists to prove the scheduler/frontend stack
is model-agnostic (2-model genericity test), and doubles as a fast CPU
smoke engine.
"""
from __future__ import annotations

import functools
from typing import Protocol, runtime_checkable

import numpy as np

from ..inference import kv_migrate
from ..inference.cache import BlockCacheManager
from ..inference.step_engine import StepEngine

__all__ = ["EngineCore", "MLPLMEngine"]


@runtime_checkable
class EngineCore(Protocol):
    """Structural protocol: `LlamaInferenceEngine` satisfies it as-is."""

    max_batch_size: int
    manager: BlockCacheManager
    # the `sampled` of the last `sampled_step`, still on the device (None
    # before the first): what a `fed_token` of the next step reads
    last_sampled: object

    def verify_step(self, tokens: np.ndarray, context_lens: np.ndarray,
                    block_tables: np.ndarray) -> np.ndarray:
        """Batched multi-token verify (speculative decoding): tokens
        [B, S] (pending last token + S-1 drafts), `context_lens` counting
        the cache INCLUDING all S tokens, returns logits [B, S, V] where
        row i is the distribution after tokens[:, i]. Fixed S every call
        so the steady state never recompiles. A special case of
        `ragged_step` (q_len == S for every lane) and implemented on top
        of it by both in-tree engines."""
        ...

    def sampled_step(self, tokens: np.ndarray, lanes: np.ndarray,
                     block_tables: np.ndarray, temperature: np.ndarray):
        """ONE fixed-shape step over a packed ragged batch: tokens [T]
        lane-major (lane i owns q_lens[i] consecutive slots, token j at
        position kv_lens[i] - q_lens[i] + j; q_len 0 = empty lane);
        `lanes` [B, 6] int32 holds q_lens, kv_lens, each lane's last
        packed row, top_k, seed and draw index
        (`ops/sampling.LANE_COLS`), `temperature` [B] float32. Returns
        `sampled [2, B] int32`, on the device: each lane's sampled token
        and whether its whole band is finite (`ops/sampling.step_tail`);
        the head runs over the `B` sampled rows. The serving scheduler's
        only decode-path dispatch — decode lanes and chunked-prefill
        tokens share it, so the steady state holds ONE executable with
        no prompt-length or bucket shape family."""
        ...

    def ragged_step(self, tokens: np.ndarray, q_lens: np.ndarray,
                    kv_lens: np.ndarray,
                    block_tables: np.ndarray) -> np.ndarray:
        """Every packed row's logits [T, V], for callers that sample on
        the host (`generate`, proposers, probes, checks): the sampled
        step's stack with the head over all rows, an executable of its
        own that no round runs (`ops/sampling.ragged_step`)."""
        ...


def _mlp_ragged_stack(params, pools, tokens, q_lens, kv_lens, tables, *,
                      block_size, tp=None):
    """Shared ragged body: packed tokens [T] + per-lane (q_len, kv_len)
    metadata. Token t embeds, writes its embedding at its absolute
    position (guard slots' writes are OOB-dropped), and conditions on
    (own embedding, masked mean of its lane's window through `tok_pos`).

    `pools` is `(cache,)`, or `(cache, cache_scale)` with the scale plane
    ([NB, BS] f32) of an int8-quantized embedding pool
    (`inference/kv_quant.py`): writes quantize per slot, the gathered
    window dequantizes right after the gather — the float pool never
    exists. Returns (hidden [T, 2D], pools), a token's own embedding
    beside its window's mean: the `stack` of `ops/sampling.with_tail`,
    whose `head` is `_mlp_head`.

    `tp` (`distributed.tp_overlap.TPInfo`, set by `serving/tp.py` when
    the body runs inside shard_map) marks a feature-sharded pool: each
    shard writes/gathers its contiguous D/tp embedding slice, the int8
    scale quantizes over the FULL feature vector (absmax is a global
    reduction — sharding it would change the scale and break bitwise
    parity) and the plane stays replicated, and the head runs w1
    row-parallel / w2 column-parallel (`_mlp_head`)."""
    import jax.numpy as jnp

    from ..inference import kv_quant
    from ..ops.pallas.paged_attention import ragged_metadata

    cache = pools[0]
    t = tokens.shape[0]
    nb = cache.shape[0]
    maxb = tables.shape[1]
    tok_lane, tok_pos = ragged_metadata(q_lens, kv_lens, t)
    x = jnp.take(params["embed"], tokens, axis=0)            # [T, D]
    if tp is not None:
        import jax

        dl = cache.shape[-1]                  # local feature width D/tp
        off = jax.lax.axis_index(tp.axis) * dl
        x_loc = jax.lax.dynamic_slice_in_dim(x, off, dl, axis=1)
    else:
        x_loc = x
    pos = jnp.maximum(tok_pos, 0)
    blocks = tables[tok_lane, pos // block_size]             # [T]
    blocks = jnp.where(tok_pos >= 0, blocks, jnp.int32(nb))  # OOB -> drop
    if len(pools) == 2:
        cache_scale = pools[1]
        q, s = kv_quant.quantize_kv(x)                       # [T, D] / [T]
        if tp is not None:
            q = jax.lax.dynamic_slice_in_dim(q, off, dl, axis=1)
        cache = cache.at[blocks, pos % block_size].set(q)
        cache_scale = cache_scale.at[blocks, pos % block_size].set(s)
        pools = (cache, cache_scale)
        window = kv_quant.dequantize_kv(
            jnp.take(cache, tables, axis=0),
            jnp.take(cache_scale, tables, axis=0)).reshape(
                tables.shape[0], maxb * block_size, -1)      # [B, W, D]
    else:
        cache = cache.at[blocks, pos % block_size].set(x_loc)
        pools = (cache,)
        window = jnp.take(cache, tables, axis=0).reshape(
            tables.shape[0], maxb * block_size, -1)          # [B, W, D]
    window = jnp.take(window, tok_lane, axis=0)              # [T, W, D]
    wpos = jnp.arange(maxb * block_size, dtype=jnp.int32)
    mask = (wpos[None, :] <= tok_pos[:, None]).astype(x.dtype)
    mean = (window * mask[..., None]).sum(1) / jnp.maximum(
        mask.sum(1, keepdims=True), 1.0)                     # [T, D]
    return jnp.concatenate([x_loc, mean], axis=-1), pools    # [T, 2D]


def _mlp_mm(h, w):
    """h [..., K] @ head weight: dense [K, N] array, weight-only-
    quantized {"q": [N, K], "s": [N]} / int4 {"q4": [N, K//2], "s"}
    through the shared `nn.quant.dequant_matmul` (the same dict layout
    the Llama engine's `_mm` consumes — `serving/quant.py` produces
    both), or a multi-LoRA epilogue dict {"w", "la", "lb", "ids"} that
    recursively wraps either (`serving/lora.py`)."""
    if not isinstance(w, dict):
        return h @ w
    if "la" in w:
        from .lora import lora_mm

        return lora_mm(h, w, _mlp_mm)
    from ..nn.quant import dequant_matmul

    if "q4" in w:
        return dequant_matmul(h, w["q4"], w["s"], "int4")
    return dequant_matmul(h, w["q"], w["s"])


def _mlp_head(state, h, lane, *, tp=None):
    """The `head` of `ops/sampling.with_tail` over the rows `h` [N, 2D] it
    is given (`state[0]` the params): `gelu(h @ w1 + b1) @ w2 + b2` as
    float32 logits [N, V].

    Under TP (`tp` set, inside shard_map): `h` holds the local feature
    slices, `w1` is the matching row-parallel shard (rows permuted by
    `serving/tp.py` so shard s holds [last_s, mean_s]) whose partial sums
    psum-reduce tile-by-tile — tile k's collective overlaps tile k+1's
    gemm (`distributed/tp_overlap.py`) — and `w2`/`b2` are
    column-parallel vocab shards; `tp.gather_logits` finishes with an
    in-program all-gather so the sampler sees replicated logits."""
    import jax
    import jax.numpy as jnp

    params = state[0]
    if tp is None:
        h = jax.nn.gelu(_mlp_mm(h, params["w1"]) + params["b1"])
        return (_mlp_mm(h, params["w2"]) + params["b2"]).astype(jnp.float32)
    from ..distributed.tp_overlap import gather_columns, row_parallel_matmul

    h = jax.nn.gelu(
        row_parallel_matmul(h, params["w1"], axis_name=tp.axis,
                            ntiles=tp.tiles, mm=_mlp_mm) + params["b1"])
    logits = _mlp_mm(h, params["w2"]) + params["b2"]
    if tp.gather_logits:
        logits = gather_columns(logits, tp.axis)
    return logits.astype(jnp.float32)


class MLPLMEngine(kv_migrate.PagedPools, StepEngine):
    """Bag-of-embeddings MLP LM over the paged cache (EngineCore #2).

    The "KV" cache is [num_blocks, block_size, D] token embeddings; a token
    conditions on (its own embedding, masked mean of the context window
    gathered through the block table). Same paged bookkeeping, same
    fixed-shape step discipline as the Llama engine, ~1000x smaller.
    """

    def __init__(self, vocab_size: int = 256, hidden: int = 32,
                 max_batch_size: int = 8, num_blocks: int = 64,
                 block_size: int = 8, max_blocks_per_seq: int = 8,
                 seed: int = 0, kv_bits: int = 16):
        import jax.numpy as jnp

        self._init_kwargs = dict(
            vocab_size=vocab_size, hidden=hidden,
            max_batch_size=max_batch_size, num_blocks=num_blocks,
            block_size=block_size, max_blocks_per_seq=max_blocks_per_seq,
            seed=seed, kv_bits=kv_bits)
        self.vocab_size = vocab_size
        self.max_batch_size = max_batch_size
        self.block_size = block_size
        self.kv_bits = int(kv_bits)
        if self.kv_bits not in (8, 16):
            raise ValueError(f"kv_bits must be 8 or 16, got {kv_bits}")
        self.manager = BlockCacheManager(num_blocks, block_size,
                                         max_blocks_per_seq)
        rng = np.random.default_rng(seed)
        d = hidden

        def init(*shape):
            return jnp.asarray(rng.normal(0, 0.08, shape), jnp.float32)

        self.params = {
            "embed": init(vocab_size, d),
            "w1": init(2 * d, 2 * d), "b1": jnp.zeros((2 * d,), jnp.float32),
            "w2": init(2 * d, vocab_size),
            "b2": jnp.zeros((vocab_size,), jnp.float32),
        }
        # the "KV" pool tuple: per-token embeddings, paged; int8 + per-slot
        # scale plane under kv_bits=8 (inference/kv_quant.py)
        if self.kv_bits == 8:
            self.pools = (jnp.zeros((num_blocks, block_size, d), jnp.int8),
                          jnp.zeros((num_blocks, block_size), jnp.float32))
            bpb = block_size * d * 1 + block_size * 4
        else:
            self.pools = (jnp.zeros((num_blocks, block_size, d),
                                    jnp.float32),)
            bpb = block_size * d * 4
        self._slab_names = ("cache", "scale")[:len(self.pools)]
        self._kv_bytes_per_token = bpb / block_size
        self.manager.set_kv_geometry(bpb, self.kv_bits)
        self._build_programs(
            functools.partial(_mlp_ragged_stack, block_size=block_size),
            _mlp_head)
        # COW copy and KV migration over the block axis (axis 0):
        # `kv_migrate.PagedPools`
        self._build_block_ops(0)
        self._mig_header = {
            "version": kv_migrate.PAYLOAD_VERSION, "engine": "mlp",
            "block_size": block_size,
            "max_blocks_per_seq": max_blocks_per_seq,
            "kv_bits": self.kv_bits, "tp": 1, "hidden": hidden,
            "dtype": str(self.pools[0].dtype),
        }

    def kv_bytes_per_token(self) -> float:
        """HBM bytes one cached token costs (int8 pools include the
        scale plane) — the `serving.kv_bytes_per_token` gauge."""
        return self._kv_bytes_per_token

    def quant_info(self) -> dict:
        """Quantization mode surface (see
        `LlamaInferenceEngine.quant_info`); `wbits` reflects the
        serving/quant.py weight pass (16 = unquantized)."""
        wb = 16
        w1 = self.params.get("w1")
        if isinstance(w1, dict):
            wb = 4 if "q4" in w1 else 8
        return {"wbits": wb, "kv_bits": self.kv_bits,
                "kv_bytes_per_token": self._kv_bytes_per_token}

    def respawn(self) -> "MLPLMEngine":
        """Build a fresh engine with IDENTICAL weights (seed-derived) and
        an empty cache/pool — the watchdog `engine_factory` for this
        engine class (`engine_factory=broken_engine.respawn`)."""
        return MLPLMEngine(**self._init_kwargs)
