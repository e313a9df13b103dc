"""Serving engine for the DeepSeek-V3 architecture (`models/deepseek_v3.py`):
an `EngineCore` whose paged pool holds ONE latent row a token a layer.

- The weights are the model's own pytree, taken by reference in the layout
  they are served in (routed experts stacked `[E, in, out]`): nothing is
  stacked, concatenated or cast at build, so weights + pool are all the
  device holds.
- The pool is one donated array `[L, NB, BS, row]`, written in place; the
  layer is an index. It never enters a `lax.scan` as per-layer inputs and
  stacked outputs (what cost the Llama engine 84 % of its step until its
  pool became the scan's carry, PERF.md PR 28): here the layers are
  unrolled and each scatters into, and reads from, the one buffer. `row` is the latent width rounded up to whole 128-lane tiles
  (576 -> 640, zero columns; `ops/pallas/paged_attention_mla.py` says why).
- The `EngineCore` surface and the three programs are the shell's
  (`inference/step_engine.StepEngine`); this file holds the stack, the
  head and the pool's layout. Guard slots (`q_len` 0) write nothing and
  reach no expert.
- Expert load is counted inside the step, on the device, in donated
  counters: no host fetch a step. `expert_load()` reads them.

The engine transforms (`quantize_engine`, `shard_engine`, `attach_adapters`)
look for a Llama or an MLP parameter layout and refuse this engine by its
name; KV migration is refused here, by family.
"""
from __future__ import annotations

import functools
import time
from typing import Dict

import jax
import jax.numpy as jnp

from ..models import deepseek_v3 as dsv3
from ..ops.pallas import paged_attention_mla as pm
from ..ops.pallas.paged_attention import ragged_metadata
from . import live_prefix, step_engine
from .cache import BlockCacheManager

__all__ = ["DeepseekV3InferenceEngine"]

FAMILY = "deepseek_v3"


def _ragged_stack(params, pool, counters, tokens, q_lens, kv_lens, tables,
                  *, cfg: dsv3.DeepseekV3Config, narrow: bool = False):
    """Packed tokens `[T]` + per-lane `(q_len, kv_len)` through the decoder:
    `(hidden [T, H] before the final norm, pool, counters)`, the `stack` of
    `ops/sampling.with_tail`. `narrow`: a step whose live
    rows number at most its lanes runs the layers' row-wise segments over
    that prefix of the packed buffer (`live_prefix.rowwise`; the choice is
    made on the device, from `q_lens`)."""
    t = tokens.shape[0]
    nb, bs, row = pool.shape[1:]
    rank = cfg.kv_lora_rank
    kv_lens = kv_lens.astype(jnp.int32)
    tables = tables.astype(jnp.int32)
    tok_lane, tok_pos = ragged_metadata(q_lens, kv_lens, t)
    step = live_prefix.prologue(q_lens, tok_pos, narrow)
    pos = jnp.maximum(tok_pos, 0)
    # a guard slot's row goes to a block past the pool: the scatter drops it
    blk = jnp.where(step.live, tables[tok_lane, pos // bs], jnp.int32(nb))
    off = pos % bs
    cos, sin, x = step_engine.token_rows(params, tokens, pos)

    # the kernel takes the packed buffer as the query segment leaves it
    # (`attend.pack`: `pm.mla_prepare`, placed by the segment's `rowwise`)
    # and hands its own buffer to the next one
    packed = pm.mla_supported((t, cfg.num_attention_heads, cfg.latent_dim),
                              pool.shape, pool.dtype, tables.shape[1], rank)

    def attend_layer(i):
        def attend(q_abs, rows):
            nonlocal pool
            with jax.named_scope("llama.kv_write"):
                rows = jnp.pad(rows.astype(pool.dtype),
                               ((0, 0), (0, row - rows.shape[-1])))
                pool = pool.at[i, blk, off].set(rows, mode="drop")
            with jax.named_scope("llama.attn"):
                kernel = pm.paged_attention_mla_packed if packed \
                    else pm.paged_attention_mla_ref
                return kernel(q_abs, pool, jnp.int32(i), tables, kv_lens,
                              tok_lane, tok_pos, rank,
                              cfg.qk_head_dim ** -0.5)
        if packed:
            attend.pack = lambda q_abs: pm.mla_prepare(q_abs, pool)
        return attend

    sizes = []
    for i in range(cfg.num_hidden_layers):
        x, n = dsv3.decoder_layer(x, dsv3.layer_params(params, i), cfg, cos,
                                  sin, attend_layer(i), step.live,
                                  step.rowwise)
        sizes.append(jnp.zeros((cfg.n_routed_experts,), jnp.int32)
                     if n is None else n)
    return x, pool, live_prefix.moe_counters(counters, sizes, step)


def _head(state, x, lane, *, cfg):
    """The `head` of `ops/sampling.with_tail`: the final norm and the output
    matmul over the rows it is given; `state[0]` is the params."""
    return dsv3.head(x, state[0], cfg)


class DeepseekV3InferenceEngine(step_engine.BlockCopy,
                                step_engine.StepEngine):
    """`EngineCore` over `DeepseekV3ForCausalLM` with a paged latent cache.
    Serves in the dtype the model's weights have."""

    FAMILY = FAMILY
    DONATED = ("pool", "counters")
    NO_MIGRATION = "a latent (MLA) cache has no migration payload yet"

    def __init__(self, model: dsv3.DeepseekV3ForCausalLM,
                 max_batch_size: int = 8, num_blocks: int = 256,
                 block_size: int = 16, max_blocks_per_seq: int = 16):
        began = time.time()     # `engine.build_s`: this line to the last
        cfg = model.config
        self.config = cfg
        self.block_size = block_size
        self.max_batch_size = max_batch_size
        self.manager = BlockCacheManager(num_blocks, block_size,
                                         max_blocks_per_seq)
        cos, sin = dsv3.rope_tables(cfg, max_blocks_per_seq * block_size)
        # the model's own arrays, by reference, beside the rope tables
        self.params: Dict[str, jax.Array] = dict(
            model.weight_tree(), rope_cos=cos, rope_sin=sin)
        cdtype = self.params["model.embed_tokens.weight"].dtype
        L, e = cfg.num_hidden_layers, cfg.n_routed_experts
        self.row_width = -(-cfg.latent_dim // 128) * 128
        self.pool = jnp.zeros((L, num_blocks, block_size, self.row_width),
                              cdtype)
        self.counters = {"tokens": jnp.zeros((L, e), jnp.int32),
                         "touched": jnp.zeros((L,), jnp.int32),
                         "steps": jnp.zeros((), jnp.int32),
                         "narrow_steps": jnp.zeros((), jnp.int32)}
        self._row_bytes = self.row_width * jnp.dtype(cdtype).itemsize
        self.manager.set_kv_geometry(L * block_size * self._row_bytes, 16)

        # COW copy (prefix caching): one latent block, every layer
        self._build_block_ops(1)
        self._build_programs(
            functools.partial(_ragged_stack, cfg=cfg, narrow=True),
            functools.partial(_head, cfg=cfg),
            window=functools.partial(_ragged_stack, cfg=cfg), began=began)

    # ---- hooks the scheduler and the cache manager look for ----
    def kv_bytes_per_token(self) -> float:
        """HBM bytes one cached token costs across all layers: one latent
        row a layer, as stored."""
        return float(self.config.num_hidden_layers * self._row_bytes)

    # ---- expert load ----
    def expert_load(self) -> dict:
        """The step's device-side counters, fetched now
        (`step_engine.expert_load`: every expert is held here, so no
        `held_assignment_share` and no `"held"`)."""
        return step_engine.expert_load(
            self.counters, None, self.config.first_k_dense_replace)
