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
- `sampled_step` is a round's one compiled step, ending in the NaN screen,
  the head over the sampled rows and the sampler (`ops/sampling.with_tail`);
  `ragged_step` is the same stack with the head over every row (a program
  of its own, `ops/sampling.all_rows`), `verify_step` a case of the stack
  and `generate` a host loop over `ragged_step`
  (`inference/generate.py`). Guard slots
  (`q_len` 0) write nothing and reach no expert.
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
import numpy as np

from ..framework import monitor
from ..models import deepseek_v3 as dsv3
from ..observability import compile_trace
from ..ops import sampling
from ..ops.pallas import paged_attention_mla as pm
from ..ops.pallas.paged_attention import ragged_metadata
from . import kv_migrate, live_prefix
from .cache import BlockCacheManager
from .generate import generate

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
    live = tok_pos >= 0
    lanes = q_lens.shape[0]
    n_live = jnp.sum(q_lens.astype(jnp.int32))
    rowwise = live_prefix.rowwise(n_live, lanes if narrow else None, t)
    pos = jnp.maximum(tok_pos, 0)
    # a guard slot's row goes to a block past the pool: the scatter drops it
    blk = jnp.where(live, tables[tok_lane, pos // bs], jnp.int32(nb))
    off = pos % bs
    with jax.named_scope("llama.rope"):
        cos = jnp.take(params["rope_cos"], pos, axis=0)
        sin = jnp.take(params["rope_sin"], pos, axis=0)
    with jax.named_scope("llama.embed"):
        x = jnp.take(params["model.embed_tokens.weight"], tokens, axis=0)

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
                                  sin, attend_layer(i), live, rowwise)
        sizes.append(jnp.zeros((cfg.n_routed_experts,), jnp.int32)
                     if n is None else n)
    sizes = jnp.stack(sizes)                                     # [L, E]
    counters = {
        "tokens": counters["tokens"] + sizes,
        "touched": counters["touched"] + jnp.sum(sizes > 0, axis=1,
                                                 dtype=jnp.int32),
        "steps": counters["steps"] + 1,
        "narrow_steps": counters["narrow_steps"] + (
            (n_live <= lanes).astype(jnp.int32) if narrow else 0),
    }
    return x, pool, counters


def _head(state, x, lane, *, cfg):
    """The `head` of `ops/sampling.with_tail`: the final norm and the output
    matmul over the rows it is given; `state[0]` is the params."""
    return dsv3.head(x, state[0], cfg)


def _verify_fn(params, pool, counters, tokens, ctx_lens, tables, *, cfg):
    """Speculative verify as a case of the ragged step: every lane a fixed
    window of S tokens; logits fold back to `[B, S, V]`."""
    monitor.inc("serving.verify_retraces")        # trace-time only
    b, s = tokens.shape
    x, pool, counters = _ragged_stack(
        params, pool, counters, tokens.reshape(b * s),
        jnp.full((b,), s, jnp.int32), ctx_lens, tables, cfg=cfg)
    return dsv3.head(x, params, cfg).reshape(b, s, -1), pool, counters


class DeepseekV3InferenceEngine:
    """`EngineCore` over `DeepseekV3ForCausalLM` with a paged latent cache.
    Serves in the dtype the model's weights have."""

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

        stack = functools.partial(_ragged_stack, cfg=cfg, narrow=True)
        head = functools.partial(_head, cfg=cfg)
        verify = functools.partial(_verify_fn, cfg=cfg)
        verify.__name__ = _verify_fn.__name__      # the XLA module's name
        # the screen, the row gather, the head over the sampled rows and
        # the sampler end the round's one program (`ops/sampling.with_tail`);
        # `_logits` is the same stack with the head over every row,
        # compiled when `ragged_step` first calls it
        self._ragged = jax.jit(sampling.with_tail(stack, head),
                               donate_argnums=(1, 2))
        self._logits = jax.jit(sampling.all_rows(stack, head),
                               donate_argnums=(1, 2))
        self.last_sampled = None    # the last step's `sampled`, on device
        self._verify = jax.jit(verify, donate_argnums=(1, 2))
        # COW copy (prefix caching): one latent block, every layer, donated;
        # src/dst trace as scalars, so COWs never recompile
        self._copy_block = jax.jit(
            lambda p, s, d: p.at[:, d].set(p[:, s]), donate_argnums=(0,))
        compile_trace.stamp("engine.build", began)

    # ---- the EngineCore dispatch surface ----
    def sampled_step(self, tokens: np.ndarray, lanes: np.ndarray,
                     block_tables: np.ndarray, temperature: np.ndarray):
        """ONE fixed-shape step over a packed ragged batch, sampled (see
        `EngineCore.sampled_step`): `sampled [2, B] int32`, on the device."""
        self.last_sampled = self._run(
            self._ragged, *sampling.call_arrays(
                tokens, lanes, block_tables, temperature, self.last_sampled))
        return self.last_sampled

    def _run(self, fn, *arrays):
        """One of the step programs over this engine's state, which it
        replaces; what the program returns ahead of it."""
        out, self.pool, self.counters = fn(self.params, self.pool,
                                           self.counters, *arrays)
        return out

    ragged_step = sampling.ragged_step

    def verify_step(self, tokens: np.ndarray, context_lens: np.ndarray,
                    block_tables: np.ndarray):
        """Multi-token verify (see `EngineCore.verify_step`): `[B, S, V]`."""
        return self._run(
            self._verify, np.asarray(tokens, np.int32),
            np.asarray(context_lens, np.int32),
            np.asarray(block_tables, np.int32))

    generate = generate

    # ---- hooks the scheduler and the cache manager look for ----
    def copy_kv_block(self, src: int, dst: int) -> None:
        """Copy one physical latent block, all layers (the manager's COW
        hook when prefix caching is on)."""
        self.pool = self._copy_block(self.pool, np.int32(src), np.int32(dst))

    def kv_bytes_per_token(self) -> float:
        """HBM bytes one cached token costs across all layers: one latent
        row a layer, as stored."""
        return float(self.config.num_hidden_layers * self._row_bytes)

    def quant_info(self) -> dict:
        """What `serving.quant.*` and `serving.kv_bytes_per_token` publish."""
        return {"wbits": 16, "kv_bits": 16,
                "kv_bytes_per_token": self.kv_bytes_per_token()}

    def cost_card_args(self, phase: str):
        fn = {"decode": self._ragged, "ragged": self._ragged,
              "verify": self._verify}[phase]
        return fn, (self.params, self.pool, self.counters)

    def extract_kv_blocks(self, seq_id: int):
        raise kv_migrate.KVMigrationError(
            f"{FAMILY}: a latent (MLA) cache has no migration payload yet")

    def inject_kv_blocks(self, seq_id: int, payload) -> None:
        raise kv_migrate.KVMigrationError(
            f"{FAMILY}: a latent (MLA) cache has no migration payload yet")

    # ---- expert load ----
    def expert_load(self) -> dict:
        """The counters the step keeps on the device, fetched now: `tokens
        [L, E]` routed to each expert since the engine was built, `touched
        [L]` experts with at least one token summed over steps, `steps`,
        `narrow_steps` (those whose row-wise work ran over the live prefix).
        Publishes `serving.moe.expert_tokens` (their sum) and the gauges
        `serving.moe.load_max_over_mean` (busiest expert of an expert layer
        against the mean one) and `serving.step.live_prefix_share`
        (`narrow_steps / steps`)."""
        c = jax.device_get(self.counters)
        tokens = np.asarray(c["tokens"], np.int64)
        moe = tokens[self.config.first_k_dense_replace:]
        monitor.set_value("serving.moe.expert_tokens", int(moe.sum()))
        if moe.sum():
            monitor.set_gauge("serving.moe.load_max_over_mean",
                              round(float(moe.max() / moe.mean()), 3))
        steps, narrow = int(c["steps"]), int(c["narrow_steps"])
        if steps:
            monitor.set_gauge("serving.step.live_prefix_share",
                              round(narrow / steps, 4))
        return {"tokens": tokens, "touched": np.asarray(c["touched"], np.int64),
                "steps": steps, "narrow_steps": narrow}
