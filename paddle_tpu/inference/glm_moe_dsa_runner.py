"""Serving engine for the GLM-MoE-DSA architecture (`models/glm_moe_dsa.py`):
an `EngineCore` with TWO paged pools on ONE block table, and a selection that
travels from a layer with an indexer to the layers that share it.

- The pools are one donated tuple `(latent, index)`: `latent [L, NB, BS, row]`
  holds one latent row a token a layer (`deepseek_v3_runner`'s pool: `row` is
  the latent width rounded up to whole 128-lane tiles), `index [F, NB, BS,
  index_head_dim]` one indexer key a token and `full` layer. A block id names
  the same `BS` tokens in both, so the cache manager knows one group: a block
  is taken, freed, shared and copied in both pools at once.
- A `full` layer scores each live row against its lane's live pages of the
  index pool (`ops/pallas/dsa.dsa_index_scores`) and keeps the `index_topk`
  best positions (`ops/pallas/dsa.dsa_select`: a threshold found by
  bisection over the scores' bits and a placement by rank, no sort; the
  scope `llama.dsa_topk` says what it costs; a tile of rows that all lie in
  the first quarter of the table's span selects over that quarter,
  `NEAR_SHARE`); the selection `(idx [T, K], n [T])`, `idx` in POSITION
  order over its first `n` (`models/glm_moe_dsa.select`'s set, not its
  order: nothing downstream reads an order), is carried, inside the one
  compiled step, to the `shared` layers after it.
- EVERY row attends over its selected rows, gathered: `sparse_rows` reads `K`
  latent rows through the block table (`row_ids`, worked out once a
  selection, not once a layer), `mla_sparse_attention` takes their softmax
  whole. So attention's bytes and FLOPs follow `K` and not the
  context, for a decode lane and for a row of a prefill chunk alike; a chunk
  could instead walk its pages under a mask (fewer bytes, more FLOPs: about
  even at the benchmark's contexts, PERF.md section 7), but one path serves a
  decode lane, a chunk, a verify window and a resumed lane with one kernel.
- The rows are walked a tile of at most `lanes` at a time (`_live_tiles`), and
  only the tiles that hold a live row: a round of decode lanes alone scores,
  selects and gathers for its lanes, not for the chunk's guard rows.
- Expert load and selection load are counted inside the step, on the
  device, in donated counters; `expert_load()` / `selection_load()` read them.
- `attention_witness` is a THIRD program over the same stack, compiled when
  first called and never by a served round: one decode row a lane, and per
  layer what that row's attention was given (the selection) and what the
  attention sub-block made of it. An operator or a check replays a cached
  position through it; the served step carries nothing for it.

The `EngineCore` surface and the three served programs are the shell's
(`inference/step_engine.StepEngine`); this file holds the stack, the head,
the two pools' layout and the witness. The radix prefix cache works over
this engine (`copy_kv_block` copies a block
in both pools: an indexer key is a function of its token's prefix as a latent
row is) and so does speculative decoding (`verify_step` is a case of the
stack: every row of a window selects for itself). The engine transforms
(`quantize_engine`, `shard_engine`, `attach_adapters`) refuse this engine by
its name; KV migration is refused here, by family.
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
from ..models import glm_moe_dsa as glm
from ..ops.pallas import _support, dsa
from ..ops.pallas.paged_attention import ragged_metadata
from . import live_prefix, step_engine
from .cache import BlockCacheManager

__all__ = ["GlmMoeDsaInferenceEngine"]

FAMILY = "glm_moe_dsa"
POOLS = ("latent", "index")
# the selection takes its time from the positions it is given, not from the
# live ones: rows in the first 1 / NEAR_SHARE of the table's span select
# over that part (most of a prefill, whose rows pass every depth)
NEAR_SHARE = 4


def _tile_rows(t: int, lanes: int) -> int:
    """Rows a tile of the context-owning loops: the largest divisor of the
    packed buffer's `t` slots that is at most the lane count."""
    return max(d for d in range(1, min(t, lanes) + 1) if t % d == 0)


def _live_tiles(n_live, rows: int, t: int, fn, outs):
    """`fn(r0) -> tuple of [rows, ...]` over the tiles `[r0, r0 + rows)` of
    a packed buffer of `t` slots that hold a live row (the first `n_live`
    slots are the live ones), written into `outs` (`[t, ...]`: blank where
    only live rows are read of them, zeros where a reduction reads them
    whole): a loop whose trip count the device takes from the step's own
    `q_lens`."""
    def body(i, outs):
        r0 = i * jnp.int32(rows)
        return tuple(jax.lax.dynamic_update_slice_in_dim(o, g, r0, 0)
                     for o, g in zip(outs, fn(r0)))

    return jax.lax.fori_loop(0, (n_live + rows - 1) // rows, body, outs)


def _ragged_stack(params, pools, counters, tokens, q_lens, kv_lens, tables,
                  *, cfg: glm.GlmMoeDsaConfig, narrow: bool = False,
                  witness: list = None):
    """Packed tokens `[T]` + per-lane `(q_len, kv_len)` through the decoder:
    `(hidden [T, H] before the final norm, pools, counters)`, the `stack` of
    `ops/sampling.with_tail`. `narrow`: a step whose live rows number at
    most its lanes runs the layers' row-wise segments over that prefix of
    the packed buffer (`live_prefix.rowwise`; the choice is made on the
    device, from `q_lens`). `witness`: a list that takes, a layer, `(idx
    [B, K], n [B], out [B, H] float32)` at each lane's last packed row: the
    selection the layer's attention was given there and the attention
    sub-block's output (`_witness_fn`); a served step is traced without."""
    t = tokens.shape[0]
    latent, index = pools
    nb, bs, row = latent.shape[1:]
    rank, topk = cfg.kv_lora_rank, cfg.index_topk
    kv_lens = kv_lens.astype(jnp.int32)
    tables = tables.astype(jnp.int32)
    tok_lane, tok_pos = ragged_metadata(q_lens, kv_lens, t)
    step = live_prefix.prologue(q_lens, tok_pos, narrow)
    live, n_live = step.live, step.n_live
    tile = _tile_rows(t, q_lens.shape[0])
    span = tables.shape[1] * bs
    k_sel = min(topk, span)
    near = span // NEAR_SHARE
    pos = jnp.maximum(tok_pos, 0)
    # a guard slot's rows go to a block past the pools: the scatter drops it
    blk = jnp.where(live, tables[tok_lane, pos // bs], jnp.int32(nb))
    off = pos % bs
    cos, sin, x = step_engine.token_rows(params, tokens, pos)

    def cut(a, r0):
        return jax.lax.dynamic_slice_in_dim(a, r0, tile, 0)

    # the index-score kernel takes the packed buffers as the query segment
    # leaves them (`pick.pack`: `dsa.index_prepare`, placed by the
    # segment's `rowwise`)
    packed = dsa.index_supported(
        (t, cfg.index_n_heads, cfg.index_head_dim), index.shape, index.dtype,
        tables.shape[1])

    def index_layer(n):
        def pick(q_i, k_i, w):
            nonlocal index
            with jax.named_scope("llama.dsa_index_write"):
                index = index.at[n, blk, off].set(k_i.astype(index.dtype),
                                                  mode="drop")
            with jax.named_scope("llama.dsa_index_scores"):
                kernel = dsa.dsa_index_scores_packed if packed \
                    else dsa.dsa_index_scores_ref
                scores = kernel(q_i, w, index, n, tables, kv_lens, tok_lane,
                                tok_pos)
            with jax.named_scope("llama.dsa_topk"):
                def select_tile(r0):
                    at = cut(tok_pos, r0)

                    def over(positions):
                        return lambda: dsa.dsa_select(
                            dsa.score_tile(scores, r0, tile, positions), at,
                            k_sel)

                    # a tile whose rows all lie in the table's first
                    # quarter selects over that quarter alone: what lies
                    # past a row's own position is no candidate
                    if near < k_sel:
                        return over(span)()
                    return jax.lax.cond(jnp.max(at) < near, over(near),
                                        over(span))

                # `n` is summed whole (`dsa_selected`): zeros past the
                # live tiles; of `idx` a live tile's rows alone are read
                return _live_tiles(
                    n_live, tile, t, select_tile,
                    (_support.blank((t, k_sel), jnp.int32),
                     jnp.zeros((t,), jnp.int32)))
        if packed:
            pick.pack = lambda q_i, w: dsa.index_prepare(q_i, w, index)
        return pick

    ids_of = {}     # a selection's `(idx, row ids)`, by `id(idx)`

    def attend_layer(i):
        def attend(q_abs, rows, selection):
            nonlocal latent
            idx, n = selection
            # where the selected rows lie is the same in every layer: the
            # first layer handed a selection works it out beside its own
            # gather, the layers that share it read it
            known = ids_of.get(id(idx))
            with jax.named_scope("llama.kv_write"):
                rows = jnp.pad(rows.astype(latent.dtype),
                               ((0, 0), (0, row - rows.shape[-1])))
                latent = latent.at[i, blk, off].set(rows, mode="drop")
            with jax.named_scope("llama.attn_sparse"):
                kernel = dsa.mla_sparse_attention if dsa.sparse_supported(
                    (tile,) + q_abs.shape[1:], (tile, k_sel, row),
                    latent.dtype, rank) else dsa.mla_sparse_attention_ref

                def rows_of(r0):
                    ids = cut(known[1], r0) if known else dsa.row_ids(
                        tables, cut(tok_lane, r0), cut(idx, r0), bs)
                    out = kernel(cut(q_abs, r0),
                                 dsa.sparse_rows(latent, i, ids), cut(n, r0),
                                 rank, cfg.qk_head_dim ** -0.5)
                    return (out,) if known else (out, ids)

                outs = (_support.blank(q_abs.shape[:2] + (rank,),
                                       q_abs.dtype),)
                if not known:
                    outs += (_support.blank(idx.shape, jnp.int32),)
                o_lat, *ids = _live_tiles(n_live, tile, t, rows_of, outs)
                if ids:
                    ids_of[id(idx)] = (idx, ids[0])
            if witness is not None:
                at = jnp.maximum(jnp.cumsum(q_lens.astype(jnp.int32)) - 1, 0)
                witness.append((idx[at], n[at], dsv3.mla_output(
                    o_lat[at], dsv3.layer_params(params, i), cfg,
                    jnp.float32)))
            return o_lat
        return attend

    sizes, carried = [], None
    picked = candidates = jnp.zeros((), jnp.int32)
    full = {layer: n for n, layer in enumerate(cfg.full_layers)}
    for i, kind in enumerate(cfg.indexer_types):
        x, n, carried = glm.decoder_layer(
            x, dsv3.layer_params(params, i), cfg, kind, cos, sin,
            index_layer(full.get(i)), attend_layer(i), carried, live,
            step.rowwise)
        sizes.append(jnp.zeros((cfg.n_routed_experts,), jnp.int32)
                     if n is None else n)
        if kind == glm.FULL:
            picked = picked + jnp.sum(carried[1])
            candidates = candidates + jnp.sum(tok_pos + 1)
    counters = dict(
        live_prefix.moe_counters(counters, sizes, step, cfg.held),
        # float32: a step's candidates pass 2^31 in two hundred steps
        dsa_selected=counters["dsa_selected"] + picked.astype(jnp.float32),
        dsa_candidates=counters["dsa_candidates"]
        + candidates.astype(jnp.float32))
    return x, (latent, index), counters


def _head(state, x, lane, *, cfg):
    """The `head` of `ops/sampling.with_tail`: the final norm and the output
    matmul over the rows it is given; `state[0]` is the params."""
    return glm.head(x, state[0], cfg)


def _witness_fn(params, pools, counters, tokens, kv_lens, tables, *, cfg):
    """One decode row a lane (`kv_lens` 0: an idle lane) through the stack
    with its witness: `({"idx" [L, B, K], "n" [L, B], "out" [L, B, H]},
    pools, counters)`."""
    seen = []
    live = kv_lens > 0
    # the packed buffer holds the live lanes' rows first, in lane order
    packed = tokens[jnp.argsort(~live, stable=True)]
    _, pools, counters = _ragged_stack(
        params, pools, counters, packed, live.astype(jnp.int32), kv_lens,
        tables, cfg=cfg, witness=seen)
    idx, n, out = (jnp.stack(a) for a in zip(*seen))
    return {"idx": idx, "n": n, "out": out}, pools, counters


class GlmMoeDsaInferenceEngine(step_engine.BlockCopy,
                               step_engine.StepEngine):
    """`EngineCore` over `GlmMoeDsaForCausalLM` with a paged latent cache and
    a paged index cache on one block table. Serves in the dtype the model's
    weights have."""

    FAMILY = FAMILY
    DONATED = ("pools", "counters")
    NO_MIGRATION = ("a block of two pools (latent rows and indexer keys) "
                    "has no migration payload yet")
    PHASES = dict(step_engine.StepEngine.PHASES, witness="_witness")

    def __init__(self, model: glm.GlmMoeDsaForCausalLM,
                 max_batch_size: int = 8, num_blocks: int = 256,
                 block_size: int = 16, max_blocks_per_seq: int = 16):
        began = time.time()     # `engine.build_s`: this line to the last
        cfg = model.config
        self.config = cfg
        self.block_size = block_size
        self.max_batch_size = max_batch_size
        self.manager = BlockCacheManager(num_blocks, block_size,
                                         max_blocks_per_seq, name=POOLS[0])
        cos, sin = dsv3.rope_tables(cfg, max_blocks_per_seq * block_size)
        # the model's own arrays, by reference, beside the rope tables
        self.params: Dict[str, jax.Array] = dict(
            model.weight_tree(), rope_cos=cos, rope_sin=sin)
        cdtype = self.params["model.embed_tokens.weight"].dtype
        L, e = cfg.num_hidden_layers, cfg.n_routed_experts
        self.row_width = -(-cfg.latent_dim // 128) * 128
        self.pools = (
            jnp.zeros((L, num_blocks, block_size, self.row_width), cdtype),
            jnp.zeros((len(cfg.full_layers), num_blocks, block_size,
                       cfg.index_head_dim), cdtype))
        self.counters = {"tokens": jnp.zeros((L, e), jnp.int32),
                         "touched": jnp.zeros((L,), jnp.int32),
                         "steps": jnp.zeros((), jnp.int32),
                         "narrow_steps": jnp.zeros((), jnp.int32),
                         "dsa_selected": jnp.zeros((), jnp.float32),
                         "dsa_candidates": jnp.zeros((), jnp.float32)}
        itemsize = jnp.dtype(cdtype).itemsize
        self._pool_bytes_per_token = {
            POOLS[0]: L * self.row_width * itemsize,
            POOLS[1]: len(cfg.full_layers) * cfg.index_head_dim * itemsize}
        self.manager.set_kv_geometry(
            block_size * sum(self._pool_bytes_per_token.values()), 16)

        self._witness = jax.jit(functools.partial(_witness_fn, cfg=cfg),
                                donate_argnums=(1, 2))
        # COW copy (prefix caching): one block of BOTH pools, every layer
        self._build_block_ops(1)
        self._build_programs(
            functools.partial(_ragged_stack, cfg=cfg, narrow=True),
            functools.partial(_head, cfg=cfg),
            window=functools.partial(_ragged_stack, cfg=cfg), began=began)

    # ---- hooks the scheduler and the cache manager look for ----
    def kv_bytes_per_token(self, pool: str = None) -> float:
        """HBM bytes one cached token costs: in `pool` (`"latent"`: one row
        a layer as stored; `"index"`: one key a `full` layer), or in both."""
        if pool is None:
            return float(sum(self._pool_bytes_per_token.values()))
        return float(self._pool_bytes_per_token[pool])

    def quant_info(self) -> dict:
        """What `serving.quant.*` and `serving.kv_bytes_per_token[.<pool>]`
        publish."""
        return dict(super().quant_info(), kv_bytes_per_token_by_group=dict(
            self._pool_bytes_per_token))

    # ---- the device-side counters ----
    def expert_load(self) -> dict:
        """The step's device-side counters, fetched now
        (`step_engine.expert_load`, over the held experts of the expert
        layers: `tokens` counts the ROUTER's experts, held and absent
        alike)."""
        return step_engine.expert_load(
            self.counters, self.config.held,
            self.config.first_k_dense_replace)

    def attention_witness(self, tokens: np.ndarray, context_lens: np.ndarray,
                          block_tables: np.ndarray) -> dict:
        """What each layer's attention does at ONE decode row a lane: lane b
        feeds `tokens[b]` at position `context_lens[b] - 1` of the sequence
        `block_tables[b]` names (0: an idle lane), exactly as a served decode
        round would. Per layer `idx [L, B, K]` of which the first `n [L, B]`
        count, the positions the row's attention was given, and `out [L, B,
        H]` float32, the attention sub-block's output there (before the
        residual). A position already cached is REPLAYED: its rows are
        written again with what they held (a row is a function of its
        prefix), so a check can ask after a window what its decode rounds
        read. The program is compiled at the first call; the engine's
        counters count its rows like any step's."""
        out = self._run(self._witness, np.asarray(tokens, np.int32),
                        np.asarray(context_lens, np.int32),
                        np.asarray(block_tables, np.int32))
        return {k: np.asarray(v) for k, v in jax.device_get(out).items()}

    def selection_load(self) -> dict:
        """Over the live rows of every `full` layer since the engine was
        built: `selected`, the positions their selections hold (`|S_t|`
        summed), and `candidates`, the positions they were picked from (`t +
        1` summed). Publishes the gauge `serving.dsa.selected_share`
        (`selected / candidates`: 1 while no context passes `index_topk`)."""
        c = jax.device_get({k: self.counters[k]
                            for k in ("dsa_selected", "dsa_candidates")})
        selected, candidates = (float(c["dsa_selected"]),
                                float(c["dsa_candidates"]))
        if candidates:
            monitor.set_gauge("serving.dsa.selected_share",
                              round(selected / candidates, 4))
        return {"selected": selected, "candidates": candidates}
