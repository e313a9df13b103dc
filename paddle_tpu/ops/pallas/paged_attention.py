"""Pallas TPU paged (block) KV-cache attention — the decode kernel.

TPU-native equivalent of the reference's paged-attention CUDA kernel
(`paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu`, python
surface `incubate.nn.functional.block_multihead_attention`): the KV cache is a
pool of fixed-size blocks; each sequence owns a list of block ids (its block
table), so cache memory is allocated in O(block_size) granules instead of one
max-seqlen slab per sequence.

Kernel design (TPU-first, not a CUDA translation):
- grid = (batch, kv_heads, max_blocks_per_seq); the block table and context
  lengths ride scalar prefetch (SMEM) so the K/V ``BlockSpec`` index maps can
  gather the *physical* block for each (seq, logical-block) pair — the gather
  happens in the pipeline's DMA engine, not in the kernel body.
- GQA is native: the q block is the whole query-head group [G, D] for one kv
  head, so the kernel's matmuls are (G×D)·(D×BS) on the MXU with no KV
  repetition in HBM.
- online softmax (flash-style) accumulates across logical blocks in VMEM
  scratch; the output is written once on the last block step.

Caches use the reference layout ``[num_blocks, kv_heads, block_size, head_dim]``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _support

NEG_INF = -1e30


def _decode_kernel(lens_ref, tables_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, sm_scale, block_size):
    b = pl.program_id(0)
    j = pl.program_id(2)
    nb = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ctx_len = lens_ref[b]

    @pl.when(j * block_size < ctx_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale      # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)                 # (BS, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (G, BS)
        pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        # typed scalar: a python-float NEG_INF weak-types to f64 when the
        # interpret-mode kernel is traced inside an x64-on outer program
        s = jnp.where(pos < ctx_len, s, jnp.float32(NEG_INF))
        m_prev = m_ref[...][:, 0]
        l_prev = l_ref[...][:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new[:, None]
        l_ref[...] = l_new[:, None]

    @pl.when(j == nb - 1)
    def _finish():
        l = l_ref[...][:, 0]
        l_safe = jnp.where(l == 0.0, jnp.float32(1.0), l)
        o_ref[0, 0] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)


def _decode_call(q, k_cache, v_cache, block_tables, context_lens, sm_scale):
    """q: [B, KV_H, G, D] (G padded); caches: [KV_H, NB, BS, D]."""
    batch, kv_h, g, d = q.shape
    block_size = k_cache.shape[2]
    max_blocks = block_tables.shape[1]

    kern = functools.partial(_decode_kernel, sm_scale=sm_scale,
                             block_size=block_size)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch, kv_h, max_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, g, d),
                         lambda b, h, j, lens, tables: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_size, d),
                         lambda b, h, j, lens, tables: (h, tables[b, j], 0, 0)),
            pl.BlockSpec((1, 1, block_size, d),
                         lambda b, h, j, lens, tables: (h, tables[b, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda b, h, j, lens, tables: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
    )
    return _support.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, kv_h, g, d), q.dtype),
        name="paged_attention_decode",
        interpret=_support.interpret_mode(),
    )(context_lens, block_tables, q, k_cache, v_cache)


def paged_attention(q, k_cache, v_cache, block_tables, context_lens,
                    sm_scale=None):
    """Decode-step paged attention over raw arrays.

    Args:
      q: [B, H, D] — one query token per sequence.
      k_cache/v_cache: [num_blocks, kv_heads, block_size, head_dim].
      block_tables: [B, max_blocks_per_seq] int32 physical block ids (pad 0).
      context_lens: [B] int32 — tokens already in cache (incl. current).
    Returns [B, H, D].
    """
    batch, h, d = q.shape
    kv_h = k_cache.shape[1]
    g = h // kv_h
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(d))
    # [B, H, D] -> [B, KV_H, G, D], pad the group dim to the 8-row sublane
    # tile so the MXU matmul has a full tile even for MHA (G=1).
    qg = q.reshape(batch, kv_h, g, d)
    g_pad = max(g, 8)
    if g_pad != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g_pad - g), (0, 0)))
    kc = jnp.swapaxes(k_cache, 0, 1)  # [KV_H, NB, BS, D]
    vc = jnp.swapaxes(v_cache, 0, 1)
    out = _decode_call(qg, kc, vc, block_tables.astype(jnp.int32),
                       context_lens.astype(jnp.int32), float(sm_scale))
    return out[:, :, :g, :].reshape(batch, h, d)


def _verify_kernel(lens_ref, tables_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, sm_scale, block_size,
                   num_queries, g_pad):
    """Multi-query causal decode kernel (speculative-decode verify pass).

    Same online-softmax structure as `_decode_kernel`, but the q block holds
    S query tokens × G head-group rows: row r is query s = r // g_pad, whose
    absolute position is ctx_len - S + s, so its causal limit is
    `pos <= ctx_len - S + s` — one extra iota against the same score tile.
    """
    b = pl.program_id(0)
    j = pl.program_id(2)
    nb = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ctx_len = lens_ref[b]

    @pl.when(j * block_size < ctx_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale      # (S*G, D)
        k = k_ref[0, 0].astype(jnp.float32)                 # (BS, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        # typed scalars: python ints weak-type to i64 when the interpret-
        # mode kernel is traced inside an x64-on outer program (see the
        # NEG_INF note in _decode_kernel)
        qpos = (ctx_len - jnp.int32(num_queries)
                + row // jnp.int32(g_pad))                  # per-row limit
        s = jnp.where(pos <= qpos, s, jnp.float32(NEG_INF))
        m_prev = m_ref[...][:, 0]
        l_prev = l_ref[...][:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new[:, None]
        l_ref[...] = l_new[:, None]

    @pl.when(j == nb - 1)
    def _finish():
        l = l_ref[...][:, 0]
        l_safe = jnp.where(l == 0.0, jnp.float32(1.0), l)
        o_ref[0, 0] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)


def _verify_call(q, k_cache, v_cache, block_tables, context_lens, sm_scale,
                 num_queries, g_pad):
    """q: [B, KV_H, S*Gp, D]; caches: [KV_H, NB, BS, D]."""
    batch, kv_h, rows, d = q.shape
    block_size = k_cache.shape[2]
    max_blocks = block_tables.shape[1]

    kern = functools.partial(_verify_kernel, sm_scale=sm_scale,
                             block_size=block_size, num_queries=num_queries,
                             g_pad=g_pad)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch, kv_h, max_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, rows, d),
                         lambda b, h, j, lens, tables: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_size, d),
                         lambda b, h, j, lens, tables: (h, tables[b, j], 0, 0)),
            pl.BlockSpec((1, 1, block_size, d),
                         lambda b, h, j, lens, tables: (h, tables[b, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, d),
                               lambda b, h, j, lens, tables: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, d), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
        ],
    )
    return _support.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, kv_h, rows, d), q.dtype),
        name="paged_attention_verify",
        interpret=_support.interpret_mode(),
    )(context_lens, block_tables, q, k_cache, v_cache)


def paged_attention_verify(q, k_cache, v_cache, block_tables, context_lens,
                           sm_scale=None):
    """Batched multi-token verify attention over the paged KV cache.

    The speculative-decode verify pass: S tokens per sequence (the pending
    token + K drafts) attend causally against the paged cache, whose last S
    positions are the tokens themselves (already written via
    `write_kv_to_cache`).

    Args:
      q: [B, S, H, D] — query token i of row b sits at absolute position
         context_lens[b] - S + i and attends to positions <= its own.
      k_cache/v_cache: [num_blocks, kv_heads, block_size, head_dim].
      block_tables: [B, max_blocks_per_seq] int32 physical block ids.
      context_lens: [B] int32 — tokens in cache INCLUDING all S new ones.
    Returns [B, S, H, D].
    """
    batch, s, h, d = q.shape
    kv_h = k_cache.shape[1]
    g = h // kv_h
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(d))
    # [B, S, H, D] -> [B, KV_H, S*Gp, D]: group queries by kv head, pad the
    # group dim so each query's row band is sublane-aligned and the kernel
    # can recover the query index as row // g_pad.
    g_pad = g if g % 8 == 0 else (g // 8 + 1) * 8
    qg = jnp.swapaxes(q.reshape(batch, s, kv_h, g, d), 1, 2)  # [B,KVH,S,G,D]
    if g_pad != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, g_pad - g), (0, 0)))
    qg = qg.reshape(batch, kv_h, s * g_pad, d)
    kc = jnp.swapaxes(k_cache, 0, 1)  # [KV_H, NB, BS, D]
    vc = jnp.swapaxes(v_cache, 0, 1)
    out = _verify_call(qg, kc, vc, block_tables.astype(jnp.int32),
                       context_lens.astype(jnp.int32), float(sm_scale),
                       s, g_pad)
    out = out.reshape(batch, kv_h, s, g_pad, d)[:, :, :, :g, :]
    return jnp.swapaxes(out, 1, 2).reshape(batch, s, h, d)


def _ragged_kernel(kv_lens_ref, tables_ref, lane_ref, pos_ref,
                   q_ref, k_ref, v_ref, *rest, sm_scale, block_size):
    """Ragged paged attention: ONE fixed-shape kernel for mixed
    prefill-chunk + decode + verify batches.

    The grid iterates fixed-shape token tiles over a PACKED query buffer:
    tile t is one query token's head-group band [g_pad, D] (so a decode
    lane costs exactly one tile and a 32-token prefill chunk costs 32 —
    zero bucket padding). Per-token scalar-prefetch metadata maps every
    tile to its owning sequence lane (`lane_ref`) and absolute position
    (`pos_ref`, -1 for guard/empty token slots); the per-lane
    `(kv_len, q_len, q_start)` prefix sums are folded into those two
    arrays on the host/XLA side. Causal masking per tile is
    `kv_pos <= pos_ref[t]`; guard tiles (pos -1, or a lane with
    kv_len == 0) compute nothing and emit zeros via the l_safe finish.
    Same online-softmax structure as `_decode_kernel` — the decode and
    verify kernels are special cases of this one (q_len==1 / q_len==S).

    Quantized KV (`inference/kv_quant.py` layout): `rest` then leads with
    the block's per-slot f32 scale rows `ks_ref`/`vs_ref`, each (1, BS),
    and K/V arrive as int8 — the bf16/f32 KV never exists in HBM, which
    is the point: a decode step is KV-bandwidth-bound, so halving the
    bytes read halves the step's HBM traffic. The per-slot scale is
    constant along D, so it factors out of both contractions and is
    applied on the (Gp, BS) score tile: `(q.k_int) * ks` before the
    softmax and `p * vs` before `p @ v_int` — the same maths as
    dequantizing the (BS, D) blocks, with the scale as a lane-aligned
    row instead of a sublane column."""
    if len(rest) == 6:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        ks_ref = vs_ref = None
        o_ref, acc_ref, m_ref, l_ref = rest
    t = pl.program_id(0)
    j = pl.program_id(2)
    nb = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lane = lane_ref[t]
    ctx_len = kv_lens_ref[lane]
    qpos = pos_ref[t]

    @pl.when((j * block_size < ctx_len) & (qpos >= 0))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale      # (Gp, D)
        k = k_ref[0, 0].astype(jnp.float32)                 # (BS, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if ks_ref is not None:
            s = s * ks_ref[0, 0]                            # (1, BS) row
        pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        # typed scalar: see the NEG_INF note in _decode_kernel
        s = jnp.where(pos <= qpos, s, jnp.float32(NEG_INF))
        m_prev = m_ref[...][:, 0]
        l_prev = l_ref[...][:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + p.sum(axis=-1)
        if vs_ref is not None:
            p = p * vs_ref[0, 0]
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new[:, None]
        l_ref[...] = l_new[:, None]

    @pl.when(j == nb - 1)
    def _finish():
        l = l_ref[...][:, 0]
        l_safe = jnp.where(l == 0.0, jnp.float32(1.0), l)
        o_ref[0, 0] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)


def _ragged_call(q, k_cache, v_cache, block_tables, kv_lens, tok_lane,
                 tok_pos, sm_scale, k_scale=None, v_scale=None):
    """q: [T, KV_H, Gp, D] packed tokens; caches: [KV_H, NB, BS, D]
    (int8 when the f32 scale planes [KV_H, NB, 1, BS] ride along)."""
    tokens, kv_h, g_pad, d = q.shape
    block_size = k_cache.shape[2]
    max_blocks = block_tables.shape[1]

    def page(*block):
        return pl.BlockSpec(
            (1, 1) + block,
            lambda t, h, j, lens, tables, lane, pos:
            (h, tables[lane[t], j], 0, 0))

    def band():
        return pl.BlockSpec((1, 1, g_pad, d),
                            lambda t, h, j, lens, tables, lane, pos:
                            (t, h, 0, 0))

    operands = [q, k_cache, v_cache]
    in_specs = [band(), page(block_size, d), page(block_size, d)]
    if k_scale is not None:
        operands += [k_scale, v_scale]
        in_specs += [page(1, block_size), page(1, block_size)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(tokens, kv_h, max_blocks),
        in_specs=in_specs,
        out_specs=band(),
        scratch_shapes=[
            pltpu.VMEM((g_pad, d), jnp.float32),
            pltpu.VMEM((g_pad, 1), jnp.float32),
            pltpu.VMEM((g_pad, 1), jnp.float32),
        ],
    )
    return _support.pallas_call(
        functools.partial(_ragged_kernel, sm_scale=sm_scale,
                          block_size=block_size),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tokens, kv_h, g_pad, d), q.dtype),
        name="paged_attention_ragged",
        interpret=_support.interpret_mode(),
    )(kv_lens, block_tables, tok_lane, tok_pos, *operands)


def ragged_metadata(q_lens, kv_lens, num_tokens):
    """Per-token `(lane, position)` metadata for the packed query buffer.

    q_lens/kv_lens: [B] int32 per-lane token counts (q_len 0 = empty
    lane). Returns (tok_lane [T], tok_pos [T]) int32 where lane i owns
    the packed slots [sum(q_lens[:i]), sum(q_lens[:i+1])) and its token
    j sits at absolute position kv_len - q_len + j; guard slots past
    sum(q_lens) get pos -1 (and lane clamped into range), which gates
    every kernel/ref compute off. Pure jnp — callable inside jit."""
    q_lens = q_lens.astype(jnp.int32)
    kv_lens = kv_lens.astype(jnp.int32)
    ends = jnp.cumsum(q_lens)                                 # [B]
    t_idx = jnp.arange(num_tokens, dtype=jnp.int32)           # [T]
    lane = jnp.searchsorted(ends, t_idx, side="right").astype(jnp.int32)
    valid = t_idx < ends[-1]
    lane = jnp.minimum(lane, q_lens.shape[0] - 1)
    off = t_idx - (ends[lane] - q_lens[lane])
    pos = kv_lens[lane] - q_lens[lane] + off
    return lane, jnp.where(valid, pos, jnp.int32(-1))


def paged_attention_ragged(q, k_cache, v_cache, block_tables, kv_lens,
                           tok_lane, tok_pos, sm_scale=None,
                           k_scale=None, v_scale=None):
    """Ragged paged attention over a packed query token buffer.

    ONE kernel for every serving batch composition: decode lanes
    (q_len 1), prefill chunks (q_len n), and speculative verify windows
    (q_len K+1) share this fixed-shape dispatch — the grid depends only
    on the packed token budget T, never on the batch composition, so the
    serving steady state holds exactly one compiled executable.

    Args:
      q: [T, H, D] — packed query tokens (lane-major, see
         `ragged_metadata`).
      k_cache/v_cache: [num_blocks, kv_heads, block_size, head_dim].
      block_tables: [B, W] int32 physical block ids per lane.
      kv_lens: [B] int32 — tokens in cache per lane INCLUDING this
         dispatch's own tokens (0 for empty lanes).
      tok_lane/tok_pos: [T] int32 per-token owner lane / absolute
         position (-1 = guard slot, output forced to 0).
      k_scale/v_scale: optional f32 [num_blocks, kv_heads, block_size]
         per-slot scale planes for int8 quantized caches
         (`inference/kv_quant.py`): dequantization then happens inside
         the kernel body, right before the MXU.
    Returns [T, H, D]; guard rows are exact zeros.
    """
    tokens, h, d = q.shape
    kv_h = k_cache.shape[1]
    g = h // kv_h
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(d))
    g_pad = g if g % 8 == 0 else (g // 8 + 1) * 8
    qg = q.reshape(tokens, kv_h, g, d)
    if g_pad != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g_pad - g), (0, 0)))
    kc = jnp.swapaxes(k_cache, 0, 1)  # [KV_H, NB, BS, D]
    vc = jnp.swapaxes(v_cache, 0, 1)
    if k_scale is not None:
        # [NB, KV_H, BS] -> [KV_H, NB, 1, BS]: each page's scales become a
        # (1, BS) row, the one block shape Mosaic accepts for them (a
        # (1, BS) block of a [.., NB, BS] plane is neither (8, 128)-
        # divisible nor the array's own last two dims)
        k_scale = jnp.swapaxes(k_scale, 0, 1)[:, :, None, :]
        v_scale = jnp.swapaxes(v_scale, 0, 1)[:, :, None, :]
    out = _ragged_call(qg, kc, vc, block_tables.astype(jnp.int32),
                       kv_lens.astype(jnp.int32),
                       tok_lane.astype(jnp.int32),
                       tok_pos.astype(jnp.int32), float(sm_scale),
                       k_scale, v_scale)
    return out[:, :, :g, :].reshape(tokens, h, d)


# above this many packed tokens the ref tiles its per-token window
# gather: an untiled T x window_capacity gather is O(T * max_seq) memory,
# which a monolithic multi-k-token prefill chunk would blow into GBs
_REF_TOKEN_TILE = 128


def paged_attention_ragged_ref(q, k_cache, v_cache, block_tables, kv_lens,
                               tok_lane, tok_pos, sm_scale=None,
                               k_scale=None, v_scale=None):
    """XLA reference for the ragged kernel (also the CPU fallback).

    Same gather + masked-softmax structure as `paged_attention_ref`, per
    packed token; guard rows (tok_pos < 0) come back exactly zero. Large
    packed buffers (T > _REF_TOKEN_TILE) stream through `lax.map` token
    tiles so the gathered windows stay bounded — each row's reduction is
    unchanged, only how many rows are materialized at once.

    `k_scale`/`v_scale` (f32 [NB, KVH, BS]) mark int8 quantized caches:
    the gathered per-lane windows dequantize right after the gather —
    only the gathered window is ever materialized in float, never the
    pool."""
    tokens, h, d = q.shape
    nb, kv_h, bs, _ = k_cache.shape
    g = h // kv_h
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(d))
    k = jnp.take(k_cache, block_tables, axis=0)   # [B, W, KV_H, BS, D]
    v = jnp.take(v_cache, block_tables, axis=0)
    if k_scale is not None:
        ks = jnp.take(k_scale, block_tables, axis=0)   # [B, W, KV_H, BS]
        vs = jnp.take(v_scale, block_tables, axis=0)
        k = k.astype(jnp.float32) * ks[..., None]
        v = v.astype(jnp.float32) * vs[..., None]
    max_s = block_tables.shape[1] * bs
    k = jnp.swapaxes(k, 2, 3).reshape(block_tables.shape[0], max_s, kv_h, d)
    v = jnp.swapaxes(v, 2, 3).reshape(block_tables.shape[0], max_s, kv_h, d)
    wpos = jnp.arange(max_s, dtype=jnp.int32)

    def tile(args):
        qg, lane, pos = args                      # [t, KV_H, G, D] / [t]
        kt = jnp.take(k, lane, axis=0)            # [t, max_s, KV_H, D]
        vt = jnp.take(v, lane, axis=0)
        s = jnp.einsum("thgd,tshd->thgs", qg.astype(jnp.float32),
                       kt.astype(jnp.float32),
                       preferred_element_type=jnp.float32) * sm_scale
        mask = wpos[None, :] <= pos[:, None]                 # [t, max_s]
        s = jnp.where(mask[:, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("thgs,tshd->thgd", p, vt.astype(jnp.float32))
        return jnp.where((pos >= 0)[:, None, None, None], out, 0.0)

    qg = q.reshape(tokens, kv_h, g, d)
    if tokens <= _REF_TOKEN_TILE:
        out = tile((qg, tok_lane, tok_pos))
        return out.reshape(tokens, h, d).astype(q.dtype)
    tile_n = _REF_TOKEN_TILE
    pad = (-tokens) % tile_n
    qg = jnp.pad(qg, ((0, pad), (0, 0), (0, 0), (0, 0)))
    lane = jnp.pad(tok_lane, (0, pad))
    pos = jnp.pad(tok_pos, (0, pad), constant_values=-1)
    n_tiles = (tokens + pad) // tile_n
    out = jax.lax.map(tile, (qg.reshape(n_tiles, tile_n, kv_h, g, d),
                             lane.reshape(n_tiles, tile_n),
                             pos.reshape(n_tiles, tile_n)))
    out = out.reshape(n_tiles * tile_n, kv_h, g, d)[:tokens]
    return out.reshape(tokens, h, d).astype(q.dtype)


def paged_attention_verify_ref(q, k_cache, v_cache, block_tables,
                               context_lens, sm_scale=None):
    """XLA reference for the verify pass (also the CPU fallback)."""
    batch, s, h, d = q.shape
    nb, kv_h, bs, _ = k_cache.shape
    g = h // kv_h
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(d))
    k = jnp.take(k_cache, block_tables, axis=0)
    v = jnp.take(v_cache, block_tables, axis=0)
    max_s = block_tables.shape[1] * bs
    k = jnp.swapaxes(k, 2, 3).reshape(batch, max_s, kv_h, d)
    v = jnp.swapaxes(v, 2, 3).reshape(batch, max_s, kv_h, d)
    qg = jnp.swapaxes(q.reshape(batch, s, kv_h, g, d), 1, 2)  # [B,KVH,S,G,D]
    sc = jnp.einsum("bhqgd,bshd->bhqgs", qg.astype(jnp.float32),
                    k.astype(jnp.float32),
                    preferred_element_type=jnp.float32) * sm_scale
    wpos = jnp.arange(max_s, dtype=jnp.int32)
    qpos = (context_lens[:, None] - s
            + jnp.arange(s, dtype=jnp.int32)[None, :])       # [B, S]
    mask = wpos[None, None, :] <= qpos[:, :, None]           # [B, S, W]
    sc = jnp.where(mask[:, None, :, None, :], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bhqgs,bshd->bhqgd", p, v.astype(jnp.float32))
    return jnp.swapaxes(out, 1, 2).reshape(batch, s, h, d).astype(q.dtype)


def paged_attention_ref(q, k_cache, v_cache, block_tables, context_lens,
                        sm_scale=None):
    """XLA reference path (gather + masked softmax); also the CPU fallback."""
    batch, h, d = q.shape
    nb, kv_h, bs, _ = k_cache.shape
    g = h // kv_h
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(d))
    # gather each sequence's blocks: [B, max_blocks, KV_H, BS, D]
    k = jnp.take(k_cache, block_tables, axis=0)
    v = jnp.take(v_cache, block_tables, axis=0)
    max_s = block_tables.shape[1] * bs
    k = jnp.swapaxes(k, 2, 3).reshape(batch, max_s, kv_h, d)
    v = jnp.swapaxes(v, 2, 3).reshape(batch, max_s, kv_h, d)
    qg = q.reshape(batch, kv_h, g, d)
    s = jnp.einsum("bhgd,bshd->bhgs", qg.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * sm_scale
    mask = jnp.arange(max_s)[None, :] < context_lens[:, None]  # [B, S]
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v.astype(jnp.float32))
    return out.reshape(batch, h, d).astype(q.dtype)


def write_kv_to_cache(k, v, k_cache, v_cache, block_tables, start_pos):
    """Scatter new K/V tokens into the block pool.

    k/v: [B, S, KV_H, D] new tokens for positions [start_pos, start_pos+S).
    start_pos: [B] int32 (tokens already cached per sequence).
    Returns updated (k_cache, v_cache). Pure-XLA scatter (no kernel needed:
    the write is bandwidth-bound and XLA lowers it to an efficient
    dynamic-update stream).
    """
    batch, s, kv_h, d = k.shape
    nb, _, bs, _ = k_cache.shape
    pos = start_pos[:, None] + jnp.arange(s)[None, :]          # [B, S]
    blk = jnp.take_along_axis(block_tables, pos // bs, axis=1)  # [B, S]
    flat = blk * bs + pos % bs                                  # [B, S]
    kc = k_cache.swapaxes(1, 2).reshape(nb * bs, kv_h, d)
    vc = v_cache.swapaxes(1, 2).reshape(nb * bs, kv_h, d)
    kc = kc.at[flat.reshape(-1)].set(k.reshape(-1, kv_h, d))
    vc = vc.at[flat.reshape(-1)].set(v.reshape(-1, kv_h, d))
    kc = kc.reshape(nb, bs, kv_h, d).swapaxes(1, 2)
    vc = vc.reshape(nb, bs, kv_h, d).swapaxes(1, 2)
    return kc, vc


def write_kv_to_cache_ragged(k, v, k_cache, v_cache, block_tables,
                             tok_lane, tok_pos, k_scale=None,
                             v_scale=None):
    """Scatter packed ragged K/V tokens into the block pool.

    k/v: [T, KV_H, D] — one new token per packed slot, landing at
    absolute position `tok_pos[t]` of lane `tok_lane[t]`'s block table.
    Guard slots (tok_pos < 0) are routed to an out-of-bounds flat index,
    which jnp scatter DROPS under jit — no guard-block lease needed for
    the ragged write path. Returns updated (k_cache, v_cache).

    Quantize-on-write (`inference/kv_quant.py`): when `k_scale`/
    `v_scale` planes (f32 [NB, KVH, BS]) ride along, each token's K/V
    quantizes to int8 with its own per-head absmax scale and BOTH the
    int8 values and the scale scatter at the same flat index — exact,
    collision-free (no shared block scalar to read-modify-write), and
    atomic with respect to the guard-slot drop. Returns (k_cache,
    v_cache, k_scale, v_scale) in that case."""
    from ...inference import kv_quant

    tokens, kv_h, d = k.shape
    nb, _, bs, _ = k_cache.shape
    pos = jnp.maximum(tok_pos, 0)
    blk = block_tables[tok_lane, pos // bs]                   # [T]
    flat = jnp.where(tok_pos >= 0, blk * bs + pos % bs,
                     jnp.int32(nb * bs))                      # OOB -> drop
    kc = k_cache.swapaxes(1, 2).reshape(nb * bs, kv_h, d)
    vc = v_cache.swapaxes(1, 2).reshape(nb * bs, kv_h, d)
    if k_scale is not None:
        kq, ks_tok = kv_quant.quantize_kv(k)                  # [T,KVH,(D)]
        vq, vs_tok = kv_quant.quantize_kv(v)
        ks = k_scale.swapaxes(1, 2).reshape(nb * bs, kv_h)
        vs = v_scale.swapaxes(1, 2).reshape(nb * bs, kv_h)
        kc = kc.at[flat].set(kq)
        vc = vc.at[flat].set(vq)
        ks = ks.at[flat].set(ks_tok)
        vs = vs.at[flat].set(vs_tok)
        kc = kc.reshape(nb, bs, kv_h, d).swapaxes(1, 2)
        vc = vc.reshape(nb, bs, kv_h, d).swapaxes(1, 2)
        ks = ks.reshape(nb, bs, kv_h).swapaxes(1, 2)
        vs = vs.reshape(nb, bs, kv_h).swapaxes(1, 2)
        return kc, vc, ks, vs
    kc = kc.at[flat].set(k)
    vc = vc.at[flat].set(v)
    kc = kc.reshape(nb, bs, kv_h, d).swapaxes(1, 2)
    vc = vc.reshape(nb, bs, kv_h, d).swapaxes(1, 2)
    return kc, vc


def supported(q_shape, dtype) -> bool:
    if not _support.kernels_enabled():
        return False
    if len(q_shape) != 3:
        return False
    if q_shape[-1] > 256:
        return False
    return _support.float_dtype_ok(dtype)


def verify_supported(q_shape, dtype) -> bool:
    """Gate for `paged_attention_verify` (q: [B, S, H, D])."""
    if not _support.kernels_enabled():
        return False
    if len(q_shape) != 4:
        return False
    if q_shape[-1] > 256:
        return False
    if q_shape[1] > 64:          # S*Gp rows must stay a small VMEM tile
        return False
    return _support.float_dtype_ok(dtype)


def ragged_supported(q_shape, dtype) -> bool:
    """Gate for `paged_attention_ragged` (q: [T, H, D]). The per-tile
    VMEM footprint is one token's head-group band — independent of T —
    so only the head dim and dtype gate."""
    if not _support.kernels_enabled():
        return False
    if len(q_shape) != 3:
        return False
    if q_shape[-1] > 256:
        return False
    return _support.float_dtype_ok(dtype)
