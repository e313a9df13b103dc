"""Pallas TPU paged (block) KV-cache attention.

TPU-native equivalent of the reference's paged-attention CUDA kernel
(`paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu`, python
surface `incubate.nn.functional.block_multihead_attention`): the KV cache is a
pool of fixed-size blocks; each sequence owns a list of block ids (its block
table), so cache memory is allocated in O(block_size) granules instead of one
max-seqlen slab per sequence.

ONE kernel body, `_ragged_kernel` (TPU-first, not a CUDA translation):
- grid = (lanes,); lengths, spans and the block table ride scalar prefetch
  (SMEM), q, the pools and the output stay in HBM and the body DMAs a lane's
  live pages and live tokens, so its work follows them, never the table's
  width. Decode (`q_len` 1), prefill chunks and verify windows share it;
  `paged_attention(q [B, H, D], ...)` is its `q_len == 1` case.
- the chunk of query tokens a lane is computed in follows the lane's own
  `q_len`: `_RAGGED_Q_CHUNK` tokens, and ONE for a decode lane, which so
  pays for one token's rows (its head group, padded to 8) and not for 64.
  The body is instantiated at both sizes inside the one kernel; what picks
  is the prefetched scalar `q_lens[b]`, nothing a caller sets.
- GQA is native: a query-head group is a band of MXU rows against one kv
  head's page group, with no KV repetition in HBM.
- online softmax (flash-style) accumulates across page groups in VMEM.

Caches use the reference layout ``[num_blocks, kv_heads, block_size, head_dim]``.
The serving step's two functions, `write_kv_to_cache_ragged` and
`paged_attention_ragged`, also take the engine's whole pool, that layout
under a leading layer axis, with the layer an operand: they index
``pool[layer, block]`` and never slice a layer out.
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


# VMEM the ragged kernel sizes its buffers against: the page double buffer
# and one query tile's q/acc/m/l, leaving the rest of the 16 MiB scoped
# limit to the per-head temporaries (K/V tiles, scores) Mosaic allocates.
_RAGGED_VMEM_BUDGET = 10 << 20
# query tokens per compute chunk: the granule the work per lane follows
_RAGGED_Q_CHUNK = 8


def online_softmax_step(s, m_prev, l_prev):
    """One step of the online softmax on a masked f32 score tile `s
    [rows, cols]` against the running max and sum `m_prev`, `l_prev
    [rows, 128]` (lane-replicated): `(p [rows, cols], alpha [rows, 128],
    m_new, l_new)`; the caller scales its accumulator by `alpha[:, :1]`
    and adds `p @ v`. Shared by the ragged kernels of this package."""
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new[:, :1])
    alpha = jnp.exp(m_prev - m_new)
    return p, alpha, m_new, l_prev * alpha + p.sum(axis=-1, keepdims=True)


def lane_spans(tok_lane, tok_pos, lanes):
    """Each lane's `(q_lens, q_starts)` in the packed buffer, from the
    per-token metadata `ragged_metadata` made (guard slots, pos -1, belong
    to no lane)."""
    q_lens = jnp.zeros((lanes,), jnp.int32).at[tok_lane.astype(jnp.int32)] \
        .add((tok_pos >= 0).astype(jnp.int32))
    return q_lens, jnp.cumsum(q_lens) - q_lens


def _group_pad(g):
    """A query-head group's rows, padded to whole 8-row sublane tiles."""
    return -(-g // 8) * 8


def _ragged_tiles(tokens, kv_h, g_pad, d, block_size, width, kv_itemsize):
    """Static tile sizes of the ragged kernel, from shapes alone:
    `(pages, q_tile)` or None when no tile fits `_RAGGED_VMEM_BUDGET`.

    `pages` K/V pages are fetched per loop step so that one step's score
    tile is a full 128-lane row of kv positions (fewer when the table is
    narrower). `q_tile` query tokens share those pages: the largest
    power-of-two multiple of `_RAGGED_Q_CHUNK` whose f32 q/acc rows and
    lane-replicated m/l rows fit beside the page double buffer, and no
    more than the packed buffer holds."""
    pages = max(1, min(128 // block_size, width))
    kv_bytes = 2 * 2 * pages * kv_h * block_size * max(d, 128) * kv_itemsize
    per_token = kv_h * g_pad * (2 * max(d, 128) + 2 * 128) * 4
    fit = (_RAGGED_VMEM_BUDGET - kv_bytes) // (per_token * _RAGGED_Q_CHUNK)
    if fit < 1:
        return None
    chunks = min(1 << (fit.bit_length() - 1),
                 -(-tokens // _RAGGED_Q_CHUNK))
    return pages, chunks * _RAGGED_Q_CHUNK


def _ragged_kernel(layer_ref, window_ref, kv_lens_ref, q_lens_ref,
                   q_starts_ref, tables_ref, q_hbm, k_hbm, v_hbm, *rest, sm_scale,
                   block_size, pages, q_tile, g_pad, quantized, mxu_dtype):
    """Ragged paged attention: ONE fixed-shape kernel for mixed
    prefill-chunk + decode + verify batches, whose work follows the live
    pages and live tokens of each lane.

    grid = (lanes,). Everything ragged is scalar-prefetched: lane b owns
    the packed query tokens [q_start, q_start + q_len) (lane-major, as
    `ragged_metadata` packs them) whose first sits at absolute position
    kv_len - q_len. The packed q buffer, the K/V pools (as stored,
    [L, NB, KVH, BS, D], the layer one more prefetched scalar: a page is
    `pool[layer, block]`, an index and never a slice) and the output stay
    in HBM; the body moves what it needs with its own DMAs:

    - a lane's tokens go in tiles of `q_tile` (an empty lane issues none,
      guard slots past sum(q_lens) belong to no lane and cost nothing);
      a tile's q rows come in, and its output rows leave, in chunks of
      `qc` tokens, only the live ones. `qc` is `_RAGGED_Q_CHUNK`, and 1
      for a decode lane (`q_len == 1`): `lane(qc, ...)` below is the whole
      body at one chunk size, instantiated at both, and a lane runs the
      one its `q_len` names, on the first token's slice of the same
      scratch. The arithmetic of a row does not depend on how many rows
      share its tile, so a decode lane's output is bitwise what the
      8-token chunk gave it, for `g_pad` MXU rows a kv head and page group
      where that took 64; and its one chunk needs no loop, so a group's kv
      heads run as straight-line code whose chains of matmul, softmax and
      matmul interleave (inside a rolled loop each waits for the last:
      that, not the rows, was a decode lane's cost; PERF.md, PR 37);
    - per tile a rolled loop walks the lane's live pages — up to the
      tile's last query position, never the table's width — `pages` at a
      time, double-buffered: one DMA brings a page for ALL kv heads (it
      is contiguous in the pool), so a page is fetched once per tile.
      The tail group's missing pages re-fetch the last live one and are
      masked, so no dead page is ever read. A sliding-window layer
      (`window_ref[0]` > 0, one more prefetched scalar: a query sees the
      `window` positions that end at its own) bounds the walk from the
      other end: it starts at the page group that holds the tile's first
      query's oldest visible position, and that group's pages behind it
      re-fetch the first live one and are masked, so a page the cache
      manager has released behind the window is never read. With window 0
      the walk, the DMAs and every row's arithmetic are what they were;
    - per kv head the [rows, D] x [D, pages*BS] score tile and the
      [rows, pages*BS] x [pages*BS, D] update (rows = qc * g_pad) run per
      live chunk with the online softmax in f32 (m/l lane-replicated
      scratch). Scores take
      the MXU in `mxu_dtype`: the operands' own bf16 when q and the pool
      are bf16/int8 (products of bf16 values are exact in the f32
      accumulator), f32 otherwise; `sm_scale` is applied to the f32
      scores. Causal mask per row: `kv_pos <= position` and < kv_len,
      and `kv_pos > position - window` in a window layer.

    Output rows of a tile's last chunk past the lane's tokens are written
    as zeros; the next lane, processed after it, overwrites the ones it
    owns. A row no lane owns is never written: the output is whatever its
    allocation held there. (A decode lane's chunk is its one token: it
    writes no row but its own.)

    Quantized KV (`inference/kv_quant.py` layout): K/V arrive as int8 and
    `rest` leads with the lane's per-slot f32 scale rows in logical order
    `ks_ref`/`vs_ref` [1, KVH, groups, pages*BS] — the bf16/f32 KV never
    exists in HBM. The per-slot scale is constant along D, so it factors
    out of both contractions and is applied on the score tile:
    `(q.k_int) * ks` before the softmax and `p * vs` before `p @ v_int`."""
    if quantized:
        ks_ref, vs_ref = rest[:2]
        rest = rest[2:]
    o_hbm, qbuf, kbuf, vbuf, acc_ref, m_ref, l_ref, sem = rest
    kv_h, d = kbuf.shape[2], kbuf.shape[4]
    cols = pages * block_size             # kv positions of one page group
    i32 = jnp.int32
    b = pl.program_id(0)
    layer = layer_ref[0]
    window = window_ref[0]
    kv_len = kv_lens_ref[b]
    q_len = q_lens_ref[b]
    q_start = q_starts_ref[b]
    # how far behind itself a query sees: its window, or (window 0) further
    # than any position lies
    reach = jnp.where(window > 0, window, i32(1 << 30))

    def lane(qc, n_tiles):
        """`n_tiles` tiles of the lane at `qc` query tokens a compute
        chunk."""
        rows = qc * g_pad                 # MXU rows of one compute chunk

        def chunk_loop(n, body):
            if qc == 1:
                # a decode lane's one token is its one chunk: no loop, so
                # a page group's kv heads are straight-line code whose
                # matmul -> softmax -> matmul chains Mosaic interleaves
                body(i32(0))
            else:
                jax.lax.fori_loop(0, n, lambda c, _: body(c), None)

        def toks(c):                      # chunk c's tokens of the tile
            return pl.ds(c * i32(qc), qc)

        def band(c):                      # ... and their MXU rows
            return pl.ds(pl.multiple_of(c * i32(rows), rows), rows)

        def tile(i):
            t0 = i * i32(q_tile)          # the tile's first token, in-lane
            n_tok = jnp.minimum(q_len - t0, i32(q_tile))
            n_chunks = pl.cdiv(n_tok, i32(qc))
            pos0 = kv_len - q_len + t0    # its absolute position
            n_pages = pl.cdiv(pos0 + n_tok, i32(block_size))
            n_groups = pl.cdiv(n_pages, i32(pages))
            # the first page the tile's first query still sees, and its group
            page0 = jnp.maximum(pos0 - reach + 1, 0) // i32(block_size)
            group0 = page0 // i32(pages)

            def q_copy(c):
                return pltpu.make_async_copy(
                    q_hbm.at[pl.ds(q_start + t0 + c * i32(qc), qc)],
                    qbuf.at[toks(c)], sem.at[2, 0])

            def o_copy(c):
                return pltpu.make_async_copy(
                    acc_ref.at[toks(c)],
                    o_hbm.at[pl.ds(q_start + t0 + c * i32(qc), qc)],
                    sem.at[2, 1])

            def page_copies(g, slot):
                for p in range(pages):
                    j = jnp.clip(g * i32(pages) + i32(p), page0, n_pages - 1)
                    blk = tables_ref[b, j]
                    yield pltpu.make_async_copy(k_hbm.at[layer, blk],
                                                kbuf.at[slot, p],
                                                sem.at[0, slot])
                    yield pltpu.make_async_copy(v_hbm.at[layer, blk],
                                                vbuf.at[slot, p],
                                                sem.at[1, slot])

            chunk_loop(n_chunks, lambda c: q_copy(c).start())
            for cp in page_copies(group0, 0):
                cp.start()

            def init(c):
                acc_ref[toks(c)] = jnp.zeros(
                    (qc,) + acc_ref.shape[1:], jnp.float32)
                m_ref[:, band(c), :] = jnp.full(
                    (kv_h, rows, 128), NEG_INF, jnp.float32)
                l_ref[:, band(c), :] = jnp.zeros(
                    (kv_h, rows, 128), jnp.float32)

            chunk_loop(n_chunks, init)
            chunk_loop(n_chunks, lambda c: q_copy(c).wait())

            def group(g, _):
                slot = (g - group0) % 2

                @pl.when(g + 1 < n_groups)
                def _prefetch():
                    for cp in page_copies(g + 1, 1 - slot):
                        cp.start()

                for cp in page_copies(g, slot):
                    cp.wait()
                kv_pos = g * i32(cols) + jax.lax.broadcasted_iota(
                    i32, (rows, cols), 1)
                tok = jax.lax.broadcasted_iota(
                    i32, (rows, cols), 0) // i32(g_pad)
                for h in range(kv_h):
                    k = kbuf[slot, :, h].reshape(cols, d).astype(mxu_dtype)
                    v = vbuf[slot, :, h].reshape(cols, d).astype(jnp.float32)
                    if quantized:
                        ks = ks_ref[0, h, pl.ds(g, 1), :]        # (1, cols)
                        vs = vs_ref[0, h, pl.ds(g, 1), :]

                    def chunk(c, h=h, k=k, v=v):
                        ts, rs = toks(c), band(c)
                        q = qbuf[ts, h].reshape(rows, d).astype(mxu_dtype)
                        s = jax.lax.dot_general(
                            q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
                        if quantized:
                            s = s * ks
                        pos = jnp.minimum(pos0 + c * i32(qc) + tok,
                                          kv_len - 1)
                        live = (kv_pos <= pos) & (kv_pos > pos - reach)
                        # typed scalars: python numbers weak-type to 64 bits
                        # when the interpret-mode kernel is traced inside an
                        # x64-on outer program
                        s = jnp.where(live, s, jnp.float32(NEG_INF))
                        p, alpha, m_ref[h, rs, :], l_ref[h, rs, :] = \
                            online_softmax_step(s, m_ref[h, rs, :],
                                                l_ref[h, rs, :])
                        if quantized:
                            # a masked slot's scale may be anything
                            p = jnp.where(live, p * vs, jnp.float32(0.0))
                        pv = jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
                        acc = acc_ref[ts, h].reshape(rows, d)
                        acc_ref[ts, h] = (acc * alpha[:, :1] + pv).reshape(
                            qc, g_pad, d)

                    chunk_loop(n_chunks, chunk)

            jax.lax.fori_loop(group0, n_groups, group, None)

            def finish(c):
                ts, rs = toks(c), band(c)
                owned = (c * i32(qc) + jax.lax.broadcasted_iota(
                    i32, (rows, d), 0) // i32(g_pad)) < n_tok
                for h in range(kv_h):
                    l = l_ref[h, rs, :][:, :1]
                    l_safe = jnp.where(l == 0.0, jnp.float32(1.0), l)
                    out = acc_ref[ts, h].reshape(rows, d) / l_safe
                    acc_ref[ts, h] = jnp.where(
                        owned, out, jnp.float32(0.0)).reshape(qc, g_pad, d)
                o_copy(c).start()

            chunk_loop(n_chunks, finish)
            chunk_loop(n_chunks, lambda c: o_copy(c).wait())

        jax.lax.fori_loop(0, n_tiles, lambda i, _: tile(i), None)

    # ONE of the two instantiations computes a lane, the one its own q_len
    # names; the other's trip count is zero (both are, for an empty lane).
    # Trip counts and not `pl.when`: under a `cond` the interpreter's CPU
    # program copies the whole pool (tests/test_inference.py, the step
    # never copies it)
    n_tiles = jnp.where(kv_len > 0, pl.cdiv(q_len, i32(q_tile)), i32(0))
    decode = q_len == 1
    lane(1, jnp.where(decode, n_tiles, i32(0)))
    lane(_RAGGED_Q_CHUNK, jnp.where(decode, i32(0), n_tiles))


def _ragged_call(q, k_cache, v_cache, layer, window, block_tables, kv_lens,
                 q_lens, q_starts, sm_scale, tiles, mxu_dtype, k_scale=None,
                 v_scale=None):
    """q: f32 [T + chunk, KV_H, Gp, D] packed tokens; caches as stored,
    [L, NB, KV_H, BS, D], `layer` int32 [1], which of them to read, and
    `window` int32 [1], the layer's sliding window (0: none)
    (int8 when the per-lane f32 scale windows [B, KV_H, groups, pages*BS]
    ride along). Returns f32, q's shape, written where a lane owns rows."""
    tokens, kv_h, g_pad, d = q.shape
    block_size = k_cache.shape[3]
    lanes = block_tables.shape[0]
    pages, q_tile = tiles
    rows = q_tile * g_pad

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    operands = [q, k_cache, v_cache]
    in_specs = [hbm, hbm, hbm]
    if k_scale is not None:
        scale_rows = pl.BlockSpec(
            (1,) + k_scale.shape[1:],
            lambda b, layer, window, lens, qlens, starts, tables:
            (b, 0, 0, 0))
        operands += [k_scale, v_scale]
        in_specs += [scale_rows, scale_rows]
    page_buf = pltpu.VMEM((2, pages, kv_h, block_size, d), k_cache.dtype)
    lm = pltpu.VMEM((kv_h, rows, 128), jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(lanes,),
        in_specs=in_specs,
        out_specs=hbm,
        scratch_shapes=[
            pltpu.VMEM((q_tile, kv_h, g_pad, d), jnp.float32),   # q tile
            page_buf, page_buf,
            pltpu.VMEM((q_tile, kv_h, g_pad, d), jnp.float32),   # acc
            lm, lm,
            pltpu.SemaphoreType.DMA((3, 2)),   # K, V pages by slot; q, out
        ],
    )
    return _support.pallas_call(
        functools.partial(_ragged_kernel, sm_scale=sm_scale,
                          block_size=block_size, pages=pages, q_tile=q_tile,
                          g_pad=g_pad, quantized=k_scale is not None,
                          mxu_dtype=mxu_dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="paged_attention_ragged",
        interpret=_support.interpret_mode(),
    )(layer, window, kv_lens, q_lens, q_starts, block_tables, *operands)


def _layered(layer, k_cache, *pools):
    """`(layer int32 [], k_cache, *pools)` with every pool `[L, ...]`. What
    decides is the K pool's rank: `[L, NB, KVH, BS, D]` comes with the
    `layer` to use; `[NB, KVH, BS, D]` is a pool of one layer, and the
    leading axis of 1 it gets here is free."""
    if k_cache.ndim == 5:
        if layer is None:
            raise ValueError("a [L, NB, KVH, BS, D] pool needs its `layer`")
        return (jnp.asarray(layer, jnp.int32), k_cache) + pools
    if layer is not None:
        raise ValueError("a [NB, KVH, BS, D] pool is one layer: no `layer`")
    return (jnp.int32(0),) + tuple(
        None if p is None else p[None] for p in (k_cache,) + pools)


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


def _window_scalar(window):
    """`window` (None, an int or a traced scalar) as int32 []; 0 = none."""
    return jnp.asarray(0 if window is None else window, jnp.int32)


def ragged_prepare(q, kv_heads: int) -> _support.Packed:
    """Row-wise, before the rows are placed: `q [n, H, D]` as the kernel
    takes it, f32 `[n, KV_H, Gp, D]` (a token's head-group band is then
    whole (8, 128) tiles, so a chunk of tokens folds into MXU rows without
    a relayout; `Gp`: the group's rows padded to whole sublane tiles), and
    the spare chunk the last live chunk's DMA may run over."""
    n, h, d = q.shape
    g = h // kv_heads
    qg = q.reshape(n, kv_heads, g, d).astype(jnp.float32)
    return _support.Packed(
        jnp.pad(qg, ((0, 0), (0, 0), (0, _group_pad(g) - g), (0, 0))),
        _RAGGED_Q_CHUNK)


def ragged_finish(out, heads: int, dtype):
    """Row-wise, on rows cut from what `paged_attention_ragged_packed`
    left: f32 `[n, KV_H, Gp, D]` -> `[n, H, D]` in `dtype`."""
    n, kv_h, _, d = out.shape
    return out[:, :, :heads // kv_h, :].reshape(n, heads, d).astype(dtype)


def paged_attention_ragged_packed(qg, k_cache, v_cache, block_tables, kv_lens,
                                  tok_lane, tok_pos, sm_scale=None,
                                  k_scale=None, v_scale=None, layer=None,
                                  window=None, mxu_bf16: bool = False):
    """`paged_attention_ragged` on the buffer as the kernel takes it and
    leaves it: qg f32 `[T + chunk, KV_H, Gp, D]` (`ragged_prepare`, placed;
    `T` is `tok_lane`'s); `mxu_bf16`: the queries were bfloat16, so beside
    a bf16 or int8 pool the MXU takes bf16 operands. Returns f32, qg's
    shape: a live row's answer (`ragged_finish` takes its rows on); a guard
    row and the spare chunk hold whatever. Nothing is padded, filled or
    cut."""
    layer, k_cache, v_cache, k_scale, v_scale = _layered(
        layer, k_cache, v_cache, k_scale, v_scale)
    tokens = tok_lane.shape[0]
    _, kv_h, g_pad, d = qg.shape
    block_size = k_cache.shape[3]
    lanes, width = block_tables.shape
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(d))
    tiles = _ragged_tiles(tokens, kv_h, g_pad, d, block_size, width,
                          k_cache.dtype.itemsize)
    if tiles is None:
        raise ValueError(
            f"paged_attention_ragged: no tile of {kv_h} kv heads x {g_pad} "
            f"rows x {d} fits VMEM; ask ragged_supported first")
    if qg.shape[0] != tokens + _RAGGED_Q_CHUNK or qg.dtype != jnp.float32:
        raise ValueError(f"paged_attention_ragged_packed: q {qg.dtype}"
                         f"{qg.shape} is not a placed `ragged_prepare`")
    pages, _ = tiles
    q_lens, q_starts = lane_spans(tok_lane, tok_pos, lanes)
    block_tables = block_tables.astype(jnp.int32)
    if k_scale is not None:
        # the lane's scale rows in logical order, [B, KV_H, groups,
        # pages*BS]: one (1, pages*BS) row per page group lies along the
        # score tile's lanes, which no in-kernel gather of (KV_H, BS)
        # pieces could give without a relayout. A table-wide gather, but
        # of planes 1/D the pool's size (and of this layer's alone).
        groups = -(-width // pages)
        padded = jnp.pad(block_tables, ((0, 0), (0, groups * pages - width)))

        def by_lane(scale):
            return jnp.swapaxes(scale[layer, padded], 1, 2) \
                .reshape(lanes, kv_h, groups, pages * block_size)

        k_scale, v_scale = by_lane(k_scale), by_lane(v_scale)
    exact_bf16 = mxu_bf16 and k_cache.dtype in (jnp.bfloat16, jnp.int8)
    return _ragged_call(qg, k_cache, v_cache, layer.reshape(1),
                        _window_scalar(window).reshape(1), block_tables,
                        kv_lens.astype(jnp.int32), q_lens, q_starts,
                        float(sm_scale), tiles,
                        jnp.bfloat16 if exact_bf16 else jnp.float32,
                        k_scale, v_scale)


def paged_attention_ragged(q, k_cache, v_cache, block_tables, kv_lens,
                           tok_lane, tok_pos, sm_scale=None,
                           k_scale=None, v_scale=None, layer=None,
                           window=None):
    """Ragged paged attention over a packed query token buffer.

    ONE kernel for every serving batch composition: decode lanes
    (q_len 1), prefill chunks (q_len n), and speculative verify windows
    (q_len K+1) share this fixed-shape dispatch — the grid is the lane
    count and every buffer is sized by static shapes (`_ragged_tiles`),
    never by the batch composition, so the serving steady state holds
    exactly one compiled executable. Its work follows, per lane, live
    pages x query tokens: the table's width and the guard slots of the
    packed buffer cost nothing (see `_ragged_kernel`).

    Args:
      q: [T, H, D] — packed query tokens, lane-major as
         `ragged_metadata` packs them: lane i owns the contiguous slots
         after lane i-1's, its tokens at consecutive positions ending at
         kv_lens[i] - 1.
      k_cache/v_cache: [layers, num_blocks, kv_heads, block_size,
         head_dim], read as stored, with `layer` (below); or one layer's
         [num_blocks, kv_heads, block_size, head_dim], without.
      block_tables: [B, W] int32 physical block ids per lane; entries
         past a lane's live pages are never read.
      kv_lens: [B] int32 — tokens in cache per lane INCLUDING this
         dispatch's own tokens (0 for empty lanes).
      tok_lane/tok_pos: [T] int32 per-token owner lane / absolute
         position (-1 = guard slot, output forced to 0); the kernel
         takes each lane's (q_start, q_len) from them.
      k_scale/v_scale: optional f32 [(layers,) num_blocks, kv_heads,
         block_size] per-slot scale planes for int8 quantized caches
         (`inference/kv_quant.py`), of the pools' rank less one:
         dequantization then happens inside the kernel body, on the
         score tile.
      layer: which layer of a 5-D pool, an int or a traced int32 scalar
         (one compiled kernel serves every layer: it rides scalar
         prefetch, and a page's DMA source is `pool[layer, block]`).
      window: a sliding-window layer's window, an int or a traced int32
         scalar (it rides scalar prefetch too: one compiled kernel for
         window and full layers): a query at position i sees the keys at
         `i - window < j <= i`, and table entries of pages wholly behind
         the window of the lane's first query are never read (the cache
         manager has released them). None or 0: every key up to i.
    Returns [T, H, D]; guard rows are exact zeros. `ragged_prepare`,
    placed, through `paged_attention_ragged_packed`, and `ragged_finish`
    of what it left on the live rows.
    """
    tokens, h, _ = q.shape
    kv_h = k_cache.shape[-3]
    out = paged_attention_ragged_packed(
        _support.place(ragged_prepare(q, kv_h), tokens, tokens), k_cache,
        v_cache, block_tables, kv_lens, tok_lane, tok_pos, sm_scale, k_scale,
        v_scale, layer, window, q.dtype == jnp.bfloat16)
    return jnp.where((tok_pos >= 0)[:, None, None],
                     ragged_finish(out[:tokens], h, q.dtype), 0)


# above this many packed tokens the ref tiles its per-token window
# gather: an untiled T x window_capacity gather is O(T * max_seq) memory,
# which a monolithic multi-k-token prefill chunk would blow into GBs
_REF_TOKEN_TILE = 128


def paged_attention_ragged_ref(q, k_cache, v_cache, block_tables, kv_lens,
                               tok_lane, tok_pos, sm_scale=None,
                               k_scale=None, v_scale=None, layer=None,
                               window=None):
    """XLA reference for the ragged kernel (also the CPU fallback).

    Same gather + masked-softmax structure as `paged_attention_ref`, per
    packed token; guard rows (tok_pos < 0) come back exactly zero. Large
    packed buffers (T > _REF_TOKEN_TILE) stream through `lax.map` token
    tiles so the gathered windows stay bounded — each row's reduction is
    unchanged, only how many rows are materialized at once.

    `k_scale`/`v_scale` (f32 [(L,) NB, KVH, BS]) mark int8 quantized
    caches: the gathered per-lane windows dequantize right after the
    gather — only the gathered window is ever materialized in float,
    never the pool. Pools, `layer` and `window` as
    `paged_attention_ragged` takes them: the windows are gathered at
    `[layer, block]`, the layer never sliced out; what a released table
    entry points at is gathered and masked."""
    layer, k_cache, v_cache, k_scale, v_scale = _layered(
        layer, k_cache, v_cache, k_scale, v_scale)
    tokens, h, d = q.shape
    kv_h, bs = k_cache.shape[2:4]
    g = h // kv_h
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(d))
    k = k_cache[layer, block_tables]              # [B, W, KV_H, BS, D]
    v = v_cache[layer, block_tables]
    if k_scale is not None:
        ks = k_scale[layer, block_tables]         # [B, W, KV_H, BS]
        vs = v_scale[layer, block_tables]
        k = k.astype(jnp.float32) * ks[..., None]
        v = v.astype(jnp.float32) * vs[..., None]
    max_s = block_tables.shape[1] * bs
    k = jnp.swapaxes(k, 2, 3).reshape(block_tables.shape[0], max_s, kv_h, d)
    v = jnp.swapaxes(v, 2, 3).reshape(block_tables.shape[0], max_s, kv_h, d)
    wpos = jnp.arange(max_s, dtype=jnp.int32)
    window = _window_scalar(window)
    reach = jnp.where(window > 0, window, jnp.int32(1 << 30))

    def tile(args):
        qg, lane, pos = args                      # [t, KV_H, G, D] / [t]
        kt = jnp.take(k, lane, axis=0)            # [t, max_s, KV_H, D]
        vt = jnp.take(v, lane, axis=0)
        s = jnp.einsum("thgd,tshd->thgs", qg.astype(jnp.float32),
                       kt.astype(jnp.float32),
                       preferred_element_type=jnp.float32) * sm_scale
        mask = (wpos[None, :] <= pos[:, None]) \
            & (wpos[None, :] > pos[:, None] - reach)         # [t, max_s]
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


def paged_attention(q, k_cache, v_cache, block_tables, context_lens,
                    sm_scale=None):
    """Decode-step paged attention over raw arrays: the `q_len == 1` case
    of `paged_attention_ragged` (lane i's one token at position
    `context_lens[i] - 1`), behind the same gate, `ragged_supported`.

    Args:
      q: [B, H, D] — one query token per sequence.
      k_cache/v_cache: [num_blocks, kv_heads, block_size, head_dim].
      block_tables: [B, max_blocks_per_seq] int32 physical block ids (pad 0).
      context_lens: [B] int32 — tokens already in cache (incl. current).
    Returns [B, H, D].
    """
    context_lens = context_lens.astype(jnp.int32)
    return paged_attention_ragged(
        q, k_cache, v_cache, block_tables, context_lens,
        jnp.arange(q.shape[0], dtype=jnp.int32), context_lens - 1, sm_scale)



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


# VMEM the ragged write sizes its block buffers against (K and V, one
# block a token slot of a tile), beside the tile's f32 K/V rows
_KV_WRITE_VMEM_BUDGET = 8 << 20


def _kv_write_tile(tokens, kv_h, block_size, d, itemsize):
    """Token slots a grid step of the ragged write handles: every slot may
    own a K and a V block buffer, so as many as `_KV_WRITE_VMEM_BUDGET`
    holds, in whole 8s, and no more than the packed buffer has."""
    block_bytes = kv_h * block_size * max(d, 128) * itemsize
    fit = _KV_WRITE_VMEM_BUDGET // (2 * block_bytes) // 8 * 8
    return min(fit, -(-tokens // 8) * 8)


def _kv_write_kernel(layer_ref, blk_ref, off_ref, slot_ref, k_ref, v_ref,
                     *rest, tile, num_blocks):
    """The ragged KV write, in place: read-modify-write of whole blocks.

    Mosaic cannot DMA one row of a packed (bf16, int8) tile, and XLA's
    scatter walks a token's rows one (token, head) at a time; a block
    `pool[layer, block]` is contiguous, so it moves whole. A grid step owns
    `tile` packed token slots. Token j's block is `blk_ref[j]` (a guard
    slot's lies past the pool: skipped), its row `off_ref[j]`, and
    `slot_ref[j]` names the tile's FIRST token of the same block: a lane's
    tokens sit at consecutive positions, so the tokens of one block are
    neighbours, and that first one's buffer collects the rows of all of
    them. Fetch every first token's K and V block; wait; put each token's
    rows into its block's buffer at `[:, off, :]` (a select on f32 copies
    of the head's tile: exact); send every buffer back; wait. Blocks of
    one tile are distinct, tiles run in order, so no write meets a
    read."""
    _, _, k_pool, v_pool, kbuf, vbuf, sem = rest
    kv_h, block_size, d = kbuf.shape[1:]
    i32 = jnp.int32
    base = pl.program_id(0) * i32(tile)
    layer = layer_ref[0]

    def each(body, firsts_only):
        def step(j, _):
            blk = blk_ref[base + j]
            go = blk < i32(num_blocks)
            if firsts_only:
                go = jnp.logical_and(go, slot_ref[base + j] == j)

            @pl.when(go)
            def _():
                body(j, blk)

        jax.lax.fori_loop(0, tile, step, None)

    def move(method, back):
        """Start or wait (`method`) the K and V block copies of a run's
        first token: pool -> its buffer, or `back`."""
        def body(j, blk):
            for which, (pool, buf) in enumerate(((k_pool, kbuf),
                                                 (v_pool, vbuf))):
                ends = (pool.at[layer, blk], buf.at[j])
                getattr(pltpu.make_async_copy(
                    *(ends[::-1] if back else ends), sem.at[which]),
                    method)()
        each(body, firsts_only=True)

    def put(j, _):
        slot = slot_ref[base + j]
        at_row = jax.lax.broadcasted_iota(
            i32, (block_size, d), 0) == off_ref[base + j]
        for rows_ref, buf in ((k_ref, kbuf), (v_ref, vbuf)):
            rows = rows_ref[j]                              # (KV_H, D) f32
            for h in range(kv_h):
                new = jnp.broadcast_to(rows[h:h + 1], (block_size, d))
                buf[slot, h] = jnp.where(
                    at_row, new, buf[slot, h].astype(jnp.float32)
                ).astype(buf.dtype)

    move("start", back=False)
    move("wait", back=False)
    each(put, firsts_only=False)
    move("start", back=True)
    move("wait", back=True)


def _kv_write_call(k, v, k_cache, v_cache, layer, blk, off):
    """k/v [T, KV_H, D]; pools [L, NB, KV_H, BS, D], aliased to the
    result; layer int32 []; blk/off int32 [T], a guard slot's blk = NB."""
    tokens, kv_h, d = k.shape
    nb, block_size = k_cache.shape[1], k_cache.shape[3]
    tile = _kv_write_tile(tokens, kv_h, block_size, d,
                          k_cache.dtype.itemsize)
    pad = -tokens % tile
    blk = jnp.pad(blk, (0, pad), constant_values=nb)
    off = jnp.pad(off, (0, pad))
    # the tile's first token of each run of equal blocks, for every token
    j = jnp.arange(tile, dtype=jnp.int32)
    by_tile = blk.reshape(-1, tile)
    first = jnp.concatenate(
        [jnp.ones_like(by_tile[:, :1], bool),
         by_tile[:, 1:] != by_tile[:, :-1]], axis=1)
    slot = jax.lax.cummax(jnp.where(first, j, 0), axis=1).reshape(-1)

    def rows(x):                      # what `.at[].set` would have stored
        x = x.astype(k_cache.dtype).astype(jnp.float32)
        return jnp.pad(x, ((0, pad), (0, 0), (0, 0)))

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    new_rows = pl.BlockSpec((tile, kv_h, d), lambda i, *_: (i, 0, 0))
    buf = pltpu.VMEM((tile, kv_h, block_size, d), k_cache.dtype)
    pool = jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype)
    return _support.pallas_call(
        functools.partial(_kv_write_kernel, tile=tile, num_blocks=nb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=((tokens + pad) // tile,),
            in_specs=[new_rows, new_rows, hbm, hbm],
            out_specs=[hbm, hbm],
            scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=[pool, pool],
        input_output_aliases={6: 0, 7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="kv_write_ragged",
        interpret=_support.interpret_mode(),
    )(layer.reshape(1), blk, off, slot, rows(k), rows(v), k_cache, v_cache)


def kv_write_supported(pool_shape, pool_dtype) -> bool:
    """Gate for the ragged write's kernel (`pool_shape` the pool's
    [(L,) NB, KVH, BS, D]): float pools whose block fits the buffers.
    Int8 pools (values and scale planes) take the XLA scatter."""
    if not _support.kernels_enabled():
        return False
    if not _support.float_dtype_ok(pool_dtype):
        return False
    kv_h, block_size, d = pool_shape[-3:]
    return _kv_write_tile(8, kv_h, block_size, d,
                          np.dtype(pool_dtype).itemsize) >= 8


def _write_ragged(kernel, k, v, k_cache, v_cache, block_tables, tok_lane,
                  tok_pos, k_scale, v_scale, layer):
    from ...inference import kv_quant

    one_layer = k_cache.ndim == 4
    layer, k_cache, v_cache, k_scale, v_scale = _layered(
        layer, k_cache, v_cache, k_scale, v_scale)
    nb, kv_h, bs = k_cache.shape[1:4]
    pos = jnp.maximum(tok_pos, 0)
    # a guard slot's rows go to a block past the pool: dropped
    blk = jnp.where(tok_pos >= 0, block_tables[tok_lane, pos // bs],
                    jnp.int32(nb))
    off = pos % bs
    heads = jnp.arange(kv_h, dtype=jnp.int32)

    def put(pool, rows):
        # one row a (token, head): a window that spans the head axis makes
        # the TPU compiler re-lay the whole pool out, and back, every layer
        return pool.at[layer, blk[:, None], heads[None, :],
                       off[:, None]].set(rows, mode="drop")

    if kernel:
        out = _kv_write_call(k, v, k_cache, v_cache, layer, blk, off)
    elif k_scale is not None:
        kq, ks_tok = kv_quant.quantize_kv(k)                  # [T,KVH,(D)]
        vq, vs_tok = kv_quant.quantize_kv(v)
        out = (put(k_cache, kq), put(v_cache, vq),
               put(k_scale, ks_tok), put(v_scale, vs_tok))
    else:
        out = put(k_cache, k), put(v_cache, v)
    return tuple(p[0] for p in out) if one_layer else tuple(out)


def write_kv_to_cache_ragged(k, v, k_cache, v_cache, block_tables,
                             tok_lane, tok_pos, k_scale=None,
                             v_scale=None, layer=None):
    """Write packed ragged K/V tokens into the block pool, in place.

    k/v: [T, KV_H, D] — one new token per packed slot, landing at
    absolute position `tok_pos[t]` of lane `tok_lane[t]`'s block table.
    k_cache/v_cache: the pool as stored, [L, NB, KV_H, BS, D], with the
    `layer` to write (an int or a traced int32 scalar); or one layer's
    [NB, KV_H, BS, D], without. Each live token's [KV_H, D] rows go
    straight to `pool[layer, block, :, offset, :]`: the pool is never
    reshaped, transposed or sliced, so a donated pool is written where
    it lies — by the kernel `kv_write_ragged` where `kv_write_supported`
    (float pools), by an XLA scatter otherwise
    (`write_kv_to_cache_ragged_ref`, the same bytes). Guard slots
    (tok_pos < 0) are given a block past the pool and write NOTHING — no
    guard-block lease needed for the ragged write path. A lane's tokens
    sit at consecutive positions (`ragged_metadata`), and no two lanes
    write one block. Returns updated (k_cache, v_cache), of the rank
    given.

    Quantize-on-write (`inference/kv_quant.py`): when `k_scale`/
    `v_scale` planes (f32 [(L,) NB, KVH, BS]) ride along, each token's
    K/V quantizes to int8 with its own per-head absmax scale and BOTH the
    int8 values and the scale scatter at the same indices — exact,
    collision-free (no shared block scalar to read-modify-write), and
    atomic with respect to the guard-slot drop. Returns (k_cache,
    v_cache, k_scale, v_scale) in that case."""
    kernel = k_scale is None and kv_write_supported(k_cache.shape,
                                                    k_cache.dtype)
    return _write_ragged(kernel, k, v, k_cache, v_cache, block_tables,
                         tok_lane, tok_pos, k_scale, v_scale, layer)


def write_kv_to_cache_ragged_ref(k, v, k_cache, v_cache, block_tables,
                                 tok_lane, tok_pos, k_scale=None,
                                 v_scale=None, layer=None):
    """XLA composite of `write_kv_to_cache_ragged` (also the CPU and the
    int8 path): one scatter a pool at `[layer, block, head, offset]`, a
    row of D numbers a (token, head), out-of-bounds rows dropped."""
    return _write_ragged(False, k, v, k_cache, v_cache, block_tables,
                         tok_lane, tok_pos, k_scale, v_scale, layer)



def ragged_supported(q_shape, dtype, cache_shape, cache_dtype,
                     table_width) -> bool:
    """Gate for `paged_attention_ragged` (q: [T, H, D]; `cache_shape` the
    pool's [(L,) NB, KVH, BS, D]). The kernel's VMEM footprint is the page
    double buffer — 2 slots x K and V x `pages` pages of all kv heads —
    plus one query tile's f32 q and acc rows and lane-replicated m/l rows
    (`_ragged_tiles`); it does not grow with T or the table's width. A
    shape whose smallest tile (one 8-token chunk) does not fit beside the
    page buffer is refused, besides the head dim and dtype."""
    if not _support.kernels_enabled():
        return False
    if len(q_shape) != 3:
        return False
    if q_shape[-1] > 256:
        return False
    if not _support.float_dtype_ok(dtype):
        return False
    kv_h, block_size, d = cache_shape[-3:]
    g = q_shape[1] // kv_h
    return _ragged_tiles(q_shape[0], kv_h, _group_pad(g), d, block_size,
                         table_width, np.dtype(cache_dtype).itemsize) \
        is not None
