"""Pallas TPU ragged paged attention over a LATENT cache (absorbed MLA).

The cache pool holds one row a token a layer, `[c | rotated k_rope]`
(`kv_lora_rank + qk_rope_head_dim` numbers: `[L, NB, BS, DK]`, the layer a
leading index, never a slice). Every query head attends to the same row,
which is key over its whole width and value in its first `v_dim` columns,
so a page is fetched ONCE and serves as both.

On the TPU `DK` and `v_dim` are multiples of 128: Mosaic refuses to slice a
buffer whose minor dimension is not whole lane tiles, and XLA lays a
576-wide bf16 pool out with ANOTHER dimension minor (which the kernel could
only read after a relayout of the whole pool). So the pool's owner pads a
row with zero columns to the next 128 (576 -> 640); q is padded to match
(`mla_prepare`), and zero columns add nothing to a score.

Built as `paged_attention_ragged` is (`paged_attention.py`, whose packing,
metadata and online-softmax step it shares): grid = (lanes,), everything
ragged scalar-prefetched, the packed q buffer, the pool and the output in
HBM, live pages only, walked in double-buffered groups. What differs: no kv
heads to loop over; a query token's MXU rows are its heads; a decode lane
(`q_len` 1) takes a path of its own whose tile is just those heads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _support
from .paged_attention import (NEG_INF, _RAGGED_VMEM_BUDGET, lane_spans,
                              online_softmax_step)

# query tokens per compute chunk of a lane that holds more than one (a
# prefill chunk, a verify window); a decode lane's chunk is its one token
_MLA_Q_CHUNK = 4
# kv positions per page group: the width of one score tile
_MLA_GROUP_COLS = 512
# query tokens per tile at most (their f32 accumulators sit in VMEM)
_MLA_MAX_Q_TILE = 32


def _lanes_of(d):
    return -(-d // 128) * 128


def _mla_tiles(tokens, heads, dk, dv, block_size, width, itemsize):
    """Static tile sizes from shapes alone: `(pages, q_tile)`, or None when
    one chunk does not fit `_RAGGED_VMEM_BUDGET` beside the page buffers."""
    pages = max(1, min(_MLA_GROUP_COLS // block_size, width))
    page_bytes = 2 * pages * block_size * _lanes_of(dk) * itemsize
    per_token = heads * (_lanes_of(dk) * itemsize      # q
                         + _lanes_of(dv) * (4 + itemsize)   # acc, out
                         + 2 * 128 * 4)                 # m, l
    fit = (_RAGGED_VMEM_BUDGET - page_bytes) // (per_token * _MLA_Q_CHUNK)
    if fit < 1:
        return None
    chunks = min(1 << (fit.bit_length() - 1),
                 _MLA_MAX_Q_TILE // _MLA_Q_CHUNK,
                 -(-tokens // _MLA_Q_CHUNK))
    return pages, chunks * _MLA_Q_CHUNK


def _mla_kernel(layer_ref, kv_lens_ref, q_lens_ref, q_starts_ref, tables_ref,
                q_hbm, pool_hbm, o_hbm, qbuf, kbuf, acc_ref, obuf, m_ref,
                l_ref, sem, *, sm_scale, block_size, pages, q_tile, v_dim):
    """See the module docstring. Lane b owns the packed query tokens
    [q_start, q_start + q_len), the first at absolute position kv_len -
    q_len. Per tile of `q_tile` tokens a rolled loop walks the lane's live
    pages up to the tile's last position, `pages` at a time, double
    buffered, one DMA a page; per page group and per live chunk of tokens
    one `[rows, DK] x [DK, cols]` score tile and one `[rows, cols] x [cols,
    v_dim]` update on the SAME page buffer, online softmax in f32, the
    probabilities fed to the MXU in the pool's own dtype. Rows of a tile's
    last chunk past the lane's tokens are written as zeros and overwritten
    by the next lane; a row no lane owns is never written (the output is
    whatever its allocation held there)."""
    heads = qbuf.shape[1]
    cols = pages * block_size
    i32 = jnp.int32
    b = pl.program_id(0)
    layer = layer_ref[0]
    kv_len = kv_lens_ref[b]
    q_len = q_lens_ref[b]
    q_start = q_starts_ref[b]

    def lane(qc):
        """The lane's tiles at `qc` query tokens a compute chunk."""
        rows = qc * heads

        def chunk_loop(n, body):
            jax.lax.fori_loop(0, n, lambda c, _: body(c), None)

        def toks(c):
            return pl.ds(c * i32(qc), qc)

        def band(c):
            return pl.ds(pl.multiple_of(c * i32(rows), rows), rows)

        def tile(i):
            t0 = i * i32(q_tile)
            n_tok = jnp.minimum(q_len - t0, i32(q_tile))
            n_chunks = pl.cdiv(n_tok, i32(qc))
            pos0 = kv_len - q_len + t0
            n_pages = pl.cdiv(pos0 + n_tok, i32(block_size))
            n_groups = pl.cdiv(n_pages, i32(pages))

            def q_copy(c):
                return pltpu.make_async_copy(
                    q_hbm.at[pl.ds(q_start + t0 + c * i32(qc), qc)],
                    qbuf.at[toks(c)], sem.at[1, 0])

            def o_copy(c):
                return pltpu.make_async_copy(
                    obuf.at[toks(c)],
                    o_hbm.at[pl.ds(q_start + t0 + c * i32(qc), qc)],
                    sem.at[1, 1])

            def page_copies(g, slot):
                for p in range(pages):
                    j = jnp.minimum(g * i32(pages) + i32(p), n_pages - 1)
                    yield pltpu.make_async_copy(
                        pool_hbm.at[layer, tables_ref[b, j]],
                        kbuf.at[slot, p], sem.at[0, slot])

            chunk_loop(n_chunks, lambda c: q_copy(c).start())
            for cp in page_copies(i32(0), 0):
                cp.start()

            def init(c):
                acc_ref[toks(c)] = jnp.zeros(
                    (qc,) + acc_ref.shape[1:], jnp.float32)
                m_ref[band(c), :] = jnp.full((rows, 128), NEG_INF,
                                             jnp.float32)
                l_ref[band(c), :] = jnp.zeros((rows, 128), jnp.float32)

            chunk_loop(n_chunks, init)
            chunk_loop(n_chunks, lambda c: q_copy(c).wait())

            def group(g, _):
                slot = g % 2

                @pl.when(g + 1 < n_groups)
                def _prefetch():
                    for cp in page_copies(g + 1, 1 - slot):
                        cp.start()

                for cp in page_copies(g, slot):
                    cp.wait()
                k = kbuf[slot].reshape(cols, kbuf.shape[-1])
                v = kbuf[slot, :, :, :v_dim].reshape(cols, v_dim)
                kv_pos = g * i32(cols) + jax.lax.broadcasted_iota(
                    i32, (rows, cols), 1)
                tok = jax.lax.broadcasted_iota(
                    i32, (rows, cols), 0) // i32(heads)

                def chunk(c):
                    ts, rs = toks(c), band(c)
                    q = qbuf[ts].reshape(rows, qbuf.shape[-1])
                    s = jax.lax.dot_general(
                        q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * sm_scale
                    live = kv_pos <= jnp.minimum(
                        pos0 + c * i32(qc) + tok, kv_len - 1)
                    s = jnp.where(live, s, jnp.float32(NEG_INF))
                    p, alpha, m_ref[rs, :], l_ref[rs, :] = \
                        online_softmax_step(s, m_ref[rs, :], l_ref[rs, :])
                    pv = jax.lax.dot_general(
                        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    acc = acc_ref[ts].reshape(rows, v_dim)
                    acc_ref[ts] = (acc * alpha[:, :1] + pv).reshape(
                        qc, heads, v_dim)

                chunk_loop(n_chunks, chunk)

            jax.lax.fori_loop(0, n_groups, group, None)

            def finish(c):
                ts, rs = toks(c), band(c)
                owned = (c * i32(qc) + jax.lax.broadcasted_iota(
                    i32, (rows, v_dim), 0) // i32(heads)) < n_tok
                l = l_ref[rs, :][:, :1]
                l_safe = jnp.where(l == 0.0, jnp.float32(1.0), l)
                out = acc_ref[ts].reshape(rows, v_dim) / l_safe
                obuf[ts] = jnp.where(owned, out, jnp.float32(0.0)).reshape(
                    qc, heads, v_dim).astype(obuf.dtype)
                o_copy(c).start()

            chunk_loop(n_chunks, finish)
            chunk_loop(n_chunks, lambda c: o_copy(c).wait())

        n_tiles = pl.cdiv(q_len, i32(q_tile))
        jax.lax.fori_loop(0, n_tiles, lambda i, _: tile(i), None)

    @pl.when((kv_len > 0) & (q_len == 1))
    def _decode_lane():
        lane(1)

    @pl.when((kv_len > 0) & (q_len > 1))
    def _chunk_lane():
        lane(_MLA_Q_CHUNK)


def _mla_call(q, pool, layer, block_tables, kv_lens, q_lens, q_starts,
              sm_scale, tiles, v_dim):
    """q `[T + chunk, H, DK]` in the MXU's dtype; the pool as stored. Returns
    `[T + chunk, H, v_dim]` in q's dtype, written where a lane owns rows."""
    _, heads, dk = q.shape
    block_size = pool.shape[2]
    lanes = block_tables.shape[0]
    pages, q_tile = tiles
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(lanes,),
        in_specs=[hbm, hbm],
        out_specs=hbm,
        scratch_shapes=[
            pltpu.VMEM((q_tile, heads, dk), q.dtype),               # q tile
            pltpu.VMEM((2, pages, block_size, dk), pool.dtype),     # pages
            pltpu.VMEM((q_tile, heads, v_dim), jnp.float32),        # acc
            pltpu.VMEM((q_tile, heads, v_dim), q.dtype),            # out tile
            pltpu.VMEM((q_tile * heads, 128), jnp.float32),         # m
            pltpu.VMEM((q_tile * heads, 128), jnp.float32),         # l
            pltpu.SemaphoreType.DMA((2, 2)),   # pages by slot; q, out
        ],
    )
    return _support.pallas_call(
        functools.partial(_mla_kernel, sm_scale=sm_scale,
                          block_size=block_size, pages=pages, q_tile=q_tile,
                          v_dim=v_dim),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape[:2] + (v_dim,), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="paged_attention_mla",
        interpret=_support.interpret_mode(),
    )(layer, kv_lens, q_lens, q_starts, block_tables, q, pool)


def mla_prepare(q_abs, pool) -> _support.Packed:
    """Row-wise, before the rows are placed: `q_abs [n, H, <= DK]` in the
    pool's dtype (the MXU takes it: bf16 products are exact in the f32
    accumulator) with zero columns up to the pool's `DK`, and the spare
    chunk a lane's last chunk's DMA may run over."""
    dk = pool.shape[-1]
    return _support.Packed(
        jnp.pad(q_abs.astype(pool.dtype),
                ((0, 0), (0, 0), (0, dk - q_abs.shape[-1]))), _MLA_Q_CHUNK)


def paged_attention_mla_packed(q, pool, layer, block_tables, kv_lens,
                               tok_lane, tok_pos, v_dim, sm_scale):
    """`paged_attention_mla` on the buffer as the kernel takes it and leaves
    it: q `[T + chunk, H, DK]` (`mla_prepare`, placed; `T` is `tok_lane`'s).
    Returns `[T + chunk, H, v_dim]` in the pool's dtype: a live row's
    answer; a guard row and the spare chunk hold whatever. Nothing is
    padded, filled or cut."""
    tokens = tok_lane.shape[0]
    heads = q.shape[1]
    block_size, dk = pool.shape[2:]
    lanes, width = block_tables.shape
    tiles = _mla_tiles(tokens, heads, dk, v_dim, block_size, width,
                       pool.dtype.itemsize)
    if tiles is None:
        raise ValueError(
            f"paged_attention_mla: no tile of {heads} heads x {dk} fits "
            "VMEM; ask mla_supported first")
    if q.shape != (tokens + _MLA_Q_CHUNK, heads, dk) or q.dtype != pool.dtype:
        raise ValueError(f"paged_attention_mla_packed: q {q.dtype}{q.shape} "
                         "is not a placed `mla_prepare`")
    q_lens, q_starts = lane_spans(tok_lane, tok_pos, lanes)
    return _mla_call(q, pool, jnp.asarray(layer, jnp.int32).reshape(1),
                     block_tables.astype(jnp.int32),
                     kv_lens.astype(jnp.int32), q_lens, q_starts,
                     float(sm_scale), tiles, v_dim)


def paged_attention_mla(q_abs, pool, layer, block_tables, kv_lens, tok_lane,
                        tok_pos, v_dim, sm_scale):
    """Absorbed-MLA attention of a packed ragged batch over the latent pool.

    Args:
      q_abs: `[T, H, DK]` packed query tokens (lane-major, as
        `ragged_metadata` packs them), each head's `[q_lat | q_rope]`.
      pool: `[L, NB, BS, DK]`, read as stored; `layer`: which `L`, a traced
        int32 scalar (one compiled kernel serves every layer).
      block_tables `[B, W]`, kv_lens `[B]` (this dispatch's tokens
        included), tok_lane / tok_pos `[T]`: as `paged_attention_ragged`.
      v_dim: the leading columns of a row that are its value.
    Returns `[T, H, v_dim]` in q's dtype; guard rows are exact zeros.
    `mla_prepare`, placed, through `paged_attention_mla_packed`, and of
    what it left the live rows.
    """
    tokens = q_abs.shape[0]
    out = paged_attention_mla_packed(
        _support.place(mla_prepare(q_abs, pool), tokens, tokens), pool,
        layer, block_tables, kv_lens, tok_lane, tok_pos, v_dim, sm_scale)
    return jnp.where((tok_pos >= 0)[:, None, None], out[:tokens],
                     0).astype(q_abs.dtype)


# the ref gathers each token's whole window: bound what is live at once
_REF_TOKEN_TILE = 64


def paged_attention_mla_ref(q_abs, pool, layer, block_tables, kv_lens,
                            tok_lane, tok_pos, v_dim, sm_scale):
    """XLA reference of `paged_attention_mla` (and the path off the TPU):
    per packed token a gather of its lane's window and a masked softmax in
    f32; guard rows come back exact zeros. `kv_lens` is implied by
    `tok_pos` and unused."""
    del kv_lens
    tokens, heads, _ = q_abs.shape
    block_size, dk = pool.shape[2:]
    q_abs = jnp.pad(q_abs, ((0, 0), (0, 0), (0, dk - q_abs.shape[-1])))
    max_s = block_tables.shape[1] * block_size
    rows = jnp.take(pool[layer], block_tables, axis=0) \
        .reshape(block_tables.shape[0], max_s, dk)        # [B, S, DK]
    wpos = jnp.arange(max_s, dtype=jnp.int32)

    def tile(args):
        q, lane, pos = args
        kt = jnp.take(rows, lane, axis=0).astype(jnp.float32)   # [t, S, DK]
        s = jnp.einsum("thd,tsd->ths", q.astype(jnp.float32), kt,
                       precision=jax.lax.Precision.HIGHEST) * sm_scale
        mask = wpos[None, :] <= pos[:, None]
        p = jax.nn.softmax(jnp.where(mask[:, None, :], s, NEG_INF), axis=-1)
        out = jnp.einsum("ths,tsc->thc", p, kt[..., :v_dim],
                         precision=jax.lax.Precision.HIGHEST)
        return jnp.where((pos >= 0)[:, None, None], out, 0.0)

    n = _REF_TOKEN_TILE
    if tokens <= n:
        return tile((q_abs, tok_lane, tok_pos)).astype(q_abs.dtype)
    pad = (-tokens) % n
    q = jnp.pad(q_abs, ((0, pad), (0, 0), (0, 0)))
    lane = jnp.pad(tok_lane, (0, pad))
    pos = jnp.pad(tok_pos, (0, pad), constant_values=-1)
    out = jax.lax.map(tile, (q.reshape(-1, n, heads, dk),
                             lane.reshape(-1, n), pos.reshape(-1, n)))
    return out.reshape(-1, heads, v_dim)[:tokens].astype(q_abs.dtype)


def mla_supported(q_shape, pool_shape, pool_dtype, table_width, v_dim) -> bool:
    """Gate for `paged_attention_mla` (q `[T, H, <= DK]`, the pool's `[L,
    NB, BS, DK]`): kernels enabled, a float pool, on the TPU whole lane
    tiles in `DK` and `v_dim`, and one chunk's buffers fit VMEM beside the
    page double buffer."""
    if not _support.kernels_enabled():
        return False
    if not _support.float_dtype_ok(pool_dtype):
        return False
    tokens, heads, _ = q_shape
    dk = pool_shape[-1]
    if _support.on_tpu() and (dk % 128 or v_dim % 128):
        return False
    return _mla_tiles(tokens, heads, dk, v_dim, pool_shape[2], table_width,
                      np.dtype(pool_dtype).itemsize) is not None
