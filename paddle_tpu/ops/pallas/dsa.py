"""Pallas TPU kernels of learned sparse attention (DSA) over paged caches:
the indexer's scores of a packed ragged batch against a lane's live pages of
the INDEX pool, and absorbed-MLA attention of a row over the latent rows its
selection names.

`dsa_index_scores` is built as `paged_attention_mla` is (grid = (lanes,),
everything ragged scalar-prefetched, q, the pool and the output in HBM, live
pages only, walked in double-buffered groups; a decode lane's tile is its one
token, a prefill chunk's eight), with nothing carried from one page group to
the next: a group's scores `sum_h w[t, h] ReLU(q_I[t, h] . k_I[s])` leave as
they are made. The output is `[T, S / 128, 128]` float32 so that a token's
group of scores is whole `(8, 128)` tiles however few tokens a lane holds (a
one-row slice of a `[T, S]` array is a piece of a tile, which a DMA cannot
address). What lies past a token's causal context is whatever was there: the
caller masks it (`models/glm_moe_dsa.select` does).

`mla_sparse_attention` attends rows that were GATHERED: `gathered [R, K, DK]`
holds row r's selected latent rows (`sparse_rows` over `row_ids`: a gather of
`(block, offset)` through the block table, `K` rows of `DK` numbers whatever
the context), of which the first `n[r]` count. One grid step a row; the `K` rows
fit VMEM, so the softmax is taken whole. Bytes and FLOPs follow `K`, not the
context.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _support
from .paged_attention import NEG_INF, lane_spans

# query tokens per compute chunk of a lane that holds more than one
_INDEX_Q_CHUNK = 8
# kv positions per page group: the width of one score tile, 16 x 128 (a
# decode lane pays some 3 us a group whatever its width: my chip run, PR 47)
_INDEX_GROUP_COLS = 2048


def _index_pages(block_size, width):
    return max(1, min(_INDEX_GROUP_COLS // block_size, width))


def _index_kernel(layer_ref, kv_lens_ref, q_lens_ref, q_starts_ref,
                  tables_ref, q_hbm, w_hbm, pool_hbm, o_hbm, qbuf, wbuf, kbuf,
                  obuf, sem, *, block_size, pages):
    """See the module docstring. Lane b owns the packed query tokens
    [q_start, q_start + q_len), the first at absolute position kv_len -
    q_len. Per chunk of `qc` tokens a rolled loop walks the lane's live
    pages up to the chunk's last position, `pages` at a time, double
    buffered, one DMA a page; per group one `[qc * heads, D] x [D, cols]`
    score tile, ReLU, the head weights (lane-replicated `[.., 128]`), the
    sum over a token's heads, and one DMA of `[qc, cols / 128, 128]` out,
    double buffered too."""
    heads = qbuf.shape[1]
    cols = pages * block_size
    tiles = cols // 128
    i32 = jnp.int32
    b = pl.program_id(0)
    layer = layer_ref[0]
    kv_len = kv_lens_ref[b]
    q_len = q_lens_ref[b]
    q_start = q_starts_ref[b]

    def lane(qc):
        rows = qc * heads

        def chunk(c, _):
            t0 = c * i32(qc)
            row0 = q_start + t0
            n_tok = jnp.minimum(q_len - t0, i32(qc))
            pos0 = kv_len - q_len + t0
            n_pages = pl.cdiv(pos0 + n_tok, i32(block_size))
            n_groups = pl.cdiv(n_pages, i32(pages))

            q_copy = pltpu.make_async_copy(
                q_hbm.at[pl.ds(row0, qc)], qbuf.at[pl.ds(0, qc)], sem.at[2, 0])
            w_copy = pltpu.make_async_copy(
                w_hbm.at[pl.ds(row0, qc)], wbuf.at[pl.ds(0, qc)], sem.at[2, 1])

            def page_copies(g, slot):
                for p in range(pages):
                    j = jnp.minimum(g * i32(pages) + i32(p), n_pages - 1)
                    yield pltpu.make_async_copy(
                        pool_hbm.at[layer, tables_ref[b, j]],
                        kbuf.at[slot, p], sem.at[0, slot])

            def o_copy(g, slot):
                return pltpu.make_async_copy(
                    obuf.at[slot, pl.ds(0, qc)],
                    o_hbm.at[pl.ds(row0, qc),
                             pl.ds(pl.multiple_of(g * i32(tiles), tiles),
                                   tiles)],
                    sem.at[1, slot])

            q_copy.start()
            w_copy.start()
            for cp in page_copies(i32(0), 0):
                cp.start()
            q_copy.wait()
            w_copy.wait()
            q = qbuf[pl.ds(0, qc)].reshape(rows, qbuf.shape[-1])
            w = wbuf[pl.ds(0, qc)].reshape(rows, 128)

            def group(g, _):
                slot = g % 2

                @pl.when(g + 1 < n_groups)
                def _prefetch():
                    for cp in page_copies(g + 1, 1 - slot):
                        cp.start()

                for cp in page_copies(g, slot):
                    cp.wait()

                @pl.when(g >= 2)
                def _reuse():
                    o_copy(g - 2, slot).wait()

                k = kbuf[slot].reshape(cols, kbuf.shape[-1])
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                s = jnp.maximum(s, jnp.float32(0.0))
                for j in range(tiles):
                    x = s[:, j * 128:(j + 1) * 128] * w
                    red = jnp.sum(x.reshape(qc, heads, 128), axis=1)
                    for r in range(qc):
                        obuf[slot, r, pl.ds(j, 1), :] = red[r:r + 1, :]
                o_copy(g, slot).start()

            jax.lax.fori_loop(0, n_groups, group, None)

            @pl.when(n_groups >= 2)
            def _drain_two():
                o_copy(n_groups - 2, n_groups % 2).wait()

            o_copy(n_groups - 1, (n_groups - 1) % 2).wait()

        jax.lax.fori_loop(0, pl.cdiv(q_len, i32(qc)), chunk, None)

    @pl.when((kv_len > 0) & (q_len == 1))
    def _decode_lane():
        lane(1)

    @pl.when((kv_len > 0) & (q_len > 1))
    def _chunk_lane():
        lane(_INDEX_Q_CHUNK)


def dsa_index_scores(q_i, w, pool, layer, block_tables, kv_lens, tok_lane,
                     tok_pos):
    """The indexer's scores of a packed ragged batch over the index pool.

    Args:
      q_i: `[T, heads, D]` packed indexer queries (lane-major, as
        `ragged_metadata` packs them); w: `[T, heads]` float32 head weights.
      pool: `[L, NB, BS, D]` index keys, read as stored; `layer`: which `L`.
      block_tables `[B, W]`, kv_lens `[B]` (this dispatch's tokens
        included), tok_lane / tok_pos `[T]`: as `paged_attention_ragged`.
    Returns `[T + 8, S / 128, 128]` float32, `S >= W * BS` (whole page
    groups): row t's `I[t, s]` at `[t, s // 128, s % 128]` for every
    position `s` of its lane up to its own; what lies past that, and the 8
    spare rows, is NOT defined. `score_rows` reads rows of it.
    """
    tokens, heads, d = q_i.shape
    block_size = pool.shape[2]
    lanes, width = block_tables.shape
    pages = _index_pages(block_size, width)
    cols = pages * block_size
    groups = -(-width // pages)
    qc = _INDEX_Q_CHUNK
    q = jnp.pad(q_i.astype(pool.dtype), ((0, qc), (0, 0), (0, 0)))
    wrep = jnp.broadcast_to(
        jnp.pad(w.astype(jnp.float32), ((0, qc), (0, 0)))[:, :, None],
        (tokens + qc, heads, 128))
    q_lens, q_starts = lane_spans(tok_lane, tok_pos, lanes)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out_shape = (tokens + qc, groups * cols // 128, 128)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(lanes,),
        in_specs=[hbm, hbm, hbm],
        out_specs=hbm,
        scratch_shapes=[
            pltpu.VMEM((qc, heads, d), pool.dtype),                 # q chunk
            pltpu.VMEM((qc, heads, 128), jnp.float32),              # weights
            pltpu.VMEM((2, pages, block_size, d), pool.dtype),      # pages
            pltpu.VMEM((2, qc, cols // 128, 128), jnp.float32),     # scores
            pltpu.SemaphoreType.DMA((3, 2)),   # pages, out by slot; q, w
        ],
    )
    return _support.pallas_call(
        functools.partial(_index_kernel, block_size=block_size, pages=pages),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="dsa_index_scores",
        interpret=_support.interpret_mode(),
    )(jnp.asarray(layer, jnp.int32).reshape(1), kv_lens.astype(jnp.int32),
      q_lens, q_starts, block_tables.astype(jnp.int32), q, wrep, pool)


def score_rows(scores, r0, rows: int, positions: int):
    """Rows `[r0, r0 + rows)` of what `dsa_index_scores` (or its ref)
    returned, as `[rows, positions]`: the tiles of a few rows re-laid, not
    of the whole buffer (125 MB at the benchmark's size)."""
    cut = jax.lax.dynamic_slice_in_dim(scores, r0, rows, 0)
    return cut.reshape(rows, -1)[:, :positions]


# the ref gathers each token's whole window: bound what is live at once
_REF_TOKEN_TILE = 64


def dsa_index_scores_ref(q_i, w, pool, layer, block_tables, kv_lens, tok_lane,
                         tok_pos):
    """XLA reference of `dsa_index_scores` (and the path off the TPU): per
    packed token a gather of its lane's window, `[T, W * BS]` (`score_rows`
    reads it too). Scores past a token's own position are computed too (the
    kernel leaves them undefined)."""
    del kv_lens, tok_pos
    tokens, heads, d = q_i.shape
    block_size = pool.shape[2]
    max_s = block_tables.shape[1] * block_size
    keys = jnp.take(pool[layer], block_tables, axis=0) \
        .reshape(block_tables.shape[0], max_s, d)          # [B, S, D]

    def tile(args):
        q, wt, lane = args
        kt = jnp.take(keys, lane, axis=0)                  # [t, S, D]
        s = jnp.einsum("thd,tsd->ths", q.astype(kt.dtype), kt,
                       preferred_element_type=jnp.float32)
        return jnp.sum(jax.nn.relu(s) * wt[:, :, None], axis=1)

    n = _REF_TOKEN_TILE
    w = w.astype(jnp.float32)
    if tokens <= n:
        return tile((q_i, w, tok_lane))
    pad = (-tokens) % n
    q = jnp.pad(q_i, ((0, pad), (0, 0), (0, 0)))
    wt = jnp.pad(w, ((0, pad), (0, 0)))
    lane = jnp.pad(tok_lane, (0, pad))
    out = jax.lax.map(tile, (q.reshape(-1, n, heads, d),
                             wt.reshape(-1, n, heads), lane.reshape(-1, n)))
    return out.reshape(-1, max_s)[:tokens]


def index_supported(q_shape, pool_shape, pool_dtype, table_width) -> bool:
    """Gate for `dsa_index_scores` (q `[T, heads, D]`, the pool's `[L, NB,
    BS, D]`): kernels enabled, a float pool, a page group whole 128-lane
    tiles wide; on the TPU whole lane tiles in `D` and whole sublane tiles
    of heads."""
    if not _support.kernels_enabled():
        return False
    if not _support.float_dtype_ok(pool_dtype):
        return False
    _, heads, d = q_shape
    block_size = pool_shape[2]
    if (_index_pages(block_size, table_width) * block_size) % 128:
        return False
    if _support.on_tpu() and (d % 128 or heads % 8):
        return False
    return True


# --- attention over gathered rows ----------------------------------------------------

def row_ids(block_tables, tok_lane, idx, block_size: int):
    """Where row r's selected positions lie in a pool: `idx [R, K]`
    positions in the sequence of `tok_lane[r]` -> `[R, K]` int32, `block *
    block_size + offset` through the lane's block table. An entry of `idx`
    that is no selection names whatever block the table names there (every
    table entry is a block of the pool). The same for every pool and layer
    on that table: layers that share a selection share these."""
    width = block_tables.shape[1]
    blk = jnp.take_along_axis(
        jnp.take(block_tables, tok_lane, axis=0),
        jnp.clip(idx // block_size, 0, width - 1), axis=1)
    return blk * block_size + idx % block_size


def sparse_rows(pool, layer: int, ids):
    """Row r's selected cache rows, gathered: `pool [L, NB, BS, DK]`, `ids
    [R, K]` (`row_ids`) -> `[R, K, DK]`. One flat gather over the pool read
    as `[L * NB * BS, DK]`: no slice of a layer is made."""
    layers, nb, bs, dk = pool.shape
    return jnp.take(pool.reshape(layers * nb * bs, dk),
                    ids + jnp.int32(layer * nb * bs), axis=0, mode="clip")


def _sparse_kernel(n_ref, q_ref, g_ref, o_ref, *, sm_scale, v_dim):
    n = n_ref[pl.program_id(0)]
    q = q_ref[0]                                            # [H, DK]
    g = g_ref[0]                                            # [K, DK]
    s = jax.lax.dot_general(q, g, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    chosen = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) < n
    s = jnp.where(chosen, s, jnp.float32(NEG_INF))
    m = jnp.max(s, axis=1, keepdims=True)
    p = jnp.where(chosen, jnp.exp(s - m), jnp.float32(0.0))
    l = jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(p.astype(g.dtype), g[:, :v_dim],
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    o_ref[0] = (pv / jnp.where(l == 0.0, jnp.float32(1.0), l)) \
        .astype(o_ref.dtype)


def mla_sparse_attention(q_abs, gathered, n, v_dim, sm_scale):
    """Absorbed-MLA attention of rows over their gathered selections.

    Args:
      q_abs: `[R, H, <= DK]` each head's `[q_lat | q_rope]`.
      gathered: `[R, K, DK]` row r's selected cache rows (`sparse_rows`),
        key over their whole width and value in their first `v_dim` columns.
      n: `[R]` int32, how many of row r's `K` count (0: a guard row).
    Returns `[R, H, v_dim]` in q's dtype; a row with `n` 0 is exact zeros.
    """
    rows, heads, _ = q_abs.shape
    _, k, dk = gathered.shape
    q = jnp.pad(q_abs.astype(gathered.dtype),
                ((0, 0), (0, 0), (0, dk - q_abs.shape[-1])))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows,),
        in_specs=[pl.BlockSpec((1, heads, dk), lambda r, n: (r, 0, 0)),
                  pl.BlockSpec((1, k, dk), lambda r, n: (r, 0, 0))],
        out_specs=pl.BlockSpec((1, heads, v_dim), lambda r, n: (r, 0, 0)),
    )
    out = _support.pallas_call(
        functools.partial(_sparse_kernel, sm_scale=float(sm_scale),
                          v_dim=v_dim),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, heads, v_dim), gathered.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="mla_sparse_attention",
        interpret=_support.interpret_mode(),
    )(n.astype(jnp.int32), q, gathered)
    return out.astype(q_abs.dtype)


def mla_sparse_attention_ref(q_abs, gathered, n, v_dim, sm_scale):
    """XLA reference of `mla_sparse_attention` (and the path off the TPU):
    a masked softmax in f32 over each row's gathered rows."""
    dk = gathered.shape[-1]
    q = jnp.pad(q_abs, ((0, 0), (0, 0), (0, dk - q_abs.shape[-1])))
    g = gathered.astype(jnp.float32)
    s = jnp.einsum("rhd,rkd->rhk", q.astype(jnp.float32), g,
                   precision=jax.lax.Precision.HIGHEST) * sm_scale
    chosen = (jnp.arange(g.shape[1])[None, :] < n[:, None])[:, None, :]
    p = jax.nn.softmax(jnp.where(chosen, s, NEG_INF), axis=-1)
    out = jnp.einsum("rhk,rkc->rhc", jnp.where(chosen, p, 0.0),
                     g[..., :v_dim], precision=jax.lax.Precision.HIGHEST)
    return jnp.where((n > 0)[:, None, None], out, 0.0).astype(q_abs.dtype)


def sparse_supported(q_shape, gathered_shape, dtype, v_dim) -> bool:
    """Gate for `mla_sparse_attention`: kernels enabled, a float cache; on
    the TPU whole lane tiles in `DK`, `v_dim` and `K` and whole sublane
    tiles of heads."""
    if not _support.kernels_enabled():
        return False
    if not _support.float_dtype_ok(dtype):
        return False
    _, heads, _ = q_shape
    _, k, dk = gathered_shape
    if _support.on_tpu() and (dk % 128 or v_dim % 128 or k % 128
                              or heads % 8):
        return False
    return True
