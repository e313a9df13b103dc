"""Learned sparse attention (DSA) over paged caches: two Pallas TPU kernels,
the indexer's scores of a packed ragged batch against a lane's live pages of
the INDEX pool and absorbed-MLA attention of a row over the latent rows its
selection names, and between them the selection of each row's best positions.

`dsa_index_scores` is built as `paged_attention_mla` is (grid = (lanes,),
everything ragged scalar-prefetched, q, the pool and the output in HBM, live
pages only, walked in double-buffered groups; a decode lane's tile is its one
token, a prefill chunk's eight), with nothing carried from one page group to
the next: a group's scores `sum_h w[t, h] ReLU(q_I[t, h] . k_I[s])` leave as
they are made. The output is `[T, S / 128, 128]` float32 so that a token's
group of scores is whole `(8, 128)` tiles however few tokens a lane holds (a
one-row slice of a `[T, S]` array is a piece of a tile, which a DMA cannot
address). What lies past a token's causal context is whatever was there: the
caller masks it (`select_keys` does).

`dsa_select` picks a tile of rows' `index_topk` best positions out of those
scores WITHOUT a sort (the v5e compiler lowers `lax.top_k` to a full sort of
the row), in plain `jnp`: the k-th largest score is found exactly, by
bisection over the bits of the float32 scores mapped to integers of the same
order (32 passes of compare-and-count), and the chosen positions are placed
by their rank (a product with a triangle of ones inside a group of 128, a
prefix across groups, a product with a one-hot to bring a group's counts to
a slot). The set is `models/glm_moe_dsa.select`'s to the last tie; the order
is by position.

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


def index_prepare(q_i, w, pool) -> tuple:
    """Row-wise, before the rows are placed: the indexer's queries `q_i [n,
    heads, D]` in the pool's dtype and its head weights `w [n, heads]` as
    float32 `[n, heads, 128]` (a weight a lane tile, as the kernel reads
    them), each with the spare chunk a lane's last chunk's DMA may run
    over: two `Packed`."""
    n, heads = w.shape
    return (_support.Packed(q_i.astype(pool.dtype), _INDEX_Q_CHUNK),
            _support.Packed(jnp.broadcast_to(
                w.astype(jnp.float32)[:, :, None], (n, heads, 128)),
                _INDEX_Q_CHUNK))


def dsa_index_scores_packed(q, wrep, pool, layer, block_tables, kv_lens,
                            tok_lane, tok_pos):
    """`dsa_index_scores` on the buffers as the kernel takes them: q `[T +
    8, heads, D]` and wrep `[T + 8, heads, 128]` (`index_prepare`, placed;
    `T` is `tok_lane`'s). Nothing is padded or filled."""
    tokens = tok_lane.shape[0]
    _, heads, d = q.shape
    block_size = pool.shape[2]
    lanes, width = block_tables.shape
    pages = _index_pages(block_size, width)
    cols = pages * block_size
    groups = -(-width // pages)
    qc = _INDEX_Q_CHUNK
    if q.shape[0] != tokens + qc or wrep.shape != (tokens + qc, heads, 128) \
            or q.dtype != pool.dtype:
        raise ValueError(f"dsa_index_scores_packed: q {q.dtype}{q.shape}, "
                         f"w {wrep.shape} are not a placed `index_prepare`")
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
    spare rows, is NOT defined. `score_tile` cuts rows of it.
    `index_prepare`, placed, through `dsa_index_scores_packed`.
    """
    tokens = q_i.shape[0]
    return dsa_index_scores_packed(
        *_support.place(index_prepare(q_i, w, pool), tokens, tokens), pool,
        layer, block_tables, kv_lens, tok_lane, tok_pos)


def score_tile(scores, r0, rows: int, positions: int):
    """Rows `[r0, r0 + rows)` of what `dsa_index_scores` (or its ref)
    returned, over their first `positions` columns, as `[rows, G, 128]`
    (`G` whole groups of 128; the kernel's own layout, cut and not re-laid):
    what `dsa_select` takes. Columns past `positions` in the last group are
    whatever was there (the ref's are `-inf`)."""
    cut = jax.lax.dynamic_slice_in_dim(scores, r0, rows, 0)
    groups = -(-positions // 128)
    if cut.ndim == 3:
        return cut[:, :groups]
    cut = cut[:, :positions]
    cut = jnp.pad(cut, ((0, 0), (0, groups * 128 - cut.shape[1])),
                  constant_values=-jnp.inf)
    return cut.reshape(rows, groups, 128)


# the ref gathers each token's whole window: bound what is live at once
_REF_TOKEN_TILE = 64


def dsa_index_scores_ref(q_i, w, pool, layer, block_tables, kv_lens, tok_lane,
                         tok_pos):
    """XLA reference of `dsa_index_scores` (and the path off the TPU): per
    packed token a gather of its lane's window, `[T, W * BS]` (`score_tile`
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


# --- the selection: a threshold and a rank, not a sort ------------------------------

_KEY_MIN = -2 ** 31


def select_keys(tile, pos):
    """Step 1: the scores `tile [R, G, 128]` float32 of rows at positions
    `pos [R]` as int32 keys whose order is the selection's: a column past
    its row's position is `-inf`, and a float's bits `b` become `b ^ ((b >>
    31) & 0x7fffffff)`, which orders as `lax.top_k` orders floats (their
    total order: `-0.0` under `+0.0`, a denormal by its bits)."""
    rows, groups, _ = tile.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, groups, 128), 1) * 128 \
        + jax.lax.broadcasted_iota(jnp.int32, (rows, groups, 128), 2)
    b = jax.lax.bitcast_convert_type(
        jnp.where(col <= pos.astype(jnp.int32)[:, None, None],
                  tile.astype(jnp.float32), -jnp.inf), jnp.int32)
    return b ^ ((b >> 31) & jnp.int32(0x7fffffff))


def select_threshold(keys, k: int):
    """Step 2: each row's k-th largest key, exactly, by bisection over its
    32 bits from the top (`t + 2^bit` wraps from the least int32 to 0 at
    the sign bit): a pass counts `key >= candidate` a row. Plain XLA: the
    v5e compiler keeps a tile's 7.3 MB of keys in VMEM over the 32 passes
    (0.077 ms at the table's span beside 0.082 for a Pallas kernel that held
    eight rows in VMEM: my chip run, PR 48), so there is no kernel."""
    def bit(i, t):
        cand = t + (jnp.int32(1) << (31 - i).astype(jnp.int32))
        count = jnp.sum(keys >= cand[:, None, None], axis=(1, 2),
                        dtype=jnp.int32)
        return jnp.where(count >= k, cand, t)

    return jax.lax.fori_loop(
        0, 32, bit, jnp.full((keys.shape[0],), _KEY_MIN, jnp.int32))


def select_place(keys, t, k: int):
    """Steps 3 and 4: the positions of the `k` largest keys of each row of
    `keys [R, G, 128]`, given the row's k-th largest `t [R]`, in position
    order, `[R, k]` int32. In are the keys over `t` and, of those equal to
    it, the first `k - count(key > t)` by position (`lax.top_k`'s ties).

    A chosen position's rank is how many chosen ones lie before it: inside
    a group of 128 a product with a triangle of ones (counts to 128 are
    exact in bfloat16), across groups a prefix over `G` totals. Slot `j`
    lies in the group `g_j` whose prefix first passes `j`; that group's
    counts reach the slot as `one_hot(g_j) @ counts`, and the offset in it
    is how many of them are at most the slot's rank in the group. No
    scatter, no gather, no sort."""
    rows, groups, _ = keys.shape
    i32 = jnp.int32
    lane = jax.lax.broadcasted_iota(i32, (128, 128), 0)
    upto = (lane <= lane.T).astype(jnp.bfloat16)        # [l', l]: l' <= l
    t = t[:, None, None]
    over_eq = jnp.stack([keys > t, keys == t]).astype(jnp.bfloat16)
    over, eq = jnp.einsum("xrgl,lm->xrgm", over_eq, upto,
                          preferred_element_type=jnp.float32).astype(i32)
    need = (k - jnp.sum(over[:, :, -1], axis=1, dtype=i32))[:, None]
    eq_before = jnp.cumsum(eq[:, :, -1], axis=1, dtype=i32) - eq[:, :, -1]
    tied = jnp.minimum(eq_before[:, :, None] + eq, need[:, :, None]) \
        - jnp.minimum(eq_before, need)[:, :, None]
    counts = over + tied                  # chosen up to each lane of a group
    ends = jnp.cumsum(counts[:, :, -1], axis=1, dtype=i32)      # [R, G]
    slot = jax.lax.broadcasted_iota(i32, (rows, k, groups), 1)
    passed = ends[:, None, :] <= slot
    g = jnp.sum(passed, axis=2, dtype=i32)                      # [R, k]
    before = jnp.max(jnp.where(passed, ends[:, None, :], 0), axis=2)
    mine = jnp.einsum(
        "rjg,rgl->rjl", jax.nn.one_hot(g, groups, dtype=jnp.bfloat16),
        counts.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
    rank = (slot[:, :, 0] - before).astype(jnp.float32)[:, :, None]
    return g * 128 + jnp.sum(mine <= rank, axis=2, dtype=i32)


def dsa_select(tile, pos, k: int):
    """The selection of rows whose scores are `tile [R, G, 128]` float32
    (`score_tile`) and whose own positions are `pos [R]` (negative: a guard
    row): `models/glm_moe_dsa.select`'s set, `(idx [R, k] int32, n [R]
    int32)`, the `n = min(k, pos + 1)` causal positions of largest score
    first in `idx`, ties to the lower position, IN POSITION ORDER; what
    follows them in `idx` is not a selection (the lowest positions past the
    row's own). Found by a threshold (`select_threshold`) and placed by
    rank (`select_place`): plain `jnp`, on the TPU and off it."""
    k = min(k, tile.shape[1] * 128)
    keys = select_keys(tile, pos)
    idx = select_place(keys, select_threshold(keys, k), k)
    return idx, jnp.clip(pos + 1, 0, k).astype(jnp.int32)


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
