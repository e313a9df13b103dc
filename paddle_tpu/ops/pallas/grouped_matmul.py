"""Pallas TPU grouped matmul: `out[rows of group g] = lhs[rows of group g] @
rhs[g]` over rows sorted by group, with no capacity and no dropped row. The
expert FFN of a dropless mixture of experts (`models/deepseek_v3.py`).

The megablox construction (`jax.experimental.pallas.ops.tpu.megablox`, whose
group metadata it uses), cut to what serving needs: the grid is the list of
(row tile, group) pairs that hold a row, a traced length, so a group with no
row is never visited and its matrix never read, and rows past the groups'
sum belong to no pair and cost nothing. A pair's block of `rhs` is the
group's whole `[K, tn]` matrix: at decode a step touches most experts with a
row or two each, and the kernel is bound by the bytes of their matrices,
each read once. Rows of a tile that belong to another group are masked in
the store; a tile's first pair zeroes the rest.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

from . import _support

_ROW_TILE = 128
# one block of rhs: double-buffered, beside the row tile and the output
_RHS_BLOCK_BYTES = 4 << 20


def _col_tile(k, n, itemsize):
    """Columns of `rhs` a block holds: all of them if `[k, n]` fits
    `_RHS_BLOCK_BYTES`, else the largest 128-multiple divisor that does."""
    if k * n * itemsize <= _RHS_BLOCK_BYTES:
        return n
    for tn in range(n - n % 128, 0, -128):
        if n % tn == 0 and k * tn * itemsize <= _RHS_BLOCK_BYTES:
            return tn
    return None


def _kernel(offsets_ref, groups_ref, tiles_ref, lhs_ref, rhs_ref, out_ref, *,
            tm):
    w = pl.program_id(1)
    tile = tiles_ref[w]
    acc = jax.lax.dot_general(lhs_ref[...], rhs_ref[...],
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    rows = tile * tm + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
    group = groups_ref[w]
    mine = (rows >= offsets_ref[group]) & (rows < offsets_ref[group + 1])
    first = (w == 0) | (tiles_ref[jnp.maximum(w - 1, 0)] != tile)
    # a tile's first pair zeroes the rows of the others (what the block
    # held before is selected away, never computed with)
    kept = jnp.where(first, jnp.zeros_like(out_ref), out_ref[...])
    out_ref[...] = jnp.where(mine, acc.astype(out_ref.dtype), kept)


def _row_tile(m):
    return min(_ROW_TILE, -(-m // 8) * 8)


def buffer_rows(m: int) -> int:
    """Rows of the buffer the kernel takes for `m` rows: whole row tiles."""
    return -(-m // _row_tile(m)) * _row_tile(m)


def prepare(lhs, rhs, m: int) -> _support.Packed:
    """Row-wise, before the rows are placed: `lhs`, all or the first of `m`
    sorted rows, in `rhs`'s dtype, with the spare rows that make `m` whole
    row tiles."""
    return _support.Packed(lhs.astype(rhs.dtype), buffer_rows(m) - m)


def grouped_matmul_packed(lhs, rhs, group_sizes, out_dtype=jnp.float32):
    """`grouped_matmul` on the buffer as the kernel takes it and leaves it:
    lhs `[buffer_rows(M), K]` in rhs's dtype (`prepare`, placed), the
    groups' rows first. Returns `[buffer_rows(M), N]`: row r of group g is
    `lhs[r] @ rhs[g]`; rows of a visited tile that belong to no group are
    zeros, rows of a tile no group reaches are never written and hold
    whatever. Nothing is padded, filled or cut."""
    mp, k = lhs.shape
    groups, _, n = rhs.shape
    tm = _row_tile(mp)
    tn = _col_tile(k, n, rhs.dtype.itemsize)
    if tn is None:
        raise ValueError(f"grouped_matmul: no block of rhs [{k}, {n}] fits; "
                         "ask supported first")
    if mp % tm or lhs.dtype != rhs.dtype:
        raise ValueError(f"grouped_matmul_packed: lhs {lhs.dtype}[{mp}, {k}] "
                         "is not a placed `prepare`")
    with _support.x64_off():
        (offsets, group_ids, tile_ids), pairs = make_group_metadata(
            group_sizes=group_sizes.astype(jnp.int32), m=mp, tm=tm,
            start_group=jnp.int32(0), num_nonzero_groups=groups,
            visit_empty_groups=False)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n // tn, pairs),
        in_specs=[
            pl.BlockSpec((tm, k), lambda j, w, off, gid, tid: (tid[w], 0)),
            pl.BlockSpec((None, k, tn),
                         lambda j, w, off, gid, tid: (gid[w], 0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn),
                               lambda j, w, off, gid, tid: (tid[w], j)),
    )
    return _support.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mp, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="moe_grouped_matmul",
        interpret=_support.interpret_mode(),
    )(offsets, group_ids, tile_ids, lhs, rhs)


def grouped_matmul(lhs, rhs, group_sizes, out_dtype=jnp.float32):
    """lhs `[M, K]` (rows sorted by group, the groups' rows first), rhs
    `[G, K, N]`, group_sizes `[G]` int32 with a sum of at most M. Returns
    `[M, N]`: row r of group g is `lhs[r] @ rhs[g]`; rows of a visited tile
    that belong to no group are zeros, rows of a tile no group reaches are
    never written. `prepare`, placed, through `grouped_matmul_packed`, and
    the first M rows of what it left."""
    m = lhs.shape[0]
    return grouped_matmul_packed(
        _support.place(prepare(lhs, rhs, m), m, m), rhs, group_sizes,
        out_dtype)[:m]


def supported(rhs_shape, dtype) -> bool:
    """Gate for `grouped_matmul`: kernels enabled, a float dtype, on the
    TPU whole lane tiles in K and N, and a block of rhs that fits VMEM."""
    if not _support.kernels_enabled():
        return False
    if not _support.float_dtype_ok(dtype):
        return False
    _, k, n = rhs_shape
    if _support.on_tpu() and (k % 128 or n % 128):
        return False
    return _col_tile(k, n, jnp.dtype(dtype).itemsize) is not None
