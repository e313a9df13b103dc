"""Power retention of degree 2 (Gelada et al., "Scaling Context Requires
Rethinking Attention", arXiv:2507.04239): a layer whose memory of a sequence
is one fixed-size state a KV head, not keys and values a token.

For one KV head, `q_t, k_t, v_t` in R^d, the log-gate `a_t <= 0`, `G_t` the
running sum of `a`, and `phi` the symmetric degree-2 embedding with `phi(q) .
phi(k) = (q . k)^2`:

- recurrent: `S_t = e^{a_t} S_{t-1} + phi(k_t) v_t^T`, `z_t = e^{a_t} z_{t-1}
  + phi(k_t)`, `y_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)`;
- attention form, the same numbers: `y_i = sum_{j<=i} w_ij v_j / (sum_{j<=i}
  w_ij + eps)`, `w_ij = exp(G_i - G_j) (q_i . k_j)^2`;
- chunked: inside a chunk the attention form over the chunk's own keys, plus
  `exp(G_i - G_c0) phi(q_i)^T S_c0` from the state at the chunk's start,
  numerator and normaliser alike; the state leaves the chunk as `exp(G_end -
  G_c0) S_c0 + sum_j exp(G_end - G_j) phi(k_j) v_j^T`.

The query heads of a group read one `S`, `z`. Callers fold the scale `s`
into q (`phi(s q) . phi(k) = (q . k)^2 s^2`).

THE FEATURE AXIS, AS RUN. `phi(x)` is laid out by circular offset: row `o`
is `w_o x_i x_{i-o}` over the `d` lanes `i`, for `o = 0 .. d/2` (`w_0 =
w_{d/2} = 1`, else sqrt 2). Offsets `1 .. d/2 - 1` hold every unordered pair
once, offset 0 the squares, offset `d/2` its pairs twice at weight 1, so the
inner product is `(q . k)^2` exactly, with `(d/2 + 1) d` features (8,320 for
d = 128; the 8,256 distinct products padded by 64: whole 128-lane tiles).
A tile of `phi` is one lane rotation and two multiplies of a row that is
already in VMEM: it never exists in HBM. The state is kept transposed,
`S[o, c, i] = sum_t decay . v_t[c] . phi(k_t)[o, i]` (`[offsets, d, d]`), so
an update is `v` down the sublanes times a `phi(k)` row along the lanes, and
a read is a row-wise multiply and one lane reduction at the end.

Two kernels, one `pallas_call` each, and their plain `jnp` twins (`*_ref`,
built on `recurrent_step` / `chunk_form`, the single-sequence forms
`models/brumby.py` exports):

- `power_retention_update`: one token a lane (decode). Grid `(lanes, kv
  heads)`; a live lane's `S`, `z` blocks are read once and written once, in
  place (`input_output_aliases`), the group's query heads on the one read; a
  dead lane's step maps to the block of the live step before it (no DMA) and
  does nothing.
- `power_retention_chunk`: the lanes that hold more than one token (a
  prefill chunk). Grid `(kv heads, lanes)`; a head's rows and its y block
  stay in VMEM over its lanes; a live lane's state blocks are pipelined in as
  the update kernel's, carried in the output's own block while the lane's
  rows are walked in aligned blocks of the packed buffer (intra-block
  attention form, the carried state's part, then the state's own update),
  and written back once. The state rides the blocks' own pipeline and not
  hand-made DMAs of `S[layer, slot, head]`: those compile and interpret, and
  hang on the v5e (PERF.md section 6, PR 45).

Both leave the state of a lane that is not theirs untouched.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _support

__all__ = ["n_offsets", "feature_dim", "phi", "recurrent_step", "chunk_form",
           "power_retention_update", "power_retention_update_ref",
           "power_retention_chunk", "power_retention_chunk_ref",
           "retention_supported"]

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
ROW_BLOCK = 128          # packed rows the chunk kernel takes at a time
_SQRT2 = float(np.sqrt(2.0))
_VMEM_LIMIT = 48 << 20   # the update kernel double-buffers two 4.3 MB blocks


def n_offsets(d: int) -> int:
    return d // 2 + 1


def feature_dim(d: int) -> int:
    """The feature axis as run: `(d / 2 + 1) d`."""
    return n_offsets(d) * d


def _weights(d: int) -> np.ndarray:
    w = np.full((n_offsets(d),), _SQRT2, np.float32)
    w[0] = w[-1] = 1.0
    return w


def phi(x):
    """`[..., d] -> [..., d / 2 + 1, d]`, float32: the module docstring's
    layout, `phi(q) . phi(k) == (q . k)^2` (d even)."""
    x = x.astype(F32)
    d = x.shape[-1]
    rolled = jnp.stack([jnp.roll(x, o, axis=-1) for o in range(n_offsets(d))],
                       axis=-2)
    return x[..., None, :] * rolled * _weights(d)[:, None]


# --- one sequence, one KV head: the forms -------------------------------------

def recurrent_step(q, k, v, a, S, z, eps):
    """One token of the recurrent form. q `[G, d]` (the group's query heads,
    scaled), k, v `[d]`, a `[]`; S `[O, d, d]`, z `[O, d]` float32. Returns
    `(y [G, d], S, z)`."""
    g = jnp.exp(a.astype(F32))
    kf = phi(k)                                               # [O, d]
    S = g * S + v.astype(F32)[None, :, None] * kf[:, None, :]
    z = g * z + kf
    qf = phi(q)                                               # [G, O, d]
    num = jnp.einsum("goi,oci->gc", qf, S, precision=HI)
    den = jnp.einsum("goi,oi->g", qf, z, precision=HI)
    return num / (den[:, None] + eps), S, z


def chunk_form(q, k, v, a, S, z, eps, mask=None):
    """One chunk of one sequence, the chunked form. q `[n, G, d]` (scaled),
    k, v `[n, d]`, a `[n]`; S, z the state at the chunk's start. `mask [n]`
    (bool): the rows that ARE the chunk; the others (another lane's rows of
    a packed buffer) neither see nor are seen, and read 0. Returns `(y [n, G,
    d], S, z)` with the state at the chunk's end."""
    n = q.shape[0]
    m = jnp.ones((n,), bool) if mask is None else mask
    q, k, v = q.astype(F32), k.astype(F32), v.astype(F32)
    G = jnp.cumsum(jnp.where(m, a.astype(F32), 0.0))
    see = jnp.tril(jnp.ones((n, n), bool)) & m[:, None] & m[None, :]
    decay = jnp.exp(jnp.where(see, G[:, None] - G[None, :], -jnp.inf))
    s = jnp.einsum("igd,jd->gij", q, k, precision=HI)
    w = s * s * decay[None]
    num = jnp.einsum("gij,jc->igc", w, v, precision=HI)
    den = jnp.sum(w, axis=-1).T                               # [n, G]
    into = jnp.where(m, jnp.exp(G), 0.0)                      # from the start
    qf = phi(q)                                               # [n, G, O, d]
    num = num + into[:, None, None] * jnp.einsum("igoj,ocj->igc", qf, S,
                                                 precision=HI)
    den = den + into[:, None] * jnp.einsum("igoj,oj->ig", qf, z, precision=HI)
    out = jnp.where(m, jnp.exp(G[-1] - G), 0.0)               # to the end
    kf = phi(k)                                               # [n, O, d]
    S = jnp.exp(G[-1]) * S + jnp.einsum("j,jc,joi->oci", out, v, kf,
                                        precision=HI)
    z = jnp.exp(G[-1]) * z + jnp.einsum("j,joi->oi", out, kf, precision=HI)
    return num / (den[..., None] + eps), S, z


# --- a step's lanes: the twins in plain jnp ------------------------------------

def _lane_state(S, z, layer, slot, fresh):
    """The lanes' states `[B, KV, ...]`, zero where a lane starts afresh."""
    def take(x):
        rows = x[layer, slot]
        return jnp.where(fresh.reshape((-1,) + (1,) * (rows.ndim - 1)), 0.0,
                         rows)
    return take(S), take(z)


def _put_state(S, z, layer, slot, live, S1, z1):
    at = jnp.where(live, slot, S.shape[1])        # a dead lane's is dropped
    return (S.at[layer, at].set(S1, mode="drop"),
            z.at[layer, at].set(z1, mode="drop"))


def power_retention_update_ref(q, k, v, a, S, z, *, layer, slot, live, fresh,
                               eps):
    """`power_retention_update` in plain jnp."""
    S0, z0 = _lane_state(S, z, layer, slot, fresh)
    step = jax.vmap(jax.vmap(functools.partial(recurrent_step, eps=eps)))
    y, S1, z1 = step(q, k, v, a, S0, z0)
    S, z = _put_state(S, z, layer, slot, live, S1, z1)
    return jnp.where(live[:, None, None, None], y, 0.0), S, z


def power_retention_chunk_ref(q, k, v, a, S, z, *, layer, slot, live, fresh,
                              tok_lane, eps, row_block=None):
    """`power_retention_chunk` in plain jnp: every lane over the whole packed
    buffer under its own mask."""
    S0, z0 = _lane_state(S, z, layer, slot, fresh)
    lanes = jnp.arange(slot.shape[0], dtype=tok_lane.dtype)
    masks = (tok_lane[None, :] == lanes[:, None]) & live[:, None]   # [B, T]
    heads = jax.vmap(lambda q, k, v, a, S, z, m: chunk_form(
        q, k, v, a, S, z, eps, m), in_axes=(1, 1, 1, 1, 0, 0, None),
        out_axes=(1, 0, 0))
    y, S1, z1 = jax.vmap(heads, in_axes=(None, None, None, None, 0, 0, 0))(
        q, k, v, a, S0, z0, masks)
    S, z = _put_state(S, z, layer, slot, live, S1, z1)
    return jnp.sum(y, axis=0), S, z


# --- the update kernel ------------------------------------------------------------

def _update_kernel(slot_ref, head_ref, live_ref, fresh_ref, q_ref, kvg_ref,
                   s_ref, z_ref, num_ref, den_ref, so_ref, zo_ref, acc_ref, *,
                   groups, d):
    b, h = pl.program_id(0), pl.program_id(1)
    live = live_ref[b] == 1

    @pl.when(jnp.logical_not(live) & (b == 0) & (h == 0))
    def _no_one_yet():
        # the output blocks of the steps before the first live one (all of
        # them, if no lane is live) go back as they came
        so_ref[...] = s_ref[...]
        zo_ref[...] = z_ref[...]

    @pl.when(jnp.logical_not(live))
    def _dead():
        num_ref[...] = jnp.zeros(num_ref.shape, F32)
        den_ref[...] = jnp.zeros(den_ref.shape, F32)

    @pl.when(live)
    def _live():
        q = q_ref[0, 0]                                      # [G8, d]
        k = kvg_ref[0, 0, 0:1]                               # [1, d]
        v = kvg_ref[0, 0, 1:2]
        gate = kvg_ref[0, 0, 2:3]                            # e^a, every lane
        fresh = fresh_ref[b] == 1
        vb = jnp.broadcast_to(v, (d, d)).T                   # [c, i] = v[c]
        acc_ref[...] = jnp.zeros(acc_ref.shape, F32)

        def tile(o, den):
            w = jnp.where((o == 0) | (o == d // 2), F32(1.0), F32(_SQRT2))
            kf = k * pltpu.roll(k, o, 1) * w
            qf = q * pltpu.roll(q, o, 1) * w
            s = jnp.where(fresh, F32(0.0), s_ref[0, 0, 0, o]) * gate + vb * kf
            so_ref[0, 0, 0, o] = s
            zr = jnp.where(fresh, F32(0.0),
                           z_ref[0, 0, 0, pl.ds(o, 1), :]) * gate + kf
            zo_ref[0, 0, 0, pl.ds(o, 1), :] = zr
            for g in range(groups):
                acc_ref[g] += s * qf[g:g + 1]
            return den + qf * zr

        den_ref[0, 0] = jax.lax.fori_loop(
            0, n_offsets(d), tile, jnp.zeros(q.shape, F32))
        for g in range(groups):
            num_ref[0, 0, :, g:g + 1] = jnp.sum(acc_ref[g], axis=1,
                                                keepdims=True)


def _revisit(live, slot, last_head):
    """Per lane, the `(slot, head)` block a DEAD lane's steps stay on, so
    that they move nothing: the last block of the live lane before it
    (`last_head`: the update kernel's heads are its inner grid axis), else
    the first block of the first live lane, else (no lane live) lane 0's own
    first block. `head` is -1 for a live lane (its steps take their own)."""
    lanes = jnp.arange(live.shape[0], dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(live, lanes, -1))
    first = jnp.argmax(live).astype(jnp.int32)       # 0 when none is live
    at = jnp.where(last >= 0, last, first)
    head = jnp.where(live, -1, jnp.where(last >= 0, last_head, 0))
    return slot[at].astype(jnp.int32), head.astype(jnp.int32)


def power_retention_update(q, k, v, a, S, z, *, layer, slot, live, fresh,
                           eps):
    """The one-token state update and read of a step's decode lanes.

    q `[B, KV, G, d]` (scaled), k, v `[B, KV, d]`, a `[B, KV]` log-gates: each
    lane's one token. S `[L, slots, KV, O, d, d]`, z `[L, slots, KV, O, d]`
    float32, updated in place at `[layer, slot[b]]` for every `live[b]` lane
    (from zero where `fresh[b]`), untouched elsewhere. Returns `(y [B, KV, G,
    d] float32, S, z)`; a dead lane's y is 0."""
    B, KV, G, d = q.shape
    O = n_offsets(d)
    g8 = -(-G // 8) * 8
    qp = jnp.pad(q.astype(F32), ((0, 0), (0, 0), (0, g8 - G), (0, 0)))
    kvg = jnp.stack([k.astype(F32), v.astype(F32),
                     jnp.broadcast_to(jnp.exp(a.astype(F32))[..., None],
                                      k.shape)], axis=2)
    kvg = jnp.pad(kvg, ((0, 0), (0, 0), (0, 5), (0, 0)))        # [B, KV, 8, d]
    at_slot, at_head = _revisit(live, slot, KV - 1)

    def state_block(*trail):
        def index(b, h, slots, heads, _live, _fresh):
            fixed = heads[b]
            return (layer, slots[b], jnp.where(fixed < 0, h, fixed)) + trail
        return index

    lane_block = lambda b, h, *_: (b, h, 0, 0)                  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, KV),
        in_specs=[
            pl.BlockSpec((1, 1, g8, d), lane_block),
            pl.BlockSpec((1, 1, 8, d), lane_block),
            pl.BlockSpec((1, 1, 1, O, d, d), state_block(0, 0, 0)),
            pl.BlockSpec((1, 1, 1, O, d), state_block(0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, d, g8), lane_block),
            pl.BlockSpec((1, 1, g8, d), lane_block),
            pl.BlockSpec((1, 1, 1, O, d, d), state_block(0, 0, 0)),
            pl.BlockSpec((1, 1, 1, O, d), state_block(0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((G, d, d), F32)],
    )
    num, den, S, z = _support.pallas_call(
        functools.partial(_update_kernel, groups=G, d=d),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, KV, d, g8), F32),
                   jax.ShapeDtypeStruct((B, KV, g8, d), F32),
                   jax.ShapeDtypeStruct(S.shape, F32),
                   jax.ShapeDtypeStruct(z.shape, F32)],
        input_output_aliases={6: 2, 7: 3},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="power_retention_update",
        interpret=_support.interpret_mode(),
    )(at_slot, at_head, live.astype(jnp.int32), fresh.astype(jnp.int32),
      qp, kvg, S, z)
    num = jnp.swapaxes(num, 2, 3)[:, :, :G]                     # [B, KV, G, d]
    den = jnp.sum(den[:, :, :G], axis=-1, keepdims=True)
    return num / (den + eps), S, z


# --- the chunk kernel ---------------------------------------------------------------

def _chunk_kernel(slot_ref, live_ref, fresh_ref, lo_ref, hi_ref, q_ref, k_ref,
                  v_ref, gcol_ref, grow_ref, lcol_ref, lrow_ref, s_ref, z_ref,
                  y_ref, so_ref, zo_ref, *, groups, d, rb, eps):
    b = pl.program_id(1)
    live = live_ref[b] == 1
    O = n_offsets(d)

    @pl.when(b == 0)
    def _first_lane():
        # a head's y block stays in VMEM over its lanes; so do the state's
        # blocks of the dead lanes before the first live one (all of them,
        # if none is live), which go back as they came
        y_ref[...] = jnp.zeros(y_ref.shape, F32)

        @pl.when(jnp.logical_not(live))
        def _no_one_yet():
            so_ref[...] = s_ref[...]
            zo_ref[...] = z_ref[...]

    @pl.when(live)
    def _live():
        fresh = fresh_ref[b] == 1
        # the state is carried in the output's own block over the lane's
        # row blocks and written back once, when the grid moves on
        so_ref[...] = jnp.where(fresh, F32(0.0), s_ref[...])
        zo_ref[...] = jnp.where(fresh, F32(0.0), z_ref[...])
        tri = jax.lax.broadcasted_iota(jnp.int32, (rb, rb), 0) \
            >= jax.lax.broadcasted_iota(jnp.int32, (rb, rb), 1)

        def block(r, g_prev):
            rows = pl.ds(pl.multiple_of(r * rb, rb), rb)
            mine_c = lcol_ref[rows, :] == b                      # [rb, 1]
            mine_r = lrow_ref[:, rows] == b                      # [1, rb]
            gc = gcol_ref[0, rows, :]                            # [rb, 1]
            gr = grow_ref[0, :, rows]                            # [1, rb]
            # G falls along a lane's rows: its last row here has the least
            g_end = jnp.min(jnp.where(mine_c, gc, F32(0.0)), axis=0,
                            keepdims=True)                       # [1, 1]
            into = jnp.where(mine_c, jnp.exp(gc - g_prev), F32(0.0))
            out = jnp.where(mine_c, jnp.exp(g_end - gc), F32(0.0))
            carry = jnp.exp(g_end - g_prev)                      # [1, 1]
            decay = jnp.where(tri & mine_c & mine_r, jnp.exp(gc - gr),
                              F32(0.0))                          # [rb, rb]
            kk = k_ref[rows, :].astype(F32)
            vv = v_ref[rows, :].astype(F32)
            qq = jnp.concatenate(
                [q_ref[rows, g * d:(g + 1) * d].astype(F32)
                 for g in range(groups)], axis=0)                # [G rb, d]
            s = jax.lax.dot_general(qq, kk, (((1,), (1,)), ((), ())),
                                    precision=HI, preferred_element_type=F32)
            w = s * s * jnp.concatenate([decay] * groups, axis=0)
            num = jnp.dot(w, vv, precision=HI, preferred_element_type=F32)
            den = jnp.sum(w, axis=1, keepdims=True)              # [G rb, 1]
            into_q = jnp.concatenate([into] * groups, axis=0)
            vt = (vv * out).T                                    # [d, rb]

            def tile(o, carried):
                num, dacc = carried
                wo = jnp.where((o == 0) | (o == d // 2), F32(1.0),
                               F32(_SQRT2))
                kf = kk * pltpu.roll(kk, o, 1) * wo              # [rb, d]
                qf = qq * pltpu.roll(qq, o, 1) * wo              # [G rb, d]
                st = so_ref[0, 0, 0, o]                          # [d, d]
                zr = zo_ref[0, 0, 0, pl.ds(o, 1), :]             # [1, d]
                num = num + into_q * jax.lax.dot_general(
                    qf, st, (((1,), (1,)), ((), ())), precision=HI,
                    preferred_element_type=F32)
                so_ref[0, 0, 0, o] = carry * st + jnp.dot(
                    vt, kf, precision=HI, preferred_element_type=F32)
                zo_ref[0, 0, 0, pl.ds(o, 1), :] = carry * zr + jnp.sum(
                    kf * out, axis=0, keepdims=True)
                return num, dacc + qf * zr

            num, dacc = jax.lax.fori_loop(
                0, O, tile, (num, jnp.zeros(qq.shape, F32)))
            den = den + into_q * jnp.sum(dacc, axis=1, keepdims=True)
            y = num / (den + F32(eps))
            for g in range(groups):
                cols = slice(g * d, (g + 1) * d)
                y_ref[rows, cols] = jnp.where(
                    mine_c, y[g * rb:(g + 1) * rb], y_ref[rows, cols])
            return g_end

        jax.lax.fori_loop(lo_ref[b] // rb, (hi_ref[b] - 1) // rb + 1, block,
                          jnp.zeros((1, 1), F32))


def power_retention_chunk(q, k, v, a, S, z, *, layer, slot, live, fresh,
                          tok_lane, eps, row_block=ROW_BLOCK):
    """The chunked form over a step's packed buffer, for the lanes that hold
    more than one token.

    q `[T, KV, G, d]` (scaled), k, v `[T, KV, d]`, a `[T, KV]`: the packed
    rows, lane-major; `tok_lane [T]` the lane of each row (-1: none). For
    every `live[b]` lane its rows go through the chunked form from the state
    at `[layer, slot[b]]` (from zero where `fresh[b]`), which is updated in
    place; rows of other lanes read 0 and touch nothing. Returns `(y [T, KV,
    G, d] float32, S, z)`."""
    T, KV, G, d = q.shape
    B = slot.shape[0]
    O = n_offsets(d)
    rb = row_block
    tp = -(-T // rb) * rb
    rows = lambda x, fill=0: jnp.pad(                           # noqa: E731
        x, ((0, tp - T),) + ((0, 0),) * (x.ndim - 1), constant_values=fill)
    lane_of = rows(tok_lane.astype(jnp.int32), -1)
    # G, summed along each lane's own rows: a lane's rows are contiguous, so
    # it is the packed cumulative sum less its value before the lane's first
    total = jnp.cumsum(jnp.where(lane_of[:, None] >= 0,
                                 rows(a.astype(F32)), 0.0), axis=0)
    q_lens = jnp.sum(lane_of[None, :] == jnp.arange(B)[:, None], axis=1,
                     dtype=jnp.int32)
    lo = jnp.cumsum(q_lens) - q_lens
    hi = lo + q_lens
    before = jnp.where((lo > 0)[:, None], total[jnp.maximum(lo - 1, 0)], 0.0)
    gt = (total - before[jnp.maximum(lane_of, 0)]).T            # [KV, tp]
    at_slot, _ = _revisit(live, slot, 0)

    head = lambda h, b, *_: (0, h)                              # noqa: E731
    whole = lambda h, b, *_: (0, 0)                             # noqa: E731

    def state_block(*trail):
        return lambda h, b, slots, *_: (layer, slots[b], h) + trail

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(KV, B),
        in_specs=[
            pl.BlockSpec((tp, G * d), head),
            pl.BlockSpec((tp, d), head),
            pl.BlockSpec((tp, d), head),
            pl.BlockSpec((1, tp, 1), lambda h, b, *_: (h, 0, 0)),
            pl.BlockSpec((1, 1, tp), lambda h, b, *_: (h, 0, 0)),
            pl.BlockSpec((tp, 1), whole),
            pl.BlockSpec((1, tp), whole),
            pl.BlockSpec((1, 1, 1, O, d, d), state_block(0, 0, 0)),
            pl.BlockSpec((1, 1, 1, O, d), state_block(0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tp, G * d), head),
            pl.BlockSpec((1, 1, 1, O, d, d), state_block(0, 0, 0)),
            pl.BlockSpec((1, 1, 1, O, d), state_block(0, 0)),
        ],
    )
    y, S, z = _support.pallas_call(
        functools.partial(_chunk_kernel, groups=G, d=d, rb=rb, eps=eps),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((tp, KV * G * d), F32),
                   jax.ShapeDtypeStruct(S.shape, F32),
                   jax.ShapeDtypeStruct(z.shape, F32)],
        input_output_aliases={12: 1, 13: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="power_retention_chunk",
        interpret=_support.interpret_mode(),
    )(at_slot, live.astype(jnp.int32), fresh.astype(jnp.int32), lo, hi,
      rows(q).reshape(tp, KV * G * d), rows(k).reshape(tp, KV * d),
      rows(v).reshape(tp, KV * d), gt[:, :, None], gt[:, None, :],
      lane_of[:, None], lane_of[None, :], S, z)
    return y[:T].reshape(T, KV, G, d), S, z


def retention_supported(d: int, dtype) -> bool:
    """Gate for both kernels: on the TPU the head size is whole 128-lane
    tiles (a tile of the state is `[d, d]`); through the interpreter any even
    size."""
    if not _support.kernels_enabled() or not _support.float_dtype_ok(dtype):
        return False
    return d % 128 == 0 if _support.on_tpu() else d % 2 == 0
