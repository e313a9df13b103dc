"""Ragged paged attention (ISSUE 10): kernel parity vs the XLA reference
and vs the legacy decode/verify kernels on mixed batches, packed-metadata
edge cases (chunk/block boundaries, kv_len==0 guard lanes, MHA G=1 group
padding), the ragged KV scatter, and engine-level ragged_step semantics.

Kernels run through the Pallas interpreter on CPU (FLAGS_pallas_interpret)
— same kernel code compiles via Mosaic on TPU.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.framework import flags
from paddle_tpu.ops.pallas import paged_attention as pa


@pytest.fixture(autouse=True)
def _enable_interpret():
    flags.set_flags({"pallas_interpret": True})
    yield
    flags.set_flags({"pallas_interpret": False})


def _pool(rng, nb=16, kvh=2, bs=4, d=32):
    kc = jnp.asarray(rng.normal(size=(nb, kvh, bs, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(nb, kvh, bs, d)), jnp.float32)
    return kc, vc


def _meta(q_lens, kv_lens, t):
    lane, pos = pa.ragged_metadata(jnp.asarray(q_lens, jnp.int32),
                                   jnp.asarray(kv_lens, jnp.int32), t)
    return np.asarray(lane), np.asarray(pos)


class TestRaggedMetadata:
    def test_packing_positions_and_guard_slots(self):
        lane, pos = _meta([1, 5, 0], [9, 7, 0], 8)
        assert lane.tolist() == [0, 1, 1, 1, 1, 1, 2, 2]
        assert pos.tolist() == [8, 2, 3, 4, 5, 6, -1, -1]

    def test_empty_lane_in_the_middle_is_skipped(self):
        lane, pos = _meta([2, 0, 3], [4, 0, 3], 6)
        assert lane.tolist() == [0, 0, 2, 2, 2, 2]
        assert pos.tolist() == [2, 3, 0, 1, 2, -1]

    def test_all_empty(self):
        lane, pos = _meta([0, 0], [0, 0], 4)
        assert (pos == -1).all()


class TestRaggedKernelParity:
    def _mixed(self, rng, kvh, h, d=32, bs=4):
        """Decode lane + prefill chunk + verify window + guard lanes in
        ONE grid — the serving batch composition."""
        kc, vc = _pool(rng, nb=20, kvh=kvh, bs=bs, d=d)
        tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8],
                              [9, 10, 11, 12], [13, 14, 15, 16]], jnp.int32)
        # lane0: decode (q 1, kv 11); lane1: chunk (q 6, kv 9);
        # lane2: verify window (q 3, kv 13); lane3: empty guard
        q_lens = [1, 6, 3, 0]
        kv_lens = [11, 9, 13, 0]
        t = 16                                    # 10 real + 6 guard slots
        lane, pos = _meta(q_lens, kv_lens, t)
        q = jnp.asarray(rng.normal(size=(t, h, d)), jnp.float32)
        return q, kc, vc, tables, jnp.asarray(kv_lens, jnp.int32), \
            jnp.asarray(lane), jnp.asarray(pos)

    @pytest.mark.parametrize("kvh,h", [(2, 4), (2, 2), (1, 4)])
    def test_kernel_matches_ref_mixed_batch(self, kvh, h):
        rng = np.random.default_rng(1)
        q, kc, vc, tables, kv_lens, lane, pos = self._mixed(rng, kvh, h)
        ref = pa.paged_attention_ragged_ref(q, kc, vc, tables, kv_lens,
                                            lane, pos)
        out = pa.paged_attention_ragged(q, kc, vc, tables, kv_lens,
                                        lane, pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-4)

    def test_mha_group1_padding(self):
        """MHA (H == KV_H, G = 1) exercises the 8-row sublane padding."""
        rng = np.random.default_rng(2)
        q, kc, vc, tables, kv_lens, lane, pos = self._mixed(rng, 4, 4)
        ref = pa.paged_attention_ragged_ref(q, kc, vc, tables, kv_lens,
                                            lane, pos)
        out = pa.paged_attention_ragged(q, kc, vc, tables, kv_lens,
                                        lane, pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-4)

    def test_guard_lanes_emit_exact_zeros(self):
        rng = np.random.default_rng(3)
        q, kc, vc, tables, kv_lens, lane, pos = self._mixed(rng, 2, 4)
        out = pa.paged_attention_ragged(q, kc, vc, tables, kv_lens,
                                        lane, pos)
        ref = pa.paged_attention_ragged_ref(q, kc, vc, tables, kv_lens,
                                            lane, pos)
        guard = np.asarray(pos) < 0
        assert guard.sum() == 6
        assert float(np.abs(np.asarray(out)[guard]).max()) == 0.0
        assert float(np.abs(np.asarray(ref)[guard]).max()) == 0.0

    @pytest.mark.parametrize("kvh,h", [(2, 4), (4, 4)])
    def test_the_buffer_taking_call_answers_the_live_rows(self, kvh, h,
                                                          monkeypatch):
        """ISSUE 49: `ragged_prepare`, placed in a blank (here full of NaN),
        through `paged_attention_ragged_packed`: the kernel's own buffer,
        whose live rows `ragged_finish` turns into the public function's;
        a guard row and the spare chunk hold whatever (the interpreter's
        unwritten rows read NaN). The public function, made of the same
        three, still gives exact zeros there."""
        from paddle_tpu.ops.pallas import _support
        from test_live_prefix import poison

        rng = np.random.default_rng(3)
        q, kc, vc, tables, kv_lens, lane, pos = self._mixed(rng, kvh, h)
        want = pa.paged_attention_ragged(q, kc, vc, tables, kv_lens, lane,
                                         pos)
        monkeypatch.setattr(_support, "blank", poison)
        t, g_pad = q.shape[0], -(-(h // kvh) // 8) * 8
        buf = _support.place(pa.ragged_prepare(q, kvh), t, t)
        assert buf.shape == (t + 8, kvh, g_pad, 32) \
            and buf.dtype == jnp.float32
        assert np.isnan(np.asarray(buf[t:])).all()
        out = pa.paged_attention_ragged_packed(buf, kc, vc, tables, kv_lens,
                                               lane, pos)
        assert out.shape == buf.shape
        live = np.asarray(pos) >= 0
        np.testing.assert_array_equal(
            np.asarray(pa.ragged_finish(out[:t], h, q.dtype))[live],
            np.asarray(want)[live])
        again = pa.paged_attention_ragged(q, kc, vc, tables, kv_lens, lane,
                                          pos)
        np.testing.assert_array_equal(np.asarray(again), np.asarray(want))
        with pytest.raises(ValueError, match="placed"):
            pa.paged_attention_ragged_packed(buf[:t], kc, vc, tables,
                                             kv_lens, lane, pos)

    def test_decode_composition_matches_decode_reference(self):
        """A pure decode batch through the ragged kernel is the
        single-query XLA reference `paged_attention_ref` to float
        rounding, and `paged_attention` is that composition."""
        rng = np.random.default_rng(4)
        kc, vc = _pool(rng)
        tables = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
        kv_lens = jnp.asarray([9, 5], jnp.int32)
        q = jnp.asarray(rng.normal(size=(2, 4, 32)), jnp.float32)
        lane, pos = pa.ragged_metadata(jnp.asarray([1, 1]), kv_lens, 2)
        out = pa.paged_attention_ragged(q, kc, vc, tables, kv_lens,
                                        lane, pos)
        ref = pa.paged_attention_ref(q, kc, vc, tables, kv_lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-6, rtol=1e-5)
        np.testing.assert_array_equal(
            np.asarray(pa.paged_attention(q, kc, vc, tables, kv_lens)),
            np.asarray(out))

    def test_verify_composition_matches_per_row_decode_reference(self):
        """A fixed q_len == S batch through the ragged kernel is, row by
        row, `paged_attention_ref` over the context truncated at that
        row, to float rounding — verify_step really is a special case of
        the one kernel."""
        rng = np.random.default_rng(5)
        kc, vc = _pool(rng)
        tables = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
        kv_lens = jnp.asarray([10, 7], jnp.int32)
        s = 3
        qb = jnp.asarray(rng.normal(size=(2, s, 4, 32)), jnp.float32)
        lane, pos = pa.ragged_metadata(jnp.asarray([s, s]), kv_lens, 2 * s)
        out = pa.paged_attention_ragged(qb.reshape(2 * s, 4, 32), kc, vc,
                                        tables, kv_lens, lane, pos)
        out = np.asarray(out).reshape(2, s, 4, 32)
        for i in range(s):
            row = pa.paged_attention_ref(qb[:, i], kc, vc, tables,
                                         kv_lens - (s - 1 - i))
            np.testing.assert_allclose(out[:, i], np.asarray(row),
                                       atol=1e-6, rtol=1e-5)

    def test_chunk_at_block_boundaries(self):
        """q_len landing exactly on / one past a block boundary, and a
        chunk whose kv span starts mid-block — the index-map edges."""
        rng = np.random.default_rng(6)
        kc, vc = _pool(rng, bs=4)
        tables = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
        for q_len, kv_len in ((4, 4), (4, 8), (5, 8), (3, 11), (1, 12),
                              (8, 16), (7, 15)):
            t = 10                             # 2-9 guard slots
            lane, pos = pa.ragged_metadata(
                jnp.asarray([q_len]), jnp.asarray([kv_len]), t)
            q = jnp.asarray(rng.normal(size=(t, 4, 32)), jnp.float32)
            out = _ragged(q, kc, vc, tables, jnp.asarray([kv_len]),
                          lane, pos)
            ref = _ragged_ref(q, kc, vc, tables, jnp.asarray([kv_len]),
                              lane, pos)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-4,
                err_msg=f"q_len={q_len} kv_len={kv_len}")


# jitted once: cases that share a shape share one interpreted executable
# (an eager call would trace and compile the kernel anew every time)
_ragged = jax.jit(pa.paged_attention_ragged)
_ragged_ref = jax.jit(pa.paged_attention_ragged_ref)


def _live_case(seed, q_lens, kv_lens, t, *, kvh=2, h=8, bs=16, d=32,
               w=128, nb=48, dtype=jnp.float32, quant=False, dead=0.0):
    """A pool whose every block NOT among a lane's live pages — and so
    every table entry past a lane's kv_len, which point at two such blocks
    a lane — is filled with `dead`; live pages are normal draws. Returns
    the kernel's positional args and its scale kwargs."""
    from paddle_tpu.inference import kv_quant

    rng = np.random.default_rng(seed)
    b = len(q_lens)
    live = [-(-kv // bs) for kv in kv_lens]
    assert 1 + sum(live) + 2 * b <= nb
    ids = rng.permutation(np.arange(1, nb))
    tables = np.zeros((b, w), np.int32)
    is_live = np.zeros(nb, bool)
    at = 0
    for i, n in enumerate(live):
        tables[i, :n] = ids[at:at + n]
        is_live[ids[at:at + n]] = True
        tables[i, n:] = np.resize(ids[at + n:at + n + 2], w - n)
        at += n + 2
    k = rng.normal(size=(nb, kvh, bs, d)).astype(np.float32)
    v = rng.normal(size=(nb, kvh, bs, d)).astype(np.float32)
    kw = {}
    if quant:
        kc, ks = kv_quant.quantize_kv(jnp.asarray(k))
        vc, vs = kv_quant.quantize_kv(jnp.asarray(v))
        ks, vs = np.array(ks), np.array(vs)
        ks[~is_live] = dead
        vs[~is_live] = dead
        kw = dict(k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs))
    else:
        k[~is_live] = dead
        v[~is_live] = dead
        kc, vc = jnp.asarray(k, dtype), jnp.asarray(v, dtype)
    lane, pos = pa.ragged_metadata(jnp.asarray(q_lens, jnp.int32),
                                   jnp.asarray(kv_lens, jnp.int32), t)
    q = jnp.asarray(rng.normal(size=(t, h, d)), dtype)
    return (q, kc, vc, jnp.asarray(tables),
            jnp.asarray(kv_lens, jnp.int32), lane, pos), kw


# What the lane-by-lane grid makes new, as (q_lens, kv_lens) of ONE shape:
# 5 lanes, 80 packed slots (query tiles of 64, compute chunks of 8), a
# table 128 wide against 1-14 live pages of 16, GQA 4:1.
_LIVE_T = 80
_LIVE_CASES = {
    "wide_table_live_1_to_14": ([1, 1, 5, 1, 0], [3, 224, 100, 16, 0]),
    # kv_len on and one past a page boundary, decode lanes and chunks
    "page_boundary_decode": ([1, 1, 1, 1, 1], [16, 17, 32, 33, 48]),
    "page_boundary_chunk": ([16, 17, 0, 0, 0], [32, 33, 0, 0, 0]),
    # a 64-token chunk behind prior context (the prefix-hit first step)
    "chunk64_with_prior_context": ([64, 1, 0, 0, 1], [100, 7, 0, 0, 40]),
    # a lane longer than the 64-token query tile; lanes that straddle the
    # 8-token chunks with empty lanes between; an all-guard tail
    "straddles_query_tile": ([70, 0, 3, 0, 0], [75, 0, 3, 0, 0]),
    "straddles_chunks_empty_middle": ([13, 0, 3, 0, 9], [29, 0, 3, 0, 40]),
    "all_guard_tail": ([2, 1, 0, 0, 0], [18, 5, 0, 0, 0]),
    "all_lanes_empty": ([0, 0, 0, 0, 0], [0, 0, 0, 0, 0]),
    # decode lanes (`q_len == 1`: the one-token tile) between a prefill
    # chunk and verify windows; with empty lanes first, in the middle and
    # last; and as the LAST lane of a full buffer, its token the last row
    "decode_between_chunk_and_windows": ([1, 40, 1, 4, 1],
                                         [129, 40, 15, 60, 224]),
    "decode_with_empty_first_middle_last": ([0, 1, 0, 3, 0],
                                            [0, 33, 0, 19, 0]),
    "decode_lane_last_fills_the_buffer": ([64, 8, 4, 3, 1],
                                          [64, 24, 100, 3, 130]),
}
# other head groupings and page geometries: (q_lens, kv_lens, T, shape)
_SHAPE_CASES = {
    "mha_g1_pads_to_8": ([9, 1, 1], [25, 16, 40], 16, dict(kvh=4, h=4)),
    "one_kv_head": ([9, 1, 1], [25, 16, 40], 16, dict(kvh=1, h=4)),
    "group_of_8": ([9, 1], [25, 16], 16, dict(kvh=1, h=8)),
    # fewer pages in the table than one 128-column group holds
    "narrow_table": ([4, 1], [20, 33], 8, dict(w=3)),
    "block_size_4": ([6, 1, 3], [9, 11, 13], 16, dict(bs=4, w=8)),
}
_KINDS = {"f32": (jnp.float32, False), "bf16": (jnp.bfloat16, False),
          "int8kv": (jnp.bfloat16, True)}


def _assert_matches_ref(args, kw, dtype):
    out, ref = _ragged(*args, **kw), _ragged_ref(*args, **kw)
    assert out.dtype == ref.dtype == dtype
    tol = dict(atol=2e-5, rtol=2e-4) if dtype == jnp.float32 else \
        dict(atol=2e-2, rtol=2e-2)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol)
    guard = np.asarray(args[-1]) < 0
    assert float(np.abs(np.asarray(out, np.float32))[guard].sum()) == 0.0


class TestRaggedLiveWork:
    """ISSUE 26: the kernel walks each lane's live pages and live tokens
    only, whatever the table's width and the packed buffer's guard slots."""

    @pytest.mark.parametrize("name", sorted(_LIVE_CASES))
    @pytest.mark.parametrize("kind", sorted(_KINDS))
    def test_matches_ref(self, name, kind):
        dtype, quant = _KINDS[kind]
        args, kw = _live_case(11, *_LIVE_CASES[name], _LIVE_T, dtype=dtype,
                              quant=quant)
        _assert_matches_ref(args, kw, dtype)

    @pytest.mark.parametrize("name,kind",
                             [(n, "f32") for n in sorted(_SHAPE_CASES)]
                             + [("mha_g1_pads_to_8", "bf16"),
                                ("mha_g1_pads_to_8", "int8kv")])
    def test_matches_ref_other_shapes(self, name, kind):
        dtype, quant = _KINDS[kind]
        q_lens, kv_lens, t, shape = _SHAPE_CASES[name]
        args, kw = _live_case(14, q_lens, kv_lens, t, dtype=dtype,
                              quant=quant, **shape)
        _assert_matches_ref(args, kw, dtype)

    @pytest.mark.parametrize("kind", sorted(_KINDS))
    @pytest.mark.parametrize("dead", [np.nan, np.inf])
    @pytest.mark.parametrize("q_lens", [[1, 12, 0, 1, 3], [1, 1, 0, 1, 1]],
                             ids=["mixed", "decode"])
    def test_dead_pages_are_never_read(self, kind, dead, q_lens):
        """Every page past each lane's kv_len, and every block no lane
        owns, poisoned: the output is finite and is what a clean pool
        gives, bit for bit — nothing of a dead page enters the sums, in
        the chunked body or in a decode lane's one-token tile."""
        dtype, quant = _KINDS[kind]
        outs = []
        for fill in (0.0, dead):
            args, kw = _live_case(12, q_lens, [35, 44, 0, 16, 224],
                                  _LIVE_T, dtype=dtype, quant=quant,
                                  dead=fill)
            outs.append(np.asarray(_ragged(*args, **kw), np.float32))
        assert np.isfinite(outs[1]).all()
        np.testing.assert_array_equal(outs[0], outs[1])

    @staticmethod
    def _the_pallas_call():
        """The traced function's `pallas_call` equation: there is ONE."""
        args, _ = _live_case(13, *_LIVE_CASES["straddles_query_tile"],
                             _LIVE_T)
        jaxpr = jax.make_jaxpr(pa.paged_attention_ragged)(*args)
        call, = [e for e in jaxpr.jaxpr.eqns
                 if e.primitive.name == "pallas_call"]
        return call

    def test_grid_is_the_lane_count(self):
        """The work bound: the `pallas_call`'s static grid is one step a
        lane — no axis, and no product of axes, follows the packed token
        budget T or the table's width, let alone T x max_blocks."""
        call = self._the_pallas_call()
        assert tuple(call.params["grid_mapping"].grid) == (5,)
        assert call.params["name"] == "paged_attention_ragged"

    def test_one_kernel_holds_a_decode_tile_and_a_chunk_tile(self):
        """ISSUE 37: the ONE `pallas_call` holds its body twice, under a
        test of the lane's `q_len`: the score and update matmuls of a
        decode lane at `g_pad` = 8 MXU rows (one token's head group), of
        every other lane at `_RAGGED_Q_CHUNK` tokens' 64; both against
        the same 128-position page group, for each of the 2 kv heads."""
        call = self._the_pallas_call()
        dots = []

        def walk(j):
            for e in j.eqns:
                if e.primitive.name == "dot_general":
                    dots.append(tuple(v.aval.shape for v in e.invars))
                for sub in jax.core.jaxprs_in_params(e.params):
                    walk(sub)

        walk(call.params["jaxpr"])
        rows = sorted({lhs[0] for lhs, _ in dots})
        assert rows == [8, 8 * pa._RAGGED_Q_CHUNK]
        for r in rows:
            assert sorted(d for d in dots if d[0][0] == r) == \
                sorted(2 * [((r, 32), (128, 32)), ((r, 128), (128, 32))])

    def test_gate_refuses_a_tile_that_cannot_fit(self):
        """`ragged_supported` derives the tile the kernel would run: the
        cells' shape gets 8 pages x 64 tokens, the smoke's 32 kv heads 8
        tokens, and one 8-token chunk of 512 kv heads x 8 rows is over
        the VMEM budget, so the gate sends it to the XLA reference."""
        assert pa._ragged_tiles(96, 8, 8, 128, 16, 128, 2) == (8, 64)
        assert pa._ragged_tiles(72, 32, 8, 128, 16, 128, 2) == (8, 8)
        assert pa._ragged_tiles(96, 512, 8, 128, 16, 128, 2) is None
        bf16 = jnp.bfloat16
        assert pa.ragged_supported((96, 32, 128), bf16, (64, 8, 16, 128),
                                   bf16, 128)
        assert not pa.ragged_supported((96, 512, 128), bf16,
                                       (64, 512, 16, 128), bf16, 128)


def _stack_layers(seed, at, layers, *pools):
    """Each one-layer pool of `pools` as layer `at` of an `[L, ...]` pool
    whose other layers hold other draws (never zeros: a read of the wrong
    layer must show)."""
    rng = np.random.default_rng(seed)

    def other(p):
        if p.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, size=p.shape),
                               jnp.int8)
        return jnp.asarray(rng.normal(size=p.shape), p.dtype)

    return [jnp.stack([p if i == at else other(p) for i in range(layers)])
            for p in pools]


class TestLayerOperand:
    """ISSUE 28: the pool as stored, `[L, NB, KVH, BS, D]`, with the layer
    an operand. The kernel and the write at layer `i` are bitwise the
    one-layer call on `pool[i]`; no other layer is read or written."""

    @pytest.mark.parametrize("fn", ["kernel", "ref"])
    @pytest.mark.parametrize("kind", ["bf16", "int8kv"])
    @pytest.mark.parametrize("layer", [0, 2])
    def test_attention_at_layer_equals_one_layer_call(self, fn, kind, layer):
        dtype, quant = _KINDS[kind]
        args, kw = _live_case(21, *_LIVE_CASES["chunk64_with_prior_context"],
                              _LIVE_T, dtype=dtype, quant=quant)
        q, kc, vc, *rest = args
        call = {"kernel": _ragged, "ref": _ragged_ref}[fn]
        want = np.asarray(call(*args, **kw), np.float32)
        kc5, vc5 = _stack_layers(22, layer, 3, kc, vc)
        kw5 = dict(zip(kw, _stack_layers(23, layer, 3, *kw.values())))
        # `layer` traced: one executable for every layer
        got = call(q, kc5, vc5, *rest, layer=jnp.int32(layer), **kw5)
        np.testing.assert_array_equal(np.asarray(got, np.float32), want)

    @pytest.mark.parametrize("kind", ["bf16", "int8kv"])
    @pytest.mark.parametrize("layer", [0, 2])
    def test_write_at_layer_equals_one_layer_call(self, kind, layer):
        """Rows land at `[layer, block, :, offset, :]` exactly as the
        one-layer write puts them at `[block, :, offset, :]`; every other
        layer keeps its bytes; a guard slot writes nothing anywhere."""
        dtype, quant = _KINDS[kind]
        rng = np.random.default_rng(24)
        nb, kvh, bs, d, t = 12, 2, 4, 16, 8
        tables = jnp.asarray([[1, 2, 3], [7, 5, 9]], jnp.int32)
        # lane 0 a 5-token chunk at positions 3..7 (two blocks), lane 1 a
        # decode token at position 9, two guard slots
        lane, pos = pa.ragged_metadata(jnp.asarray([5, 1], jnp.int32),
                                       jnp.asarray([8, 10], jnp.int32), t)
        k = jnp.asarray(rng.normal(size=(t, kvh, d)), dtype)
        v = jnp.asarray(rng.normal(size=(t, kvh, d)), dtype)
        pool_dtype = jnp.int8 if quant else dtype
        pools = [jnp.asarray(rng.integers(-9, 9, size=(nb, kvh, bs, d)),
                             pool_dtype) for _ in range(2)]
        kw = {}
        if quant:
            kw = dict(k_scale=jnp.asarray(rng.random((nb, kvh, bs)),
                                          jnp.float32),
                      v_scale=jnp.asarray(rng.random((nb, kvh, bs)),
                                          jnp.float32))
        write = jax.jit(pa.write_kv_to_cache_ragged)
        want = write(k, v, *pools, tables, lane, pos, **kw)
        before = _stack_layers(25, layer, 3, *pools, *kw.values())
        kw5 = dict(zip(kw, before[2:]))
        got = write(k, v, *before[:2], tables, lane, pos,
                    layer=jnp.int32(layer), **kw5)
        assert len(got) == len(want) == (4 if quant else 2)
        for new, old, one in zip(got, before, want):
            assert new.shape == old.shape and new.dtype == old.dtype
            np.testing.assert_array_equal(np.asarray(new[layer]),
                                          np.asarray(one))
            for other in set(range(3)) - {layer}:
                np.testing.assert_array_equal(np.asarray(new[other]),
                                              np.asarray(old[other]))
            # six live tokens changed six (block, offset) slots, the two
            # guard slots none
            changed = (np.asarray(new, np.float32)
                       != np.asarray(old, np.float32))
            changed = changed.reshape(3, nb, kvh, bs, -1).any(axis=(2, 4))
            assert changed.sum() == 6 and changed[layer].sum() == 6

    def test_rank_decides_and_layer_must_match_it(self):
        args, _ = _live_case(26, [1, 1], [5, 9], 8, w=4, nb=16)
        with pytest.raises(ValueError, match="one layer"):
            pa.paged_attention_ragged(*args, layer=0)
        q, kc, vc, *rest = args
        with pytest.raises(ValueError, match="needs its `layer`"):
            pa.paged_attention_ragged(q, kc[None], vc[None], *rest)


# The decode tile's cases: five decode lanes a call, one a context length
# (block 16, so a page group is 8 pages = 128 tokens; the table 12 wide)
_DECODE_BS, _DECODE_W, _DECODE_T = 16, 12, 16
_DECODE_CTX = {"one_token": 1, "block_less_one": _DECODE_BS - 1,
               "one_block": _DECODE_BS, "page_group_plus_one": 129,
               "full_table": _DECODE_W * _DECODE_BS}


@functools.cache
def _decode_and_control(kind, g, layer):
    """(decode, control, ref, value row) as float32 numpy, one row a
    context of `_DECODE_CTX`, for the serving kinds (bf16 q over a bf16 or
    an int8 pool). `decode`: each token as a `q_len == 1` lane,
    which takes the kernel's one-token tile. `control`: the same token as
    the LAST of a two-token lane over the same pages, which takes the
    `_RAGGED_Q_CHUNK` body (no lane of two tokens fits a context of one:
    that lane stays a decode lane, and its control is the value row
    itself). Same pool, same tables, same shapes: ONE executable serves
    both calls, what differs is the data `q_lens`."""
    dtype, quant = _KINDS[kind]
    kv_lens = list(_DECODE_CTX.values())
    lanes = len(kv_lens)
    (q, kc, vc, tables, kv, lane, pos), kw = _live_case(
        31, [1] * lanes, kv_lens, _DECODE_T, kvh=2, h=2 * g,
        bs=_DECODE_BS, w=_DECODE_W, dtype=dtype, quant=quant)
    row = np.asarray(vc[tables[0, 0], :, 0], np.float32)       # [KVH, D]
    if quant:
        row = row * np.asarray(kw["v_scale"][tables[0, 0], :, 0])[:, None]
    kc, vc = _stack_layers(32, layer, 3, kc, vc)
    kw = dict(zip(kw, _stack_layers(33, layer, 3, *kw.values())),
              layer=jnp.int32(layer))
    decode = _ragged(q, kc, vc, tables, kv, lane, pos, **kw)
    ref = _ragged_ref(q, kc, vc, tables, kv, lane, pos, **kw)
    two = [1] + [2] * (lanes - 1)
    last = np.cumsum(two) - 1                  # each lane's last token
    q2 = jnp.roll(q, 3, axis=0).at[last].set(q[:lanes])
    lane2, pos2 = pa.ragged_metadata(jnp.asarray(two, jnp.int32), kv,
                                     _DECODE_T)
    control = _ragged(q2, kc, vc, tables, kv, lane2, pos2, **kw)
    assert decode.dtype == control.dtype == ref.dtype == dtype
    first = np.arange(lanes)
    return (np.asarray(decode, np.float32)[first],
            np.asarray(control, np.float32)[last],
            np.asarray(ref, np.float32)[first], np.repeat(row, g, axis=0))


class TestDecodeTile:
    """ISSUE 37: a `q_len == 1` lane is computed in a one-token tile
    (`g_pad` MXU rows a kv head and page group, not 8 tokens' worth). It
    is the same arithmetic a row: a decode lane's output is, to the last
    bit, what the chunked body gives the same token."""

    @pytest.mark.parametrize("ctx", list(_DECODE_CTX))
    @pytest.mark.parametrize("layer", [0, 2])
    @pytest.mark.parametrize("g", [1, 2, 4])
    @pytest.mark.parametrize("kind", ["bf16", "int8kv"])
    def test_decode_lane_is_the_chunked_body_bit_for_bit(self, kind, g,
                                                         layer, ctx):
        decode, control, ref, row = _decode_and_control(kind, g, layer)
        i = list(_DECODE_CTX).index(ctx)
        if ctx == "one_token":
            # softmax over one position is 1.0: the (dequantized) value
            # row itself, rounded once to the output's dtype
            want = np.asarray(jnp.asarray(row, _KINDS[kind][0]), np.float32)
            np.testing.assert_array_equal(decode[i], want)
        np.testing.assert_array_equal(decode[i], control[i])
        np.testing.assert_allclose(decode[i], ref[i], atol=2e-2, rtol=2e-2)

    @pytest.mark.parametrize("kind", ["f32", "bf16"])
    def test_last_decode_lane_stays_inside_the_buffer(self, kind):
        """A decode lane moves ONE token of q in and one of o out: as the
        last lane of a buffer with no spare chunk behind it (the kernel's
        own call, below the public function's padding) its token, the
        buffer's last row, is still the reference's."""
        dtype, _ = _KINDS[kind]
        t, kvh, g, d = 8, 2, 4, 32
        args, _ = _live_case(34, [1] * t, [5, 16, 17, 1, 130, 40, 192, 64],
                             t, kvh=kvh, h=kvh * g, bs=_DECODE_BS,
                             w=_DECODE_W, nb=64, d=d, dtype=dtype)
        q, kc, vc, tables, kv, _, _ = args
        tiles = pa._ragged_tiles(t, kvh, 8, d, _DECODE_BS, _DECODE_W,
                                 kc.dtype.itemsize)
        qg = jnp.pad(q.reshape(t, kvh, g, d).astype(jnp.float32),
                     ((0, 0), (0, 0), (0, 8 - g), (0, 0)))
        out = pa._ragged_call(
            qg, kc[None], vc[None], jnp.zeros((1,), jnp.int32),
            jnp.zeros((1,), jnp.int32), tables, kv,       # layer 0, no window
            jnp.ones((t,), jnp.int32), jnp.arange(t, dtype=jnp.int32),
            1.0 / float(np.sqrt(d)), tiles,
            jnp.float32 if kind == "f32" else jnp.bfloat16)
        assert out.shape == qg.shape
        got = out[:, :, :g].reshape(t, kvh * g, d).astype(dtype)
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(_ragged(*args), np.float32))

    @pytest.mark.parametrize("kind", ["f32", "bf16"])
    def test_paged_attention_is_its_decode_lanes(self, kind):
        """`paged_attention(q [B, H, D])`, every lane a decode lane: row
        for row and bit for bit the chunked body's answer for the same
        tokens (each the last of a two-token lane)."""
        dtype, _ = _KINDS[kind]
        kv_lens = [9, 16, 17, 40]
        lanes = len(kv_lens)
        (q, kc, vc, tables, kv, _, _), _ = _live_case(
            35, [1] * lanes, kv_lens, 2 * lanes, w=4, nb=32, dtype=dtype)
        out = jax.jit(pa.paged_attention)(q[:lanes], kc, vc, tables, kv)
        last = 2 * np.arange(lanes) + 1
        q2 = jnp.roll(q, 1, axis=0).at[last].set(q[:lanes])
        lane2, pos2 = pa.ragged_metadata(
            jnp.full((lanes,), 2, jnp.int32), kv, 2 * lanes)
        control = _ragged(q2, kc, vc, tables, kv, lane2, pos2)
        assert out.dtype == dtype and out.shape == (lanes, 8, 32)
        got = np.asarray(out, np.float32)
        want = np.asarray(control, np.float32)[last]
        if kind == "f32":
            # to the last bits only: the CPU's f32 gemm picks its
            # blocking, and so its order of summation, by the row count
            # (the MXU does not: PERF.md has the chip's bitwise check)
            np.testing.assert_allclose(got, want, atol=1e-6, rtol=0)
        else:
            np.testing.assert_array_equal(got, want)


class TestRaggedWrite:
    def test_scatter_lands_at_positions_and_drops_guards(self):
        rng = np.random.default_rng(7)
        nb, kvh, bs, d = 8, 2, 4, 16
        kc = jnp.zeros((nb, kvh, bs, d), jnp.float32)
        vc = jnp.zeros((nb, kvh, bs, d), jnp.float32)
        tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
        # lane0 writes positions 5..6 (block 1 of its table, offsets 1-2);
        # lane1 writes position 0; one guard slot
        lane = jnp.asarray([0, 0, 1, 1], jnp.int32)
        pos = jnp.asarray([5, 6, 0, -1], jnp.int32)
        k = jnp.asarray(rng.normal(size=(4, kvh, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(4, kvh, d)), jnp.float32)
        kc2, vc2 = pa.write_kv_to_cache_ragged(k, v, kc, vc, tables,
                                               lane, pos)
        np.testing.assert_array_equal(np.asarray(kc2)[2, :, 1], k[0])
        np.testing.assert_array_equal(np.asarray(kc2)[2, :, 2], k[1])
        np.testing.assert_array_equal(np.asarray(vc2)[3, :, 0], v[2])
        # the guard slot wrote NOTHING anywhere: exactly the 3 real
        # tokens' (block, offset) rows are populated, slot 3 is dropped
        for cache in (kc2, vc2):
            nz = np.abs(np.asarray(cache)).sum(axis=(1, 3))   # [NB, BS]
            assert (nz > 0).sum() == 3

    def test_matches_contiguous_writer_on_chunk(self):
        """A contiguous chunk through the ragged scatter == the legacy
        start_pos writer."""
        rng = np.random.default_rng(8)
        nb, kvh, bs, d = 8, 2, 4, 16
        kc = jnp.zeros((nb, kvh, bs, d), jnp.float32)
        vc = jnp.zeros((nb, kvh, bs, d), jnp.float32)
        tables = jnp.asarray([[1, 2, 3]], jnp.int32)
        k = jnp.asarray(rng.normal(size=(1, 5, kvh, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, 5, kvh, d)), jnp.float32)
        ref_k, ref_v = pa.write_kv_to_cache(
            k, v, kc, vc, tables, jnp.asarray([3], jnp.int32))
        lane = jnp.zeros((5,), jnp.int32)
        pos = jnp.asarray([3, 4, 5, 6, 7], jnp.int32)
        out_k, out_v = pa.write_kv_to_cache_ragged(
            k[0], v[0], kc, vc, tables, lane, pos)
        np.testing.assert_array_equal(np.asarray(out_k), np.asarray(ref_k))
        np.testing.assert_array_equal(np.asarray(out_v), np.asarray(ref_v))


class TestEngineRaggedStep:
    """Engine-level semantics shared by both EngineCore implementations:
    a prompt fed in chunks == the prompt fed whole, bitwise."""

    @pytest.mark.parametrize("which", ["mlp", "llama"])
    def test_chunked_ragged_equals_whole_prompt_step(self, which):
        import paddle_tpu as paddle

        if which == "mlp":
            from paddle_tpu.serving import MLPLMEngine

            def build():
                return MLPLMEngine(vocab_size=64, hidden=16,
                                   max_batch_size=4, num_blocks=48,
                                   block_size=4, max_blocks_per_seq=8)
        else:
            from paddle_tpu.inference import LlamaInferenceEngine
            from paddle_tpu.models import llama_tiny

            paddle.seed(3)
            model = llama_tiny(vocab=64, layers=2, hidden=32, heads=2,
                               seq=64)
            model.eval()

            def build():
                return LlamaInferenceEngine(model, max_batch_size=4,
                                            num_blocks=48, block_size=4,
                                            max_blocks_per_seq=8)

        rng = np.random.default_rng(9)
        prompt = rng.integers(1, 64, 9).astype(np.int32)
        T, B = 10, 2

        def stepper(eng):
            eng.manager.allocate(-1, 1)        # guard block
            guard = eng.manager.block_table_array([-1])[0, 0]
            eng.manager.allocate(0, 0)

            def step(toks, q, kv):
                eng.manager.append_tokens(0, len(toks))
                tokens = np.zeros(T, np.int32)
                tokens[:len(toks)] = toks
                tb = np.full((B, 8), guard, np.int32)
                tb[0] = eng.manager.block_table_array([0])[0]
                return np.asarray(eng.ragged_step(
                    tokens, np.asarray(q, np.int32),
                    np.asarray(kv, np.int32), tb))
            return step

        # whole: the 9-token prompt as ONE step, then one q_len==1 round
        whole = stepper(build())
        lg = whole(prompt, [9, 0], [9, 0])
        tok = int(np.argmax(lg[8]))
        dl = whole([tok], [1, 0], [10, 0])

        # chunked: 4+5 tokens, then the same q_len==1 round, same T
        step = stepper(build())
        step(prompt[:4], [4, 0], [4, 0])
        out = step(prompt[4:9], [5, 0], [9, 0])
        np.testing.assert_array_equal(lg[8], out[4])
        out2 = step([tok], [1, 0], [10, 0])
        np.testing.assert_array_equal(dl[0], out2[0])
