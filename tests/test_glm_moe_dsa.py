"""The GLM-MoE-DSA architecture (`models/glm_moe_dsa.py`), its two kernels
(`ops/pallas/dsa.py`) and its serving engine (`inference/
glm_moe_dsa_runner.py`) at a small size on the CPU, held against the
benchmark's plain reference (`benchmark/reference/glm_moe_dsa_arch.py`, loaded
by path: it imports nothing of the program).

Contexts run to six times a small `index_topk` (16 of 96), so the selection
bites in every case. Float32 unless said. Logit tolerances: float32 against
float32 `highest` differ only in the order of sums (readings 2e-6 on logits of
spread 0.65, so 1e-4 is fifty times the noise and a ten-thousandth of what a
wrong selection moves, 2.7); bfloat16 and the int8 grid are measured against
the reference as the benchmark measures them, in units of a row's logit std.
"""
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.framework import flags, monitor
from paddle_tpu.inference import kv_migrate
from paddle_tpu.inference.glm_moe_dsa_runner import GlmMoeDsaInferenceEngine
from paddle_tpu.models import deepseek_v3 as dsv3
from paddle_tpu.models import glm_moe_dsa as glm
from paddle_tpu.ops import sampling
from paddle_tpu.ops.pallas import dsa
from paddle_tpu.ops.pallas.paged_attention import ragged_metadata
from paddle_tpu.serving import RequestStatus, ServingFrontend
from test_sampled_step import all_rows_round

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    path = os.path.join(ROOT, "benchmark", "reference", "glm_moe_dsa_arch.py")
    spec = importlib.util.spec_from_file_location("ref_glm_moe_dsa", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()

TOPK, WIDTH = 16, 96
HF = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
          moe_intermediate_size=32, num_hidden_layers=5, num_attention_heads=4,
          kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
          qk_rope_head_dim=8, v_head_dim=24, n_routed_experts=8,
          n_shared_experts=1, num_experts_per_tok=2, first_k_dense_replace=1,
          routed_scaling_factor=2.5, norm_topk_prob=True, rms_norm_eps=1e-5,
          rope_parameters={"rope_theta": 10000.0, "rope_type": "default"},
          rope_interleave=True, n_group=1, topk_group=1,
          scoring_func="sigmoid", index_n_heads=4, index_head_dim=16,
          index_topk=TOPK, indexer_rope_interleave=True,
          indexer_types=["full", "shared", "shared", "shared", "full"])
CFG = glm.GlmMoeDsaConfig.from_hf(HF)


def make_params(dtype=jnp.float32, seed=3, cfg=CFG):
    """Weights large enough that routing, the indexer and attention are not
    flat: std 0.08, drawn in float32 and rounded to `dtype`."""
    return {k: v.astype(dtype)
            for k, v in glm.init_params(cfg, seed, jnp.float32, 0.08).items()}


class Recording(GlmMoeDsaInferenceEngine):
    """The engine, remembering every packed row's logits with the request
    and position it belongs to."""

    def __init__(self, *a, **k):
        super().__init__(*a, **k)
        self.rows, self.slots_of = [], None

    def sampled_step(self, tokens, lanes, tables, temperature):
        # every packed row's logits come from the all-rows program, on the
        # same step (a cache write is indexed by position: made twice, it
        # is made once); the round itself must sample what they sample to
        want, logits = all_rows_round(self, tokens, lanes, tables,
                                      temperature)
        sampled = super().sampled_step(tokens, lanes, tables, temperature)
        np.testing.assert_array_equal(np.asarray(sampled), want)
        cursor = 0
        for lane, (n, kv) in enumerate(lanes[:, :2]):
            req = self.slots_of()[lane]
            for j in range(int(n)):
                self.rows.append((req.req_id, int(kv) - int(n) + j,
                                  logits[cursor + j]))
            cursor += int(n)
        return sampled


def serve(params, prompts, new_tokens, num_blocks=4 * 6 + 1, engine=Recording,
          **frontend):
    model = glm.GlmMoeDsaForCausalLM(CFG, weights=params)
    eng = engine(model, max_batch_size=4, num_blocks=num_blocks,
                 block_size=16, max_blocks_per_seq=6)
    fe = ServingFrontend(eng, prefill_chunk_tokens=16, **frontend)
    if isinstance(eng, Recording):
        eng.slots_of = lambda: fe.scheduler.slots
    handles = [fe.submit(p, max_new_tokens=new_tokens) for p in prompts]
    fe.run_until_idle()
    assert all(h.status is RequestStatus.FINISHED for h in handles)
    return eng, handles


def prompts_of(rng, lengths):
    return [rng.integers(1, CFG.vocab_size, n).tolist() for n in lengths]


def row_gap(got, want):
    """Median over rows of the rms difference in units of the row's std (the
    median: a flipped expert or a flipped selection moves a whole row)."""
    return float(np.median(np.sqrt(np.mean(np.square(got - want), -1))
                           / want.std(-1)))


def reference_rows(params, prompts, handles, rows, quant=None, **kw):
    """The reference's logits at the (request, position) of every row."""
    f32 = {k: v.astype(jnp.float32) for k, v in params.items()}
    ids = np.zeros((len(prompts), WIDTH), np.int32)
    for r, (p, h) in enumerate(zip(prompts, handles)):
        ids[r, :len(p) + len(h.tokens)] = p + h.tokens
    forward = jax.jit(jax.vmap(lambda i: ref.forward(f32, i, HF, quant, **kw)))
    full = dict(zip((h.request_id for h in handles), np.asarray(forward(ids))))
    return np.stack([full[r[0]][r[1]] for r in rows])


# prompts: one that stays under index_topk + its answer (5 + 10 <= 16), one
# that CROSSES it while it decodes (12 -> 22), and three several times past
LENGTHS = (5, 12, 37, 80, 50)


def served_against_reference(dtype, rng, with_grid=False, **kw):
    """Requests through `ServingFrontend` (chunked prefill, then decode
    through both pools): every packed row's logits and the reference's at
    the same request and position."""
    params = make_params(dtype)
    prompts = prompts_of(rng, LENGTHS)
    eng, handles = serve(params, prompts, 10, **kw)
    got = np.stack([r[2] for r in eng.rows])
    want = reference_rows(params, prompts, handles, eng.rows)
    grid = reference_rows(params, prompts, handles, eng.rows, "int8") \
        if with_grid else None
    return eng, handles, got, want, grid


def test_model_forward_is_the_reference(rng):
    params = make_params()
    ids = jnp.asarray(rng.integers(1, CFG.vocab_size, WIDTH), jnp.int32)
    got = glm.GlmMoeDsaForCausalLM(CFG, weights=params)(ids)
    np.testing.assert_allclose(got, ref.forward(params, ids, HF), atol=1e-4,
                               rtol=0)


def test_served_logits_match_reference(rng):
    eng, handles, got, want, _ = served_against_reference(jnp.float32, rng)
    assert len(eng.rows) == sum(LENGTHS) + 5 * 9
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)
    # the lane that crossed index_topk while it decoded is among the rows
    crossed = [r[1] for r in eng.rows if r[0] == handles[1].request_id]
    assert min(crossed) < TOPK - 1 < max(crossed)


def test_bf16_passes_where_the_int8_grid_fails(rng):
    """bf16 weights, caches and arithmetic against the float32 reference on
    the same bf16-valued weights, and the reference on an int8 grid: the
    limit lies between their readings (0.0135-0.0153 and 0.064-0.081 over
    three seeds of the prompts)."""
    _, _, got, want, grid = served_against_reference(jnp.bfloat16, rng, True)
    limit = 0.03
    assert row_gap(got, want) < limit < row_gap(grid, want)


def test_a_preempted_lane_resumes_on_the_same_logits(rng):
    """A pool too small for four lanes at once: a lane is preempted, loses
    its blocks in BOTH pools, and prefills again; every row it serves after
    still reads the reference's logits."""
    params = make_params()
    prompts = prompts_of(rng, (30, 30, 30, 30))
    eng, handles = serve(params, prompts, 20, num_blocks=11)
    assert sum(h._req.num_preemptions for h in handles) > 0
    got = np.stack([r[2] for r in eng.rows])
    want = reference_rows(params, prompts, handles, eng.rows)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)


def test_a_shared_layer_attends_the_carried_selection(rng):
    """The served rows agree with the reference that carries a `full`
    layer's selection to the `shared` layers after it, and NOT with one
    whose `shared` layers attend the most recent `index_topk` positions."""
    params = make_params()
    prompts = prompts_of(rng, (80, 50))
    eng, handles = serve(params, prompts, 6)
    got = np.stack([r[2] for r in eng.rows])
    pos = np.arange(WIDTH)
    recent = ref._pack(jnp.asarray((pos[None, :] <= pos[:, None])
                                   & (pos[None, :] > pos[:, None] - TOPK)))
    want = reference_rows(params, prompts, handles, eng.rows)
    wrong = reference_rows(params, prompts, handles, eng.rows,
                           reselect=lambda i, carried: recent)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)
    assert np.abs(got - wrong).max() > 0.1


def test_selection_is_the_top_k_with_ties_to_the_lower_position():
    scores = jnp.asarray([[3., 1., 3., 2., 3., 0.],
                          [5., 4., 9., 9., 9., 9.],
                          [1., 1., 1., 1., 1., 1.]], jnp.float32)
    idx, n = glm.select(scores, jnp.asarray([5, 1, -1], jnp.int32), 3)
    assert n.tolist() == [3, 2, 0]
    assert idx[0].tolist() == [0, 2, 4]
    assert sorted(idx[1, :2].tolist()) == [0, 1], "positions past its own"
    picked = ref.pick(scores, jnp.asarray([5, 1, -1]), 3)
    assert picked.tolist() == [[1, 0, 1, 0, 1, 0], [1, 1, 0, 0, 0, 0],
                               [0] * 6]


@pytest.mark.parametrize("near", [16, 24, 48])
def test_rows_in_the_near_part_select_over_it_alone(near):
    """What the step's tiles do (`glm_moe_dsa_runner.NEAR_SHARE`): rows
    whose positions all lie in the first `near` of the table's span pick
    from those columns the positions they pick from the whole span, ties
    and guard rows among them."""
    rng = np.random.default_rng(near)
    scores = jnp.asarray(np.round(rng.normal(size=(12, 96)), 1), jnp.float32)
    pos = jnp.asarray(np.r_[rng.integers(0, near, 10), near - 1, -1],
                      jnp.int32)
    whole, n = glm.select(scores, pos, TOPK)
    part, n_part = glm.select(scores[:, :near], pos, TOPK)
    assert n.tolist() == n_part.tolist()
    for r in range(12):
        assert whole[r, :n[r]].tolist() == part[r, :n[r]].tolist()


def _select_cases():
    """name -> (scores [R, S], pos [R], k): what `dsa.dsa_select` must pick
    as `glm.select` does. The widths: one group of 128, the benchmark
    table's near quarter (14,336) and its span (57,344), and ragged ones."""
    rng = np.random.default_rng(48)

    def rows(s, r=5):
        return np.r_[rng.integers(0, s, r - 2), s - 1, -1]

    cases = {}
    for s, k in ((128, 16), (96, 16), (1408, 290), (14336, 2048),
                 (57344, 2048)):
        r = 5 if s < 10000 else 3
        x = rng.normal(size=(r, s))
        cases[f"random scores, {s} wide"] = (x, rows(s, r), k)
        cases[f"scores rounded to one decimal, {s} wide"] = (
            np.round(x, 1), rows(s, r), k)
    cases["a row of one value"] = (np.full((4, 300), 0.25), rows(300, 4), 40)
    cases["fewer causal positions than k"] = (
        rng.normal(size=(4, 640)), np.asarray([0, 7, 38, 39]), 40)
    cases["k wider than the row"] = (rng.normal(size=(3, 96)), rows(96, 3),
                                     200)
    cases["guard rows alone"] = (rng.normal(size=(2, 256)),
                                 np.asarray([-1, -1]), 16)
    x = rng.normal(size=(5, 700))
    x[:, ::3] = -np.inf
    x[:, 1::7], x[:, 2::7] = -0.0, 0.0
    x[:, 5::11], x[:, 6::11] = 1e-40, -1e-41
    cases["-inf, zeros of both signs and denormals"] = (x, rows(700), 290)
    cases["scores near -1e30"] = (-1e30 * (1 + rng.random((5, 700))),
                                  rows(700), 64)
    # the k-th and (k + 1)-th scores equal, the tie's members on both sides
    # of a group's edge: 8 over the tie, 8 of the tie's 12 to take
    x = np.zeros((2, 512))
    x[:, [3, 130, 200, 255, 256, 300, 400, 500]] = 2.0
    x[:, 250:256], x[:, 256:262] = 1.0, 1.0
    x[1] = np.roll(x[1], 128)
    cases["the k-th and the next equal across a group's edge"] = (
        x, np.asarray([511, 511]), 16)
    return cases


SELECT_CASES = _select_cases()


@pytest.mark.parametrize("case", sorted(SELECT_CASES))
def test_the_selection_by_threshold_is_the_top_k_s_set(case):
    """`dsa.dsa_select` (a threshold by bisection over the scores' bits,
    a placement by rank) against `glm.select` (`lax.top_k`): `n` equal and
    the first `n` positions the same set row by row, `lax.top_k`'s ties
    among them, in strictly ascending position order."""
    scores, pos, k = SELECT_CASES[case]
    rows, s = scores.shape
    scores = jnp.asarray(scores, jnp.float32)
    pos = jnp.asarray(pos, jnp.int32)
    want, n = glm.select(scores, pos, k)
    got, m = dsa.dsa_select(dsa.score_tile(scores, 0, rows, s), pos, k)
    assert got.dtype == jnp.int32
    assert got.shape == (rows, min(k, -(-s // 128) * 128))
    assert m.tolist() == n.tolist()
    for r in range(rows):
        mine = np.asarray(got[r, :n[r]])
        assert mine.tolist() == sorted(want[r, :n[r]].tolist()), r
        assert (np.diff(mine) > 0).all(), "position order"


@pytest.mark.parametrize("rows,groups,k", [(8, 8, 100), (5, 3, 16),
                                           (16, 12, 290), (3, 1, 128)])
def test_the_threshold_is_the_k_th_largest_key(rows, groups, k, rng):
    """`dsa.select_threshold` over `select_keys`: at least `k` keys reach
    it and fewer than `k` pass it, with ties by the hundred, a row at the
    tile's last position and a guard row; and the keys order as the
    masked scores do."""
    # `+ 0.0`: no -0.0, which the keys put under +0.0 and a float sort does not
    tile = jnp.asarray(np.round(rng.normal(size=(rows, groups, 128)), 1)
                       + 0.0, jnp.float32)
    pos = jnp.asarray(np.r_[rng.integers(0, groups * 128, rows - 2),
                            groups * 128 - 1, -1], jnp.int32)
    keys = np.asarray(dsa.select_keys(tile, pos), np.int64).reshape(rows, -1)
    t = np.asarray(dsa.select_threshold(dsa.select_keys(tile, pos), k))
    assert ((keys >= t[:, None]).sum(1) >= k).all()
    assert ((keys > t[:, None]).sum(1) < k).all()
    masked = np.where(np.arange(groups * 128)[None, :] <= np.asarray(pos)[:, None],
                      np.asarray(tile).reshape(rows, -1), -np.inf)
    for r in range(rows):
        order = np.argsort(masked[r], kind="stable")
        assert (np.diff(keys[r][order]) >= 0).all()


def _under(jaxpr, scope, path=""):
    """Every equation of `jaxpr`, nested ones too, whose scope path (an
    inner jaxpr's name stack starts anew: the outer equation's is put
    before it) holds `scope`."""
    for eqn in jaxpr.eqns:
        here = f"{path}/{eqn.source_info.name_stack}"
        if scope in here:
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _under(sub, scope, here)


def test_the_step_selects_near_tiles_over_the_near_part(rng):
    """The traced step holds the selection at both widths (the table's span
    and its first `1 / NEAR_SHARE`), a table too narrow for a part that
    holds `index_topk` positions holds one, and no equation under
    `llama.dsa_topk` is a sort, a top-k, a gather or a scatter."""
    from paddle_tpu.inference import glm_moe_dsa_runner as gr

    model = glm.GlmMoeDsaForCausalLM(CFG, weights=make_params())

    def select_widths(blocks_per_seq):
        eng = GlmMoeDsaInferenceEngine(model, max_batch_size=2, num_blocks=9,
                                       block_size=16,
                                       max_blocks_per_seq=blocks_per_seq)
        fn, lead = eng.cost_card_args("ragged")
        jaxpr = jax.make_jaxpr(fn)(*lead, *sampling.step_args(
            np.zeros((18,), np.int32), [1, 1], [5, 9],
            np.zeros((2, blocks_per_seq), np.int32))).jaxpr
        scoped = list(_under(jaxpr, "llama.dsa_topk"))
        names = {e.primitive.name for e in scoped}
        assert "while" in names and "dot_general" in names      # it is there
        assert not names & {"sort", "top_k", "approx_top_k", "gather",
                            "scatter", "scatter-add"}, names
        # a tile's keys: the bitcast of its masked scores `[rows, G, 128]`
        return {e.invars[0].aval.shape[1] for e in scoped
                if e.primitive.name == "bitcast_convert_type"}

    assert gr.NEAR_SHARE == 4
    assert select_widths(24) == {3, 1}          # 384 and its quarter, 96
    assert select_widths(6) == {1}              # 96 and 24: a group each
    assert select_widths(2) == {1}              # 8 < index_topk 16: one


# ---- the kernels -----------------------------------------------------------------
L, NB, BS, D, H, B, W = 2, 40, 16, 16, 4, 4, 8

KERNEL_CASES = {
    "decode only": ([1, 1, 1, 1], [5, 17, 128, 9]),
    "chunk and decode lanes": ([7, 1, 1, 11], [7, 16, 100, 41]),
    "guard lanes": ([1, 0, 13, 0], [5, 0, 33, 0]),
    "a chunk over several compute chunks": ([1, 19, 1, 0], [37, 100, 128, 0]),
}


@pytest.fixture
def interpret():
    flags.set_flags({"pallas_interpret": True})
    yield
    flags.set_flags({"pallas_interpret": False})


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_index_kernel_against_its_ref(case, rng, interpret):
    q_lens, kv_lens = (jnp.asarray(a, jnp.int32) for a in KERNEL_CASES[case])
    tokens = 28
    pool = jnp.asarray(rng.normal(size=(L, NB, BS, D)), jnp.float32)
    tables = jnp.asarray(rng.permutation(NB)[:B * W].reshape(B, W), jnp.int32)
    lane, pos = ragged_metadata(q_lens, kv_lens, tokens)
    q = jnp.asarray(rng.normal(size=(tokens, H, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(tokens, H)), jnp.float32)
    assert dsa.index_supported(q.shape, pool.shape, pool.dtype, W)
    got = dsa.score_tile(
        dsa.dsa_index_scores(q, w, pool, 1, tables, kv_lens, lane, pos), 0,
        tokens, W * BS).reshape(tokens, W * BS)
    want = dsa.dsa_index_scores_ref(q, w, pool, 1, tables, kv_lens, lane, pos)
    np.testing.assert_array_equal(
        dsa.score_tile(want, 3, 8, W * BS).reshape(8, W * BS), want[3:11])
    # a cut that ends inside a group of 128: the ref's is padded with -inf
    part = np.asarray(dsa.score_tile(want, 3, 8, 100)).reshape(8, 128)
    np.testing.assert_array_equal(part[:, :100], want[3:11, :100])
    assert np.isneginf(part[:, 100:]).all()
    causal = np.arange(W * BS)[None, :] <= np.asarray(pos)[:, None]
    assert causal.sum() == sum(
        q * k - q * (q - 1) // 2 for q, k in zip(*KERNEL_CASES[case]))
    np.testing.assert_allclose(np.where(causal, got, 0.0),
                               np.where(causal, want, 0.0), atol=2e-5, rtol=0)
    # and the scores are the equation's, position by position
    keys = pool[1][tables[lane[0]]].reshape(W * BS, D)
    np.testing.assert_allclose(
        np.where(causal[0], want[0], 0.0),
        np.where(causal[0], glm.index_scores(q[:1], w[:1], keys)[0], 0.0),
        atol=2e-5, rtol=0)


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_the_index_kernel_takes_the_buffers_as_they_are(case, rng, interpret,
                                                        monkeypatch):
    """ISSUE 49: `index_prepare`, placed in blanks (here full of NaN),
    through `dsa_index_scores_packed`: the public function's scores at every
    causal position of a live row."""
    from test_live_prefix import poison

    from paddle_tpu.ops.pallas import _support

    q_lens, kv_lens = (jnp.asarray(a, jnp.int32) for a in KERNEL_CASES[case])
    tokens = 28
    pool = jnp.asarray(rng.normal(size=(L, NB, BS, D)), jnp.float32)
    tables = jnp.asarray(rng.permutation(NB)[:B * W].reshape(B, W), jnp.int32)
    lane, pos = ragged_metadata(q_lens, kv_lens, tokens)
    q = jnp.asarray(rng.normal(size=(tokens, H, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(tokens, H)), jnp.float32)
    want = dsa.dsa_index_scores(q, w, pool, 1, tables, kv_lens, lane, pos)
    monkeypatch.setattr(_support, "blank", poison)
    qb, wb = _support.place(dsa.index_prepare(q, w, pool), tokens, tokens)
    assert qb.shape == (tokens + 8, H, D) and wb.shape == (tokens + 8, H, 128)
    assert np.isnan(qb[tokens:]).all() and np.isnan(wb[tokens:]).all()
    got = dsa.dsa_index_scores_packed(qb, wb, pool, 1, tables, kv_lens, lane,
                                      pos)
    causal = np.arange(W * BS)[None, :] <= np.asarray(pos)[:, None]

    def rows(scores):
        return np.where(causal, np.asarray(dsa.score_tile(
            scores, 0, tokens, W * BS)).reshape(tokens, W * BS), 0.0)

    np.testing.assert_array_equal(rows(got), rows(want))
    with pytest.raises(ValueError, match="placed"):
        dsa.dsa_index_scores_packed(qb[:tokens], wb, pool, 1, tables, kv_lens,
                                    lane, pos)


@pytest.mark.parametrize("case", ["whole selections", "short and guard rows"])
def test_sparse_kernel_against_its_ref(case, rng, interpret):
    rows, k, dk, dv = 6, 16, 48, 32
    q = jnp.asarray(rng.normal(size=(rows, H, 40)), jnp.float32)
    got_rows = jnp.asarray(rng.normal(size=(rows, k, dk)), jnp.float32)
    n = jnp.asarray([k] * rows if case == "whole selections"
                    else [k, 3, 0, 1, 9, 0], jnp.int32)
    assert dsa.sparse_supported(q.shape, got_rows.shape, got_rows.dtype, dv)
    got = dsa.mla_sparse_attention(q, got_rows, n, dv, 0.3)
    want = dsa.mla_sparse_attention_ref(q, got_rows, n, dv, 0.3)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
    assert not np.asarray(got)[np.asarray(n) == 0].any(), \
        "guard rows are exact zeros"


def test_sparse_rows_reads_through_the_block_table(rng):
    pool = jnp.asarray(rng.normal(size=(3, NB, BS, 24)), jnp.float32)
    tables = jnp.asarray(rng.permutation(NB)[:B * W].reshape(B, W), jnp.int32)
    lane = jnp.asarray([2, 0, 3], jnp.int32)
    idx = jnp.asarray(rng.integers(0, W * BS, (3, 5)), jnp.int32)
    got = dsa.sparse_rows(pool, 1, dsa.row_ids(tables, lane, idx, BS))
    for r in range(3):
        for j in range(5):
            p = int(idx[r, j])
            np.testing.assert_array_equal(
                got[r, j], pool[1, tables[lane[r], p // BS], p % BS])


def test_kernels_lower_for_tpu_at_glm_width(monkeypatch):
    """Pallas' TPU block-shape checks at the cell's shapes (no libtpu)."""
    from paddle_tpu.ops.pallas import _support

    monkeypatch.setattr(_support, "backend", lambda: "tpu")
    s = jax.ShapeDtypeStruct
    text = jax.jit(
        lambda q, w, pool, tb, kv, lane, pos: dsa.dsa_index_scores(
            q, w, pool, 1, tb, kv, lane, pos)
    ).trace(s((544, 32, 128), jnp.bfloat16), s((544, 32), jnp.float32),
            s((2, 129, 64, 128), jnp.bfloat16), s((32, 896), jnp.int32),
            s((32,), jnp.int32), s((544,), jnp.int32), s((544,), jnp.int32)
            ).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text and "dsa_index_scores" in text
    text = jax.jit(
        lambda q, g, n: dsa.mla_sparse_attention(q, g, n, 512, 256 ** -0.5)
    ).trace(s((32, 64, 576), jnp.bfloat16), s((32, 2048, 640), jnp.bfloat16),
            s((32,), jnp.int32)).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text and "mla_sparse_attention" in text


# ---- the chip's share of an expert layer -------------------------------------------
def test_the_shares_add_up_to_the_uncut_layer(rng):
    """Four chips hold two of the router's eight experts each: their routed
    parts, with the shared expert counted once, are the uncut layer's
    feed-forward (the reference's, every expert on every row)."""
    params = make_params()
    p = dsv3.layer_params(params, 2)
    x = jnp.asarray(rng.normal(size=(24, CFG.hidden_size)), jnp.float32)
    live = jnp.ones((24,), bool)
    experts, weights = dsv3.route(x, p, CFG)
    routed = jnp.zeros_like(x)
    for first in range(0, 8, 2):
        mine = {k: (v[first:first + 2] if k.startswith("mlp.experts.") else v)
                for k, v in p.items()}
        (xs, order, keep), (held, _) = dsv3.dispatch(x, experts, live, CFG,
                                                     (first, 2))
        routed = routed + dsv3.combine(dsv3.expert_ffn(xs, held, mine), order,
                                       keep, weights, x.dtype)
    got = routed + dsv3.swiglu(x, p["mlp.shared_experts.gate_proj.weight"],
                               p["mlp.shared_experts.up_proj.weight"],
                               p["mlp.shared_experts.down_proj.weight"])
    want = ref.every_expert_moe(x, p, HF) + ref.shared_expert(x, p)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_a_share_is_served_as_the_reference_computes_it(rng):
    """The model told it holds experts 2-5 of the router's eight, against
    the reference given the same share."""
    cut = dict(HF, n_routed_experts=4, reduced={"n_routed_experts": {
        "published": 8, "held": [2, 4]}})
    cfg = glm.GlmMoeDsaConfig.from_hf(dict(HF), held_experts=(2, 4))
    assert {k: s for k, (s, _) in glm.param_shapes(cfg).items()} == \
        {k: tuple(s) for k, (s, _) in ref.param_shapes(cut).items()}
    params = make_params(cfg=cfg)
    ids = jnp.asarray(rng.integers(1, CFG.vocab_size, 64), jnp.int32)
    got = glm.model_forward(params, ids, cfg)
    np.testing.assert_allclose(got, ref.forward(params, ids, cut), atol=1e-4,
                               rtol=0)


# ---- the two pools on one block table ------------------------------------------------
def test_the_index_pool_s_blocks_go_with_the_latent_pool_s(rng):
    """One manager, one group, one table: a block id names the same tokens
    in both pools. While a request runs its blocks hold rows in both; when
    it ends every block is free again, in one count."""
    model = glm.GlmMoeDsaForCausalLM(CFG, weights=make_params())
    eng = GlmMoeDsaInferenceEngine(model, max_batch_size=2, num_blocks=9,
                                   block_size=16, max_blocks_per_seq=4)
    mgr = eng.manager
    assert mgr.n_groups == 1 and mgr.group_names == ("latent",)
    assert eng.pools[0].shape[1:3] == eng.pools[1].shape[1:3] == (9, 16)
    assert eng.pools[1].shape[0] == len(CFG.full_layers) == 2
    fe = ServingFrontend(eng, prefill_chunk_tokens=16)
    free = mgr.free_blocks
    h = fe.submit(prompts_of(rng, (40,))[0], max_new_tokens=4)
    while not h.tokens:
        fe.step()
    blocks = mgr.blocks_of(h._req.req_id)
    assert len(blocks) == 3 and mgr.free_blocks == free - 3
    latent, index = (np.asarray(p) for p in eng.pools)
    wrote = lambda pool: {b for b in range(9)                # noqa: E731
                          if np.abs(pool[:, b]).sum() > 0}
    assert set(blocks) <= wrote(latent) and wrote(latent) == wrote(index)
    fe.run_until_idle()
    assert mgr.free_blocks == free
    assert eng.kv_bytes_per_token() == eng.kv_bytes_per_token("latent") \
        + eng.kv_bytes_per_token("index") == (5 * 128 + 2 * 16) * 4
    assert monitor.get("serving.kv_bytes_per_token.index") == 2 * 16 * 4
    assert mgr.bytes_per_block == 16 * (5 * 128 + 2 * 16) * 4


def test_the_witness_replays_what_decode_rows_attended(rng):
    """`attention_witness`: decode positions of live sequences, replayed a
    lane each, attend the reference's selection there (the `full` layers'
    own, the `shared` layers' the carried one: float32, the same set) and
    give the reference's attention output; the replay leaves the caches as
    they were, so the lanes go on to the tokens an undisturbed run serves."""
    params = make_params()
    prompts = prompts_of(rng, (70, 45))

    def start():
        model = glm.GlmMoeDsaForCausalLM(CFG, weights=params)
        eng = GlmMoeDsaInferenceEngine(model, max_batch_size=4,
                                       num_blocks=25, block_size=16,
                                       max_blocks_per_seq=6)
        fe = ServingFrontend(eng, prefill_chunk_tokens=16)
        handles = [fe.submit(p, max_new_tokens=12) for p in prompts]
        while min(len(h.tokens) for h in handles) < 6:
            fe.step()
        return eng, fe, handles

    eng, fe, handles = start()
    # lanes: each sequence at two decode positions, the second lane idle
    asked = [(0, 72), (None, 0), (1, 47), (0, 74)]
    tokens, lens = np.zeros((4,), np.int32), np.zeros((4,), np.int32)
    tables = np.zeros((4, 6), np.int32)
    for lane, (r, pos) in enumerate(asked):
        if r is not None:
            ids = prompts[r] + handles[r].tokens
            tokens[lane], lens[lane] = ids[pos], pos + 1
            tables[lane] = eng.manager.block_table_array(
                [handles[r]._req.seq_id])[0]
    got = eng.attention_witness(tokens, lens, tables)
    assert got["idx"].shape == (5, 4, TOPK) and got["out"].shape == (5, 4, 64)
    f32 = {k: v.astype(jnp.float32) for k, v in params.items()}
    cos, sin = ref.rope_tables(HF, WIDTH)
    for r in (0, 1):
        ids = prompts[r] + handles[r].tokens
        x = jnp.take(f32["model.embed_tokens.weight"], jnp.asarray(
            np.pad(ids, (0, WIDTH - len(ids))), jnp.int32), axis=0)
        carried = None
        for i, kind in enumerate(CFG.indexer_types):
            p = ref.layer_params(f32, i)
            out, chosen = ref.attention(
                ref.rms_norm(x, p["input_layernorm.weight"],
                             HF["rms_norm_eps"]), p, HF, kind, cos, sin,
                carried)
            x, carried = ref.layer(x, p, HF, kind, cos, sin, carried)
            for lane, (who, pos) in enumerate(asked):
                if who != r:
                    continue
                want = np.flatnonzero(np.asarray(
                    ref._unpack(chosen[pos:pos + 1]))[0])
                assert int(got["n"][i, lane]) == TOPK
                assert sorted(got["idx"][i, lane, :TOPK].tolist()) \
                    == want.tolist()
                np.testing.assert_allclose(got["out"][i, lane],
                                           np.asarray(out[pos]), atol=2e-5)
    fe.run_until_idle()
    _, fe2, undisturbed = start()
    fe2.run_until_idle()
    assert [h.tokens for h in handles] == [h.tokens for h in undisturbed]


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["composites", "kernels"])
def test_nothing_reads_a_blank_row(rng, kernels, monkeypatch):
    """ISSUE 49: a guard row of a packed buffer holds whatever (the
    attention's output and a selection's positions past the live tiles,
    what a narrow round leaves past its lanes' rows, a kernel's spare rows).
    With every blank buffer full of NaN (`_support.blank`, the one maker of
    them) the backlog gives the tokens it gave, selects the rows it
    selected, and moves no fault or restart counter; through the XLA
    composites and through the kernels (the interpreter)."""
    from test_live_prefix import FAULTS, poison

    from paddle_tpu.framework import monitor
    from paddle_tpu.ops.pallas import _support

    params, prompts = make_params(), prompts_of(rng, LENGTHS)
    plain, want = serve(params, prompts, 10, engine=GlmMoeDsaInferenceEngine)
    before = {k: monitor.get(k) or 0 for k in FAULTS}
    monkeypatch.setattr(_support, "blank", poison)
    flags.set_flags({"pallas_interpret": kernels})
    try:
        eng, got = serve(params, prompts, 10, engine=GlmMoeDsaInferenceEngine)
    finally:
        flags.set_flags({"pallas_interpret": False})
    assert [h.tokens for h in got] == [h.tokens for h in want]
    assert {k: monitor.get(k) or 0 for k in FAULTS} == before
    assert eng.selection_load() == plain.selection_load()
    assert eng.expert_load()["tokens"].tolist() \
        == plain.expert_load()["tokens"].tolist()


def test_selection_load_counts_live_rows(rng):
    eng, _ = serve(make_params(), prompts_of(rng, (40, 9)), 5,
                   engine=GlmMoeDsaInferenceEngine)
    load = eng.selection_load()
    # two full layers; row t picks min(16, t + 1) of its t + 1 positions
    rows = [t for n in (40, 9) for t in range(n + 4)]
    assert load["candidates"] == 2 * sum(t + 1 for t in rows)
    assert load["selected"] == 2 * sum(min(TOPK, t + 1) for t in rows)
    assert monitor.get("serving.dsa.selected_share") == round(
        load["selected"] / load["candidates"], 4)
    held = eng.expert_load()
    assert held["tokens"][0].sum() == 0, "the dense layer has no experts"
    assert (held["tokens"][1:].sum(axis=1)
            == len(rows) * CFG.num_experts_per_tok).all()


def test_the_step_names_its_regions_and_kernels(interpret):
    """The scopes and kernel names a device trace tells the new work by
    (docs/OBSERVABILITY.md), in the lowered step with the kernels in it."""
    import re

    from paddle_tpu.ops.sampling import step_args

    model = glm.GlmMoeDsaForCausalLM(CFG, weights=make_params())
    eng = GlmMoeDsaInferenceEngine(model, max_batch_size=2, num_blocks=17,
                                   block_size=16, max_blocks_per_seq=8)
    text = eng._ragged.lower(eng.params, eng.pools, eng.counters, *step_args(
        np.zeros((18,), np.int32), np.zeros((2,), np.int32),
        np.zeros((2,), np.int32), np.zeros((2, 8), np.int32))).as_text(
            debug_info=True)
    paths = set(re.findall(r'loc\("([^"]+)"', text))
    parts = {part for path in paths for part in path.split("/")}
    assert {"llama.dsa_index", "llama.dsa_index_q", "llama.dsa_index_write",
            "llama.dsa_index_scores", "llama.dsa_topk", "llama.attn_sparse",
            "llama.mla_q", "llama.kv_write", "llama.moe_experts",
            "dsa_index_scores", "mla_sparse_attention"} <= parts
    assert any("llama.dsa_index/llama.dsa_topk" in p for p in paths)
    # (as a scope's component: a cached jit's frames may name the file)
    assert not [p for p in paths
                if re.search(r"/paged_attention_mla(/|$)", p)]


def test_summary_has_a_selection_line(rng):
    from paddle_tpu.profiler import profiler as prof_mod

    eng, _ = serve(make_params(), prompts_of(rng, (40,)), 3,
                   engine=GlmMoeDsaInferenceEngine)
    eng.selection_load()
    text = "\n".join(prof_mod.Profiler._serving_summary_lines())
    assert "selection:" in text and "(latent + index)" in text


def test_one_step_whatever_the_batch(rng):
    before = monitor.get("serving.ragged_retraces") or 0
    serve(make_params(), prompts_of(rng, LENGTHS), 6)
    assert (monitor.get("serving.ragged_retraces") or 0) - before == 1


# ---- what is served over it, and what is refused by name ---------------------------
def test_the_prefix_cache_shares_blocks_of_both_pools(rng):
    """A second request with the first one's 40-token prefix: its shared
    blocks hold the first one's latent rows AND indexer keys (a copied
    block is copied in both pools), and its logits are the reference's."""
    params = make_params()
    first = prompts_of(rng, (50,))[0]
    second = first[:40] + prompts_of(rng, (20,))[0]
    model = glm.GlmMoeDsaForCausalLM(CFG, weights=params)
    eng = Recording(model, max_batch_size=4, num_blocks=25, block_size=16,
                    max_blocks_per_seq=6)
    fe = ServingFrontend(eng, prefill_chunk_tokens=16, prefix_cache=True)
    eng.slots_of = lambda: fe.scheduler.slots
    handles = []
    for prompt in (first, second):
        handles.append(fe.submit(prompt, max_new_tokens=6))
        fe.run_until_idle()
    assert (monitor.get("serving.prefix_cache.hit_tokens") or 0) >= 32
    rows = [r for r in eng.rows if r[0] == handles[1].request_id]
    assert len(rows) < len(second) + 5, "the shared prefix was not prefilled"
    got = np.stack([r[2] for r in rows])
    want = reference_rows(params, [first, second], handles, rows)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)


def test_a_verify_window_selects_row_by_row(rng):
    """`verify_step` over a window of 4 tokens a lane past contexts several
    times `index_topk`: every row's logits are the reference's."""
    params = make_params()
    model = glm.GlmMoeDsaForCausalLM(CFG, weights=params)
    eng = GlmMoeDsaInferenceEngine(model, max_batch_size=2, num_blocks=13,
                                   block_size=16, max_blocks_per_seq=6)
    seqs = [rng.integers(1, CFG.vocab_size, n) for n in (70, 33)]
    tables = np.arange(12, dtype=np.int32).reshape(2, 6)
    for lane, ids in enumerate(seqs):         # prefill all but the window
        n = len(ids) - 4
        q = np.zeros((2,), np.int32)
        q[lane] = n
        eng.ragged_step(np.pad(ids[:n], (0, 80 - n)).astype(np.int32), q, q,
                        tables)
    window = np.stack([s[-4:] for s in seqs]).astype(np.int32)
    got = np.asarray(eng.verify_step(
        window, np.asarray([len(s) for s in seqs], np.int32), tables))
    for lane, ids in enumerate(seqs):
        padded = np.zeros((WIDTH,), np.int32)
        padded[:len(ids)] = ids
        want = np.asarray(ref.forward(params, jnp.asarray(padded), HF))
        np.testing.assert_allclose(got[lane], want[len(ids) - 4:len(ids)],
                                   atol=1e-4, rtol=0)


def _engine():
    model = glm.GlmMoeDsaForCausalLM(CFG, weights=make_params())
    return GlmMoeDsaInferenceEngine(model, max_batch_size=2, num_blocks=9,
                                    block_size=16, max_blocks_per_seq=4)


def _refusals():
    from paddle_tpu.serving.lora import AdapterError, attach_adapters
    from paddle_tpu.serving.quant import quantize_engine
    from paddle_tpu.serving.tp import ShardingConfigError, shard_engine

    return {
        "quantize_engine": (TypeError, lambda e: quantize_engine(e, 8)),
        "shard_engine": (ShardingConfigError, lambda e: shard_engine(e, tp=2)),
        "attach_adapters": (AdapterError, attach_adapters),
        "kv_migrate.extract": (kv_migrate.KVMigrationError,
                               lambda e: e.extract_kv_blocks(0)),
        "kv_migrate.inject": (kv_migrate.KVMigrationError,
                              lambda e: e.inject_kv_blocks(0, None)),
    }


@pytest.mark.parametrize("transform", [
    "quantize_engine", "shard_engine", "attach_adapters",
    "kv_migrate.extract", "kv_migrate.inject"])
def test_transforms_refuse_the_family_by_name(transform):
    error, call = _refusals()[transform]
    with pytest.raises(error, match="(?i)glm_?moe_?dsa"):
        call(_engine())


def test_config_refuses_what_it_does_not_compute():
    with pytest.raises(ValueError, match="q_lora_rank"):
        glm.GlmMoeDsaConfig.from_hf(dict(HF, q_lora_rank=None))
    with pytest.raises(ValueError, match="indexer_types"):
        glm.GlmMoeDsaConfig.from_hf(dict(HF, indexer_types=["shared"] * 5))
    with pytest.raises(ValueError, match="held experts"):
        glm.GlmMoeDsaConfig.from_hf(dict(HF), held_experts=(6, 4))


def test_kanana_s_query_is_untouched_by_the_q_lora_branch(rng):
    """`mla_query` over Kanana's weights traces the program it traced
    before it could be handed its queries: one projection of its own, two
    results; handed them, it projects nothing."""
    cfg = dsv3.DeepseekV3Config(hidden_size=64, num_attention_heads=4,
                                kv_lora_rank=32, qk_nope_head_dim=16,
                                qk_rope_head_dim=8, v_head_dim=16)
    p = {k: jnp.asarray(rng.normal(size=s), jnp.float32) for k, (s, _)
         in dsv3.layer_shapes(cfg, 0).items()}
    x = jnp.asarray(rng.normal(size=(6, 64)), jnp.float32)
    cos, sin = dsv3.rope_tables(cfg, 6)
    out = dsv3.mla_query(x, p, cfg, cos, sin)
    assert len(out) == 2
    text = jax.jit(lambda x: dsv3.mla_query(x, p, cfg, cos, sin)).lower(
        x).as_text()
    assert text.count("dot_general") == 3       # q, kv_a, the absorb
    q = x @ p["self_attn.q_proj.weight"]
    given = jax.jit(lambda x, q: dsv3.mla_query(x, p, cfg, cos, sin, q))
    assert given.lower(x, q).as_text().count("dot_general") == 2
    for a, b in zip(out, given(x, q)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

