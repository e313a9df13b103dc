"""The plain reference of the GLM-MoE-DSA architecture against itself and
against the program, at a small size: its expanded attention over a packed
bit mask is the program's absorbed attention over gathered rows; its pick by
the k-th largest score is the program's `lax.top_k`; applying each held
expert only to the rows routed to it is applying every held expert to every
row; blocks and groups do not change the answer; `check_glm_moe_dsa.
served_gap`, which holds a sub-block's weights at a time, reads what the plain
loop over whole logits reads."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import HERE, load

check = load("check.py", "check")        # as run.py imports it
mine = check.load("check_glm_moe_dsa.py")
ref = mine.ref

with open(os.path.join(HERE, "presets", "tiny-serve_glm_moe_dsa.json")) as f:
    CFG = json.load(f)
SEED = 3000000047


@pytest.fixture(scope="module")
def params():
    return {k: check.weights.make_leaf(SEED, k, s, kind, "float32")
            for k, (s, kind) in ref.param_shapes(CFG).items()}


@pytest.fixture(scope="module")
def ids():
    return jnp.asarray(np.random.default_rng(5).integers(1, 256, 96), jnp.int32)


def test_imports_nothing_of_the_program():
    with open(os.path.join(os.path.dirname(HERE), "reference",
                           "glm_moe_dsa_arch.py")) as f:
        assert "paddle_tpu" not in f.read()


def test_routed_rows_are_every_held_expert_on_every_row(params, ids):
    a = ref.forward(params, ids, CFG)
    b = ref.forward(params, ids, CFG, moe=ref.every_expert_moe)
    np.testing.assert_allclose(a, b, atol=2e-6, rtol=0)


def test_the_reference_is_the_programs_forward(params, ids):
    from paddle_tpu.models import glm_moe_dsa as program

    width, first, count = ref.share(CFG)
    cfg = program.GlmMoeDsaConfig.from_hf(
        dict(CFG, n_routed_experts=width), held_experts=(first, count))
    got = program.model_forward(params, ids, cfg)
    np.testing.assert_allclose(got, ref.forward(params, ids, CFG),
                               atol=2e-5, rtol=0)


@pytest.mark.parametrize("sizes", [
    {"QUERY_BLOCK": 16, "INDEX_BLOCK": 8, "HEAD_GROUP": 2, "ROW_BLOCK": 8,
     "MOE_ROWS": 32},
    # three runs of one 32-query block, each over the keys up to its end
    {"QUERY_BLOCK": 32},
    {"QUERY_BLOCK": 32, "CAUSAL_PARTS": 1}], ids=["small", "parts", "whole"])
def test_blocks_and_groups_do_not_change_the_answer(params, ids, monkeypatch,
                                                    sizes):
    want = ref.forward(params, ids, CFG)
    for name, value in sizes.items():
        monkeypatch.setattr(ref, name, value)
    np.testing.assert_allclose(ref.forward(params, ids, CFG), want,
                               atol=2e-6, rtol=0)


def test_the_selection_bites_and_is_carried(params, ids):
    """With `index_topk` past the sequence the answer is plain causal
    attention's, which the run's 16 of 96 is not; and a `shared` layer that
    attended the most recent 16 in place of the carried set moves it again."""
    want = np.asarray(ref.forward(params, ids, CFG))
    dense = np.asarray(ref.forward(params, ids, dict(CFG, index_topk=96)))
    pos = np.arange(96)
    recent = ref._pack(jnp.asarray((pos[None, :] <= pos[:, None])
                                   & (pos[None, :] > pos[:, None] - 16)))
    moved = np.asarray(ref.forward(params, ids, CFG,
                                   reselect=lambda i, carried: recent))
    first = CFG["index_topk"]           # rows before it select everything
    np.testing.assert_allclose(dense[:first], want[:first], atol=2e-6, rtol=0)
    assert np.abs(dense[first:] - want[first:]).max() > 1e-3
    assert np.abs(moved[first:] - want[first:]).max() > 1e-3


def test_pick_is_the_top_k_of_the_causal_scores():
    rng = np.random.default_rng(1)
    scores = np.round(rng.normal(size=(40, 64)), 1).astype(np.float32)
    qpos = jnp.asarray(rng.integers(0, 64, 40))
    got = np.asarray(ref.pick(jnp.asarray(scores), qpos, 8))
    for r in range(40):
        t = int(qpos[r])
        order = sorted(range(t + 1), key=lambda s: (-scores[r, s], s))
        assert sorted(np.flatnonzero(got[r]).tolist()) == sorted(order[:8])
    back = ref._unpack(ref._pack(jnp.asarray(got)))
    np.testing.assert_array_equal(back, got)


@pytest.mark.parametrize("control", [None, "ref-int8"])
def test_served_gap_reads_what_whole_logits_read(control, monkeypatch):
    monkeypatch.setattr(mine, "HEAD_ROWS", 16)
    rng = np.random.default_rng(0)
    samples = [(rng.integers(1, 256, n).tolist(),
                rng.integers(1, 256, m).tolist()) for n, m in ((30, 20),
                                                               (100, 50))]
    got = mine.served_gap(CFG, SEED, "bfloat16", samples, 128, control)
    worst, total, count = 0.0, 0.0, 0
    for p, t in samples:
        width = -(-(len(p) + len(t)) // 32) * 32       # the preset's step
        ids = np.zeros((1, width), np.int32)
        ids[0, :len(p) + len(t)] = p + t
        rows = np.asarray(mine.reference_logits(CFG, SEED, ids, "bfloat16"))[
            0, len(p) - 1:len(p) - 1 + len(t)]
        toks = np.asarray(t) if control is None else np.asarray(
            mine.reference_logits(CFG, SEED, ids, "bfloat16", "int8"))[
                0, len(p) - 1:len(p) - 1 + len(t)].argmax(-1)
        gap = (rows.max(-1) - rows[np.arange(len(t)), toks]) / rows.std(-1)
        worst, total = max(worst, gap.max()), total + gap.sum()
        count += len(t)
    np.testing.assert_allclose(got, (worst, total / count, count), rtol=1e-4)


def test_compile_ahead_leaves_the_reference_nothing_to_compile(caplog):
    """`compile_ahead` makes, from shapes alone, exactly the programs
    `reference_hidden` then calls at `check.width`: after it a run of the
    reference lowers none of the five again."""
    import logging

    import jax

    cfg = dict(CFG, check=dict(CFG["check"], width=128))
    ids = np.zeros((128,), np.int32)
    ids[:100] = np.arange(1, 101)
    named = ("_attend", "_dense", "_routed", "_shared")

    def lowered():
        return [r.getMessage() for r in caplog.records
                if "Compiling" in r.getMessage()
                and any(n in r.getMessage() for n in named)]

    with jax.log_compiles(True), caplog.at_level(logging.WARNING):
        mine.compile_ahead(cfg, 4)
        assert len(lowered()) == 5, lowered()     # attention of both kinds
        mine.reference_hidden(cfg, SEED, [ids, ids], "bfloat16",
                              watch=[[3, 50, 70, 99]] * 2)
        assert len(lowered()) == 5, lowered()
