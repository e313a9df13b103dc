"""The plain reference of the Cohere2-MoE architecture against itself and
against the program, at a small size: attention a kv head and a block of
queries at a time over the keys a window reaches is attention over every
key at once; applying each held expert only to the rows routed to it is
applying every held expert to every row; `check_cohere2_moe.served_gap`,
a sub-block's weights and a sequence at a time and each sample at its own
width, reads what the plain loop over whole logits reads."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import HERE, load

check = load("check.py", "check")        # as run.py imports it
mine = check.load("check_cohere2_moe.py")
ref = mine.ref

with open(os.path.join(HERE, "presets", "tiny-serve_cohere2_moe.json")) as f:
    CFG = json.load(f)
SEED = 3000000038


@pytest.fixture(scope="module")
def params():
    return {k: check.weights.make_leaf(SEED, k, s, kind, "float32")
            for k, (s, kind) in ref.param_shapes(CFG).items()}


@pytest.fixture(scope="module")
def ids():
    return jnp.asarray(np.random.default_rng(5).integers(1, 256, 192), jnp.int32)


def test_imports_nothing_of_the_program():
    with open(os.path.join(os.path.dirname(HERE), "reference",
                           "cohere2_moe_arch.py")) as f:
        assert "paddle_tpu" not in f.read()


def test_the_preset_is_a_share():
    assert ref.share(CFG) == (8, 2, 4)
    shapes = ref.param_shapes(CFG)
    assert shapes["model.layers.0.mlp.gate.weight"][0] == (64, 8)
    assert shapes["model.layers.0.mlp.experts.up_proj.weight"][0] == (4, 64, 32)
    assert "lm_head.weight" not in shapes                     # tied


def test_blocked_is_unblocked(params, ids, monkeypatch):
    """A window (32) shorter than the sequence (192) and blocks of 16: the
    sliding layers' blocks slice their keys; the dense form sees them all."""
    dense = ref.forward(params, ids, CFG, moe=ref.every_expert_moe,
                        attend=ref.dense_attention)
    for name, value in (("QUERY_BLOCK", 16), ("ROW_BLOCK", 8),
                        ("MOE_ROWS", 32)):
        monkeypatch.setattr(ref, name, value)
    np.testing.assert_allclose(ref.forward(params, ids, CFG), dense,
                               atol=3e-6, rtol=0)
    # and the window matters at this size: with it as wide as the sequence
    # the logits move
    wide = ref.forward(params, ids, dict(CFG, sliding_window=192))
    assert np.abs(np.asarray(wide) - np.asarray(dense))[40:].max() > 1e-3


def test_the_program_computes_the_reference(params, ids):
    from paddle_tpu.models import cohere2_moe as program

    width, first, count = ref.share(CFG)
    cfg = program.Cohere2MoeConfig.from_hf(dict(CFG, num_experts=width),
                                           held_experts=(first, count))
    np.testing.assert_allclose(program.model_forward(params, ids, cfg),
                               ref.forward(params, ids, CFG),
                               atol=2e-5, rtol=0)


@pytest.mark.parametrize("control", [None, "ref-int8"])
def test_served_gap_reads_what_whole_logits_read(control, monkeypatch):
    monkeypatch.setattr(mine, "HEAD_ROWS", 16)
    cfg = dict(CFG, check=dict(CFG["check"], width_step=64))
    rng = np.random.default_rng(0)
    samples = [(rng.integers(1, 256, n).tolist(),
                rng.integers(1, 256, m).tolist())
               for n, m in ((30, 20), (100, 50), (9, 3))]
    got = mine.served_gap(cfg, SEED, "bfloat16", samples, 128, control)
    ids = np.zeros((3, 192), np.int32)
    for r, (p, t) in enumerate(samples):
        ids[r, :len(p) + len(t)] = p + t
    logits = np.asarray(mine.reference_logits(cfg, SEED, ids, "bfloat16"))
    low = None if control is None else np.asarray(
        mine.reference_logits(cfg, SEED, ids, "bfloat16", "int8"))
    worst, total, count = 0.0, 0.0, 0
    for r, (p, t) in enumerate(samples):
        rows = logits[r, len(p) - 1:len(p) - 1 + len(t)]
        toks = np.asarray(t) if low is None else \
            low[r, len(p) - 1:len(p) - 1 + len(t)].argmax(-1)
        gap = (rows.max(-1) - rows[np.arange(len(t)), toks]) / rows.std(-1)
        worst, total = max(worst, gap.max()), total + gap.sum()
        count += len(t)
    np.testing.assert_allclose(got, (worst, total / count, count), rtol=1e-4)
    # each sample went through at its own width: 64, 192, 64
    assert [len(r) for r in mine._padded(cfg, samples, 128)] == [64, 192, 64]


def test_reference_holds_one_sub_block_and_one_sequence(monkeypatch):
    """While the layers run the device holds one sequence's state (its
    rows and their normed copy) and never a second sequence's, nor two
    sub-blocks' weights at once."""
    import jax

    cfg = dict(CFG, check=dict(CFG["check"], width_step=64))
    rng = np.random.default_rng(1)
    rows = [rng.integers(1, 256, n).astype(np.int32) for n in (192, 320, 192)]
    h = CFG["hidden_size"]

    def count(shape):
        return sum(a.shape == shape for a in jax.live_arrays())

    before = {n: count((n, h)) for n in (192, 320)}   # no weight's shape
    blocks = (mine.ref.ATTENTION, mine.ref.ROUTED, mine.ref.SHARED)
    shapes = {s[0] for k, s in ref.param_shapes(cfg).items() if ".layers." in k}
    real, seen = mine.weights.make_leaf, []

    def make_leaf(seed, name, shape, kind, dtype):
        if ".layers." in name and "layernorm" not in name:
            states = [count((n, h)) - before[n] for n in (192, 320)]
            assert sorted(states)[0] == 0 and sum(states) <= 2, \
                "a second sequence's hidden state on the device"
            mine_now = next(b for b in blocks if name.split(".", 3)[3] in b)
            if name.split(".", 3)[3] == mine_now[0]:       # a block's first
                held = [a for a in jax.live_arrays() if a.shape in shapes
                        and a.ndim > 1 and a.dtype == jnp.bfloat16]
                assert not held, "another sub-block's weights still held"
            seen.append(name)
        return real(seed, name, shape, kind, dtype)

    monkeypatch.setattr(mine.weights, "make_leaf", make_leaf)
    out = mine.reference_hidden(cfg, SEED, rows, "bfloat16")
    assert all(isinstance(x, np.ndarray) for x in out)
    assert [x.shape for x in out] == [(192, h), (320, h), (192, h)]
    assert len(seen) == len(rows) * CFG["num_hidden_layers"] * 11
