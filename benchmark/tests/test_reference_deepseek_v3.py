"""The plain reference of the DeepSeek-V3 architecture against itself and
against the program, at a small size: its expanded attention is the
program's absorbed attention; applying each expert only to the rows routed
to it is applying every expert to every row; `check_deepseek_v3.served_gap`,
which never holds a sequence's logits, reads what the plain loop over whole
logits reads."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import HERE, load

check = load("check.py", "check")        # as run.py imports it
mine = check.load("check_deepseek_v3.py")
ref = mine.ref

with open(os.path.join(HERE, "presets", "tiny-serve_deepseek_v3.json")) as f:
    CFG = json.load(f)
SEED = 3000000031


@pytest.fixture(scope="module")
def params():
    return {k: check.weights.make_leaf(SEED, k, s, kind, "float32")
            for k, (s, kind) in ref.param_shapes(CFG).items()}


@pytest.fixture(scope="module")
def ids():
    return jnp.asarray(np.random.default_rng(5).integers(1, 256, 96), jnp.int32)


def test_imports_nothing_of_the_program():
    with open(os.path.join(os.path.dirname(HERE), "reference",
                           "deepseek_v3_arch.py")) as f:
        assert "paddle_tpu" not in f.read()


def test_routed_rows_are_every_expert_on_every_row(params, ids):
    a = ref.forward(params, ids, CFG)
    b = ref.forward(params, ids, CFG, moe=ref.every_expert_moe)
    np.testing.assert_allclose(a, b, atol=2e-6, rtol=0)


def test_expanded_attention_is_the_programs_absorbed_form(params, ids):
    from paddle_tpu.models import deepseek_v3 as program

    got = program.model_forward(
        params, ids, program.DeepseekV3Config.from_hf(CFG))
    np.testing.assert_allclose(got, ref.forward(params, ids, CFG),
                               atol=2e-5, rtol=0)


def test_blocks_and_groups_do_not_change_the_answer(params, ids, monkeypatch):
    want = ref.forward(params, ids, CFG)
    for name, value in (("QUERY_BLOCK", 16), ("HEAD_GROUP", 2),
                        ("ROW_BLOCK", 8), ("MOE_ROWS", 32)):
        monkeypatch.setattr(ref, name, value)
    np.testing.assert_allclose(ref.forward(params, ids, CFG), want,
                               atol=2e-6, rtol=0)


@pytest.mark.parametrize("control", [None, "ref-int8"])
def test_served_gap_reads_what_whole_logits_read(control, monkeypatch):
    monkeypatch.setattr(mine, "VOCAB_CHUNK", 64)
    monkeypatch.setattr(mine, "HEAD_ROWS", 16)
    rng = np.random.default_rng(0)
    samples = [(rng.integers(1, 256, n).tolist(),
                rng.integers(1, 256, m).tolist()) for n, m in ((30, 20),
                                                               (100, 50))]
    got = mine.served_gap(CFG, SEED, "bfloat16", samples, 128, control)
    ids = np.zeros((2, 256), np.int32)
    for r, (p, t) in enumerate(samples):
        ids[r, :len(p) + len(t)] = p + t
    logits = np.asarray(mine.reference_logits(CFG, SEED, ids, "bfloat16"))
    low = None if control is None else np.asarray(
        mine.reference_logits(CFG, SEED, ids, "bfloat16", "int8"))
    worst, total, count = 0.0, 0.0, 0
    for r, (p, t) in enumerate(samples):
        rows = logits[r, len(p) - 1:len(p) - 1 + len(t)]
        toks = np.asarray(t) if low is None else \
            low[r, len(p) - 1:len(p) - 1 + len(t)].argmax(-1)
        gap = (rows.max(-1) - rows[np.arange(len(t)), toks]) / rows.std(-1)
        worst, total = max(worst, gap.max()), total + gap.sum()
        count += len(t)
    np.testing.assert_allclose(got, (worst, total / count, count), rtol=1e-4)


def _parent_reference_hidden(cfg, seed, ids, dtype, quant=None):
    """`reference_hidden` as the parent (PR 30's tree) had it, verbatim but
    for the print: every sampled sequence's hidden state on the device at
    once, in and out of each layer."""
    import jax

    shapes = ref.param_shapes(cfg)
    cos, sin = ref.rope_tables(cfg, ids.shape[1])
    embed = mine._leaf(seed, dtype, "model.embed_tokens.weight", shapes)
    xs = [jnp.take(embed, jnp.asarray(row), axis=0).astype(jnp.float32)
          for row in ids]
    del embed
    step = jax.jit(lambda x, p: ref.layer(x, p, cfg, cos, sin, quant,
                                          flips=True))
    for i in range(cfg["num_hidden_layers"]):
        p = {k: mine._leaf(seed, dtype, f"model.layers.{i}.{k}", shapes)
             for k in ref.layer_shapes(cfg, i)}
        xs, _ = zip(*(step(x, p) for x in xs))
        del p
    return list(xs)


@pytest.mark.parametrize("control", [None, "ref-int8"])
def test_served_gap_is_the_parents_to_the_last_bit_and_holds_less(
        control, monkeypatch):
    """Hidden states on the host between layers, one sequence on the device
    at a time and the head's weights made after the last layer: the three
    numbers are the parent's, bit for bit, on samples of unequal length; and
    while `reference_hidden` runs the device holds no array of the head's
    shape, and no sequence's hidden state whenever a layer's weights are
    made."""
    import jax

    monkeypatch.setattr(mine, "VOCAB_CHUNK", 64)
    monkeypatch.setattr(mine, "HEAD_ROWS", 16)
    rng = np.random.default_rng(1)
    samples = [(rng.integers(1, 256, n).tolist(),
                rng.integers(1, 256, m).tolist())
               for n, m in ((30, 20), (100, 50), (70, 90), (9, 3))]
    width, h, v = 256, CFG["hidden_size"], CFG["vocab_size"]

    def count(shape):
        return sum(a.shape == shape for a in jax.live_arrays())

    # what other tests' fixtures hold (their `lm_head.weight` among it)
    before = {s: count(s) for s in ((h, v), (width, h))}

    def live(shape):
        return count(shape) - before[shape]

    real_hidden, real_leaf = mine.reference_hidden, mine._leaf
    inside, seen = [False], {"layers": 0}

    def leaf(seed, dtype, name, shapes):
        if inside[0] and ".layers." in name:
            assert live((h, v)) == 0, "the head's weights beside a layer's"
            assert live((width, h)) == 0, "a hidden state left on the device"
            seen["layers"] += 1
        return real_leaf(seed, dtype, name, shapes)

    def hidden(*a, **k):
        assert live((h, v)) == 0, "the head's weights made before the layers"
        inside[0] = True
        out = real_hidden(*a, **k)
        inside[0] = False
        assert live((h, v)) == 0 and live((width, h)) == 0
        assert all(isinstance(x, np.ndarray) for x in out)
        return out

    monkeypatch.setattr(mine, "_leaf", leaf)
    monkeypatch.setattr(mine, "reference_hidden", hidden)
    got = mine.served_gap(CFG, SEED, "bfloat16", samples, 128, control)
    assert seen["layers"] >= CFG["num_hidden_layers"]

    monkeypatch.setattr(mine, "_leaf", real_leaf)
    monkeypatch.setattr(mine, "reference_hidden", _parent_reference_hidden)
    want = mine.served_gap(CFG, SEED, "bfloat16", samples, 128, control)
    assert got == want, (got, want)          # floats compared exactly
    assert got[2] == 20 + 50 + 90 + 3 and got[1] > 0
