"""The plain reference of the Brumby architecture against itself and against
the program, at a small size: retention a KV head and a block of queries at a
time is retention over every head and query at once; `check_brumby`'s walk (a
sub-block's weights and a sequence at a time, each sample at its own width)
reads what the plain loop over whole logits reads; the gate biases it makes
are slow; the program's model, which runs the recurrent and chunked forms
over a state, gives the reference's logits."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import HERE, load

check = load("check.py", "check")        # as run.py imports it
mine = check.load("check_brumby.py")
ref = mine.ref

with open(os.path.join(HERE, "presets", "tiny-serve_brumby.json")) as f:
    CFG = json.load(f)
SEED = 3000000045


@pytest.fixture(scope="module")
def params():
    return {k: mine.leaf(SEED, k, s, kind, "float32")
            for k, (s, kind) in ref.param_shapes(CFG).items()}


@pytest.fixture(scope="module")
def ids():
    return jnp.asarray(np.random.default_rng(5).integers(1, 256, 192), jnp.int32)


def test_imports_nothing_of_the_program():
    with open(os.path.join(os.path.dirname(HERE), "reference",
                           "brumby_arch.py")) as f:
        assert "paddle_tpu" not in f.read()


def test_the_gates_are_slow(params):
    """Every head's half-life (at a zero gate input) lies in 64-4,096
    tokens, and the draws differ: a state is carried for hundreds of tokens."""
    biases = np.concatenate([np.asarray(v) for k, v in params.items()
                             if k.endswith("g_proj.bias")])
    half = np.log(0.5) / np.log(np.asarray(jax.nn.sigmoid(biases), np.float64))
    assert half.min() >= 63.9 and half.max() <= 4097 and len(set(half)) > 2


def test_blocked_is_unblocked(params, ids, monkeypatch):
    dense = ref.forward(params, ids, CFG, retain=ref.dense_retention)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 16)
    monkeypatch.setattr(ref, "MLP_ROWS", 32)
    np.testing.assert_allclose(ref.forward(params, ids, CFG), dense,
                               atol=1e-5, rtol=0)


def test_the_check_s_walk_is_the_forward(params, ids):
    rows = np.stack([np.asarray(ids), np.asarray(ids)[::-1]])
    got = mine.reference_logits(CFG, SEED, rows, "float32")
    for r in range(2):
        np.testing.assert_allclose(
            got[r], ref.forward(params, jnp.asarray(rows[r]), CFG),
            atol=1e-5, rtol=0)


def test_the_state_is_carried_far(params, ids):
    """What the comparison can see: a token 150 positions back still moves
    the last row's logits (with a zero gate bias it would not)."""
    base = ref.forward(params, ids, CFG)[-1]
    moved = ref.forward(params, ids.at[40].set((ids[40] + 1) % 256), CFG)[-1]
    assert float(jnp.abs(base - moved).max()) > 1e-3 * float(base.std())
    fast = dict(params)
    for k in params:
        if k.endswith("g_proj.bias"):
            fast[k] = jnp.zeros_like(params[k])
    base = ref.forward(fast, ids, CFG)[-1]
    moved = ref.forward(fast, ids.at[40].set((ids[40] + 1) % 256), CFG)[-1]
    assert float(jnp.abs(base - moved).max()) < 1e-5 * float(base.std())


def test_the_int8_control_moves_the_logits(params, ids):
    full = ref.forward(params, ids, CFG)
    low = ref.forward(params, ids, CFG, quant="int8")
    assert float(jnp.abs(full - low).max()) > 0.01 * float(full.std())
    with pytest.raises(ValueError):
        ref.matmul(full, full.T, "int4")


def test_the_program_s_model_is_the_reference(params, ids):
    from paddle_tpu.models import brumby as bm

    config = bm.BrumbyConfig.from_hf(CFG)
    assert {k: tuple(s) for k, (s, _) in bm.param_shapes(config).items()} \
        == {k: tuple(s) for k, (s, _) in ref.param_shapes(CFG).items()}
    got = bm.model_forward(params, ids, config)
    np.testing.assert_allclose(got, ref.forward(params, ids, CFG), atol=2e-4,
                               rtol=0)
