"""The plain reference against the program's own dense model (`llama_tiny`)
on the CPU in float32, for MHA and for GQA: logits, loss and gradients.
Both sides get the same seeded weights; float32 against float32, so the
tolerance is summation order."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import load

check = load("check.py", "benchmark_check_t")
ref, weights = check.ref, check.weights


def _pair(kv_heads):
    from paddle_tpu.models.llama import llama_tiny

    model = llama_tiny(vocab=256, layers=2, hidden=64, heads=4, seq=64,
                       num_key_value_heads=kv_heads, rms_norm_eps=1e-5)
    cfg = {"hidden_size": 64, "intermediate_size": 192,
           "num_attention_heads": 4, "num_key_value_heads": kv_heads,
           "num_hidden_layers": 2, "vocab_size": 256,
           "rope_theta": 10000.0, "rms_norm_eps": 1e-5}
    shapes = ref.param_shapes(cfg)
    params = weights.make_all(11, shapes, jnp.float32)
    named = dict(model.named_parameters())
    assert {k: tuple(v.shape) for k, v in named.items()} == \
        {k: tuple(s) for k, (s, _) in shapes.items()}
    for k, v in params.items():
        named[k]._data = v
    return model, cfg, params


@pytest.mark.parametrize("kv_heads", [4, 2], ids=["mha", "gqa"])
def test_forward_loss_and_gradients(kv_heads):
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.jit import functional_call, state_arrays

    model, cfg, params = _pair(kv_heads)
    model.eval()
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, 256, (48,)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, 256, (48,)), jnp.int32)

    want = ref.forward(params, ids, cfg)
    got = functional_call(model, state_arrays(model), Tensor(ids[None]))._data[0]
    assert float(jnp.abs(got - want).max()) < 2e-4 * float(jnp.abs(want).max())

    def program_loss(p):
        loss, _ = functional_call(model, p, Tensor(ids[None]),
                                  labels=Tensor(labels[None]))
        return loss._data

    l_ref, g_ref = jax.value_and_grad(
        lambda p: ref.loss(p, ids, labels, cfg, row_block=16))(params)
    l_got, g_got = jax.value_and_grad(program_loss)(state_arrays(model))
    assert abs(float(l_got) - float(l_ref)) < 1e-5 * float(l_ref)
    for k in g_ref:
        scale = float(jnp.abs(g_ref[k]).max())
        assert float(jnp.abs(g_got[k] - g_ref[k]).max()) < 1e-3 * scale, k


def test_leaf_by_leaf_equals_all_at_once():
    """`make_leaf` (the reference's way) gives the leaf `make_all` (the
    program's way) gave, bit for bit, also past 32 signed bits of seed."""
    cfg = {"hidden_size": 64, "intermediate_size": 128,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "num_hidden_layers": 1, "vocab_size": 256}
    shapes = ref.param_shapes(cfg)
    seed = 2**31 + 12345
    whole = weights.make_all(seed, shapes, jnp.bfloat16)
    for name, (shape, kind) in shapes.items():
        one = weights.make_leaf(seed, name, shape, kind, jnp.bfloat16)
        assert bool(jnp.array_equal(one, whole[name])), name
    other = weights.make_all(seed + 1, shapes, jnp.bfloat16)
    assert not bool(jnp.array_equal(other["lm_head.weight"],
                                    whole["lm_head.weight"]))
