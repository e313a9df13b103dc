"""How the benchmark gets a model of the program's with the seed's weights in
it. Shared by the runners.

The program draws every initial weight on the HOST, in float64 numpy, and
copies it over (`nn/initializer`): 2.9 B parameters took 58-120 s of a run's
set-up on the chip's host, twice as long in one run as in the next (my chip
run, PR 24). The benchmark overwrites every weight anyway, so while it
constructs the model it has the program's default initializer hand out zeros
on the device in the type the weights are served in. What only the program
can mend (a model born on the device, in its type) is in PERF.md.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _zeros_on_device(dtype):
    import jax.numpy as jnp

    from paddle_tpu.nn import initializer

    cls = initializer.XavierUniform
    real = cls.__call__
    cls.__call__ = lambda self, shape, _dtype: jnp.zeros(
        tuple(int(s) for s in shape), dtype)
    try:
        yield
    finally:
        cls.__call__ = real


def llama(cfg: dict, weights_mod, shapes: dict, seed, dtype, train: bool,
          fake_int8: bool = False):
    """`LlamaForCausalLM` at the configuration's sizes holding the seed's
    weights (`weights.make_all`, one jitted call) in `dtype`. The names and
    shapes of the program's parameters must be the reference's."""
    import jax

    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    with _zeros_on_device(dtype):
        model = LlamaForCausalLM(LlamaConfig(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            num_hidden_layers=cfg["num_hidden_layers"],
            num_attention_heads=cfg["num_attention_heads"],
            num_key_value_heads=cfg["num_key_value_heads"],
            max_position_embeddings=cfg["max_position_embeddings"],
            rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
            tie_word_embeddings=cfg["tie_word_embeddings"]))
    model.train() if train else model.eval()
    model.bfloat16()
    named = dict(model.named_parameters())
    have = {k: tuple(v.shape) for k, v in named.items()}
    want = {k: tuple(s) for k, (s, _) in shapes.items()}
    if have != want:
        raise SystemExit("the program's parameters are not the reference's: "
                         f"{sorted(set(have.items()) ^ set(want.items()))}")
    made = weights_mod.make_all(seed, shapes, dtype, fake_int8=fake_int8)
    for k in list(made):
        named[k]._data = made.pop(k)
    jax.block_until_ready([p._data for p in named.values()])
    return model
