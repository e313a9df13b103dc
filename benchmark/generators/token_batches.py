"""The one general generator of training batches: `batch` x `seq` token ids
and their shifted labels, made on the device from the seed and the step
number, so that every step's rows differ and no host pipeline is needed.
(The program has no input pipeline in its measured step today; PERF.md, Open
questions.)

    {"generator": "token_batches", "batch": 1, "seq": 4096}
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("batch", "seq", "vocab"))
def _batch(seed_lo, seed_hi, step, batch, seq, vocab):
    key = jax.random.key(seed_lo, impl="rbg")
    key = jax.random.fold_in(jax.random.fold_in(key, seed_hi), step)
    toks = jax.random.randint(key, (batch, seq + 1), 0, vocab, jnp.int32)
    return toks[:, :-1], toks[:, 1:]


def make(traffic: dict, seed: int, vocab_size: int):
    """-> batch_of(step) giving (ids, labels), each [batch, seq] int32."""
    seed = int(seed)
    lo, hi = seed & 0x7FFFFFFF, seed >> 31

    def batch_of(step: int):
        return _batch(lo, hi, int(step), traffic["batch"], traffic["seq"],
                      vocab_size)

    return batch_of
