"""Seeded weights, made on the device in the type they are served or
trained in. `make_all` is one jitted call for the program's whole state;
`make_leaf` gives the reference the same leaf again, one at a time, so that
neither side takes a weight from the other.

Matrices are N(0, 0.02^2) (the `initializer_range` of both published
configs), norm gains 1 + N(0, 0.02^2). The key is an RBG key: threefry
under the program's x64 mode costs 13-15 s of compile per large array
(PERF.md, PR 21).
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp

STD = 0.02


def _key(seed_lo, seed_hi, name_hash):
    key = jax.random.key(seed_lo, impl="rbg")
    return jax.random.fold_in(jax.random.fold_in(key, seed_hi), name_hash)


def name_hash(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def leaf(seed_lo, seed_hi, hashed, shape, kind, dtype, fake_int8=False):
    """One leaf from (seed, name hash); traceable, so that one compiled
    program serves every leaf of a shape. `make_all` and `make_leaf` both
    come here: the same (seed, name) is the same array whoever asks."""
    key = _key(seed_lo, seed_hi, hashed)
    w = jax.random.normal(key, shape, jnp.float32) * STD
    if kind == "norm":
        w = 1.0 + w
    w = w.astype(dtype)
    if fake_int8:
        # the control: projection weights rounded to a per-output-channel
        # int8 grid, as the program's `weight_only="int8"` engine stores
        # them, and handed over in the served type
        f = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(f), axis=0, keepdims=True) / 127.0
        w = (jnp.clip(jnp.round(f / scale), -127, 127) * scale).astype(dtype)
    return w


def split_seed(seed):
    seed = int(seed)
    if seed < 0:
        raise ValueError("--seed must not be negative")
    return seed & 0x7FFFFFFF, seed >> 31


@functools.partial(jax.jit, static_argnames=("spec", "dtype", "fake_int8"))
def _make_all(seed_lo, seed_hi, spec, dtype, fake_int8):
    return {name: leaf(seed_lo, seed_hi, name_hash(name), shape, kind, dtype,
                       fake_int8 and kind == "matrix" and "_proj." in name)
            for name, shape, kind in spec}


def make_all(seed, shapes, dtype, fake_int8=False):
    """shapes: name -> (shape, kind). One jitted call, every leaf."""
    spec = tuple((n, tuple(s), k) for n, (s, k) in sorted(shapes.items()))
    lo, hi = split_seed(seed)
    return _make_all(lo, hi, spec, jnp.dtype(dtype), bool(fake_int8))


@functools.partial(jax.jit, static_argnames=("shape", "kind", "dtype"))
def _make_leaf(seed_lo, seed_hi, hashed, shape, kind, dtype):
    return leaf(seed_lo, seed_hi, hashed, shape, kind, dtype)


def make_leaf(seed, name, shape, kind, dtype):
    lo, hi = split_seed(seed)
    return _make_leaf(lo, hi, name_hash(name), tuple(shape), kind,
                      jnp.dtype(dtype))
