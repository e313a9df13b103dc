"""Pallas fused rotary position embedding.

Reference: `paddle/phi/kernels/fusion/gpu/fused_rope_kernel.cu` (python surface
`incubate.nn.functional.fused_rotary_position_embedding`). One kernel rotates
q and k together — a single HBM pass instead of the 8+ elementwise ops the
unfused form costs. The backward is the transposed rotation, i.e. the same
kernel with the sine negated (`conj=True`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import _support


def _rope_kernel(q_ref, k_ref, c_ref, s_ref, oq_ref, ok_ref, *, conj):
    c = c_ref[:][:, None, :].astype(jnp.float32)   # (bs, 1, D/2)
    s = s_ref[:][:, None, :].astype(jnp.float32)
    if conj:
        s = -s
    for ref, out in ((q_ref, oq_ref), (k_ref, ok_ref)):
        x = ref[0].astype(jnp.float32)             # (bs, H, D)
        d2 = x.shape[-1] // 2
        x1, x2 = x[..., :d2], x[..., d2:]
        out[0] = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                                 axis=-1).astype(out.dtype)


def _pallas_rope(q, k, cos, sin, conj):
    b, s, h, d = q.shape
    bs = _support.row_block(s, h * d * q.dtype.itemsize)
    return _support.pallas_call(
        functools.partial(_rope_kernel, conj=conj),
        grid=(b, s // bs),
        in_specs=[
            pl.BlockSpec((1, bs, h, d), lambda b_, i: (b_, i, 0, 0)),
            pl.BlockSpec((1, bs, h, d), lambda b_, i: (b_, i, 0, 0)),
            pl.BlockSpec((bs, d // 2), lambda b_, i: (i, 0)),
            pl.BlockSpec((bs, d // 2), lambda b_, i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bs, h, d), lambda b_, i: (b_, i, 0, 0)),
            pl.BlockSpec((1, bs, h, d), lambda b_, i: (b_, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
        ],
        name="fused_rope",
        interpret=_support.interpret_mode(),
    )(q, k, cos, sin)


@jax.custom_vjp
def _rope(q, k, cos, sin):
    oq, ok = _pallas_rope(q, k, cos, sin, conj=False)
    return oq, ok


def _rope_fwd_rule(q, k, cos, sin):
    return _pallas_rope(q, k, cos, sin, conj=False), (cos, sin)


def _rope_bwd_rule(res, g):
    cos, sin = res
    gq, gk = g
    dq, dk = _pallas_rope(gq, gk, cos, sin, conj=True)
    return dq, dk, None, None


_rope.defvjp(_rope_fwd_rule, _rope_bwd_rule)


def fused_rope(q, k, cos, sin, offset=0):
    """q/k: [B, S, H, D]; cos/sin: [T, D/2] rotation tables."""
    s = q.shape[1]
    return _rope(q, k, cos[offset:offset + s], sin[offset:offset + s])


def supported(q_shape, dtype) -> bool:
    if len(q_shape) != 4 or q_shape[-1] % 2:
        return False
    return _support.float_dtype_ok(dtype)
