"""Pallas fused RMSNorm (reference: `paddle/phi/kernels/gpu/rms_norm_kernel.cu`).

Forward is a single VMEM-resident kernel (one HBM read + one write per
element); backward recomputes the normalisation in plain XLA — it is
bandwidth-bound elementwise math that XLA fuses into adjacent matmuls anyway.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import _support


def _rms_fwd_kernel(x_ref, w_ref, y_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    # cast again after the weight: an f32 weight on bf16 rows (autocast)
    # would otherwise promote the product past the output ref's dtype
    y_ref[:] = ((x * inv).astype(y_ref.dtype) * w_ref[:]).astype(y_ref.dtype)


def _pallas_fwd(x2d, w, eps):
    r, hdim = x2d.shape
    br = _support.row_block(r, hdim * x2d.dtype.itemsize)
    return _support.pallas_call(
        functools.partial(_rms_fwd_kernel, eps=eps),
        grid=(pl.cdiv(r, br),),
        in_specs=[
            pl.BlockSpec((br, hdim), lambda i: (i, 0)),
            pl.BlockSpec((hdim,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, hdim), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, hdim), x2d.dtype),
        name="rms_norm",
        interpret=_support.interpret_mode(),
    )(x2d, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms2d(x2d, w, eps):
    return _pallas_fwd(x2d, w, eps)


def _rms_fwd_rule(x2d, w, eps):
    return _pallas_fwd(x2d, w, eps), (x2d, w)


def _rms_bwd_rule(eps, res, g):
    x2d, w = res
    xf = x2d.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    n = xf * inv
    gh = gf * w.astype(jnp.float32)
    dx = inv * (gh - n * jnp.mean(gh * n, axis=-1, keepdims=True))
    dw = jnp.sum(gf * n, axis=0)
    return dx.astype(x2d.dtype), dw.astype(w.dtype)


_rms2d.defvjp(_rms_fwd_rule, _rms_bwd_rule)


def rms_norm(x, w, epsilon=1e-6):
    """Raw-array fused rms_norm over the last axis; any leading shape."""
    shape = x.shape
    y = _rms2d(x.reshape(-1, shape[-1]), w, float(epsilon))
    return y.reshape(shape)


def supported(shape, dtype) -> bool:
    if len(shape) < 2:
        return False
    return _support.float_dtype_ok(dtype)
