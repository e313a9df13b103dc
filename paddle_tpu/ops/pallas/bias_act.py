"""Pallas fused bias+activation and SwiGLU.

Reference kernels: `paddle/phi/kernels/fusion/gpu/fused_bias_act_kernel.cu`
and the swiglu op (`python/paddle/incubate/nn/functional/swiglu`). One HBM
pass: add bias, apply activation (and the GLU product for swiglu/geglu).
Backward recomputes through the plain-XLA reference (fuses fine).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import _support

def _erf_approx(x):
    # Mosaic has no erf/erfc primitive; Abramowitz-Stegun 7.1.26 rational
    # approximation (|err| < 1.5e-7, below bf16/f32-accum noise) using only
    # exp, which Mosaic lowers natively.
    a1, a2, a3 = 0.254829592, -0.284496736, 1.421413741
    a4, a5, p = -1.453152027, 1.061405429, 0.3275911
    # not jnp.sign: without a newer libtpu Pallas lowers it through a helper
    # whose constants come out f64 under the package's x64 mode (the value
    # at 0 is immaterial: the bracket below vanishes there)
    s = jnp.where(x < 0, -1.0, 1.0)
    ax = jnp.abs(x)
    t = 1.0 / (1.0 + p * ax)
    poly = t * (a1 + t * (a2 + t * (a3 + t * (a4 + t * a5))))
    return s * (1.0 - poly * jnp.exp(-ax * ax))


def _gelu_erf(x):
    # jax.nn.gelu(approximate=False) lowers via erfc, which Mosaic cannot
    # compile; the erf formulation is mathematically identical.
    return x * 0.5 * (1.0 + _erf_approx(x * jnp.float32(0.7071067811865476)))


_ACTS = {
    "gelu": _gelu_erf,
    "gelu_tanh": lambda x: jax.nn.gelu(x, approximate=True),
    "relu": lambda x: jnp.maximum(x, 0),
    "silu": jax.nn.silu,
    "swish": jax.nn.silu,
    "sigmoid": jax.nn.sigmoid,
    "identity": lambda x: x,
}


def _ref_bias_act(x, bias, act_method):
    xf = x.astype(jnp.float32) + bias.astype(jnp.float32)
    if act_method in ("swiglu", "geglu"):
        a, b = jnp.split(xf, 2, axis=-1)
        inner = _ACTS["silu" if act_method == "swiglu" else "gelu"](a)
        return (inner * b).astype(x.dtype)
    return _ACTS[act_method](xf).astype(x.dtype)


def _blocks(r, hdim):
    """(row, hidden) block of an elementwise pass over [r, hdim]. The
    hidden axis is tiled too: a row block times the whole axis does not
    fit scoped VMEM at real widths ([256, 11008] blocks of a SwiGLU need
    32 MiB against the 16 MiB limit). The lane block must be a multiple
    of 128 or the whole axis, so small/odd widths stay untiled."""
    bh = _support.pick_block(hdim, 1024)
    return (_support.pick_block(r, 256) or r,
            bh if bh and bh % 128 == 0 else hdim)


def _kernel(*refs, act_method):
    """refs: x blocks, then their bias rows, then y — one x/bias pair,
    or the two matching half-blocks of a packed GLU input."""
    *ins, y_ref = refs
    n = len(ins) // 2
    xs = [x[:].astype(jnp.float32) + b[:].astype(jnp.float32)
          for x, b in zip(ins[:n], ins[n:])]
    if n == 2:
        inner = _ACTS["silu" if act_method == "swiglu" else "gelu"](xs[0])
        y_ref[:] = (inner * xs[1]).astype(y_ref.dtype)
    else:
        y_ref[:] = _ACTS[act_method](xs[0]).astype(y_ref.dtype)


def _pallas_bias_act(x2d, bias, act_method):
    r, hdim = x2d.shape
    glu = act_method in ("swiglu", "geglu")
    out_h = hdim // 2 if glu else hdim
    br, bh = _blocks(r, out_h)
    # a GLU reads block j of each half of the packed axis: the same
    # arrays twice, the second index map offset by the half's block count
    offs = (0, out_h // bh) if glu else (0,)
    return _support.pallas_call(
        functools.partial(_kernel, act_method=act_method),
        grid=(pl.cdiv(r, br), out_h // bh),
        in_specs=[pl.BlockSpec((br, bh), lambda i, j, o=o: (i, j + o))
                  for o in offs]
        # bias as a [1, H] row: a 1-D (bh,) block is tiled T(bh) by Mosaic
        # against XLA's own 1-D layout and refused
        + [pl.BlockSpec((1, bh), lambda i, j, o=o: (0, j + o))
           for o in offs],
        out_specs=pl.BlockSpec((br, bh), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, out_h), x2d.dtype),
        name="bias_act_fused",
        interpret=_support.interpret_mode(),
    )(*([x2d] * len(offs) + [bias.reshape(1, hdim)] * len(offs)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _bias_act2d(x2d, bias, act_method):
    return _pallas_bias_act(x2d, bias, act_method)


def _ba_fwd(x2d, bias, act_method):
    return _pallas_bias_act(x2d, bias, act_method), (x2d, bias)


def _ba_bwd(act_method, res, g):
    x2d, bias = res
    _, vjp = jax.vjp(lambda x, b: _ref_bias_act(x, b, act_method), x2d, bias)
    return vjp(g)


_bias_act2d.defvjp(_ba_fwd, _ba_bwd)


def fused_bias_act(x, bias=None, act_method="gelu"):
    """Raw-array fused bias+act over the last axis."""
    shape = x.shape
    x2d = x.reshape(-1, shape[-1])
    if bias is None:
        bias = jnp.zeros((shape[-1],), x.dtype)
    y = _bias_act2d(x2d, bias, act_method)
    return y.reshape(shape[:-1] + (y.shape[-1],))


def _kernel2(x_ref, y_ref, o_ref):
    a = x_ref[:].astype(jnp.float32)
    b = y_ref[:].astype(jnp.float32)
    o_ref[:] = (jax.nn.silu(a) * b).astype(o_ref.dtype)


def _pallas_swiglu2(x2d, y2d):
    r, hdim = x2d.shape
    br, bh = _blocks(r, hdim)
    spec = pl.BlockSpec((br, bh), lambda i, j: (i, j))
    return _support.pallas_call(
        _kernel2,
        grid=(pl.cdiv(r, br), hdim // bh),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((r, hdim), x2d.dtype),
        name="bias_act_swiglu2",
        interpret=_support.interpret_mode(),
    )(x2d, y2d)


@jax.custom_vjp
def _swiglu2(x2d, y2d):
    return _pallas_swiglu2(x2d, y2d)


def _sw2_fwd(x2d, y2d):
    return _pallas_swiglu2(x2d, y2d), (x2d, y2d)


def _sw2_bwd(res, g):
    x2d, y2d = res
    xf = x2d.astype(jnp.float32)
    yf = y2d.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    sig = jax.nn.sigmoid(xf)
    silu = xf * sig
    dx = gf * yf * (sig * (1 + xf * (1 - sig)))
    dy = gf * silu
    return dx.astype(x2d.dtype), dy.astype(y2d.dtype)


_swiglu2.defvjp(_sw2_fwd, _sw2_bwd)


def swiglu(x, y=None):
    """silu(x) * y; packed form splits x's last axis when y is None.
    Two-tensor form reads both inputs in place — no concat copy."""
    if y is None:
        return fused_bias_act(x, None, "swiglu")
    shape = x.shape
    out = _swiglu2(x.reshape(-1, shape[-1]), y.reshape(-1, shape[-1]))
    return out.reshape(shape)
