"""Normalization functionals: batch/layer/group/instance/rms/local-response norm.

Analog of `python/paddle/nn/functional/norm.py`. The reference uses cuDNN
batch-norm + a hand-fused rms_norm CUDA kernel (`phi/kernels/gpu/rms_norm_kernel.cu`);
here each norm is a composite that XLA fuses into surrounding ops; rms_norm
additionally has a Pallas fast path (paddle_tpu/ops/pallas/) used when available.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from ...core import dispatch
from ...core.tensor import Tensor
from ...ops._helpers import as_tensor

__all__ = ["batch_norm", "layer_norm", "group_norm", "instance_norm",
           "local_response_norm", "normalize", "rms_norm"]


def _bn_train_fn(x, mean, var, w, b, momentum, epsilon, data_format):
    import jax.numpy as jnp

    c_axis = 1 if data_format.startswith("NC") and x.ndim > 1 else x.ndim - 1
    axes = tuple(i for i in range(x.ndim) if i != c_axis)
    batch_mean = x.mean(axis=axes)
    batch_var = ((x - _bshape(batch_mean, x, c_axis)) ** 2).mean(axis=axes)
    inv = 1.0 / jnp.sqrt(batch_var + epsilon)
    y = (x - _bshape(batch_mean, x, c_axis)) * _bshape(inv, x, c_axis)
    if w is not None:
        y = y * _bshape(w, x, c_axis)
    if b is not None:
        y = y + _bshape(b, x, c_axis)
    n = np.prod([x.shape[i] for i in axes])
    unbiased = batch_var * (n / max(n - 1, 1))
    new_mean = momentum * mean + (1 - momentum) * batch_mean
    new_var = momentum * var + (1 - momentum) * unbiased
    return y, new_mean, new_var


def _bn_eval_fn(x, mean, var, w, b, epsilon, data_format):
    import jax.numpy as jnp

    c_axis = 1 if data_format.startswith("NC") and x.ndim > 1 else x.ndim - 1
    inv = 1.0 / jnp.sqrt(var + epsilon)
    y = (x - _bshape(mean, x, c_axis)) * _bshape(inv, x, c_axis)
    if w is not None:
        y = y * _bshape(w, x, c_axis)
    if b is not None:
        y = y + _bshape(b, x, c_axis)
    return y


def _bshape(v, x, c_axis):
    shape = [1] * x.ndim
    shape[c_axis] = v.shape[0]
    return v.reshape(shape)


dispatch.register_op("batch_norm_train", _bn_train_fn, multi_out=True)
dispatch.register_op("batch_norm_eval", _bn_eval_fn)


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5, data_format="NCHW",
               use_global_stats=None, name=None):
    x = as_tensor(x)
    rm, rv = as_tensor(running_mean), as_tensor(running_var)
    w = as_tensor(weight) if weight is not None else None
    b = as_tensor(bias) if bias is not None else None
    if use_global_stats is None:
        use_global_stats = not training
    if training and not use_global_stats:
        args = [x, rm, rv] + ([w] if w is not None else []) + \
            ([b] if b is not None else [])

        # register variants lazily for the none-weight cases
        key = ("batch_norm_train", w is not None, b is not None)
        opname = _bn_variant(key)
        outs = dispatch.apply(opname, args,
                              {"momentum": float(momentum),
                               "epsilon": float(epsilon),
                               "data_format": data_format})
        y, new_mean, new_var = outs
        # update running stats in-place (buffers)
        running_mean._data = new_mean._data if isinstance(new_mean, Tensor) else new_mean
        running_var._data = new_var._data if isinstance(new_var, Tensor) else new_var
        return y
    args = [x, rm, rv] + ([w] if w is not None else []) + \
        ([b] if b is not None else [])
    opname = _bn_variant(("batch_norm_eval", w is not None, b is not None))
    return dispatch.apply(opname, args, {"epsilon": float(epsilon),
                                         "data_format": data_format})


_bn_variants = {}


def _bn_variant(key):
    name, has_w, has_b = key
    if has_w and has_b:
        return name
    vname = f"{name}_w{int(has_w)}b{int(has_b)}"
    if vname not in _bn_variants:
        if name == "batch_norm_train":
            if has_w:
                fn = lambda x, m, v, w, momentum, epsilon, data_format: \
                    _bn_train_fn(x, m, v, w, None, momentum, epsilon, data_format)
            elif has_b:
                fn = lambda x, m, v, b, momentum, epsilon, data_format: \
                    _bn_train_fn(x, m, v, None, b, momentum, epsilon, data_format)
            else:
                fn = lambda x, m, v, momentum, epsilon, data_format: \
                    _bn_train_fn(x, m, v, None, None, momentum, epsilon, data_format)
            dispatch.register_op(vname, fn, multi_out=True)
        else:
            if has_w:
                fn = lambda x, m, v, w, epsilon, data_format: \
                    _bn_eval_fn(x, m, v, w, None, epsilon, data_format)
            elif has_b:
                fn = lambda x, m, v, b, epsilon, data_format: \
                    _bn_eval_fn(x, m, v, None, b, epsilon, data_format)
            else:
                fn = lambda x, m, v, epsilon, data_format: \
                    _bn_eval_fn(x, m, v, None, None, epsilon, data_format)
            dispatch.register_op(vname, fn)
        _bn_variants[vname] = True
    return vname


# ---------------------------------------------------------------------------
# layer norm
# ---------------------------------------------------------------------------

def _ln_fn(x, w, b, norm_ndim, epsilon):
    import jax.numpy as jnp

    axes = tuple(range(x.ndim - norm_ndim, x.ndim))
    mean = x.mean(axis=axes, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=axes, keepdims=True)
    y = (x - mean) / jnp.sqrt(var + epsilon)
    if w is not None:
        y = y * w
    if b is not None:
        y = y + b
    return y


dispatch.register_op("layer_norm", _ln_fn)
dispatch.register_op("layer_norm_now", lambda x, b, norm_ndim, epsilon:
                     _ln_fn(x, None, b, norm_ndim, epsilon))
dispatch.register_op("layer_norm_nob", lambda x, w, norm_ndim, epsilon:
                     _ln_fn(x, w, None, norm_ndim, epsilon))
dispatch.register_op("layer_norm_nowb", lambda x, norm_ndim, epsilon:
                     _ln_fn(x, None, None, norm_ndim, epsilon))


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5,
               name=None):
    x = as_tensor(x)
    if isinstance(normalized_shape, int):
        normalized_shape = [normalized_shape]
    norm_ndim = len(list(normalized_shape))
    attrs = {"norm_ndim": norm_ndim, "epsilon": float(epsilon)}
    if weight is not None and bias is not None:
        return dispatch.apply("layer_norm",
                              [x, as_tensor(weight), as_tensor(bias)], attrs)
    if weight is not None:
        return dispatch.apply("layer_norm_nob", [x, as_tensor(weight)], attrs)
    if bias is not None:
        return dispatch.apply("layer_norm_now", [x, as_tensor(bias)], attrs)
    return dispatch.apply("layer_norm_nowb", [x], attrs)


# ---------------------------------------------------------------------------
# rms norm (fused hot path; reference: phi/kernels/gpu/rms_norm_kernel.cu)
# ---------------------------------------------------------------------------

def _rms_norm_fn(x, w, epsilon):
    import jax.numpy as jnp

    # compute in f32 for bf16 inputs (matches the reference's accumulate-in-float)
    xf = x.astype(jnp.float32) if x.dtype in (jnp.bfloat16, jnp.float16) else x
    var = (xf * xf).mean(axis=-1, keepdims=True)
    y = xf / jnp.sqrt(var + epsilon)
    return (y.astype(x.dtype) * w)


dispatch.register_op("rms_norm", _rms_norm_fn)


def rms_norm(x, weight, epsilon=1e-6, name=None):
    x = as_tensor(x)
    try:
        from ...ops.pallas import _support as _ps
        from ...ops.pallas import rms_norm as _prms

        if _ps.kernels_enabled(x._data) and _prms.supported(
                tuple(x.shape), x._data.dtype):
            from ...incubate.nn import functional as _inc  # registers the op

            return dispatch.apply("pallas_rms_norm", [x, as_tensor(weight)],
                                  {"epsilon": float(epsilon)})
    except ImportError:
        pass
    return dispatch.apply("rms_norm", [x, as_tensor(weight)],
                          {"epsilon": float(epsilon)})


# ---------------------------------------------------------------------------
# group / instance norm
# ---------------------------------------------------------------------------

def _gn_fn(x, w, b, num_groups, epsilon, data_format):
    import jax.numpy as jnp

    channel_last = data_format.endswith("C") and not data_format.startswith("NC")
    if channel_last:
        x = jnp.moveaxis(x, -1, 1)
    n, c = x.shape[0], x.shape[1]
    spatial = x.shape[2:]
    g = num_groups
    xg = x.reshape((n, g, c // g) + spatial)
    axes = tuple(range(2, xg.ndim))
    mean = xg.mean(axis=axes, keepdims=True)
    var = ((xg - mean) ** 2).mean(axis=axes, keepdims=True)
    y = ((xg - mean) / jnp.sqrt(var + epsilon)).reshape(x.shape)
    shape = (1, c) + (1,) * len(spatial)
    if w is not None:
        y = y * w.reshape(shape)
    if b is not None:
        y = y + b.reshape(shape)
    if channel_last:
        y = jnp.moveaxis(y, 1, -1)
    return y


dispatch.register_op("group_norm", _gn_fn)
dispatch.register_op("group_norm_nowb", lambda x, num_groups, epsilon, data_format:
                     _gn_fn(x, None, None, num_groups, epsilon, data_format))


def group_norm(x, num_groups, weight=None, bias=None, epsilon=1e-5,
               data_format="NCHW", name=None):
    x = as_tensor(x)
    attrs = {"num_groups": int(num_groups), "epsilon": float(epsilon),
             "data_format": data_format}
    if weight is None and bias is None:
        return dispatch.apply("group_norm_nowb", [x], attrs)
    w = as_tensor(weight) if weight is not None else None
    b = as_tensor(bias) if bias is not None else None
    if w is None:
        import jax.numpy as jnp

        w = Tensor(jnp.ones(x.shape[1], x._data.dtype))
    if b is None:
        import jax.numpy as jnp

        b = Tensor(jnp.zeros(x.shape[1], x._data.dtype))
    return dispatch.apply("group_norm", [x, w, b], attrs)


def _in_fn(x, w, b, epsilon):
    import jax.numpy as jnp

    axes = tuple(range(2, x.ndim))
    mean = x.mean(axis=axes, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=axes, keepdims=True)
    y = (x - mean) / jnp.sqrt(var + epsilon)
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 2)
    if w is not None:
        y = y * w.reshape(shape)
    if b is not None:
        y = y + b.reshape(shape)
    return y


dispatch.register_op("instance_norm", _in_fn)
dispatch.register_op("instance_norm_nowb",
                     lambda x, epsilon: _in_fn(x, None, None, epsilon))


def instance_norm(x, running_mean=None, running_var=None, weight=None, bias=None,
                  use_input_stats=True, momentum=0.9, eps=1e-5,
                  data_format="NCHW", name=None):
    x = as_tensor(x)
    if weight is None and bias is None:
        return dispatch.apply("instance_norm_nowb", [x], {"epsilon": float(eps)})
    import jax.numpy as jnp

    w = as_tensor(weight) if weight is not None else Tensor(
        jnp.ones(x.shape[1], x._data.dtype))
    b = as_tensor(bias) if bias is not None else Tensor(
        jnp.zeros(x.shape[1], x._data.dtype))
    return dispatch.apply("instance_norm", [x, w, b], {"epsilon": float(eps)})


def _lrn_fn(x, size, alpha, beta, k, data_format):
    import jax
    import jax.numpy as jnp

    channel_axis = 1 if data_format.startswith("NC") else x.ndim - 1
    sq = x * x
    half = size // 2
    wdims = [1] * x.ndim
    wdims[channel_axis] = size
    pads = [(0, 0)] * x.ndim
    pads[channel_axis] = (half, size - half - 1)
    summed = jax.lax.reduce_window(sq, 0.0, jax.lax.add,
                                   tuple(wdims), (1,) * x.ndim, pads)
    div = (k + alpha * summed) ** beta
    return x / div


dispatch.register_op("local_response_norm", _lrn_fn)


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    return dispatch.apply("local_response_norm", [as_tensor(x)],
                          {"size": int(size), "alpha": float(alpha),
                           "beta": float(beta), "k": float(k),
                           "data_format": data_format})


def _normalize_fn(x, p, axis, epsilon):
    import jax.numpy as jnp

    if p == 2.0:
        norm = jnp.sqrt((x * x).sum(axis=axis, keepdims=True))
    else:
        norm = (jnp.abs(x) ** p).sum(axis=axis, keepdims=True) ** (1.0 / p)
    return x / jnp.maximum(norm, jnp.asarray(epsilon, x.dtype))


dispatch.register_op("fn_normalize", _normalize_fn)


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    return dispatch.apply("fn_normalize", [as_tensor(x)],
                          {"p": float(p), "axis": int(axis),
                           "epsilon": float(epsilon)})
