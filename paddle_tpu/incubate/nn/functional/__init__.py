"""Fused-op functional API (reference `python/paddle/incubate/nn/functional/`).

Every function here dispatches to a Pallas TPU kernel when available
(`paddle_tpu.ops.pallas`) and otherwise to the equivalent XLA composite —
same contract as the reference where these bind CUDA fusion kernels
(`paddle/phi/kernels/fusion/gpu/`).
"""
from __future__ import annotations

import numpy as np

from ....core import dispatch
from ....core.tensor import Tensor
from ....ops._helpers import as_tensor
from ....ops.pallas import _support as _psupport
from ....ops.pallas import bias_act as _pba
from ....ops.pallas import rms_norm as _prms
from ....ops.pallas import rope as _prope

__all__ = ["fused_rms_norm", "fused_layer_norm",
           "fused_rotary_position_embedding", "swiglu", "fused_bias_act",
           "fused_linear", "fused_linear_activation",
           "variable_length_memory_efficient_attention",
           "masked_multihead_attention", "block_multihead_attention",
           "blha_get_max_len"]

from .decode_attention import (blha_get_max_len,  # noqa: E402
                               block_multihead_attention,
                               masked_multihead_attention)

dispatch.register_op("pallas_rms_norm",
                     lambda x, w, epsilon: _prms.rms_norm(x, w, epsilon))
dispatch.register_op("pallas_rope",
                     lambda q, k, cos, sin, offset:
                     _prope.fused_rope(q, k, cos, sin, offset),
                     multi_out=True)
dispatch.register_op("pallas_bias_act",
                     lambda x, b, act_method: _pba.fused_bias_act(x, b, act_method))
dispatch.register_op("pallas_bias_act_nob",
                     lambda x, act_method: _pba.fused_bias_act(x, None, act_method))
dispatch.register_op("pallas_swiglu",
                     lambda x, y: _pba.swiglu(x, y))
dispatch.register_op("pallas_swiglu_packed",
                     lambda x: _pba.swiglu(x))


def _pallas_on(x) -> bool:
    return _psupport.kernels_enabled(x._data) \
        and _psupport.float_dtype_ok(x._data.dtype)


def fused_rms_norm(x, norm_weight, norm_bias=None, epsilon=1e-6,
                   begin_norm_axis=-1, bias=None, residual=None,
                   quant_scale=-1, **kwargs):
    """Fused RMSNorm (+optional pre-norm residual add), reference
    `incubate.nn.functional.fused_rms_norm`. Normalizes over axes
    [begin_norm_axis:] (flattened for the kernel). Returns (out,
    residual_out) when a residual is passed."""
    from ....ops import manipulation

    x = as_tensor(x)
    if bias is not None:
        x = x + as_tensor(bias)
    if residual is not None:
        x = x + as_tensor(residual)
    residual_out = x if residual is not None else None
    w = as_tensor(norm_weight)

    axis = begin_norm_axis if begin_norm_axis >= 0 else begin_norm_axis + x.ndim
    orig_shape = list(x.shape)
    flat = x
    if axis < x.ndim - 1:  # flatten the normalized axes into one
        lead = orig_shape[:axis]
        flat = manipulation.reshape(x, lead + [-1])
        w = manipulation.reshape(w, [-1])
    if _pallas_on(flat) and _prms.supported(tuple(flat.shape),
                                            flat._data.dtype):
        out = dispatch.apply("pallas_rms_norm", [flat, w],
                             {"epsilon": float(epsilon)})
    else:
        out = dispatch.apply("rms_norm", [flat, w],
                             {"epsilon": float(epsilon)})
    if norm_bias is not None:
        nb = as_tensor(norm_bias)
        if axis < x.ndim - 1:
            nb = manipulation.reshape(nb, [-1])
        out = out + nb
    if axis < x.ndim - 1:
        out = manipulation.reshape(out, orig_shape)
    return (out, residual_out) if residual is not None else out


def fused_layer_norm(x, norm_weight, norm_bias, epsilon=1e-5,
                     begin_norm_axis=-1, bias=None, residual=None, **kwargs):
    """Fused LayerNorm (+residual), reference
    `incubate.nn.functional.fused_layer_norm`. Normalizes over axes
    [begin_norm_axis:] with the reference's flattened-1-D weight convention.
    """
    from ....nn import functional as F
    from ....ops import manipulation

    x = as_tensor(x)
    if bias is not None:
        x = x + as_tensor(bias)
    if residual is not None:
        x = x + as_tensor(residual)
    residual_out = x if residual is not None else None
    axis = begin_norm_axis if begin_norm_axis >= 0 else begin_norm_axis + x.ndim
    orig_shape = list(x.shape)
    flat = x
    w, b = norm_weight, norm_bias
    if axis < x.ndim - 1:  # flatten normalized axes (1-D weight convention)
        flat = manipulation.reshape(x, orig_shape[:axis] + [-1])
        if w is not None:
            w = manipulation.reshape(as_tensor(w), [-1])
        if b is not None:
            b = manipulation.reshape(as_tensor(b), [-1])
    out = F.layer_norm(flat, flat.shape[-1:], weight=w, bias=b,
                       epsilon=epsilon)
    if axis < x.ndim - 1:
        out = manipulation.reshape(out, orig_shape)
    return (out, residual_out) if residual is not None else out


def _rope_generic_fn(x, cos, sin, neox, batched, offset):
    """XLA rotation: x [B,S,H,D]; cos/sin [T,D/2] or [B,S,D/2] (batched)."""
    import jax.numpy as jnp

    s_len = x.shape[1]
    if batched:
        c = cos[:, :, None, :].astype(jnp.float32)
        s = sin[:, :, None, :].astype(jnp.float32)
    else:
        c = cos[offset:offset + s_len][None, :, None, :].astype(jnp.float32)
        s = sin[offset:offset + s_len][None, :, None, :].astype(jnp.float32)
    xf = x.astype(jnp.float32)
    if neox:
        # reference True = "every two adjacent numbers are calculated"
        # (rotate_every_two in fused_rope_utils.h): pairs (x[2i], x[2i+1]).
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
        r1 = x1 * c - x2 * s
        r2 = x2 * c + x1 * s
        out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    else:
        # reference False = front-half/back-half segments (rotate_half):
        # pairs (x[i], x[i + D/2]).
        d2 = x.shape[-1] // 2
        x1, x2 = xf[..., :d2], xf[..., d2:]
        out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


dispatch.register_op("rope_generic", _rope_generic_fn)


def fused_rotary_position_embedding(q, k=None, v=None, sin=None, cos=None,
                                    position_ids=None,
                                    use_neox_rotary_style=True,
                                    rotary_emb_base=10000.0, offset=0):
    """Reference `incubate.nn.functional.fused_rotary_position_embedding`
    (kernel `phi/kernels/fusion/gpu/fused_rope_kernel.cu`).

    q/k/v: [B, S, H, D] — every provided tensor is rotated (reference
    semantics). cos/sin: [T, D/2] half tables or [T, D]/broadcastable full
    tables (auto-halved per layout). `position_ids` [B, S] gathers per-batch
    rows. `use_neox_rotary_style=True` rotates adjacent interleaved pairs
    (x[2i], x[2i+1]); `False` rotates front-half/back-half segments
    (x[i], x[i+D/2]) — the reference convention.
    """
    import jax.numpy as jnp

    q = as_tensor(q)
    d = q.shape[-1]
    if cos is None or sin is None:
        t = max(q.shape[1] + offset, 1)
        if position_ids is not None:
            t = max(t, int(np.asarray(as_tensor(position_ids)._data).max()) + 1)
        inv = 1.0 / (rotary_emb_base **
                     (np.arange(0, d, 2, dtype=np.float64) / d))
        freqs = np.outer(np.arange(t, dtype=np.float64), inv)
        cos = Tensor(jnp.asarray(np.cos(freqs), q._data.dtype))
        sin = Tensor(jnp.asarray(np.sin(freqs), q._data.dtype))
    cos, sin = as_tensor(cos), as_tensor(sin)
    # accept [*, T, D] full tables: squeeze + halve per rotary layout.
    # Adjacent-pair (neox=True) full tables duplicate each freq at positions
    # (2i, 2i+1) -> take the strided [0::2] half; rotate-half (neox=False)
    # tables duplicate front/back -> take [:D/2].
    if cos.ndim > 2:
        cos = Tensor(cos._data.reshape(-1, cos.shape[-1]))
        sin = Tensor(sin._data.reshape(-1, sin.shape[-1]))
    if cos.shape[-1] == d:
        if use_neox_rotary_style:
            cos = Tensor(cos._data[..., 0::2])
            sin = Tensor(sin._data[..., 0::2])
        else:
            cos = Tensor(cos._data[..., : d // 2])
            sin = Tensor(sin._data[..., : d // 2])

    batched = position_ids is not None
    if batched:
        pid = as_tensor(position_ids)
        cos = Tensor(jnp.take(cos._data, pid._data, axis=0))  # [B,S,D/2]
        sin = Tensor(jnp.take(sin._data, pid._data, axis=0))

    tensors = [("q", q)]
    if k is not None:
        tensors.append(("k", as_tensor(k)))
    if v is not None:
        tensors.append(("v", as_tensor(v)))

    # The Pallas kernel implements the rotate-half (front/back segment)
    # rotation, i.e. the reference's use_neox_rotary_style=False layout.
    use_pallas = (not use_neox_rotary_style and not batched and _pallas_on(q)
                  and _prope.supported(tuple(q.shape), q._data.dtype)
                  and k is not None
                  and tuple(q.shape) == tuple(as_tensor(k).shape))
    outs = {}
    if use_pallas:
        oq, ok = dispatch.apply("pallas_rope",
                                [q, as_tensor(k), cos, sin],
                                {"offset": int(offset)})
        outs["q"], outs["k"] = oq, ok
        if v is not None:
            outs["v"] = dispatch.apply(
                "rope_generic", [as_tensor(v), cos, sin],
                {"neox": False, "batched": False, "offset": int(offset)})
    else:
        attrs = {"neox": bool(use_neox_rotary_style), "batched": batched,
                 "offset": int(offset)}
        for name, t in tensors:
            outs[name] = dispatch.apply("rope_generic", [t, cos, sin], attrs)
    result = [outs[name] for name, _ in tensors]
    return result[0] if len(result) == 1 else tuple(result)


def swiglu(x, y=None, name=None):
    """silu(x) * y (packed split when y is None); reference
    `incubate.nn.functional.swiglu`."""
    x = as_tensor(x)
    if _pallas_on(x):
        if y is None:
            return dispatch.apply("pallas_swiglu_packed", [x])
        return dispatch.apply("pallas_swiglu", [x, as_tensor(y)])
    if y is None:
        return dispatch.apply("swiglu_packed", [x])
    return dispatch.apply("swiglu", [x, as_tensor(y)])


def fused_bias_act(x, bias=None, dequant_scales=None, shift=None, smooth=None,
                   act_method="gelu", compute_dtype="default",
                   quant_scale=-1, quant_round_type=0, quant_max_bound=0,
                   quant_min_bound=0):
    """Reference `incubate.nn.functional.fused_bias_act`
    (kernel `phi/kernels/fusion/gpu/fused_bias_act_kernel.cu`)."""
    x = as_tensor(x)
    act = act_method.lower()
    if _pallas_on(x):
        if bias is None:
            return dispatch.apply("pallas_bias_act_nob", [x],
                                  {"act_method": act})
        return dispatch.apply("pallas_bias_act", [x, as_tensor(bias)],
                              {"act_method": act})
    from ....ops.pallas.bias_act import _ref_bias_act
    import jax.numpy as jnp

    op = "xla_bias_act"
    if op not in dispatch.op_registry():
        dispatch.register_op(op, lambda x, b, act_method:
                             _ref_bias_act(x, b, act_method))
    b = as_tensor(bias) if bias is not None else \
        Tensor(jnp.zeros((x.shape[-1],), x._data.dtype))
    return dispatch.apply(op, [x, b], {"act_method": act})


def fused_linear(x, weight, bias=None, transpose_weight=False, name=None):
    """matmul+bias in one XLA op (the MXU fuses the epilogue);
    reference `incubate.nn.functional.fused_linear`."""
    from ....nn import functional as F
    from ....ops import manipulation

    w = as_tensor(weight)
    if transpose_weight:
        w = manipulation.transpose(w, [1, 0])
    return F.linear(x, w, bias)


def fused_linear_activation(x, y, bias=None, trans_x=False, trans_y=False,
                            activation="gelu"):
    """gemm + bias + activation epilogue (reference
    `incubate.nn.functional.fused_linear_activation`)."""
    from ....ops import linalg

    out = linalg.matmul(as_tensor(x), as_tensor(y), transpose_x=trans_x,
                        transpose_y=trans_y)
    return fused_bias_act(out, bias, act_method=activation)


def variable_length_memory_efficient_attention(query, key, value, seq_lens,
                                               kv_seq_lens, mask=None,
                                               scale=None, causal=False,
                                               pre_cache_length=0):
    """Varlen attention (reference
    `incubate.nn.functional.variable_length_memory_efficient_attention`);
    maps to the varlen masked composite / Pallas flash path.

    query/key/value: [B, H, S, D]; seq_lens: [B] valid lengths.
    """
    import jax.numpy as jnp

    q, k, v = as_tensor(query), as_tensor(key), as_tensor(value)
    sl, kl = as_tensor(seq_lens), as_tensor(kv_seq_lens)

    def fn(q, k, v, sl, kl, mask, scale, causal):
        import jax

        d = q.shape[-1]
        if scale is None:
            scale = 1.0 / np.sqrt(d)
        sq, skv = q.shape[2], k.shape[2]
        scores = jnp.einsum("bhsd,bhtd->bhst", q, k,
                            preferred_element_type=jnp.float32) * scale
        qpos = jnp.arange(sq)
        kpos = jnp.arange(skv)
        valid = (qpos[:, None] < sl.reshape(-1, 1, 1, 1)[:, :, 0, 0, None]) & \
                (kpos[None, :] < kl.reshape(-1, 1, 1, 1)[:, :, 0, 0, None])
        valid = valid[:, None]
        if causal:
            # bottom-right aligned (decode: sq=1 attends to all cached kv)
            valid = valid & (qpos[:, None] + (skv - sq) >=
                             kpos[None, :])[None, None]
        scores = jnp.where(valid, scores, -1e30)
        if mask is not None:
            if mask.dtype == jnp.bool_:
                scores = jnp.where(mask, scores, -1e30)
            else:
                scores = scores + mask.astype(scores.dtype)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("bhst,bhtd->bhsd", probs, v)

    attrs = {"scale": scale, "causal": bool(causal)}
    if mask is not None:
        op = "varlen_mea_mask"
        if op not in dispatch.op_registry():
            dispatch.register_op(
                op, lambda q, k, v, sl, kl, m, **a: fn(q, k, v, sl, kl, m, **a))
        return dispatch.apply(op, [q, k, v, sl, kl, as_tensor(mask)], attrs)
    op = "varlen_mea"
    if op not in dispatch.op_registry():
        dispatch.register_op(
            op, lambda q, k, v, sl, kl, **a: fn(q, k, v, sl, kl, None, **a))
    return dispatch.apply(op, [q, k, v, sl, kl], attrs)
