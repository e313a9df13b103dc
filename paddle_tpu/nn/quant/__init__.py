"""Quantized execution ops (reference:
`python/paddle/nn/quant/quantized_linear.py`).

The QUANTIZED path, not fake-quant: weights live in int8 / int4(packed) /
fp8 and are dequantized inside the matmul — on TPU via the Pallas
dequant-in-kernel gemm (`ops/pallas/quant_matmul.py`), elsewhere via an XLA
composite whose convert fuses into the matmul. Layout contract matches the
reference: `weight_quantize` returns the TRANSPOSED quantized weight
([out_features, in_features]) plus a per-channel f32 scale.
"""
from __future__ import annotations

import numpy as np

from ...core import dispatch
from ...core.tensor import Tensor
from ..layer.layers import Layer

__all__ = ["weight_quantize", "weight_dequantize", "weight_only_linear",
           "llm_int8_linear", "apply_per_channel_scale", "WeightOnlyLinear",
           "per_channel_quantize", "dequant_matmul", "pack_int4",
           "unpack_int4", "quantize_with_scales"]

_ALGOS = ("weight_only_int8", "weight_only_int4", "llm.int8", "fp8")


def _arr(x):
    return x._data if isinstance(x, Tensor) else x


def quantize_with_scales(w, scales, bits: int):
    """Round/clip `w [..., N, K]` to int8 storage at the GIVEN
    per-channel scales (`[..., N]`, the `absmax / qmax` convention).
    The single int-quantization step — `per_channel_quantize` routes its
    own absmax scales here, `serving.quant.quantize_engine` its
    observer-calibrated ones, so the round/clip/zero-scale formula
    cannot drift between the constructor and offline passes."""
    import jax.numpy as jnp

    qmax = (1 << (bits - 1)) - 1                      # 7 or 127
    safe = jnp.where(scales > 0, scales, 1.0)
    return jnp.clip(jnp.round(w / safe[..., None]), -qmax, qmax) \
        .astype(jnp.int8)


def per_channel_quantize(w, algo: str):
    """Absmax per-channel quantization over the LAST axis of `w`
    ([..., N, K] layout). Returns (q, scale[..., N] f32). The single source
    of the 127 / 448 scale formulas — shared with the inference engine's
    stacked-weight path."""
    import jax.numpy as jnp

    if algo == "fp8":
        scale = jnp.max(jnp.abs(w), axis=-1) / 448.0  # fp8 e4m3 max
        safe = jnp.where(scale > 0, scale, 1.0)
        q = (w / safe[..., None]).astype(jnp.float8_e4m3fn)
    else:
        bits = 4 if algo == "weight_only_int4" else 8
        qmax = (1 << (bits - 1)) - 1                  # 7 or 127
        scale = jnp.max(jnp.abs(w), axis=-1) / qmax
        q = quantize_with_scales(w, scale, bits)
    return q, scale.astype(jnp.float32)


def weight_quantize(x, algo: str = "weight_only_int8", arch=None,
                    group_size: int = -1, name=None):
    """Quantize a [K, N] float weight; returns (quantized [N, K] (int4:
    packed [N, K//2]), scale [N] f32) — the reference layout
    (quantized_linear.py:56)."""
    import jax.numpy as jnp

    if algo not in _ALGOS:
        raise ValueError(f"algo must be one of {_ALGOS}, got {algo}")
    w = jnp.asarray(_arr(x), jnp.float32).T          # [N, K]
    if algo == "weight_only_int4" and w.shape[1] % 2:
        raise ValueError(
            f"weight_only_int4 packs two values per byte and needs an even "
            f"in_features, got {w.shape[1]}")
    q, scale = per_channel_quantize(w, algo)
    if algo == "weight_only_int4":
        q = pack_int4(q)
    return (Tensor(q, stop_gradient=True),
            Tensor(scale, stop_gradient=True))


def pack_int4(q):
    """Pack int4 values (int8 storage, range [-8, 7]) two-per-byte along
    the LAST axis: ``[..., K] -> [..., K//2]``.

    SPLIT-HALF layout (not interleaved): byte j holds ``q[..., j]`` in
    the low nibble and ``q[..., K//2 + j]`` in the high nibble. The
    layout exists for the Pallas int4 gemm (`ops/pallas/quant_matmul`):
    unpacking a K-block is then two nibble extractions feeding two MXU
    contractions against the matching halves of the activation block —
    no in-kernel lane interleave/relayout. `unpack_int4` inverts it
    exactly for every representable value (round-trip property test in
    tests/test_quant_serving.py).

    FORMAT BREAK (PR 14): this replaced the earlier interleaved packing
    (byte j = q[2j], q[2j+1]). An int4 `WeightOnlyLinear` checkpoint
    written BEFORE the change loads shape/dtype-clean but decodes
    column-permuted — re-quantize from the float checkpoint instead of
    loading stale int4 buffers. (int8/fp8 storage is unaffected; no
    in-tree artifact carries the old layout.)"""
    import jax.numpy as jnp

    k = q.shape[-1]
    if k % 2:
        raise ValueError(f"pack_int4 needs an even last axis, got {k}")
    lo = q[..., :k // 2] & 0x0F
    hi = (q[..., k // 2:] & 0x0F) << 4
    return (lo | hi).astype(jnp.int8)


def unpack_int4(q):
    """``[..., K//2]`` split-half packed -> ``[..., K]`` int8 with sign
    extension (exact inverse of `pack_int4`)."""
    import jax.numpy as jnp

    lo = (q & 0x0F).astype(jnp.int8)
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = ((q >> 4) & 0x0F).astype(jnp.int8)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    return jnp.concatenate([lo, hi], axis=-1)


# back-compat alias (pre-split-half callers used the private name)
_unpack_int4 = unpack_int4


def weight_dequantize(x, scale, algo: str = "weight_only_int8",
                      out_dtype="float16", group_size: int = -1, name=None):
    """Inverse of weight_quantize: returns the [K, N] float weight
    (quantized_linear.py:123)."""
    import jax.numpy as jnp

    from ...framework import dtype as dtype_mod

    q, s = _arr(x), _arr(scale)
    if algo == "weight_only_int4":
        q = _unpack_int4(q)
    w = q.astype(jnp.float32) * jnp.asarray(s, jnp.float32)[:, None]
    return Tensor(w.T.astype(dtype_mod.to_np(out_dtype)),
                  stop_gradient=True)


def dequant_matmul(x, wq, scale, weight_dtype: str = "int8"):
    """x [..., K] @ dequant(wq [N, K] / int4-packed [N, K//2]).T -> [..., N].

    THE weight-only execution primitive (shared by weight_only_linear and
    the llama inference engine): Pallas dequant-in-kernel gemm on aligned
    TPU shapes, XLA convert+matmul fallback elsewhere (the convert fuses
    into the gemm there too)."""
    from ...ops.pallas import _support
    from ...ops.pallas import quant_matmul as qm

    lead = x.shape[:-1]
    k = x.shape[-1]
    x2d = x.reshape(-1, k)
    n = wq.shape[0]
    if weight_dtype == "int4":
        # wq is split-half packed [N, K//2]; the Pallas path unpacks the
        # nibbles in VMEM (two contractions against the activation
        # halves), the XLA path unpacks ahead of the matmul (the convert
        # fuses into the gemm there)
        if (_support.kernels_enabled(x)
                and qm.int4_supported(x2d.shape, wq.shape, wq.dtype)):
            out = qm.quant_matmul_int4(x2d, wq, scale, out_dtype=x.dtype)
        else:
            wf = unpack_int4(wq).astype(x.dtype) \
                * scale[:, None].astype(x.dtype)
            out = x2d @ wf.T
        return out.reshape(lead + (n,))
    if (_support.kernels_enabled(x)
            and qm.supported(x2d.shape, wq.shape, wq.dtype)):
        out = qm.quant_matmul(x2d, wq, scale, out_dtype=x.dtype)
    else:
        wf = wq.astype(x.dtype) * scale[:, None].astype(x.dtype)
        out = x2d @ wf.T
    return out.reshape(lead + (n,))


def _woq_impl(x, wq, scale, bias, *, weight_dtype, has_bias):
    out = dequant_matmul(x, wq, scale, weight_dtype)
    if has_bias:
        out = out + bias
    return out


def weight_only_linear(x, weight, bias=None, weight_scale=None,
                       weight_dtype: str = "int8", arch=None,
                       group_size: int = -1, name=None):
    """x @ dequant(weight).T + bias with int8/int4/fp8 weights
    (quantized_linear.py:183)."""
    x = x if isinstance(x, Tensor) else Tensor(x)
    weight = weight if isinstance(weight, Tensor) else Tensor(weight)
    if weight_scale is None:
        raise ValueError("weight_scale is required (per-channel f32 scale)")
    ws = weight_scale if isinstance(weight_scale, Tensor) \
        else Tensor(weight_scale)
    if "weight_only_linear" not in dispatch.op_registry():
        dispatch.register_op("weight_only_linear", _woq_impl)
    args = [x, weight, ws]
    has_bias = bias is not None
    if has_bias:
        args.append(bias if isinstance(bias, Tensor) else Tensor(bias))
    else:
        args.append(Tensor(np.zeros((1,), np.float32), stop_gradient=True))
    return dispatch.apply("weight_only_linear", args,
                          {"weight_dtype": str(weight_dtype),
                           "has_bias": has_bias})


def llm_int8_linear(x, weight, bias=None, weight_scale=None,
                    threshold: float = 6.0, name=None):
    """LLM.int8(): activation columns with |x| above `threshold` run in the
    original dtype against the DEQUANTIZED weight (outlier path); the rest
    run through the int8 weight (quantized_linear.py:276)."""
    import jax.numpy as jnp

    x = x if isinstance(x, Tensor) else Tensor(x)
    weight = weight if isinstance(weight, Tensor) else Tensor(weight)
    ws = weight_scale if isinstance(weight_scale, Tensor) \
        else Tensor(weight_scale)

    def impl(x, wq, scale, *, threshold):
        import jax

        lead = x.shape[:-1]
        x2d = x.reshape(-1, x.shape[-1])
        # outlier feature columns by max |activation| (LLM.int8 decomposition)
        outlier = (jnp.max(jnp.abs(x2d), axis=0) >= threshold)  # [K]
        x_main = jnp.where(outlier[None, :], 0, x2d)
        x_out = jnp.where(outlier[None, :], x2d, 0)
        # main path: dynamic per-row int8 activations x int8 weights on the
        # MXU, accumulated in int32, rescaled by (row_scale * col_scale)
        row_s = jnp.max(jnp.abs(x_main), axis=1, keepdims=True) / 127.0
        safe = jnp.where(row_s > 0, row_s, 1.0)
        xq = jnp.clip(jnp.round(x_main / safe), -127, 127).astype(jnp.int8)
        main = jax.lax.dot_general(
            xq, wq, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32).astype(jnp.float32)
        main = main * safe * scale[None, :]
        # outlier path: full-precision against the dequantized columns
        wf = wq.astype(x.dtype) * scale[:, None].astype(x.dtype)  # [N, K]
        out = main.astype(x.dtype) + x_out @ wf.T
        return out.reshape(lead + (wq.shape[0],))

    if "llm_int8_linear" not in dispatch.op_registry():
        dispatch.register_op("llm_int8_linear", impl)
    out = dispatch.apply("llm_int8_linear", [x, weight, ws],
                         {"threshold": float(threshold)})
    if bias is not None:
        out = out + (bias if isinstance(bias, Tensor) else Tensor(bias))
    return out


def apply_per_channel_scale(x, scales, name=None):
    """x * scales broadcast over the last dim (smooth-quant prescale,
    quantized_linear.py:342)."""
    x = x if isinstance(x, Tensor) else Tensor(x)
    scales = scales if isinstance(scales, Tensor) else Tensor(scales)
    return x * scales


class WeightOnlyLinear(Layer):
    """Deploy-form Linear: holds int8/int4/fp8 weight + scale, executes via
    weight_only_linear (the convert target of PTQ/QAT; reference
    `nn/quant/quant_layers.py` QuantizedLinear deploy path)."""

    def __init__(self, weight, weight_scale, bias=None, weight_dtype="int8"):
        super().__init__()
        # buffers, not attributes: state_dict()/checkpoints must carry the
        # quantized weights
        self.register_buffer("weight", weight)
        self.register_buffer("weight_scale", weight_scale)
        if bias is not None:
            self.bias = bias
        else:
            self.bias = None
        self.weight_dtype = weight_dtype

    def forward(self, x):
        return weight_only_linear(x, self.weight, bias=self.bias,
                                  weight_scale=self.weight_scale,
                                  weight_dtype=self.weight_dtype)

    @staticmethod
    def from_linear(linear, algo: str = "weight_only_int8"):
        wq, scale = weight_quantize(linear.weight, algo=algo)
        dt = {"weight_only_int8": "int8", "weight_only_int4": "int4",
              "fp8": "fp8"}.get(algo, "int8")
        return WeightOnlyLinear(wq, scale, bias=linear.bias,
                                weight_dtype=dt)
