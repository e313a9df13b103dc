"""Pallas weight-only quantized matmul (reference:
`paddle/phi/kernels/fusion/cutlass/gemm_epilogue/` int8/fp8 gemm +
dequant epilogues).

TPU-first rationale: weight-only decode is HBM-bandwidth-bound, so the win
comes from READING int8/fp8 weights (2x fewer bytes than bf16) and
dequantizing inside VMEM right before the MXU — the bf16 weight matrix
never exists in HBM. The kernel tiles (M, N, K), accumulates in f32 over
the K grid axis, and applies the per-output-channel scale once at the last
K step.

Layout contract matches the reference `weight_quantize`: quantized weight
is [N, K] (transposed), scale is [N] f32. int4 / non-TPU fall back to the
XLA composite in `nn/quant` (convert fuses into the matmul there too).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _support


def _qmm_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, n_k):
    """One (i, j, k) grid step: acc += x[i,k] @ dequant(w[j,k]).T"""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                              # [bm, bk] bf16/f32
    w = w_ref[...].astype(x.dtype)              # [bn, bk] int8/fp8 -> x dtype
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())),         # contract K, w transposed
        preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        scale = s_ref[...].astype(jnp.float32)  # [1, bn]
        o_ref[...] = (acc_ref[...] * scale).astype(o_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def quant_matmul(x2d, wq, scale, out_dtype=None):
    """x2d [M, K] (bf16/f32) @ dequant(wq [N, K], scale [N]) -> [M, N].
    Differentiable w.r.t. x2d only (weights are quantized constants);
    backward is an XLA dequant-matmul (bandwidth-light: runs on the grad,
    not the weights' hot decode path)."""
    return _quant_matmul_fwd_only(x2d, wq, scale, out_dtype)


def _quant_matmul_fwd_rule(x2d, wq, scale, out_dtype):
    return _quant_matmul_fwd_only(x2d, wq, scale, out_dtype), (wq, scale)


def _quant_matmul_bwd_rule(out_dtype, res, g):
    import numpy as np

    wq, scale = res
    wf = wq.astype(g.dtype) * scale[:, None].astype(g.dtype)   # [N, K]
    # int8 weights take a float0 (symbolic-zero) cotangent
    wq_ct = np.zeros(wq.shape, jax.dtypes.float0)
    return g @ wf, wq_ct, jnp.zeros_like(scale)


quant_matmul.defvjp(_quant_matmul_fwd_rule, _quant_matmul_bwd_rule)


def _scale_spec(bn):
    """Per-out-channel scales ride as a [1, N] row in (1, bn) blocks: a
    1-D (bn,) block makes Mosaic tile the operand T(bn) against XLA's
    T(1024) layout for a 1-D f32 array, which the compiler refuses."""
    return pl.BlockSpec((1, bn), lambda i, j, kk: (0, j))


def _build_qmm(m, n, k, out_dtype, cfg):
    bm, bn, bk = cfg
    n_k = pl.cdiv(k, bk)
    return _support.pallas_call(
        functools.partial(_qmm_kernel, n_k=n_k),
        grid=(pl.cdiv(m, bm), pl.cdiv(n, bn), n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bn, bk), lambda i, j, kk: (j, kk)),
            _scale_spec(bn),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        # f32 accumulator carried across the K grid axis
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="quant_matmul_int8",
        interpret=_support.interpret_mode(),
    )


def _quant_matmul_fwd_only(x2d, wq, scale, out_dtype=None):
    from . import autotune

    m, k = x2d.shape
    n, k2 = wq.shape
    assert k == k2, (x2d.shape, wq.shape)
    out_dtype = out_dtype or x2d.dtype
    scale = scale.reshape(1, n)

    default = (_support.pick_block(m, 256) or m,
               _support.pick_block(n, 512) or n,
               _support.pick_block(k, 512) or k)
    cfg = autotune.pick(
        "quant_matmul", (m, n, k, str(wq.dtype), str(out_dtype)),
        autotune.candidate_blocks(m, n, k),
        lambda c: _build_qmm(m, n, k, out_dtype, c),
        (x2d, wq, scale), default)
    return _build_qmm(m, n, k, out_dtype, cfg)(x2d, wq, scale)


def _qmm4_kernel(xlo_ref, xhi_ref, wp_ref, s_ref, o_ref, acc_ref, *, n_k):
    """One (i, j, k) grid step of the packed-int4 gemm.

    `wp` is the SPLIT-HALF packed weight block [bn, bkp] (bkp = bk/2
    bytes, see `nn.quant.pack_int4`): the low nibble of byte c is weight
    column c of the K first-half, the high nibble column c of the
    second-half. Unpacking is therefore two nibble extractions feeding
    two MXU contractions against the matching activation halves — no
    in-kernel lane interleave, which an interleaved packing would need.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xlo = xlo_ref[...]                           # [bm, bkp] bf16/f32
    xhi = xhi_ref[...]
    # widened first: Mosaic has no i8 vector compare/select ("Target does
    # not support this comparison"), and in i32 the sign extension needs
    # neither — an arithmetic shift pair does it
    wp = wp_ref[...].astype(jnp.int32)           # [bn, bkp] packed bytes
    lo = (wp << 28) >> 28
    hi = wp >> 4
    acc_ref[...] += jax.lax.dot_general(
        xlo, lo.astype(xlo.dtype), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        xhi, hi.astype(xhi.dtype), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        scale = s_ref[...].astype(jnp.float32)   # [1, bn]
        o_ref[...] = (acc_ref[...] * scale).astype(o_ref.dtype)


def _build_qmm4(m, n, kp, out_dtype, cfg):
    """kp = K // 2: the packed-byte axis the K grid iterates over. The
    activation is read as TWO blocks per step — block column kk of the
    first K-half and kk + n_k of the second — so its BlockSpec stays in
    bkp units with no relayout."""
    bm, bn, bkp = cfg
    n_k = pl.cdiv(kp, bkp)
    return _support.pallas_call(
        functools.partial(_qmm4_kernel, n_k=n_k),
        grid=(pl.cdiv(m, bm), pl.cdiv(n, bn), n_k),
        in_specs=[
            pl.BlockSpec((bm, bkp), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bm, bkp),
                         lambda i, j, kk, _n=n_k: (i, kk + _n)),
            pl.BlockSpec((bn, bkp), lambda i, j, kk: (j, kk)),
            _scale_spec(bn),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="quant_matmul_int4",
        interpret=_support.interpret_mode(),
    )


def quant_matmul_int4(x2d, wq_packed, scale, out_dtype=None):
    """x2d [M, K] @ dequant(split-half packed wq [N, K//2], scale [N])
    -> [M, N]. Forward-only (int4 is a deploy format; training never
    sees it) — the serving weight-only decode path for wbits=4."""
    m, k = x2d.shape
    n, kp = wq_packed.shape
    assert k == 2 * kp, (x2d.shape, wq_packed.shape)
    out_dtype = out_dtype or x2d.dtype
    cfg = (_support.pick_block(m, 256) or m,
           _support.pick_block(n, 512) or n,
           _support.pick_block(kp, 256) or kp)
    return _build_qmm4(m, n, kp, out_dtype, cfg)(x2d, x2d, wq_packed,
                                                 scale.reshape(1, n))


def _tileable(m, n, k_block_axis) -> bool:
    """Block legality shared by both gemms: `pick_block` must find a
    sublane-aligned M block, a lane-aligned N block and a lane-aligned
    block on the K axis the grid walks (else it returns a whole odd axis
    that Mosaic cannot tile)."""
    return m % 8 == 0 and n % 128 == 0 and k_block_axis % 128 == 0


def supported(x_shape, w_shape, w_dtype) -> bool:
    """Gate for `quant_matmul`: int8/fp8 [N, K] weights against 2-D
    activations whose dims divide into legal tiles."""
    import numpy as np

    if len(x_shape) != 2 or len(w_shape) != 2:
        return False
    name = np.dtype(w_dtype).name if not isinstance(w_dtype, str) else w_dtype
    return name in ("int8", "float8_e4m3fn", "float8_e5m2") \
        and _tileable(x_shape[0], w_shape[0], w_shape[1])


def int4_supported(x_shape, wp_shape, wp_dtype) -> bool:
    """Gate for `quant_matmul_int4`: split-half packed int8 storage,
    2-D, K = 2 * packed width, dims dividing into legal tiles (the K
    grid walks the packed byte axis)."""
    import numpy as np

    if len(x_shape) != 2 or len(wp_shape) != 2:
        return False
    if x_shape[1] != 2 * wp_shape[1]:
        return False
    name = np.dtype(wp_dtype).name if not isinstance(wp_dtype, str) \
        else wp_dtype
    return name == "int8" and _tileable(x_shape[0], wp_shape[0],
                                        wp_shape[1])
