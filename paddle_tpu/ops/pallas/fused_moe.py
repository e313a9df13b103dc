"""Pallas fused MoE dispatch/combine kernels.

The `fused_moe` role (reference
`paddle/phi/kernels/fusion/cutlass/fused_moe_kernel.cu` and the
`MoEScatter/MoEGather` ops, `incubate/distributed/models/moe/moe_layer.py:99`):
token routing into per-(expert, capacity-slot) buffers and the gather back.

Kernel design: routing is a data-dependent permutation, so the (expert,
slot) indices ride scalar prefetch (SMEM) and drive the OUTPUT BlockSpec
index map — each grid step DMAs one token row straight to its capacity
slot (dispatch) or from it (gather). The copy engine does the scatter; the
kernel body is a single row move, and no [T, E] one-hot or [T, E, C]
dispatch mask is ever materialised. Dropped tokens route to a sacrificial
slot (capacity index C) that is sliced off afterwards.

Both kernels carry custom VJPs: scatter's backward is the gather and vice
versa, so the EP training path differentiates through them.

Measured on TPU v5e (N=512 tokens, H=512, E=8, C=128, bf16): gather kernel
1.85ms vs 1.97ms XLA gather; dispatch kernel 2.1ms vs 1.5ms XLA scatter
(per-row DMA grid overhead dominates), both exact vs the XLA path and both
O(N*H) memory vs the dense einsum path's O(N*E*C) dispatch mask. The EP
layer therefore defaults to the XLA scatter/gather contract
(xla_dispatch/xla_gather) and enables these kernels under
FLAGS_fused_moe_kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _support


def _copy_row_kernel(f_ref, x_ref, z_ref, o_ref):
    del f_ref, z_ref
    o_ref[0, 0, :] = x_ref[0, 0, :]


def _read_row_kernel(f_ref, b_ref, o_ref):
    del f_ref
    o_ref[0, 0, :] = b_ref[0, 0, :]


def _scatter_call(e_idx, p_idx, x, n_experts, capacity):
    """x: [N, H] rows -> [E, C, H]; p_idx < 0 routes to the garbage slot."""
    rows, hdim = x.shape
    cp1 = capacity + 1
    e = e_idx.astype(jnp.int32)
    # dropped rows land in the sacrificial slot C (sliced off below);
    # the (E, C+1) grid is flattened so the row DMA indexes an untiled
    # leading dim (Mosaic requires the last two dims be whole blocks)
    slot = jnp.where(p_idx >= 0, p_idx, capacity).astype(jnp.int32)
    flat = e * cp1 + slot
    zeros = jnp.zeros((n_experts * cp1, 1, hdim), x.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows,),
        in_specs=[
            pl.BlockSpec((1, 1, hdim), lambda i, f_: (i, 0, 0)),
            pl.BlockSpec((1, 1, hdim), lambda i, f_: (f_[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, hdim), lambda i, f_: (f_[i], 0, 0)),
    )
    out = _support.pallas_call(
        _copy_row_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_experts * cp1, 1, hdim), x.dtype),
        # the zeros operand aliases the output: slots no row routes to
        # stay zero (operand index counts the scalar-prefetch args)
        input_output_aliases={2: 0},
        name="fused_moe_scatter",
        interpret=_support.interpret_mode(),
    )(flat, x[:, None, :], zeros)
    return out.reshape(n_experts, cp1, hdim)[:, :capacity]


def _gather_call(e_idx, p_idx, buf):
    """[E, C, H] capacity slots -> [N, H] rows (dropped rows -> zeros)."""
    rows = e_idx.shape[0]
    n_experts, capacity, hdim = buf.shape
    keep = p_idx >= 0
    flat = (e_idx.astype(jnp.int32) * capacity
            + jnp.clip(p_idx, 0, capacity - 1).astype(jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows,),
        in_specs=[
            pl.BlockSpec((1, 1, hdim), lambda i, f_: (f_[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, hdim), lambda i, f_: (i, 0, 0)),
    )
    out = _support.pallas_call(
        _read_row_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, 1, hdim), buf.dtype),
        name="fused_moe_gather",
        interpret=_support.interpret_mode(),
    )(flat, buf.reshape(n_experts * capacity, 1, hdim))
    return out[:, 0, :] * keep[:, None].astype(buf.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def moe_dispatch(x_rows, e_idx, p_idx, n_experts, capacity):
    """Scatter token rows into [E, C, H] capacity slots.

    x_rows: [N, H] (already weighted/masked rows, N = top_k * tokens);
    e_idx/p_idx: [N] expert / slot per row, p_idx < 0 = dropped. Slot
    indices must be unique per expert (capacity-slot assignment)."""
    return _scatter_call(e_idx, p_idx, x_rows, n_experts, capacity)


def _dispatch_fwd(x_rows, e_idx, p_idx, n_experts, capacity):
    return moe_dispatch(x_rows, e_idx, p_idx, n_experts, capacity), \
        (e_idx, p_idx)


def _dispatch_bwd(n_experts, capacity, res, g):
    e_idx, p_idx = res
    return _gather_call(e_idx, p_idx, g), None, None


moe_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def moe_gather(buf, e_idx, p_idx):
    """Gather rows back from [E, C, H] capacity slots -> [N, H]
    (dropped rows give zeros)."""
    return _gather_call(e_idx, p_idx, buf)


def _gather_fwd(buf, e_idx, p_idx):
    return moe_gather(buf, e_idx, p_idx), \
        (e_idx, p_idx, buf.shape[0], buf.shape[1])


def _gather_bwd(res, g):
    e_idx, p_idx, n_experts, capacity = res
    return _scatter_call(e_idx, p_idx, g, n_experts, capacity), None, None


moe_gather.defvjp(_gather_fwd, _gather_bwd)


def xla_dispatch(x_rows, e_idx, p_idx, n_experts, capacity):
    """XLA scatter fallback (same contract, no kernel)."""
    hdim = x_rows.shape[-1]
    keep = p_idx >= 0
    pc = jnp.clip(p_idx, 0, capacity - 1)
    out = jnp.zeros((n_experts, capacity, hdim), x_rows.dtype)
    return out.at[e_idx, pc].add(x_rows * keep[:, None].astype(x_rows.dtype))


def xla_gather(buf, e_idx, p_idx):
    keep = p_idx >= 0
    pc = jnp.clip(p_idx, 0, buf.shape[1] - 1)
    return buf[e_idx, pc] * keep[:, None].astype(buf.dtype)


from ...framework import flags as _flags

_flags.define_flag("fused_moe_kernels", False,
                   "use the Pallas MoE dispatch/combine kernels in the EP "
                   "path (default: XLA scatter/gather, faster as of v5e "
                   "measurements)")


def kernels_available() -> bool:
    return _support.kernels_enabled() and \
        bool(_flags.flag_value("fused_moe_kernels"))
