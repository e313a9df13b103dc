"""Ring attention: sequence-parallel exact attention for long context.

Fills the gap SURVEY.md §5.7.4 identifies: the reference exposes the `sep`
mesh axis (`fleet/base/topology.py:199`, `SegmentParallel`) but ships no ring
/ blockwise attention kernel. TPU-native implementation: q/k/v are sharded on
the sequence dim over the `sep` axis; each step every device computes
blockwise online-softmax attention against the K/V block it currently holds,
then `ppermute`s K/V one hop around the ICI ring — compute fully overlaps the
rotation (Liu et al., Ring Attention; blockwise softmax accumulation m/l/acc
as in flash attention). Differentiable end-to-end (lax.scan + ppermute have
transposes), so one `jax.grad` gives the ring backward.

Also provides `ulysses_attention` — the all-to-all (DeepSpeed-Ulysses) form:
reshard [B, S/n, H, D] -> [B, S, H/n, D], run local attention, reshard back.
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np

__all__ = ["ring_flash_attention", "ring_attention", "ulysses_attention"]


def _block_attn(q, k, v, m, l, acc, mask):
    """One online-softmax accumulation step. q,k,v: [B,H,S,D] f32."""
    import jax
    import jax.numpy as jnp

    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32)
    if mask is not None:
        s = jnp.where(mask, s, -1e30)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + p.sum(axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v, preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def ring_attention(q, k, v, axis_name: str = "sep", causal: bool = False,
                   sm_scale: Optional[float] = None):
    """Per-device body: runs INSIDE shard_map/jit over `axis_name`.

    q/k/v: the local sequence shard [B, S_local, H, D] (paddle layout).
    Returns the local attention output [B, S_local, H, D].
    """
    import jax
    import jax.numpy as jnp

    d = q.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(d)
    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    s_local = q.shape[1]

    qt = jnp.swapaxes(q, 1, 2).astype(jnp.float32) * scale  # [B,H,Sq,D]
    kt = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vt = jnp.swapaxes(v, 1, 2).astype(jnp.float32)

    b, h, sq, _ = qt.shape
    m0 = jnp.full((b, h, sq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    acc0 = jnp.zeros_like(qt)

    perm = [(i, (i + 1) % n) for i in range(n)]
    rows = jax.lax.broadcasted_iota(jnp.int32, (sq, s_local), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (sq, s_local), 1)

    def step(carry, t):
        kc, vc, m, l, acc = carry
        # block currently held came from rank (my - t) mod n
        src = (my - t) % n
        if causal:
            q_pos = my * s_local + rows
            k_pos = src * s_local + cols
            mask = (q_pos >= k_pos)[None, None]
        else:
            mask = None
        m, l, acc = _block_attn(qt, kc, vc, m, l, acc, mask)
        # rotate K/V to the next device over ICI (overlaps with compute)
        kn = jax.lax.ppermute(kc, axis_name, perm)
        vn = jax.lax.ppermute(vc, axis_name, perm)
        return (kn, vn, m, l, acc), None

    (_, _, m, l, acc), _ = jax.lax.scan(step, (kt, vt, m0, l0, acc0),
                                        jnp.arange(n))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l_safe[..., None]).astype(q.dtype)
    return jnp.swapaxes(out, 1, 2)


def _ring_shard_mapped(q, k, v, pmesh, axis_name, causal, sm_scale):
    """The shard_map'd ring program (traceable; called under jit/dispatch)."""
    import jax
    from jax.sharding import PartitionSpec as P

    jmesh = pmesh.to_jax_mesh() if hasattr(pmesh, "to_jax_mesh") else pmesh
    spec = P(None, axis_name, None, None)
    body = functools.partial(ring_attention, axis_name=axis_name,
                             causal=causal, sm_scale=sm_scale)
    fn = jax.shard_map(body, mesh=jmesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)


@functools.lru_cache(maxsize=64)
def _ring_jitted(pmesh, axis_name, causal, sm_scale):
    import jax

    return jax.jit(functools.partial(_ring_shard_mapped, pmesh=pmesh,
                                     axis_name=axis_name, causal=causal,
                                     sm_scale=sm_scale))


def _resolve_mesh(mesh, name):
    from .process_mesh import get_mesh

    pmesh = mesh or get_mesh()
    if pmesh is None:
        raise ValueError(f"{name} needs a mesh (dist.set_mesh or fleet.init)")
    return pmesh


def ring_flash_attention(q, k, v, mesh=None, axis_name: str = "sep",
                         causal: bool = False,
                         sm_scale: Optional[float] = None):
    """Whole-array entry: q/k/v are GLOBAL [B, S, H, D] arrays (or Tensors)
    sharded on S over `axis_name`; returns the global output with the same
    sharding. Compiles one XLA program (cached per mesh/flags): n_ring steps
    of block attention + K/V ppermute. Tensor inputs go through eager
    dispatch, so the autograd tape records the ring backward."""
    from ..core import dispatch
    from ..core.tensor import Tensor

    pmesh = _resolve_mesh(mesh, "ring_flash_attention")
    if isinstance(q, Tensor):
        if "ring_attention" not in dispatch.op_registry():
            dispatch.register_op(
                "ring_attention",
                lambda q, k, v, pmesh, axis_name, causal, sm_scale:
                _ring_shard_mapped(q, k, v, pmesh, axis_name, causal,
                                   sm_scale))
        return dispatch.apply(
            "ring_attention", [q, k, v],
            {"pmesh": pmesh, "axis_name": axis_name, "causal": bool(causal),
             "sm_scale": sm_scale})
    return _ring_jitted(pmesh, axis_name, bool(causal), sm_scale)(q, k, v)


def _ulysses_fn(q, k, v, pmesh, axis_name, causal):
    """Traceable Ulysses body: sharding constraints make XLA emit the
    seq<->head all-to-alls around a local full-sequence attention."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    jmesh = pmesh.to_jax_mesh() if hasattr(pmesh, "to_jax_mesh") else pmesh
    head_sharded = NamedSharding(jmesh, P(None, None, axis_name, None))
    seq_sharded = NamedSharding(jmesh, P(None, axis_name, None, None))

    q = jax.lax.with_sharding_constraint(q, head_sharded)
    k = jax.lax.with_sharding_constraint(k, head_sharded)
    v = jax.lax.with_sharding_constraint(v, head_sharded)
    scale = 1.0 / np.sqrt(q.shape[-1])
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        sq = s.shape[-2]
        mask = jnp.tril(jnp.ones((sq, sq), bool))
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", p, vt), 1, 2)
    return jax.lax.with_sharding_constraint(out, seq_sharded)


def ulysses_attention(q, k, v, axis_name: str = "sep", mesh=None,
                      causal: bool = False):
    """DeepSpeed-Ulysses style sequence parallelism (the all-to-all form the
    reference's PaddleNLP layer implements over the sep groups): reshard
    seq-sharded -> head-sharded, local full-sequence attention, reshard back.
    q/k/v: global [B, S, H, D] Tensors/arrays sharded on S. Tensor inputs go
    through eager dispatch (autograd + executable cache)."""
    from ..core import dispatch
    from ..core.tensor import Tensor

    pmesh = _resolve_mesh(mesh, "ulysses_attention")
    if isinstance(q, Tensor):
        if "ulysses_attention" not in dispatch.op_registry():
            dispatch.register_op(
                "ulysses_attention",
                lambda q, k, v, pmesh, axis_name, causal:
                _ulysses_fn(q, k, v, pmesh, axis_name, causal))
        return dispatch.apply(
            "ulysses_attention", [q, k, v],
            {"pmesh": pmesh, "axis_name": axis_name, "causal": bool(causal)})
    import jax

    return jax.jit(functools.partial(_ulysses_fn, pmesh=pmesh,
                                     axis_name=axis_name,
                                     causal=causal))(q, k, v)
