"""Mixture-of-Experts with expert parallelism.

Analog of the reference MoE stack: `incubate/distributed/models/moe/
moe_layer.py:263` (`MoELayer`), gates (`gate/naive_gate.py`,
`switch_gate.py`, `gshard_gate.py`), `MoEScatter/MoEGather` (`moe_layer.py:
99-149`) and the cutlass `fused_moe_kernel.cu`.

TPU-native design: dispatch/combine are dense einsums against a [tokens,
experts, capacity] one-hot — the GShard formulation — with expert weights
stacked on a leading dim placed over the `ep` mesh axis. When tokens are
dp-sharded and experts ep-sharded, XLA lowers the two einsums to the same
all-to-all pair the reference implements as `global_scatter/global_gather`
(`distributed/utils/moe_utils.py:20,153`), fused with the expert matmuls.
"""
from __future__ import annotations

import math
from typing import List, Optional

import numpy as np

from .....core import dispatch
from .....core.tensor import Tensor
from .....nn.layer.layers import Layer
from .....ops._helpers import as_tensor

__all__ = ["MoELayer", "NaiveGate", "SwitchGate", "GShardGate",
           "StackedExperts"]


# ---------------------------------------------------------------------------
# gates
# ---------------------------------------------------------------------------

class BaseGate(Layer):
    def __init__(self, d_model: int, num_experts: int):
        super().__init__()
        self.d_model = d_model
        self.num_experts = num_experts
        from .....nn.layer.common import Linear

        self.gate_proj = Linear(d_model, num_experts, bias_attr=False)
        self.loss = None


class NaiveGate(BaseGate):
    """top-k softmax gate, no capacity dropping (reference
    `gate/naive_gate.py`)."""

    def __init__(self, d_model, num_experts, top_k=2):
        super().__init__(d_model, num_experts)
        self.top_k = top_k

    def forward(self, x):
        from .....ops import math as om, manipulation as man

        logits = self.gate_proj(x)  # [T, E]
        from .....nn import functional as F

        probs = F.softmax(logits, axis=-1)
        return probs


class SwitchGate(NaiveGate):
    """top-1 gate with load-balancing loss (reference `gate/switch_gate.py`;
    the aux loss is set on `.loss` by MoELayer.forward). switch_eps accepted
    for API parity."""

    def __init__(self, d_model, num_experts, top_k=1, switch_eps=0.1,
                 capacity_factor=1.25):
        super().__init__(d_model, num_experts, top_k=1)
        self.capacity_factor = capacity_factor


class GShardGate(NaiveGate):
    """top-2 gate with GShard aux loss (reference `gate/gshard_gate.py`;
    aux loss = E * Σ_e fraction_e · mean_prob_e, set on `.loss` by
    MoELayer.forward). random_routing accepted for API parity."""

    def __init__(self, d_model, num_experts, top_k=2, capacity_factor=2.0,
                 random_routing=True):
        super().__init__(d_model, num_experts, top_k=2)
        self.capacity_factor = capacity_factor


def _aux_loss_fn(probs):
    """GShard load-balancing loss: E * Σ_e f_e·P_e (f_e = non-diff dispatch
    fraction to expert e; P_e = mean gate prob)."""
    import jax
    import jax.numpy as jnp

    e = probs.shape[-1]
    me = probs.mean(axis=0)
    top1 = jnp.argmax(probs, axis=-1)
    ce = jax.nn.one_hot(top1, e, dtype=probs.dtype).mean(axis=0)
    return e * jnp.sum(me * jax.lax.stop_gradient(ce))


dispatch.register_op("moe_aux_loss", _aux_loss_fn)


# ---------------------------------------------------------------------------
# stacked experts (the jit/EP-friendly form)
# ---------------------------------------------------------------------------

class StackedExperts(Layer):
    """num_experts FFNs as stacked weights [E, ...] — placed Shard(0) over
    the ep axis so each device owns its experts (the reference's per-rank
    expert list, `moe_layer.py`)."""

    def __init__(self, num_experts, d_model, d_hidden, activation="gelu"):
        super().__init__()
        scale = 1.0 / math.sqrt(d_model)
        self.w1 = self.create_parameter(
            [num_experts, d_model, d_hidden],
            default_initializer=_uniform_init(scale))
        self.b1 = self.create_parameter([num_experts, 1, d_hidden],
                                        is_bias=True)
        self.w2 = self.create_parameter(
            [num_experts, d_hidden, d_model],
            default_initializer=_uniform_init(1.0 / math.sqrt(d_hidden)))
        self.b2 = self.create_parameter([num_experts, 1, d_model],
                                        is_bias=True)
        self.activation = activation

    def forward(self, expert_inputs):
        """expert_inputs: [E, C, H] -> [E, C, H]."""
        return dispatch.apply(
            "moe_experts", [expert_inputs, self.w1, self.b1, self.w2,
                            self.b2], {"activation": self.activation})


def _uniform_init(scale):
    from .....nn.initializer import Uniform

    return Uniform(-scale, scale)


def _experts_fn(x, w1, b1, w2, b2, activation):
    import jax
    import jax.numpy as jnp

    h = jnp.einsum("ech,ehf->ecf", x, w1,
                   preferred_element_type=jnp.float32).astype(x.dtype) + b1
    act = {"gelu": jax.nn.gelu, "relu": lambda v: jnp.maximum(v, 0),
           "silu": jax.nn.silu}[activation]
    h = act(h)
    return jnp.einsum("ecf,efh->ech", h, w2,
                      preferred_element_type=jnp.float32).astype(x.dtype) + b2


dispatch.register_op("moe_experts", _experts_fn)


def _dispatch_combine_fn(x, probs, capacity, top_k):
    """GShard dense dispatch: returns (combine [T,E,C], dispatch [T,E,C])."""
    import jax
    import jax.numpy as jnp

    t, e = probs.shape
    # top-k expert choice per token
    topv, topi = jax.lax.top_k(probs, top_k)          # [T,k]
    # GShard gate semantics: combine weights are the top-k probs renormalized
    # over the selected experts (gshard_gate divides the top-2 gates by their
    # sum) — without this, expert outputs are systematically down-weighted.
    # top-1 gates (Switch) keep the raw prob: renormalizing would collapse the
    # weight to 1.0 and cut the router out of the task-loss gradient.
    if top_k > 1:
        topv = topv / jnp.maximum(topv.sum(axis=-1, keepdims=True), 1e-9)
    # position of each token within its expert's queue (per k-slot,
    # sequential over slots so top-1 fills first — GShard's priority order)
    combine = jnp.zeros((t, e, capacity), probs.dtype)
    counts = jnp.zeros((e,), jnp.int32)
    for k in range(top_k):
        sel = jax.nn.one_hot(topi[:, k], e, dtype=jnp.int32)     # [T,E]
        pos_in_expert = (jnp.cumsum(sel, axis=0) - 1) + counts[None, :]
        within = pos_in_expert < capacity
        pos = jnp.clip(pos_in_expert, 0, capacity - 1)
        onehot_pos = jax.nn.one_hot(pos, capacity, dtype=probs.dtype)
        mask = (sel.astype(probs.dtype) * within.astype(probs.dtype))
        combine = combine + topv[:, k, None, None] * mask[:, :, None] * \
            onehot_pos
        counts = counts + sel.sum(axis=0)
    dispatch_mask = (combine > 0).astype(x.dtype)
    return combine.astype(x.dtype), dispatch_mask


dispatch.register_op("moe_dispatch", _dispatch_combine_fn, multi_out=True)


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------

class MoELayer(Layer):
    """reference `MoELayer` (`incubate/distributed/models/moe/moe_layer.py:
    263`): gate -> dispatch -> experts (EP) -> combine.

    experts: a StackedExperts, OR a list of per-expert Layers (reference
    style; used for the eager python loop), OR None with (d_model, d_hidden)
    given.
    """

    def __init__(self, d_model=None, experts=None, gate=None, top_k=2,
                 num_experts=None, d_hidden=None, capacity_factor=2.0,
                 moe_group=None, recompute_interval=0, **kwargs):
        super().__init__()
        if isinstance(gate, dict):
            top_k = gate.get("top_k", top_k)
            gate = gate.get("type", "gshard")
        if isinstance(experts, (list, tuple)):
            self.experts_list = list(experts)
            for i, ex in enumerate(self.experts_list):
                self.add_sublayer(f"expert_{i}", ex)
            self.experts = None
            num_experts = len(self.experts_list)
            if d_model is None:
                raise ValueError("d_model is required with an expert list")
        elif isinstance(experts, StackedExperts):
            self.experts = experts
            self.experts_list = None
            num_experts = experts.w1.shape[0]
            if d_model is None:
                d_model = experts.w1.shape[1]
        else:
            if num_experts is None or d_model is None:
                raise ValueError("need experts or (num_experts, d_model)")
            self.experts = StackedExperts(num_experts, d_model,
                                          d_hidden or 4 * d_model)
            self.experts_list = None
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        if gate is None or gate == "gshard":
            self.gate = GShardGate(d_model, num_experts, top_k=min(top_k, 2),
                                   capacity_factor=capacity_factor)
        elif gate == "switch":
            self.gate = SwitchGate(d_model, num_experts)
            self.top_k = 1
        elif gate == "naive":
            self.gate = NaiveGate(d_model, num_experts, top_k=top_k)
        elif isinstance(gate, Layer):
            self.gate = gate
        else:
            raise ValueError(f"unknown gate {gate}")
        self._place_experts()

    def _place_experts(self):
        """Shard stacked expert weights over the ep (or mp) mesh axis."""
        from .....distributed.auto_parallel.api import shard_tensor
        from .....distributed.placement import Replicate, Shard
        from .....distributed.process_mesh import get_mesh

        mesh = get_mesh()
        if mesh is None or self.experts is None:
            return
        axis = None
        for cand in ("ep", "mp", "sharding"):
            if cand in mesh.dim_names:
                axis = mesh.dim_names.index(cand)
                break
        if axis is None or self.num_experts % mesh.shape[axis] != 0:
            return
        for p in self.experts.parameters():
            placements = [Replicate()] * mesh.ndim
            placements[axis] = Shard(0)
            st = shard_tensor(Tensor(p._data), mesh, placements,
                              stop_gradient=False)
            p._data = st._data
            p._dist_meta = st._dist_meta

    def _ep_mesh(self):
        """(jax_mesh, axis_name) when the all-to-all EP path applies."""
        from .....distributed.process_mesh import get_mesh

        if not getattr(self, "use_alltoall", True) or self.experts is None:
            return None
        if not isinstance(self.gate, NaiveGate):
            return None
        mesh = get_mesh()
        if mesh is None or "ep" not in mesh.dim_names:
            return None
        n = mesh.get_dim_size("ep")
        if n <= 1 or self.num_experts % n != 0:
            return None
        return mesh.to_jax_mesh(), "ep"

    def forward(self, x):
        """x: [..., H] — flattened to tokens internally. With an `ep` mesh
        axis the layer routes through the all-to-all dispatch/combine
        (`moe_ep_forward`); otherwise the dense GShard einsum formulation."""
        from .....ops import manipulation as man

        orig_shape = list(x.shape)
        h = orig_shape[-1]
        xt = man.reshape(as_tensor(x), [-1, h])       # [T, H]
        t = xt.shape[0]

        ep = self._ep_mesh()
        if ep is not None:
            mesh, axis = ep
            n = mesh.shape[axis]
            if t % n == 0:
                t_local = t // n
                cap = max(1, int(self.capacity_factor * t_local *
                                 max(1, self.top_k) / self.num_experts))
                ex = self.experts
                y, aux = dispatch.apply(
                    "moe_ep_forward",
                    [xt, self.gate.gate_proj.weight, ex.w1, ex.b1, ex.w2,
                     ex.b2],
                    {"top_k": self.top_k, "capacity": cap,
                     "activation": ex.activation, "axis_name": axis,
                     "mesh": mesh})
                self.gate.loss = aux
                self.aux_loss = aux
                return man.reshape(y, orig_shape)

        probs = self.gate(xt)                          # [T, E]
        if isinstance(self.gate, (SwitchGate, GShardGate)):
            aux = dispatch.apply("moe_aux_loss", [probs], {})
            self.gate.loss = aux
            self.aux_loss = aux
        else:
            self.aux_loss = None
        capacity = max(1, int(self.capacity_factor * t / self.num_experts)) \
            * max(1, self.top_k)
        combine, disp = dispatch.apply(
            "moe_dispatch", [xt, probs],
            {"capacity": capacity, "top_k": self.top_k})
        # dispatch: [T,E,C] x [T,H] -> [E,C,H]  (the all-to-all on hardware)
        expert_in = dispatch.apply("moe_einsum_dispatch", [disp, xt], {})
        if self.experts is not None:
            expert_out = self.experts(expert_in)
        else:
            # per-expert python loop through dispatched slicing/stack so the
            # tape reaches every expert's parameters
            outs = [layer(expert_in[e])
                    for e, layer in enumerate(self.experts_list)]
            expert_out = man.stack(outs, axis=0)
        # combine: [T,E,C] x [E,C,H] -> [T,H]
        out = dispatch.apply("moe_einsum_combine", [combine, expert_out], {})
        return man.reshape(out, orig_shape)


# ---------------------------------------------------------------------------
# expert-parallel all-to-all path (the real EP formulation)
# ---------------------------------------------------------------------------

def _ep_local_fn(x, gate_w, w1, b1, w2, b2, *, top_k, capacity, axis_name,
                 activation):
    """Per-ep-shard MoE: gate -> scatter into a [E, C, H] send buffer ->
    all_to_all -> local experts -> all_to_all back -> gather-combine.

    The TPU-native `global_scatter`/`global_gather`
    (`distributed/utils/moe_utils.py:20,153`): token routing is a scatter
    into per-(expert, source-shard) capacity slots and the device exchange
    is `lax.all_to_all` over the ep axis — per-device memory is
    O(E*C*H) = O(top_k * capacity_factor * T_local * H), never the dense
    [T, E, C] one-hot.
    """
    import jax
    import jax.numpy as jnp

    t, hdim = x.shape
    e_total = gate_w.shape[1]
    n = jax.lax.axis_size(axis_name)
    logits = jnp.einsum("th,he->te", x, gate_w,
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, top_k)                  # [t, k]
    topv = topv.astype(x.dtype)
    if top_k > 1:
        topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    # priority order: every token's top-1 before any top-2 (GShard)
    ti = topi.T.reshape(-1)                                   # [k*t]
    tv = topv.T.reshape(-1)
    sel = jax.nn.one_hot(ti, e_total, dtype=jnp.int32)        # [k*t, E]
    pos_all = jnp.cumsum(sel, axis=0) - 1
    pos = jnp.take_along_axis(pos_all, ti[:, None], axis=1)[:, 0]
    keep = (pos < capacity)
    pos_c = jnp.clip(pos, 0, capacity - 1)
    tok = jnp.tile(jnp.arange(t), top_k)
    xs = x[tok] * keep[:, None].astype(x.dtype)
    # fused dispatch: Pallas scatter into capacity slots when kernels are
    # on (reference fused_moe_kernel.cu role); XLA scatter otherwise
    from .....ops.pallas import fused_moe as _fmoe

    slot = jnp.where(keep, pos_c, -1).astype(jnp.int32)
    if _fmoe.kernels_available():
        send = _fmoe.moe_dispatch(xs, ti.astype(jnp.int32), slot,
                                  e_total, capacity)
    else:
        send = _fmoe.xla_dispatch(xs, ti.astype(jnp.int32), slot,
                                  e_total, capacity)
    # exchange: [E, C, H] -> [E/n, n*C, H] (each device keeps its experts,
    # receives every shard's capacity slots for them)
    recv = jax.lax.all_to_all(send, axis_name, split_axis=0, concat_axis=1,
                              tiled=True)
    act = {"gelu": jax.nn.gelu, "relu": lambda v: jnp.maximum(v, 0),
           "silu": jax.nn.silu}[activation]
    h = jnp.einsum("ech,ehf->ecf", recv, w1,
                   preferred_element_type=jnp.float32).astype(x.dtype) + b1
    h = act(h)
    out = jnp.einsum("ecf,efh->ech", h, w2,
                     preferred_element_type=jnp.float32).astype(x.dtype) + b2
    # inverse exchange back to the token owners: [E/n, n*C, H] -> [E, C, H]
    back = jax.lax.all_to_all(out, axis_name, split_axis=1, concat_axis=0,
                              tiled=True)
    if _fmoe.kernels_available():
        rows = _fmoe.moe_gather(back, ti.astype(jnp.int32), slot)
    else:
        rows = _fmoe.xla_gather(back, ti.astype(jnp.int32), slot)
    gathered = rows * (tv * keep.astype(x.dtype))[:, None]
    y = gathered.reshape(top_k, t, hdim).sum(axis=0)
    # GShard aux loss on the local shard, averaged over the ep group
    me = probs.mean(axis=0)
    ce = jax.nn.one_hot(topi[:, 0], e_total, dtype=probs.dtype).mean(axis=0)
    aux = e_total * jnp.sum(me * jax.lax.stop_gradient(ce))
    aux = jax.lax.pmean(aux, axis_name)
    return y, aux


def _ep_moe_fn(x, gate_w, w1, b1, w2, b2, *, top_k, capacity, activation,
               axis_name, mesh):
    """shard_map wrapper: tokens sharded over ep (dim 0), experts sharded
    over ep (dim 0), gate replicated."""
    import functools

    import jax
    from jax.sharding import PartitionSpec as P

    local = functools.partial(_ep_local_fn, top_k=top_k, capacity=capacity,
                              axis_name=axis_name, activation=activation)
    ep = P(axis_name)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(ep, P(), ep, ep, ep, ep),
        out_specs=(ep, P()), check_vma=False)
    return fn(x, gate_w, w1, b1, w2, b2)


dispatch.register_op("moe_ep_forward", _ep_moe_fn, multi_out=True)


def _einsum_dispatch_fn(disp, x):
    import jax.numpy as jnp

    return jnp.einsum("tec,th->ech", disp, x,
                      preferred_element_type=jnp.float32).astype(x.dtype)


def _einsum_combine_fn(combine, expert_out):
    import jax.numpy as jnp

    return jnp.einsum("tec,ech->th", combine, expert_out,
                      preferred_element_type=jnp.float32
                      ).astype(expert_out.dtype)


dispatch.register_op("moe_einsum_dispatch", _einsum_dispatch_fn)
dispatch.register_op("moe_einsum_combine", _einsum_combine_fn)
