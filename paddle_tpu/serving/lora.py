"""Multi-tenant LoRA serving — paged adapter pool + per-lane
batched-gather low-rank epilogues on ONE ragged engine (ROADMAP item 4).

Every tenant wants a fine-tuned variant; dedicating a replica per
variant wastes the fleet. `attach_adapters(engine, pool_slots=...)`
wraps a built serving engine (bf16, or a PR 14 int8/int4 weight-only
base — the LoRA epilogue composes with the `_mm` dict-swap mechanism,
so the base matmul stays quantized) the same way `quantize_engine` /
`shard_engine` wrap: the wrapper IS an `EngineCore`, so the scheduler,
frontend, fleet router, and chaos harness drive it unchanged.

Mechanism (Ragged Paged Attention, PAPERS.md arxiv 2604.15464): the
ragged dispatch already derives per-token `(lane, position)` metadata
from the scalar-prefetch arrays (`ragged_metadata`). Adapter identity
rides the SAME path — a host `[B]` int32 lane->slot vector enters the
jit as data, the trace gathers `ids = lane_slots[tok_lane]`, and every
projection's epilogue becomes a batched gather-matmul:

    y = base_mm(x, W) + (x @ A[ids]) @ B[ids]

with A/B living in fixed device-resident pool tensors
(`[slots+1, K, Rmax]` / `[slots+1, Rmax, N]`; stacked-layer engines add
a leading L axis that `lax.scan` slices with the weights). Adapter ids
are DATA, not shape: one fixed-shape executable serves any adapter mix,
and switching adapters between steps can never retrace
(`serving.lora.switch_retraces` pins exactly that). The last pool row
is the reserved ZERO slot — all-zero A/B, so a no-adapter lane adds an
exact zero and stays bitwise the base model.

Heterogeneous ranks share that one trace by RANK PADDING: an adapter of
rank r registers into the smallest bucket >= r (`rank_buckets`), then
zero-pads to the pool's physical Rmax — padded columns of A and rows of
B are zero, so the result is exact while the gather shape never varies.

`AdapterPool` mirrors `BlockCacheManager` for adapter weights: a
host-side registry (`register`/`deregister`/`pin`), fixed device slots,
refcounted leases (`lease`/`release` — the scheduler leases at
admission, releases at every exit path), and LRU eviction of idle
unpinned adapters when a miss needs a slot. A resident adapter admits
for free; a miss pays a priced upload (one donated scatter per pool
tensor) and is budgeted per admission round by the scheduler. The
`serve.adapter` chaos site fires at the top of the miss path — BEFORE
any pool mutation — so an injected fault can never leave the registry,
slot map, or refcounts torn (`check_consistency` audits exactly that).

Sizing (docs/SERVING.md "Multi-LoRA serving"): pool bytes per slot =
sum over targets of 4*(K+N)*Rmax; slots should cover the hot working
set (steady-state misses ~0) while leaving HBM for the KV pool —
adapters are small next to KV, so err generous.
"""
from __future__ import annotations

import functools
import itertools
import sys
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..framework import monitor
from ..inference import kv_migrate
from ..inference.cache import BlockCacheManager
from ..inference.step_engine import StepEngine

__all__ = [
    "attach_adapters", "LoRAEngine", "AdapterPool", "lora_mm",
    "random_adapter", "AdapterError", "AdapterPoolExhausted",
    "AdapterRankError", "UnknownAdapterError",
]

# per-engine-kind LoRA target projections (the same gemm sites the
# weight-only quantization pass rewrites — serving/quant.py)
_LLAMA_KEYS = ("qkv_w", "o_w", "gate_up_w", "down_w")
_MLP_KEYS = ("w1", "w2")

DEFAULT_RANK_BUCKETS = (4, 8, 16)


class AdapterError(RuntimeError):
    """Base class for adapter-pool failures."""


class AdapterPoolExhausted(AdapterError):
    """Every device slot is leased or pinned — nothing can evict."""


class AdapterRankError(AdapterError):
    """Adapter rank exceeds the largest configured rank bucket."""


class UnknownAdapterError(AdapterError):
    """Lease/pin of a name the registry has never seen."""


def _chaos(site: str) -> None:
    """Chaos check via weak import (the `inference/cache.py` pattern):
    zero overhead unless `resilience.faults` is already loaded AND has
    an armed rule."""
    m = sys.modules.get("paddle_tpu.resilience.faults")
    if m is not None:
        m.check(site)


def lora_mm(x, w, base_mm):
    """The batched-gather LoRA epilogue behind the `_mm` dict-swap.

    `w` is `{"w": base_weight, "la": [S, K, R], "lb": [S, R, N],
    "ids": [T]}` (per-layer view — the stacked `[L, ...]` pools are
    sliced by `lax.scan` before this runs). `base_mm` recursively
    handles `w["w"]` — a dense array or a quantized `{"q"|"q4","s"}`
    dict, so int8/int4 bases keep their dequant-in-kernel gemm. The
    low-rank half gathers each TOKEN's adapter (`ids` come from
    `ragged_metadata`'s lane map) and runs two thin einsums; the zero
    slot's all-zero factors make no-adapter lanes exact."""
    import jax.numpy as jnp

    y = base_mm(x, w["w"])
    ids = w["ids"]
    a = jnp.take(w["la"], ids, axis=0).astype(x.dtype)     # [T, K, R]
    b = jnp.take(w["lb"], ids, axis=0).astype(x.dtype)     # [T, R, N]
    xa = jnp.einsum("...tk,tkr->...tr", x, a)
    return y + jnp.einsum("...tr,trn->...tn", xa, b)


def _swap_lora(params: dict, adapters: dict, ids) -> dict:
    """Rebuild the params pytree with every target weight replaced by
    the `{"w","la","lb","ids"}` epilogue dict `lora_mm` consumes."""
    out = dict(params)
    for key, pl in adapters.items():
        out[key] = {"w": params[key], "la": pl["a"], "lb": pl["b"],
                    "ids": ids}
    return out


def _lane_ids(q_lens, kv_lens, num_tokens, lane_slots):
    """Per-token adapter slot ids off the scalar-prefetch metadata —
    the IDENTICAL `ragged_metadata` call the inner ragged stack makes,
    so token->lane attribution can never diverge from attention's."""
    import jax.numpy as jnp

    from ..ops.pallas.paged_attention import ragged_metadata

    tok_lane, _ = ragged_metadata(q_lens, kv_lens, num_tokens)
    return lane_slots[jnp.maximum(tok_lane, 0)]


# ---- wrapper jit bodies -------------------------------------------------
# Each computes per-token ids, swaps the target weights, and calls the
# BASE engine's function `base` (its stack or its head, the Llama engine's
# or the MLP engine's, its static arguments already bound) under the
# shell's three wrappers (`ops/sampling`), so the tail's and the verify
# step's retrace counters bump at OUR trace time and the zero-recompile
# suite's assertions carry over unchanged. The
# `serving.lora.switch_retraces` bump is trace-time too: adapter ids are
# data, so any post-warmup bump means an adapter switch recompiled.
# `nlayers` is the leading axis of a stacked engine's weights (they ride
# `lax.scan` xs, and so must the ids), None for a flat one.

def _lora_stack(params, adapters, pools, lane_slots, tokens, q_lens,
                kv_lens, tables, *, base, nlayers):
    """The `stack` of `ops/sampling.with_tail` over the base engine's."""
    import jax.numpy as jnp

    monitor.inc("serving.lora.switch_retraces")  # trace-time only
    ids = _lane_ids(q_lens, kv_lens, tokens.shape[0], lane_slots)
    if nlayers is not None:
        ids = jnp.broadcast_to(ids[None, :], (nlayers, tokens.shape[0]))
    return base(_swap_lora(params, adapters, ids), pools, tokens, q_lens,
                kv_lens, tables)


def _lora_head(state, x, lane, *, base):
    """The `head` of `ops/sampling.with_tail` over the base engine's: a
    row's adapter is its lane's (the MLP engine's head weights are
    adapted; the Llama engine's head reads none of the swapped weights)."""
    params, adapters, _pools, lane_slots = state
    return base((_swap_lora(params, adapters, lane_slots[lane]),), x, lane)


# ---- the paged adapter pool --------------------------------------------

class AdapterPool:
    """Fixed device-resident A/B slots with refcounted leases, LRU
    eviction of idle adapters, and a host-side registry — the
    `BlockCacheManager` discipline applied to adapter weights.

    Slots `0..pool_slots-1` hold adapters; slot `pool_slots` is the
    reserved all-zero slot no lease may ever occupy. The pool mutates
    the owner engine's pool tensors through `owner._upload_slot` (one
    donated scatter per target tensor — fixed shapes, so repeated
    uploads never recompile)."""

    def __init__(self, owner, pool_slots: int,
                 rank_buckets: Tuple[int, ...]):
        if pool_slots < 1:
            raise ValueError(f"pool_slots must be >= 1, got {pool_slots}")
        if not rank_buckets or any(r < 1 for r in rank_buckets):
            raise ValueError(f"bad rank_buckets {rank_buckets!r}")
        self._owner = owner
        self.pool_slots = int(pool_slots)
        self.rank_buckets = tuple(sorted(set(int(r) for r in rank_buckets)))
        self.rank_max = self.rank_buckets[-1]
        # name -> padded host factors {key: (A [..,K,Rmax], B [..,Rmax,N])}
        self._registry: Dict[str, Dict[str, Tuple[np.ndarray, np.ndarray]]] = {}
        self._rank: Dict[str, int] = {}
        self._bucket: Dict[str, int] = {}
        self._slot_of: Dict[str, int] = {}       # resident name -> slot
        self._name_of: Dict[int, str] = {}       # slot -> resident name
        self._refs: Dict[str, int] = {}          # outstanding leases
        self._pinned: set = set()
        self._free: List[int] = list(range(self.pool_slots))
        self._tick = itertools.count(1)
        self._last_used: Dict[str, int] = {}
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    # -- registry --
    def bucket_for(self, rank: int) -> int:
        for b in self.rank_buckets:
            if rank <= b:
                return b
        raise AdapterRankError(
            f"adapter rank {rank} exceeds the largest rank bucket "
            f"{self.rank_max} (buckets {self.rank_buckets})")

    def register(self, name: str, adapters: Dict[str, Tuple], *,
                 allow_update: bool = False) -> int:
        """Register host-side factors under `name`. `adapters` maps each
        target key to `(A, B)` with shapes `[.., K, r]` / `[.., r, N]`
        (stacked engines carry the leading `[L]` axis). The rank pads to
        its bucket then to the pool's Rmax (zero columns/rows — exact).
        Returns the bucket rank. Registration is host-only: no device
        slot is touched until the first lease/pin."""
        if name in self._registry and not allow_update:
            raise AdapterError(f"adapter {name!r} already registered")
        if name in self._slot_of:
            raise AdapterError(
                f"adapter {name!r} is device-resident; release/evict "
                "before re-registering new weights")
        targets = self._owner._lora_targets
        if set(adapters) != set(targets):
            raise AdapterError(
                f"adapter {name!r} keys {sorted(adapters)} != engine "
                f"targets {sorted(targets)}")
        rank = None
        padded = {}
        for key, (a, b) in adapters.items():
            a = np.asarray(a, np.float32)
            b = np.asarray(b, np.float32)
            k, n = targets[key]
            r = a.shape[-1]
            if rank is None:
                rank = r
            if a.shape[-1] != rank or b.shape[-2] != rank:
                raise AdapterRankError(
                    f"adapter {name!r}: rank differs across targets "
                    f"({key}: A rank {a.shape[-1]}, B rank "
                    f"{b.shape[-2]}, expected {rank})")
            self.bucket_for(int(rank))   # over-Rmax rank: typed, pre-pad
            if a.shape[-2] != k or b.shape[-1] != n:
                raise AdapterError(
                    f"adapter {name!r} target {key}: A {a.shape} / "
                    f"B {b.shape} do not match (K={k}, N={n})")
            pad_a = np.zeros(a.shape[:-1] + (self.rank_max,), np.float32)
            pad_a[..., :rank] = a
            pad_b = np.zeros(b.shape[:-2] + (self.rank_max, n), np.float32)
            pad_b[..., :rank, :] = b
            padded[key] = (pad_a, pad_b)
        bucket = self.bucket_for(int(rank))
        self._registry[name] = padded
        self._rank[name] = int(rank)
        self._bucket[name] = bucket
        self._publish()
        return bucket

    def deregister(self, name: str) -> None:
        """Forget `name`. Refuses while leases or a pin are outstanding;
        an idle resident copy is evicted first."""
        self._require(name)
        if self._refs.get(name, 0) > 0:
            raise AdapterError(
                f"adapter {name!r} has {self._refs[name]} outstanding "
                "leases")
        if name in self._pinned:
            raise AdapterError(f"adapter {name!r} is pinned")
        if name in self._slot_of:
            self._evict(name)
        del self._registry[name], self._rank[name], self._bucket[name]
        self._refs.pop(name, None)
        self._last_used.pop(name, None)
        self._publish()

    # -- leases --
    def lease(self, name: str) -> int:
        """Take a refcounted lease; returns the device slot. Resident
        adapters are free (hit). A miss pays the priced load: evict an
        idle unpinned LRU adapter if no slot is free, then upload — or
        raise typed `AdapterPoolExhausted` when everything resident is
        leased/pinned. The `serve.adapter` chaos site fires BEFORE any
        mutation, so a fault here never tears the pool."""
        self._require(name)
        slot = self._slot_of.get(name)
        if slot is not None:
            self.hits += 1
            self._refs[name] = self._refs.get(name, 0) + 1
            self._last_used[name] = next(self._tick)
            return slot
        _chaos("serve.adapter")          # load/evict fault site
        slot = self._acquire_slot()
        try:
            self._owner._upload_slot(slot, self._registry[name])
        except Exception:
            self._free.append(slot)      # a failed upload never leaks
            raise
        self.misses += 1
        monitor.inc("serving.lora.miss_loads")
        self._slot_of[name] = slot
        self._name_of[slot] = name
        self._refs[name] = self._refs.get(name, 0) + 1
        self._last_used[name] = next(self._tick)
        self._publish()
        return slot

    def release(self, name: str) -> None:
        """Drop one lease. The adapter STAYS resident (an LRU eviction
        candidate) — the common re-lease is then a free hit."""
        self._require(name)
        refs = self._refs.get(name, 0)
        if refs <= 0:
            raise AdapterError(f"adapter {name!r} has no lease to release")
        self._refs[name] = refs - 1
        self._last_used[name] = next(self._tick)

    def pin(self, name: str) -> int:
        """Make (and keep) `name` resident without a refcount: a pinned
        adapter never LRU-evicts. Returns its slot."""
        self._require(name)
        slot = self._slot_of.get(name)
        if slot is None:
            slot = self.lease(name)
            # pin holds residency, not a lease — give the count back
            self._refs[name] -= 1
        self._pinned.add(name)
        self._publish()
        return slot

    def unpin(self, name: str) -> None:
        self._require(name)
        self._pinned.discard(name)
        self._publish()

    # -- queries --
    def is_registered(self, name: str) -> bool:
        return name in self._registry

    def is_resident(self, name: str) -> bool:
        return name in self._slot_of

    def resident_names(self) -> List[str]:
        return sorted(self._slot_of)

    def slot_of(self, name: str) -> Optional[int]:
        return self._slot_of.get(name)

    def leases(self) -> int:
        return sum(self._refs.values())

    def rank_of(self, name: str) -> int:
        self._require(name)
        return self._rank[name]

    def stats(self) -> Dict[str, object]:
        return {
            "pool_slots": self.pool_slots,
            "rank_buckets": list(self.rank_buckets),
            "rank_max": self.rank_max,
            "registered": len(self._registry),
            "resident_adapters": len(self._slot_of),
            "free_slots": len(self._free),
            "pinned": len(self._pinned),
            "leases": self.leases(),
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
        }

    def check_consistency(self) -> None:
        """Audit the pool's invariants (the chaos smoke runs this after
        every injected fault): slot maps are mutually inverse and
        disjoint from the free list, every slot is accounted exactly
        once, the zero slot is never allocated, refcounts are
        non-negative and only on resident adapters, pins are resident."""
        assert self._name_of == {s: n for n, s in self._slot_of.items()}, \
            "slot maps diverged"
        used = set(self._slot_of.values())
        assert len(used) == len(self._slot_of), "duplicate slot assignment"
        assert not (used & set(self._free)), "slot both used and free"
        assert len(self._free) == len(set(self._free)), \
            "duplicate free slot"
        assert used | set(self._free) == set(range(self.pool_slots)), \
            "slot accounting does not cover the pool"
        assert self.pool_slots not in used, "zero slot allocated"
        for name, refs in self._refs.items():
            assert refs >= 0, f"negative refcount on {name!r}"
            assert refs == 0 or name in self._slot_of, \
                f"lease on non-resident adapter {name!r}"
        assert self._pinned <= set(self._slot_of), "pin on non-resident"

    # -- internals --
    def _require(self, name: str) -> None:
        if name not in self._registry:
            raise UnknownAdapterError(f"adapter {name!r} not registered")

    def _acquire_slot(self) -> int:
        if self._free:
            return self._free.pop()
        idle = [n for n, s in self._slot_of.items()
                if self._refs.get(n, 0) == 0 and n not in self._pinned]
        if not idle:
            raise AdapterPoolExhausted(
                f"all {self.pool_slots} adapter slots leased or pinned")
        victim = min(idle, key=lambda n: self._last_used.get(n, 0))
        self._evict(victim)
        return self._free.pop()

    def _evict(self, name: str) -> None:
        slot = self._slot_of.pop(name)
        del self._name_of[slot]
        self._free.append(slot)
        self.evictions += 1
        monitor.inc("serving.lora.evictions")
        self._publish()

    def _publish(self) -> None:
        monitor.set_gauge("serving.lora.resident_adapters",
                          len(self._slot_of))
        monitor.set_gauge("serving.lora.registered_adapters",
                          len(self._registry))


# ---- the engine wrapper -------------------------------------------------

class LoRAEngine(kv_migrate.PagedPools, StepEngine):
    """`EngineCore` over a base engine plus a paged adapter pool: the
    shell's dispatch surfaces (`inference.step_engine.StepEngine`) re-jitted
    with the per-lane LoRA epilogue, fresh paged bookkeeping (own
    `BlockCacheManager` + zeroed KV pools — the base engine's donated
    executables stay valid; `copy_kv_block` and KV migration are the
    base's pure block executables over THIS engine's pools), and the
    observability hooks (`cost_card_args`, `quant_info`, `lora_info`)."""

    # the adapters and the lane slots lead a step's arguments beside the
    # params and the pools (`cost_card_args` hands them on)
    LEADING = ("params", "_adapters", "pools", "_lane_slots")

    def __init__(self, base, pool_slots: int = 8,
                 rank_buckets: Tuple[int, ...] = DEFAULT_RANK_BUCKETS):
        import jax
        import jax.numpy as jnp

        if hasattr(base, "adapter_pool"):
            raise AdapterError(
                "engine already carries an adapter pool — attach_adapters "
                "wraps a base engine exactly once")
        if hasattr(base, "tpinfo"):
            raise AdapterError(
                "attach_adapters wraps the single-chip engine; shard the "
                "LoRA-wrapped engine instead of wrapping the shard")
        self.base = base
        self.max_batch_size = base.max_batch_size
        self.block_size = base.block_size
        self.kv_bits = int(getattr(base, "kv_bits", 16))
        self.weight_only = getattr(base, "weight_only", None)
        if hasattr(base, "vocab_size"):
            self.vocab_size = base.vocab_size
        # fresh paged bookkeeping + zeroed pools, same geometry: donating
        # the base's cache buffers from NEW executables would invalidate
        # the base engine's own jits (the ShardedEngine discipline)
        m = base.manager
        self.manager = BlockCacheManager(m.num_blocks, m.block_size,
                                         m.max_blocks_per_seq)
        params = getattr(base, "params", None)
        if not isinstance(params, dict):
            raise AdapterError(
                f"{type(base).__name__} has no params dict to adapt")
        self.params = params
        if "qkv_w" in params:
            self._kind = "llama"
            cfg = base.config
            self.config = cfg
            nh, kvh, d = (cfg.num_attention_heads,
                          cfg.num_key_value_heads, cfg.head_dim)
            H, inter = cfg.hidden_size, cfg.intermediate_size
            self._nlayers = cfg.num_hidden_layers
            self._lora_targets = {
                "qkv_w": (H, (nh + 2 * kvh) * d),
                "o_w": (nh * d, H),
                "gate_up_w": (H, 2 * inter),
                "down_w": (inter, H),
            }
        elif "w1" in params:
            self._kind = "mlp"
            d = base._init_kwargs["hidden"]
            self._nlayers = None
            self._lora_targets = {
                "w1": (2 * d, 2 * d),
                "w2": (2 * d, base.vocab_size),
            }
        else:
            raise AdapterError(
                f"{type(base).__name__}: unrecognized parameter layout "
                "(expected llama projection keys or MLP w1/w2)")

        self.adapter_pool = AdapterPool(self, pool_slots, rank_buckets)
        self.zero_slot = self.adapter_pool.pool_slots
        S, R = self.zero_slot + 1, self.adapter_pool.rank_max
        self._adapters = {}
        for key, (k, n) in self._lora_targets.items():
            if self._kind == "llama":
                a = jnp.zeros((self._nlayers, S, k, R), jnp.float32)
                b = jnp.zeros((self._nlayers, S, R, n), jnp.float32)
            else:
                a = jnp.zeros((S, k, R), jnp.float32)
                b = jnp.zeros((S, R, n), jnp.float32)
            self._adapters[key] = {"a": a, "b": b}
        # slot scatter: ONE traced executable per pool-tensor shape
        # (slot is a traced scalar — uploads never recompile)
        if self._kind == "llama":
            self._slot_set = jax.jit(lambda p, u, s: p.at[:, s].set(u),
                                     donate_argnums=(0,))
        else:
            self._slot_set = jax.jit(lambda p, u, s: p.at[s].set(u),
                                     donate_argnums=(0,))
        # every lane starts on the zero slot (base model)
        self._lane_slots = np.full((self.max_batch_size,), self.zero_slot,
                                   np.int32)
        self._default_lease: Optional[str] = None

        if self._kind == "llama":
            from ..inference import llama_runner as lr

            stack, head = (
                functools.partial(fn, cfg=lr._StaticCfg(base.config))
                for fn in (lr._ragged_stack, lr._head))
        else:
            from .engine import _mlp_head, _mlp_ragged_stack

            stack = functools.partial(_mlp_ragged_stack,
                                      block_size=base.block_size)
            head = _mlp_head
        self.pools = jax.tree.map(jnp.zeros_like, base.pools)
        self._build_programs(
            functools.partial(_lora_stack, base=stack,
                              nlayers=self._nlayers),
            functools.partial(_lora_head, base=head))
        # the base's block executables are pure: over THIS engine's
        # pools they cost no extra trace
        self._copy_block, self._kv_gather, self._kv_scatter = (
            base._copy_block, base._kv_gather, base._kv_scatter)
        self._mig_header = base._mig_header
        self._slab_names = base._slab_names
        gb = getattr(base.manager, "bytes_per_block", None)
        if gb:
            self.manager.set_kv_geometry(gb, self.kv_bits)

    # -- adapter surface --
    def _upload_slot(self, slot: int, padded: Dict[str, Tuple]) -> None:
        """Scatter one registered adapter's padded factors into `slot`
        across every target pool tensor (donated, fixed-shape)."""
        s = np.int32(slot)
        for key, (a, b) in padded.items():
            pl = self._adapters[key]
            pl["a"] = self._slot_set(pl["a"], a, s)
            pl["b"] = self._slot_set(pl["b"], b, s)

    def set_lane_adapters(self, slots: np.ndarray) -> None:
        """Install the per-lane adapter-slot vector the next dispatch
        carries ([max_batch_size] int32; the scheduler rebuilds it every
        ragged/verify round). Data, not shape: never retraces."""
        slots = np.asarray(slots, np.int32)
        if slots.shape != (self.max_batch_size,):
            raise ValueError(
                f"lane_slots must be [{self.max_batch_size}], got "
                f"{slots.shape}")
        self._lane_slots = slots

    def use_adapter(self, name: Optional[str]) -> None:
        """Point EVERY lane at `name` (leased; `None` returns all lanes
        to the base model) — the single-model harness path
        (`greedy_agreement`, dedicated-engine parity runs)."""
        if self._default_lease is not None:
            self.adapter_pool.release(self._default_lease)
            self._default_lease = None
        if name is None:
            slot = self.zero_slot
        else:
            slot = self.adapter_pool.lease(name)
            self._default_lease = name
        self._lane_slots = np.full((self.max_batch_size,), slot, np.int32)

    def lora_info(self) -> Dict[str, object]:
        """Pool-state surface the serving metrics publish at bind time
        (`ServingMetrics.on_lora` -> `serving.lora.*` gauges)."""
        return self.adapter_pool.stats()

    # -- observability / lifecycle --
    def quant_info(self) -> Dict[str, object]:
        info = getattr(self.base, "quant_info", None)
        return dict(info()) if info is not None else {
            "wbits": 16, "kv_bits": self.kv_bits,
            "kv_bytes_per_token": None}

    def kv_bytes_per_token(self) -> float:
        return self.base.kv_bytes_per_token()

    def respawn(self) -> "LoRAEngine":
        """Watchdog `engine_factory` hook: rebuild the base through ITS
        factory, re-wrap, and carry the host-side registry over (pins
        re-pin; device residency rebuilds lazily on the next leases —
        the old pool's device state died with the old engine)."""
        factory = getattr(self.base, "respawn", None)
        if factory is None:
            raise AdapterError(
                f"{type(self.base).__name__} has no respawn()")
        fresh = LoRAEngine(factory(),
                           pool_slots=self.adapter_pool.pool_slots,
                           rank_buckets=self.adapter_pool.rank_buckets)
        pool = self.adapter_pool
        for name, padded in pool._registry.items():
            fresh.adapter_pool._registry[name] = padded
            fresh.adapter_pool._rank[name] = pool._rank[name]
            fresh.adapter_pool._bucket[name] = pool._bucket[name]
        for name in pool._pinned:
            fresh.adapter_pool.pin(name)
        fresh.adapter_pool._publish()
        return fresh


def attach_adapters(engine, pool_slots: int = 8,
                    rank_buckets: Tuple[int, ...] = DEFAULT_RANK_BUCKETS
                    ) -> LoRAEngine:
    """Wrap a built engine for multi-LoRA serving (see `LoRAEngine`).

    `pool_slots`: device-resident adapter slots (the working set that
    serves without upload traffic). `rank_buckets`: allowed padded
    ranks, ascending; the largest is the pool's physical rank axis."""
    return LoRAEngine(engine, pool_slots=pool_slots,
                      rank_buckets=rank_buckets)


def random_adapter(engine, rank: int = 4, seed: int = 0,
                   scale: float = 0.05) -> Dict[str, Tuple]:
    """Seed-deterministic host-side factors for every target of a
    LoRA-wrapped engine — the test/bench fixture (a real deployment
    registers factors from fine-tuning checkpoints)."""
    rng = np.random.default_rng(seed)
    out = {}
    L = engine._nlayers
    for key, (k, n) in engine._lora_targets.items():
        if L is not None:
            a = rng.normal(0, scale, (L, k, rank))
            b = rng.normal(0, scale, (L, rank, n))
        else:
            a = rng.normal(0, scale, (k, rank))
            b = rng.normal(0, scale, (rank, n))
        out[key] = (a.astype(np.float32), b.astype(np.float32))
    return out
