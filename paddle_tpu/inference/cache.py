"""Paged KV-cache block management (host side).

The serving analog of the reference's block-cache machinery around
`block_multihead_attention` (`paddle/phi/kernels/fusion/gpu/
block_multi_head_attention_kernel.cu`): device memory is a pool of
fixed-size blocks; each sequence holds a block table mapping logical block
index → physical block id. Allocation/free is O(1) host bookkeeping —
device arrays never reallocate, which keeps XLA programs static-shaped.

Physical blocks are REFERENCE COUNTED: several leases (sequences, or the
radix prefix tree in `inference/prefix_cache.py`) may point at the same
physical block, which is how a shared system prompt's KV is prefilled
once and attended by every request that carries it. A block returns to
the free list only when its last lease drops. Writes into a shared block
trigger COPY-ON-WRITE (`append_tokens`): the writer gets a private copy
(the optional `cow_hook` copies the device-side KV), every other lease
keeps the original bytes — a divergent `append` after a `trim` into a
shared region can never corrupt a sibling's context.

GROUPS OF LAYERS: a model whose layer kinds keep different amounts of
context (full attention beside a sliding window) gives the manager further
block groups, each with a block-id space, a free list and a table a
sequence of its own; a windowed group gives a block back once it lies wholly
behind the window of the committed length (`_BlockGroup`). Sharing,
copy-on-write and the reclaimer are the FIRST group's; a manager of one
group, which is every other engine's, is all of the above and nothing more.

A STATE GROUP (`BlockCacheManager.state_group`): a model whose layers keep
one fixed-size recurrent state a sequence, not keys and values a token, has
its memory reckoned in SLOTS. A sequence holds exactly one for its whole
life, however long it grows (`append_tokens` takes nothing, the slot goes
back at `free`), its table is the slot (`block_table_array` is `[n, 1]`),
and what bounds its length is the engine's position table alone. A state
cannot be shortened: `trim` below a sequence's own length raises
`StateNotTrimmable`. It is the same bookkeeping with one block a sequence
that spans every position, so everything that counts blocks counts slots.

Exhaustion is a *scheduling event*, not a crash: `allocate`/`append_token`
raise the typed `KVCacheExhausted` (pool empty) or `SequenceTooLong`
(per-sequence block cap), which the continuous-batching scheduler
(`paddle_tpu.serving.scheduler`) consumes to queue or preempt requests.
Before raising `KVCacheExhausted` the manager first asks its registered
`reclaimer` (the prefix tree) to evict unpinned cached blocks — cached
prefixes are capacity opportunistically held, never capacity denied.
"""
from __future__ import annotations

import sys as _sys
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

__all__ = ["BlockCacheManager", "KVCacheExhausted", "SequenceTooLong",
           "StateNotTrimmable"]


def _chaos(site: str) -> None:
    """`serve.cache` fault-injection site (resilience.faults). Active
    only when the registry module is already loaded AND armed — cache
    ops in processes that never touch fault injection pay one
    sys.modules lookup, no import."""
    mod = _sys.modules.get("paddle_tpu.resilience.faults")
    if mod is not None:
        mod.check(site)


def _monitor_inc(name: str, n: int = 1) -> None:
    """Weak monitor bump (same sys.modules guard as `_chaos`): cache.py
    stays import-light, but COW copies are a serving-level counter
    (`serving.prefix_cache.cow_copies`) when the monitor is loaded."""
    mod = _sys.modules.get("paddle_tpu.framework.monitor")
    if mod is not None:
        try:
            mod.inc(name, n)
        except Exception:
            pass


class KVCacheExhausted(RuntimeError):
    """The physical block pool has no free block.

    Recoverable by design: the serving scheduler catches this to delay
    admission or preempt a running sequence (blocks come back via `free`).
    Subclasses RuntimeError so pre-existing callers keep working.
    """

    def __init__(self, need: int, free: int, total: int,
                 group: Optional[str] = None):
        self.need = need
        self.free = free
        self.total = total
        self.group = group       # which block group ran out (None: the one)
        pool = "pool" if group is None else f"pool of group {group!r}"
        super().__init__(
            f"KV cache {pool} exhausted: need {need} block(s), "
            f"{free}/{total} free")


class SequenceTooLong(ValueError):
    """A single sequence asked for more than `max_blocks_per_seq` blocks.

    Unlike `KVCacheExhausted` this is not recoverable by waiting — the
    request can never fit and must be rejected (or its generation capped).
    Subclasses ValueError so pre-existing callers keep working.
    """

    def __init__(self, need_blocks: int, max_blocks: int,
                 group: Optional[str] = None):
        self.need_blocks = need_blocks
        self.max_blocks = max_blocks
        self.group = group
        where = "" if group is None else f" in group {group!r}"
        super().__init__(
            f"sequence needs {need_blocks} blocks{where} > "
            f"max_blocks_per_seq {max_blocks}")


class StateNotTrimmable(ValueError):
    """`trim` asked a state group to forget tokens: a recurrent state has
    taken them in and cannot give them back. The caller restarts the
    sequence from its tokens instead (`serving/scheduler.py`)."""

    def __init__(self, seq_id, have: int, want: int, group: Optional[str]):
        self.seq_id, self.have, self.want, self.group = \
            seq_id, have, want, group
        super().__init__(
            f"sequence {seq_id}: the state group {group!r} holds {have} "
            f"tokens and cannot be trimmed to {want}")


class _BlockGroup:
    """One FURTHER group of layers beside the manager's first: a block-id
    space, a free list and a table a sequence of its own. The layers of a
    group share one pool on the device (`[layers_in_group, num_blocks,
    ...]`), so a model whose layers keep different amounts of context (full
    attention beside a sliding window) has one group a kind.

    `window`: how many positions a query of these layers still sees, its
    own among them (None: all). A windowed group gives a block back once it
    lies WHOLLY behind the window of the next query the sequence can ever
    ask: released by the length the sequence had BEFORE the append that is
    being accounted, less what the caller says is still in flight of it
    (`append_tokens(in_flight=)`: what is committed), never by the appended
    length, so a `trim` back to any length since (a rejected speculation,
    the rollback of a failed round and of the one launched behind it)
    needs no block that is gone. A released entry of the
    table holds -1 and comes out of `block_table_array` as padding; the
    attention kernel starts its page walk behind it and never reads it.

    No sharing here: a block is free or leased by one sequence (the radix
    prefix cache is refused over such an engine)."""

    def __init__(self, name: str, num_blocks: int, block_size: int,
                 window: Optional[int]):
        if window is not None and window < 1:
            raise ValueError(f"group {name!r}: window {window} < 1")
        self.name = name
        self.num_blocks = int(num_blocks)
        self.block_size = block_size
        self.window = window
        self._free: List[int] = list(range(self.num_blocks - 1, -1, -1))
        self._tables: Dict[int, List[int]] = {}
        self._head: Dict[int, int] = {}     # leading entries given back
        self.bytes_per_block: Optional[int] = None
        self.kv_bits = 16
        self.released = 0                   # blocks given back behind windows

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    def _covering(self, num_tokens: int) -> int:
        """Blocks that cover `num_tokens` positions (a sequence holds one
        from its first token on)."""
        return max(1, -(-num_tokens // self.block_size))

    def first_needed(self, num_tokens: int) -> int:
        """The first logical block a query at position `num_tokens` (the
        next one of a sequence that long) still sees."""
        if self.window is None:
            return 0
        return max(0, num_tokens - self.window + 1) // self.block_size

    def blocks_needed(self, num_tokens: int, step: int = 1) -> int:
        """Blocks a sequence of `num_tokens` holds at most at once when it
        grows `step` tokens an append."""
        total = self._covering(num_tokens)
        if self.window is None:
            return total
        return min(total, (self.window + step - 2) // self.block_size + 2)

    def seq_blocks(self, seq_id) -> int:
        return len(self._tables.get(seq_id, ())) - self._head.get(seq_id, 0)

    def blocks_of(self, seq_id) -> Tuple[int, ...]:
        return tuple(self._tables.get(seq_id, ())[self._head.get(seq_id, 0):])

    def can_allocate(self, num_tokens: int) -> bool:
        return len(self._free) >= self._covering(num_tokens)

    def check_allocate(self, num_tokens: int) -> None:
        need = self._covering(num_tokens)
        if need > len(self._free):
            raise KVCacheExhausted(need, len(self._free), self.num_blocks,
                                   self.name)

    def allocate(self, seq_id, num_tokens: int) -> None:
        self._tables[seq_id] = [self._free.pop()
                                for _ in range(self._covering(num_tokens))]
        self._head[seq_id] = 0

    def _plan(self, seq_id, old_len: int, new_len: int):
        table = self._tables[seq_id]
        head = max(self._head[seq_id], min(self.first_needed(old_len),
                                           len(table)))
        need = self._covering(new_len) - len(table)
        return table, head, max(need, 0)

    def check_append(self, seq_id, old_len: int, new_len: int) -> None:
        """Raises what `append` would run into; changes nothing."""
        _table, head, need = self._plan(seq_id, old_len, new_len)
        free = len(self._free) + head - self._head[seq_id]
        if need > free:
            raise KVCacheExhausted(need, free, self.num_blocks, self.name)

    def append(self, seq_id, old_len: int, new_len: int) -> None:
        """Give back what lies behind the window at `old_len`, then lease
        up to `new_len` (the caller ran `check_append`)."""
        table, head, need = self._plan(seq_id, old_len, new_len)
        gone = head - self._head[seq_id]
        if gone:
            for i in range(self._head[seq_id], head):
                self._free.append(table[i])
                table[i] = -1
            self._head[seq_id] = head
            self.released += gone
            _monitor_inc("serving.kv.window_blocks_released", gone)
        for _ in range(need):
            table.append(self._free.pop())

    def check_trim(self, seq_id, num_tokens: int) -> None:
        if self.first_needed(num_tokens) < self._head[seq_id]:
            raise ValueError(
                f"trim to {num_tokens} tokens needs block "
                f"{self.first_needed(num_tokens)} of group {self.name!r}, "
                f"released behind its window of {self.window}")

    def trim(self, seq_id, num_tokens: int) -> None:
        table = self._tables[seq_id]
        keep = max(self._covering(num_tokens), self._head[seq_id])
        while len(table) > keep:
            self._free.append(table.pop())

    def free(self, seq_id) -> None:
        head = self._head.pop(seq_id)
        self._free.extend(self._tables.pop(seq_id)[head:])

    def utilization(self, guard_ids) -> float:
        guard = sum(self.seq_blocks(sid) for sid in guard_ids)
        used = self.num_blocks - len(self._free) - guard
        return max(0, used) / max(self.num_blocks - guard, 1)

    def check_consistency(self) -> None:
        free = self._free
        assert len(free) == len(set(free)), \
            f"group {self.name}: duplicate free-list entry"
        live = [b for sid, t in self._tables.items()
                for b in t[self._head[sid]:]]
        assert len(live) == len(set(live)), \
            f"group {self.name}: a block leased twice"
        assert not set(free) & set(live), \
            f"group {self.name}: block both free and leased"
        assert len(free) + len(live) == self.num_blocks, \
            f"group {self.name}: pool accounting broken: {len(free)} free " \
            f"+ {len(live)} live != {self.num_blocks}"
        for sid, t in self._tables.items():
            head = self._head[sid]
            assert all(b == -1 for b in t[:head]) and \
                all(b >= 0 for b in t[head:]), \
                f"group {self.name}: seq {sid}'s released entries are " \
                "not its leading ones"


class BlockCacheManager:
    """Block tables over one group of layers or several.

    The FIRST group is what every engine has: `num_blocks` blocks that keep
    every token, refcounted, shareable, copy-on-write (all of the module
    docstring). `further_groups` — `(name, num_blocks, window)` each, given
    by an engine whose layer types keep different amounts of context —
    add block-id spaces of their own (`_BlockGroup`): a sequence then has
    one table a group, `allocate`/`append_tokens`/`trim`/`free` move all of
    them or none, and the calls that ask about blocks take `group=` (0, the
    first, by default). `block_table_array` lays a sequence's tables side
    by side, `[n, n_groups * max_blocks_per_seq]`: the one 2-D table every
    engine's step takes, of which an engine with several groups reads its
    own columns. With no further group every call is what it always was.
    """

    def __init__(self, num_blocks: int, block_size: int,
                 max_blocks_per_seq: int, name: Optional[str] = None,
                 further_groups=()):
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.max_blocks_per_seq = max_blocks_per_seq
        self.name = name               # the first group's, for messages
        self.state = False             # `state_group`: slots, not blocks
        self._further: Tuple[_BlockGroup, ...] = tuple(
            _BlockGroup(n, nb, block_size, w) for n, nb, w in further_groups)
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        self._tables: Dict[int, List[int]] = {}
        self._lens: Dict[int, int] = {}
        # physical block -> lease count, for every block OUT of the free
        # list. A plain (no-sharing) workload keeps every count at 1 and
        # pays one dict write per block transition.
        self._refs: Dict[int, int] = {}
        self._guard_ids: set = set()   # guard seqs, so utilization() is
        #                                O(#guards) on the admission path
        # copy-on-write plumbing: `cow_hook(src, dst)` copies the
        # device-side KV of one physical block (engines provide it via
        # `copy_kv_block`); None = bookkeeping-only COW (tests, engines
        # without device state). `reclaimer` is asked to free unpinned
        # cached blocks before KVCacheExhausted surfaces.
        self._cow_hook: Optional[Callable[[int, int], None]] = None
        self._reclaimer = None
        self.cow_copies = 0            # lifetime COW count (this manager)
        # KV byte geometry (engines register it via `set_kv_geometry`):
        # what one block costs in HBM and at how many bits per KV
        # element — fragmentation() and the OOM forensics dumps report
        # it so capacity claims (int8 KV => ~2x blocks per HBM byte)
        # are auditable from telemetry, not inferred from configs
        self._bytes_per_block: Optional[int] = None
        self._kv_bits: int = 16
        # memory observability registry (weak; same sys.modules guard
        # pattern as _chaos — processes that never import observability
        # pay one dict lookup at construction, nothing per op)
        mod = _sys.modules.get("paddle_tpu.observability.memory")
        if mod is not None:
            try:
                mod.register_kv_manager(self)
            except Exception:
                pass

    @classmethod
    def state_group(cls, slots: int, context_tokens: int,
                    name: str = "state") -> "BlockCacheManager":
        """A manager whose one group is of kind STATE (module docstring):
        `slots + 1` ids (one for a scheduler's guard), one a sequence, each
        good for `context_tokens` positions. The kind comes from the engine
        that builds it, as a further group's window does."""
        mgr = cls(slots + 1, context_tokens, 1, name=name)
        mgr.state = True
        return mgr

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def num_seqs(self) -> int:
        return len(self._tables)

    # ---- groups of layers ----
    @property
    def n_groups(self) -> int:
        return 1 + len(self._further)

    @property
    def group_names(self) -> Tuple[str, ...]:
        return (self.name or "kv",) + tuple(g.name for g in self._further)

    @property
    def table_width(self) -> int:
        """Columns of `block_table_array`: every group's table, side by
        side."""
        return self.n_groups * self.max_blocks_per_seq

    def group_window(self, group: int = 0) -> Optional[int]:
        return self._further[group - 1].window if group else None

    def num_blocks_of(self, group: int = 0) -> int:
        return self._further[group - 1].num_blocks if group \
            else self.num_blocks

    def free_blocks_of(self, group: int = 0) -> int:
        return self._further[group - 1].free_blocks if group \
            else len(self._free)

    def blocks_released(self, group: int) -> int:
        """Blocks a windowed group has given back behind its window since
        the manager was built."""
        return self._further[group - 1].released

    # ---- refcounted block primitives ----
    def _take_free(self) -> int:
        b = self._free.pop()
        self._refs[b] = 1
        return b

    def incref(self, block: int) -> None:
        """Add one lease to an already-allocated physical block (the
        prefix tree pins published blocks this way)."""
        n = self._refs[block] + 1
        self._refs[block] = n
        if n == 2 and self._reclaimer is not None:
            # 1 -> 2: a cached block just got a second lease (pinned)
            self._note_ref(block, n)

    def release_block(self, block: int) -> None:
        """Drop one lease; the block returns to the free pool when the
        last lease goes (the prefix tree's eviction path)."""
        self._release(block)

    def _release(self, b: int) -> None:
        n = self._refs[b] - 1
        if n:
            self._refs[b] = n
            if n == 1 and self._reclaimer is not None:
                # 2 -> 1: the cache may be the only lease left (unpinned)
                self._note_ref(b, n)
        else:
            del self._refs[b]
            self._free.append(b)

    def _note_ref(self, block: int, n: int) -> None:
        """Tell the reclaimer a block crossed the pinned/unpinned
        boundary — how `RadixPrefixCache.reclaimable()` stays O(1) on
        the per-submit admission path instead of walking the tree."""
        try:
            self._reclaimer.note_ref(block, n)
        except Exception:
            pass

    def ref_count(self, block: int) -> int:
        """Current lease count of a physical block (0 = free)."""
        return self._refs.get(block, 0)

    def set_cow_hook(self, hook: Optional[Callable[[int, int], None]]):
        """`hook(src_block, dst_block)` copies device KV on COW."""
        self._cow_hook = hook

    def set_kv_geometry(self, bytes_per_block: int,
                        kv_bits: int = 16, group: int = 0) -> None:
        """Register the device-side byte cost of one pool block of
        `group` (across K+V, the group's layers, INCLUDING any
        quantization scale planes) and the KV element width. Engines call
        this at construction (`inference/kv_quant.kv_bytes_per_block`
        owns the formula)."""
        if group:
            g = self._further[group - 1]
            g.bytes_per_block, g.kv_bits = int(bytes_per_block), int(kv_bits)
            return
        self._bytes_per_block = int(bytes_per_block)
        self._kv_bits = int(kv_bits)

    def bytes_per_block_of(self, group: int = 0) -> Optional[int]:
        return self._further[group - 1].bytes_per_block if group \
            else self._bytes_per_block

    @property
    def kv_bits(self) -> int:
        return self._kv_bits

    @property
    def bytes_per_block(self) -> Optional[int]:
        return self._bytes_per_block

    def set_reclaimer(self, reclaimer) -> None:
        """Register the cache-eviction authority: an object with
        `evict(n_blocks) -> int` (free at least n unpinned cached
        blocks, best-effort) and `reclaimable() -> int`. Called under
        pool pressure BEFORE `KVCacheExhausted` is raised."""
        self._reclaimer = reclaimer

    def reclaimable_blocks(self) -> int:
        """Blocks held only by the cache tree (refcount 1 from the
        reclaimer) — free-on-demand capacity."""
        if self._reclaimer is None:
            return 0
        try:
            return int(self._reclaimer.reclaimable())
        except Exception:
            return 0

    def _ensure_free(self, need: int) -> None:
        """Best-effort: reclaim cached blocks until `need` are free.
        Never raises — the caller re-checks and raises the typed
        exhaustion itself."""
        if need > len(self._free) and self._reclaimer is not None:
            try:
                self._reclaimer.evict(need - len(self._free))
            except Exception:
                pass

    @staticmethod
    def _is_guard(seq_id) -> bool:
        """Guard/infrastructure sequences hold sacrificial padding blocks
        (the serving scheduler leases them under negative seq ids); they
        are capacity overhead, not load."""
        return isinstance(seq_id, int) and seq_id < 0

    def _guard_blocks(self) -> int:
        return sum(len(self._tables[sid]) for sid in self._guard_ids)

    def utilization(self, group: int = 0) -> float:
        """Fraction of `group`'s usable pool currently held by REAL demand.

        Counted over PHYSICAL blocks — a block shared by N leases is one
        block of pressure, not N (per-lease summing would inflate past
        1.0 under prefix sharing and false-trip the admission KV
        watermarks). Guard blocks are excluded from both sides of the
        ratio (leased forever = a permanent floor, not load), and so are
        cache-held reclaimable blocks: the prefix tree surrenders them
        on demand, so they are free capacity wearing a cache hat — the
        watermark ladder must not shed over them."""
        if group:
            return self._further[group - 1].utilization(self._guard_ids)
        guard = self._guard_blocks()
        used = self.num_blocks - len(self._free) - guard \
            - self.reclaimable_blocks()
        return max(0, used) / max(self.num_blocks - guard, 1)

    def fragmentation(self, group: int = 0) -> Dict:
        """Fragmentation view of the pool (observability/memory.py); of a
        further group, what such a group has (no sharing, no per-sequence
        waste worth a line: its blocks are a window's, not a context's):

        - per-sequence leased-vs-used blocks and token counts (`per_seq`);
        - token-level internal fragmentation: leased block capacity vs
          tokens actually stored (partial last blocks); under sharing the
          ratio is clamped at 0 (two sequences packing one physical block
          is negative waste);
        - sharing: `leased_blocks` counts a shared physical block ONCE
          (`lease_count` keeps the per-lease sum, `shared_blocks` the
          number of physical blocks with >1 lease);
        - free-list shape: largest contiguous run of free block ids and
          the fragmentation ratio `1 - largest_run / free` (0.0 = one
          clean run, →1.0 = free space shattered into single blocks —
          irrelevant to correctness here because blocks are
          position-indexed, but the predictor of allocator behavior on
          backends with contiguous KV layouts).
        """
        if group:
            g = self._further[group - 1]
            leased = g.num_blocks - g.free_blocks
            bpb = g.bytes_per_block
            return {
                "group": g.name, "window": g.window,
                "num_blocks": g.num_blocks, "block_size": self.block_size,
                "kv_bits": g.kv_bits, "bytes_per_block": bpb,
                "pool_bytes": bpb * g.num_blocks if bpb else None,
                "leased_bytes": bpb * leased if bpb else None,
                "free_blocks": g.free_blocks, "leased_blocks": leased,
                "released_blocks": g.released,
                "utilization": round(g.utilization(self._guard_ids), 4),
            }
        free = sorted(self._free)
        largest_run = run = 0
        prev = None
        for b in free:
            run = run + 1 if prev is not None and b == prev + 1 else 1
            largest_run = max(largest_run, run)
            prev = b
        per_seq = {}
        physical: set = set()
        lease_count = used = tokens = guard = 0
        for sid, table in self._tables.items():
            if self._is_guard(sid):
                guard += len(table)
                continue
            n_leased = len(table)
            n_used = min(n_leased, self.blocks_needed(self._lens[sid]))
            per_seq[sid] = {"leased_blocks": n_leased,
                            "used_blocks": n_used,
                            "tokens": self._lens[sid]}
            physical.update(table)
            lease_count += n_leased
            used += n_used
            tokens += self._lens[sid]
        leased = len(physical)
        capacity_tokens = leased * self.block_size
        bpb = self._bytes_per_block
        return {
            "num_blocks": self.num_blocks,
            "block_size": self.block_size,
            # byte-auditable capacity (None until an engine registers
            # its geometry): pool/leased bytes derive from the SAME
            # bytes_per_block the engine allocated with, so the int8-KV
            # "2x sequences per HBM byte" claim reads straight off the
            # fragmentation snapshot and every OOM forensics dump
            "kv_bits": self._kv_bits,
            "bytes_per_block": bpb,
            "pool_bytes": bpb * self.num_blocks if bpb else None,
            "leased_bytes": bpb * leased if bpb else None,
            "free_blocks": len(free),
            "guard_blocks": guard,
            "leased_blocks": leased,
            "lease_count": lease_count,
            "shared_blocks": sum(1 for n in self._refs.values() if n > 1),
            "reclaimable_blocks": self.reclaimable_blocks(),
            "cow_copies": self.cow_copies,
            "used_blocks": used,
            "tokens": tokens,
            "utilization": round(self.utilization(), 4),
            "internal_frag_ratio": round(max(
                0.0, 1.0 - tokens / capacity_tokens), 4) if capacity_tokens
            else 0.0,
            "largest_free_run": largest_run,
            "free_fragmentation_ratio": round(
                1.0 - largest_run / len(free), 4) if free else 0.0,
            "per_seq": per_seq,
        }

    def blocks_needed(self, num_tokens: int, group: int = 0,
                      step: int = 1) -> int:
        """Blocks of `group` a sequence of `num_tokens` holds at once; of
        a windowed group, at most what its window and an append of `step`
        tokens span."""
        if group:
            return self._further[group - 1].blocks_needed(num_tokens, step)
        return max(1, (num_tokens + self.block_size - 1) // self.block_size)

    def can_allocate(self, num_tokens: int) -> bool:
        return len(self._free) + self.reclaimable_blocks() \
            >= self.blocks_needed(num_tokens) \
            and all(g.can_allocate(num_tokens) for g in self._further)

    def allocate(self, seq_id: int, num_tokens: int) -> List[int]:
        """Reserve blocks for a new sequence of `num_tokens` tokens.

        Raises `SequenceTooLong` (never fits) or `KVCacheExhausted`
        (fits once blocks are freed) — never asserts: the serving path
        turns both into admission-control decisions.
        """
        if seq_id in self._tables:
            raise ValueError(f"sequence {seq_id} already allocated")
        _chaos("serve.cache")
        need = self.blocks_needed(num_tokens)
        if need > self.max_blocks_per_seq:
            raise SequenceTooLong(need, self.max_blocks_per_seq, self.name)
        for g in self._further:
            g.check_allocate(num_tokens)
        self._ensure_free(need)
        if need > len(self._free):
            raise KVCacheExhausted(need, len(self._free), self.num_blocks,
                                   self.name)
        blocks = [self._take_free() for _ in range(need)]
        for g in self._further:
            g.allocate(seq_id, num_tokens)
        self._tables[seq_id] = blocks
        self._lens[seq_id] = num_tokens
        if self._is_guard(seq_id):
            self._guard_ids.add(seq_id)
        return blocks

    def adopt(self, seq_id: int, blocks: List[int],
              num_tokens: int) -> List[int]:
        """Create a sequence whose table STARTS with already-allocated
        (shared) physical blocks — the prefix-tree lease path. Each
        block gains one lease (incref); `num_tokens` of KV in them are
        the sequence's context. The table grows past them through the
        normal `append_tokens` path (COW fires if the first append lands
        inside the last shared block)."""
        if seq_id in self._tables:
            raise ValueError(f"sequence {seq_id} already allocated")
        if self.state:
            raise ValueError(
                f"adopt: a slot of the state group {self.name!r} is one "
                "sequence's; a state has no shared prefix to lease")
        if self._further:
            raise ValueError(
                "adopt: shared blocks are the first group's alone; a "
                f"manager with the groups {self.group_names} has no prefix "
                "lease yet")
        if len(blocks) > self.max_blocks_per_seq:
            raise SequenceTooLong(len(blocks), self.max_blocks_per_seq)
        if num_tokens > len(blocks) * self.block_size:
            raise ValueError("adopt: num_tokens exceeds block capacity")
        _chaos("serve.cache")
        for b in blocks:
            self.incref(b)
        self._tables[seq_id] = list(blocks)
        self._lens[seq_id] = num_tokens
        return list(blocks)

    def append_token(self, seq_id: int) -> None:
        """Account one generated token; grows the table on block boundary."""
        self.append_tokens(seq_id, 1)

    def append_tokens(self, seq_id: int, n: int, in_flight: int = 0) -> None:
        """Account `n` new tokens at once (the speculative-decode grow path:
        one pending token + K draft tokens per step), growing the block
        table across as many block boundaries as needed.

        `in_flight`: how many of the sequence's tokens so far belong to a
        round whose result the caller has not seen (it may still `trim`
        them away): a windowed group releases behind the window of the
        length less these, the committed one.

        Copy-on-write: when the first new token lands inside a block
        whose refcount is >1 (a shared prefix leased from the radix
        tree, or a `trim` back into shared territory followed by a
        divergent append), the block is copied to a fresh private block
        first (`cow_hook` moves the device KV) — the other leases keep
        the original bytes.

        All-or-nothing: on `SequenceTooLong`/`KVCacheExhausted` neither the
        length nor the table changes, so the caller can retry with a
        smaller `n` (fewer drafts) or preempt — the same contract
        `append_token` always had. Rollback of a *successful* append (e.g.
        rejected speculations) is `trim(seq_id, old_len)`."""
        if n < 0:
            raise ValueError(f"append_tokens: n must be >= 0, got {n}")
        _chaos("serve.cache")
        old_len = self._lens[seq_id]
        new_len = old_len + n
        table = self._tables[seq_id]
        need = self.blocks_needed(new_len) - len(table)
        # COW trigger: the FIRST new token's write target is an existing
        # table block (not a fresh allocation) that other leases share —
        # either a partial shared block (old_len mid-block) or a full
        # shared block the lease kept past a boundary-capped prefix hit
        cow_idx = None
        if n > 0:
            idx = old_len // self.block_size
            if idx < len(table) and self._refs[table[idx]] > 1:
                cow_idx = idx
        extra = 1 if cow_idx is not None else 0
        if need > 0 and len(table) + need > self.max_blocks_per_seq:
            raise SequenceTooLong(len(table) + need,
                                  self.max_blocks_per_seq, self.name)
        committed = old_len - in_flight
        for g in self._further:            # all groups grow, or none
            g.check_append(seq_id, committed, new_len)
        if max(need, 0) + extra > len(self._free):
            self._ensure_free(max(need, 0) + extra)
        if max(need, 0) + extra > len(self._free):
            raise KVCacheExhausted(max(need, 0) + extra, len(self._free),
                                   self.num_blocks, self.name)
        if cow_idx is not None:
            self._cow(seq_id, cow_idx)
        for _ in range(max(need, 0)):
            table.append(self._take_free())
        for g in self._further:
            # a windowed group gives back what lies behind its window at
            # the committed length (see `_BlockGroup`)
            g.append(seq_id, committed, new_len)
        self._lens[seq_id] = new_len

    def _cow(self, seq_id: int, idx: int) -> int:
        """Copy block `idx` of `seq_id`'s table into a fresh private
        block (caller guarantees a free block exists). The device copy
        runs BEFORE any bookkeeping mutates, so a failing hook leaves
        the pool exactly as it was."""
        table = self._tables[seq_id]
        src = table[idx]
        dst = self._free.pop()
        if self._cow_hook is not None:
            try:
                self._cow_hook(src, dst)
            except Exception:
                self._free.append(dst)
                raise
        self._refs[dst] = 1
        self._release(src)             # caller checked > 1: never frees
        table[idx] = dst
        self.cow_copies += 1
        _monitor_inc("serving.prefix_cache.cow_copies")
        return dst

    def trim(self, seq_id: int, num_tokens: int) -> None:
        """Shrink a sequence to `num_tokens` tokens, returning surplus
        blocks to the pool (shared blocks just drop this sequence's
        lease). Used for speculative-decode rollback and padded-prefill
        cleanup; trimming INTO a shared block is safe — the next
        divergent append COWs it."""
        if num_tokens > self._lens[seq_id]:
            raise ValueError("trim can only shrink a sequence")
        if self.state:
            if num_tokens < self._lens[seq_id]:
                raise StateNotTrimmable(seq_id, self._lens[seq_id],
                                        num_tokens, self.name)
            return
        for g in self._further:
            g.check_trim(seq_id, num_tokens)
        keep = self.blocks_needed(num_tokens)
        table = self._tables[seq_id]
        while len(table) > keep:
            self._release(table.pop())
        for g in self._further:
            g.trim(seq_id, num_tokens)
        self._lens[seq_id] = num_tokens

    def unappend(self, seq_id: int, num_tokens: int) -> None:
        """Take back `append_tokens` down to `num_tokens`. For block groups
        this is `trim`. A state group allows it as bookkeeping alone: the
        caller vouches that the tokens beyond `num_tokens` never reached
        the device (a launch that grew its lanes and was not dispatched),
        or it could not be taken back at all (`StateNotTrimmable`)."""
        if not self.state:
            return self.trim(seq_id, num_tokens)
        if num_tokens > self._lens[seq_id]:
            raise ValueError("unappend can only shrink a sequence")
        self._lens[seq_id] = num_tokens

    def free(self, seq_id: int) -> None:
        for b in self._tables.pop(seq_id):
            self._release(b)
        for g in self._further:
            g.free(seq_id)
        self._lens.pop(seq_id)
        self._guard_ids.discard(seq_id)

    def seq_len(self, seq_id: int) -> int:
        return self._lens[seq_id]

    def seq_blocks(self, seq_id: int, group: int = 0) -> int:
        """Number of physical blocks of `group` currently leased by
        `seq_id` (0 for an unknown sequence). Lets the serving watchdog
        audit for leaks without reaching into private tables."""
        if group:
            return self._further[group - 1].seq_blocks(seq_id)
        return len(self._tables.get(seq_id, ()))

    def blocks_of(self, seq_id: int, group: int = 0) -> Tuple[int, ...]:
        """The physical block ids of `group` leased by `seq_id` in logical
        order (empty for an unknown sequence) — the prefix tree's publish
        input and the leak auditor's unique-set input."""
        if group:
            return self._further[group - 1].blocks_of(seq_id)
        return tuple(self._tables.get(seq_id, ()))

    def check_consistency(self, external: Optional[Dict[int, int]] = None):
        """Invariant audit (tests / chaos smoke): free list unique and
        disjoint from live refs, every pool block accounted exactly
        once, every refcount positive and — when `external` maps block
        -> lease count held by non-sequence owners (the prefix tree) —
        exactly equal to table appearances + external leases. Raises
        AssertionError naming the broken invariant (a double-freed
        shared block shows up here as a duplicate free-list entry or a
        refcount mismatch)."""
        for g in self._further:
            g.check_consistency()
            assert set(g._tables) == set(self._tables), \
                f"group {g.name}: its sequences are not the first group's"
        free = self._free
        assert len(free) == len(set(free)), "duplicate free-list entry"
        assert not (set(free) & set(self._refs)), \
            "block both free and referenced"
        assert len(free) + len(self._refs) == self.num_blocks, \
            f"pool accounting broken: {len(free)} free + " \
            f"{len(self._refs)} live != {self.num_blocks}"
        assert all(n >= 1 for n in self._refs.values()), \
            "non-positive refcount"
        counts: Dict[int, int] = {}
        for table in self._tables.values():
            for b in table:
                counts[b] = counts.get(b, 0) + 1
        if external is not None:
            for b, n in external.items():
                counts[b] = counts.get(b, 0) + n
            assert counts == self._refs, \
                f"refcount mismatch: tables+external {counts} != " \
                f"refs {self._refs}"
        else:
            for b, n in counts.items():
                assert self._refs.get(b, 0) >= n, \
                    f"block {b}: {n} table leases > refcount " \
                    f"{self._refs.get(b, 0)}"

    def block_table_array(self, seq_ids, pad: int = 0) -> np.ndarray:
        """Dense [len(seq_ids), table_width] int32 table: every group's
        table of a sequence side by side, `max_blocks_per_seq` columns
        each (one group: `[n, max_blocks_per_seq]`, as ever).

        `pad` fills entries past each sequence's allocation, and those a
        windowed group has released (default 0). The speculative verify
        pass pads with the scheduler's guard block so fixed-shape writes
        past a short lane's allocation land in a sacrificial block instead
        of physical block 0."""
        width = self.max_blocks_per_seq
        out = np.full((len(seq_ids), self.table_width), pad, np.int32)
        for i, sid in enumerate(seq_ids):
            t = self._tables[sid]
            out[i, :len(t)] = t
            for n, g in enumerate(self._further, 1):
                head, t = g._head[sid], g._tables[sid]
                out[i, n * width + head:n * width + len(t)] = t[head:]
        return out
