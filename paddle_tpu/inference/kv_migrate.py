"""KV-block migration: export/import one sequence's paged KV (ISSUE 17).

The transfer unit of disaggregated serving is the Ragged-Paged-Attention
block (arxiv 2604.15464): a prefill worker finishes the chunked prefill,
extracts the sequence's committed blocks as ONE device gather, and a
decode worker scatters them into its own pool — tokens, KV, and (for
int8 pools, PR 14) the per-slot scale planes ride the same payload so
quantized state can never tear apart in flight. The same primitive
upgrades PR 10's relocation (block copy instead of re-prefill when the
source is reachable) and streams radix-cached shared prefixes across
replicas.

Layout contract (who owns what):

- Engines own the pools: `self.pools`, a tuple of device arrays paged
  along one block axis (`(k, v)`, with `(k_scale, v_scale)` when int8;
  `(cache,)` or `(cache, cache_scale)` for the MLP engine).
- `PagedPools` (below) owns what is done with whole blocks of such a
  tuple, for `MLPLMEngine`, `LlamaInferenceEngine`, `LoRAEngine` and
  `ShardedEngine` alike: `copy_kv_block`, `extract_kv_blocks(seq_id)`,
  `inject_kv_blocks(seq_id, payload)`, one jit each, built ONCE at
  construction, every pool of the tuple in the same executable. The
  gather is NOT donated (the source pool lives on — extraction is a
  copy); copy and scatter donate the pools like every other
  pool-mutating executable.
- This module owns the wire format: the versioned header, the
  fixed-shape index padding, and the pre-inject validation.

Fixed-shape discipline: block-index vectors are padded to
``max_blocks_per_seq`` by repeating the LAST real index
(`pad_block_indices`), so one compiled gather and one compiled scatter
cover every sequence length — migration never retraces. Duplicate
gather rows are dead payload; duplicate scatter writes rewrite
identical content into the same block, which is deterministic
regardless of write order.

Failure semantics are typed and ordered: `check_header` raises
`KVMigrationError` naming the first mismatching field BEFORE the target
pool or block manager is touched; capacity problems surface as the
manager's own `KVCacheExhausted`/`SequenceTooLong` from `allocate`; any
failure after allocation frees the just-allocated blocks before
re-raising, so a failed inject never leaks.
"""
from __future__ import annotations

from typing import Any, Dict, Mapping, Sequence

import numpy as np

__all__ = ["PAYLOAD_VERSION", "KVMigrationError", "KVBlockPayload",
           "PagedPools", "pad_block_indices", "check_header"]

PAYLOAD_VERSION = 1


class KVMigrationError(ValueError):
    """A payload that cannot be injected into this engine — version,
    geometry, kv_bits, or head-partition mismatch. Raised BEFORE any
    allocation or pool mutation on the target, so the caller can fall
    back (e.g. the router's committed-prefix re-prefill) with the
    target engine untouched."""


class KVBlockPayload:
    """One sequence's migrated KV: a header (geometry + provenance) and
    the device slabs gathered from the source pool.

    ``header`` carries the source engine's geometry (validated against
    the target by `check_header`) plus per-payload facts:
    ``num_blocks`` (real blocks; the slab's leading block dimension is
    the fixed ``max_blocks_per_seq``, rows past ``num_blocks`` are
    padding) and ``num_tokens`` (committed KV length). ``slabs`` maps
    plane name -> device array and stays valid after inject (the
    scatter does not donate it), so one payload can stream to several
    decode workers — the cross-replica prefix-reuse path.
    """

    __slots__ = ("header", "slabs")

    def __init__(self, header: Mapping[str, Any],
                 slabs: Mapping[str, Any]):
        self.header: Dict[str, Any] = dict(header)
        self.slabs: Dict[str, Any] = dict(slabs)

    @property
    def num_tokens(self) -> int:
        return int(self.header["num_tokens"])

    @property
    def num_blocks(self) -> int:
        return int(self.header["num_blocks"])

    @property
    def nbytes(self) -> int:
        """Real payload bytes: the slabs' bytes scaled down to the
        occupied block rows (padding rows are transport overhead, not
        migrated state)."""
        total = sum(int(s.nbytes) for s in self.slabs.values())
        cap = max(1, int(self.header["max_blocks_per_seq"]))
        return total * self.num_blocks // cap

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"KVBlockPayload(engine={self.header.get('engine')!r}, "
                f"tokens={self.header.get('num_tokens')}, "
                f"blocks={self.header.get('num_blocks')}, "
                f"kv_bits={self.header.get('kv_bits')})")


def pad_block_indices(blocks: Sequence[int], max_blocks: int) -> np.ndarray:
    """``[n]`` real block ids -> ``[max_blocks]`` int32, padded by
    repeating the last real id. This is what keeps migration at one
    compiled gather + one compiled scatter across every sequence
    length: the executable shape never changes, and the duplicate
    trailing writes are idempotent (same content into the same block)."""
    n = len(blocks)
    if n == 0 or n > max_blocks:
        raise KVMigrationError(
            f"cannot pad {n} block indices into max_blocks_per_seq="
            f"{max_blocks}")
    idx = np.empty((max_blocks,), np.int32)
    idx[:n] = np.asarray(blocks, np.int32)
    idx[n:] = idx[n - 1]
    return idx


def check_header(header: Mapping[str, Any],
                 expected: Mapping[str, Any]) -> None:
    """Validate an incoming payload header against the target engine's
    own geometry header — every key the target declares must match.
    Raises `KVMigrationError` naming the first mismatching field; runs
    BEFORE any allocation so a rejected payload leaves the target
    engine bit-for-bit untouched."""
    if not isinstance(header, Mapping):
        raise KVMigrationError(
            f"payload header must be a mapping, got "
            f"{type(header).__name__}")
    for key in sorted(expected):
        if key not in header:
            raise KVMigrationError(
                f"payload header missing field {key!r} "
                f"(target expects {expected[key]!r})")
        if header[key] != expected[key]:
            raise KVMigrationError(
                f"payload header mismatch on {key!r}: payload has "
                f"{header[key]!r}, target engine expects "
                f"{expected[key]!r}")


class PagedPools:
    """Whole-block operations over an engine's `self.pools`.

    The engine supplies `pools` (a tuple of arrays whose block axis is
    the `axis` given to `_build_block_ops`), `manager`, `_mig_header`
    (its geometry, validated against a payload's) and `_slab_names` (the
    payload's name for each pool, in order). Every pool of the tuple
    moves in the same executable, so an int8 block and its scale rows
    never tear apart; indices trace as int32, so nothing here retraces."""

    def _build_block_ops(self, axis: int) -> None:
        import jax

        lead = (slice(None),) * axis
        self._copy_block = jax.jit(
            lambda pools, s, d: jax.tree.map(
                lambda p: p.at[lead + (d,)].set(p[lead + (s,)]), pools),
            donate_argnums=(0,))
        self._kv_gather = jax.jit(
            lambda pools, i: jax.tree.map(lambda p: p[lead + (i,)], pools))
        self._kv_scatter = jax.jit(
            lambda pools, i, slabs: jax.tree.map(
                lambda p, s: p.at[lead + (i,)].set(s), pools, slabs),
            donate_argnums=(0,))

    def copy_kv_block(self, src: int, dst: int) -> None:
        """Copy one physical block, every layer and plane
        (`BlockCacheManager` COW hook — the scheduler wires it when
        prefix caching is on). Positions past the writer's divergence
        point are overwritten or never attended."""
        self.pools = self._copy_block(self.pools, np.int32(src),
                                      np.int32(dst))

    def extract_kv_blocks(self, seq_id: int) -> KVBlockPayload:
        """Export `seq_id`'s committed blocks as ONE device gather (the
        disaggregated handoff / KV-shipping relocation, ISSUE 17). The
        source pools are untouched; indices pad to the fixed
        `max_blocks_per_seq` shape, so every sequence length rides one
        compiled executable. Under TP the slabs stay sharded, each chip
        contributing its slice."""
        mgr = self.manager
        blocks = mgr.blocks_of(seq_id)
        if not blocks:
            raise KVMigrationError(
                f"sequence {seq_id} holds no KV blocks on this engine")
        idx = pad_block_indices(blocks, mgr.max_blocks_per_seq)
        header = dict(self._mig_header, num_blocks=len(blocks),
                      num_tokens=mgr.seq_len(seq_id))
        return KVBlockPayload(header, dict(zip(
            self._slab_names, self._kv_gather(self.pools, idx))))

    def inject_kv_blocks(self, seq_id: int, payload: KVBlockPayload) -> None:
        """Import a migrated payload under `seq_id`: typed header
        validation BEFORE any allocation, the manager's typed capacity
        errors propagate from `allocate`, one donated scatter writes
        every pool; any failure after allocation frees the blocks, so a
        failed inject never leaks. The payload's slabs are not donated
        (one payload can stream to several workers)."""
        mgr = self.manager
        check_header(payload.header, self._mig_header)
        blocks = mgr.allocate(seq_id, payload.num_tokens)
        try:
            if len(blocks) != payload.num_blocks:
                raise KVMigrationError(
                    f"payload carries {payload.num_blocks} blocks but "
                    f"{payload.num_tokens} tokens allocate "
                    f"{len(blocks)} here")
            idx = pad_block_indices(blocks, mgr.max_blocks_per_seq)
            self.pools = self._kv_scatter(
                self.pools, idx,
                tuple(payload.slabs[n] for n in self._slab_names))
        except Exception:
            mgr.free(seq_id)
            raise
