"""Continuous-batching scheduler over an `EngineCore`.

The serving analog of vLLM-style continuous batching, with the TPU shape
discipline from Ragged Paged Attention (PAPERS.md): the decode step is ONE
fixed-shape program over `max_batch_size` slots — requests churn through
the slots (admit / finish mid-batch / preempt), the program never changes
shape, so the steady state performs ZERO recompiles.

Policy (documented in docs/SERVING.md):
- admission: FIFO from the waiting queue into free slots; admission
  leases only the sequence id (one block) — the prompt's KV enters the
  cache chunk-by-chunk through the ragged step, sized to the TRUE
  context (no bucket padding, no `trim`-back). Pool exhaustion
  (`KVCacheExhausted`) leaves it queued — never crashes.
- prefix caching (optional, `prefix_cache=True`): a radix tree over
  the paged pool publishes committed KV at finish/preemption and leases
  the deepest cached prefix at admission (refcount bump, zero prefill
  for the hit; chunking resumes from the first uncached block — a full
  hit makes TTFT ≈ one decode step). Divergent writes into shared
  blocks copy-on-write; unpinned tree nodes LRU-evict under pressure.
- multi-tenant SLOs (optional, `slo=SLOConfig(...)`): per-tenant KV
  quotas/reserves gate admission without cross-tenant head blocking,
  decode lanes allocate by deficit-weighted fair queuing, and each
  latency tier scales the overload watermarks with its own latches.
- load shedding (optional `AdmissionConfig`): watermark latches with
  hysteresis over queue depth, queued `max_new_tokens` cost, and KV
  utilization, plus deadline-aware early shedding — overload degrades to
  fast SHED responses instead of collapsing TTFT for everyone.
- chunked prefill: every step packs the decode lanes (one token each)
  plus at most `prefill_chunk_tokens` of pending-prompt tokens into ONE
  fixed-shape `engine.sampled_step` dispatch over a packed token buffer
  of `max_batch_size + prefill_chunk_tokens` slots. A 32k-token prompt
  advances chunk-by-chunk while decode lanes keep emitting a token
  every step — prefill can no longer stall decode TPOT, and the steady
  state holds ONE executable for every batch composition and prompt
  length (no bucket family, no prompt-length recompiles). The first
  token samples when the final chunk completes.
- preemption: when a RUNNING sequence cannot grow (pool exhausted on a
  block boundary), the most-recently-admitted other sequence is evicted
  back to the FRONT of the queue (LIFO victim, FIFO service order); its
  tokens so far are kept and re-prefilled on re-admission.
- eviction: finished/cancelled/expired sequences free their blocks
  immediately; the slot admits a new request on the same step.
- padding: empty slots decode with ctx_len=1 against a dedicated guard
  block (never a sequence's block), so padded lanes can't corrupt live KV.
- fault isolation: every engine dispatch runs behind a typed boundary
  (`serving/fault_tolerance.py`). Faults attributable to specific lanes
  (NaN logits, typed `EngineStepError(seq_ids=...)`, cache failures,
  failed probe replays) fail ONLY those requests; survivors roll back to
  their pre-step cache lengths and replay next round with identical
  tokens. Unattributed faults retry under a bounded budget, then
  escalate to the watchdog.
- watchdog (optional `WatchdogConfig` + `engine_factory`): stall
  detection (per-dispatch wall clock + zero-progress rounds) drives a
  bounded-restart supervisor — in-flight sequences re-queue with
  tokens-so-far intact, the engine is rebuilt, the guard block is
  re-leased from the fresh pool. Budget exhaustion fails every
  non-terminal request typed (`engine_unrecoverable:*`): no request is
  ever lost silently.
- speculative decoding (optional, `SpecDecodeConfig`): each round a
  proposer drafts up to K tokens per lane; ONE fixed-shape
  `engine.verify_step` scores all lanes' pending+draft tokens at once;
  the accepted prefix plus a bonus/correction token commit, and rejected
  speculation rolls back via `BlockCacheManager.trim`. Greedy speculative
  output is token-for-token identical to plain decode.

Sampling (both paths) is the device-side fused batched sampler
(`ops/sampling.py`): temperature/top-k/Gumbel-max with a per-request
counter-based RNG — no per-lane host numpy in the loop. On the plain
round it is the tail of the engine step's own program, with the NaN
screen and the gather of each lane's last row: one program and one host
fetch a round (docs/SERVING.md "One program, one fetch"); the
speculative round runs screen and sampler as programs of their own.

A plain round is LAUNCHED by one `step()` and SETTLED (fetched, screened,
committed) by the next, after that one has launched its own: the decode
lanes' tokens go from round to round on the device, and the scheduler's
Python, the launch and the fetch run under the device's work
(docs/SERVING.md "A round in flight").
"""
from __future__ import annotations

import enum
import itertools
import time
from collections import deque
from typing import Callable, Deque, List, Optional

import numpy as np

from .. import observability as _obs
from ..framework import monitor as _monitor
from ..profiler import RecordEvent
from ..framework.retry import Budget, retry_call
from ..inference.cache import KVCacheExhausted, SequenceTooLong
from ..inference.kv_migrate import KVMigrationError
from ..inference.prefix_cache import RadixPrefixCache
from ..ops.sampling import (call_arrays, fed_token, pack_lanes,
                            sample_tokens)
from ..resilience import faults as _faults
from .engine import EngineCore
from .lora import AdapterPoolExhausted
from .fault_tolerance import (AdmissionConfig, EngineStepError,
                              OverloadController, WatchdogConfig)
from .metrics import ServingMetrics
from .slo import DEFAULT_TENANT, SLOConfig
from .spec import SpecDecodeConfig

__all__ = ["SamplingParams", "RequestStatus", "Request", "Scheduler"]

_PAD_SEQ_ID = -1


class SamplingParams:
    """Per-request decoding knobs (greedy by default)."""

    def __init__(self, max_new_tokens: int = 16, temperature: float = 0.0,
                 top_k: int = 0, eos_token_id: Optional[int] = None,
                 seed: int = 0):
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        self.top_k = top_k
        self.eos_token_id = eos_token_id
        self.seed = seed


class RequestStatus(enum.Enum):
    QUEUED = "queued"
    RUNNING = "running"
    PREEMPTED = "preempted"     # back in queue, tokens-so-far kept
    FINISHED = "finished"
    CANCELLED = "cancelled"
    REJECTED = "rejected"
    SHED = "shed"               # overload admission control turned it away
    FAILED = "failed"           # engine fault isolated to this request
    TIMED_OUT = "timed_out"

    @property
    def terminal(self) -> bool:
        return self in (RequestStatus.FINISHED, RequestStatus.CANCELLED,
                        RequestStatus.REJECTED, RequestStatus.SHED,
                        RequestStatus.FAILED, RequestStatus.TIMED_OUT)


class Request:
    """One generation request and its lifecycle bookkeeping."""

    _ids = itertools.count()

    def __init__(self, prompt_ids, sampling: Optional[SamplingParams] = None,
                 deadline: Optional[float] = None,
                 stream_cb: Optional[Callable[["Request", int], None]] = None,
                 tenant: str = DEFAULT_TENANT,
                 adapter: Optional[str] = None):
        self.req_id = next(Request._ids)
        self.prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        self.sampling = sampling or SamplingParams()
        self.deadline = deadline              # absolute, scheduler's clock
        self.stream_cb = stream_cb
        # multi-tenant SLO class (serving/slo.py): quota, lane weight,
        # and watermark tier all key off this; "default" = untiered
        self.tenant = tenant or DEFAULT_TENANT
        # multi-LoRA serving (serving/lora.py): which registered adapter
        # decorates this request's lanes; None = the base model.
        # `_adapter_slot` != None ⟺ this request holds one pool lease in
        # the CURRENT engine's adapter pool (taken at admission, dropped
        # at every slot/queue exit — and zeroed without release when a
        # watchdog swap discards the pool with the engine)
        self.adapter = adapter
        self._adapter_slot: Optional[int] = None
        self.generated: List[int] = []
        self.status = RequestStatus.QUEUED
        self.finish_reason: Optional[str] = None
        self.num_preemptions = 0
        # fleet placement (serving/fleet.py): which replica currently
        # serves this request, and how many times a replica failure or
        # drain moved it (committed tokens carried as prompt prefix).
        # None/0 for a request served by a standalone frontend.
        self.replica_id: Optional[str] = None
        self.num_relocations = 0
        self.session_id: Optional[str] = None
        self.t_submit: Optional[float] = None
        self.t_first_token: Optional[float] = None
        self.t_finish: Optional[float] = None
        self._last: Optional[int] = None      # sampled, KV not yet written
        self._admit_seq = -1                  # admission order (victim pick)
        # chunked-prefill cursor: context tokens whose KV is already in
        # cache (reset at every (re-)admission; the target snapshot is
        # taken then too, so re-prefill after preemption replays the
        # full prompt + kept tokens). A radix prefix-cache hit starts
        # the cursor AT the hit length — those tokens never prefill.
        self._prefill_ctx = np.zeros((0,), np.int32)
        self._prefill_pos = 0
        self._prefix_hit_tokens = 0           # cached tokens this admission
        self._chunks = 0
        self._t_admit: Optional[float] = None
        # context KV arrived as a migrated payload (`import_session`,
        # ISSUE 17): admission skips the lease/prefill for the covered
        # context; cleared at admission, and any queue exit before then
        # frees the resident blocks (`_drop_resident_kv`)
        self._kv_resident = False

    @property
    def prefilling(self) -> bool:
        """True while context KV is still entering the cache chunk-wise
        (the lane contributes prompt chunks, not decode tokens)."""
        return self._prefill_pos < len(self._prefill_ctx)

    @property
    def seq_id(self) -> int:
        return self.req_id

    @property
    def cost(self) -> int:
        """Admission-control weight: decode steps this request may still
        consume (`max_new_tokens` less what it already produced)."""
        return max(1, self.sampling.max_new_tokens - len(self.generated))

    def context_tokens(self) -> np.ndarray:
        """Tokens whose KV must be in-cache before the next decode: the
        prompt plus all generated tokens EXCEPT the pending last one (the
        decode step itself writes the pending token's KV)."""
        gen = self.generated[:-1] if self._last is not None else self.generated
        return np.concatenate([self.prompt,
                               np.asarray(gen, np.int32)]).astype(np.int32)

    def all_tokens(self) -> np.ndarray:
        """Prompt + every generated token INCLUDING the pending last one —
        the stream a speculative proposer continues from."""
        return np.concatenate([
            self.prompt, np.asarray(self.generated, np.int32)]).astype(
                np.int32)

    def ttft(self) -> Optional[float]:
        if self.t_first_token is None or self.t_submit is None:
            return None
        return self.t_first_token - self.t_submit

    def tpot(self) -> Optional[float]:
        """Mean time-per-output-token after the first."""
        if (self.t_finish is None or self.t_first_token is None
                or len(self.generated) < 2):
            return None
        return (self.t_finish - self.t_first_token) / (len(self.generated) - 1)


class _Lane:
    """One request's share of a plain round between the round's launch and
    its settle: `n` tokens from cache length `pre_len` on, a prefill chunk
    or one decode token, and whether the round samples a token for it."""

    __slots__ = ("slot", "req", "admit_seq", "n", "pre_len", "chunk",
                 "samples")

    def __init__(self, slot: int, req: Request, n: int, pre_len: int,
                 chunk: bool, samples: bool):
        self.slot, self.req, self.admit_seq = slot, req, req._admit_seq
        self.n, self.pre_len = n, pre_len
        self.chunk, self.samples = chunk, samples

    def holds(self, sched: "Scheduler") -> bool:
        """The request still sits in the slot it was launched from, on the
        same admission: what left since (finished, cancelled, preempted,
        convicted) has its lane's result discarded."""
        return sched.slots[self.slot] is self.req \
            and self.req._admit_seq == self.admit_seq


class _Round:
    """A plain round in flight: launched, not yet settled. `sampled` is
    the step's `[2, B]` result, still on the device."""

    __slots__ = ("lanes", "sampled", "flagged", "began", "probe", "error")

    def __init__(self, lanes):
        self.lanes = lanes            # slot -> _Lane, in slot order
        self.sampled = None
        self.flagged = False          # an injected fault poisons one lane
        self.began = 0.0              # the dispatch's start, `_clock`
        self.error = None             # what the dispatch raised, and
        self.probe = None             # what then replays one lane alone

    def pairs(self):
        return [(i, ln.req) for i, ln in self.lanes.items()]


class _SettleFirst(Exception):
    """Growth met the pool's or a sequence's limit while a round is in
    flight: who may grow, and who is preempted or finished for it, is
    decided on committed state, so the launch undoes what it has grown,
    the round in flight is settled, and the launch starts over."""


class Scheduler:
    """Admits requests into decode slots and drives fixed-shape steps."""

    def __init__(self, engine: EngineCore,
                 metrics: Optional[ServingMetrics] = None,
                 max_queue: int = 256,
                 spec: Optional[SpecDecodeConfig] = None,
                 admission: Optional[AdmissionConfig] = None,
                 watchdog: Optional[WatchdogConfig] = None,
                 engine_factory: Optional[Callable[[], EngineCore]] = None,
                 prefill_chunk_tokens: int = 32,
                 prefix_cache: bool = False,
                 slo: Optional[SLOConfig] = None,
                 clock: Callable[[], float] = time.perf_counter):
        """`prefill_chunk_tokens`: per-step token budget for pending
        prompts — the packed ragged dispatch holds `max_batch_size +
        prefill_chunk_tokens` token slots. Larger chunks finish prefill
        in fewer steps (better TTFT); smaller chunks bound how much a
        long prompt can stretch any single step (better decode TPOT
        under mixed traffic). See docs/SERVING.md for tuning.

        `prefix_cache`: enable the shared-prefix radix cache
        (`inference/prefix_cache.py`): committed prompt/response KV is
        published block-wise at finish/preemption; a new request leases
        the deepest cached prefix at admission (refcount bump, zero
        prefill for those tokens) and chunked prefill resumes from the
        first uncached block — a full hit makes TTFT ≈ one decode step.
        Divergent writes into shared blocks copy-on-write; unpinned
        cached blocks LRU-evict under pool pressure.

        `slo`: optional multi-tenant `SLOConfig` (serving/slo.py):
        per-tenant KV quotas/reserves, deficit-weighted decode-lane
        allocation, and latency-tier watermark scaling."""
        if prefill_chunk_tokens < 1:
            raise ValueError("prefill_chunk_tokens must be >= 1, got "
                             f"{prefill_chunk_tokens}")
        self.engine = engine
        self.metrics = metrics or ServingMetrics()
        self.max_queue = max_queue
        self.spec = spec
        self.prefill_chunk_tokens = int(prefill_chunk_tokens)
        # the packed query buffer: every slot may decode one token, plus
        # the chunk budget — FIXED for the scheduler's lifetime, so the
        # ragged step is one compiled executable
        self.ragged_tokens = engine.max_batch_size + self.prefill_chunk_tokens
        # what a windowed block group holds of a lane beyond its window:
        # the chunk being appended and the one still in flight before it
        self._window_step = 2 * self.prefill_chunk_tokens
        self.engine_factory = engine_factory
        self._overload = OverloadController(admission) if admission else None
        if watchdog is None and engine_factory is not None:
            # a factory without a config opts into the default watchdog —
            # otherwise the restart budget would be 0 and the caller's
            # factory would silently never run
            watchdog = WatchdogConfig()
        self._wd = watchdog
        self._restart_budget = Budget(
            watchdog.max_restarts if watchdog is not None else 0)
        self._clock = clock
        self.slots: List[Optional[Request]] = [None] * engine.max_batch_size
        self.waiting: Deque[Request] = deque()
        self._queued_cost = 0          # sum of waiting requests' .cost
        self._admit_counter = itertools.count()
        # recent decode/verify dispatch wall times; the deadline-shed
        # estimate uses the MEDIAN, which a compile-time outlier (first
        # trace ~100x a steady step) cannot drag the way an EMA can
        self._tpot_samples: Deque[float] = deque(maxlen=32)
        self._zero_progress = 0        # consecutive no-progress steps
        self._finish_events = 0        # terminal transitions, monotonic
        self._step_index = 0           # `sched.step` span id, monotonic
        self.tokens_committed = 0      # tokens committed to request
        # streams over this scheduler's lifetime (decode + prefill first
        # tokens + speculative accepts) — the per-replica throughput
        # figure fleet aggregation reads (monitor counters are global)
        self._step_faults = 0          # consecutive unattributed faults
        self._pending_stall: Optional[str] = None
        self._broken: Optional[str] = None   # rebind failed mid-restart
        self._finite_fn = None               # jitted NaN screen, lazy
        self._last_decode_dt: Optional[float] = None
        self._chunk_progress = 0             # prefill tokens last round
        # the plain round launched and not yet settled (docs/SERVING.md
        # "A round in flight"), when the one before it was settled, and
        # the decode tokens this `step()` has committed
        self._launched: Optional[_Round] = None
        self._settled_at = 0.0
        self._produced = 0
        self._prefix_enabled = bool(prefix_cache)
        self._prefix_tree: Optional[RadixPrefixCache] = None
        self._slo = slo
        # per-tenant overload controllers (tier-scaled watermarks, own
        # hysteresis latches) and virtual-time clocks for the
        # deficit-weighted lane allocator — both lazy. `_vclock` is the
        # system virtual time (the last admission's start time): a
        # tenant returning from idle is charged from max(own, _vclock),
        # so it competes from NOW instead of spending banked arrears
        self._overload_by_tenant = {}
        self._vtime = {}
        self._vclock = 0.0
        # multi-LoRA admission pricing (serving/lora.py): how many
        # adapter-MISS admissions (pool upload + possible eviction) one
        # admission round may pay for; resident-adapter admissions are
        # free and never count against it
        self.adapter_miss_loads_per_step = 1
        self._bind_manager(engine.manager)

    def _bind_manager(self, mgr):
        """(Re)lease the guard block and derive pool geometry — on
        construction and again after every watchdog engine rebuild."""
        # Guard block for padded decode lanes: empty slots point their
        # block table at this block (ctx_len=1), so the decode write for
        # a padded lane lands here, never in a live sequence's block.
        # Negative ids keep it out of the request id space; probe
        # downward in case another scheduler already leases -1 on a
        # shared engine.
        pad_id = _PAD_SEQ_ID
        while True:
            try:
                self._pad_block = mgr.allocate(pad_id, 1)[0]
                break
            except ValueError:
                pad_id -= 1
        self._pad_seq_id = pad_id
        # an engine whose layer kinds keep different amounts of context
        # has further block groups (`BlockCacheManager`): the guard leases
        # one block in each, and what is built on ONE table a sequence
        # refuses such an engine by name
        self._further_pads = [(g, mgr.blocks_of(pad_id, g)[0])
                              for g in range(1, mgr.n_groups)]
        # a STATE group: a sequence's memory is one slot whose recurrent
        # state cannot be trimmed, shared or replayed into
        self._state = bool(getattr(mgr, "state", False))
        if self._state and (self._prefix_enabled or self.spec is not None):
            what = "the radix prefix cache" if self._prefix_enabled \
                else "speculative decoding"
            raise ValueError(
                f"{type(self.engine).__name__}: {what} over the state group "
                f"{mgr.group_names[0]!r} is not implemented: a recurrent "
                "state has no blocks to share at a prefix boundary and no "
                "snapshot for a verify window's rollback")
        if self._further_pads and (self._prefix_enabled
                                   or self.spec is not None):
            what = "the radix prefix cache" if self._prefix_enabled \
                else "speculative decoding"
            raise ValueError(
                f"{type(self.engine).__name__}: {what} over the block "
                f"groups {mgr.group_names} is not implemented: a windowed "
                "group releases blocks a shared prefix or a verify window's "
                "rollback would have to keep")
        # What one sequence can ever hold: pool minus the guard (and minus
        # blocks other users of a shared engine already lease).
        self._usable_blocks = min(mgr.free_blocks, mgr.max_blocks_per_seq)
        # cross-replica prefix streaming (ISSUE 17): `_mig_seq` mints
        # transient sequence ids for the export/import lease (negative,
        # far below the pad-guard probe range); the hook — set by a
        # fleet router — is asked for a peer's cached copy on an
        # admission-time radix first-miss
        self._mig_seq = -(1 << 30)
        self.prefix_stream_hook: Optional[Callable] = None
        # radix prefix cache: built on THIS manager (and rebuilt with a
        # fresh one after a watchdog engine swap — the old tree's KV
        # died with the old device state); the engine's block-copy hook
        # backs COW, and the tree is the pool's eviction authority
        if self._prefix_enabled:
            self._prefix_tree = RadixPrefixCache(mgr)
            mgr.set_reclaimer(self._prefix_tree)
            mgr.set_cow_hook(getattr(self.engine, "copy_kv_block", None))
        # publish the engine's quantization mode (wbits/kv_bits/
        # kv_bytes_per_token gauges) — bind-time, not per-step; an
        # engine swap re-runs this with the fresh engine's mode
        info = getattr(self.engine, "quant_info", None)
        if info is not None:
            try:
                self.metrics.on_quant(info())
            except Exception:
                # bind must survive a broken hook, but not silently:
                # unset quant gauges + this counter point at the cause
                _monitor.inc("serving.quant_info_errors")
        # multi-LoRA engine surface (serving/lora.py), re-resolved after
        # every engine swap: the adapter pool leases at admission, and
        # the per-lane slot vector is pushed before each dispatch
        self._lora = getattr(self.engine, "adapter_pool", None)
        self._set_lanes = getattr(self.engine, "set_lane_adapters", None)
        self._lora_zero = int(getattr(self.engine, "zero_slot", 0))
        # a swap killed the old pool's device state with the old engine:
        # any queued request still pointing at an old slot re-leases
        # against the fresh pool at its next admission
        for req in self.waiting:
            req._adapter_slot = None
        for req in self.slots:
            if req is not None:
                req._adapter_slot = None
        linfo = getattr(self.engine, "lora_info", None)
        if linfo is not None:
            try:
                self.metrics.on_lora(linfo())
            except Exception:
                _monitor.inc("serving.lora_info_errors")

    # ---- waiting-queue bookkeeping (cost-accounted) ----
    def _queue_push(self, req: Request, front: bool = False):
        if front:
            self.waiting.appendleft(req)
        else:
            self.waiting.append(req)
        self._queued_cost += req.cost
        self.metrics.gauge_queue(len(self.waiting), self._queued_cost)

    def _queue_pop(self) -> Request:
        req = self.waiting.popleft()
        self._queued_cost = max(0, self._queued_cost - req.cost)
        self.metrics.gauge_queue(len(self.waiting), self._queued_cost)
        return req

    def _queue_remove(self, req: Request):
        self.waiting.remove(req)
        self._queued_cost = max(0, self._queued_cost - req.cost)
        self.metrics.gauge_queue(len(self.waiting), self._queued_cost)

    # ---- submission / cancellation ----
    def submit(self, req: Request, now: Optional[float] = None) -> Request:
        """Admission control. Rejects/sheds (with `finish_reason`)
        instead of raising: over-long prompts, a full queue, and
        overload watermarks are load conditions, not bugs."""
        now = self._clock() if now is None else now
        req.t_submit = now
        self.metrics.on_submit()
        if _obs.enabled():
            self._obs_req(req, "queued", t0=now,
                          prompt_tokens=int(len(req.prompt)),
                          max_new_tokens=req.sampling.max_new_tokens)
        if self._broken is not None:
            return self._reject(req, self._broken)
        mgr = self.engine.manager
        if len(req.prompt) == 0:
            return self._reject(req, "empty_prompt")
        # +1: the sequence must be able to hold at least one generated token
        if mgr.blocks_needed(len(req.prompt) + 1) > self._usable_blocks:
            return self._reject(req, "prompt_too_long")
        for g, _pad in self._further_pads:
            # a further group holds a window's worth of a prompt at most,
            # and that has to fit its pool beside the guard block
            if mgr.blocks_needed(len(req.prompt) + 1, g, self._window_step) \
                    > mgr.num_blocks_of(g) - 1:
                return self._reject(req, "prompt_too_long")
        if req.adapter is not None:
            # typed submit-time rejection beats an admission-time fault:
            # an unknown adapter can never become leasable by waiting
            if self._lora is None:
                return self._reject(req, "no_adapter_pool")
            if not self._lora.is_registered(req.adapter):
                return self._reject(req, "unknown_adapter")
        if self._overload is not None:
            ctrl = self._overload_for(req.tenant)
            cfg = ctrl.cfg
            # the TPOT median only feeds the deadline estimate — don't
            # pay the numpy call on every no-deadline submit
            tpot = (self.tpot_estimate()
                    if cfg.deadline_aware and req.deadline is not None
                    else None)
            reason = ctrl.shed_reason(
                queue_depth=len(self.waiting),
                queued_cost=self._queued_cost,
                req_cost=req.cost,
                kv_utilization=mgr.utilization(),
                deadline=req.deadline, now=now,
                tpot_s=tpot, lanes=len(self.slots))
            if reason is not None:
                return self._shed(req, reason)
        if len(self.waiting) >= self.max_queue:
            return self._reject(req, "queue_full")
        self._queue_push(req)
        return req

    def import_session(self, req: Request, payload,
                       now: Optional[float] = None) -> Request:
        """Admit a request whose context KV arrives as a migrated
        `KVBlockPayload` (`inference/kv_migrate.py`) instead of through
        chunked prefill — the disaggregated-serving handoff and the
        KV-shipping relocation entry (ISSUE 17).

        Load conditions come back as terminal statuses exactly like
        `submit` (broken scheduler, empty prompt, over-long context,
        full queue — all checked BEFORE the pool is touched, so a
        rejection never leaks blocks). Migration problems raise TYPED:
        `KVMigrationError` (geometry/kv_bits/version mismatch, or an
        engine without the primitive) and the manager's
        `KVCacheExhausted`/`SequenceTooLong` from the inject's allocate
        — the router catches these and falls back to a committed-prefix
        re-prefill. On success the blocks sit resident under
        `req.seq_id`; `_admit` skips the lease/prefill for the covered
        context, so the pending `_last` token (when present) decodes on
        the importing replica's very next round — the decode worker
        owns the stream from token 1. Overload shedding is deliberately
        skipped: an import carries already-spent prefill work, and
        turning it away would discard it (capacity pressure still
        rejects through the queue/pool checks)."""
        self.settle()
        now = self._clock() if now is None else now
        if req.t_submit is None:
            req.t_submit = now
        self.metrics.on_submit()
        if _obs.enabled():
            self._obs_req(req, "queued", t0=now, imported_kv=True,
                          prompt_tokens=int(len(req.prompt)),
                          max_new_tokens=req.sampling.max_new_tokens)
        if self._broken is not None:
            return self._reject(req, self._broken)
        if len(req.prompt) == 0:
            return self._reject(req, "empty_prompt")
        mgr = self.engine.manager
        if mgr.blocks_needed(int(payload.num_tokens) + 1) \
                > self._usable_blocks:
            return self._reject(req, "prompt_too_long")
        if len(self.waiting) >= self.max_queue:
            return self._reject(req, "queue_full")
        inject = getattr(self.engine, "inject_kv_blocks", None)
        if inject is None:
            raise KVMigrationError(
                f"{type(self.engine).__name__} has no inject_kv_blocks "
                "— this engine cannot accept migrated KV")
        ctx = req.context_tokens()
        if int(payload.num_tokens) != len(ctx):
            raise KVMigrationError(
                f"payload carries KV for {payload.num_tokens} tokens "
                f"but the request's committed context is {len(ctx)}")
        inject(req.seq_id, payload)     # typed errors propagate; a
        req._kv_resident = True         # failed inject leaves no blocks
        req.status = RequestStatus.QUEUED
        req.finish_reason = None
        self._queue_push(req)
        return req

    def _drop_resident_kv(self, req: Request) -> None:
        """Free KV imported via `import_session` for a request leaving
        the WAITING queue (deadline, cancel, release, fail-all) before
        admission claimed it — the in-slot paths free through the
        normal `_finish`/`release` branches. Idempotent; never raises
        into a terminal transition."""
        if not req._kv_resident:
            return
        req._kv_resident = False
        try:
            if self.engine.manager.seq_blocks(req.seq_id) > 0:
                self.engine.manager.free(req.seq_id)
        except Exception:
            pass

    def _overload_for(self, tenant: str) -> OverloadController:
        """The overload controller for `tenant`: the shared base one
        without an SLO config; with one, a per-tenant controller whose
        watermarks are tier-scaled (`SLOClass.admission_scale`) and
        whose hysteresis latches are private — a batch tier latching
        shed must not shed the interactive tier."""
        if self._slo is None:
            return self._overload
        ctrl = self._overload_by_tenant.get(tenant)
        if ctrl is None:
            c = self._slo.cls(tenant)
            cfg = (self._overload.cfg if c.admission_scale == 1.0
                   else c.scaled_admission(self._overload.cfg))
            ctrl = OverloadController(cfg)
            self._overload_by_tenant[tenant] = ctrl
        return ctrl

    def _tenant_held(self) -> dict:
        """Pool blocks held per tenant (running slots only), counting
        each running request at its COMMITTED footprint — the larger of
        blocks leased now and blocks its admitted context will need —
        so a quota can't overshoot while prefill chunks are still
        landing. Per-lease counts: a shared prefix charges each tenant
        holding it, the conservative reading of a quota."""
        mgr = self.engine.manager
        held: dict = {}
        for r in self.slots:
            if r is not None:
                blocks = max(mgr.seq_blocks(r.seq_id),
                             mgr.blocks_needed(len(r._prefill_ctx) + 1))
                held[r.tenant] = held.get(r.tenant, 0) + blocks
        return held

    def prefix_stats(self) -> Optional[dict]:
        """Per-instance prefix-cache counters (None with the cache
        off) — what the fleet heartbeat payload reports per replica
        (monitor counters are process-global)."""
        t = self._prefix_tree
        return None if t is None else t.stats()

    @property
    def prefix_cache(self) -> Optional[RadixPrefixCache]:
        return self._prefix_tree

    # ---- cross-replica prefix streaming (ISSUE 17) ----
    def _mig_seq_id(self) -> int:
        """A fresh transient sequence id for a prefix-stream lease —
        negative and far below the pad-guard probe range, so it cannot
        collide with request ids (non-negative) or another scheduler's
        guard on a shared engine."""
        mgr = self.engine.manager
        while True:
            self._mig_seq -= 1
            if mgr.seq_blocks(self._mig_seq) == 0:
                return self._mig_seq

    def export_prefix(self, tokens):
        """Export the radix-cached KV for the longest FULL-block cached
        prefix of `tokens` as a migration payload
        (`inference/kv_migrate.py`) — the sender side of cross-replica
        prefix reuse. The gather rides a transient lease (adopt →
        extract → free), so the tree's pins and every concurrent
        request are untouched and extraction stays a copy. Returns None
        when there is nothing to ship: cache off, engine without the
        primitive, or a hit shorter than one block."""
        tree = self._prefix_tree
        extract = getattr(self.engine, "extract_kv_blocks", None)
        if tree is None or extract is None:
            return None
        self.settle()
        blocks, hit = tree.match_export(tokens)
        if not blocks:
            return None
        mgr = self.engine.manager
        tmp = self._mig_seq_id()
        mgr.adopt(tmp, blocks, hit)
        try:
            return extract(tmp)
        finally:
            mgr.free(tmp)

    def import_prefix(self, tokens, payload) -> int:
        """Publish a streamed prefix payload (a peer's `export_prefix`)
        into THIS replica's radix tree: inject under a transient
        sequence, publish the full blocks, release the lease — the
        tree's pins keep the KV alive for future leases, and blocks
        whose content the local tree already indexes fall straight back
        to the pool (existing nodes win ties). Returns cached tokens
        gained; 0 when the local tree already covers the payload, the
        pool has no room (a stream must not pressure a loaded pool), or
        the cache/primitive is off. Typed migration errors propagate —
        the fleet caller counts and swallows them (a failed stream just
        means a cold prefill, never a failed request)."""
        tree = self._prefix_tree
        inject = getattr(self.engine, "inject_kv_blocks", None)
        if tree is None or inject is None:
            return 0
        self.settle()
        toks = np.asarray(tokens).reshape(-1).tolist()
        n = int(payload.num_tokens)
        if n < 1 or len(toks) < n:
            return 0
        _blocks, local = tree.match_export(toks)
        if local >= n:
            return 0
        mgr = self.engine.manager
        if int(payload.num_blocks) > min(mgr.free_blocks,
                                         self._usable_blocks):
            return 0
        tmp = self._mig_seq_id()
        inject(tmp, payload)
        try:
            added = tree.publish(tmp, toks[:n])
        finally:
            mgr.free(tmp)
        return n if added else 0

    def _reject(self, req: Request, reason: str) -> Request:
        req.status = RequestStatus.REJECTED
        req.finish_reason = reason
        req.t_finish = self._clock()
        self.metrics.on_reject(reason)
        if _obs.enabled():
            self._obs_req(req, "terminal:rejected", t0=req.t_finish,
                          reason=reason)
        return req

    def _shed(self, req: Request, reason: str) -> Request:
        req.status = RequestStatus.SHED
        req.finish_reason = reason
        req.t_finish = self._clock()
        self.metrics.on_shed(reason)
        if _obs.enabled():
            self._obs_req(req, "terminal:shed", t0=req.t_finish,
                          reason=reason)
        return req

    def in_flight(self) -> List[Request]:
        """Every non-terminal request this scheduler owns, admission
        order first (running slots by admit sequence) then the waiting
        queue — the export surface a fleet router drains or relocates
        from."""
        running = sorted(((r._admit_seq, i) for i, r in
                          enumerate(self.slots) if r is not None))
        return [self.slots[i] for _, i in running] + list(self.waiting)

    def release(self, req: Request) -> bool:
        """Remove a non-terminal request from this scheduler WITHOUT
        assigning a terminal status: its blocks are freed, speculative
        state dropped, and the request lands in PREEMPTED with its
        tokens-so-far intact — exactly the preemption invariant, so a
        re-submission elsewhere (fleet relocation, drain) replays
        token-deterministically with the committed tokens as prompt
        prefix. Unlike a preemption it does NOT re-queue here and does
        not bump preemption counters (a drain is policy, not pressure).
        Returns False when the request is terminal or not owned here."""
        if req.status.terminal:
            return False
        if req in self.waiting:
            self._queue_remove(req)
            self._drop_resident_kv(req)
            self._adapter_release(req)
            req.status = RequestStatus.PREEMPTED
            return True
        for i, r in enumerate(self.slots):
            if r is req:
                self.slots[i] = None
                self._publish_prefix(req)
                self.engine.manager.free(req.seq_id)
                self._release_spec(req)
                self._adapter_release(req)
                req.status = RequestStatus.PREEMPTED
                return True
        return False

    def cancel(self, req: Request) -> bool:
        if req.status.terminal:
            return False
        if req in self.waiting:
            self._queue_remove(req)
            self._finish(req, RequestStatus.CANCELLED, "cancelled",
                         in_slot=False)
            return True
        for i, r in enumerate(self.slots):
            if r is req:
                self._finish(req, RequestStatus.CANCELLED, "cancelled",
                             slot=i)
                return True
        return False

    # ---- the step ----
    def step(self, now: Optional[float] = None) -> int:
        """One scheduling round: expire deadlines, admit into free slots,
        LAUNCH one fixed-shape step over the occupied slots, then SETTLE
        the step launched by the call before (docs/SERVING.md "A round in
        flight"). Returns the number of tokens it committed, which are
        that earlier launch's: the first call after a lull commits none,
        and a call with nothing to launch settles what is in flight.
        `idle` stays false until the last launch is settled. (A
        speculative scheduler launches and settles in the same call.)"""
        self._step_index += 1
        with RecordEvent("sched.step", step=self._step_index):
            # the round's own start, for its wall: a caller's `now` (a
            # replayed or synthetic clock) only decides the scheduling
            began = self._clock()
            now = began if now is None else now
            finish_mark = self._finish_events
            with RecordEvent("sched.expire"):
                self._expire(now)
            with RecordEvent("sched.admit"):
                admitted = self._admit(now)
            produced = self._decode(now)
            # progress = tokens, prefill-chunk advancement, admissions,
            # terminal transitions, or a round launched (its tokens come
            # with the next call); a non-idle scheduler sustaining zero
            # progress is wedged — the watchdog's restart trigger and
            # `EngineStalled`'s evidence
            if produced > 0 or admitted > 0 or self._chunk_progress > 0 \
                    or self._finish_events > finish_mark \
                    or self._launched is not None:
                self._zero_progress = 0
            else:
                self._zero_progress += 1
            if self._pending_stall is not None:
                reason, self._pending_stall = self._pending_stall, None
                self._stall(reason)
            elif (self._wd is not None and not self.idle
                    and self._zero_progress >= self._wd.stall_steps):
                self._stall("zero_progress")
            mgr = self.engine.manager
            # occupancy = decoded lanes / total lanes for THIS step (finished
            # sequences were already evicted, so num_running undercounts)
            self.metrics.on_step(
                occupancy=produced / len(self.slots),
                kv_utilization=mgr.utilization(),
                queue_depth=len(self.waiting),
                decoded=produced > 0,
                wall_s=self._clock() - began)
            if self._state:
                self.metrics.on_state_slots(
                    mgr.num_blocks - mgr.free_blocks - 1)
            return produced

    @property
    def num_running(self) -> int:
        return sum(r is not None for r in self.slots)

    @property
    def idle(self) -> bool:
        return self.num_running == 0 and not self.waiting \
            and self._launched is None

    @property
    def zero_progress_steps(self) -> int:
        """Consecutive steps with no token, admission, or finish — the
        frontend raises `EngineStalled` off this when no watchdog runs."""
        return self._zero_progress

    @property
    def engine_restarts_remaining(self) -> int:
        return self._restart_budget.remaining

    @property
    def watchdog_active(self) -> bool:
        """True when a watchdog owns stall recovery — the frontend's
        `stall_after` fallback must stand down, or a tight setting would
        raise `EngineStalled` before the configured restart ever fires
        (stranding requests non-terminal with a live engine_factory)."""
        return self._wd is not None

    def tpot_estimate(self) -> Optional[float]:
        """Median recent decode-dispatch wall time (s), or None before
        the first timed dispatch — what deadline-aware shedding prices a
        queued token at."""
        if not self._tpot_samples:
            return None
        return float(np.median(np.asarray(self._tpot_samples)))

    def kv_leaked_blocks(self) -> int:
        """Blocks leased in the manager that belong to neither the
        guard, a running sequence, nor the radix prefix tree — must be 0
        for a sole-tenant scheduler (asserted by the chaos smoke after
        every injected fault). Counted over UNIQUE physical blocks: a
        shared block is one block however many leases point at it."""
        mgr = self.engine.manager
        held = mgr.num_blocks - mgr.free_blocks
        legit = set(mgr.blocks_of(self._pad_seq_id))
        for r in self.slots:
            if r is not None:
                legit.update(mgr.blocks_of(r.seq_id))
        if self._prefix_tree is not None:
            legit.update(self._prefix_tree.blocks())
        return held - len(legit)

    def _publish_prefix(self, req: Request) -> None:
        """Publish a departing request's committed context KV into the
        radix tree (full blocks only), BEFORE the manager frees its
        lease — a popular prompt's KV outlives its first request. A
        prefilling lane publishes only the chunks already committed;
        publication must never break the terminal-status path."""
        tree = self._prefix_tree
        if tree is None:
            return
        mgr = self.engine.manager
        if not mgr.seq_blocks(req.seq_id):
            return
        try:
            if req.prefilling:
                toks = req._prefill_ctx[
                    :min(req._prefill_pos, mgr.seq_len(req.seq_id))]
            else:
                toks = req.context_tokens()
                toks = toks[:min(len(toks), mgr.seq_len(req.seq_id))]
            if len(toks) >= mgr.block_size:
                tree.publish(req.seq_id, toks)
        except Exception:
            pass

    # ---- fault boundary ----
    def _dispatch(self, phase: str, fn, *args):
        """One engine dispatch behind the typed fault boundary: the
        `serve.<phase>` injection site fires here, the wall clock feeds
        the TPOT estimate + watchdog stall detection, and a `"flag"`
        injection asks the caller to poison one lane (NaN path).
        Returns (result, flagged)."""
        flagged = _faults.check_flag(f"serve.{phase}")
        obs_on = _obs.enabled()
        if obs_on:
            # trace-time counter snapshot: a bump during the call below
            # means THIS dispatch retraced — its signature diff is the why
            retraces_before = _monitor.get(f"serving.{phase}_retraces")
            compiles_before = _obs.compile_trace.mark()
        t0 = self._clock()
        try:
            out = fn(*args)
            self.metrics.on_step_program()
        finally:
            dt = self._clock() - t0
            if self._wd is not None and dt > self._wd.stall_timeout_s:
                self.metrics.on_stall()
                self._pending_stall = f"step_timeout:{phase}"
        if phase in ("decode", "verify"):
            # successful dispatches only: a burst of fast-failing
            # dispatches would otherwise drag the median toward zero and
            # silently disable deadline-aware shedding exactly while the
            # engine is unhealthy. The caller converts it to a per-token
            # price once it knows how many tokens the round committed
            # (a verify dispatch commits up to K+1 per lane).
            self._last_decode_dt = dt
        if obs_on:
            self._obs_dispatch(phase, args, t0, dt, retraces_before,
                               compiles_before)
        return out, flagged

    def _obs_dispatch(self, phase: str, args, t0: float, dt: float,
                      retraces_before: int, compiles_before: int):
        """Observability bookkeeping for one successful dispatch: retrace
        cause attribution (signature diff vs the previous dispatch of the
        same phase, put on the compile record the dispatch made since
        `compiles_before`), the engine-track timeline span, per-executable call
        accounting, and — once per phase — the XLA CostCard. Only ever
        called with observability enabled."""
        name = f"serve.{phase}"
        sig = tuple((np.shape(a), str(np.asarray(a).dtype)) for a in args)
        if _monitor.get(f"serving.{phase}_retraces") > retraces_before:
            cause = _obs.compile_trace.note_retrace(name, sig,
                                                    compiles_before)
            if cause is not None:   # None = first trace: not a retrace
                _monitor.inc(f"serving.{phase}_retrace_causes."
                             + ("shape" if "shape" in cause else
                                "dtype" if "dtype" in cause else "other"))
        else:
            _obs.compile_trace.note_signature(name, sig)
        _obs.timeline.dispatch_span(phase, t0, t0 + dt)
        _obs.costs.record_call(name, dt)
        # the card lowers the engine fn once (one extra trace, charged to
        # the counters AFTER the snapshot above — never misattributed)
        if phase == "decode":
            args = call_arrays(*args)    # with the `fed` the engine adds
        _obs.costs.ensure_engine_card(name, self.engine, phase, args)

    def _obs_req(self, req: Request, name: str, t0: Optional[float] = None,
                 t1: Optional[float] = None, **meta):
        """Request-track timeline event; call sites guard on
        `_obs.enabled()` so the disabled path allocates nothing. A
        LoRA request's adapter rides every event — the timeline answers
        "whose TTFT paid an adapter load" without a metrics join."""
        if req.adapter is not None and "adapter" not in meta:
            meta["adapter"] = req.adapter
        _obs.timeline.request_event(
            req.req_id, name, self._clock() if t0 is None else t0, t1,
            **meta)

    def _live_requests_brief(self):
        """The running set, compact, for the OOM forensics dump."""
        return [{"req_id": r.req_id, "seq_id": r.seq_id, "slot": i,
                 "tokens": len(r.generated),
                 "kv_blocks": self.engine.manager.seq_blocks(r.seq_id)}
                for i, r in enumerate(self.slots) if r is not None]

    def _obs_oom(self, reason: str, **extra):
        """OOM forensics (observability/memory.py): memory + KV map +
        live request set to `flight_oom_*.jsonl`. Rate-limited inside
        `dump_oom`; call sites guard on `_obs.enabled()`."""
        _obs.memory.dump_oom(reason, manager=self.engine.manager,
                             live_requests=self._live_requests_brief(),
                             extra=extra or None)

    def _record_tpot(self, n_lanes: int, produced: int):
        """Price the last decode/verify dispatch per lane-token: a round
        that committed `produced` tokens across `n_lanes` lanes costs
        `dt / (produced / n_lanes)` seconds per token. Plain decode
        (1 token/lane) reduces to the raw dispatch time; pricing a
        speculative verify at its raw time would overstate the per-token
        cost ~K-fold and deadline-shed requests that are easily on time."""
        if produced > 0 and self._last_decode_dt is not None:
            self._tpot_samples.append(
                self._last_decode_dt * n_lanes / produced)

    def _finite_rows(self, logits) -> np.ndarray:
        """Row-finiteness mask reduced ON DEVICE (`[..., V] -> [...]`
        bool), for the speculative round (the plain round's screen is
        the tail of its step's program): the NaN screen must not
        materialize the full logits on host — at a realistic vocab that
        is a multi-MB D2H copy per step. One trace per logits rank,
        cached for the scheduler's lifetime."""
        import jax

        if self._finite_fn is None:
            import jax.numpy as jnp

            def nan_screen(x):
                with jax.named_scope("llama.nan_screen"):
                    return jnp.isfinite(x).all(axis=-1)

            self._finite_fn = jax.jit(nan_screen)
        with RecordEvent("sched.screen"):
            finite = np.asarray(self._finite_fn(logits))
        self.metrics.on_step_program()
        self.metrics.on_step_fetch()
        return finite

    def _isolated(self, req: Request, reason: str, phase: str,
                  slot: Optional[int] = None, in_slot: bool = True):
        """Fail ONE request at the fault boundary; everyone else keeps
        serving."""
        self.metrics.on_isolated_fault(phase)
        self._finish(req, RequestStatus.FAILED, reason, slot=slot,
                     in_slot=in_slot)

    def _step_fault(self, phase: str, exc: BaseException, lanes,
                    probe=None, rollback=None):
        """A whole-batch dispatch raised. Attribute it: typed
        `EngineStepError.seq_ids` are trusted; otherwise each lane is
        replayed alone (`probe`) and lanes that raise or return
        non-finite rows are culpable. Culpable requests fail; survivors
        roll back their cache bookkeeping (`rollback`) and replay next
        round — deterministically, since decode KV writes are
        position-indexed and idempotent. No culprit = transient: retried
        under `step_retries`, then escalated to the watchdog. Over a state
        group no lane is replayed (a replay would feed a state its tokens
        twice): the survivors' `rollback` restarts them from their tokens."""
        lanes = [(i, r) for i, r in lanes if self.slots[i] is r]
        culpable = []
        if isinstance(exc, EngineStepError) and exc.seq_ids:
            ids = set(exc.seq_ids)
            culpable = [(i, r) for i, r in lanes if r.seq_id in ids]
        elif probe is not None and not self._state \
                and not isinstance(exc, _faults.InjectedFault):
            # an untargeted injected fault models a transient dispatch
            # failure — probing real hardware state would find nothing
            for i, r in lanes:
                try:
                    row = probe(i, r)
                    bad = not np.isfinite(np.asarray(row)).all()
                except Exception:
                    bad = True
                if bad:
                    culpable.append((i, r))
        culp_ids = {r.seq_id for _, r in culpable}
        if rollback is not None:
            rollback([(i, r) for i, r in lanes if r.seq_id not in culp_ids])
        for i, r in culpable:
            self._isolated(r, f"engine_fault:{phase}", phase, slot=i)
        if culpable:
            self._step_faults = 0
            return
        self._step_faults += 1
        self.metrics.on_step_fault(phase)
        if _obs.enabled():
            _obs.timeline.dispatch_span(f"step_fault:{phase}",
                                        self._clock(), None,
                                        error=type(exc).__name__)
            _obs.timeline.dump_flight(f"step_fault_{phase}")
            if "RESOURCE_EXHAUSTED" in repr(exc):
                # backend allocation failure: the device-side OOM twin of
                # the KV-pool exhaustion dump
                self._obs_oom(f"backend_{phase}",
                              error=type(exc).__name__)
        limit = self._wd.step_retries if self._wd is not None else 3
        if self._step_faults > limit:
            self._step_faults = 0
            self._restart_engine(f"step_faults:{phase}")

    def _stall(self, reason: str):
        if reason == "zero_progress":
            self.metrics.on_stall()
        self._zero_progress = 0
        self._restart_engine(reason)

    def _restart_engine(self, reason: str) -> bool:
        """Bounded-restart supervisor: re-queue every in-flight sequence
        with tokens-so-far intact (preemption semantics — re-prefill on
        re-admission is token-deterministic), rebuild the engine through
        the factory, re-lease the guard block from the fresh pool. Out
        of budget (or no factory): fail every non-terminal request typed
        — the terminal-status contract over a dead engine."""
        # a restart resolves any stall recorded for the dispatch that
        # triggered it — without this, a dispatch that is both slow and
        # raising would burn TWO budget units (escalation restart, then
        # the stale pending stall restarting the fresh engine)
        self._pending_stall = None
        # a round in flight dies with the engine: its lanes' requests
        # re-queue below with the tokens committed so far
        self._launched = None
        if _obs.enabled():
            # post-mortem evidence FIRST: the ring holds the rounds that
            # led here, and the rebuild below may fail everything
            _obs.timeline.dump_flight(f"engine_restart_{reason}")
            _obs.timeline.dispatch_span(f"engine_restart:{reason}",
                                        self._clock(), None)
        if self.engine_factory is None or not self._restart_budget.spend():
            self._fail_all(f"engine_unrecoverable:{reason}")
            return False
        mgr = self.engine.manager
        running = sorted(((r._admit_seq, i, r)
                          for i, r in enumerate(self.slots) if r is not None),
                         reverse=True)
        for _, i, req in running:   # newest first -> oldest ends at front
            self.slots[i] = None
            try:
                mgr.free(req.seq_id)
            except KeyError:
                pass
            self._release_spec(req)
            # NOT _adapter_release: the old pool's device state (and its
            # lease books) die with the old engine — releasing a stale
            # slot against the FRESH pool would corrupt its refcounts.
            # `_bind_manager` below clears every queued slot the same way.
            req._adapter_slot = None
            req.status = RequestStatus.PREEMPTED
            req.num_preemptions += 1
            self._queue_push(req, front=True)
            self.metrics.on_preempt()
            if _obs.enabled():
                self._obs_req(req, "preempted", reason=f"restart:{reason}",
                              tokens_kept=len(req.generated))
        try:
            engine = retry_call(
                self.engine_factory,
                retries=self._wd.rebuild_retries if self._wd else 1,
                retry_on=(Exception,), base_delay=0.0, jitter=0.0,
                sleep=lambda _s: None,
                monitor_name="serving.engine_rebuild_retries")
            self.engine = engine
            # the rebind runs the serve.cache chaos site (guard-block
            # allocate) — it MUST stay inside this boundary, or a cache
            # fault here escapes step() and strands the re-queued
            # requests non-terminal
            self._bind_manager(engine.manager)
        except Exception:
            # a failed rebind can leave a stale guard-block id pointing
            # into the fresh pool (where it is free, so a real sequence
            # could lease it and pad writes would corrupt it): this
            # scheduler must not serve again
            self._broken = f"engine_rebuild_failed:{reason}"
            self._fail_all(self._broken)
            return False
        self._step_faults = 0
        self._zero_progress = 0
        # the old window priced tokens at the DEAD engine's dispatch
        # times — keeping it would deadline-shed requests the fresh
        # engine can easily serve
        self._tpot_samples.clear()
        self._last_decode_dt = None
        self.metrics.on_engine_restart(reason)
        return True

    def _fail_all(self, reason: str):
        for i, req in enumerate(self.slots):
            if req is not None:
                self._finish(req, RequestStatus.FAILED, reason, slot=i)
        while self.waiting:
            req = self._queue_pop()
            self._finish(req, RequestStatus.FAILED, reason, in_slot=False)

    # ---- phases ----
    def _expire(self, now: float):
        for req in [r for r in self.waiting
                    if r.deadline is not None and now > r.deadline]:
            self._queue_remove(req)
            self._finish(req, RequestStatus.TIMED_OUT, "deadline_in_queue",
                         in_slot=False)
        for i, req in enumerate(self.slots):
            if req is not None and req.deadline is not None \
                    and now > req.deadline:
                self._finish(req, RequestStatus.TIMED_OUT,
                             "deadline_while_running", slot=i)

    def _next_admit(self, mgr, skip: set) -> Optional[Request]:
        """The next request to TRY admitting. Without an SLO config:
        strict FIFO (the head). With one: deficit-weighted fair queuing
        across tenants — each tenant's head request competes, the
        eligible tenant with the lowest virtual time wins (admissions
        cost `1/weight`), quota-capped tenants are skipped WITHOUT
        blocking the others. Returns None when nothing is eligible."""
        if self._slo is None:
            return self.waiting[0]
        heads = {}
        for r in self.waiting:          # queue order -> FIFO tie-break
            if r.tenant not in heads:
                heads[r.tenant] = r
        held = None
        eligible = []
        for t, r in heads.items():
            if t in skip:
                continue
            c = self._slo.cls(t)
            if c.kv_quota_blocks is not None:
                if held is None:
                    held = self._tenant_held()
                need_all = mgr.blocks_needed(len(r.context_tokens()) + 1)
                if held.get(t, 0) + need_all > c.kv_quota_blocks:
                    skip.add(t)         # its own finishes free quota
                    self.metrics.on_tenant_deferred(t, "kv_quota")
                    continue
            eligible.append((t, r))
        if not eligible:
            return None
        # effective time = max(own clock, system clock): an idle
        # tenant's stale low clock fast-forwards to NOW (the system
        # clock only advances at admissions), so it cannot bank arrears
        # while quiet and then monopolize every lane on return
        _best_t, best_r = min(
            eligible,
            key=lambda tr: max(self._vtime.get(tr[0], 0.0), self._vclock))
        return best_r

    def _charge_admission(self, tenant: str) -> None:
        if self._slo is not None:
            start = max(self._vtime.get(tenant, 0.0), self._vclock)
            self._vclock = start
            self._vtime[tenant] = start \
                + 1.0 / self._slo.cls(tenant).weight
            self.metrics.on_tenant_admit(tenant)

    def _admit(self, now: float) -> int:
        """Place queued requests into free slots. Admission leases the
        deepest radix-cached prefix of the context when the prefix cache
        is on (refcount bump — those tokens never prefill; chunking
        resumes from the first uncached block) and otherwise only the
        sequence id (a zero-token allocation = one block); the remaining
        KV enters the cache chunk-by-chunk through the ragged step — no
        bucket padding, no per-admission prefill dispatch, and the lease
        always tracks the TRUE context length. The first token samples
        when the final chunk completes (inside the ragged round's commit
        loop). Under an SLO config the admit order is tenant-fair
        (`_next_admit`) and gated by per-tenant quotas and reserves."""
        mgr = self.engine.manager
        admitted = 0
        skip: set = set()               # tenants deferred this round
        # adapter-miss admissions are PRICED: each pays a pool upload
        # (possibly an eviction first), so only this many may enter per
        # round — resident-adapter requests stay free and unbudgeted
        miss_budget = self.adapter_miss_loads_per_step
        while self.waiting and None in self.slots:
            req = self._next_admit(mgr, skip)
            if req is None:
                break                  # every queued tenant deferred
            ctx = req.context_tokens()
            # admit only when the WHOLE context could lease right now —
            # the same admission pressure the full-prefill scheduler had
            # (it physically leased the full context at admission, so a
            # second admission saw the first's blocks already gone; here
            # that outstanding demand is the prefill DEBT of admitted
            # lanes still mid-chunking, and must be subtracted or two
            # large prompts would both admit against the same free count
            # and preempt-churn mid-prefill). Radix-cached blocks and
            # tree-reclaimable blocks both count as capacity: a hit
            # adopts shared blocks (no free-list draw), and the tree
            # surrenders unpinned blocks on demand.
            debt = sum(
                max(0, mgr.blocks_needed(len(r._prefill_ctx))
                    - mgr.seq_blocks(r.seq_id))
                for r in self.slots if r is not None and r.prefilling)
            # imported-KV admission (`import_session`, ISSUE 17): the
            # context blocks are already leased under seq_id, so the
            # request needs NO new capacity and no radix lease
            resident = req._kv_resident and mgr.seq_blocks(req.seq_id) > 0
            hit_blocks = (self._prefix_tree.match_blocks(ctx)
                          if self._prefix_tree is not None
                          and not resident else 0)
            need = 0 if resident \
                else mgr.blocks_needed(len(ctx)) - hit_blocks
            headroom = mgr.free_blocks + mgr.reclaimable_blocks() - debt
            if need > headroom or self._further_short(mgr, ctx):
                break                  # blocks return as runners finish
            if self._slo is not None:
                reserve = self._slo.total_reserve_excluding(
                    req.tenant, self._tenant_held())
                if need > headroom - reserve:
                    # honoring OTHER tenants' unused reserves: this
                    # tenant waits, the others may still admit
                    skip.add(req.tenant)
                    self.metrics.on_tenant_deferred(req.tenant,
                                                    "kv_reserve")
                    continue
            if req.adapter is not None and self._lora is not None:
                # adapter lease precedes the KV lease: residency is the
                # cheap common case (refcount bump), a miss spends the
                # round's priced load budget, and a full pool defers —
                # without an SLO config the queue is strict FIFO, so a
                # deferral must stop the round (skip is FIFO-invisible)
                resident_ad = self._lora.is_resident(req.adapter)
                if not resident_ad and miss_budget <= 0:
                    if self._slo is None:
                        break
                    skip.add(req.tenant)
                    self.metrics.on_tenant_deferred(req.tenant,
                                                    "adapter_miss")
                    continue
                try:
                    req._adapter_slot = self._lora.lease(req.adapter)
                except AdapterPoolExhausted:
                    if self._slo is None:
                        break          # leases return as runners finish
                    skip.add(req.tenant)
                    self.metrics.on_tenant_deferred(req.tenant,
                                                    "adapter_pool")
                    continue
                except Exception:      # injected/failed adapter load
                    self._queue_remove(req)
                    self._isolated(req, "engine_fault:adapter",
                                   "adapter", in_slot=False)
                    continue
                if not resident_ad:
                    miss_budget -= 1
            hit = 0
            if resident:
                # the migrated KV covers the committed context; the
                # chunk cursor starts past it. Without a pending `_last`
                # token the FINAL context token re-enters as a one-token
                # chunk so the first sample happens here — trim keeps
                # manager length == attended KV, and the position-
                # indexed rewrite is idempotent (same content, same
                # slot). With `_last` pending the cursor covers the
                # whole context and the token decodes next round — the
                # importing replica owns the stream immediately.
                req._kv_resident = False
                target = len(ctx) if req._last is not None \
                    else max(len(ctx) - 1, 0)
                if mgr.seq_len(req.seq_id) > target:
                    mgr.trim(req.seq_id, target)
                hit = mgr.seq_len(req.seq_id)
            else:
                try:
                    if self._prefix_tree is not None:
                        if self.prefix_stream_hook is not None \
                                and self._prefix_tree.match_tokens(
                                    ctx) == 0:
                            # first miss: ask the router for a peer's
                            # cached copy before paying a cold prefill
                            # (cross-replica prefix reuse); the hook
                            # never raises into admission
                            try:
                                self.prefix_stream_hook(ctx)
                            except Exception:
                                pass
                        hit = self._prefix_tree.lease(req.seq_id, ctx)
                    if hit == 0:
                        mgr.allocate(req.seq_id, 0)
                except (KVCacheExhausted, SequenceTooLong):
                    # the adapter lease taken above must not outlive
                    # this failed admission attempt
                    self._adapter_release(req)
                    break
                except Exception:      # injected/corrupt cache state
                    self._queue_remove(req)
                    self._isolated(req, "engine_fault:cache", "cache",
                                   in_slot=False)
                    continue
            self._queue_remove(req)
            with RecordEvent("sched.admit_one", req=req.req_id,
                             prompt=len(ctx), prefix_hit=hit):
                slot = self.slots.index(None)
                # snapshot the prefill target HERE: for a preempted
                # re-admission it includes the kept tokens, so the replay is
                # token-deterministic; the pending `_last` (when present)
                # stays pending and decodes after the chunks complete. A
                # prefix hit starts the cursor AT the hit — chunking resumes
                # from the first uncached token (a full hit leaves exactly
                # one token: TTFT ≈ one decode step).
                req._prefill_ctx = ctx
                req._prefill_pos = hit
                req._prefix_hit_tokens = 0 if resident else hit
                req._chunks = 0
                req._t_admit = self._clock()
                req.status = RequestStatus.RUNNING
                req._admit_seq = next(self._admit_counter)
                self.slots[slot] = req
                admitted += 1
                self._charge_admission(req.tenant)
                if self._prefix_tree is not None and not resident:
                    # a resident cursor is migrated KV, not a radix hit —
                    # keep the prefix-cache hit accounting honest
                    self.metrics.on_prefix_lease(hit)
                if _obs.enabled():
                    self._obs_req(req, "admitted", t0=req._t_admit, slot=slot,
                                  prefix_hit_tokens=hit or None,
                                  queue_wait_ms=round(
                                      (req._t_admit - req.t_submit) * 1e3, 3)
                                  if req.t_submit is not None else None)
        return admitted

    def _further_short(self, mgr, ctx) -> bool:
        """The admission test of `_admit` in every FURTHER block group
        (none for an engine of one layer kind): what the context holds of
        the group at once, a window's worth at most, against the group's
        free blocks less what the lanes still prefilling will take."""
        step = self._window_step
        for g, _pad in self._further_pads:
            debt = sum(
                max(0, mgr.blocks_needed(len(r._prefill_ctx), g, step)
                    - mgr.seq_blocks(r.seq_id, g))
                for r in self.slots if r is not None and r.prefilling)
            if mgr.blocks_needed(len(ctx), g, step) \
                    > mgr.free_blocks_of(g) - debt:
                return True
        return False

    def _grow_chunk(self, req: Request, slot: int, want: int) -> int:
        """Reserve cache slots for the next `want` prefill-chunk tokens.
        Under pool pressure the chunk shrinks to what the free pool (plus
        the last leased block's slack) holds before anyone is preempted —
        the prefill analog of `_grow_n`'s drop-the-drafts degrade.
        Returns tokens reserved (0 = nothing this round, or the request
        left the batch). Raises `_SettleFirst` where it would preempt or
        finish while a round is in flight."""
        mgr = self.engine.manager
        ahead = self._ahead(slot, req)
        held = ahead.n if ahead is not None else 0
        while True:
            try:
                mgr.append_tokens(req.seq_id, want, in_flight=held)
                return want
            except SequenceTooLong:
                cap = mgr.max_blocks_per_seq * mgr.block_size \
                    - mgr.seq_len(req.seq_id)
                if cap >= 1:
                    want = min(want, cap)
                    continue
                if self._launched is not None:
                    raise _SettleFirst from None
                # unreachable for submit-screened prompts (ctx + 1 fits
                # the per-seq cap); terminal rather than a spin if an
                # engine swap shrank the cap under a live request
                self._finish(req, RequestStatus.FINISHED, "length_cap",
                             slot=slot)
                return 0
            except KVCacheExhausted as e:
                # capacity already in hand: the leased blocks' unused
                # tail (a fresh admission holds one ENTIRELY empty
                # block), plus whatever the free pool still has. A
                # shorter chunk moves no one else, so it needs no settle
                slack = mgr.seq_blocks(req.seq_id) * mgr.block_size \
                    - mgr.seq_len(req.seq_id)
                # `e.free`: what the group that ran out (`e.group`) has
                fit = e.free * mgr.block_size + slack
                if 1 <= fit < want:
                    want = fit
                    continue
                if self._launched is not None:
                    raise _SettleFirst from None
                if _obs.enabled():
                    self._obs_oom("kv_exhausted", need=e.need, free=e.free,
                                  total=e.total, seq_id=req.seq_id,
                                  group=e.group)
                if not self._preempt_one(exclude=req):
                    # sole lane over an externally-held pool: wait (the
                    # stall detectors own the pathological case)
                    return 0

    def _pad_tables(self, mgr, lanes: int) -> np.ndarray:
        """`[lanes, table_width]` block tables that point every entry at
        the guard block (of its own group, where the engine has several)."""
        tables = np.full((lanes, mgr.table_width), self._pad_block, np.int32)
        for g, pad in self._further_pads:
            w = mgr.max_blocks_per_seq
            tables[:, g * w:(g + 1) * w] = pad
        return tables

    @staticmethod
    def _sampling_arrays(reqs):
        """Per-lane (temperature, top_k, seed, draw_idx) vectors for the
        fused device sampler; `None` entries (padded lanes) sample greedy
        with dummy params. `draw_idx` is tokens drawn so far, so draws are
        reproducible across preemption and batch-slot churn. The seed is
        the request's own (same seed + same prompt -> same stream, across
        runs and speculative/plain paths alike — nothing process-global
        enters the key)."""
        temps = np.asarray([0.0 if r is None else r.sampling.temperature
                            for r in reqs], np.float32)
        # mask user-supplied ints to 31 bits: numpy >= 2.0 raises
        # OverflowError on out-of-range int32 construction, and a caller
        # passing seed=2**31 must not crash the whole decode step (the
        # mask is deterministic, so reproducibility is preserved)
        topks = np.asarray([0 if r is None else
                            int(r.sampling.top_k) & 0x7FFFFFFF
                            for r in reqs], np.int32)
        seeds = np.asarray([0 if r is None else
                            int(r.sampling.seed) & 0x7FFFFFFF
                            for r in reqs], np.int32)
        draws = np.asarray([0 if r is None else len(r.generated)
                            for r in reqs], np.int32)
        return temps, topks, seeds, draws

    def _grow(self, req: Request, slot: int) -> bool:
        """Account the pending token's cache slot; preempt on exhaustion.
        Returns False if the request left the batch instead. One policy,
        two entry points: this is `_grow_n` with a single-token request,
        so the length_cap/kv_capacity/preemption ladder cannot diverge
        between the plain and speculative decode paths."""
        return self._grow_n(req, slot, 1) == 1

    def _preempt_one(self, exclude: Request) -> bool:
        """Evict the most-recently-admitted running request (≠ exclude)
        back to the FRONT of the queue, keeping its tokens so far."""
        victims = [(r._admit_seq, i) for i, r in enumerate(self.slots)
                   if r is not None and r is not exclude]
        if not victims:
            return False
        _, slot = max(victims)
        req = self.slots[slot]
        with RecordEvent("sched.preempt", req=req.req_id):
            self._publish_prefix(req)
            self.engine.manager.free(req.seq_id)
            self._release_spec(req)
            self._adapter_release(req)
            self.slots[slot] = None
            req.status = RequestStatus.PREEMPTED
            req.num_preemptions += 1
            self._queue_push(req, front=True)
            self.metrics.on_preempt()
            if _obs.enabled():
                self._obs_req(req, "preempted", reason="kv_pressure",
                              tokens_kept=len(req.generated))
        return True

    def _decode(self, now: float) -> int:
        """One plain round's worth of `step()`: LAUNCH the next round
        (grow, pack, dispatch: `_launch`), then SETTLE the one launched
        before it (fetch `[2, B]`, screen, commit: `_settle`), so the
        host's work for round n+1 and round n's fetch run under the
        device's work. Returns the decode tokens committed, which are the
        older round's (prefill progress is tracked separately)."""
        self._chunk_progress = 0
        self._produced = 0
        if self.spec is not None:
            # a speculative scheduler launches no plain round, so none is
            # ever in flight here
            return self._decode_spec(now)
        flying = self._launched
        if flying is not None \
                and self.engine.last_sampled is not flying.sampled:
            # the engine has stepped for someone else since (a shared
            # engine, a caller's `generate`), or a wrapper hides the
            # engine's array: its last `sampled` is not this round's, so
            # no lane can be fed on the device. Counted, because a
            # deployment that always lands here serves with no overlap
            self.metrics.on_forced_settle()
            self.settle()
        newer = self._launch()         # may settle `_launched` under pressure
        older, self._launched = self._launched, newer
        if older is not None:
            self._settle(older)        # a fetch fault drops `newer` too
        if newer is not None and newer.error is not None \
                and self._launched is newer:
            # the dispatch raised; the round before it is settled by now,
            # so today's probe replays each lane on the host's tokens
            self._launched = None
            self._step_fault("decode", newer.error, newer.pairs(),
                             probe=newer.probe,
                             rollback=lambda _s: self._rollback(
                                 newer.lanes.values()))
        return self._produced

    def settle(self) -> int:
        """Fetch and commit the round in flight, if there is one: what
        reads a running sequence's KV, length or pending token from
        outside a round (a session's or a prefix's export and import, KV
        migration) calls this first. Returns the tokens it committed."""
        rnd, self._launched = self._launched, None
        if rnd is None:
            return 0
        before = self._produced
        self._settle(rnd)
        return self._produced - before

    def _ahead(self, slot: int, req: Request) -> Optional["_Lane"]:
        """`req`'s lane in the round still in flight, or None."""
        rnd = self._launched
        lane = rnd.lanes.get(slot) if rnd is not None else None
        return lane if lane is not None and lane.holds(self) else None

    def _rollback(self, lanes, dispatched: bool = True) -> None:
        """Undo the growth of `lanes` (a newer round's before an older
        one's) where the request still holds its slot, so that the next
        round replays it cleanly from the length before the oldest. Over a
        state group a lane whose tokens were `dispatched` cannot be taken
        back (the state has them, or may have): the lane is restarted from
        its tokens instead."""
        mgr = self.engine.manager
        held = [lane for lane in lanes if lane.holds(self)]
        if self._state and dispatched:
            # newest admission first, so the oldest ends at the queue's front
            for lane in sorted(held, key=lambda ln: -ln.admit_seq):
                if lane.holds(self):       # one request, two rounds' lanes
                    self._restart_lane(lane.req, lane.slot)
            return
        for lane in held:
            mgr.unappend(lane.req.seq_id, lane.pre_len)

    def _restart_lane(self, req: Request, slot: int) -> None:
        """Free a state group's slot and re-queue its request at the front
        with its tokens kept: the re-prefill starts from a zero state at
        position 0, which is the only way back for a recurrent state."""
        with RecordEvent("sched.state_restart", req=req.req_id):
            self.engine.manager.free(req.seq_id)
            self._adapter_release(req)
            self.slots[slot] = None
            req.status = RequestStatus.PREEMPTED
            self._queue_push(req, front=True)
            self.metrics.on_state_restart()
            if _obs.enabled():
                self._obs_req(req, "preempted", reason="state_restart",
                              tokens_kept=len(req.generated))

    def _grow_lanes(self, active, plan) -> None:
        """Grow the launch's lanes and append them to `plan`: `(lane,
        first)` in slot order, `first` a chunk's start in the request's
        context or a decode lane's token (`fed_token(slot)` where it is
        still on the device). Raises `_SettleFirst` where growth meets a
        limit with a round in flight: `plan` then holds what was grown."""
        mgr = self.engine.manager
        budget = self.prefill_chunk_tokens
        for i, req in active:
            if self.slots[i] is not req:
                continue
            ahead = self._ahead(i, req)
            pos = req._prefill_pos + (ahead.n if ahead is not None
                                      and ahead.chunk else 0)
            rem = len(req._prefill_ctx) - pos
            pre_len = mgr.seq_len(req.seq_id)
            if rem > 0:
                if budget <= 0:
                    continue           # next step's budget serves it
                try:
                    got = self._grow_chunk(req, i, min(rem, budget))
                except _SettleFirst:
                    raise
                except Exception:      # injected/corrupt cache state
                    self._isolated(req, "engine_fault:cache", "cache",
                                   slot=i)
                    continue
                if got:
                    budget -= got
                    plan.append((_Lane(
                        i, req, got, pre_len, chunk=True,
                        samples=got == rem and req._last is None), pos))
                continue
            fed = ahead is not None and ahead.samples
            if fed and len(req.generated) + 1 \
                    >= req.sampling.max_new_tokens:
                continue               # its token in flight is its last
            try:
                ok = self._grow(req, i)
            except _SettleFirst:
                raise
            except Exception:          # injected/corrupt cache state:
                self._isolated(req, "engine_fault:cache", "cache",
                               slot=i)
                continue               # attribution is trivial
            if ok:
                plan.append((_Lane(i, req, 1, pre_len, chunk=False,
                                   samples=True),
                             fed_token(i) if fed else req._last))

    def _launch(self) -> Optional["_Round"]:
        """Grow, pack and dispatch one ragged round: decode lanes (one
        token each) plus up to `prefill_chunk_tokens` pending-prompt
        tokens, in ONE fixed-shape `engine.sampled_step` whose program
        also screens and samples. Nothing here waits for the device.

        With a round still in flight (`_launched`) a lane is planned from
        what is known of it without its token: its chunk's end, its
        cache length and draw index with the token in flight counted, and
        whether that token is its last by `max_new_tokens` (then it has no
        lane here). The token itself is fed on the device
        (`ops/sampling.fed_token`). Returns the round, None when there was
        nothing to launch; a round whose dispatch raised comes back with
        `error` set and its growth in place, for `_decode` to attribute."""
        active = [(i, r) for i, r in enumerate(self.slots) if r is not None]
        if not active:
            return None
        mgr = self.engine.manager
        with RecordEvent("sched.grow"):
            # grow (and possibly preempt) before building the batch arrays
            plan = []
            try:
                self._grow_lanes(active, plan)
            except _SettleFirst:
                # grown, never dispatched: the bookkeeping alone goes back
                self._rollback([ln for ln, _first in plan], dispatched=False)
                self.settle()
                plan.clear()
                self._grow_lanes(active, plan)
            # growth-path preemptions may have evicted earlier entries
            plan = [(ln, first) for ln, first in plan
                    if self.slots[ln.slot] is ln.req]
        if not plan:
            return None
        with RecordEvent("sched.pack"):
            B = len(self.slots)
            T = self.ragged_tokens
            tokens = np.zeros((T,), np.int32)
            q_lens = np.zeros((B,), np.int32)
            kv_lens = np.zeros((B,), np.int32)
            tables = self._pad_tables(mgr, B)
            rows = np.zeros((B,), np.int32)   # last packed row per lane
            # the step samples every lane's LAST packed row itself (fixed
            # [B] shape): decode lanes commit their token; a prefill lane
            # samples only on the round its final chunk completes (counter
            # draw_idx 0 — exactly the draw sequential decode would make).
            # Which lanes those are is known before the dispatch.
            lane_sample: List[Optional[Request]] = [None] * B
            fed_lanes = []
            cursor = 0
            for lane, first in plan:          # slot order = packing order
                i, req, n = lane.slot, lane.req, lane.n
                if lane.chunk:
                    tokens[cursor:cursor + n] = req._prefill_ctx[
                        first:first + n]
                else:
                    tokens[cursor] = first
                    if first < 0:
                        fed_lanes.append(i)
                q_lens[i] = n
                kv_lens[i] = mgr.seq_len(req.seq_id)   # == pre_len + n
                rows[i] = cursor + n - 1
                cursor += n
                tables[i] = mgr.block_table_array([req.seq_id])[0]
                if lane.samples:
                    lane_sample[i] = req
            temps, topks, seeds, draws = self._sampling_arrays(lane_sample)
            draws[fed_lanes] += 1          # the token in flight is drawn
            lanes = pack_lanes(q_lens, kv_lens, rows, topks, seeds, draws)
        rnd = _Round({ln.slot: ln for ln, _first in plan})

        def probe(i, req):
            """Replay ONE lane of the failed step (same fixed shapes, so
            no recompile; KV writes are position-indexed and idempotent
            with the retry). The round before it is settled by now, so a
            token that was fed on the device is the request's pending one."""
            n = int(q_lens[i])
            start = int(rows[i]) - n + 1
            t = np.zeros((T,), np.int32)
            t[:n] = tokens[start:start + n]
            if t[0] < 0:
                t[0] = req._last
            q = np.zeros((B,), np.int32)
            q[i] = n
            kv = np.zeros((B,), np.int32)
            kv[i] = kv_lens[i]
            tb = self._pad_tables(mgr, B)
            tb[i] = tables[i]
            # the lane's WHOLE packed band: a NaN confined to an earlier
            # chunk row must still convict this lane (the caller's
            # finiteness check reduces over everything returned)
            return np.asarray(self.engine.ragged_step(t, q, kv, tb))[:n]

        self._install_lane_adapters()
        overlapped = self._launched is not None
        rnd.began = self._clock()
        try:
            with RecordEvent("sched.dispatch", phase="decode",
                             prefill_tokens=sum(ln.n for ln, _first in plan
                                                if ln.chunk),
                             decode_lanes=sum(not ln.chunk
                                              for ln, _first in plan)):
                rnd.sampled, rnd.flagged = self._dispatch(
                    "decode", self.engine.sampled_step, tokens, lanes,
                    tables, temps)
        except Exception as e:
            rnd.error, rnd.probe = e, probe
            return rnd
        self.metrics.on_round_launched(overlapped)
        return rnd

    def _settle(self, rnd: "_Round") -> None:
        """The other half of a plain round: block on its `[2, B]` tokens
        and finiteness flags (the round's ONE fetch), screen, commit. A
        lane whose request left its slot since the launch (finished on the
        token before by EOS, cancelled, preempted, convicted) is
        discarded: its token is never read, its KV write landed in blocks
        that were its own and went back with the sequence."""
        t_fetch = self._clock()
        try:
            _faults.check("serve.sample")
            with RecordEvent("sched.sample"):
                # every lane's token and its band's finiteness flag,
                # screened and sampled by the step's own program; the
                # logits stay on the device
                picked, finite = np.asarray(rnd.sampled)
                self.metrics.on_step_fetch()
        except Exception as e:
            # the round launched behind this one read tokens that no one
            # will ever see: both go, back to the lengths before this one
            behind, self._launched = self._launched, None
            lanes = list(rnd.lanes.values())
            if behind is not None:
                lanes = list(behind.lanes.values()) + lanes
            holders = {ln.slot: ln.req for ln in reversed(lanes)}
            self._step_fault("sample", e, list(holders.items()),
                             rollback=lambda _s: self._rollback(lanes))
            return
        t_tok = self._clock()
        # the round's wall, what TPOT is priced at: from its launch, or
        # from the settle before it where it queued behind that round on
        # the device, to here. The watchdog's budget is held against what
        # the scheduler WAITED: the fetch here, like the dispatch before
        # it (`_dispatch`), never the caller's time between two steps (a
        # slow consumer of `stream()` is no stalled engine)
        self._last_decode_dt = t_tok - max(rnd.began, self._settled_at)
        self._settled_at = t_tok
        if self._wd is not None \
                and t_tok - t_fetch > self._wd.stall_timeout_s:
            self.metrics.on_stall()
            self._pending_stall = "step_timeout:sample"
        live = [ln for ln in rnd.lanes.values() if ln.holds(self)]
        if len(live) < len(rnd.lanes):
            self.metrics.on_wasted_lanes(len(rnd.lanes) - len(live))
        if live:
            finite = finite.astype(bool)
            if rnd.flagged:          # injection path: poison one lane
                finite[live[0].slot] = False
            for ln in live:
                if not finite[ln.slot]:
                    # the garbage KV went into this lane's own blocks;
                    # freeing the sequence discards it (its token is
                    # never read, nor what a lane behind it made of it)
                    self._isolated(ln.req, "nan_logits", "decode",
                                   slot=ln.slot)
            live = [ln for ln in live if ln.holds(self)]
        if not live:
            return
        self._step_faults = 0   # a full dispatch+sample round succeeded
        with RecordEvent("sched.commit"):
            decode_lanes = [ln for ln in live if not ln.chunk]
            produced = chunk_tokens = 0
            for ln in decode_lanes:
                if not ln.holds(self):         # cancelled by a stream_cb
                    continue                   # earlier in this very loop
                produced += 1
                self._commit_token(ln.req, int(picked[ln.slot]), ln.slot,
                                   t_tok, obs_decode=True)
            for ln in live:
                if not ln.chunk or not ln.holds(self):   # or mid-commit
                    continue
                chunk_tokens += ln.n
                self._commit_chunk(ln.req, ln.n, ln.slot, t_tok,
                                   picked[ln.slot])
        self._chunk_progress += chunk_tokens
        self._produced += produced
        self.metrics.on_ragged_step(chunk_tokens, len(decode_lanes))
        if decode_lanes:
            self._record_tpot(len(decode_lanes), produced)
            self.metrics.on_decode(produced)

    # ---- speculative decoding ----
    def _grow_n(self, req: Request, slot: int, want: int) -> int:
        """Reserve cache slots for the pending token plus `want - 1` draft
        tokens. Degrades before it preempts: on pressure the drafts are
        dropped first (want -> 1, plain decode growth), THEN the normal
        preempt/finish policy applies. Returns slots reserved (0 if the
        request left the batch). Raises `_SettleFirst` where it would
        preempt or finish while a round is in flight."""
        mgr = self.engine.manager
        ahead = self._ahead(slot, req)
        held = ahead.n if ahead is not None else 0
        while True:
            try:
                mgr.append_tokens(req.seq_id, want, in_flight=held)
                return want
            except SequenceTooLong:
                cap = mgr.max_blocks_per_seq * mgr.block_size \
                    - mgr.seq_len(req.seq_id)
                if cap >= 1:
                    want = min(want, cap)
                    continue
                if self._launched is not None:
                    raise _SettleFirst from None
                self._finish(req, RequestStatus.FINISHED, "length_cap",
                             slot=slot)
                return 0
            except KVCacheExhausted as e:
                if want > 1:
                    want = 1
                    continue
                if self._launched is not None:
                    raise _SettleFirst from None
                if _obs.enabled():
                    # real pressure (a single-token grow failed): snapshot
                    # the memory picture BEFORE the preempt/finish below
                    # mutates the pool it should explain
                    self._obs_oom("kv_exhausted", need=e.need, free=e.free,
                                  total=e.total, seq_id=req.seq_id)
                if not self._preempt_one(exclude=req):
                    self._finish(req, RequestStatus.FINISHED, "kv_capacity",
                                 slot=slot)
                    return 0

    def _decode_spec(self, now: float) -> int:
        """One speculative round: propose -> ONE fixed-shape verify over
        all lanes -> fused sampling -> accept longest matching draft
        prefix + bonus token -> `trim` rollback of rejected slots.

        Shape discipline: the verify batch is always [B, K+1] tokens.
        Lanes with fewer than K drafts reserve only what they hold; the
        surplus fixed-shape KV writes land in guard-padded block-table
        entries, never in live blocks.

        Chunked prefill rides the SAME dispatch: a prefilling lane's
        window carries its next (up to K+1) prompt tokens instead of
        pending+drafts — the verify pass is itself a ragged-step special
        case, so a prompt chunk is just a lane whose "drafts" are known
        tokens nobody samples. A prompt is never completed mid-window:
        the final chunk is held to exactly one token so the first-token
        sample lands at window slot 0, whose counter-RNG draw offset (0)
        matches what the plain path and sequential decode draw — exact
        spec==plain parity under chunking, greedy and stochastic alike."""
        active = [(i, r) for i, r in enumerate(self.slots) if r is not None]
        if not active:
            return 0
        mgr = self.engine.manager
        K = self.spec.num_draft_tokens
        S = K + 1
        proposer = self.spec.proposer
        with RecordEvent("sched.grow"):
            lanes = []             # (slot, req, toks, pre_len, prefilling)
            for i, req in active:
                if self.slots[i] is not req:
                    continue
                pre_len = mgr.seq_len(req.seq_id)
                if req.prefilling:
                    rem = len(req._prefill_ctx) - req._prefill_pos
                    want = min(S, rem)
                    if want == rem and rem > 1:
                        want = rem - 1      # complete next round, at slot 0
                    try:
                        got = self._grow_chunk(req, i, want)
                    except Exception:       # injected/corrupt cache state
                        self._isolated(req, "engine_fault:cache", "cache",
                                       slot=i)
                        continue
                    if got == 0:
                        continue
                    toks = list(req._prefill_ctx[
                        req._prefill_pos:req._prefill_pos + got])
                    lanes.append((i, req, toks, pre_len, True))
                    continue
                try:
                    drafts = list(proposer.propose(
                        req.seq_id, req.all_tokens(), K))[:K]
                except Exception:
                    drafts = []          # proposers must never kill the step
                try:
                    got = self._grow_n(req, i, 1 + len(drafts))
                except Exception:        # injected/corrupt cache state
                    self._isolated(req, "engine_fault:cache", "cache", slot=i)
                    continue
                if got == 0:
                    continue
                lanes.append((i, req, [req._last] + drafts[:got - 1], pre_len,
                              False))
            lanes = [ln for ln in lanes if self.slots[ln[0]] is ln[1]]
        if not lanes:
            return 0
        with RecordEvent("sched.pack"):
            B = len(self.slots)
            tokens = np.zeros((B, S), np.int32)
            ctx = np.full((B,), S, np.int32)      # pad lanes write guard block
            # a lane within S tokens of its hard length cap has a table FULL
            # of real blocks while ctx still counts the fixed S-token window,
            # so the engines' block gather for positions past the cap indexes
            # past the table width. Without the trailing guard columns the
            # write survives only by accident (jnp OOB-gather fill int32-min,
            # times a power-of-two block size, wraps to physical block 0 —
            # which is the guard only because it's the first block ever
            # leased); make the invariant explicit instead (width is a
            # function of the fixed S: still one compiled program).
            width = mgr.max_blocks_per_seq + (S + mgr.block_size - 2) \
                // mgr.block_size
            tables = np.full((B, width), self._pad_block, np.int32)
            lane_reqs: List[Optional[Request]] = [None] * B
            pre_lens = {}
            for i, req, toks, pre_len, prefilling in lanes:
                tokens[i, :len(toks)] = toks
                # uniform layout: token j sits at position pre_len + j, so
                # ctx counts the full fixed window even when the lane holds
                # fewer than S real tokens (short drafts / a short chunk)
                ctx[i] = pre_len + S
                tables[i, :mgr.max_blocks_per_seq] = mgr.block_table_array(
                    [req.seq_id], pad=self._pad_block)[0]
                # sampled rows matter for decode lanes always, and for a
                # prefill lane only on its completing (one-token) chunk
                if not prefilling:
                    lane_reqs[i] = req
                elif req._prefill_pos + len(toks) >= len(req._prefill_ctx) \
                        and req._last is None:
                    lane_reqs[i] = req
                pre_lens[req.seq_id] = pre_len
        def probe(i, req):
            t = np.zeros((B, S), np.int32)
            t[i] = tokens[i]
            c = np.full((B,), S, np.int32)
            c[i] = ctx[i]
            tb = np.full((B, width), self._pad_block, np.int32)
            tb[i] = tables[i]
            return np.asarray(self.engine.verify_step(t, c, tb))[i]

        def rollback(survivors):
            for i, r in survivors:
                mgr.trim(r.seq_id, pre_lens[r.seq_id])

        lane_pairs = [(i, r) for i, r, _t, _p, _f in lanes]
        self._install_lane_adapters()
        try:
            with RecordEvent("sched.dispatch", phase="verify",
                             prefill_tokens=sum(len(ln[2]) for ln in lanes
                                                if ln[4]),
                             decode_lanes=sum(not ln[4] for ln in lanes)):
                logits, flagged = self._dispatch(
                    "verify", self.engine.verify_step, tokens, ctx, tables)
        except Exception as e:
            self._step_fault("verify", e, lane_pairs, probe=probe,
                             rollback=rollback)
            return 0
        if flagged:                  # injection path: poison one lane
            arr = np.array(logits)
            arr[lanes[0][0]] = np.nan
            logits = arr
            finite = np.isfinite(arr).all(axis=(-2, -1))
        else:                        # hot path: [B, S] bool fetch only
            finite = self._finite_rows(logits).all(axis=-1)
        for i, req in lane_pairs:
            if not finite[i]:
                self._isolated(req, "nan_logits", "verify", slot=i)
                lane_reqs[i] = None
        lanes = [ln for ln in lanes if self.slots[ln[0]] is ln[1]]
        if not lanes:
            return 0
        t_tok = self._clock()
        try:
            _faults.check("serve.sample")
            with RecordEvent("sched.sample"):
                picked = sample_tokens(logits,
                                       *self._sampling_arrays(lane_reqs))
            self.metrics.on_step_program()
            self.metrics.on_step_fetch()
        except Exception as e:
            self._step_fault("sample", e,
                             [(i, r) for i, r, _t, _p, _f in lanes],
                             rollback=rollback)
            return 0
        self._step_faults = 0   # a full verify+sample round succeeded
        with RecordEvent("sched.commit"):
            produced = proposed = accepted = 0
            chunk_tokens = decode_lanes = 0
            obs_on = _obs.enabled()
            for i, req, toks, pre_len, prefilling in lanes:
                if self.slots[i] is not req:   # cancelled by a stream_cb
                    continue                   # earlier in this very loop
                if prefilling:
                    got = len(toks)
                    chunk_tokens += got
                    # a completing chunk has got == 1 -> window slot 0, the
                    # draw offset sequential decode would use
                    self._commit_chunk(req, got, i, t_tok, picked[i, got - 1])
                    continue
                decode_lanes += 1
                drafts = toks[1:]
                a = 0
                while a < len(drafts) and drafts[a] == int(picked[i, a]):
                    a += 1
                proposed += len(drafts)
                accepted += a
                committed = 0
                # emit the accepted drafts (== the sampled tokens) plus the
                # bonus/correction token from the first unmatched position
                for tok in (int(picked[i, j]) for j in range(a + 1)):
                    produced += 1
                    committed += 1
                    self._commit_token(req, tok, i, t_tok)
                    if req.status.terminal:
                        break
                if obs_on:
                    self._obs_req(req, "verify_round", t0=t_tok,
                                  tokens=committed, drafts=len(drafts),
                                  accepted=a)
                if not req.status.terminal:
                    # roll back rejected speculation: keep pending + accepted
                    mgr.trim(req.seq_id, pre_len + 1 + a)
        self._chunk_progress = chunk_tokens
        self.metrics.on_ragged_step(chunk_tokens, decode_lanes)
        if decode_lanes:
            self._record_tpot(decode_lanes, produced)
            self.metrics.on_decode(produced)
            self.metrics.on_spec(proposed=proposed, accepted=accepted,
                                 produced=produced, lanes=decode_lanes)
        return produced

    def _commit_chunk(self, req: Request, n: int, slot: int, t_tok: float,
                      first_tok) -> None:
        """Advance a lane's chunked prefill by `n` committed tokens. On
        the round the FINAL chunk completes: account the prefill, emit
        the request-track event, and commit the request's first token
        (`first_tok` — ignored while chunks remain, and on a preempted
        re-admission whose pending token already exists). The one
        prefill-completion bookkeeping site for the plain and spec
        paths, so their parity cannot drift."""
        req._prefill_pos += n
        req._chunks += 1
        self.metrics.on_prefill_chunk(n)
        if req.prefilling:
            return                         # more chunks next round
        self.metrics.on_prefill_done()
        if _obs.enabled():
            self._obs_req(req, "prefill", t0=req._t_admit, t1=t_tok,
                          tokens=int(len(req._prefill_ctx)),
                          chunks=req._chunks)
        if req._last is None:              # fresh: the FIRST token
            self._commit_token(req, int(first_tok), slot, t_tok)

    def _commit_token(self, req: Request, tok: int, slot: int,
                      t_tok: float, obs_decode: bool = False):
        """Commit one sampled token: the ONE place the generated stream,
        pending token, TTFT stamp, stream callback, and finish check
        advance together — the decode lanes, both prefill-completion
        sites, and the speculative accept loop share it so first-token
        accounting can never diverge between the plain and spec paths."""
        req.generated.append(tok)
        req._last = tok
        self.tokens_committed += 1
        if req.t_first_token is None:
            req.t_first_token = t_tok
            with RecordEvent("sched.first_token", req=req.req_id):
                self.metrics.on_first_token(req)
        if req.stream_cb is not None:
            req.stream_cb(req, tok)
        if obs_decode and _obs.enabled():
            self._obs_req(req, "decode", t0=t_tok, tokens=1,
                          total=len(req.generated))
        self._maybe_finish_on_token(req, tok, slot)

    def _maybe_finish_on_token(self, req: Request, tok: int, slot: int):
        if req.status.terminal:
            # a stream callback may cancel mid-commit (reentrancy): the
            # slot and blocks are already released — don't finish twice
            return
        sp = req.sampling
        if sp.eos_token_id is not None and tok == sp.eos_token_id:
            self._finish(req, RequestStatus.FINISHED, "eos", slot=slot)
        elif len(req.generated) >= sp.max_new_tokens:
            self._finish(req, RequestStatus.FINISHED, "max_new_tokens",
                         slot=slot)

    def _finish(self, req: Request, status: RequestStatus, reason: str,
                slot: Optional[int] = None, in_slot: bool = True):
        with RecordEvent("sched.finish", req=req.req_id,
                         status=status.value):
            if in_slot:
                if slot is None:
                    slot = self.slots.index(req)
                self.slots[slot] = None
                if status is not RequestStatus.FAILED:
                    # a FAILED lane's KV may be poison (NaN isolation,
                    # engine fault) — never publish it into the shared tree
                    self._publish_prefix(req)
                self.engine.manager.free(req.seq_id)
            else:
                # a WAITING request may hold imported KV (`import_session`)
                # that no slot path will ever free
                self._drop_resident_kv(req)
            self._release_spec(req)
            self._adapter_release(req)
            req.status = status
            req.finish_reason = reason
            req.t_finish = self._clock()
            self._finish_events += 1
            self.metrics.on_finish(req)
            if _obs.enabled():
                self._obs_req(req, f"terminal:{status.value}",
                              t0=req.t_finish, reason=reason,
                              tokens=len(req.generated))
                if status is RequestStatus.FAILED:
                    _obs.timeline.dump_flight(f"request_failed_{reason}")

    def _release_spec(self, req: Request):
        """Drop any speculative-proposer state for a request leaving the
        batch (finish, cancel, preempt). Idempotent; never raises into
        the serving path."""
        if self.spec is None:
            return
        try:
            self.spec.proposer.release(req.seq_id)
        except Exception:
            pass

    def _adapter_release(self, req: Request):
        """Drop a request's adapter-pool lease on any exit from the
        batch or queue (finish, cancel, preempt, drain, failed
        admission). Idempotent — `_adapter_slot` is the lease token, and
        clearing it first makes a re-entrant release a no-op; never
        raises into the serving path."""
        if req._adapter_slot is None or self._lora is None:
            return
        req._adapter_slot = None
        try:
            self._lora.release(req.adapter)
        except Exception:
            _monitor.inc("serving.lora.release_errors")

    def _install_lane_adapters(self):
        """Push the per-lane adapter-slot vector for this round's
        dispatch: occupied lanes carry their request's leased slot,
        empty/base lanes the reserved zero slot. Pure data on a fixed
        [B] shape — adapter churn between rounds can never retrace."""
        if self._set_lanes is None:
            return
        lanes = np.full((len(self.slots),), self._lora_zero, np.int32)
        for i, r in enumerate(self.slots):
            if r is not None and r._adapter_slot is not None:
                lanes[i] = r._adapter_slot
        self._set_lanes(lanes)
