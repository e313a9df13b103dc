"""ServingFrontend — submit/stream/cancel over the continuous-batching
scheduler.

The user-facing surface of the serving subsystem (the role of the
reference's serving C API, `paddle/fluid/inference/capi_exp/pd_inference_api.h`,
minus the C): callers submit token prompts and get back a `RequestHandle`
they can poll, stream, or cancel. Degradation is graceful by construction —
over-capacity submissions come back REJECTED with a reason string,
overload watermarks come back SHED in microseconds, expired deadlines come
back TIMED_OUT, isolated engine faults come back FAILED, and the engine
itself never sees a request the cache cannot hold. Every submitted
request reaches a terminal status (docs/SERVING.md, "Failure semantics").

The frontend is synchronously driven: `step()` advances the world one
scheduling round; `stream()` and `run_until_idle()` drive it for you —
and both raise a typed `EngineStalled` (never spin) when the scheduler
sustains `stall_after` consecutive zero-progress steps on a wedged
engine. Single-threaded by design — TPU serving wants one driver loop
feeding the fixed-shape decode program, not a thread per request.
"""
from __future__ import annotations

import time
from typing import Iterator, List, Optional, Sequence

from ..profiler import RecordEvent
from .fault_tolerance import (AdmissionConfig, EngineStalled,
                              WatchdogConfig)
from .metrics import ServingMetrics
from .scheduler import Request, RequestStatus, SamplingParams, Scheduler
from .slo import DEFAULT_TENANT

__all__ = ["RequestHandle", "ServingFrontend"]


class RequestHandle:
    """Caller's view of one request."""

    def __init__(self, req: Request):
        self._req = req

    @property
    def request_id(self) -> int:
        return self._req.req_id

    @property
    def status(self) -> RequestStatus:
        return self._req.status

    @property
    def finished(self) -> bool:
        return self._req.status.terminal

    @property
    def finish_reason(self) -> Optional[str]:
        return self._req.finish_reason

    @property
    def tokens(self) -> List[int]:
        return list(self._req.generated)

    @property
    def num_preemptions(self) -> int:
        return self._req.num_preemptions

    @property
    def replica_id(self):
        """Replica currently serving this request (`serving/fleet.py`
        placement); None under a standalone frontend."""
        return self._req.replica_id

    @property
    def num_relocations(self) -> int:
        """How many times a replica failure or drain moved this request
        to another replica (committed tokens carried as prompt prefix);
        each move also lands a `relocated` event on the request's
        timeline."""
        return self._req.num_relocations

    def timeline(self) -> list:
        """This request's recorded observability events (oldest first),
        as dicts — empty unless `observability.enable()` was on while it
        was served. The debugging surface behind the chrome-trace
        request tracks: queued -> admitted -> prefill -> decode/verify
        rounds -> (preempted ->) terminal."""
        from .. import observability as _obs

        if not _obs.enabled():
            # zero-cost-off: no ring walk while disabled — and a stale
            # ring from an earlier, since-disabled session must not leak
            # into a "disabled" read
            return []
        return [e.as_dict() for e in _obs.timeline.events()
                if e.req_id == self._req.req_id]

    def ttft_ms(self) -> Optional[float]:
        t = self._req.ttft()
        return None if t is None else t * 1e3

    def tpot_ms(self) -> Optional[float]:
        t = self._req.tpot()
        return None if t is None else t * 1e3

    def __repr__(self):
        return (f"RequestHandle(id={self.request_id}, "
                f"status={self.status.value}, "
                f"tokens={len(self._req.generated)}, "
                f"reason={self.finish_reason})")


class ServingFrontend:
    def __init__(self, engine, metrics: Optional[ServingMetrics] = None,
                 max_queue: int = 256,
                 default_timeout_s: Optional[float] = None,
                 spec=None,
                 admission: Optional[AdmissionConfig] = None,
                 watchdog: Optional[WatchdogConfig] = None,
                 engine_factory=None,
                 stall_after: int = 512,
                 prefill_chunk_tokens: int = 32,
                 prefix_cache: bool = False,
                 slo=None,
                 clock=time.perf_counter):
        """`spec`: optional `SpecDecodeConfig` enabling speculative
        decoding (proposer + fixed draft length K) for every request
        served through this frontend.

        `admission`: optional `AdmissionConfig` enabling overload load
        shedding (watermarks + deadline-aware early rejection).
        `watchdog` + `engine_factory`: optional `WatchdogConfig` enabling
        stall detection and bounded engine restarts (the factory must
        rebuild an identically-configured engine; a factory alone opts
        into the default `WatchdogConfig` — it would otherwise never
        run). `stall_after`: with
        no watchdog, `run_until_idle`/`stream` raise `EngineStalled`
        after this many consecutive zero-progress scheduler steps
        instead of spinning on a wedged engine.
        `prefill_chunk_tokens`: per-step pending-prompt token budget for
        chunked prefill (docs/SERVING.md "Ragged batching & chunked
        prefill" — the TPOT-vs-TTFT knob). `prefix_cache`: enable the
        shared-prefix radix cache — repeated prompts/sessions skip the
        cached part of prefill entirely (docs/SERVING.md "Prefix caching
        & multi-tenant SLOs"). `slo`: optional `SLOConfig` of per-tenant
        quotas, decode-lane weights, and latency-tier watermark scaling;
        submissions then carry `tenant=`. `clock`: time source for
        deadlines, latency stamps, and stall detection — shared with the
        scheduler so fake-clock tests never mix time bases."""
        self.metrics = metrics or ServingMetrics()
        self._clock = clock
        self.scheduler = Scheduler(engine, metrics=self.metrics,
                                   max_queue=max_queue, spec=spec,
                                   admission=admission, watchdog=watchdog,
                                   engine_factory=engine_factory,
                                   prefill_chunk_tokens=prefill_chunk_tokens,
                                   prefix_cache=prefix_cache, slo=slo,
                                   clock=clock)
        self.default_timeout_s = default_timeout_s
        self.stall_after = stall_after

    # ---- request API ----
    def submit(self, prompt_ids: Sequence[int], max_new_tokens: int = 16,
               temperature: float = 0.0, top_k: int = 0,
               eos_token_id: Optional[int] = None,
               timeout_s: Optional[float] = None,
               stream_cb=None, seed: int = 0,
               tenant: Optional[str] = None,
               adapter: Optional[str] = None) -> RequestHandle:
        """Enqueue a generation request. NEVER raises on load conditions:
        a request that cannot be served comes back already-terminal with
        `finish_reason` in {prompt_too_long, queue_full, empty_prompt,
        unknown_adapter, no_adapter_pool} (REJECTED) or a
        watermark/deadline reason (SHED). `tenant` names the request's
        SLO class when an `SLOConfig` is installed (unknown/None -> the
        default class). `adapter` names a registered LoRA adapter on a
        multi-LoRA engine (`serving/lora.py`); when the installed SLO
        config carries a class per adapter (`slo_for_adapters`) and no
        explicit tenant was given, the adapter IS the tenant — quota,
        reserve, and fair-share compose per adapter for free."""
        timeout_s = self.default_timeout_s if timeout_s is None else timeout_s
        now = self._clock()
        deadline = None if timeout_s is None else now + timeout_s
        sp = SamplingParams(max_new_tokens=max_new_tokens,
                            temperature=temperature, top_k=top_k,
                            eos_token_id=eos_token_id, seed=seed)
        cb = None
        if stream_cb is not None:
            cb = lambda req, tok, _cb=stream_cb: _cb(tok)  # noqa: E731
        if adapter is not None and (tenant is None or tenant == DEFAULT_TENANT):
            slo = self.scheduler._slo
            if slo is not None and adapter in slo.classes:
                tenant = adapter
        req = Request(prompt_ids, sampling=sp, deadline=deadline,
                      stream_cb=cb, tenant=tenant, adapter=adapter)
        with RecordEvent("frontend.submit", req=req.req_id):
            self.scheduler.submit(req, now=now)
        return RequestHandle(req)

    def cancel(self, handle: RequestHandle) -> bool:
        return self.scheduler.cancel(handle._req)

    # ---- fleet hooks (serving/fleet.py) ----
    def in_flight(self) -> List[Request]:
        """Non-terminal requests this frontend owns (admission order
        then queue) — what a drain or replica-failure relocation must
        account for."""
        return self.scheduler.in_flight()

    def release(self, handle_or_req) -> bool:
        """Take a non-terminal request OUT of this frontend without a
        terminal status (blocks freed, tokens-so-far kept, status
        PREEMPTED) so a router can re-submit it elsewhere. Accepts a
        `RequestHandle` or a raw `Request`."""
        req = getattr(handle_or_req, "_req", handle_or_req)
        return self.scheduler.release(req)

    def resubmit(self, req: Request) -> Request:
        """Route an existing `Request` object through this frontend's
        admission (the relocation path — `submit()` builds fresh
        requests). The caller must have reset the request to QUEUED with
        its committed tokens folded into the prompt; admission may still
        reject/shed it (terminal status on return, never an
        exception)."""
        return self.scheduler.submit(req, now=self._clock())

    def import_session(self, req: Request, payload) -> Request:
        """Admit an existing `Request` whose context KV arrives as a
        migrated `KVBlockPayload` instead of through prefill — the
        disaggregated handoff / KV-shipping relocation entry
        (`Scheduler.import_session`, ISSUE 17). Load conditions come
        back as a terminal status on the request; migration mismatches
        and pool exhaustion raise TYPED so the router can fall back to
        a committed-prefix re-prefill."""
        return self.scheduler.import_session(req, payload,
                                             now=self._clock())

    # ---- driving ----
    def step(self) -> int:
        """Advance one scheduling round; returns tokens produced."""
        return self.scheduler.step()

    def _check_stalled(self):
        sch = self.scheduler
        if sch.watchdog_active:
            # the watchdog owns stall recovery (restart, then typed
            # failure on budget exhaustion); raising here on a tighter
            # stall_after would preempt the restart the caller configured
            return
        if self.stall_after and not sch.idle \
                and sch.zero_progress_steps >= self.stall_after:
            from .. import observability as _obs

            if _obs.enabled():
                # post-mortem: the rounds that led to the wedge, on disk
                # before the typed raise unwinds the driver loop
                _obs.timeline.dump_flight("engine_stalled")
            mgr = sch.engine.manager
            raise EngineStalled(
                sch.zero_progress_steps,
                f"running={sch.num_running} queued={len(sch.waiting)} "
                f"free_blocks={mgr.free_blocks}/{mgr.num_blocks}")

    def run_until_idle(self, max_steps: int = 100000) -> int:
        """Drive until every submitted request is terminal. Returns steps
        taken. A wedged engine raises `EngineStalled` after
        `stall_after` zero-progress steps (the watchdog, when installed,
        restarts the engine first and only ends up here once its budget
        is gone and every request was failed typed); `max_steps` bounds
        runaway loops (a bug, not a load condition — so it raises)."""
        for n in range(max_steps):
            if self.scheduler.idle:
                return n
            self.step()
            self._check_stalled()
        if not self.scheduler.idle:
            raise RuntimeError(f"not idle after {max_steps} steps")
        return max_steps

    def stream(self, handle: RequestHandle,
               max_steps: int = 100000) -> Iterator[int]:
        """Yield tokens for `handle` as they are produced, driving the
        scheduler. Other in-flight requests advance on the same steps
        (that's the point of continuous batching)."""
        seen = 0
        for _ in range(max_steps):
            toks = handle._req.generated
            while seen < len(toks):
                yield toks[seen]
                seen += 1
            if handle.finished:
                return
            self.step()
            self._check_stalled()
        raise RuntimeError(f"stream not finished after {max_steps} steps")

    def summary(self) -> dict:
        return self.metrics.summary()
