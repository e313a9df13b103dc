"""Serving observability: request/token counters, TTFT/TPOT latency,
queue depth, batch occupancy, KV utilization, preemptions.

Everything is double-published:
- counters/gauges go to `framework.monitor` under the `serving.` prefix,
  the same scrape surface the reference exposes via
  `fluid/platform/monitor.h` stat registries — `profiler.summary()`
  renders them as a serving section;
- per-request latency samples stay in-process on `ServingMetrics` so
  `summary()` can report p50/p99 TTFT and mean TPOT (percentiles can't
  be rebuilt from monotonic counters).

Retrace counters (`serving.decode_retraces` / `serving.ragged_retraces` /
`serving.verify_retraces` / `serving.logits_retraces`)
are bumped by the ENGINES' programs at jit-trace time (see
serving/engine.py); this module only reads them. In steady state they must
stay flat. `serving.step.all_rows_calls` counts, on the host, the calls of
an engine's all-rows program (`ops/sampling.ragged_step`: `generate`,
proposers, a fault probe): with `serving.logits_retraces` it says whether
that second program engaged, and a run that serves plain rounds and never
probes reads 0 for both.
"""
from __future__ import annotations

from collections import deque
from typing import Dict, Optional

import numpy as np

from ..framework import monitor

__all__ = ["ServingMetrics"]

# Latency percentiles come from a bounded sliding window: a long-running
# server must not grow sample lists (or pay O(all-requests) percentile
# passes) forever.
_WINDOW = 4096
_PUBLISH_EVERY = 16


def _pct(samples, q: float) -> Optional[float]:
    if not samples:
        return None
    return float(np.percentile(np.asarray(samples, np.float64), q))


class ServingMetrics:
    """Collector owned by one Scheduler (monitor names are global: reset
    with `reset_monitor()` when running several engines in-process)."""

    def __init__(self):
        self.ttft_s = deque(maxlen=_WINDOW)
        self.tpot_s = deque(maxlen=_WINDOW)
        # cached-vs-cold TTFT split (shared-prefix radix caching): a
        # request admitted WITH a prefix-cache hit lands in `cached`,
        # everything else in `cold` — the side-by-side distribution the
        # prefix cache's whole existence is judged on
        self.ttft_cached_s = deque(maxlen=_WINDOW)
        self.ttft_cold_s = deque(maxlen=_WINDOW)
        # per-step acceptance-rate samples (speculative decoding) — same
        # bounded-window contract as the latency deques: a long-running
        # server must never grow a sample list
        self.accept_rate = deque(maxlen=_WINDOW)
        self._occ_sum = 0.0
        self._steps = 0
        self._finishes = 0
        self._spec_steps = 0
        self._spec_produced = 0
        self._plain_rounds = 0
        self._overlapped = 0

    def reset_window(self):
        """Drop latency samples and the occupancy accumulator (e.g. at a
        warmup/measurement boundary) without touching monitor counters."""
        self.ttft_s.clear()
        self.tpot_s.clear()
        self.ttft_cached_s.clear()
        self.ttft_cold_s.clear()
        self.accept_rate.clear()
        self._occ_sum = 0.0
        self._steps = 0
        self._finishes = 0
        self._spec_steps = 0
        self._spec_produced = 0
        self._plain_rounds = 0
        self._overlapped = 0

    # ---- request lifecycle ----
    def on_submit(self):
        monitor.inc("serving.requests_submitted")

    def on_reject(self, reason: str):
        monitor.inc("serving.requests_rejected")
        monitor.inc(f"serving.rejected.{reason}")

    def on_shed(self, reason: str):
        """Overload shed at admission (status SHED): the request was
        structurally servable but the watermark/deadline admission
        control turned it away in microseconds instead of letting it
        collapse every admitted request's latency."""
        monitor.inc("serving.shed_total")
        monitor.inc(f"serving.shed.{reason}")

    @staticmethod
    def shed_by_reason() -> dict:
        """Non-zero shed counts keyed by reason — the one owner of the
        `serving.shed.<reason>` counter namespace (profiler summary and
        bench extras both render this)."""
        return {k[len("serving.shed."):]: v
                for k, v in monitor.snapshot("serving.shed.").items() if v}

    def on_preempt(self):
        monitor.inc("serving.preemptions")

    # ---- fault tolerance ----
    def on_isolated_fault(self, phase: str):
        """One request failed by the fault-isolation boundary (NaN lane,
        targeted `EngineStepError`, cache fault, failed probe replay) —
        the surviving lanes kept serving."""
        monitor.inc("serving.isolated_faults")
        monitor.inc(f"serving.isolated_faults.{phase}")

    def on_step_fault(self, phase: str):
        """One UNattributed (transient) dispatch fault: nothing
        committed, no lane culpable; the whole step replays next round."""
        monitor.inc("serving.step_faults")
        monitor.inc(f"serving.step_faults.{phase}")

    def on_stall(self):
        monitor.inc("serving.stall_detections")

    def on_engine_restart(self, reason: str):
        monitor.inc("serving.engine_restarts")
        # reasons carry a phase suffix (step_faults:decode) — keep the
        # leading class so the counter space stays bounded
        monitor.inc(f"serving.engine_restarts.{reason.split(':', 1)[0]}")

    def on_step_program(self):
        """The scheduler launched one device program (an engine step; on
        the speculative round also its NaN screen and its sampler). With
        `on_step_fetch`: a plain round costs exactly one of each."""
        monitor.inc("serving.step.programs")

    def on_step_fetch(self):
        """The scheduler blocked on one device-to-host fetch."""
        monitor.inc("serving.step.fetches")

    def on_round_launched(self, overlapped: bool):
        """A plain round was launched, `overlapped` while the one before
        it was still unfetched (its host work then runs under the
        device's): the share of such rounds is the gauge."""
        self._plain_rounds += 1
        if overlapped:
            self._overlapped += 1
            monitor.inc("serving.step.overlapped")
        monitor.set_gauge("serving.step.overlap_share",
                          round(self._overlapped / self._plain_rounds, 4))

    def on_forced_settle(self):
        """A round in flight was settled BEFORE the next launch because
        the engine's `last_sampled` was not that round's `sampled` (someone
        else stepped the engine, or a wrapper does not forward the
        attribute): that launch fed no token on the device."""
        monitor.inc("serving.step.forced_settles")

    def on_state_slots(self, in_use: int):
        """Slots of a state group that sequences hold (the guard's is not
        one of them), after a scheduling round."""
        monitor.set_gauge("serving.state.slots_in_use", in_use)

    def on_state_restart(self):
        """A lane of a state group was restarted from its tokens (freed and
        re-queued at the front) where a block group's would have been
        trimmed: a recurrent state cannot forget what a failed round fed
        it."""
        monitor.inc("serving.state.restarts")

    def on_wasted_lanes(self, n: int):
        """`n` lanes of a settled round belonged to requests that had left
        their slots since its launch (finished by EOS on the token
        before, cancelled, preempted, convicted): computed, never read."""
        monitor.inc("serving.step.wasted_lanes", n)

    def on_prefill_chunk(self, num_tokens: int):
        """`num_tokens` of pending-prompt context entered the cache via
        one ragged-step chunk (chunked prefill)."""
        monitor.inc("serving.prefill_tokens", num_tokens)

    def on_prefill_done(self):
        """A request's full context finished prefilling (its final chunk
        committed). `serving.prefills` therefore counts completed
        prefills — one per (re-)admission, as it always did — while
        `prefill_tokens` advances chunk by chunk."""
        monitor.inc("serving.prefills")

    def on_ragged_step(self, prefill_tokens: int, decode_lanes: int):
        """Per-step ragged batch composition: how many pending-prompt
        tokens and decode lanes shared this round's ONE fixed-shape
        dispatch — the live view of chunked prefill interleaving."""
        monitor.set_gauge("serving.step_prefill_tokens", prefill_tokens)
        monitor.set_gauge("serving.step_decode_lanes", decode_lanes)

    def on_first_token(self, req):
        t = req.ttft()
        if t is not None:
            self.ttft_s.append(t)
            # fixed-bucket histogram: the Prometheus-scrapable latency
            # distribution (percentile gauges below stay for summary())
            monitor.observe("serving.ttft_seconds", t)
            if getattr(req, "_prefix_hit_tokens", 0) > 0:
                self.ttft_cached_s.append(t)
                monitor.observe("serving.ttft_cached_seconds", t)
            else:
                self.ttft_cold_s.append(t)
                monitor.observe("serving.ttft_cold_seconds", t)
            if getattr(req, "adapter", None):
                # per-adapter TTFT attribution: an adapter whose
                # requests keep missing the pool (priced admission)
                # shows up as a fat histogram right here
                monitor.observe(f"serving.lora.ttft_seconds.{req.adapter}",
                                t)

    # ---- shared-prefix radix cache ----
    def on_prefix_lease(self, hit_tokens: int):
        """One admission through the radix prefix cache: `hit_tokens`
        context tokens were served from cache (0 = miss). The raw
        `serving.prefix_cache.{hits,misses,hit_tokens,evictions}`
        counters are bumped at their source (`prefix_cache.py`;
        `cow_copies` in `cache.py`) — this hook derives the rate
        gauge."""
        hits = monitor.get("serving.prefix_cache.hits")
        miss = monitor.get("serving.prefix_cache.misses")
        if hits + miss:
            monitor.set_gauge("serving.prefix_cache.hit_rate_pct",
                              round(hits / (hits + miss) * 100.0, 1))

    # ---- disaggregated prefill/decode (ISSUE 17) ----
    def on_handoff(self, nbytes: int, wall_s: float):
        """One prefill→decode session handoff landed: `nbytes` of KV
        payload migrated (slabs + scale planes), `wall_s` extract→inject
        wall time. Counters size the interconnect a real deployment
        needs; the histogram is the handoff-latency SLO surface
        (docs/SERVING.md "Disaggregated prefill/decode")."""
        monitor.inc("serving.handoff.count")
        monitor.inc("serving.handoff.bytes", int(nbytes))
        monitor.inc("serving.handoff.wall_ms", wall_s * 1e3)
        monitor.observe("serving.handoff.latency_seconds", wall_s)

    # ---- quantized serving ----
    def on_quant(self, info: dict):
        """Publish the engine's quantization mode (serving/quant.py
        `quant_summary`): weight bits, KV bits, and the per-token KV
        byte cost — the gauges the capacity math audits against
        (`serving.quant.{wbits,kv_bits}`, `serving.kv_bytes_per_token`).
        Called once at scheduler bind (and again after an engine swap),
        never on the step path."""
        monitor.set_gauge("serving.quant.wbits", int(info.get("wbits", 16)))
        monitor.set_gauge("serving.quant.kv_bits",
                          int(info.get("kv_bits", 16)))
        bpt = info.get("kv_bytes_per_token")
        if bpt is not None:
            monitor.set_gauge("serving.kv_bytes_per_token",
                              round(float(bpt), 1))
        # an engine with several block groups: what a token costs in each,
        # under the group's name (a windowed group's bytes are a token's
        # only while it lies inside the window; the plain gauge above is
        # the first group's, what a token costs for as long as it lives)
        for group, b in (info.get("kv_bytes_per_token_by_group")
                         or {}).items():
            monitor.set_gauge(f"serving.kv_bytes_per_token.{group}",
                              round(float(b), 1))
        # an engine over a state group: what one resident sequence holds,
        # whatever its length (its `kv_bytes_per_token` is 0)
        if info.get("state_bytes_per_seq") is not None:
            monitor.set_gauge("serving.state.bytes_per_seq",
                              int(info["state_bytes_per_seq"]))

    # ---- multi-LoRA serving ----
    def on_lora(self, info: dict):
        """Publish the adapter pool's shape (serving/lora.py
        `lora_info`): slot count, residency, registry size, padded rank
        — `serving.lora.{pool_slots,resident_adapters,
        registered_adapters,rank_max}`. Bind-time like `on_quant`; the
        churn counters (`serving.lora.{miss_loads,evictions,
        switch_retraces}`) are bumped at their source in the pool and
        the wrapper traces."""
        monitor.set_gauge("serving.lora.pool_slots",
                          int(info.get("pool_slots", 0)))
        monitor.set_gauge("serving.lora.resident_adapters",
                          int(info.get("resident_adapters", 0)))
        monitor.set_gauge("serving.lora.registered_adapters",
                          int(info.get("registered", 0)))
        monitor.set_gauge("serving.lora.rank_max",
                          int(info.get("rank_max", 0)))

    # ---- multi-tenant SLO classes ----
    def on_tenant_admit(self, tenant: str):
        monitor.inc(f"serving.tenant.{tenant}.admitted")

    def on_tenant_deferred(self, tenant: str, reason: str):
        """A tenant's head-of-queue request was passed over this
        admission round (kv_quota / kv_reserve) WITHOUT blocking other
        tenants — quota pressure made visible."""
        monitor.inc(f"serving.tenant.{tenant}.deferred.{reason}")

    def on_finish(self, req):
        from .scheduler import RequestStatus

        name = {RequestStatus.FINISHED: "serving.requests_completed",
                RequestStatus.CANCELLED: "serving.requests_cancelled",
                RequestStatus.TIMED_OUT: "serving.requests_timed_out",
                RequestStatus.FAILED: "serving.requests_failed"}.get(
                    req.status)
        if name:
            monitor.inc(name)
        t = req.tpot()
        if t is not None:
            self.tpot_s.append(t)
            monitor.observe("serving.tpot_seconds", t)
        self._finishes += 1
        # percentile passes are O(window): publish on the first finish
        # (so gauges exist) then every few — summary() always recomputes
        if self._finishes == 1 or self._finishes % _PUBLISH_EVERY == 0:
            self._publish_latency()

    # ---- step-level gauges ----
    def on_decode(self, tokens: int):
        monitor.inc("serving.decode_steps")
        monitor.inc("serving.tokens_generated", tokens)

    def on_spec(self, proposed: int, accepted: int, produced: int,
                lanes: int):
        """One speculative verify round: `proposed` draft tokens offered,
        `accepted` matched the target, `produced` tokens committed
        (accepted + one bonus/correction per lane) across `lanes` decoded
        lanes. `spec_tokens_per_lane_step` is the speculative speedup
        estimate: a non-speculative decode commits exactly 1 token per
        lane per step."""
        monitor.inc("serving.spec_steps")
        monitor.inc("serving.spec_proposed_tokens", proposed)
        monitor.inc("serving.spec_accepted_tokens", accepted)
        self._spec_steps += max(lanes, 1)
        self._spec_produced += produced
        if proposed:
            self.accept_rate.append(accepted / proposed)
        tot_p = monitor.get("serving.spec_proposed_tokens")
        tot_a = monitor.get("serving.spec_accepted_tokens")
        if tot_p:
            monitor.set_value("serving.spec_acceptance_pct",
                              round(tot_a / tot_p * 100.0, 1))
        monitor.set_value(
            "serving.spec_tokens_per_lane_step",
            round(self._spec_produced / max(self._spec_steps, 1), 2))

    def on_step(self, occupancy: float, kv_utilization: float,
                queue_depth: int, decoded: bool = True,
                wall_s: float = 0.0):
        # every round's wall, warm-up and lane fill included: what a
        # set-up spent stepping (`setup_fill_s`) is this less the window's
        monitor.inc("serving.step.wall_s", wall_s)
        # occupancy averages over DECODE steps only — idle polling rounds
        # (no sequence in flight) say nothing about batching efficiency
        if decoded:
            self._steps += 1
            self._occ_sum += occupancy
            monitor.set_gauge("serving.batch_occupancy_pct",
                              round(occupancy * 100.0, 1))
            monitor.set_gauge("serving.batch_occupancy_avg_pct",
                              round(self._occ_sum / self._steps * 100.0, 1))
        monitor.set_gauge("serving.kv_utilization_pct",
                          round(kv_utilization * 100.0, 1))
        monitor.set_max("serving.kv_utilization_peak_pct",
                        round(kv_utilization * 100.0, 1))
        monitor.set_gauge("serving.queue_depth", queue_depth)
        monitor.set_max("serving.queue_depth_peak", queue_depth)

    def gauge_queue(self, depth: int, queued_cost: Optional[int] = None):
        monitor.set_gauge("serving.queue_depth", depth)
        monitor.set_max("serving.queue_depth_peak", depth)
        if queued_cost is not None:
            # max_new_tokens-weighted backlog: what the cost watermark
            # and the deadline-shed estimate actually latch on
            monitor.set_gauge("serving.queued_cost", queued_cost)
            monitor.set_max("serving.queued_cost_peak", queued_cost)

    def _publish_latency(self):
        for name, val in (("serving.ttft_p50_ms", _pct(self.ttft_s, 50)),
                          ("serving.ttft_p99_ms", _pct(self.ttft_s, 99)),
                          ("serving.prefix_cache.ttft_cached_p50_ms",
                           _pct(self.ttft_cached_s, 50)),
                          ("serving.prefix_cache.ttft_cold_p50_ms",
                           _pct(self.ttft_cold_s, 50)),
                          ("serving.tpot_mean_ms",
                           float(np.mean(self.tpot_s)) if self.tpot_s
                           else None)):
            if val is not None:
                monitor.set_gauge(name, round(val * 1e3, 3))

    # ---- reporting ----
    def summary(self) -> Dict[str, object]:
        # the scalar slice of the registry; the histogram expansion
        # (ttft_seconds_bucket_*) stays out of summary() — callers key on
        # exact metric names
        out = monitor.snapshot("serving.", include_histograms=False)
        # whether the all-rows program engaged, said even where it did not
        for name in ("serving.step.all_rows_calls",
                     "serving.logits_retraces"):
            out.setdefault(name, 0)
        out["serving.ttft_p50_ms"] = _r(_pct(self.ttft_s, 50))
        out["serving.ttft_p99_ms"] = _r(_pct(self.ttft_s, 99))
        out["serving.tpot_mean_ms"] = _r(
            float(np.mean(self.tpot_s)) if self.tpot_s else None)
        if self.ttft_cached_s or self.ttft_cold_s:
            out["serving.prefix_cache.ttft_cached_p50_ms"] = _r(
                _pct(self.ttft_cached_s, 50))
            out["serving.prefix_cache.ttft_cached_p99_ms"] = _r(
                _pct(self.ttft_cached_s, 99))
            out["serving.prefix_cache.ttft_cold_p50_ms"] = _r(
                _pct(self.ttft_cold_s, 50))
            out["serving.prefix_cache.ttft_cold_p99_ms"] = _r(
                _pct(self.ttft_cold_s, 99))
        return out

    @staticmethod
    def reset_monitor():
        """Zero every serving.* monitor counter/histogram (tests,
        engine swap)."""
        monitor.reset_prefix("serving.")


def _r(seconds: Optional[float]) -> Optional[float]:
    return None if seconds is None else round(seconds * 1e3, 3)
