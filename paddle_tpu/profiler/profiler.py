"""Profiler implementation. See package docstring; reference
`python/paddle/profiler/profiler.py:358` (Profiler), `:129`
(make_scheduler), `utils.py:30` (RecordEvent)."""
from __future__ import annotations

import enum
import functools
import json
import os
import threading
import time
from typing import Callable, Iterable, List, Optional

__all__ = ["Profiler", "ProfilerState", "ProfilerTarget", "RecordEvent",
           "SortedKeys", "SummaryView", "make_scheduler",
           "export_chrome_tracing", "export_protobuf",
           "load_profiler_result"]


class ProfilerState(enum.Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(enum.Enum):
    CPU = 0
    GPU = 1
    XPU = 2
    CUSTOM_DEVICE = 3
    TPU = 4


class SortedKeys(enum.Enum):
    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


class SummaryView(enum.Enum):
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """Step-indexed state machine (reference `profiler.py:129`):
    skip_first CLOSED steps, then cycles of closed/ready/record, the last
    record step of each cycle returning RECORD_AND_RETURN."""
    cycle = closed + ready + record
    if record <= 0:
        raise ValueError("record steps must be > 0")

    def fn(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        step -= skip_first
        if repeat > 0 and step >= repeat * cycle:
            return ProfilerState.CLOSED
        pos = step % cycle
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return fn


def _default_state_fn(step: int) -> ProfilerState:
    return ProfilerState.RECORD  # profile everything between start and stop


class _HostEvent:
    __slots__ = ("name", "start", "end", "tid", "kind")

    def __init__(self, name, start, end, tid, kind):
        self.name = name
        self.start = start
        self.end = end
        self.tid = tid
        self.kind = kind  # "op" | "range" | "step"


class _Recorder:
    """In-process host-span collector (the host_tracer role)."""

    def __init__(self):
        self.events: List[_HostEvent] = []
        self._lock = threading.Lock()

    def add(self, name, start, end, kind):
        with self._lock:
            self.events.append(_HostEvent(name, start, end,
                                          threading.get_ident(), kind))


_active_recorder: Optional[_Recorder] = None


@functools.lru_cache(maxsize=1)
def _trace_annotation():
    """`jax.profiler.TraceAnnotation`, imported on first use: importing
    this module must not import JAX."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation


class RecordEvent:
    """User-defined host range (reference `utils.py:30`); context manager or
    explicit begin()/end().

    The one span primitive of the program. It also enters a
    `jax.profiler.TraceAnnotation(name, **ids)`: while a
    `jax.profiler.start_trace` session runs, the span lands on the
    `/host:CPU` plane of the profiler's trace, on the clock of the device
    ops, with `ids` (ints or short constant strings) as its stats; with no
    session the annotation is dropped in C++. The profiler session is the
    only switch; nothing is formatted or buffered here."""

    def __init__(self, name: str, event_type=None, **ids):
        self.name = name
        self._ids = ids
        self._t0 = None
        self._annotation = None

    def begin(self):
        self._annotation = _trace_annotation()(self.name, **self._ids)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()

    def end(self):
        if self._t0 is None:
            return
        if _active_recorder is not None:
            _active_recorder.add(self.name, self._t0, time.perf_counter(),
                                 "range")
        self._t0 = None
        self._annotation.__exit__(None, None, None)
        self._annotation = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    """on_trace_ready callback writing chrome://tracing JSON
    (reference `profiler.py:103`)."""

    def handler(prof: "Profiler"):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"host_{os.getpid()}"
        path = os.path.join(dir_name, f"{name}_time_{int(time.time())}"
                                      ".pb.trace.json")
        prof._export_chrome(path)
        prof.last_export_path = path

    return handler


def export_protobuf(dir_name: str, worker_name: Optional[str] = None):
    """Parity alias: the portable artifact on TPU is the chrome JSON +
    jax.profiler XPlane dir (reference exports .pb)."""
    return export_chrome_tracing(dir_name, worker_name)


def load_profiler_result(filename: str):
    with open(filename) as f:
        return json.load(f)


class Profiler:
    """reference `paddle.profiler.Profiler` (`profiler.py:358`).

    targets are accepted for parity; on this backend host spans are always
    collected and the device timeline comes from `jax.profiler` when any
    accelerator target is requested (TPU/GPU/CUSTOM_DEVICE).
    """

    def __init__(self, *, targets: Optional[Iterable] = None,
                 scheduler=None, on_trace_ready: Optional[Callable] = None,
                 record_shapes: bool = False, profile_memory: bool = False,
                 timer_only: bool = False, emit_nvtx: bool = False,
                 custom_device_types=None, with_flops: bool = False):
        if scheduler is None:
            self._scheduler = _default_state_fn
        elif isinstance(scheduler, tuple):
            start, end = scheduler
            self._scheduler = make_scheduler(
                closed=max(start, 0), ready=0, record=end - start, repeat=1)
        else:
            self._scheduler = scheduler
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        self._profile_memory = bool(profile_memory)
        self._targets = set(targets or [ProfilerTarget.CPU,
                                        ProfilerTarget.TPU])
        self._device_trace = any(t != ProfilerTarget.CPU
                                 for t in self._targets)
        self.current_state = ProfilerState.CLOSED
        self.step_num = 0
        self.recorder: Optional[_Recorder] = None
        self.last_export_path = None
        self._device_trace_dir = None
        self._device_tracing = False
        self._step_t0 = None
        self._step_times: List[float] = []
        self._batch_sizes: List[int] = []
        self._epoch = 0

    # -- tracer control ------------------------------------------------------
    def _enable(self):
        global _active_recorder
        from ..core import dispatch

        if self.recorder is None:
            self.recorder = _Recorder()
        _active_recorder = self.recorder
        rec = self.recorder
        dispatch.set_profile_hook(
            lambda name, t0, t1: rec.add(name, t0, t1, "op"))
        if self._profile_memory:
            from .. import device as dev_api

            # don't steal an externally-enabled sampler on disable
            self._mem_sampling_was_on = dev_api._sampling_installed
            dev_api.enable_peak_sampling()
        if self._device_trace and not self._device_tracing:
            try:
                import jax

                self._device_trace_dir = self._device_trace_dir or \
                    os.path.join("profiler_log", f"jax_{os.getpid()}")
                jax.profiler.start_trace(self._device_trace_dir)
                self._device_tracing = True
            except Exception:
                self._device_tracing = False

    def _disable(self):
        global _active_recorder
        from ..core import dispatch

        dispatch.set_profile_hook(None)
        _active_recorder = None
        if self._profile_memory and not getattr(
                self, "_mem_sampling_was_on", False):
            from .. import device as dev_api

            dev_api.disable_peak_sampling()
        if self._device_tracing:
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception:
                pass
            self._device_tracing = False

    # -- public API ----------------------------------------------------------
    def start(self):
        self.current_state = self._scheduler(self.step_num)
        if self.current_state in (ProfilerState.RECORD,
                                  ProfilerState.RECORD_AND_RETURN) and \
                not self._timer_only:
            self._enable()
        self._step_t0 = time.perf_counter()
        return self

    def stop(self):
        if self.current_state in (ProfilerState.RECORD,
                                  ProfilerState.RECORD_AND_RETURN) and \
                not self._timer_only:
            self._disable()
            if self._on_trace_ready is not None:
                self._on_trace_ready(self)
        self.current_state = ProfilerState.CLOSED

    def step(self, num_samples: Optional[int] = None):
        now = time.perf_counter()
        if self._step_t0 is not None:
            if self.recorder is not None and self.current_state in (
                    ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
                self.recorder.add(f"ProfileStep#{self.step_num}",
                                  self._step_t0, now, "step")
            self._step_times.append(now - self._step_t0)
            if num_samples:
                self._batch_sizes.append(num_samples)
        prev = self.current_state
        self.step_num += 1
        self.current_state = self._scheduler(self.step_num)
        recording = (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN)
        if prev not in recording and self.current_state in recording and \
                not self._timer_only:
            self._enable()
        if prev in recording and self.current_state not in recording:
            if not self._timer_only:
                self._disable()
                if prev == ProfilerState.RECORD_AND_RETURN or \
                        self.current_state == ProfilerState.CLOSED:
                    if self._on_trace_ready is not None:
                        self._on_trace_ready(self)
        self._step_t0 = time.perf_counter()

    def step_info(self, unit: str = "samples") -> str:
        if not self._step_times:
            return "no steps recorded"
        dt = self._step_times[-1]
        msg = f"step {self.step_num}: {dt * 1e3:.2f} ms/step"
        if self._batch_sizes:
            ips = self._batch_sizes[-1] / dt
            msg += f", ips: {ips:.2f} {unit}/s"
        return msg

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()

    # -- export / summary ----------------------------------------------------
    def _export_chrome(self, path: str):
        # correlated serving timelines (observability layer): one track
        # per request, one for engine dispatches. Exported only while
        # observability is ENABLED — a ring left over from an earlier,
        # since-disabled session must not pollute an unrelated export.
        # ONE clock base across host spans and timeline tracks keeps
        # every ts positive and the tracks aligned.
        from .. import observability as _obs

        rec = self.recorder
        tl_events = _obs.timeline.events() if _obs.enabled() else []
        candidates = [e.start for e in rec.events] if rec else []
        candidates += [e.t0 for e in tl_events]
        if _obs.enabled():
            # records AND step-overlap window starts: a window that opens
            # before the first recorded event must not push the comms
            # track to negative ts
            t0 = _obs.comms.earliest_t0()
            if t0 is not None:
                candidates.append(t0)
        base = min(candidates, default=0.0)
        events = []
        if rec:
            for e in rec.events:
                events.append({
                    "name": e.name, "ph": "X", "cat": e.kind,
                    "ts": (e.start - base) * 1e6,
                    "dur": (e.end - e.start) * 1e6,
                    "pid": os.getpid(), "tid": e.tid,
                })
        if _obs.enabled() and tl_events:
            events.extend(_obs.timeline.chrome_events(base))
        if _obs.enabled():
            # pid "comms": per-kind collective tracks + step-overlap
            # windows, on the SAME clock base as host spans/timelines
            events.extend(_obs.comms.chrome_events(base))
        with open(path, "w") as f:
            json.dump({"traceEvents": events,
                       "deviceTraceDir": self._device_trace_dir}, f)

    def export(self, path: str, format: str = "json"):
        self._export_chrome(path)

    def summary(self, sorted_by=SortedKeys.CPUTotal, op_detail: bool = True,
                thread_sep: bool = False, time_unit: str = "ms",
                views=None) -> str:
        """Aggregated host-span table (reference profiler_statistic)."""
        if self.recorder is None or not self.recorder.events:
            return "no profiling data"
        agg = {}
        for e in self.recorder.events:
            tot, cnt, mx = agg.get(e.name, (0.0, 0, 0.0))
            d = e.end - e.start
            agg[e.name] = (tot + d, cnt + 1, max(mx, d))
        unit = {"s": 1.0, "ms": 1e3, "us": 1e6}[time_unit]
        rows = sorted(agg.items(), key=lambda kv: -kv[1][0])
        lines = [f"{'Name':<40}{'Calls':>8}{'Total(' + time_unit + ')':>14}"
                 f"{'Avg(' + time_unit + ')':>12}{'Max(' + time_unit + ')':>12}"]
        for name, (tot, cnt, mx) in rows:
            lines.append(f"{name[:39]:<40}{cnt:>8}{tot * unit:>14.3f}"
                         f"{tot / cnt * unit:>12.3f}{mx * unit:>12.3f}")
        if self._profile_memory:
            from .. import device as dev_api

            st = dev_api.memory_stats()
            lines.append("")
            lines.append(
                f"Device memory [{st['device']}]: "
                f"allocated={st['bytes_in_use'] / 1e6:.2f} MB, "
                f"peak={st['peak_bytes_in_use'] / 1e6:.2f} MB, "
                f"live_arrays={st['num_live_arrays']}")
            counters = dev_api.monitor.get_all()
            if counters:
                lines.append("Monitor counters: " + ", ".join(
                    f"{k}={v}" for k, v in counters.items()))
        lines.extend(self._lazy_summary_lines())
        lines.extend(self._serving_summary_lines())
        lines.extend(self._fleet_summary_lines())
        lines.extend(self._resilience_summary_lines())
        lines.extend(self._elastic_summary_lines())
        lines.extend(self._observability_summary_lines())
        lines.extend(self._mesh_summary_lines())
        return "\n".join(lines)

    # Every section builder scrapes through ONE snapshot of the monitor
    # registry (`monitor.snapshot(prefix)`) instead of N point reads +
    # hand-rolled get_all() filters per section.
    @staticmethod
    def _reason_counts(snap: dict, prefix: str) -> dict:
        """Non-zero `<prefix><reason>` counters keyed by reason — the
        shared sub-counter formatting every section used to re-implement."""
        return {k[len(prefix):]: v for k, v in snap.items()
                if k.startswith(prefix) and v}

    @staticmethod
    def _kv_join(reasons: dict) -> str:
        return ", ".join(f"{k}={v}" for k, v in sorted(reasons.items()))

    @classmethod
    def _lazy_summary_lines(cls):
        """Lazy eager-region stats (core/lazy.py): how many flushes ran in
        the profiled window, why, and how large the fused regions were —
        the `lazy_region_flush[...]` host spans above are the per-flush
        timings."""
        from ..framework import monitor

        snap = monitor.snapshot("lazy.", include_histograms=False)
        g = snap.get
        flushes = g("lazy.flushes", 0)
        if not flushes:
            return []
        fused = g("lazy.fused_ops", 0)
        return [
            "",
            f"Lazy eager regions: {flushes} flushes, {fused} ops fused "
            f"(avg {fused / max(flushes, 1):.1f}/region, "
            f"max {g('lazy.max_region_ops', 0)}), "
            f"fused-backward {g('lazy.fused_backward', 0)}",
            "Flush reasons: " + cls._kv_join(
                cls._reason_counts(snap, "lazy.flushes.")),
        ]

    @classmethod
    def _resilience_summary_lines(cls):
        """Fault-tolerance stats (resilience/): checkpoint saves + their
        transient-I/O retries, quarantined torn directories, StepGuard
        rollbacks by trip reason, AMP skip streaks, emergency preemption
        saves, and elastic heartbeat reaps."""
        from ..framework import monitor

        snap = monitor.snapshot(include_histograms=False)
        g = lambda k: snap.get(k, 0)  # noqa: E731
        if not (g("resilience.saves") or g("resilience.rollbacks")
                or g("resilience.quarantines")
                or g("resilience.emergency_saves") or g("elastic.reaped")):
            return []
        trips = cls._reason_counts(snap, "resilience.trips.")
        lines = [
            "",
            f"Resilience: {g('resilience.saves')} checkpoint saves "
            f"({g('resilience.retries')} write retries, "
            f"{g('resilience.emergency_saves')} emergency), "
            f"{g('resilience.quarantines')} quarantined, "
            f"{g('resilience.rollbacks')} rollbacks",
            f"  amp skipped steps {g('amp.skipped_steps')}, "
            f"elastic reaped {g('elastic.reaped')} "
            f"(lock retries {g('elastic.lock_retries')})",
        ]
        if trips:
            lines.append("  trip reasons: " + cls._kv_join(trips))
        return lines

    @classmethod
    def _elastic_summary_lines(cls):
        """Elastic multichip training stats (resilience/elastic_train.py):
        mesh re-formations with lost-pod count, the current world size,
        the last kill-to-training-again recovery wall, and the fencing
        evidence (stale heartbeats rejected after an epoch bump)."""
        from ..framework import monitor

        snap = monitor.snapshot(include_histograms=False)
        g = lambda k: snap.get(k, 0)  # noqa: E731
        if not (g("elastic.reforms") or g("elastic.lost_pods")):
            return []
        lines = [
            "",
            f"Elastic: {g('elastic.reforms')} mesh re-formations "
            f"({g('elastic.lost_pods')} pods lost), "
            f"world size {g('elastic.world_size')}, "
            f"last recovery {g('elastic.recovery_ms')} ms",
            f"  stale heartbeats rejected {g('elastic.stale_heartbeats')}, "
            f"reaped {g('elastic.reaped')}",
        ]
        return lines

    @classmethod
    def _serving_summary_lines(cls):
        """Continuous-batching serving stats (serving/metrics.py): request
        outcomes, token throughput counters, latency percentiles, and the
        retrace counters that must stay flat in steady state."""
        from ..framework import monitor

        snap = monitor.snapshot("serving.", include_histograms=False)
        g = lambda k: snap.get(k, 0)  # noqa: E731
        if not g("serving.requests_submitted"):
            return []
        rejected = cls._reason_counts(snap, "serving.rejected.")
        lines = [
            "",
            f"Serving: {g('serving.requests_submitted')} submitted, "
            f"{g('serving.requests_completed')} completed, "
            f"{g('serving.requests_rejected')} rejected, "
            f"{g('serving.requests_timed_out')} timed out, "
            f"{g('serving.requests_cancelled')} cancelled, "
            f"{g('serving.preemptions')} preemptions",
            f"  tokens: {g('serving.tokens_generated')} generated over "
            f"{g('serving.decode_steps')} decode steps "
            f"(+{g('serving.prefill_tokens')} prefill tokens / "
            f"{g('serving.prefills')} prefills); retraces: "
            f"decode={g('serving.decode_retraces')}",
            f"  occupancy avg {g('serving.batch_occupancy_avg_pct')}%, "
            f"KV util {g('serving.kv_utilization_pct')}% "
            f"(peak {g('serving.kv_utilization_peak_pct')}%), "
            f"queue depth {g('serving.queue_depth')} "
            f"(peak {g('serving.queue_depth_peak')})",
        ]
        if g("serving.step.programs"):
            # a plain round is one program and one fetch, launched before
            # the round before it is fetched (docs/SERVING.md "A round in
            # flight")
            lines.append(
                f"  rounds: {g('serving.step.programs')} programs, "
                f"{g('serving.step.fetches')} fetches, "
                f"{g('serving.step.overlapped')} overlapped (share "
                f"{g('serving.step.overlap_share')}), "
                f"{g('serving.step.wasted_lanes')} wasted lanes, "
                f"{g('serving.step.forced_settles')} forced settles; "
                f"{g('serving.step.all_rows_calls')} all-rows calls "
                f"(logits retraces {g('serving.logits_retraces')})")
        if g("serving.dsa.selected_share"):
            # an engine with a learned indexer: what its selections hold of
            # what they were picked from (docs/OBSERVABILITY.md)
            lines.append(
                f"  selection: {g('serving.dsa.selected_share')} of the "
                f"causal positions selected, "
                f"{g('serving.kv_bytes_per_token.latent')} + "
                f"{g('serving.kv_bytes_per_token.index')} bytes a token "
                f"(latent + index)")
        if g("serving.state.bytes_per_seq"):
            # an engine over a state group: a sequence holds one slot of
            # recurrent state, whatever its length (docs/SERVING.md "A
            # state group")
            lines.append(
                f"  state: {g('serving.state.slots_in_use')} slots in use, "
                f"{g('serving.state.bytes_per_seq')} bytes a sequence, "
                f"{g('serving.state.resets')} started from zero, "
                f"{g('serving.state.restarts')} restarted")
        if g("serving.ttft_p50_ms"):
            lines.append(
                f"  TTFT p50 {g('serving.ttft_p50_ms')} ms / "
                f"p99 {g('serving.ttft_p99_ms')} ms, "
                f"TPOT mean {g('serving.tpot_mean_ms')} ms")
        # Quantized serving block: rendered once an engine published a
        # non-default mode (serving/quant.py; docs/SERVING.md
        # "Quantized serving")
        wb, kb = g("serving.quant.wbits"), g("serving.quant.kv_bits")
        if (wb and wb != 16) or (kb and kb != 16):
            fmt = lambda b: "native" if b == 16 else f"int{b}"  # noqa: E731
            lines.append(
                f"  quant: weights {fmt(wb)}, KV {fmt(kb)}, "
                f"{g('serving.kv_bytes_per_token')} KV bytes/token")
        if g("serving.spec_steps"):
            lines.append(
                f"  speculative: {g('serving.spec_accepted_tokens')}/"
                f"{g('serving.spec_proposed_tokens')} drafts accepted "
                f"({g('serving.spec_acceptance_pct')}%) over "
                f"{g('serving.spec_steps')} verify rounds, "
                f"{g('serving.spec_tokens_per_lane_step')} tok/lane-step "
                f"(verify retraces {g('serving.verify_retraces')}, "
                f"sample retraces {g('serving.sample_retraces')})")
        if rejected:
            lines.append("  reject reasons: " + cls._kv_join(rejected))
        # Disaggregated handoff block: rendered once a prefill→decode
        # session migration landed (serving/disagg.py; docs/SERVING.md
        # "Disaggregated prefill/decode")
        h = lambda k: snap.get(f"serving.handoff.{k}", 0)  # noqa: E731
        if h("count"):
            lines.append(
                f"  Handoffs: {h('count')} sessions streamed "
                f"prefill→decode, {h('bytes')} KV payload bytes, "
                f"{round(h('wall_ms') / max(1, h('count')), 3)} ms/handoff "
                f"mean extract→inject wall")
        # Multi-LoRA block: rendered once an adapter pool is bound
        # (serving/lora.py; docs/SERVING.md "Multi-LoRA serving") — the
        # switch_retraces figure is the one that must stay 0 in steady
        # state across any adapter mix
        lo = lambda k: snap.get(f"serving.lora.{k}", 0)  # noqa: E731
        if lo("pool_slots"):
            lines.append(
                f"  LoRA: {lo('resident_adapters')}/{lo('pool_slots')} "
                f"slots resident ({lo('registered_adapters')} registered, "
                f"rank<= {lo('rank_max')}), {lo('miss_loads')} miss loads, "
                f"{lo('evictions')} evictions, "
                f"switch retraces {lo('switch_retraces')}")
        # Prefix cache block: only rendered once the radix cache saw an
        # admission (hits + misses > 0) — docs/SERVING.md "Prefix
        # caching & multi-tenant SLOs"
        p = lambda k: snap.get(f"serving.prefix_cache.{k}", 0)  # noqa: E731
        if p("hits") or p("misses"):
            lines.append(
                f"  Prefix cache: {p('hits')} hits / {p('misses')} misses "
                f"({p('hit_rate_pct')}% of admissions), "
                f"{p('hit_tokens')} prefill tokens served from cache; "
                f"{p('evictions')} evictions, {p('cow_copies')} COW copies")
            if p("ttft_cached_p50_ms") or p("ttft_cold_p50_ms"):
                lines.append(
                    f"    TTFT p50 cached {p('ttft_cached_p50_ms')} ms "
                    f"vs cold {p('ttft_cold_p50_ms')} ms")
        tenants = sorted({k.split(".")[2] for k in snap
                          if k.startswith("serving.tenant.")})
        if tenants:
            parts = []
            for t in tenants:
                adm = snap.get(f"serving.tenant.{t}.admitted", 0)
                defer = sum(v for k, v in snap.items() if k.startswith(
                    f"serving.tenant.{t}.deferred."))
                parts.append(f"{t}={adm} admitted"
                             + (f" ({defer} deferred)" if defer else ""))
            lines.append("  tenants: " + ", ".join(parts))
        # Overload/faults block: only rendered when the fault-tolerance
        # layer actually acted (shed, isolated, restarted, or stalled)
        if (g("serving.shed_total") or g("serving.isolated_faults")
                or g("serving.step_faults") or g("serving.engine_restarts")
                or g("serving.stall_detections")
                or g("serving.requests_failed")):
            shed_by = cls._reason_counts(snap, "serving.shed.")
            lines.append(
                f"  overload/faults: {g('serving.shed_total')} shed, "
                f"{g('serving.isolated_faults')} isolated faults, "
                f"{g('serving.step_faults')} transient step faults, "
                f"{g('serving.requests_failed')} failed, "
                f"{g('serving.engine_restarts')} engine restarts, "
                f"{g('serving.stall_detections')} stall detections")
            if shed_by:
                lines.append("  shed reasons: " + cls._kv_join(shed_by))
        return lines

    @classmethod
    def _fleet_summary_lines(cls):
        """Multi-replica serving-fleet stats (`serving/fleet.py`):
        replica population, relocation/death/drain activity, placement
        failover, and session-affinity effectiveness. Empty unless a
        `FleetRouter` ran in this process."""
        from ..framework import monitor

        snap = monitor.snapshot("fleet.", include_histograms=False)
        g = lambda k: snap.get(k, 0)  # noqa: E731
        if not g("fleet.replicas_total"):
            return []
        lines = [
            "",
            f"Fleet: {g('fleet.replicas_alive')}/"
            f"{g('fleet.replicas_total')} replicas alive "
            f"({g('fleet.replicas_draining')} draining, "
            f"{g('fleet.replicas_added')} added, "
            f"{g('fleet.drained')} drained, "
            f"{g('fleet.replica_deaths')} deaths), "
            f"{g('fleet.submitted')} fleet submissions",
            f"  relocations {g('fleet.relocations')} "
            f"({g('fleet.relocated_tokens')} tokens carried), "
            f"retried submits {g('fleet.retried_submits')}, "
            f"submit faults {g('fleet.submit_faults')}, "
            f"fleet-failed {g('fleet.requests_failed')}",
        ]
        if g("fleet.session_hits") or g("fleet.session_misses"):
            lines.append(
                f"  session affinity: {g('fleet.session_hits')} hits / "
                f"{g('fleet.session_misses')} misses")
        failed = cls._reason_counts(snap, "fleet.requests_failed.")
        if failed:
            lines.append("  fleet failure reasons: " + cls._kv_join(failed))
        return lines

    @staticmethod
    def _observability_summary_lines():
        """Compile/retrace records, the per-executable cost table, and
        the collective-trace "Comms:" section (observability layer) —
        empty unless something was recorded."""
        from .. import observability as _obs

        lines = list(_obs.compile_trace.summary_lines())
        lines.extend(_obs.costs.summary_lines())
        lines.extend(_obs.comms.summary_lines())
        return lines

    @classmethod
    def _mesh_summary_lines(cls):
        """Cross-host aggregation stats (`monitor.aggregate_mesh`):
        host count, straggler attribution, step-wall spread — plus the
        current global mesh topology. Empty until an aggregation ran."""
        from ..framework import monitor

        snap = monitor.snapshot("mesh.", include_histograms=False)
        # trigger on aggregations, not mesh.hosts: init_parallel_env sets
        # the hosts gauge unconditionally, and this section's contract is
        # "empty until an aggregation ran"
        if not snap.get("mesh.aggregations"):
            return []
        hosts = snap.get("mesh.hosts", 0)
        lines = ["", f"Mesh: {hosts} host(s)"]
        try:
            from ..distributed.process_mesh import get_mesh

            mesh = get_mesh()
            if mesh is not None:
                d = mesh.describe()
                lines[-1] += (f", topology {d['shape']} "
                              f"axes={d['dim_names']}")
        except Exception:
            pass
        if "mesh.straggler_host" in snap:
            lines.append(
                f"  straggler host {snap['mesh.straggler_host']} "
                f"(step-wall spread "
                f"{snap.get('mesh.step_wall_spread_pct', 0)}%)")
        return lines
