"""paddle_tpu.observability — the runtime's own perf/behavior evidence.

Three PRs of serving work shipped with no hardware-level signal (ROADMAP
item 5): MFU claims rested on hand-coded FLOP formulas, retrace counters
said "how many" but never "why", and a faulted request's lifecycle could
only be reconstructed from print statements. This package is the layer
that lets every perf claim be *derived* instead of asserted:

- **Compile & retrace tracing** (`compile_trace.py`): ONE record a
  top-level compile, fed by JAX's own monitoring events and always on
  (which function, seconds tracing, lowering, compiling or reading the
  persistent cache); under `enable()` a retrace additionally carries a
  human-readable diff against the nearest cached signature — which aval
  shape/dtype or static arg changed — attached by `core.dispatch`
  (eager/lazy executables) and the serving scheduler (engine dispatch
  signatures). The stamps of a set-up that are not JAX's live beside it
  (`stamp`: `startup.import_s`, `engine.build_s`).
- **XLA cost-based accounting** (`costs.py`): `CostCard` wraps
  `lower().compile().cost_analysis()/memory_analysis()` — compiler-
  reported FLOPs, bytes accessed, and memory footprint per executable,
  cached in a `CostBook` together with call counts and wall time so
  `profiler.summary()` can print achieved FLOP/s per executable and
  `bench.py` derives MFU from what XLA actually compiled.
- **Per-request serving timelines + flight recorder** (`timeline.py`):
  correlated spans (one track per request, one per engine dispatch) in
  the chrome-trace export, plus a bounded in-memory flight recorder
  dumped to `profiler_log/flight_*.jsonl` on fault/stall.
- **Bench baseline store** (`baseline.py`, stdlib-only): per-scenario
  per-platform last-good results under `profiler_log/baselines/`,
  compared by `tools/bench_diff.py` (>5 % regression fails).
- **Collective tracing + overlap accounting** (`comms.py`): every eager
  collective records kind/group/bytes/wall/algbw into a bounded ring +
  `comm.<kind>.*` counters; `step_overlap` turns a step window into an
  exposed-comm-ms + overlap-efficiency report, and `hlo_comm_census`
  reports the comm volume of compiled (GSPMD) executables.
- **HBM + KV telemetry, OOM forensics** (`memory.py`): per-device
  live/peak bytes, paged-KV fragmentation snapshots, and the
  `flight_oom_*.jsonl` dump on KV exhaustion / backend allocation
  failure.

Everything but the compile record is OFF by default and costs nothing
while off: instrumented sites check one module-level bool (`enabled()`);
no span is allocated, no signature is built, and `cost_analysis()` is
never invoked when disabled (asserted by tests/test_observability.py).
The compile record's listeners run only when JAX compiles.
"""
from __future__ import annotations

from . import comms, compile_trace, costs, memory, timeline
from .baseline import BaselineStore, compare_reports
from .compile_trace import CompileRecord, compiles, retrace_causes
from .comms import CommRecord, hlo_comm_census, overlap_report, step_overlap
from .costs import CostBook, CostCard, cost_book
from .timeline import (dispatch_span, dump_flight, events, flight_events,
                       request_event)

__all__ = [
    "enable", "disable", "enabled", "reset",
    "CompileRecord", "compiles", "retrace_causes",
    "CostBook", "CostCard", "cost_book",
    "request_event", "dispatch_span", "events", "flight_events",
    "dump_flight",
    "BaselineStore", "compare_reports",
    "CommRecord", "step_overlap", "overlap_report", "hlo_comm_census",
]

_enabled = False


def enabled() -> bool:
    """One-bool gate every instrumented site checks first. Keep this a
    plain module attribute read — it IS the disabled-path overhead."""
    return _enabled


def enable(flight_capacity: int = 4096):
    """Turn the observability layer on (idempotent). `flight_capacity`
    bounds the in-memory flight recorder (events, not bytes)."""
    global _enabled
    timeline.configure(flight_capacity)
    _enabled = True


def disable():
    global _enabled
    _enabled = False


def reset():
    """Drop recorded state (tests / measurement-window boundaries); does
    not change enabled/disabled."""
    compile_trace.reset()
    costs.reset()
    timeline.reset()
    comms.reset()
    memory.reset()
