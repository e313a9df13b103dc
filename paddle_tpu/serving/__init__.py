"""paddle_tpu.serving — continuous-batching inference serving (L9+).

The reference ships a generic optimized inference engine plus a serving C
API (`paddle/fluid/inference/api/`, `paddle/fluid/inference/capi_exp/`);
this package is its TPU-native serving layer over the paged-KV decode
stack, shaped by the Ragged-Paged-Attention observation (PAPERS.md): keep
ONE fixed-shape decode program over a ragged batch of sequences with
per-sequence block tables, and let host-side scheduling — not XLA
recompilation — absorb all request churn.

Components:
- `EngineCore` (engine.py): the model-agnostic engine protocol
  (stacked params + one paged pool tuple + ONE fixed-shape ragged step).
  `LlamaInferenceEngine` is the flagship implementation; `MLPLMEngine`
  is a deliberately tiny second model family proving the scheduler is
  model-agnostic.
- `Scheduler` (scheduler.py): continuous batching — admits queued
  requests into decode slots, evicts finished sequences mid-batch,
  preempts on `KVCacheExhausted`, keeps decode shape-stable (zero
  recompiles in steady state).
- `ServingFrontend` (frontend.py): submit/stream/cancel with deadlines,
  admission control (reject-with-reason, never crash), token callbacks.
- `ServingMetrics` (metrics.py): TTFT/TPOT, queue depth, batch occupancy,
  KV utilization, preemptions, shed/fault/restart counters — published
  to `framework.monitor` and rendered by `profiler.summary()`.
- fault tolerance (fault_tolerance.py): `AdmissionConfig` overload
  shedding, the `EngineStepError` isolation boundary, `WatchdogConfig`
  bounded engine restarts, typed `EngineStalled` — every submitted
  request reaches a terminal status no matter what the engine does.
- prefix caching (`inference/prefix_cache.py`, enabled via
  `prefix_cache=True`): shared-prefix radix tree over the paged pool
  with copy-on-write refcounting — repeated prompts and multi-turn
  sessions skip the cached part of prefill entirely.
- `SLOClass`/`SLOConfig` (slo.py): multi-tenant SLO scheduling —
  per-tenant KV quotas and reserves, deficit-weighted decode-lane
  allocation, latency-tier watermark scaling.
- `FleetRouter` (fleet.py): the data-parallel replica tier — N
  frontends behind load-aware session-affine dispatch, elastic
  membership with incarnation-fenced heartbeats, and replica-failure
  relocation that carries committed tokens as prompt prefix, extending
  the terminal-status contract fleet-wide.
- `DisaggRouter` (disagg.py): disaggregated prefill/decode — the fleet
  split into role-specialized tiers, with prefill-complete sessions
  streamed to the decode tier as migrated KV-block payloads
  (`inference/kv_migrate.py`) instead of re-prefilled.
- multi-LoRA serving (lora.py): `attach_adapters` wraps a built engine
  (bf16 or quantized base) with per-lane batched-gather LoRA epilogues
  riding the ragged metadata, backed by a paged `AdapterPool` —
  hundreds of tenant adapters on ONE engine, zero steady-state
  retraces across any adapter mix.
"""
from .disagg import DisaggRouter, HandoffError, HandoffState
from .engine import EngineCore, MLPLMEngine
from .fault_tolerance import (AdmissionConfig, EngineStalled,
                              EngineStepError, WatchdogConfig)
from .fleet import FleetHandle, FleetRouter, ReplicaHandle
from .frontend import RequestHandle, ServingFrontend
from .lora import (AdapterError, AdapterPool, AdapterPoolExhausted,
                   AdapterRankError, LoRAEngine, UnknownAdapterError,
                   attach_adapters)
from .metrics import ServingMetrics
from .quant import greedy_agreement, quant_summary, quantize_engine
from .scheduler import Request, RequestStatus, SamplingParams, Scheduler
from .slo import SLOClass, SLOConfig, slo_for_adapters
from .spec import (DraftEngineProposer, NGramProposer, Proposer,
                   SpecDecodeConfig)
from .tp import ShardedEngine, ShardingConfigError, shard_engine

__all__ = [
    "AdapterError", "AdapterPool", "AdapterPoolExhausted",
    "AdapterRankError", "AdmissionConfig", "DisaggRouter",
    "DraftEngineProposer", "EngineCore",
    "EngineStalled", "EngineStepError", "FleetHandle", "FleetRouter",
    "HandoffError", "HandoffState", "LoRAEngine", "MLPLMEngine",
    "NGramProposer", "Proposer", "ReplicaHandle", "Request",
    "RequestHandle", "RequestStatus", "SamplingParams", "Scheduler",
    "ServingFrontend", "ServingMetrics", "ShardedEngine",
    "ShardingConfigError", "SLOClass", "SLOConfig", "SpecDecodeConfig",
    "UnknownAdapterError", "WatchdogConfig", "attach_adapters",
    "greedy_agreement", "quant_summary",
    "quantize_engine", "shard_engine", "slo_for_adapters",
]
