"""Declarations the tier-A passes check against.

Three ways to declare (docs/STATIC_ANALYSIS.md has the workflow):

1. **This registry** — the repo's known hot paths, threaded modules, and
   gated callees live here so the passes need no imports and no runtime
   state to know what the runtime contract is.
2. **In-source pragmas** — a trailing ``# ptlint: hot-path`` on a `def`
   line declares that function hot; ``# ptlint: gated-callee`` declares
   that the function's *callers* own the observability enable-bool check
   (its body builds payloads unguarded by design, and every call TO it
   must itself sit behind the gate); ``# ptlint: disable=<pass-id>`` on
   any line suppresses that pass there (use sparingly — the baseline is
   the sanctioned suppression channel, pragmas are for permanent
   by-design sites).
3. **The baseline** (`ptlint_baseline.json`) — for pre-existing findings
   being ratcheted out, not for new code.

Entries are ``(path_suffix, qualname)`` — the suffix matches the end of
the repo-relative path, so the registry survives checkouts at any root.
"""
from __future__ import annotations

__all__ = ["HOT_PATHS", "GATED_CALLEES", "GATED_CALLEE_NAMES",
           "THREADED_MODULES", "OBS_PAYLOAD_PRODUCERS",
           "ENABLE_CHECK_NAMES", "STATIC_PARAM_NAMES", "TRACED_FN_EXTRA",
           "is_hot_path", "is_gated_callee", "is_threaded_module"]

# ---------------------------------------------------------------------------
# hot-path discipline (pass: hot-path)
#
# The serving decode loop's per-call functions: one extra device_put,
# blocking syscall, or per-call import here is multiplied by every token
# ever served. PR 10 measured ~1 ms/arg for stray host-side jnp.asarray
# device_puts on this path.
# ---------------------------------------------------------------------------
HOT_PATHS = {
    ("serving/scheduler.py", "Scheduler._dispatch"),
    ("serving/scheduler.py", "Scheduler.step"),
    ("serving/scheduler.py", "Scheduler._decode"),
    ("serving/scheduler.py", "Scheduler._decode_spec"),
    ("serving/scheduler.py", "Scheduler._commit_token"),
    ("serving/frontend.py", "ServingFrontend.step"),
    # the engine shell: every engine class's dispatch (MLP, Llama,
    # DeepSeek-V3, Cohere2-MoE, Brumby, GLM-MoE-DSA, TP-sharded, LoRA) is
    # these, written once
    ("inference/step_engine.py", "StepEngine.sampled_step"),
    ("inference/step_engine.py", "StepEngine.verify_step"),
    ("inference/step_engine.py", "StepEngine._run"),
    ("ops/sampling.py", "sample_tokens"),
    ("ops/sampling.py", "pack_lanes"),
    ("inference/cache.py", "BlockCacheManager.append_tokens"),
    # the COW block-copy hooks run mid-decode under prefix sharing, and
    # PR 14's quantized pools extend them to move int8 blocks + scale
    # planes in one donated executable — still one dispatch, no per-call
    # host conversions allowed
    ("inference/kv_migrate.py", "PagedPools.copy_kv_block"),
    # the TP-sharded dispatch (ISSUE 16): every token of every multichip
    # serving run crosses it — the shard_map program is one dispatch;
    # stray host work here multiplies by tp chips' worth of traffic
    ("serving/tp.py", "ShardedEngine._dispatch"),
    # the multi-LoRA per-lane adapter-slot install (ISSUE 18) runs before
    # EVERY ragged/verify round — stray per-call imports or host
    # conversions here tax every tenant at once
    ("serving/lora.py", "LoRAEngine.set_lane_adapters"),
    ("serving/scheduler.py", "Scheduler._install_lane_adapters"),
    # the elastic supervisor's per-step heartbeat: one membership-store
    # write per train step — a per-call device_put/import/extra blocking
    # call here lands on EVERY step of every supervised training run
    ("resilience/elastic_train.py", "ElasticTrainSupervisor._beat"),
    # KV-block migration (ISSUE 17): extract/inject are one compiled
    # gather/scatter each, dispatched per handoff and per KV-shipping
    # relocation — per-call host conversions or blocking I/O here would
    # put a wall between the tiers; the disagg pump wraps them once per
    # router step
    ("inference/kv_migrate.py", "PagedPools.extract_kv_blocks"),
    ("inference/kv_migrate.py", "PagedPools.inject_kv_blocks"),
    ("serving/disagg.py", "DisaggRouter._pump_handoffs"),
}

# ---------------------------------------------------------------------------
# zero-cost-off (pass: zero-cost-off)
#
# Functions whose CALLERS own the `observability.enabled()` check — their
# bodies build spans/records unguarded by design (documented in each
# docstring), and every call to them must sit behind the gate. The
# observability package itself (the sink) is exempt wholesale.
# ---------------------------------------------------------------------------
GATED_CALLEES = {
    ("serving/scheduler.py", "Scheduler._obs_dispatch"),
    ("serving/scheduler.py", "Scheduler._obs_req"),
    ("serving/scheduler.py", "Scheduler._obs_oom"),
    ("distributed/communication/collective.py", "_traced_call"),
}

# Bare function names of every registry-declared gated callee: a call
# whose last segment matches one of these is a payload site in ANY
# module (an import of `_traced_call` elsewhere doesn't escape the
# gate) — keep these names distinctive for exactly that reason.
GATED_CALLEE_NAMES = {qn.rsplit(".", 1)[-1] for _sfx, qn in GATED_CALLEES}

# Observability payload producers: a call whose attribute chain ends in
# one of these, reached from OUTSIDE paddle_tpu/observability/, must be
# syntactically gated. (framework.monitor counters are NOT here — the
# serving/resilience metric counters are always-on by design; the
# zero-cost contract covers the obs layer's spans/records/dumps.)
OBS_PAYLOAD_PRODUCERS = {
    "timeline.request_event", "timeline.dispatch_span",
    "timeline.dump_flight", "timeline.events", "timeline.chrome_events",
    "timeline.flight_events",
    "costs.record_call", "costs.ensure_engine_card",
    "comms.record", "comms.step_overlap", "comms.chrome_events",
    "memory.dump_oom",
    "compile_trace.note_retrace", "compile_trace.note_signature",
    "compile_trace.on_compile", "compile_trace.attach",
}

# How a gate reads in source: a call to any of these (e.g.
# `_obs.enabled()`, `observability.enabled()`) in an `if` test — or a
# variable assigned from one (`obs_on = _obs.enabled()`) — marks the
# guarded branch gated.
ENABLE_CHECK_NAMES = {"enabled"}

# ---------------------------------------------------------------------------
# trace-hazard (pass: trace-hazard)
# ---------------------------------------------------------------------------
# Parameters of traced functions that are STATIC by convention (bound via
# functools.partial at the jit site, or hashable config objects): a
# Python `if` on these is resolved at trace time and is NOT a
# data-dependent-control-flow hazard. partial(...) keyword bindings at
# the jit site are detected automatically; these names cover decorator
# forms where the binding isn't visible.
STATIC_PARAM_NAMES = {"block_size", "cfg", "config", "static_cfg",
                      "num_heads", "num_layers", "mesh", "axis_name"}

# Extra traced entry points the resolver can't see (e.g. functions whose
# jit wrapping happens behind a helper): (path_suffix, qualname).
TRACED_FN_EXTRA: set = set()

# ---------------------------------------------------------------------------
# lock/thread hygiene (pass: lock-hygiene)
#
# Modules where more than one thread runs: background checkpoint
# writers, the fleet router vs replica engines, elastic membership
# sweeps, the fault-injection registry. Suffix match on the
# repo-relative path; a trailing "/" declares a whole directory.
# ---------------------------------------------------------------------------
THREADED_MODULES = (
    "resilience/checkpoint_manager.py",
    "resilience/elastic_train.py",   # heartbeat ticker + supervisor
    "resilience/faults.py",
    "serving/fleet.py",
    "serving/disagg.py",   # inherits the router's threaded step fan-out
    "distributed/elastic/",
    "distributed/checkpoint/save_state_dict.py",
)


def _suffix_match(path: str, suffix: str) -> bool:
    if suffix.endswith("/"):
        return f"/{suffix}" in f"/{path}"
    return path == suffix or path.endswith("/" + suffix)


def is_hot_path(path: str, qualname: str) -> bool:
    return any(_suffix_match(path, sfx) and qualname == qn
               for sfx, qn in HOT_PATHS)


def is_gated_callee(path: str, qualname: str) -> bool:
    return any(_suffix_match(path, sfx) and qualname == qn
               for sfx, qn in GATED_CALLEES)


def is_threaded_module(path: str) -> bool:
    return any(_suffix_match(path, sfx) for sfx in THREADED_MODULES)
