"""The program's own spans on the profiler's clock (docs/OBSERVABILITY.md,
"Program spans and device scopes"): `RecordEvent` enters a
`jax.profiler.TraceAnnotation`, so under a `jax.profiler.start_trace`
session the serving path's `frontend.*` / `sched.*` spans land in the
`.xplane.pb` with their ids; with no session nothing is recorded anywhere.
The names are a contract that `benchmark/program_trace.py` reads.
"""
import glob

import jax
import numpy as np
import pytest

from paddle_tpu import profiler
from paddle_tpu.framework import monitor
from paddle_tpu.serving import (MLPLMEngine, NGramProposer, ServingFrontend,
                                ServingMetrics, SpecDecodeConfig)

VOCAB = 64
# every span of the serving path that an ordinary run produces
# (`sched.preempt` needs KV pressure and has a case of its own). The plain
# round screens and samples inside the step's program and blocks once, in
# `sched.sample`; `sched.screen` is the speculative round's first fetch.
NAMES = {"frontend.submit", "sched.step", "sched.expire", "sched.admit",
         "sched.admit_one", "sched.grow", "sched.pack", "sched.dispatch",
         "sched.sample", "sched.commit", "sched.first_token", "sched.finish"}
SPEC_ONLY = {"sched.screen"}


class CountingMetrics(ServingMetrics):
    steps = prefill_tokens = decode_lanes = 0

    def on_ragged_step(self, prefill_tokens, decode_lanes):
        super().on_ragged_step(prefill_tokens, decode_lanes)
        self.steps += 1
        self.prefill_tokens += prefill_tokens
        self.decode_lanes += decode_lanes


def make_frontend(spec, num_blocks=48):
    engine = MLPLMEngine(vocab_size=VOCAB, hidden=16, max_batch_size=4,
                         num_blocks=num_blocks, block_size=4,
                         max_blocks_per_seq=8)
    hook = CountingMetrics()
    return ServingFrontend(engine, metrics=hook, prefill_chunk_tokens=8,
                           spec=spec), hook


def drive(fe, n=6):
    rng = np.random.default_rng(0)
    handles = [fe.submit(rng.integers(1, VOCAB, rng.integers(3, 14)).tolist(),
                         max_new_tokens=int(rng.integers(2, 7)))
               for _ in range(n)]
    fe.run_until_idle()
    return handles


def read_spans(trace_dir):
    """[(name, start_ns, end_ns, ids, line)] of the program's spans."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("frontend.", "sched.")):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats), line.name))
    return out


@pytest.fixture(scope="module", params=["plain", "spec"])
def traced(request, tmp_path_factory):
    """One traced run of a tiny frontend on each decode path."""
    spec = None if request.param == "plain" else SpecDecodeConfig(
        NGramProposer(), num_draft_tokens=2)
    fe, hook = make_frontend(spec)
    drive(fe, 2)                                  # compile outside the trace
    hook.steps = hook.prefill_tokens = hook.decode_lanes = 0
    trace_dir = tmp_path_factory.mktemp(f"trace_{request.param}")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0                  # TraceMe spans only
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        handles = drive(fe)
    finally:
        jax.profiler.stop_trace()
    return {"spans": read_spans(trace_dir), "handles": handles, "hook": hook,
            "path": request.param}


def test_every_name_of_the_contract_is_there(traced):
    want = NAMES | (SPEC_ONLY if traced["path"] == "spec" else set())
    assert {s[0] for s in traced["spans"]} - {"sched.preempt"} == want


def test_a_round_blocks_where_its_spans_say(traced):
    """One `sched.dispatch` and one `sched.sample` a dispatched step. The
    plain round blocks there once, for the step's tokens and flags; the
    speculative round first in `sched.screen`, for its NaN screen."""
    count = {n: sum(s[0] == n for s in traced["spans"])
             for n in ("sched.dispatch", "sched.sample", "sched.screen")}
    steps = traced["hook"].steps
    assert count["sched.dispatch"] == count["sched.sample"] == steps
    assert count["sched.screen"] == (steps if traced["path"] == "spec" else 0)


def test_children_lie_inside_a_step(traced):
    steps = [s for s in traced["spans"] if s[0] == "sched.step"]
    assert [s[3]["step"] for s in steps] == sorted(s[3]["step"] for s in steps)
    assert len({s[3]["step"] for s in steps}) == len(steps)
    for name, start, end, _ids, line in traced["spans"]:
        if name in ("sched.step", "frontend.submit"):
            continue
        assert any(a <= start and end <= b and line == ln
                   for _n, a, b, _i, ln in steps), name


def test_children_and_self_time_add_up(traced):
    """A step's direct children are disjoint and inside it, so their walls
    plus the step's self time are its wall, and the self time is >= 0."""
    spans = traced["spans"]
    for _n, a, b, _i, _l in (s for s in spans if s[0] == "sched.step"):
        phases = sorted((s, e) for n, s, e, _i, _l in spans
                        if a <= s and e <= b and n in (
                            "sched.expire", "sched.admit", "sched.grow",
                            "sched.pack", "sched.dispatch", "sched.screen",
                            "sched.sample", "sched.commit"))
        assert all(x[1] <= y[0] for x, y in zip(phases, phases[1:]))
        assert sum(e - s for s, e in phases) <= b - a


@pytest.mark.parametrize("name", ["sched.admit_one", "sched.first_token",
                                  "sched.finish", "frontend.submit"])
def test_request_spans_once_per_request_with_its_id(traced, name):
    want = sorted(h.request_id for h in traced["handles"])
    got = sorted(s[3]["req"] for s in traced["spans"] if s[0] == name)
    assert got == want
    if name == "sched.finish":
        assert {s[3]["status"] for s in traced["spans"]
                if s[0] == name} == {"finished"}
    if name == "sched.admit_one":
        prompts = {s[3]["req"]: s[3]["prompt"] for s in traced["spans"]
                   if s[0] == name}
        assert all(prompts[h.request_id] == len(h._req.prompt)
                   for h in traced["handles"])


def test_dispatch_carries_the_steps_composition(traced):
    dispatches = [s[3] for s in traced["spans"] if s[0] == "sched.dispatch"]
    hook = traced["hook"]
    assert len(dispatches) == hook.steps > 0
    assert sum(d["prefill_tokens"] for d in dispatches) == hook.prefill_tokens
    assert sum(d["decode_lanes"] for d in dispatches) == hook.decode_lanes
    assert {d["phase"] for d in dispatches} <= {"decode", "verify"}


def test_preempt_span_under_kv_pressure(tmp_path):
    fe, _ = make_frontend(None, num_blocks=9)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        handles = [fe.submit(list(range(1, 10)), max_new_tokens=12)
                   for _ in range(4)]
        fe.run_until_idle()
    finally:
        jax.profiler.stop_trace()
    spans = read_spans(tmp_path)
    preempted = [s[3]["req"] for s in spans if s[0] == "sched.preempt"]
    assert preempted and set(preempted) <= {h.request_id for h in handles}
    assert sum(h._req.num_preemptions for h in handles) == len(preempted)


@pytest.mark.parametrize("path", ["plain", "spec"])
def test_no_session_nothing_recorded(path):
    """Without a profiler session stepping leaves no trace anywhere: no
    recorder, no retrace, and the spans' ids cost no formatting."""
    spec = None if path == "plain" else SpecDecodeConfig(
        NGramProposer(), num_draft_tokens=2)
    fe, _ = make_frontend(spec)
    drive(fe, 2)
    before = monitor.get("serving.ragged_retraces") or 0
    assert profiler.profiler._active_recorder is None
    drive(fe)
    assert profiler.profiler._active_recorder is None
    assert (monitor.get("serving.ragged_retraces") or 0) == before


def test_record_event_under_a_profiler_still_records():
    with profiler.Profiler(targets=[profiler.ProfilerTarget.CPU]) as prof:
        with profiler.RecordEvent("sched.step", step=3):
            pass
        ev = profiler.RecordEvent("explicit")
        ev.begin()
        ev.end()
        ev.end()                                   # idempotent
    names = [e.name for e in prof.recorder.events if e.kind == "range"]
    assert names == ["sched.step", "explicit"]
