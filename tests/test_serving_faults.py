"""Serving fault-tolerance tests: overload admission control (watermark
hysteresis, exact-boundary contract, deadline-aware shedding), request
fault isolation (NaN lanes, targeted `EngineStepError`, cache faults,
probe attribution, transient retry), and the engine watchdog (stall
detection, bounded restarts with KV re-lease, budget exhaustion).

Every failure path is driven deterministically through the
`resilience.faults` registry (`serve.*` sites) — zero sleeps. The
terminal-status contract under test: every submitted request reaches a
terminal status no matter what the engine does, surviving requests stay
token-for-token identical to a fault-free run, and the KV pool never
leaks a block.
"""
import numpy as np
import pytest

from paddle_tpu.framework import monitor
from paddle_tpu.resilience import faults
from paddle_tpu.serving import (AdmissionConfig, EngineStalled,
                                EngineStepError, MLPLMEngine, NGramProposer,
                                RequestStatus, Scheduler, ServingFrontend,
                                ServingMetrics, SpecDecodeConfig,
                                WatchdogConfig)
from paddle_tpu.serving.fault_tolerance import OverloadController
from paddle_tpu.serving.scheduler import Request, SamplingParams

VOCAB = 64


def make_engine(max_batch=4, num_blocks=48, block_size=4,
                max_blocks_per_seq=8):
    return MLPLMEngine(vocab_size=VOCAB, hidden=16, max_batch_size=max_batch,
                       num_blocks=num_blocks, block_size=block_size,
                       max_blocks_per_seq=max_blocks_per_seq)


@pytest.fixture(autouse=True)
def _fresh_state():
    ServingMetrics.reset_monitor()
    faults.clear()
    yield
    faults.clear()


def prompts(n, seed=0, lo=2, hi=10):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, rng.integers(lo, hi)).tolist()
            for _ in range(n)]


def run_trace(fe, plist, max_new=6, max_steps=2000):
    hs = [fe.submit(p, max_new_tokens=max_new) for p in plist]
    fe.run_until_idle(max_steps=max_steps)
    return hs


def assert_no_leaks(fe):
    assert fe.scheduler.kv_leaked_blocks() == 0
    mgr = fe.scheduler.engine.manager
    # after drain only the scheduler's guard block stays leased
    assert mgr.free_blocks == mgr.num_blocks - 1


# ---------------------------------------------------------------------------
# Admission control / load shedding
# ---------------------------------------------------------------------------

class TestOverloadController:
    def test_queue_watermark_exact_boundary_and_hysteresis(self):
        c = OverloadController(AdmissionConfig(queue_high=4, queue_low=2))

        def probe(depth):
            return c.shed_reason(queue_depth=depth, queued_cost=0,
                                 req_cost=1, kv_utilization=0.0,
                                 deadline=None, now=0.0, tpot_s=None,
                                 lanes=4)

        assert probe(3) is None            # below high: admit
        assert probe(4) == "queue_depth"   # EXACTLY high: shed (latch on)
        assert probe(3) == "queue_depth"   # latched: still shedding
        assert probe(2) is None            # EXACTLY low: latch off, admit
        assert probe(3) is None            # off stays off below high

    def test_cost_watermark_weighs_max_new_tokens(self):
        c = OverloadController(AdmissionConfig(cost_high=100, cost_low=40))

        def probe(queued, req):
            return c.shed_reason(queue_depth=0, queued_cost=queued,
                                 req_cost=req, kv_utilization=0.0,
                                 deadline=None, now=0.0, tpot_s=None,
                                 lanes=4)

        # 3 queued requests is nothing by depth, but 100 queued tokens
        # IS load. The latch watches the BACKLOG only — the incoming
        # request's own cost must not enter it (an oversize request on
        # an idle server would latch shedding on forever):
        assert probe(0, 500) is None         # idle: always admit
        assert probe(99, 4) is None          # 99 < 100: admit
        assert probe(100, 1) == "queue_cost"  # exactly high: latch on
        assert probe(50, 1) == "queue_cost"  # latched above low: shed
        assert probe(40, 500) is None        # drained to <= low: admit

    def test_kv_watermark(self):
        c = OverloadController(AdmissionConfig(kv_high=0.9, kv_low=0.5))

        def probe(util):
            return c.shed_reason(queue_depth=0, queued_cost=0, req_cost=1,
                                 kv_utilization=util, deadline=None,
                                 now=0.0, tpot_s=None, lanes=4)

        assert probe(0.89) is None
        assert probe(0.9) == "kv_pressure"
        assert probe(0.6) == "kv_pressure"   # hysteresis holds
        assert probe(0.5) is None

    def test_deadline_unmeetable(self):
        c = OverloadController(AdmissionConfig(deadline_aware=True))
        kw = dict(queue_depth=0, kv_utilization=0.0, now=10.0, lanes=4)
        # 80 queued tokens / 4 lanes + 10 own = 30 steps * 10 ms = 0.3 s
        assert c.shed_reason(queued_cost=80, req_cost=10, tpot_s=0.01,
                             deadline=10.2, **kw) == "deadline_unmeetable"
        assert c.shed_reason(queued_cost=80, req_cost=10, tpot_s=0.01,
                             deadline=10.5, **kw) is None
        # no TPOT measurement yet -> no estimate -> admit
        assert c.shed_reason(queued_cost=80, req_cost=10, tpot_s=None,
                             deadline=10.2, **kw) is None

    def test_config_validation(self):
        with pytest.raises(ValueError):
            AdmissionConfig(queue_high=4, queue_low=9)
        cfg = AdmissionConfig(queue_high=8, cost_high=100, kv_high=0.9)
        assert cfg.queue_low == 4 and cfg.cost_low == 50
        assert cfg.kv_low == pytest.approx(0.75)


class TestSheddingIntegration:
    def test_queue_shed_then_recover(self):
        eng = make_engine(max_batch=1, num_blocks=32)
        fe = ServingFrontend(eng, admission=AdmissionConfig(queue_high=3,
                                                            queue_low=1))
        hs = [fe.submit([1, 2, 3], max_new_tokens=4) for _ in range(3)]
        shed = [fe.submit([1, 2, 3], max_new_tokens=4) for _ in range(2)]
        assert all(h.status is RequestStatus.SHED for h in shed)
        assert all(h.finish_reason == "queue_depth" for h in shed)
        assert all(h.status is RequestStatus.QUEUED for h in hs)
        assert monitor.get("serving.shed_total") == 2
        assert monitor.get("serving.shed.queue_depth") == 2
        fe.run_until_idle(max_steps=300)
        assert all(h.status is RequestStatus.FINISHED for h in hs)
        # drained below the low watermark: the latch released
        late = fe.submit([1, 2, 3], max_new_tokens=2)
        assert late.status is not RequestStatus.SHED
        fe.run_until_idle(max_steps=100)
        assert late.status is RequestStatus.FINISHED
        assert_no_leaks(fe)

    def test_kv_pressure_shed(self):
        eng = make_engine(max_batch=2, num_blocks=8)
        fe = ServingFrontend(
            eng, admission=AdmissionConfig(kv_high=0.6, kv_low=0.2))
        # a long request leases most of the pool
        hog = fe.submit(list(range(1, 20)), max_new_tokens=8)
        fe.step()
        assert eng.manager.utilization() >= 0.6
        shed = fe.submit([1, 2], max_new_tokens=2)
        assert shed.status is RequestStatus.SHED
        assert shed.finish_reason == "kv_pressure"
        fe.run_until_idle(max_steps=200)
        assert hog.status is RequestStatus.FINISHED
        # pool drained: next submit admits again
        ok = fe.submit([1, 2], max_new_tokens=2)
        assert ok.status is RequestStatus.QUEUED
        fe.run_until_idle(max_steps=100)
        assert ok.status is RequestStatus.FINISHED

    def test_deadline_unmeetable_shed_is_immediate(self):
        eng = make_engine(max_batch=2)
        fe = ServingFrontend(eng, admission=AdmissionConfig())
        # warm the TPOT estimate with a real request
        run_trace(fe, [[1, 2, 3]], max_new=4)
        assert fe.scheduler.tpot_estimate() is not None
        doomed = fe.submit([1, 2, 3], max_new_tokens=10 ** 6,
                           timeout_s=1e-4)
        assert doomed.status is RequestStatus.SHED
        assert doomed.finish_reason == "deadline_unmeetable"
        # shed happened at submit time, in microseconds, without queueing
        assert doomed._req.t_finish - doomed._req.t_submit < 0.005
        relaxed = fe.submit([1, 2, 3], max_new_tokens=4, timeout_s=60.0)
        assert relaxed.status is RequestStatus.QUEUED
        fe.run_until_idle(max_steps=200)
        assert relaxed.status is RequestStatus.FINISHED


# ---------------------------------------------------------------------------
# EngineStalled: the wedged-engine bugfix (no watchdog)
# ---------------------------------------------------------------------------

class TestEngineStalled:
    def _wedge(self, stall_after):
        eng = make_engine(max_batch=2, num_blocks=8)
        fe = ServingFrontend(eng, stall_after=stall_after)
        # an external tenant leases every free block: the queued request
        # can never admit and nothing is running to free blocks — the
        # old run_until_idle span forever here
        eng.manager.allocate(999, 7 * 4)
        fe.submit([1, 2, 3], max_new_tokens=2)
        return fe

    def test_run_until_idle_raises_typed_stall(self):
        fe = self._wedge(stall_after=16)
        with pytest.raises(EngineStalled) as ei:
            fe.run_until_idle(max_steps=10000)
        assert ei.value.steps >= 16
        assert "free_blocks" in str(ei.value)

    def test_stream_raises_typed_stall(self):
        fe = self._wedge(stall_after=16)
        h = fe.submit([4, 5], max_new_tokens=2)
        with pytest.raises(EngineStalled):
            list(fe.stream(h, max_steps=10000))

    def test_progress_resets_the_counter(self):
        eng = make_engine()
        fe = ServingFrontend(eng, stall_after=8)
        hs = run_trace(fe, prompts(6), max_new=12)
        assert all(h.status is RequestStatus.FINISHED for h in hs)
        assert fe.scheduler.zero_progress_steps == 0


# ---------------------------------------------------------------------------
# Request fault isolation
# ---------------------------------------------------------------------------

class TestFaultIsolation:
    def _clean_tokens(self, plist, max_new=6, spec=None):
        fe = ServingFrontend(make_engine(), spec=spec)
        return [h.tokens for h in run_trace(fe, plist, max_new=max_new)]

    def test_prefill_chunk_fault_fails_only_that_request(self):
        """A fault attributed to a lane that is MID-chunked-prefill fails
        only that request — prefill now shares the ragged dispatch with
        the decode lanes, so isolation must hold inside ONE dispatch:
        the decoding survivors roll back, replay, and stay bitwise
        identical to a fault-free run."""
        plist = prompts(3)
        clean = self._clean_tokens(plist)
        fe = ServingFrontend(make_engine(), prefill_chunk_tokens=4)
        hs = [fe.submit(p, max_new_tokens=6) for p in plist]
        for _ in range(4):                 # everyone admitted + decoding
            fe.step()
        victim = fe.submit(list(range(1, 17)), max_new_tokens=6)
        faults.inject("serve.decode", after_n=1, times=1,
                      exc=EngineStepError("decode",
                                          seq_ids=[victim.request_id]))
        fe.run_until_idle(max_steps=500)
        assert victim.status is RequestStatus.FAILED
        assert victim.finish_reason == "engine_fault:decode"
        assert victim.tokens == []         # failed before its 1st token
        for h, ref in zip(hs, clean):
            assert h.status is RequestStatus.FINISHED
            assert h.tokens == ref
        assert monitor.get("serving.isolated_faults") == 1
        assert monitor.get("serving.isolated_faults.decode") == 1
        assert_no_leaks(fe)

    def test_nan_decode_lane_isolated_survivors_bitwise(self):
        plist = prompts(4)
        clean = self._clean_tokens(plist)
        # flag: the scheduler poisons the FIRST live lane's logits row
        faults.inject("serve.decode", after_n=1, times=1, action="flag")
        fe = ServingFrontend(make_engine())
        hs = run_trace(fe, plist)
        failed = [h for h in hs if h.status is RequestStatus.FAILED]
        assert len(failed) == 1
        assert failed[0].finish_reason == "nan_logits"
        survivors = [(h, ref) for h, ref in zip(hs, clean)
                     if h.status is RequestStatus.FINISHED]
        assert len(survivors) == 3
        for h, ref in survivors:
            assert h.tokens == ref          # bitwise parity for survivors
        assert monitor.get("serving.isolated_faults.decode") == 1
        assert_no_leaks(fe)

    def test_targeted_engine_step_error_seq_ids(self):
        plist = prompts(4)
        clean = self._clean_tokens(plist)
        fe = ServingFrontend(make_engine())
        hs = [fe.submit(p, max_new_tokens=6) for p in plist]
        victim = hs[2]
        faults.inject("serve.decode", after_n=1, times=1,
                      exc=EngineStepError("decode",
                                          seq_ids=[victim.request_id]))
        fe.run_until_idle(max_steps=500)
        assert victim.status is RequestStatus.FAILED
        assert victim.finish_reason == "engine_fault:decode"
        for h, ref in zip(hs, clean):
            if h is not victim:
                assert h.status is RequestStatus.FINISHED
                assert h.tokens == ref
        assert_no_leaks(fe)

    def test_transient_decode_fault_replays_everyone(self):
        plist = prompts(4)
        clean = self._clean_tokens(plist)
        faults.inject("serve.decode", after_n=2, times=1)  # InjectedIOError
        fe = ServingFrontend(make_engine())
        hs = run_trace(fe, plist)
        assert all(h.status is RequestStatus.FINISHED for h in hs)
        assert [h.tokens for h in hs] == clean   # the retry is invisible
        assert monitor.get("serving.step_faults") == 1
        assert monitor.get("serving.isolated_faults") == 0
        assert_no_leaks(fe)

    def test_probe_attribution_of_untyped_engine_fault(self):
        """An engine that raises a PLAIN RuntimeError whenever a specific
        sequence's lane is live: per-lane probe replays must convict
        exactly that lane and replay the rest."""
        plist = prompts(4)
        clean = self._clean_tokens(plist)
        inner = make_engine()

        class VictimEngine:
            def __init__(self):
                self.victim = None

            def __getattr__(self, name):
                return getattr(inner, name)

            def _refuse_the_victim(self, tables):
                if self.victim is not None:
                    try:
                        vrow = inner.manager.block_table_array(
                            [self.victim])[0]
                    except KeyError:
                        vrow = None
                    if vrow is not None and any(
                            int(r[0]) == int(vrow[0])
                            for r in np.asarray(tables)):
                        raise RuntimeError("victim lane poisons the step")

            def sampled_step(self, tokens, lanes, tables, temperature):
                self._refuse_the_victim(tables)
                return inner.sampled_step(tokens, lanes, tables,
                                          temperature)

            def ragged_step(self, tokens, q_lens, kv_lens, tables):
                # the probe's way in: the all-rows program
                self._refuse_the_victim(tables)
                return inner.ragged_step(tokens, q_lens, kv_lens, tables)

        eng = VictimEngine()
        fe = ServingFrontend(eng)
        hs = [fe.submit(p, max_new_tokens=6) for p in plist]
        fe.step()                       # admit everyone cleanly first
        eng.victim = hs[1].request_id
        fe.run_until_idle(max_steps=500)
        assert hs[1].status is RequestStatus.FAILED
        assert hs[1].finish_reason == "engine_fault:decode"
        for h, ref in zip(hs, clean):
            if h is not hs[1]:
                assert h.status is RequestStatus.FINISHED
                assert h.tokens == ref
        assert monitor.get("serving.isolated_faults.decode") == 1

    def test_cache_fault_fails_culpable_request_only(self):
        plist = prompts(4)
        faults.inject("serve.cache", after_n=6, times=1)
        fe = ServingFrontend(make_engine())
        hs = run_trace(fe, plist)
        failed = [h for h in hs if h.status is RequestStatus.FAILED]
        assert len(failed) == 1
        assert failed[0].finish_reason == "engine_fault:cache"
        assert sum(h.status is RequestStatus.FINISHED for h in hs) == 3
        assert monitor.get("serving.isolated_faults.cache") == 1
        assert_no_leaks(fe)

    def test_sample_fault_terminal_and_leak_free(self):
        plist = prompts(4)
        clean = self._clean_tokens(plist)
        faults.inject("serve.sample", after_n=5, times=1)
        fe = ServingFrontend(make_engine())
        hs = run_trace(fe, plist)
        assert all(h.status.terminal for h in hs)
        for h, ref in zip(hs, clean):
            if h.status is RequestStatus.FINISHED:
                assert h.tokens == ref
        assert_no_leaks(fe)

    def test_spec_verify_nan_lane_isolated(self):
        plist = [([1, 2, 3] * 4)[:9], ([5, 6] * 5)[:8], prompts(1)[0]]
        clean = self._clean_tokens(plist)   # plain == spec greedy parity
        spec = SpecDecodeConfig(NGramProposer(), num_draft_tokens=3)
        faults.inject("serve.verify", after_n=1, times=1, action="flag")
        fe = ServingFrontend(make_engine(), spec=spec)
        hs = run_trace(fe, plist)
        failed = [h for h in hs if h.status is RequestStatus.FAILED]
        assert len(failed) == 1 and failed[0].finish_reason == "nan_logits"
        for h, ref in zip(hs, clean):
            if h.status is RequestStatus.FINISHED:
                assert h.tokens == ref
        assert monitor.get("serving.isolated_faults.verify") == 1
        assert_no_leaks(fe)

    def test_spec_transient_verify_fault_keeps_parity(self):
        plist = [([1, 2, 3] * 4)[:9], ([5, 6] * 5)[:8], prompts(1)[0]]
        clean = self._clean_tokens(plist)
        spec = SpecDecodeConfig(NGramProposer(), num_draft_tokens=3)
        faults.inject("serve.verify", after_n=1, times=1)
        fe = ServingFrontend(make_engine(), spec=spec)
        hs = run_trace(fe, plist)
        assert all(h.status is RequestStatus.FINISHED for h in hs)
        assert [h.tokens for h in hs] == clean
        assert monitor.get("serving.step_faults") == 1
        assert_no_leaks(fe)


# ---------------------------------------------------------------------------
# Engine watchdog: stall detection + bounded restarts
# ---------------------------------------------------------------------------

class TestWatchdog:
    def test_transient_escalation_restart_recovers_with_parity(self):
        plist = prompts(4)
        clean_fe = ServingFrontend(make_engine())
        clean = [h.tokens for h in run_trace(clean_fe, plist)]
        # 3 consecutive unattributed faults (> step_retries=2) escalate
        # to a restart; the 4th fire lands after the rebuild, then the
        # rule is exhausted and serving resumes
        faults.inject("serve.decode", times=4)
        fe = ServingFrontend(
            make_engine(),
            watchdog=WatchdogConfig(step_retries=2, max_restarts=2),
            engine_factory=make_engine)
        hs = run_trace(fe, plist)
        assert all(h.status is RequestStatus.FINISHED for h in hs)
        # requeued with tokens-so-far intact -> bitwise identical output
        assert [h.tokens for h in hs] == clean
        assert monitor.get("serving.engine_restarts") == 1
        assert fe.scheduler.engine_restarts_remaining == 1
        assert_no_leaks(fe)

    def test_budget_exhaustion_fails_everything_typed(self):
        faults.inject("serve.decode", times=None)   # fires forever
        fe = ServingFrontend(
            make_engine(),
            watchdog=WatchdogConfig(step_retries=1, max_restarts=1),
            engine_factory=make_engine)
        hs = [fe.submit(p, max_new_tokens=6) for p in prompts(4)]
        fe.run_until_idle(max_steps=500)
        assert all(h.status is RequestStatus.FAILED for h in hs)
        assert all(h.finish_reason.startswith("engine_unrecoverable")
                   for h in hs)
        assert monitor.get("serving.engine_restarts") == 1
        assert monitor.get("serving.requests_failed") == 4
        assert_no_leaks(fe)

    def test_zero_progress_restart_recovers_wedged_pool(self):
        eng = make_engine(max_batch=2, num_blocks=8)
        fe = ServingFrontend(
            eng,
            watchdog=WatchdogConfig(stall_steps=8, max_restarts=1),
            engine_factory=lambda: make_engine(max_batch=2, num_blocks=8),
            stall_after=64)
        eng.manager.allocate(999, 7 * 4)    # external tenant wedges pool
        h = fe.submit([1, 2, 3], max_new_tokens=3)
        fe.run_until_idle(max_steps=500)
        # the rebuilt engine owns a fresh pool: the request completes
        assert h.status is RequestStatus.FINISHED
        assert monitor.get("serving.engine_restarts") == 1
        assert monitor.get("serving.stall_detections") >= 1

    def test_step_timeout_stall_detection_injectable_clock(self):
        ticks = [0.0]

        def clock():
            ticks[0] += 40.0
            return ticks[0]

        sch = Scheduler(
            make_engine(),
            watchdog=WatchdogConfig(stall_timeout_s=50.0, max_restarts=1),
            engine_factory=make_engine, clock=clock)
        # every dispatch "takes" 40 s < 50 s: no stall
        r = Request([1, 2, 3], SamplingParams(max_new_tokens=3))
        sch.submit(r)
        for _ in range(20):
            if r.status.terminal:
                break
            sch.step()
        assert r.status is RequestStatus.FINISHED
        assert monitor.get("serving.stall_detections") == 0

        slow = [0.0]

        def slow_clock():
            slow[0] += 80.0
            return slow[0]

        sch2 = Scheduler(
            make_engine(),
            watchdog=WatchdogConfig(stall_timeout_s=50.0, max_restarts=1),
            engine_factory=make_engine, clock=slow_clock)
        r2 = Request([1, 2, 3], SamplingParams(max_new_tokens=3))
        sch2.submit(r2)
        for _ in range(50):
            if r2.status.terminal:
                break
            sch2.step()
        # every dispatch blows the 50 s budget: stalls are detected and
        # the restart budget drains to the typed terminal failure
        assert monitor.get("serving.stall_detections") >= 1
        assert r2.status.terminal
        assert monitor.get("serving.engine_restarts") <= 1

    def test_no_factory_means_typed_failure_not_hang(self):
        faults.inject("serve.decode", times=None)
        fe = ServingFrontend(make_engine(),
                             watchdog=WatchdogConfig(step_retries=1))
        hs = [fe.submit(p, max_new_tokens=4) for p in prompts(3)]
        fe.run_until_idle(max_steps=200)
        assert all(h.status is RequestStatus.FAILED for h in hs)
        assert all(h.finish_reason.startswith("engine_unrecoverable")
                   for h in hs)

    def test_rebuild_failure_fails_typed(self):
        calls = {"n": 0}

        def flaky_factory():
            calls["n"] += 1
            raise RuntimeError("no capacity")

        faults.inject("serve.decode", times=None)
        fe = ServingFrontend(
            make_engine(),
            watchdog=WatchdogConfig(step_retries=1, max_restarts=3,
                                    rebuild_retries=1),
            engine_factory=flaky_factory)
        hs = [fe.submit(p, max_new_tokens=4) for p in prompts(3)]
        fe.run_until_idle(max_steps=200)
        assert all(h.status is RequestStatus.FAILED for h in hs)
        assert all(h.finish_reason.startswith("engine_rebuild_failed")
                   for h in hs)
        assert calls["n"] == 2   # initial attempt + rebuild_retries

    def test_cache_fault_during_rebind_stays_terminal(self):
        # The guard-block re-lease inside the rebuild runs the
        # serve.cache site: a fault there must not escape step() and
        # strand the re-queued requests non-terminal. And since a failed
        # rebind can leave a stale guard-block id over the fresh pool,
        # the scheduler must refuse to serve again (fail-fast typed).
        def factory():
            eng = make_engine()
            # arm the cache site between the factory returning and the
            # guard-block re-lease — the rebind is the very next cache op
            faults.inject("serve.cache", times=None)
            return eng

        faults.inject("serve.decode", times=None)
        fe = ServingFrontend(
            make_engine(),
            watchdog=WatchdogConfig(step_retries=1, max_restarts=3),
            engine_factory=factory)
        hs = [fe.submit(p, max_new_tokens=4) for p in prompts(3)]
        fe.run_until_idle(max_steps=200)   # must not raise
        assert all(h.status is RequestStatus.FAILED for h in hs)
        assert all(h.finish_reason.startswith("engine_rebuild_failed")
                   for h in hs)
        faults.clear()
        late = fe.submit([1, 2, 3], max_new_tokens=2)
        assert late.status is RequestStatus.REJECTED
        assert late.finish_reason.startswith("engine_rebuild_failed")

    def test_slow_and_raising_dispatch_spends_one_restart(self):
        # A dispatch that both blows stall_timeout_s AND raises must
        # burn ONE restart-budget unit, not two (escalation restart +
        # stale pending stall restarting the fresh engine).
        tick = {"d": 80.0, "t": 0.0}

        def clock():
            tick["t"] += tick["d"]
            return tick["t"]

        faults.inject("serve.decode", times=1)
        sch = Scheduler(
            make_engine(),
            watchdog=WatchdogConfig(stall_timeout_s=50.0, step_retries=0,
                                    max_restarts=2),
            engine_factory=make_engine, clock=clock)
        r = Request([1, 2, 3], SamplingParams(max_new_tokens=3))
        sch.submit(r)
        # first step: the prefill dispatch "takes" 80 s (> 50 s, pending
        # stall recorded) AND the decode dispatch raises — one step, two
        # restart triggers, must cost ONE budget unit
        sch.step()
        assert monitor.get("serving.engine_restarts") == 1
        assert sch.engine_restarts_remaining == 1
        tick["d"] = 0.0                 # healthy timing from here on
        for _ in range(30):
            if r.status.terminal:
                break
            sch.step()
        assert r.status is RequestStatus.FINISHED
        assert monitor.get("serving.engine_restarts") == 1

    def test_factory_without_watchdog_gets_default_budget(self):
        # engine_factory alone opts into the default WatchdogConfig —
        # otherwise the budget would be 0 and the factory dead code
        faults.inject("serve.decode", times=8)   # > default step_retries
        fe = ServingFrontend(make_engine(), engine_factory=make_engine)
        hs = run_trace(fe, prompts(3), max_new=4, max_steps=500)
        assert all(h.status is RequestStatus.FINISHED for h in hs)
        assert monitor.get("serving.engine_restarts") >= 1


# ---------------------------------------------------------------------------
# Observability
# ---------------------------------------------------------------------------

class TestFaultMetrics:
    def test_profiler_overload_faults_block(self):
        from paddle_tpu import profiler

        faults.inject("serve.decode", after_n=1, times=1, action="flag")
        eng = make_engine(max_batch=1, num_blocks=32)
        fe = ServingFrontend(eng, admission=AdmissionConfig(queue_high=1,
                                                            queue_low=0))
        prof = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU])
        prof.start()
        fe.submit([1, 2, 3], max_new_tokens=6)
        shed = fe.submit([4, 5], max_new_tokens=2)
        fe.run_until_idle(max_steps=200)
        prof.stop()
        assert shed.status is RequestStatus.SHED
        text = prof.summary()
        assert "overload/faults:" in text
        assert "1 shed" in text and "shed reasons:" in text
        assert "isolated faults" in text

    def test_queued_cost_gauge_tracks_backlog(self):
        eng = make_engine(max_batch=1, num_blocks=32)
        fe = ServingFrontend(eng)
        for _ in range(3):
            fe.submit([1, 2, 3], max_new_tokens=7)
        assert monitor.get("serving.queued_cost") == 21
        assert monitor.get("serving.queued_cost_peak") == 21
        fe.run_until_idle(max_steps=300)
        assert monitor.get("serving.queued_cost") == 0
