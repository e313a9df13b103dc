"""Fleet-scale serving tests (serving/fleet.py + the elastic-layer
satellites it rides on): load-aware/session-affine placement, replica
failure relocation with committed-prefix parity, drain/scale-out
lifecycle, membership fencing, and one-surface aggregation.

Everything runs on the tiny MLP engine with ZERO sleeps; membership time
is injected where it matters.
"""
import numpy as np
import pytest

from paddle_tpu.framework import monitor
from paddle_tpu.resilience import faults
from paddle_tpu.serving import (FleetRouter, MLPLMEngine, RequestStatus,
                                ServingFrontend, ServingMetrics,
                                WatchdogConfig)

VOCAB = 64


def make_engine():
    return MLPLMEngine(vocab_size=VOCAB, hidden=16, max_batch_size=4,
                       num_blocks=48, block_size=4, max_blocks_per_seq=8,
                       seed=0)


def prompts(n=8, seed=0, lo=2, hi=10):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, int(rng.integers(lo, hi))).tolist()
            for _ in range(n)]


@pytest.fixture(autouse=True)
def _clean():
    faults.clear()
    ServingMetrics.reset_monitor()
    monitor.reset_prefix("fleet.")
    monitor.reset_prefix("elastic.")
    yield
    faults.clear()


@pytest.fixture
def router():
    r = FleetRouter(make_engine, num_replicas=3)
    yield r
    r.close()


def reference_tokens(ps, max_new=6):
    """Single-frontend greedy reference: fleet placement must not change
    any request's token stream (identical engine weights per replica)."""
    fe = ServingFrontend(make_engine())
    hs = [fe.submit(p, max_new_tokens=max_new) for p in ps]
    fe.run_until_idle()
    assert all(h.status is RequestStatus.FINISHED for h in hs)
    return [h.tokens for h in hs]


class TestPlacement:
    def test_all_finish_tokens_placement_independent(self, router):
        ps = prompts(10)
        ref = reference_tokens(ps)
        hs = [router.submit(p, max_new_tokens=6) for p in ps]
        router.run_until_idle()
        assert all(h.status is RequestStatus.FINISHED for h in hs)
        assert [h.tokens for h in hs] == ref
        # least-loaded placement spread the burst over every replica
        assert len({h.replica_id for h in hs}) == 3
        assert all(h.num_relocations == 0 for h in hs)

    def test_least_loaded_prefers_empty_replica(self, router):
        # long-running request loads replica A; the next submission must
        # land elsewhere
        a = router.submit(prompts(1)[0], max_new_tokens=30)
        router.step()
        b = router.submit(prompts(1, seed=1)[0], max_new_tokens=2)
        assert b.replica_id != a.replica_id
        router.run_until_idle()

    def test_session_affinity_sticks_and_counts(self, router):
        p = prompts(2, seed=3)
        a = router.submit(p[0], max_new_tokens=3, session_id="alice")
        router.run_until_idle()
        b = router.submit(p[1], max_new_tokens=3, session_id="alice")
        router.run_until_idle()
        assert a.replica_id == b.replica_id
        assert monitor.get("fleet.session_hits") == 1
        # the home replica dying re-maps the session (counted as a miss)
        router.fail_replica(a.replica_id)
        c = router.submit(p[0], max_new_tokens=3, session_id="alice")
        router.run_until_idle()
        assert c.replica_id != a.replica_id
        assert monitor.get("fleet.session_misses") == 1

    def test_handle_surface(self, router):
        h = router.submit(prompts(1)[0], max_new_tokens=3)
        assert h.replica_id in {r.replica_id for r in router.replicas}
        assert h.num_relocations == 0
        assert "FleetHandle" in repr(h)
        router.run_until_idle()
        assert h.finished and h.tokens

    def test_shed_retries_on_second_replica(self):
        from paddle_tpu.serving import AdmissionConfig

        # queue_high=1 on every replica: the first replica sheds once its
        # queue holds a request, and the router must try the next one
        r = FleetRouter(make_engine, num_replicas=2,
                        frontend_kwargs=dict(
                            admission=AdmissionConfig(queue_high=1)))
        try:
            hs = [r.submit(p, max_new_tokens=2) for p in prompts(6)]
            shed = [h for h in hs if h.status is RequestStatus.SHED]
            placed = [h for h in hs if not h.status.terminal]
            # with retry, placements land on BOTH replicas before any shed
            assert len({h.replica_id for h in placed}) == 2
            r.run_until_idle()
            assert all(h.finished for h in hs)
            for h in shed:   # a fleet-shed request tried both replicas
                assert h.status is RequestStatus.SHED
        finally:
            r.close()

    def test_submit_fault_fails_over(self, router):
        # an unreachable first replica must not surface to the caller
        faults.inject("fleet.submit", after_n=0, times=1)
        h = router.submit(prompts(1)[0], max_new_tokens=3)
        assert not h.status.terminal
        assert monitor.get("fleet.submit_faults") == 1
        router.run_until_idle()
        assert h.status is RequestStatus.FINISHED


class TestRelocation:
    def test_kill_mid_decode_committed_prefix_parity(self, router):
        ps = prompts(9, seed=5)
        ref = reference_tokens(ps)
        hs = [router.submit(p, max_new_tokens=6) for p in ps]
        for _ in range(3):
            router.step()
        killed = router.chaos_kill_replica()
        router.run_until_idle()
        assert all(h.status is RequestStatus.FINISHED for h in hs)
        assert [h.tokens for h in hs] == ref       # zero lost/duplicated
        relocated = [h for h in hs if h.num_relocations]
        assert relocated, "kill missed every in-flight request"
        for h in relocated:
            assert h.replica_id != killed
        # survivors leak nothing
        for rep in router.live_replicas:
            assert rep.scheduler.kv_leaked_blocks() == 0
        assert monitor.get("fleet.relocations") == len(relocated)

    def test_relocated_event_on_timeline(self, router):
        from paddle_tpu import observability as obs

        obs.enable()
        try:
            hs = [router.submit(p, max_new_tokens=6) for p in prompts(6)]
            for _ in range(2):
                router.step()
            router.fail_replica(hs[0].replica_id, reason="test")
            router.run_until_idle()
            moved = [h for h in hs if h.num_relocations][0]
            names = [e["name"] for e in moved.timeline()]
            assert "relocated" in names
            ev = [e for e in moved.timeline()
                  if e["name"] == "relocated"][0]
            assert ev["meta"]["reason"].startswith("replica_dead")
            assert ev["meta"]["tokens_carried"] == len(moved._prefix)
            chrome = obs.timeline.chrome_events()
            assert any(e.get("name") == "relocated" for e in chrome)
        finally:
            obs.disable()

    def test_relocation_budget_exhausted_fails_typed(self):
        r = FleetRouter(make_engine, num_replicas=2, relocation_budget=0)
        try:
            hs = [r.submit(p, max_new_tokens=8) for p in prompts(6)]
            for _ in range(2):
                r.step()
            dead = hs[0].replica_id
            r.fail_replica(dead)
            r.run_until_idle()
            assert all(h.finished for h in hs)
            failed = [h for h in hs
                      if h.status is RequestStatus.FAILED]
            assert failed and all(
                h.finish_reason == "relocation_budget_exhausted"
                for h in failed)
            # requests that were NOT on the dead replica finished
            assert any(h.status is RequestStatus.FINISHED for h in hs)
        finally:
            r.close()

    def test_fully_committed_request_finishes_on_relocation(self, router):
        # a request whose last token committed right before the kill has
        # nothing left to decode: the relocation IS the finish
        h = router.submit(prompts(1)[0], max_new_tokens=1)
        hs = [router.submit(p, max_new_tokens=12) for p in prompts(5)]
        while not h._req.generated:
            router.step()
        router.fail_replica(h.replica_id)
        assert h.status is RequestStatus.FINISHED
        assert h.finish_reason == "max_new_tokens"
        assert len(h.tokens) == 1
        router.run_until_idle()
        assert all(x.finished for x in hs)

    def test_last_replica_death_fails_typed(self):
        r = FleetRouter(make_engine, num_replicas=1)
        try:
            hs = [r.submit(p, max_new_tokens=8) for p in prompts(4)]
            r.step()
            r.fail_replica(hs[0].replica_id)
            assert all(h.status is RequestStatus.FAILED for h in hs)
            assert all(h.finish_reason == "no_replica_available"
                       for h in hs)
            # scale-out recovers the fleet
            r.add_replica()
            h2 = r.submit(prompts(1)[0], max_new_tokens=3)
            r.run_until_idle()
            assert h2.status is RequestStatus.FINISHED
        finally:
            r.close()

    def test_unrecoverable_replica_escalates_to_relocation(self):
        # one replica's engine lineage is permanently poisoned with
        # TRANSIENT-shaped faults (InjectedFault skips the per-lane
        # probe, so no lane is culpable): its watchdog budget exhausts,
        # requests fail typed `engine_unrecoverable:*`, and the router
        # must escalate — declare the replica dead and let the FLEET
        # finish the work the replica could not
        class BadEngine:
            def __init__(self):
                self._inner = make_engine()

            def __getattr__(self, name):
                return getattr(self._inner, name)

            def sampled_step(self, *a):
                raise faults.InjectedIOError("poisoned engine")

        r = FleetRouter(
            BadEngine, num_replicas=1,
            frontend_kwargs=dict(watchdog=WatchdogConfig(
                step_retries=1, max_restarts=1, stall_steps=8)))
        try:
            r.add_replica(make_engine)   # healthy second replica
            hs = [r.submit(p, max_new_tokens=4) for p in prompts(6)]
            r.run_until_idle(max_steps=3000)
            assert all(h.status is RequestStatus.FINISHED for h in hs)
            assert monitor.get("fleet.replica_deaths") >= 1
            sick = r.replicas[0]
            assert not sick.alive
            assert sick.death_reason == "engine_unrecoverable"
        finally:
            r.close()


class TestDrainScaleOut:
    def test_drain_relocates_then_deregisters(self, router):
        ps = prompts(8)
        ref = reference_tokens(ps)
        hs = [router.submit(p, max_new_tokens=6) for p in ps]
        for _ in range(2):
            router.step()
        victim = hs[0].replica_id
        router.drain_replica(victim)
        # a draining replica takes no new placements
        h2 = router.submit(prompts(1, seed=9)[0], max_new_tokens=2)
        assert h2.replica_id != victim
        router.run_until_idle()
        rep = router._rep(victim)
        assert not rep.alive and rep.death_reason == "drained"
        assert victim not in router.store.alive()
        assert all(h.status is RequestStatus.FINISHED for h in hs)
        assert [h.tokens for h in hs] == ref
        assert monitor.get("fleet.drained") == 1

    def test_drain_finish_in_place(self, router):
        hs = [router.submit(p, max_new_tokens=4) for p in prompts(6)]
        for _ in range(1):
            router.step()
        victim = hs[0].replica_id
        router.drain_replica(victim, relocate=False)
        router.run_until_idle()
        assert all(h.status is RequestStatus.FINISHED for h in hs)
        # nothing moved: the draining replica finished its own work
        assert all(h.num_relocations == 0 for h in hs)
        assert not router._rep(victim).alive

    def test_drain_sole_replica_finishes_in_place(self):
        # draining the ONLY replica must not lose admitted work to
        # no_replica_available: with no survivor placeable, relocation
        # falls back to the still-live draining source
        r = FleetRouter(make_engine, num_replicas=1)
        try:
            hs = [r.submit(p, max_new_tokens=5) for p in prompts(4)]
            r.step()
            r.drain_replica(hs[0].replica_id)
            r.run_until_idle()
            assert all(h.status is RequestStatus.FINISHED for h in hs)
            assert not r.replicas[0].alive
            assert r.replicas[0].death_reason == "drained"
        finally:
            r.close()

    def test_default_timeout_applies_to_fleet_submits(self, router):
        router.frontend_kwargs["default_timeout_s"] = 30.0
        h = router.submit(prompts(1)[0], max_new_tokens=2)
        assert h._req.deadline is not None
        h2 = router.submit(prompts(1)[0], max_new_tokens=2,
                           timeout_s=5.0)
        assert h2._req.deadline < h._req.deadline
        router.run_until_idle()

    def test_session_map_bounded(self, router, monkeypatch):
        from paddle_tpu.serving import fleet as fleet_mod

        monkeypatch.setattr(fleet_mod, "_SESSION_CAP", 4)
        for i in range(10):
            router.submit(prompts(1)[0], max_new_tokens=1,
                          session_id=f"s{i}")
        assert len(router._sessions) <= 4
        assert "s9" in router._sessions      # newest survives (LRU)
        router.run_until_idle()

    def test_add_replica_joins_membership_and_serves(self, router):
        rid = router.add_replica()
        assert rid in router.store.alive()
        assert monitor.get("fleet.replicas_added") == 1
        # load the original three so the newcomer wins placement
        busy = [router.submit(p, max_new_tokens=20) for p in prompts(3)]
        router.step()
        h = router.submit(prompts(1, seed=4)[0], max_new_tokens=2)
        assert h.replica_id == rid
        router.run_until_idle()
        assert h.status is RequestStatus.FINISHED
        for b in busy:
            assert b.finished


class TestMembership:
    def test_heartbeats_carry_load_payload(self):
        r = FleetRouter(make_engine, num_replicas=2, heartbeat_every=1)
        try:
            hs = [r.submit(p, max_new_tokens=4) for p in prompts(6)]
            r.step()
            pods = r.store.alive()
            assert len(pods) == 2
            for entry in pods.values():
                assert entry["incarnation"] >= 1
                pl = entry["payload"]
                assert {"queue_depth", "running", "queued_cost",
                        "kv_utilization", "tokens_generated",
                        "prefix_hit_rate"} <= set(pl)
                # prefix caching off on these replicas: rate reports 0.0
                assert pl["prefix_hit_rate"] == 0.0
            r.run_until_idle()
            assert all(h.finished for h in hs)
        finally:
            r.close()

    def test_heartbeat_payload_reports_replica_prefix_hit_rate(self):
        """Session-affine dispatch evidence (ISSUE 12): the replica
        holding a session's radix path reports its OWN hit rate in the
        heartbeat payload; dispatch keeps landing the session there
        (advisory — a dead home falls back to least-loaded exactly as
        before, covered by the relocation tests)."""
        r = FleetRouter(make_engine, num_replicas=2, heartbeat_every=1,
                        frontend_kwargs={"prefix_cache": True})
        try:
            rng = np.random.default_rng(21)
            prompt = rng.integers(1, VOCAB, 12).tolist()
            h1 = r.submit(prompt, max_new_tokens=3, session_id="s1")
            r.run_until_idle()
            home = h1.replica_id
            # turn 2 of the session: lands on the home replica and HITS
            h2 = r.submit(prompt, max_new_tokens=3, session_id="s1")
            assert h2.replica_id == home
            r.run_until_idle()
            assert h2.status is RequestStatus.FINISHED
            r.step()                       # heartbeat_every=1: publish
            pods = r.store.alive()
            rates = {rid: e["payload"]["prefix_hit_rate"]
                     for rid, e in pods.items()}
            assert rates[home] > 0.0
            others = [v for k, v in rates.items() if k != home]
            assert all(v == 0.0 for v in others), rates
            snaps = r.replica_snapshots()
            assert any(s["fleet.prefix_hit_rate_pct"] > 0 for s in snaps)
        finally:
            r.close()

    def test_reaped_replica_relocates_work(self):
        wall = [1000.0]
        r = FleetRouter(make_engine, num_replicas=2, sweep_every=1,
                        wall_clock=lambda: wall[0])
        try:
            hs = [r.submit(p, max_new_tokens=6) for p in prompts(6)]
            r.step()
            # operator deregisters replica-0 out from under the router
            r.store.deregister(r.replicas[0].replica_id)
            lost = r.sweep_membership()
            assert lost == [r.replicas[0].replica_id]
            assert not r.replicas[0].alive
            r.run_until_idle()
            assert all(h.finished for h in hs)
            assert all(h.status is RequestStatus.FINISHED for h in hs)
        finally:
            r.close()

    def test_superseded_lease_fences_replica(self):
        r = FleetRouter(make_engine, num_replicas=2, heartbeat_every=1)
        try:
            hs = [r.submit(p, max_new_tokens=6) for p in prompts(6)]
            r.step()
            rid = r.replicas[0].replica_id
            # a NEWER incarnation registers under the same pod id (a
            # replacement claimed the slot): the old replica's next
            # heartbeat is stale and it must fence itself
            r.store.register(rid)
            r.step()
            assert not r.replicas[0].alive
            assert r.replicas[0].death_reason == "lease_lost"
            assert monitor.get("elastic.stale_heartbeats") >= 1
            r.run_until_idle()
            assert all(h.finished for h in hs)
        finally:
            r.close()


class TestOneSurface:
    def test_fleet_summary_aggregates_replicas(self, router):
        hs = [router.submit(p, max_new_tokens=4) for p in prompts(8)]
        router.run_until_idle()
        fs = router.fleet_summary()
        assert fs["replicas"] == 3 and fs["alive"] == 3
        total = sum(len(h.tokens) for h in hs)
        assert fs["aggregate"]["fleet.tokens_generated"] == total
        assert fs["straggler_replica"] in {r.replica_id
                                           for r in router.replicas}
        assert fs["counters"]["fleet.submitted"] == 8

    def test_dead_replica_reports_history_not_load(self, router):
        hs = [router.submit(p, max_new_tokens=6) for p in prompts(6)]
        for _ in range(2):
            router.step()
        router.fail_replica(hs[0].replica_id)
        router.run_until_idle()
        snaps = router.replica_snapshots()
        dead_idx = next(i for i, rep in enumerate(router.replicas)
                        if not rep.alive)
        dead = snaps[dead_idx]
        assert dead["fleet.alive"] == 0
        assert dead["fleet.running"] == 0 and dead["fleet.queue_depth"] == 0
        assert dead["fleet.tokens_generated"] >= 0

    def test_profiler_fleet_section(self, router):
        hs = [router.submit(p, max_new_tokens=3) for p in prompts(4)]
        router.run_until_idle()
        assert all(h.finished for h in hs)
        from paddle_tpu.profiler import Profiler

        lines = Profiler._fleet_summary_lines()
        assert lines and any("Fleet: 3/3 replicas alive" in ln
                             for ln in lines)

    def test_parallel_step_mode_parity(self):
        ps = prompts(8, seed=11)
        ref = reference_tokens(ps)
        r = FleetRouter(make_engine, num_replicas=2, parallel=True)
        try:
            hs = [r.submit(p, max_new_tokens=6) for p in ps]
            r.run_until_idle()
            assert [h.tokens for h in hs] == ref
        finally:
            r.close()
