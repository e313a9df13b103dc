"""Disaggregated prefill/decode serving (serving/disagg.py, ISSUE 17):
role-specialized tiers, the handoff pump, KV-shipping relocation, and
every typed failure edge — all with BITWISE greedy parity against the
colocated single-frontend reference.

Everything runs on the tiny MLP engine with zero sleeps; chaos is
injected through `resilience.faults` so every run replays identically.
"""
import numpy as np
import pytest

from paddle_tpu.framework import monitor
from paddle_tpu.resilience import faults
from paddle_tpu.serving import (DisaggRouter, FleetRouter, HandoffState,
                                MLPLMEngine, NGramProposer, RequestStatus,
                                ServingFrontend, ServingMetrics,
                                SpecDecodeConfig)

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
    yield
    faults.clear()


def reference_tokens(ps, max_new=6):
    fe = ServingFrontend(make_engine())
    hs = [fe.submit(p, max_new_tokens=max_new) for p in ps]
    fe.run_until_idle()
    assert all(h.status is RequestStatus.FINISHED for h in hs)
    return [h.tokens for h in hs]


def disagg(num_prefill=2, num_decode=2, **kw):
    return DisaggRouter(make_engine, num_prefill=num_prefill,
                        num_decode=num_decode, **kw)


class TestTiers:
    def test_roles_and_tiers_surface(self):
        r = disagg(num_prefill=2, num_decode=1, num_mixed=1)
        try:
            s = r.fleet_summary()
            assert len(s["tiers"]["prefill"]) == 2
            assert len(s["tiers"]["decode"]) == 1
            assert len(s["tiers"]["mixed"]) == 1
            assert sorted(s["roles"].values()) == [
                "decode", "mixed", "mixed", "prefill"] or \
                sorted(s["roles"].values()) == [
                    "decode", "mixed", "prefill", "prefill"]
            roles = [rep.role for rep in r.replicas]
            assert roles.count("prefill") == 2
            assert roles.count("decode") == 1
        finally:
            r.close()

    def test_config_validation(self):
        with pytest.raises(ValueError):
            DisaggRouter(make_engine, num_prefill=0, num_decode=0,
                         num_mixed=0)
        with pytest.raises(ValueError):
            DisaggRouter(make_engine, roles=["mixed"])
        with pytest.raises(ValueError):
            FleetRouter(make_engine, num_replicas=2,
                        roles=["prefill", "typo"])
        with pytest.raises(ValueError):
            FleetRouter(make_engine, num_replicas=2, roles=["prefill"])

    def test_fresh_prompts_land_on_prefill_tier(self):
        r = disagg()
        try:
            tier = set(r.fleet_summary()["tiers"]["prefill"])
            hs = [r.submit(p, max_new_tokens=4) for p in prompts(6)]
            assert all(h.replica_id in tier for h in hs)
            assert all(r.handoff_state(h) is HandoffState.PREFILLING
                       for h in hs)
            r.run_until_idle()
        finally:
            r.close()

    def test_mixed_only_disagg_is_the_colocated_fleet(self):
        ps = prompts(5)
        ref = reference_tokens(ps)
        r = disagg(num_prefill=0, num_decode=0, num_mixed=2)
        try:
            hs = [r.submit(p, max_new_tokens=6) for p in ps]
            r.run_until_idle()
            assert [h.tokens for h in hs] == ref
            assert monitor.get("fleet.handoffs") == 0
        finally:
            r.close()


class TestHandoff:
    def test_bitwise_vs_colocated_and_ownership(self):
        ps = prompts(8)
        ref = reference_tokens(ps)
        r = disagg()
        try:
            decode_tier = set(r.fleet_summary()["tiers"]["decode"])
            hs = [r.submit(p, max_new_tokens=6) for p in ps]
            r.run_until_idle()
            assert all(h.status is RequestStatus.FINISHED for h in hs)
            # the streams are BITWISE the colocated reference
            assert [h.tokens for h in hs] == ref
            # every session moved: finished on the decode tier, clean
            assert all(h.replica_id in decode_tier for h in hs)
            assert all(r.handoff_state(h) is HandoffState.DECODING
                       for h in hs)
            assert monitor.get("fleet.handoffs") == len(ps)
            assert monitor.get("fleet.handoff_fallbacks") == 0
            assert monitor.get("fleet.kv_import_failures") == 0
            # handoffs are routing, not failure: no relocation consumed
            assert all(h.num_relocations == 0 for h in hs)
            for rep in r.replicas:
                assert rep.scheduler.kv_leaked_blocks() == 0
        finally:
            r.close()

    def test_handoff_metrics_and_bytes(self):
        r = disagg(num_prefill=1, num_decode=1)
        try:
            hs = [r.submit(p, max_new_tokens=4) for p in prompts(4)]
            r.run_until_idle()
            assert all(h.finished for h in hs)
            n = monitor.get("serving.handoff.count")
            assert n == monitor.get("fleet.handoffs") == 4
            assert monitor.get("serving.handoff.bytes") > 0
            assert monitor.get("serving.handoff.wall_ms") >= 0.0
            snap = monitor.snapshot("serving.handoff.")
            assert snap["serving.handoff.latency_seconds_count"] == 4
        finally:
            r.close()

    def test_zero_steady_state_retraces_both_tiers(self):
        ps = prompts(6, seed=7)
        r = disagg()
        try:
            hs = [r.submit(p, max_new_tokens=5) for p in ps]
            r.run_until_idle()
            assert all(h.finished for h in hs)
            dec = monitor.get("serving.decode_retraces")
            # a second identical burst: every executable (the ragged
            # step, KV gather, KV scatter) is already compiled on
            # BOTH tiers — zero retraces anywhere
            hs = [r.submit(p, max_new_tokens=5) for p in ps]
            r.run_until_idle()
            assert all(h.finished for h in hs)
            assert monitor.get("serving.decode_retraces") == dec
            assert monitor.get("fleet.handoffs") == 2 * len(ps)
        finally:
            r.close()

    def test_single_token_requests_finish_without_handoff_harm(self):
        ps = prompts(4, seed=2)
        ref = reference_tokens(ps, max_new=1)
        r = disagg()
        try:
            hs = [r.submit(p, max_new_tokens=1) for p in ps]
            r.run_until_idle()
            assert all(h.status is RequestStatus.FINISHED for h in hs)
            assert [h.tokens for h in hs] == ref
        finally:
            r.close()

    def test_spec_decode_parity_on_handed_off_sessions(self):
        ps = prompts(6, seed=5)
        ref = reference_tokens(ps, max_new=8)
        r = disagg(frontend_kwargs=dict(
            spec=SpecDecodeConfig(NGramProposer(), num_draft_tokens=3)))
        try:
            hs = [r.submit(p, max_new_tokens=8) for p in ps]
            r.run_until_idle()
            assert all(h.status is RequestStatus.FINISHED for h in hs)
            # spec-on-decode-tier == plain: the handed-off KV feeds the
            # verify pass exactly as locally-prefilled KV would
            assert [h.tokens for h in hs] == ref
            assert monitor.get("fleet.handoffs") >= 1
        finally:
            r.close()


class TestChaosEdges:
    def test_extraction_fault_falls_back_to_fold(self):
        ps = prompts(5, seed=11)
        ref = reference_tokens(ps)
        faults.inject("fleet.handoff", after_n=1, times=1, action="raise")
        r = disagg()
        try:
            hs = [r.submit(p, max_new_tokens=6) for p in ps]
            r.run_until_idle()
            assert all(h.status is RequestStatus.FINISHED for h in hs)
            assert [h.tokens for h in hs] == ref
            assert monitor.get("fleet.handoff_faults") == 1
            assert monitor.get("fleet.handoff_fallbacks") == 1
            # the fallen-back session consumed relocation budget (it
            # re-prefilled); clean handoffs did not
            assert sum(h.num_relocations for h in hs) == 1
            for rep in r.replicas:
                assert rep.scheduler.kv_leaked_blocks() == 0
        finally:
            r.close()

    def test_prefill_worker_killed_mid_handoff(self):
        ps = prompts(8, seed=13)
        ref = {tuple(p): t for p, t in zip(ps, reference_tokens(ps))}
        faults.inject("fleet.handoff", after_n=2, times=1, action="flag")
        r = disagg()
        try:
            hs = [r.submit(p, max_new_tokens=6) for p in ps]
            r.run_until_idle()
            # zero lost: every request reached a terminal state
            assert all(h.status.terminal for h in hs)
            dead = [rep for rep in r.replicas if not rep.alive]
            assert len(dead) == 1
            assert dead[0].role == "prefill"
            assert dead[0].death_reason == "handoff_chaos_kill"
            # bitwise parity for everything that finished — including
            # the fold-relocated victims of the crash
            for p, h in zip(ps, hs):
                if h.status is RequestStatus.FINISHED:
                    assert h.tokens == ref[tuple(p)]
            assert sum(1 for h in hs
                       if h.status is RequestStatus.FINISHED) >= len(ps) - 1
            for rep in r.replicas:
                if rep.alive:
                    assert rep.scheduler.kv_leaked_blocks() == 0
        finally:
            r.close()

    def test_budget_zero_fault_terminalizes_typed(self):
        faults.inject("fleet.handoff", after_n=0, times=None,
                      action="raise")
        r = disagg(num_prefill=1, num_decode=1, relocation_budget=0)
        try:
            h = r.submit(prompts(1)[0], max_new_tokens=6)
            r.run_until_idle()
            assert h.status is RequestStatus.FAILED
            assert h.finish_reason == "relocation_budget_exhausted"
            for rep in r.replicas:
                assert rep.scheduler.kv_leaked_blocks() == 0
        finally:
            r.close()


class TestRelocationShipsKV:
    """Satellite: PR 10's relocation upgraded — a live source ships the
    committed KV blocks (no re-prefill); a dead source folds. Both paths
    continue the stream bitwise."""

    def _run_until_decoding(self, r, h, min_tokens=2):
        for _ in range(200):
            if len(h._req.generated) >= min_tokens:
                return
            r.step()
        raise AssertionError("request never reached decode")

    def test_drain_ships_kv_no_reprefill(self):
        ps = prompts(1, seed=21, lo=6, hi=10)
        ref = reference_tokens(ps, max_new=12)
        r = FleetRouter(make_engine, num_replicas=2)
        try:
            h = r.submit(ps[0], max_new_tokens=12)
            self._run_until_decoding(r, h)
            prefills0 = monitor.get("serving.prefills")
            r.drain_replica(h.replica_id)
            r.run_until_idle()
            assert h.status is RequestStatus.FINISHED
            assert h.tokens == ref[0]
            assert h.num_relocations == 1
            assert monitor.get("fleet.relocations_shipped") == 1
            assert monitor.get("fleet.shipped_kv_bytes") > 0
            # shipped == the stream CONTINUED: no second prefill ran
            assert monitor.get("serving.prefills") == prefills0
            for rep in r.replicas:
                if rep.alive:
                    assert rep.scheduler.kv_leaked_blocks() == 0
        finally:
            r.close()

    def test_kill_folds_and_reprefills_bitwise(self):
        ps = prompts(1, seed=22, lo=6, hi=10)
        ref = reference_tokens(ps, max_new=12)
        r = FleetRouter(make_engine, num_replicas=2)
        try:
            h = r.submit(ps[0], max_new_tokens=12)
            self._run_until_decoding(r, h)
            r.fail_replica(h.replica_id, reason="test_kill")
            r.run_until_idle()
            assert h.status is RequestStatus.FINISHED
            # the dead pool was unreachable: committed-prefix fold, then
            # re-prefill on the survivor — still bitwise
            assert h.tokens == ref[0]
            assert monitor.get("fleet.relocations_shipped") == 0
            assert monitor.get("fleet.shipped_kv_bytes") == 0
        finally:
            r.close()


class TestResidentKVLifecycle:
    """A migrated session waiting in the target queue holds REAL blocks
    (`_kv_resident`); every exit path must free them."""

    def _minted(self):
        fe1 = ServingFrontend(make_engine(), stall_after=256)
        h = fe1.submit(prompts(1, seed=31, lo=5, hi=8)[0],
                       max_new_tokens=10)
        req = h._req
        while len(req.generated) < 2:
            fe1.step()
        # reading a running sequence's KV from outside a round: the round
        # in flight is committed first (`Scheduler.settle`)
        fe1.scheduler.settle()
        payload = fe1.scheduler.engine.extract_kv_blocks(req.seq_id)
        fe1.release(h)
        return req, payload

    def test_release_while_waiting_frees_blocks(self):
        req, payload = self._minted()
        fe2 = ServingFrontend(make_engine(), stall_after=256)
        free0 = fe2.scheduler.engine.manager.free_blocks
        fe2.import_session(req, payload)
        assert fe2.scheduler.engine.manager.free_blocks < free0
        assert fe2.release(req)
        assert fe2.scheduler.engine.manager.free_blocks == free0
        assert fe2.scheduler.kv_leaked_blocks() == 0
        fe2.scheduler.engine.manager.check_consistency()

    def test_imported_session_runs_to_finish_leak_free(self):
        req, payload = self._minted()
        fe2 = ServingFrontend(make_engine(), stall_after=256)
        free0 = fe2.scheduler.engine.manager.free_blocks
        fe2.import_session(req, payload)
        fe2.run_until_idle()
        assert req.status is RequestStatus.FINISHED
        assert fe2.scheduler.engine.manager.free_blocks == free0
        assert fe2.scheduler.kv_leaked_blocks() == 0


class TestCrossReplicaPrefixStream:
    """Tentpole sub-item 3b: a radix-cached shared prefix prefilled on
    one replica streams to a peer on its admission-time first miss —
    the SAME migration payload as a handoff, published into the peer's
    tree, with bitwise greedy parity and cold-prefill fallback on every
    failure."""

    PROMPT = list(range(1, 13))     # 3 full blocks on the bs=4 engine

    def _router(self, n=2, **kw):
        kw.setdefault("frontend_kwargs", dict(prefix_cache=True))
        return FleetRouter(make_engine, n, **kw)

    def test_first_miss_streams_and_matches_bitwise(self):
        with self._router() as r:
            h1 = r.submit(self.PROMPT, max_new_tokens=6)
            r.run_until_idle()
            assert h1._replica.replica_id == "replica-0"
            # occupy the publisher so least-loaded placement sends the
            # sharing request to the cold peer
            busy = r.submit(list(range(20, 28)), max_new_tokens=40)
            h2 = r.submit(self.PROMPT, max_new_tokens=6)
            assert h2._replica.replica_id == "replica-1"
            r.run_until_idle()
            assert busy.status is RequestStatus.FINISHED
            assert h2.status is RequestStatus.FINISHED
            assert h2.tokens == h1.tokens
            assert monitor.get("fleet.prefix_streams") == 1
            assert monitor.get("fleet.prefix_stream_tokens") == 12
            assert monitor.get("fleet.prefix_stream_bytes") > 0
            assert monitor.get("fleet.prefix_stream_failures") == 0
            # the peer's tree now serves the prefix locally: a third
            # same-prefix request on it streams nothing new
            h3 = r.submit(self.PROMPT, max_new_tokens=6)
            r.run_until_idle()
            assert h3.tokens == h1.tokens
            assert monitor.get("fleet.prefix_streams") == 1
            for rep in r.replicas:
                assert rep.frontend.scheduler.kv_leaked_blocks() == 0
                rep.frontend.scheduler.engine.manager.check_consistency()

    def test_stream_failure_falls_back_to_cold_prefill(self):
        [ref] = reference_tokens([self.PROMPT])
        with self._router(n=1) as r:
            # the only peer is geometry-mismatched: its bs=4 exports
            # cannot inject into the bs=8 joiner
            r.add_replica(lambda: MLPLMEngine(
                vocab_size=VOCAB, hidden=16, max_batch_size=4,
                num_blocks=48, block_size=8, max_blocks_per_seq=8,
                seed=0))
            h1 = r.submit(self.PROMPT, max_new_tokens=6)
            r.run_until_idle()   # published on the bs=4 replica
            assert h1._replica.replica_id == "replica-0"
            busy = r.submit(list(range(20, 28)), max_new_tokens=40)
            h2 = r.submit(self.PROMPT, max_new_tokens=6)
            assert h2._replica.replica_id == "replica-1"
            r.run_until_idle()
            # the stream failed typed, was counted, and the request
            # still finished bitwise through a cold prefill (identical
            # seed-derived weights; block size never changes tokens)
            assert monitor.get("fleet.prefix_stream_failures") == 1
            assert monitor.get("fleet.prefix_streams") == 0
            assert h2.status is RequestStatus.FINISHED
            assert h2.tokens == ref
            assert h2.tokens == h1.tokens

    def test_parallel_and_opt_out_leave_hook_unset(self):
        with self._router(parallel=True) as r:
            assert all(rep.frontend.scheduler.prefix_stream_hook is None
                       for rep in r.replicas)
        with self._router(prefix_streaming=False) as r:
            assert all(rep.frontend.scheduler.prefix_stream_hook is None
                       for rep in r.replicas)
        # cache off -> nothing to wire, and serving still works
        with FleetRouter(make_engine, 2) as r:
            assert all(rep.frontend.scheduler.prefix_stream_hook is None
                       for rep in r.replicas)
            h = r.submit(self.PROMPT, max_new_tokens=4)
            r.run_until_idle()
            assert h.status is RequestStatus.FINISHED

    def test_disagg_prefill_tier_streams_prefixes(self):
        """In the disaggregated router the prefill tier shares prefixes
        too: the second same-prefix request lands on the OTHER prefill
        replica and pulls the first's cached blocks instead of
        re-prefilling."""
        with disagg(frontend_kwargs=dict(prefix_cache=True)) as r:
            h1 = r.submit(self.PROMPT, max_new_tokens=6)
            prefill_1 = h1._replica
            r.run_until_idle()
            busy = r.submit(list(range(20, 28)), max_new_tokens=40)
            h2 = r.submit(self.PROMPT, max_new_tokens=6)
            assert h2._replica is not prefill_1
            r.run_until_idle()
            assert h2.status is RequestStatus.FINISHED
            assert h2.tokens == h1.tokens
            assert monitor.get("fleet.prefix_streams") >= 1
            assert monitor.get("fleet.prefix_stream_failures") == 0
