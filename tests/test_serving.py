"""Serving subsystem tests: continuous-batching scheduler admission /
eviction / preemption, steady-state zero-recompile decode (the
`test_lazy_eager.py` compile-counter pattern applied to the serving
retrace counters), timeout/cancel paths, 2-model `EngineCore` genericity
(Llama + MLP-LM through the SAME scheduler assertions), and the
`Config.enable_profile` predictor wiring.
"""
import itertools

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework import monitor
from paddle_tpu.inference import (KVCacheExhausted, LlamaInferenceEngine,
                                  SequenceTooLong)
from paddle_tpu.inference.cache import BlockCacheManager
from paddle_tpu.ops.sampling import sample_tokens
from paddle_tpu.serving import (DraftEngineProposer, MLPLMEngine,
                                NGramProposer, RequestStatus, ServingFrontend,
                                ServingMetrics, SpecDecodeConfig)

VOCAB = 64


def make_mlp_engine(max_batch=4, num_blocks=48, block_size=4,
                    max_blocks_per_seq=8):
    return MLPLMEngine(vocab_size=VOCAB, hidden=16, max_batch_size=max_batch,
                       num_blocks=num_blocks, block_size=block_size,
                       max_blocks_per_seq=max_blocks_per_seq)


@pytest.fixture(scope="module")
def llama_model():
    from paddle_tpu.models import llama_tiny

    m = llama_tiny(vocab=VOCAB, layers=2, hidden=32, heads=2, seq=64)
    m.eval()
    return m


@pytest.fixture(autouse=True)
def _fresh_serving_counters():
    ServingMetrics.reset_monitor()
    yield


@pytest.fixture(params=["mlp", "llama"])
def engine(request, llama_model):
    """The 2-model genericity axis: every test taking `engine` runs the
    identical scheduler assertions over both EngineCore implementations."""
    if request.param == "mlp":
        return make_mlp_engine()
    return LlamaInferenceEngine(llama_model, max_batch_size=4, num_blocks=48,
                                block_size=4, max_blocks_per_seq=8)


@pytest.fixture(params=["mlp", "llama"])
def engine_factory(request, llama_model):
    """Builds engines with IDENTICAL weights on every call (MLP params are
    seed-deterministic; llama reuses the module-scoped model) — the
    speculative parity tests compare a plain and a spec run over two
    fresh engines of the same model."""
    if request.param == "mlp":
        return make_mlp_engine

    def make():
        return LlamaInferenceEngine(llama_model, max_batch_size=4,
                                    num_blocks=48, block_size=4,
                                    max_blocks_per_seq=8)

    return make


def prompts(n, rng=None, lo=2, hi=12):
    rng = rng or np.random.default_rng(0)
    return [rng.integers(1, VOCAB, rng.integers(lo, hi)).tolist()
            for _ in range(n)]


# ---------------------------------------------------------------------------
# BlockCacheManager satellites: typed exhaustion, utilization, trim
# ---------------------------------------------------------------------------

class TestCacheManager:
    def test_typed_pool_exhaustion(self):
        mgr = BlockCacheManager(num_blocks=4, block_size=4,
                                max_blocks_per_seq=4)
        mgr.allocate(0, 12)   # 3 blocks
        with pytest.raises(KVCacheExhausted) as ei:
            mgr.allocate(1, 8)  # needs 2, only 1 free
        assert ei.value.need == 2 and ei.value.free == 1
        assert isinstance(ei.value, RuntimeError)  # legacy compat
        # recoverable: freeing makes the same allocation succeed
        mgr.free(0)
        assert mgr.allocate(1, 8)

    def test_typed_sequence_too_long(self):
        mgr = BlockCacheManager(num_blocks=16, block_size=4,
                                max_blocks_per_seq=2)
        with pytest.raises(SequenceTooLong):
            mgr.allocate(0, 9)
        assert isinstance(SequenceTooLong(3, 2), ValueError)  # legacy compat

    def test_append_token_no_partial_state_on_exhaustion(self):
        mgr = BlockCacheManager(num_blocks=1, block_size=2,
                                max_blocks_per_seq=4)
        mgr.allocate(0, 2)
        with pytest.raises(KVCacheExhausted):
            mgr.append_token(0)
        assert mgr.seq_len(0) == 2  # length NOT bumped by the failed append

    def test_append_tokens_crosses_block_boundary(self):
        mgr = BlockCacheManager(num_blocks=8, block_size=4,
                                max_blocks_per_seq=8)
        mgr.allocate(0, 3)                   # 1 block, 1 slot headroom
        free0 = mgr.free_blocks
        mgr.append_tokens(0, 6)              # 3 -> 9 tokens: crosses into
        assert mgr.seq_len(0) == 9           # blocks 2 AND 3 in one call
        assert mgr.free_blocks == free0 - 2
        assert len(mgr._tables[0]) == 3
        mgr.append_tokens(0, 0)              # n=0 is a no-op
        assert mgr.seq_len(0) == 9 and mgr.free_blocks == free0 - 2
        with pytest.raises(ValueError):
            mgr.append_tokens(0, -1)

    def test_append_tokens_all_or_nothing(self):
        mgr = BlockCacheManager(num_blocks=3, block_size=4,
                                max_blocks_per_seq=8)
        mgr.allocate(0, 4)                   # 1 block used, 2 free
        with pytest.raises(KVCacheExhausted) as ei:
            mgr.append_tokens(0, 12)         # needs 3 more blocks, 2 free
        assert ei.value.need == 3 and ei.value.free == 2
        # neither the length nor the table moved: retry with a smaller n
        # (the scheduler's drop-the-drafts degrade path) succeeds
        assert mgr.seq_len(0) == 4 and mgr.free_blocks == 2
        mgr.append_tokens(0, 8)
        assert mgr.seq_len(0) == 12 and mgr.free_blocks == 0

        mgr2 = BlockCacheManager(num_blocks=64, block_size=4,
                                 max_blocks_per_seq=2)
        mgr2.allocate(0, 4)
        with pytest.raises(SequenceTooLong):
            mgr2.append_tokens(0, 8)         # would need 3 > 2 blocks
        assert mgr2.seq_len(0) == 4 and mgr2.free_blocks == 63

    def test_append_tokens_then_trim_rollback_exact(self):
        """The speculative accept/reject cycle: reserve pending + K draft
        slots, reject some, `trim` back — seq_len and the free pool must
        land exactly where a plain single-token step would have put them."""
        mgr = BlockCacheManager(num_blocks=16, block_size=4,
                                max_blocks_per_seq=8)
        mgr.allocate(0, 7)
        mgr.allocate(1, 2)
        for accepted in (0, 1, 3):
            pre_len = mgr.seq_len(0)
            pre_free = mgr.free_blocks
            pre_blocks = list(mgr._tables[0])
            mgr.append_tokens(0, 4)          # pending + 3 drafts
            mgr.trim(0, pre_len + 1 + accepted)
            assert mgr.seq_len(0) == pre_len + 1 + accepted
            need = mgr.blocks_needed(pre_len + 1 + accepted)
            assert mgr.free_blocks == pre_free - (need - len(pre_blocks))
            # surviving prefix of the table is untouched
            assert mgr._tables[0][:len(pre_blocks)] == pre_blocks[:need]
            mgr.trim(0, pre_len)             # full rollback
            assert mgr.seq_len(0) == pre_len
            assert mgr.free_blocks == pre_free
            assert mgr._tables[0] == pre_blocks
        assert mgr.seq_len(1) == 2           # bystander untouched

    def test_block_table_array_pad_value(self):
        mgr = BlockCacheManager(num_blocks=8, block_size=4,
                                max_blocks_per_seq=4)
        mgr.allocate(0, 5)
        t = mgr.block_table_array([0], pad=7)
        assert t.shape == (1, 4)
        assert list(t[0][2:]) == [7, 7]      # entries past the allocation
        assert len(set(t[0][:2])) == 2       # real blocks kept

    def test_utilization_and_trim(self):
        mgr = BlockCacheManager(num_blocks=8, block_size=4,
                                max_blocks_per_seq=8)
        assert mgr.utilization() == 0.0
        mgr.allocate(0, 16)   # 4 blocks
        assert mgr.utilization() == pytest.approx(0.5)
        mgr.trim(0, 5)        # back to 2 blocks
        assert mgr.free_blocks == 6 and mgr.seq_len(0) == 5
        with pytest.raises(ValueError):
            mgr.trim(0, 99)   # trim can only shrink
        mgr.free(0)
        assert mgr.utilization() == 0.0


# ---------------------------------------------------------------------------
# Scheduler: admission / eviction / continuous batching (both engines)
# ---------------------------------------------------------------------------

class TestScheduler:
    def test_more_requests_than_slots_all_complete(self, engine):
        fe = ServingFrontend(engine)
        hs = [fe.submit(p, max_new_tokens=5) for p in prompts(9)]
        fe.run_until_idle(max_steps=500)
        assert all(h.status is RequestStatus.FINISHED for h in hs)
        assert all(len(h.tokens) == 5 for h in hs)
        assert monitor.get("serving.requests_completed") == 9

    def test_mid_batch_eviction_admits_queued(self, engine):
        """Short and long requests mixed: the short ones finish mid-batch
        and their slots admit queued requests without draining the batch."""
        fe = ServingFrontend(engine)
        short = [fe.submit(p, max_new_tokens=2) for p in prompts(4)]
        long = [fe.submit(p, max_new_tokens=10)
                for p in prompts(4, np.random.default_rng(7))]
        fe.run_until_idle(max_steps=500)
        assert all(h.finished for h in short + long)
        assert all(len(h.tokens) == 10 for h in long)
        # batch occupancy was refilled: more decode steps saw >1 seq than
        # a drain-then-refill policy would allow
        assert monitor.get("serving.decode_steps") < 40

    def test_steady_state_zero_recompiles(self, engine):
        """The compile-counter pattern from test_lazy_eager: warm up with
        churn (admissions, evictions, ragged lens), reset the retrace
        counters, then keep serving — decode must NEVER retrace, prefill
        only replays its warmed buckets."""
        fe = ServingFrontend(engine)
        rng = np.random.default_rng(3)
        for p in prompts(6, rng):
            fe.submit(p, max_new_tokens=4)
        fe.run_until_idle(max_steps=500)
        assert monitor.get("serving.decode_retraces") >= 1  # warmed up

        monitor.reset("serving.decode_retraces")
        hs = [fe.submit(p, max_new_tokens=6) for p in prompts(8, rng)]
        fe.run_until_idle(max_steps=500)
        assert all(h.finished for h in hs)
        assert monitor.get("serving.decode_retraces") == 0

    def test_eos_stops_early(self, engine):
        fe = ServingFrontend(engine)
        # find the greedy first token, then use it as the eos id so the
        # SECOND sampled occurrence terminates generation
        probe = fe.submit([1, 2, 3], max_new_tokens=1)
        fe.run_until_idle(max_steps=100)
        eos = probe.tokens[0]
        h = fe.submit([1, 2, 3], max_new_tokens=32, eos_token_id=eos)
        fe.run_until_idle(max_steps=200)
        assert h.finish_reason == "eos"
        assert len(h.tokens) < 32 and h.tokens[-1] == eos


# ---------------------------------------------------------------------------
# Preemption (MLP engine: fast; the policy is engine-agnostic host code)
# ---------------------------------------------------------------------------

class TestPreemption:
    def test_preemption_under_pressure_and_determinism(self):
        ps = prompts(6, np.random.default_rng(1), lo=5, hi=8)
        # tiny pool: 10 blocks - 1 guard = 9 usable; 6 growing seqs thrash
        eng = make_mlp_engine(max_batch=4, num_blocks=10, block_size=4,
                              max_blocks_per_seq=8)
        fe = ServingFrontend(eng)
        hs = [fe.submit(p, max_new_tokens=14) for p in ps]
        fe.run_until_idle(max_steps=2000)
        assert monitor.get("serving.preemptions") > 0
        assert all(h.status is RequestStatus.FINISHED for h in hs)
        assert all(len(h.tokens) == 14 for h in hs)
        assert sum(h.num_preemptions for h in hs) == \
            monitor.get("serving.preemptions")

        # determinism: an uncontended run (roomy pool, no preemption)
        # produces token-identical results
        ServingMetrics.reset_monitor()
        eng2 = make_mlp_engine(max_batch=6, num_blocks=64, block_size=4,
                               max_blocks_per_seq=8)
        fe2 = ServingFrontend(eng2)
        hs2 = [fe2.submit(p, max_new_tokens=14) for p in ps]
        fe2.run_until_idle(max_steps=500)
        assert monitor.get("serving.preemptions") == 0
        for h, h2 in zip(hs, hs2):
            assert h.tokens == h2.tokens

    def test_all_blocks_freed_after_drain(self):
        eng = make_mlp_engine(max_batch=4, num_blocks=10, block_size=4,
                              max_blocks_per_seq=8)
        fe = ServingFrontend(eng)
        for p in prompts(6, np.random.default_rng(2), lo=5, hi=8):
            fe.submit(p, max_new_tokens=10)
        fe.run_until_idle(max_steps=2000)
        # only the scheduler's guard block stays leased
        assert eng.manager.free_blocks == eng.manager.num_blocks - 1

    def test_sole_request_kv_capacity_finish(self):
        """A single sequence that outgrows the pool with nobody to preempt
        finishes gracefully with reason kv_capacity — never crashes."""
        eng = make_mlp_engine(max_batch=2, num_blocks=3, block_size=2,
                              max_blocks_per_seq=8)
        fe = ServingFrontend(eng)
        h = fe.submit([1, 2, 3], max_new_tokens=64)
        fe.run_until_idle(max_steps=300)
        assert h.status is RequestStatus.FINISHED
        assert h.finish_reason == "kv_capacity"
        assert 0 < len(h.tokens) < 64

    def test_length_cap_finish(self):
        eng = make_mlp_engine(max_batch=2, num_blocks=32, block_size=2,
                              max_blocks_per_seq=3)  # cap: 6 tokens
        fe = ServingFrontend(eng)
        h = fe.submit([1, 2, 3], max_new_tokens=64)
        fe.run_until_idle(max_steps=300)
        assert h.finish_reason == "length_cap"
        # 6-token cap: 3 prompt + 3 cached generations, plus the final
        # sampled token whose KV no longer fits (still a valid output)
        assert len(h.tokens) == 4


# ---------------------------------------------------------------------------
# Admission control, timeouts, cancel (frontend paths)
# ---------------------------------------------------------------------------

class TestFrontend:
    def test_reject_with_reason_not_crash(self):
        eng = make_mlp_engine(max_batch=2, num_blocks=6, block_size=4,
                              max_blocks_per_seq=4)
        fe = ServingFrontend(eng, max_queue=2)
        too_long = fe.submit(list(range(1, 40)), max_new_tokens=2)
        assert too_long.status is RequestStatus.REJECTED
        assert too_long.finish_reason == "prompt_too_long"
        empty = fe.submit([], max_new_tokens=2)
        assert empty.finish_reason == "empty_prompt"
        ok = [fe.submit([1, 2], max_new_tokens=2) for _ in range(2)]
        overflow = fe.submit([1, 2], max_new_tokens=2)
        assert overflow.status is RequestStatus.REJECTED
        assert overflow.finish_reason == "queue_full"
        fe.run_until_idle(max_steps=200)
        assert all(h.status is RequestStatus.FINISHED for h in ok)
        assert monitor.get("serving.requests_rejected") == 3

    def test_queued_deadline_expires(self):
        eng = make_mlp_engine(max_batch=1, num_blocks=32)
        fe = ServingFrontend(eng)
        running = fe.submit([1, 2, 3], max_new_tokens=30)
        doomed = fe.submit([4, 5], max_new_tokens=2, timeout_s=0.0)
        fe.run_until_idle(max_steps=300)
        assert running.status is RequestStatus.FINISHED
        assert doomed.status is RequestStatus.TIMED_OUT
        assert doomed.finish_reason == "deadline_in_queue"
        assert monitor.get("serving.requests_timed_out") == 1

    def test_running_deadline_expires(self):
        eng = make_mlp_engine(max_batch=2, num_blocks=32)
        # a clock that moves a tick a reading: the deadline then spans a
        # few rounds whatever the first one's compile takes (a token
        # still in flight when it strikes is discarded, not committed)
        ticks = itertools.count()
        fe = ServingFrontend(eng, clock=lambda: 0.01 * next(ticks))
        h = fe.submit([1, 2, 3], max_new_tokens=10 ** 6, timeout_s=0.5)
        for _ in range(10 ** 6):
            fe.step()
            if h.finished:
                break
        assert h.status is RequestStatus.TIMED_OUT
        assert h.finish_reason == "deadline_while_running"
        assert len(h.tokens) > 0  # made progress before expiring

    def test_cancel_queued_and_running(self):
        eng = make_mlp_engine(max_batch=1, num_blocks=32)
        fe = ServingFrontend(eng)
        run_h = fe.submit([1, 2, 3], max_new_tokens=50)
        queued_h = fe.submit([4, 5], max_new_tokens=5)
        fe.step()
        assert run_h.status is RequestStatus.RUNNING
        assert fe.cancel(queued_h) and fe.cancel(run_h)
        assert queued_h.status is RequestStatus.CANCELLED
        assert run_h.status is RequestStatus.CANCELLED
        assert not fe.cancel(run_h)  # already terminal
        # the slot + blocks were reclaimed: a new request completes
        h = fe.submit([6, 7], max_new_tokens=3)
        fe.run_until_idle(max_steps=200)
        assert h.status is RequestStatus.FINISHED
        assert monitor.get("serving.requests_cancelled") == 2

    def test_stream_yields_tokens_incrementally(self):
        eng = make_mlp_engine()
        fe = ServingFrontend(eng)
        h = fe.submit([1, 2, 3, 4], max_new_tokens=6)
        got = list(fe.stream(h))
        assert got == h.tokens and len(got) == 6
        assert h.status is RequestStatus.FINISHED

    def test_stream_callback_and_sampling(self):
        eng = make_mlp_engine()
        fe = ServingFrontend(eng)
        seen = []
        h = fe.submit([3, 1], max_new_tokens=5, temperature=0.8, top_k=8,
                      seed=11, stream_cb=seen.append)
        fe.run_until_idle(max_steps=200)
        assert seen == h.tokens and len(seen) == 5
        assert all(0 <= t < VOCAB for t in seen)


# ---------------------------------------------------------------------------
# Deadline / cancel races (the paths between "scheduled" and "committed")
# ---------------------------------------------------------------------------

class TestDeadlineCancelRaces:
    def test_cancel_self_from_stream_cb_during_prefill(self):
        """The first token is emitted from INSIDE the admission/prefill
        phase; a callback cancelling its own request there must not
        double-finish (the old `_maybe_finish_on_token` would free the
        slot twice and KeyError on the manager)."""
        eng = make_mlp_engine()
        fe = ServingFrontend(eng)
        h = None

        def cb(tok):
            assert fe.cancel(h)

        h = fe.submit([1, 2, 3], max_new_tokens=5, stream_cb=cb)
        fe.run_until_idle(max_steps=100)
        assert h.status is RequestStatus.CANCELLED
        assert len(h.tokens) == 1        # the prefill-sampled token
        mgr = eng.manager
        assert mgr.free_blocks == mgr.num_blocks - 1   # only the guard

    def test_cancel_other_request_from_stream_cb_mid_batch(self):
        """A callback cancelling a DIFFERENT in-flight request while the
        decode commit loop is walking the batch: the cancelled lane's
        token must not be committed onto a terminal request."""
        eng = make_mlp_engine()
        fe = ServingFrontend(eng)
        handles = {}
        fired = []

        def cb(tok):
            if not fired:
                fired.append(True)
                assert fe.cancel(handles["victim"])

        killer = fe.submit([1, 2, 3], max_new_tokens=6, stream_cb=cb)
        handles["victim"] = fe.submit([4, 5, 6], max_new_tokens=6)
        fe.run_until_idle(max_steps=200)
        assert killer.status is RequestStatus.FINISHED
        assert len(killer.tokens) == 6
        victim = handles["victim"]
        assert victim.status is RequestStatus.CANCELLED
        n_at_cancel = len(victim.tokens)
        fe.run_until_idle(max_steps=50)
        assert len(victim.tokens) == n_at_cancel   # nothing appended after
        mgr = eng.manager
        assert mgr.free_blocks == mgr.num_blocks - 1

    def test_deadline_expires_mid_preemption(self):
        """A PREEMPTED request (tokens-so-far kept, waiting at the queue
        front) whose deadline lapses before re-admission must come back
        TIMED_OUT with its partial tokens intact — and with no leaked
        blocks (they were freed at preemption time)."""
        ps = prompts(6, np.random.default_rng(1), lo=5, hi=8)
        eng = make_mlp_engine(max_batch=4, num_blocks=10, block_size=4,
                              max_blocks_per_seq=8)
        fe = ServingFrontend(eng)
        hs = [fe.submit(p, max_new_tokens=14) for p in ps]
        victim = None
        for _ in range(2000):
            fe.step()
            if victim is None:
                pre = [h for h in hs
                       if h.status is RequestStatus.PREEMPTED]
                if pre:
                    victim = pre[0]
                    # expire it while it waits for re-admission
                    victim._req.deadline = -1.0
            if all(h.finished for h in hs):
                break
        assert victim is not None, "trace never preempted"
        assert victim.status is RequestStatus.TIMED_OUT
        assert victim.finish_reason == "deadline_in_queue"
        assert victim.num_preemptions >= 1
        assert len(victim.tokens) > 0          # partial output preserved
        others = [h for h in hs if h is not victim]
        assert all(h.status is RequestStatus.FINISHED for h in others)
        assert all(len(h.tokens) == 14 for h in others)
        mgr = eng.manager
        assert mgr.free_blocks == mgr.num_blocks - 1

    def test_shed_vs_admit_at_exact_watermark(self):
        """Boundary contract through the frontend: depth == queue_high
        sheds, the latch holds between the watermarks, and depth ==
        queue_low re-admits."""
        from paddle_tpu.serving import AdmissionConfig

        eng = make_mlp_engine(max_batch=1, num_blocks=32)
        fe = ServingFrontend(eng, admission=AdmissionConfig(queue_high=2,
                                                            queue_low=1))
        a = fe.submit([1, 2], max_new_tokens=8)    # depth 0 -> queued
        b = fe.submit([1, 2], max_new_tokens=8)    # depth 1 -> queued
        c = fe.submit([1, 2], max_new_tokens=8)    # depth == high: SHED
        assert [a.status, b.status, c.status] == [
            RequestStatus.QUEUED, RequestStatus.QUEUED, RequestStatus.SHED]
        fe.step()                                  # admits a; depth 1
        assert len(fe.scheduler.waiting) == 1
        d = fe.submit([1, 2], max_new_tokens=8)    # depth == low: admitted
        assert d.status is RequestStatus.QUEUED
        e = fe.submit([1, 2], max_new_tokens=8)    # depth == high again
        assert e.status is RequestStatus.SHED
        fe.run_until_idle(max_steps=300)
        assert all(h.status is RequestStatus.FINISHED for h in (a, b, d))


# ---------------------------------------------------------------------------
# Chunked prefill through the ragged step (ISSUE 10 tentpole)
# ---------------------------------------------------------------------------

class TestChunkedPrefill:
    def test_token_parity_across_chunk_sizes(self, engine_factory):
        """The chunk schedule must never change WHAT is generated — only
        when prefill finishes. Chunk sizes straddling block boundaries,
        the prompt length, and 1-token extremes all agree."""
        ps = prompts(5, np.random.default_rng(11), lo=6, hi=20)
        outs = []
        for chunk in (1, 3, 4, 7, 64):
            fe = ServingFrontend(engine_factory(),
                                 prefill_chunk_tokens=chunk)
            hs = [fe.submit(p, max_new_tokens=6) for p in ps]
            fe.run_until_idle(max_steps=2000)
            assert all(h.status is RequestStatus.FINISHED for h in hs), \
                chunk
            outs.append([h.tokens for h in hs])
            ServingMetrics.reset_monitor()
        assert all(o == outs[0] for o in outs[1:])

    def test_long_prompt_does_not_block_decode_lanes(self):
        """While a long prompt prefills chunk-by-chunk, decode lanes keep
        committing a token EVERY step — the TPOT-isolation contract."""
        eng = make_mlp_engine(max_batch=4, num_blocks=64,
                              max_blocks_per_seq=16)
        fe = ServingFrontend(eng, prefill_chunk_tokens=4)
        short = [fe.submit([1, 2, 3], max_new_tokens=40) for _ in range(2)]
        for _ in range(5):                  # short ones admitted + decoding
            fe.step()
        n0 = [len(h.tokens) for h in short]
        long = fe.submit(list(range(1, 41)), max_new_tokens=4)
        steps_while_prefilling = 0
        for _ in range(200):
            if not long._req.prefilling and long._req._prefill_ctx.size:
                break
            fe.step()
            steps_while_prefilling += 1
        assert steps_while_prefilling >= 40 // 4
        n1 = [len(h.tokens) for h in short]
        # every step during the 10-chunk prefill produced a decode token
        # on each live short lane (they may finish mid-way: cap at 40)
        for a, b in zip(n0, n1):
            assert b == min(40, a + steps_while_prefilling)
        assert monitor.get("serving.step_prefill_tokens") >= 1
        fe.run_until_idle(max_steps=200)
        assert long.status is RequestStatus.FINISHED
        assert all(h.status is RequestStatus.FINISHED for h in short)

    def test_one_steady_state_executable_across_prompt_lengths(self,
                                                               engine_factory):
        """The bucket executable family collapses to ONE: serving prompt
        lengths from 1 token to several chunks retraces the ragged step
        exactly once (the first trace), and the PR 7 retrace-cause trace
        records zero prompt-length-shaped serving retraces."""
        import paddle_tpu.observability as obs

        obs.enable()
        try:
            monitor.reset("serving.ragged_retraces")
            monitor.reset("serving.decode_retraces")
            fe = ServingFrontend(engine_factory(), prefill_chunk_tokens=8)
            rng = np.random.default_rng(5)
            for n in (1, 2, 5, 9, 14, 23, 31):
                h = fe.submit(rng.integers(1, VOCAB, n).tolist(),
                              max_new_tokens=3)
                fe.run_until_idle(max_steps=300)
                assert h.status is RequestStatus.FINISHED
            assert monitor.get("serving.ragged_retraces") == 1
            assert monitor.get("serving.decode_retraces") == 1
            assert not [c for c in obs.retrace_causes()
                        if c["name"].startswith("serve.")]
        finally:
            obs.disable()
            obs.reset()

    def test_batch_composition_gauges_published(self):
        fe = ServingFrontend(make_mlp_engine(), prefill_chunk_tokens=4)
        # the gauges are a round's, published when it is settled: by the
        # step() after the one that launched it
        fe.submit(list(range(1, 11)), max_new_tokens=2)
        fe.step()                        # first chunk round: 4 tokens
        fe.step()                        # second launched, first settled
        assert monitor.get("serving.step_prefill_tokens") == 4
        assert monitor.get("serving.step_decode_lanes") == 0
        fe.run_until_idle(max_steps=100)
        fe.submit([1, 2], max_new_tokens=3)
        fe.step()                        # 2-token chunk, no decode lane
        fe.step()                        # pure decode round
        fe.step()                        # ... settled
        assert monitor.get("serving.step_prefill_tokens") == 0
        assert monitor.get("serving.step_decode_lanes") == 1

    def test_spec_equals_plain_under_chunking(self, engine_factory):
        """spec==plain token parity with prompts longer than the chunk —
        prefill chunks riding the fixed verify window must not disturb
        the draft/accept stream (greedy AND stochastic)."""
        ps = [list(range(1, 18)), ([3, 4, 5] * 7)[:20], [7, 8] * 8]
        for temp in (0.0, 0.8):
            outs = []
            for spec in (None, SpecDecodeConfig(NGramProposer(),
                                                num_draft_tokens=3)):
                fe = ServingFrontend(engine_factory(), spec=spec,
                                     prefill_chunk_tokens=5)
                hs = [fe.submit(p, max_new_tokens=8, temperature=temp,
                                seed=9) for p in ps]
                fe.run_until_idle(max_steps=2000)
                assert all(h.status is RequestStatus.FINISHED for h in hs)
                outs.append([h.tokens for h in hs])
                ServingMetrics.reset_monitor()
            assert outs[0] == outs[1], f"temperature={temp}"

    def test_llama_long_prompt_chunked_matches_generate(self, llama_model):
        """End-to-end fidelity with a prompt several chunks long: the
        chunked serving path reproduces `generate()`'s tokens."""
        from paddle_tpu.inference import GenerationConfig, \
            LlamaInferenceEngine

        rng = np.random.default_rng(2)
        p = rng.integers(1, VOCAB, 23).tolist()
        eng = LlamaInferenceEngine(llama_model, max_batch_size=1,
                                   num_blocks=32, block_size=4,
                                   max_blocks_per_seq=8)
        ref = eng.generate(np.asarray([p], np.int32),
                           GenerationConfig(max_new_tokens=5))[0, 23:]
        eng2 = LlamaInferenceEngine(llama_model, max_batch_size=2,
                                    num_blocks=32, block_size=4,
                                    max_blocks_per_seq=8)
        fe = ServingFrontend(eng2, prefill_chunk_tokens=6)
        h = fe.submit(p, max_new_tokens=5)
        fe.run_until_idle(max_steps=200)
        assert h.tokens == ref.tolist()


# ---------------------------------------------------------------------------
# Llama serving == Llama generate() (numeric fidelity of the serving path)
# ---------------------------------------------------------------------------

def test_llama_serving_matches_generate(llama_model):
    from paddle_tpu.inference import GenerationConfig

    rng = np.random.default_rng(0)
    ps = [rng.integers(1, VOCAB, n).tolist() for n in (3, 7, 11)]
    ref = []
    for p in ps:
        eng = LlamaInferenceEngine(llama_model, max_batch_size=1,
                                   num_blocks=32, block_size=4,
                                   max_blocks_per_seq=8)
        out = eng.generate(np.asarray([p], np.int32),
                           GenerationConfig(max_new_tokens=5))
        ref.append(out[0, len(p):].tolist())
    eng = LlamaInferenceEngine(llama_model, max_batch_size=4, num_blocks=48,
                               block_size=4, max_blocks_per_seq=8)
    fe = ServingFrontend(eng)
    hs = [fe.submit(p, max_new_tokens=5) for p in ps]
    fe.run_until_idle(max_steps=200)
    assert [h.tokens for h in hs] == ref


def _generate_engine(kind, llama_model):
    """Identical weights on every call, a fresh pool and manager."""
    from paddle_tpu.serving import shard_engine
    from paddle_tpu.serving.lora import attach_adapters, random_adapter

    def llama(**kw):
        return LlamaInferenceEngine(llama_model, max_batch_size=4,
                                    num_blocks=48, block_size=4,
                                    max_blocks_per_seq=8, **kw)

    if kind == "llama":
        return llama()
    if kind == "llama-int8-kv":
        return llama(kv_bits=8)
    if kind == "mlp":
        return make_mlp_engine()
    if kind == "lora":
        eng = attach_adapters(make_mlp_engine(), pool_slots=2)
        eng.adapter_pool.register("a", random_adapter(eng, rank=4, seed=1))
        eng.use_adapter("a")
        return eng
    if kind == "tp2":
        return shard_engine(llama(), tp=2)
    from paddle_tpu.inference.deepseek_v3_runner import \
        DeepseekV3InferenceEngine
    from paddle_tpu.models import deepseek_v3 as dsv3

    cfg = dsv3.DeepseekV3Config.from_hf(dict(
        vocab_size=VOCAB, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_hidden_layers=2,
        num_attention_heads=4, kv_lora_rank=32, q_lora_rank=None,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        n_routed_experts=8, n_shared_experts=2, num_experts_per_tok=2,
        first_k_dense_replace=1, routed_scaling_factor=2.448,
        norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=10000.0,
        rope_interleave=True, rope_scaling=None,
        max_position_embeddings=64, n_group=1, topk_group=1,
        scoring_func="sigmoid"))
    model = dsv3.DeepseekV3ForCausalLM(
        cfg, weights=dsv3.init_params(cfg, 3, np.float32, 0.08))
    return DeepseekV3InferenceEngine(model, max_batch_size=4, num_blocks=48,
                                     block_size=4, max_blocks_per_seq=8)


@pytest.mark.parametrize("kind", ["llama", "llama-int8-kv", "mlp", "lora",
                                  "tp2", "kanana"])
def test_generate_rides_the_ragged_step(kind, llama_model):
    """`generate()` is one host loop over `ragged_step` for every engine:
    its greedy tokens are the tokens the scheduler serves for the same
    prompts (chunked prefill, continuous batching), and it gives every
    block back."""
    rng = np.random.default_rng(4)
    ps = rng.integers(1, VOCAB, (3, 9)).astype(np.int32)
    eng = _generate_engine(kind, llama_model)
    free = eng.manager.free_blocks
    out = eng.generate(ps, max_new_tokens=6)
    assert out.shape == (3, 15) and (out[:, :9] == ps).all()
    assert eng.manager.free_blocks == free and eng.manager.num_seqs == 0
    fe = ServingFrontend(_generate_engine(kind, llama_model),
                         prefill_chunk_tokens=5)
    hs = [fe.submit(p.tolist(), max_new_tokens=6) for p in ps]
    fe.run_until_idle(max_steps=400)
    assert [h.tokens for h in hs] == out[:, 9:].tolist()


# ---------------------------------------------------------------------------
# Metrics / observability
# ---------------------------------------------------------------------------

class TestMetrics:
    def test_summary_and_monitor_coherence(self):
        eng = make_mlp_engine()
        fe = ServingFrontend(eng)
        hs = [fe.submit(p, max_new_tokens=4) for p in prompts(5)]
        fe.run_until_idle(max_steps=300)
        s = fe.summary()
        assert s["serving.requests_submitted"] == 5
        assert s["serving.requests_completed"] == 5
        assert s["serving.tokens_generated"] + s["serving.prefills"] == \
            sum(len(h.tokens) for h in hs)
        assert s["serving.ttft_p50_ms"] <= s["serving.ttft_p99_ms"]
        assert 0 < s["serving.batch_occupancy_avg_pct"] <= 100
        assert s["serving.kv_utilization_peak_pct"] > 0
        assert all(h.ttft_ms() is not None and h.ttft_ms() >= 0 for h in hs)

    def test_profiler_summary_serving_section(self):
        from paddle_tpu import profiler

        eng = make_mlp_engine()
        fe = ServingFrontend(eng)
        prof = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU])
        prof.start()
        fe.submit([1, 2, 3], max_new_tokens=3)
        fe.run_until_idle(max_steps=100)
        prof.stop()
        text = prof.summary()
        assert "Serving:" in text and "TTFT" in text
        assert "occupancy avg" in text

    def test_profiler_summary_speculative_line(self):
        from paddle_tpu import profiler

        fe = ServingFrontend(
            make_mlp_engine(),
            spec=SpecDecodeConfig(NGramProposer(), num_draft_tokens=3))
        prof = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU])
        prof.start()
        fe.submit([1, 2, 3, 1, 2, 3, 1, 2], max_new_tokens=6)
        fe.run_until_idle(max_steps=100)
        prof.stop()
        text = prof.summary()
        assert "speculative:" in text and "drafts accepted" in text

    def test_latency_and_spec_samples_stay_bounded(self):
        """Regression for the bounded-reservoir contract: a long-running
        server must keep every sample list capped at the window size, no
        matter how many requests/steps it has seen."""
        from types import SimpleNamespace

        from paddle_tpu.serving.metrics import _WINDOW

        m = ServingMetrics()
        req = SimpleNamespace(status=RequestStatus.FINISHED,
                              ttft=lambda: 0.01, tpot=lambda: 0.001)
        for _ in range(2 * _WINDOW + 17):
            m.on_first_token(req)
            m.on_finish(req)
            m.on_spec(proposed=4, accepted=2, produced=3, lanes=1)
        assert len(m.ttft_s) == _WINDOW and m.ttft_s.maxlen == _WINDOW
        assert len(m.tpot_s) == _WINDOW and m.tpot_s.maxlen == _WINDOW
        assert len(m.accept_rate) == _WINDOW
        assert m.accept_rate.maxlen == _WINDOW
        # summary still computes from the capped window
        s = m.summary()
        assert s["serving.ttft_p50_ms"] == pytest.approx(10.0)
        assert monitor.get("serving.spec_acceptance_pct") == 50.0


# ---------------------------------------------------------------------------
# Device-side fused batched sampling (ops/sampling.py)
# ---------------------------------------------------------------------------

class TestFusedSampler:
    def test_greedy_is_argmax_2d_and_3d(self):
        rng = np.random.default_rng(0)
        lg = rng.normal(size=(3, 17)).astype(np.float32)
        z = np.zeros(3, np.int32)
        got = sample_tokens(lg, np.zeros(3, np.float32), z, z, z)
        np.testing.assert_array_equal(got, lg.argmax(-1))
        lg3 = rng.normal(size=(3, 4, 17)).astype(np.float32)
        got3 = sample_tokens(lg3, np.zeros(3, np.float32), z, z, z)
        assert got3.shape == (3, 4)
        np.testing.assert_array_equal(got3, lg3.argmax(-1))

    def test_counter_stream_slot_offset_contract(self):
        """Slot s of a [B, S, V] draw must equal a [B, V] draw at counter
        draw_idx + s — the property that makes speculative sampling
        reproduce exactly what sequential decode would have sampled."""
        rng = np.random.default_rng(1)
        lg = rng.normal(size=(2, 3, 33)).astype(np.float32)
        temps = np.asarray([0.7, 1.3], np.float32)
        topk = np.asarray([0, 5], np.int32)
        seeds = np.asarray([11, 42], np.int32)
        draws = np.asarray([4, 9], np.int32)
        multi = sample_tokens(lg, temps, topk, seeds, draws)
        for s in range(3):
            single = sample_tokens(lg[:, s, :], temps, topk, seeds,
                                   draws + s)
            np.testing.assert_array_equal(multi[:, s], single)

    def test_seeded_determinism_and_seed_sensitivity(self):
        rng = np.random.default_rng(2)
        lg = np.broadcast_to(rng.normal(size=(1, 64)),
                             (8, 64)).astype(np.float32).copy()
        temps = np.full(8, 1.0, np.float32)
        z = np.zeros(8, np.int32)
        seeds = np.arange(8, dtype=np.int32)
        a = sample_tokens(lg, temps, z, seeds, z)
        b = sample_tokens(lg, temps, z, seeds, z)
        np.testing.assert_array_equal(a, b)        # same counters -> same
        # different draw counters move the stream
        c = sample_tokens(lg, temps, z, seeds, z + 1)
        assert (a != c).any()
        # identical logits, different per-request seeds -> diverse picks
        assert len(set(a.tolist())) > 1

    def test_top_k_restricts_support(self):
        v = 32
        lg = np.full((1, v), -5.0, np.float32)
        lg[0, 7] = 4.0
        lg[0, 19] = 3.5
        temps = np.full(1, 1.5, np.float32)
        topk = np.asarray([2], np.int32)
        for d in range(50):
            tok = sample_tokens(lg, temps, topk,
                                np.asarray([3], np.int32),
                                np.asarray([d], np.int32))
            assert int(tok[0]) in (7, 19)

    def test_mixed_greedy_and_stochastic_lanes(self):
        rng = np.random.default_rng(3)
        lg = rng.normal(size=(4, 21)).astype(np.float32)
        temps = np.asarray([0.0, 1.0, 0.0, 2.0], np.float32)
        z = np.zeros(4, np.int32)
        got = sample_tokens(lg, temps, z, np.arange(4, dtype=np.int32), z)
        assert got[0] == lg[0].argmax() and got[2] == lg[2].argmax()


# ---------------------------------------------------------------------------
# Speculative decoding (serving/spec.py + scheduler integration)
# ---------------------------------------------------------------------------

def _rep_prompts(n, rng=None):
    """Repetition-leaning prompt mix (what prompt-lookup is for) plus
    plain random prompts."""
    rng = rng or np.random.default_rng(0)
    out = []
    for i in range(n):
        if i % 2:
            phrase = rng.integers(1, VOCAB, int(rng.integers(2, 4))).tolist()
            out.append((phrase * 5)[:int(rng.integers(6, 13))])
        else:
            out.append(rng.integers(1, VOCAB, rng.integers(2, 12)).tolist())
    return out


class TestNGramProposer:
    def test_suffix_match_proposes_continuation(self):
        p = NGramProposer(max_ngram=3)
        assert p.propose(0, np.asarray([1, 2, 3, 9, 8, 7, 1, 2, 3]),
                         3) == [9, 8, 7]

    def test_self_extension_on_cyclic_tail(self):
        p = NGramProposer(max_ngram=3)
        # constant tail keeps extending instead of truncating at the
        # rightmost match (one token from the end)
        assert p.propose(0, np.asarray([5, 6, 7, 7, 7]), 4) == [7, 7, 7, 7]
        assert p.propose(0, np.asarray([9, 1, 2, 1, 2, 1]),
                         4) == [2, 1, 2, 1]

    def test_no_match_and_degenerate_contexts(self):
        p = NGramProposer()
        assert p.propose(0, np.asarray([1, 2, 3, 4]), 4) == []
        assert p.propose(0, np.asarray([5]), 4) == []
        assert p.propose(0, np.asarray([], np.int32), 4) == []
        p.release(0)  # stateless no-op

    def test_config_validation(self):
        with pytest.raises(ValueError):
            NGramProposer(max_ngram=2, min_ngram=3)
        with pytest.raises(ValueError):
            SpecDecodeConfig(NGramProposer(), num_draft_tokens=0)


class TestSpeculative:
    def _run(self, engine, plist, spec=None, temperature=0.0, seed=0,
             max_new=7):
        fe = ServingFrontend(engine, spec=spec)
        hs = [fe.submit(p, max_new_tokens=max_new, temperature=temperature,
                        seed=seed)
              for p in plist]
        fe.run_until_idle(max_steps=2000)
        assert all(h.status is RequestStatus.FINISHED for h in hs)
        return [h.tokens for h in hs]

    def test_greedy_parity_both_engines(self, engine_factory):
        """Acceptance criterion: token-for-token greedy parity of the
        speculative path vs plain decode, for both EngineCore impls."""
        plist = _rep_prompts(9)
        base = self._run(engine_factory(), plist)
        spec = self._run(
            engine_factory(), plist,
            spec=SpecDecodeConfig(NGramProposer(), num_draft_tokens=3))
        assert base == spec
        assert monitor.get("serving.spec_steps") > 0

    def test_stochastic_parity_via_counter_rng(self, engine_factory):
        """The counter-based per-request RNG extends parity beyond greedy:
        temperature sampling draws slot s with counter draw_idx + s, so a
        speculative run samples EXACTLY the tokens sequential decode
        would (acceptance compares drafts against the sampled stream)."""
        plist = _rep_prompts(6)
        base = self._run(engine_factory(), plist, temperature=0.8, seed=7)
        spec = self._run(
            engine_factory(), plist,
            spec=SpecDecodeConfig(NGramProposer(), num_draft_tokens=3),
            temperature=0.8, seed=7)
        assert base == spec

    def test_zero_retraces_in_steady_state(self, engine_factory):
        """Fixed-K fixed-shape verify + fused sampling: after a warmup
        round, long speculative runs never retrace step/verify/sample."""
        fe = ServingFrontend(
            engine_factory(),
            spec=SpecDecodeConfig(NGramProposer(), num_draft_tokens=3))
        rng = np.random.default_rng(0)
        for n in (2, 5, 9, 14):   # cover the prefill buckets + spec shapes
            fe.submit(rng.integers(1, VOCAB, n).tolist(), max_new_tokens=3)
        fe.run_until_idle(max_steps=300)
        for c in ("serving.verify_retraces", "serving.sample_retraces",
                  "serving.decode_retraces"):
            monitor.reset(c)
        hs = [fe.submit(p, max_new_tokens=6) for p in _rep_prompts(10)]
        fe.run_until_idle(max_steps=2000)
        assert all(h.status is RequestStatus.FINISHED for h in hs)
        for c in ("serving.verify_retraces", "serving.sample_retraces",
                  "serving.decode_retraces"):
            assert monitor.get(c) == 0, f"{c} = {monitor.get(c)}"

    def test_acceptance_metrics_published(self):
        eng = make_mlp_engine()
        self._run(eng, [[1, 2, 3] * 4], max_new=8,
                  spec=SpecDecodeConfig(NGramProposer(), num_draft_tokens=3))
        assert monitor.get("serving.spec_steps") > 0
        assert monitor.get("serving.spec_proposed_tokens") >= \
            monitor.get("serving.spec_accepted_tokens")
        assert monitor.get("serving.spec_tokens_per_lane_step") >= 1.0
        acc = monitor.get("serving.spec_acceptance_pct")
        assert 0.0 <= acc <= 100.0

    def test_draft_engine_proposer_perfect_drafts(self):
        """A draft engine with the TARGET's weights drafts greedily exactly
        what the target verifies: every proposed token is accepted, and
        the draft cache pool drains back to full when requests finish."""
        target = make_mlp_engine()
        draft = make_mlp_engine()   # same seed -> identical weights
        proposer = DraftEngineProposer(draft)
        plist = _rep_prompts(6)
        base = self._run(make_mlp_engine(), plist)
        spec = self._run(
            target, plist,
            spec=SpecDecodeConfig(proposer, num_draft_tokens=3))
        assert base == spec
        assert monitor.get("serving.spec_proposed_tokens") > 0
        assert monitor.get("serving.spec_accepted_tokens") == \
            monitor.get("serving.spec_proposed_tokens")
        assert draft.manager.free_blocks == 48   # all leases released

    def test_draft_proposer_context_over_draft_cap_degrades(self):
        """Regression: a verified context longer than the DRAFT cache's
        per-sequence cap must degrade to 'no proposal' — the bucket
        doubling in `_prefill` used to saturate below the context length
        and spin forever, freezing the serving loop."""
        draft = make_mlp_engine(max_blocks_per_seq=2)   # draft cap: 8
        proposer = DraftEngineProposer(draft)
        assert proposer.propose(0, np.arange(1, 12, dtype=np.int32), 3) == []
        assert draft.manager.free_blocks == 48          # nothing leaked
        # and a synced sequence whose context outgrows the cap mid-stream
        assert proposer.propose(1, np.arange(1, 7, dtype=np.int32), 3) != []
        assert proposer.propose(1, np.arange(1, 30, dtype=np.int32), 3) == []

    def test_huge_seed_does_not_crash_decode(self, engine_factory):
        """Regression: numpy >= 2.0 raises OverflowError constructing an
        int32 array from seed >= 2**31; the sampler arrays must mask user
        ints instead of killing the decode step for every lane."""
        fe = ServingFrontend(engine_factory())
        h = fe.submit([1, 2, 3], max_new_tokens=4, temperature=0.9,
                      seed=2 ** 40 + 5, top_k=2 ** 33)
        fe.run_until_idle(max_steps=200)
        assert h.status is RequestStatus.FINISHED
        assert len(h.tokens) == 4

    def test_spec_parity_under_preemption_pressure(self):
        """KV pressure: the spec path's degrade-then-preempt growth keeps
        per-request token streams identical to the plain scheduler's
        (tokens-so-far survive preemption; greedy continuations are
        deterministic regardless of scheduling order)."""
        def tight():
            return make_mlp_engine(max_batch=4, num_blocks=12,
                                   max_blocks_per_seq=6)

        plist = [p[:8] for p in _rep_prompts(7)]
        base = self._run(tight(), plist, max_new=6)
        spec = self._run(
            tight(), plist, max_new=6,
            spec=SpecDecodeConfig(NGramProposer(), num_draft_tokens=3))
        assert base == spec

    def test_spec_parity_at_length_cap(self, engine_factory, llama_model):
        """A lane within S tokens of its hard length cap keeps a table
        FULL of real blocks while the fixed-shape verify still lays out S
        positions, so the final rounds exercise the clamped `_grow_n`
        growth, the past-the-cap guard columns of the verify table (a
        narrow table would send those KV writes through an OOB-gather
        int32 wraparound into physical block 0 — see `_decode_spec`), and
        the `trim` bookkeeping right up to the `length_cap` finish. The
        pool is sized so every block (incl. block 0) is leased."""
        def tight():
            if engine_factory is make_mlp_engine:
                return make_mlp_engine(num_blocks=10, max_blocks_per_seq=3)
            return LlamaInferenceEngine(llama_model, max_batch_size=4,
                                        num_blocks=10, block_size=4,
                                        max_blocks_per_seq=3)   # cap: 12

        # cyclic prompts keep the proposer drafting right up to the cap
        plist = [[1, 2, 3] * 2, [5, 6] * 4, [9, 8] * 4]
        base = self._run(tight(), plist, max_new=12)
        spec = self._run(
            tight(), plist, max_new=12,
            spec=SpecDecodeConfig(NGramProposer(), num_draft_tokens=4))
        assert base == spec

    def test_failing_proposer_degrades_to_plain_decode(self, engine_factory):
        """A proposer that raises must never kill the serving loop — the
        round degrades to zero drafts (plain decode via verify)."""
        class Hostile:
            def propose(self, seq_id, context, k):
                raise RuntimeError("boom")

            def release(self, seq_id):
                raise RuntimeError("boom on release too")

        plist = _rep_prompts(5)
        base = self._run(engine_factory(), plist)
        spec = self._run(engine_factory(), plist,
                         spec=SpecDecodeConfig(Hostile(),
                                               num_draft_tokens=3))
        assert base == spec
        assert monitor.get("serving.spec_accepted_tokens") == 0


# ---------------------------------------------------------------------------
# Predictor Config.enable_profile wiring (satellite)
# ---------------------------------------------------------------------------

class _FakeSavedLayer:
    """Stands in for a jit-loaded program (`jax.export` is unavailable on
    some CI jax builds — the real save/load path is covered by
    test_inference when it is present)."""

    _meta = {"input_avals": [([2, 8], "float32")]}

    def __call__(self, x):
        return x


def test_predictor_enable_profile_emits_spans(monkeypatch, tmp_path):
    import paddle_tpu.inference as paddle_infer
    from paddle_tpu.jit import save_load

    monkeypatch.setattr(save_load, "load", lambda path: _FakeSavedLayer())
    cfg = paddle_infer.Config(str(tmp_path / "model.pdmodel"))
    cfg.enable_profile()
    assert cfg.summary()["profile"] is True
    predictor = paddle_infer.create_predictor(cfg)
    x = np.zeros((2, 8), np.float32)
    for _ in range(3):
        predictor.run([x])
    text = predictor.profiler_summary()
    assert "Predictor.run" in text
    # un-profiled predictor answers politely instead of crashing
    cfg2 = paddle_infer.Config(str(tmp_path / "model.pdmodel"))
    p2 = paddle_infer.create_predictor(cfg2)
    assert "not enabled" in p2.profiler_summary()
