"""Shared-prefix radix KV caching + multi-tenant SLO scheduling tests
(ISSUE 12): refcounted copy-on-write block management, the radix tree's
lease/publish/evict lifecycle, eviction-under-pressure properties
(leased blocks never reclaimed, no double-free), scheduler integration
(prefix hits skip prefill chunks, full hit ≈ one decode step,
spec==plain parity on a hit), tenant isolation (quota / reserve /
weighted lanes / tiered watermarks), and the metrics surface.
"""
import numpy as np
import pytest

from paddle_tpu.framework import monitor
from paddle_tpu.inference.cache import BlockCacheManager, KVCacheExhausted
from paddle_tpu.inference.prefix_cache import RadixPrefixCache
from paddle_tpu.serving import (AdmissionConfig, MLPLMEngine, NGramProposer,
                                RequestStatus, ServingFrontend,
                                ServingMetrics, SLOClass, SLOConfig,
                                SpecDecodeConfig)

VOCAB = 64
BS = 4


def make_engine(max_batch=4, num_blocks=48, block_size=BS,
                max_blocks_per_seq=8, seed=0):
    return MLPLMEngine(vocab_size=VOCAB, hidden=16, max_batch_size=max_batch,
                       num_blocks=num_blocks, block_size=block_size,
                       max_blocks_per_seq=max_blocks_per_seq, seed=seed)


@pytest.fixture(autouse=True)
def _fresh_counters():
    ServingMetrics.reset_monitor()
    yield


def toks(rng, n):
    return rng.integers(1, VOCAB, n).tolist()


# ---------------------------------------------------------------- manager

class TestRefcountedBlocks:
    def test_adopt_increfs_and_free_releases_last(self):
        mgr = BlockCacheManager(8, BS, 8)
        blocks = mgr.allocate(1, 8)                # 2 blocks
        mgr.adopt(2, blocks, 8)
        assert [mgr.ref_count(b) for b in blocks] == [2, 2]
        assert mgr.free_blocks == 6                # shared: leased ONCE
        mgr.free(1)
        assert mgr.free_blocks == 6                # still held by seq 2
        assert [mgr.ref_count(b) for b in blocks] == [1, 1]
        mgr.free(2)
        assert mgr.free_blocks == 8
        mgr.check_consistency()

    def test_utilization_counts_shared_block_once(self):
        # the ISSUE 12 satellite: N leases of one physical block are ONE
        # block of pressure — per-lease counting would inflate past 1.0
        mgr = BlockCacheManager(4, BS, 4)
        blocks = mgr.allocate(1, 16)               # the whole pool
        for sid in (2, 3, 4):
            mgr.adopt(sid, blocks, 16)
        assert mgr.utilization() == 1.0            # not 4.0
        frag = mgr.fragmentation()
        assert frag["leased_blocks"] == 4          # physical-unique
        assert frag["lease_count"] == 16           # per-lease evidence
        assert frag["shared_blocks"] == 4
        assert frag["internal_frag_ratio"] >= 0.0  # clamped under sharing
        for sid in (1, 2, 3, 4):
            mgr.free(sid)
        mgr.check_consistency()

    def test_trim_releases_lease_not_block(self):
        mgr = BlockCacheManager(8, BS, 8)
        blocks = mgr.allocate(1, 8)
        mgr.adopt(2, blocks, 8)
        mgr.trim(2, 2)                             # drop seq 2's 2nd lease
        assert mgr.ref_count(blocks[1]) == 1       # seq 1 still holds it
        assert mgr.free_blocks == 6                # nothing freed
        mgr.free(1)
        assert mgr.free_blocks == 7                # block 1 freed now
        mgr.check_consistency()

    def test_cow_on_divergent_append(self):
        mgr = BlockCacheManager(8, BS, 8)
        copies = []
        mgr.set_cow_hook(lambda s, d: copies.append((s, d)))
        blocks = mgr.allocate(1, 6)                # 2 blocks, 2nd partial
        mgr.adopt(2, blocks, 6)
        src = blocks[1]
        mgr.append_tokens(2, 1)                    # diverges inside shared
        assert copies and copies[0][0] == src
        dst = copies[0][1]
        assert mgr.blocks_of(2)[1] == dst != src
        assert mgr.blocks_of(1)[1] == src          # sibling untouched
        assert mgr.ref_count(src) == 1 and mgr.ref_count(dst) == 1
        assert mgr.cow_copies == 1
        # the writer's next appends stay private: no second COW
        mgr.append_tokens(2, 1)
        assert mgr.cow_copies == 1
        mgr.check_consistency()

    def test_cow_after_trim_into_shared_block(self):
        # trim back INTO shared territory (the spec-rollback shape),
        # then a divergent append: the still-shared block must COW and
        # the sibling keeps its exact blocks
        mgr = BlockCacheManager(8, BS, 8)
        blocks = mgr.allocate(1, 8)                # 2 full blocks
        mgr.adopt(3, blocks, 8)
        mgr.trim(3, 5)                             # mid-block, keeps both
        assert mgr.ref_count(blocks[1]) == 2       # still shared
        mgr.append_tokens(3, 1)                    # divergent write -> COW
        assert mgr.cow_copies == 1
        assert mgr.blocks_of(1)[1] == blocks[1]
        assert mgr.blocks_of(3)[1] != blocks[1]
        # trim at a block boundary DOES drop the lease: no COW needed on
        # the next append (a fresh private block serves it)
        mgr.adopt(4, blocks, 8)
        mgr.trim(4, 4)
        assert mgr.seq_blocks(4) == 1
        mgr.append_tokens(4, 1)
        assert mgr.cow_copies == 1                 # unchanged
        mgr.check_consistency()

    def test_cow_all_or_nothing_when_pool_empty(self):
        mgr = BlockCacheManager(3, BS, 8)
        blocks = mgr.allocate(1, 6)                # 2 blocks
        mgr.adopt(2, blocks, 6)
        mgr.allocate(3, 4)                         # last free block gone
        with pytest.raises(KVCacheExhausted):
            mgr.append_tokens(2, 1)                # COW needs a free block
        assert mgr.seq_len(2) == 6                 # nothing changed
        assert mgr.cow_copies == 0
        mgr.check_consistency()

    def test_failed_cow_hook_leaves_pool_intact(self):
        mgr = BlockCacheManager(8, BS, 8)
        mgr.set_cow_hook(lambda s, d: (_ for _ in ()).throw(
            RuntimeError("device copy failed")))
        blocks = mgr.allocate(1, 6)
        mgr.adopt(2, blocks, 6)
        free0 = mgr.free_blocks
        with pytest.raises(RuntimeError):
            mgr.append_tokens(2, 1)
        assert mgr.free_blocks == free0
        assert mgr.seq_len(2) == 6
        mgr.check_consistency()


# ------------------------------------------------------------- radix tree

class TestRadixTree:
    def _published(self, mgr, tree, rng, n_tokens, seq_id=100):
        ids = toks(rng, n_tokens)
        mgr.allocate(seq_id, n_tokens)
        tree.publish(seq_id, ids)
        mgr.free(seq_id)
        return ids

    def test_publish_then_full_and_partial_lease(self):
        mgr = BlockCacheManager(16, BS, 8)
        tree = RadixPrefixCache(mgr)
        rng = np.random.default_rng(0)
        ids = self._published(mgr, tree, rng, 12)      # 3 full blocks
        assert tree.num_nodes == 3
        # full-block walk, capped at len-1 (one token must still run)
        hit = tree.lease(1, ids)
        assert hit == 11
        assert mgr.seq_blocks(1) == 3
        # divergence mid-block: 2 full + partial of the 3rd node
        hit2 = tree.lease(2, ids[:6] + toks(rng, 6))
        assert hit2 == 6
        mgr.free(1)
        mgr.free(2)
        mgr.check_consistency(external=tree.block_ref_counts())

    def test_miss_leases_nothing(self):
        mgr = BlockCacheManager(16, BS, 8)
        tree = RadixPrefixCache(mgr)
        rng = np.random.default_rng(1)
        self._published(mgr, tree, rng, 8)
        assert tree.lease(1, toks(rng, 8)) == 0
        assert mgr.seq_blocks(1) == 0                  # caller allocates
        assert tree.misses == 1

    def test_lru_eviction_leaf_up_and_pinned_never_reclaimed(self):
        mgr = BlockCacheManager(16, BS, 8)
        tree = RadixPrefixCache(mgr)
        mgr.set_reclaimer(tree)
        rng = np.random.default_rng(2)
        a = self._published(mgr, tree, rng, 8, seq_id=100)   # path A: 2
        b = self._published(mgr, tree, rng, 8, seq_id=101)   # path B: 2
        tree.lease(1, a)                   # A leased -> pinned (+ LRU hot)
        assert tree.reclaimable() == 2     # only B's chain
        freed = tree.evict(10)
        assert freed == 2                  # B gone leaf-up, A untouched
        assert tree.num_nodes == 2
        assert set(tree.blocks()) == set(mgr.blocks_of(1))
        # A is pinned by the lease: nothing more to evict
        assert tree.evict(10) == 0
        mgr.free(1)
        assert tree.evict(10) == 2         # unpinned now
        mgr.check_consistency(external=tree.block_ref_counts())

    def test_pool_pressure_reclaims_through_manager(self):
        mgr = BlockCacheManager(6, BS, 8)
        tree = RadixPrefixCache(mgr)
        mgr.set_reclaimer(tree)
        rng = np.random.default_rng(3)
        self._published(mgr, tree, rng, 16)            # 4 nodes pinned
        assert mgr.free_blocks == 2
        blocks = mgr.allocate(1, 16)                   # needs 4: evicts
        assert len(blocks) == 4
        assert tree.evictions >= 2
        mgr.check_consistency(external=tree.block_ref_counts())

    def test_eviction_under_pressure_property(self):
        """Randomized lifecycle property test: under constant pool
        pressure, leased (refcount>1) blocks are NEVER reclaimed, no
        block is double-freed, and the pool accounting stays exact
        after every operation."""
        rng = np.random.default_rng(4)
        mgr = BlockCacheManager(24, BS, 8)
        tree = RadixPrefixCache(mgr)
        mgr.set_reclaimer(tree)
        live = {}
        next_id = 0
        vocab_pool = [toks(rng, 16) for _ in range(6)]  # overlapping pool
        for step in range(300):
            op = rng.random()
            if op < 0.5 and len(live) < 6:
                sid = next_id = next_id + 1
                base = vocab_pool[rng.integers(0, len(vocab_pool))]
                n = int(rng.integers(4, 15))
                ids = list(base[:n])
                try:
                    hit = tree.lease(sid, ids)
                    if hit == 0:
                        mgr.allocate(sid, 0)
                        hit = 0
                    leased_shared = list(mgr.blocks_of(sid))
                    mgr.append_tokens(sid, len(ids) - hit)
                except KVCacheExhausted:
                    if mgr.seq_blocks(sid):
                        mgr.free(sid)
                    continue
                live[sid] = ids
                # leased blocks stayed out of the free list
                for b in leased_shared:
                    assert mgr.ref_count(b) >= 1
            elif live:
                sid = list(live)[int(rng.integers(0, len(live)))]
                ids = live.pop(sid)
                if rng.random() < 0.8:
                    tree.publish(sid, ids)
                mgr.free(sid)
            # the standing invariants, after EVERY op
            mgr.check_consistency(external=tree.block_ref_counts())
            for sid in live:
                assert mgr.seq_blocks(sid) >= 1
        for sid in list(live):
            mgr.free(sid)
        mgr.check_consistency(external=tree.block_ref_counts())


# ------------------------------------------------- scheduler integration

class TestSchedulerPrefixCache:
    def test_hit_skips_prefill_chunks(self):
        fe = ServingFrontend(make_engine(), prefix_cache=True,
                             prefill_chunk_tokens=4)
        rng = np.random.default_rng(5)
        prompt = toks(rng, 16)
        h1 = fe.submit(prompt, max_new_tokens=3)
        fe.run_until_idle()
        pre0 = monitor.get("serving.prefill_tokens")
        h2 = fe.submit(prompt, max_new_tokens=3)
        fe.run_until_idle()
        assert h2.status is RequestStatus.FINISHED
        # only the capped final token (and nothing else) prefilled
        assert monitor.get("serving.prefill_tokens") - pre0 <= 2
        assert h2._req._prefix_hit_tokens >= 15
        assert h2.tokens == h1.tokens
        assert fe.scheduler.kv_leaked_blocks() == 0

    def test_full_hit_ttft_is_one_step(self):
        fe = ServingFrontend(make_engine(), prefix_cache=True,
                             prefill_chunk_tokens=4)
        rng = np.random.default_rng(6)
        prompt = toks(rng, 12)
        fe.submit(prompt, max_new_tokens=2)
        fe.run_until_idle()
        h = fe.submit(prompt, max_new_tokens=4)
        pre0 = monitor.get("serving.prefill_tokens")
        fe.step()                          # admission + the ONE chunk
        fe.step()                          # ... whose token this one commits
        assert len(h.tokens) >= 1, \
            "full prefix hit must produce the first token in one round"
        assert monitor.get("serving.prefill_tokens") - pre0 == 1

    def test_preempted_work_republishes_and_rehits(self):
        # publish-at-preempt: the victim's committed KV enters the tree,
        # so its re-admission (and any sibling) leases it back
        fe = ServingFrontend(make_engine(max_batch=2, num_blocks=16),
                             prefix_cache=True, prefill_chunk_tokens=8)
        rng = np.random.default_rng(7)
        hs = [fe.submit(toks(rng, 8), max_new_tokens=10) for _ in range(4)]
        fe.run_until_idle()
        assert all(h.status is RequestStatus.FINISHED for h in hs)
        assert fe.scheduler.kv_leaked_blocks() == 0
        mgr = fe.scheduler.engine.manager
        mgr.check_consistency(
            external=fe.scheduler.prefix_cache.block_ref_counts())

    def test_spec_equals_plain_on_prefix_hit(self):
        rng = np.random.default_rng(8)
        phrase = toks(rng, 3)
        prompt = (phrase * 6)[:14]         # repetitive: drafts accepted

        def run(spec):
            fe = ServingFrontend(
                make_engine(), prefix_cache=True,
                spec=SpecDecodeConfig(NGramProposer(), num_draft_tokens=3)
                if spec else None)
            a = fe.submit(prompt, max_new_tokens=6)
            fe.run_until_idle()
            b = fe.submit(prompt, max_new_tokens=6)
            fe.run_until_idle()
            assert b._req._prefix_hit_tokens > 0
            assert fe.scheduler.kv_leaked_blocks() == 0
            return a.tokens, b.tokens

        plain = run(spec=False)
        spec = run(spec=True)
        assert spec == plain

    def test_session_turns_reuse_response_kv(self):
        # multi-turn: turn 2's prompt = turn 1's prompt + response + new
        # user tokens; the tree serves the WHOLE committed history
        fe = ServingFrontend(make_engine(num_blocks=64,
                                         max_blocks_per_seq=16),
                             prefix_cache=True)
        rng = np.random.default_rng(9)
        turn1 = toks(rng, 12)
        h1 = fe.submit(turn1, max_new_tokens=4)
        fe.run_until_idle()
        turn2 = turn1 + h1.tokens + toks(rng, 4)
        h2 = fe.submit(turn2, max_new_tokens=4)
        fe.run_until_idle()
        assert h2.status is RequestStatus.FINISHED
        # at least the full blocks of turn1 + the committed response hit
        assert h2._req._prefix_hit_tokens >= (len(turn1) + 3) // BS * BS

    def test_metrics_and_profiler_section(self):
        fe = ServingFrontend(make_engine(), prefix_cache=True)
        rng = np.random.default_rng(10)
        prompt = toks(rng, 12)
        fe.submit(prompt, max_new_tokens=2)
        fe.run_until_idle()
        fe.submit(prompt, max_new_tokens=2)
        fe.run_until_idle()
        snap = monitor.snapshot("serving.prefix_cache.")
        assert snap.get("serving.prefix_cache.hits", 0) >= 1
        assert snap.get("serving.prefix_cache.misses", 0) >= 1
        assert snap.get("serving.prefix_cache.hit_tokens", 0) >= 8
        assert snap.get("serving.prefix_cache.hit_rate_pct", 0) > 0
        s = fe.summary()
        assert s["serving.prefix_cache.ttft_cached_p50_ms"] is not None
        assert s["serving.prefix_cache.ttft_cold_p50_ms"] is not None
        from paddle_tpu.profiler.profiler import Profiler

        lines = Profiler._serving_summary_lines()
        assert any("Prefix cache:" in ln for ln in lines), lines

    def test_engine_restart_rebuilds_tree(self):
        from paddle_tpu.resilience import faults
        from paddle_tpu.serving import WatchdogConfig

        fe = ServingFrontend(
            make_engine(), prefix_cache=True,
            watchdog=WatchdogConfig(step_retries=0, max_restarts=1),
            engine_factory=make_engine)
        rng = np.random.default_rng(11)
        prompt = toks(rng, 12)
        fe.submit(prompt, max_new_tokens=2)
        fe.run_until_idle()
        tree0 = fe.scheduler.prefix_cache
        faults.clear()
        faults.inject("serve.decode", after_n=0, times=1)
        h = fe.submit(prompt, max_new_tokens=2)
        fe.run_until_idle()
        faults.clear()
        assert h.finished
        # the restart swapped managers: a FRESH tree on the new pool
        # (the old KV died with the old engine)
        assert fe.scheduler.prefix_cache is not tree0
        assert fe.scheduler.kv_leaked_blocks() == 0


# --------------------------------------------------------- tenant SLOs

class TestTenantSLO:
    def test_quota_defers_without_blocking_others(self):
        slo = SLOConfig([SLOClass("small", kv_quota_blocks=3),
                         SLOClass("big")])
        fe = ServingFrontend(make_engine(max_batch=4), slo=slo)
        rng = np.random.default_rng(12)
        hs = [fe.submit(toks(rng, 6), max_new_tokens=6, tenant="small")
              for _ in range(4)]
        hb = [fe.submit(toks(rng, 6), max_new_tokens=6, tenant="big")
              for _ in range(4)]
        fe.step()
        # small capped at 3 blocks (6+1 tokens = 2 blocks each -> ONE
        # running), big fills the remaining lanes immediately
        running = [r.tenant for r in fe.scheduler.slots if r is not None]
        assert running.count("small") == 1
        assert running.count("big") == 3
        fe.run_until_idle()
        assert all(h.status is RequestStatus.FINISHED for h in hs + hb)
        assert monitor.get("serving.tenant.small.deferred.kv_quota") > 0

    def test_reserve_protects_quiet_tenant(self):
        # burst tenant may not eat into premium's reserved blocks: with
        # 11 usable and 8 reserved, the burst holds <= 3 blocks
        slo = SLOConfig([SLOClass("premium", kv_reserve_blocks=8),
                         SLOClass("burst")])
        fe = ServingFrontend(make_engine(max_batch=4, num_blocks=12),
                             slo=slo)
        rng = np.random.default_rng(13)
        hs = [fe.submit(toks(rng, 4), max_new_tokens=4, tenant="burst")
              for _ in range(6)]
        fe.step()
        mgr = fe.scheduler.engine.manager
        burst_blocks = sum(
            mgr.seq_blocks(r.seq_id) for r in fe.scheduler.slots
            if r is not None and r.tenant == "burst")
        assert burst_blocks <= 3, burst_blocks
        # premium arrives into its guaranteed headroom and admits NOW
        hp = fe.submit(toks(rng, 8), max_new_tokens=4, tenant="premium")
        fe.step()
        assert hp._req.status in (RequestStatus.RUNNING,
                                  RequestStatus.FINISHED)
        fe.run_until_idle()
        assert all(h.status is RequestStatus.FINISHED for h in hs + [hp])

    def test_weighted_lane_shares(self):
        # 3:1 weights -> admissions interleave ~3:1 under contention
        slo = SLOConfig([SLOClass("gold", weight=3.0),
                         SLOClass("econ", weight=1.0)])
        fe = ServingFrontend(make_engine(max_batch=2, num_blocks=48),
                             slo=slo)
        rng = np.random.default_rng(14)
        order = []
        for t in ("gold", "econ"):
            for _ in range(8):
                h = fe.submit(toks(rng, 4), max_new_tokens=4, tenant=t)
                h._req._tag = t
        # drive and record admission order via the running set
        seen = set()
        while not fe.scheduler.idle:
            fe.step()
            for r in fe.scheduler.slots:
                if r is not None and r.req_id not in seen:
                    seen.add(r.req_id)
                    order.append(r.tenant)
        gold_in_first_half = order[:8].count("gold")
        assert gold_in_first_half >= 5, order

    def test_tiered_watermarks_shed_batch_first(self):
        slo = SLOConfig([SLOClass("interactive", admission_scale=1.0),
                         SLOClass("batch", admission_scale=0.25)])
        fe = ServingFrontend(
            make_engine(max_batch=2),
            admission=AdmissionConfig(queue_high=8, queue_low=2),
            slo=slo)
        rng = np.random.default_rng(15)
        # build queue depth 4: over batch's scaled high (2), under
        # interactive's (8)
        hs = [fe.submit(toks(rng, 4), max_new_tokens=8,
                        tenant="interactive") for _ in range(6)]
        hb = fe.submit(toks(rng, 4), max_new_tokens=4, tenant="batch")
        hi = fe.submit(toks(rng, 4), max_new_tokens=4,
                       tenant="interactive")
        assert hb.status is RequestStatus.SHED, hb
        assert hi.status is not RequestStatus.SHED, hi
        fe.run_until_idle()
        assert all(h.finished for h in hs + [hi])

    def test_idle_tenant_accrues_no_arrears(self):
        # tenant B stays idle while A runs many admissions; when B's
        # burst arrives it must INTERLEAVE with A (system virtual clock
        # fast-forward), not monopolize every lane until its banked
        # low clock catches up
        slo = SLOConfig([SLOClass("a", weight=1.0),
                         SLOClass("b", weight=1.0)])
        fe = ServingFrontend(make_engine(max_batch=1, num_blocks=48),
                             slo=slo)
        rng = np.random.default_rng(17)
        for _ in range(10):                    # A alone: clock advances
            fe.submit(toks(rng, 4), max_new_tokens=2, tenant="a")
        fe.run_until_idle()
        for t in ("b",) * 6 + ("a",) * 6:      # B returns with a burst
            fe.submit(toks(rng, 4), max_new_tokens=2, tenant=t)
        order, seen = [], set()
        while not fe.scheduler.idle:
            fe.step()
            for r in fe.scheduler.slots:
                if r is not None and r.req_id not in seen:
                    seen.add(r.req_id)
                    order.append(r.tenant)
        # equal weights -> near-alternation; without the system-clock
        # fast-forward B would take the first 6 lanes outright
        assert order[:6].count("a") >= 2, order

    def test_no_slo_config_is_fifo(self):
        fe = ServingFrontend(make_engine(max_batch=2))
        rng = np.random.default_rng(16)
        hs = [fe.submit(toks(rng, 4), max_new_tokens=2, tenant=t)
              for t in ("a", "b", "c", "d")]
        fe.run_until_idle()
        assert all(h.status is RequestStatus.FINISHED for h in hs)
        # admission was strict FIFO: first tokens in submission order
        t_first = [h._req.t_first_token for h in hs]
        assert t_first == sorted(t_first)
