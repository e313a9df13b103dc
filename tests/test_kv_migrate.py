"""KV-block migration (ISSUE 17, `inference/kv_migrate.py`): the
extract/inject primitive behind disaggregated prefill/decode handoff
and KV-shipping relocation.

Contracts under test:
- extract -> inject round-trips BITWISE on both engine families, full
  precision AND int8 (the scale planes travel in the same payload);
- geometry / kv_bits / engine-family / tp mismatches raise a typed
  `KVMigrationError` BEFORE the target pool is touched (no allocation,
  no partial writes, zero leaked blocks);
- a failed inject AFTER allocation frees the just-allocated blocks;
- tp=2 sharded engines export per-shard slabs that round-trip into an
  identically-sharded engine and refuse a differently-partitioned one;
- the pool's refcount audit (`check_consistency`) is clean after
  inject, and freeing the imported sequence returns the pool to empty;
- `Scheduler.import_session` resumes a released mid-decode request on
  a fresh engine with a BITWISE-identical greedy continuation and no
  re-prefill.
"""
import numpy as np
import pytest

from paddle_tpu.framework import monitor
from paddle_tpu.inference.kv_migrate import (KVBlockPayload,
                                             KVMigrationError,
                                             check_header,
                                             pad_block_indices)
from paddle_tpu.serving import (MLPLMEngine, RequestStatus,
                                ServingFrontend, ServingMetrics,
                                shard_engine)

MLP_KW = dict(vocab_size=64, hidden=16, max_batch_size=4, num_blocks=32,
              block_size=4, max_blocks_per_seq=8, seed=3)


@pytest.fixture(autouse=True)
def _clean_monitor():
    ServingMetrics.reset_monitor()
    yield
    ServingMetrics.reset_monitor()


def _mlp(**over):
    return MLPLMEngine(**{**MLP_KW, **over})


def _fill(eng, seq_id=0, n=7, seed=1):
    """Write `n` tokens of real KV under `seq_id` through one ragged
    dispatch (prompt-only lane); returns the tokens."""
    mgr = eng.manager
    blocks = mgr.allocate(seq_id, n)
    rng = np.random.default_rng(seed)
    toks = rng.integers(1, 60, n).astype(np.int32)
    q = np.zeros(4, np.int32)
    kv = np.zeros(4, np.int32)
    q[0] = kv[0] = n
    tables = np.zeros((4, mgr.max_blocks_per_seq), np.int32)
    tables[0, :len(blocks)] = blocks
    eng.ragged_step(toks, q, kv, tables)
    return toks


def _slabs_equal(a, b):
    return set(a) == set(b) and all(
        np.array_equal(np.asarray(a[k]), np.asarray(b[k])) for k in a)


# ---------------------------------------------------------------------------
# payload plumbing
# ---------------------------------------------------------------------------

class TestPayloadPlumbing:
    def test_pad_block_indices(self):
        idx = pad_block_indices([3, 7, 1], 8)
        assert idx.dtype == np.int32 and idx.shape == (8,)
        assert idx.tolist() == [3, 7, 1, 1, 1, 1, 1, 1]

    def test_pad_rejects_empty_and_overflow(self):
        with pytest.raises(KVMigrationError):
            pad_block_indices([], 4)
        with pytest.raises(KVMigrationError):
            pad_block_indices([1, 2, 3, 4, 5], 4)

    def test_check_header_names_the_field(self):
        with pytest.raises(KVMigrationError, match="kv_bits"):
            check_header({"kv_bits": 8}, {"kv_bits": 16})
        with pytest.raises(KVMigrationError, match="block_size"):
            check_header({}, {"block_size": 4})

    def test_nbytes_scales_with_real_blocks(self):
        eng = _mlp()
        _fill(eng, n=7)                  # 2 of 8 index slots real
        p = eng.extract_kv_blocks(0)
        full = sum(int(np.asarray(s).nbytes) for s in p.slabs.values())
        assert p.nbytes == full * 2 // 8
        assert p.num_tokens == 7 and p.num_blocks == 2


# ---------------------------------------------------------------------------
# MLP engine round-trips
# ---------------------------------------------------------------------------

class TestMLPRoundTrip:
    def test_bitwise_full_precision(self):
        src = _mlp()
        _fill(src, seq_id=0, n=7)
        p = src.extract_kv_blocks(0)
        # extraction is a copy: source blocks still resident
        assert src.manager.seq_blocks(0) == 2
        dst = _mlp()
        dst.inject_kv_blocks(5, p)
        assert dst.manager.seq_len(5) == 7
        assert len(dst.manager.blocks_of(5)) == 2
        q = dst.extract_kv_blocks(5)
        assert _slabs_equal(p.slabs, q.slabs)
        dst.manager.check_consistency()

    def test_bitwise_int8_scales_travel(self):
        src = _mlp(kv_bits=8)
        _fill(src, seq_id=0, n=9)
        p = src.extract_kv_blocks(0)
        assert set(p.slabs) == {"cache", "scale"}
        assert np.asarray(p.slabs["cache"]).dtype == np.int8
        dst = _mlp(kv_bits=8)
        dst.inject_kv_blocks(2, p)
        q = dst.extract_kv_blocks(2)
        assert _slabs_equal(p.slabs, q.slabs)
        dst.manager.check_consistency()

    def test_free_returns_pool_to_empty(self):
        src = _mlp()
        _fill(src, n=7)
        dst = _mlp()
        free0 = dst.manager.free_blocks
        dst.inject_kv_blocks(1, src.extract_kv_blocks(0))
        assert dst.manager.free_blocks == free0 - 2
        dst.manager.free(1)
        assert dst.manager.free_blocks == free0
        dst.manager.check_consistency()

    def test_extract_without_blocks_is_typed(self):
        with pytest.raises(KVMigrationError):
            _mlp().extract_kv_blocks(99)


# ---------------------------------------------------------------------------
# typed mismatches, checked BEFORE the target pool is touched
# ---------------------------------------------------------------------------

class TestTypedMismatch:
    def _payload(self, **over):
        src = _mlp(**over)
        _fill(src, n=7)
        return src.extract_kv_blocks(0)

    @pytest.mark.parametrize("field,target_kw", [
        ("block_size", dict(block_size=8, max_blocks_per_seq=4)),
        # sorted-key check: the int8 cache's dtype plane trips first
        ("kv_bits|dtype", dict(kv_bits=8)),
    ])
    def test_geometry_mismatch_pre_inject(self, field, target_kw):
        p = self._payload()
        dst = _mlp(**target_kw)
        free0 = dst.manager.free_blocks
        with pytest.raises(KVMigrationError, match=field):
            dst.inject_kv_blocks(0, p)
        # raised BEFORE allocation: pool untouched, nothing leaked
        assert dst.manager.free_blocks == free0
        assert dst.manager.seq_blocks(0) == 0
        dst.manager.check_consistency()

    def test_tampered_block_count_frees_on_failure(self):
        p = self._payload()
        bad = KVBlockPayload(dict(p.header, num_tokens=3), p.slabs)
        dst = _mlp()
        free0 = dst.manager.free_blocks
        with pytest.raises(KVMigrationError, match="blocks"):
            dst.inject_kv_blocks(0, bad)
        # failed AFTER allocation: the just-allocated run was freed
        assert dst.manager.free_blocks == free0
        assert dst.manager.seq_blocks(0) == 0
        dst.manager.check_consistency()

    def test_version_pinned(self):
        p = self._payload()
        bad = KVBlockPayload(dict(p.header, version=0), p.slabs)
        with pytest.raises(KVMigrationError, match="version"):
            _mlp().inject_kv_blocks(0, bad)


# ---------------------------------------------------------------------------
# llama engine round-trips (bf16 pools + int8 with K/V scale planes)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def llama_model():
    from paddle_tpu.models import llama_tiny

    m = llama_tiny(vocab=128, layers=2, hidden=64, heads=4, seq=256)
    m.eval()
    return m


def _llama(model, kv_bits=16):
    from paddle_tpu.inference import LlamaInferenceEngine

    return LlamaInferenceEngine(model, max_batch_size=4, num_blocks=32,
                                block_size=8, max_blocks_per_seq=8,
                                kv_bits=kv_bits)


class TestLlamaRoundTrip:
    @pytest.mark.parametrize("kv_bits,slab_keys", [
        (16, {"k", "v"}),
        (8, {"k", "v", "k_scale", "v_scale"}),
    ])
    def test_bitwise(self, llama_model, kv_bits, slab_keys):
        src = _llama(llama_model, kv_bits)
        _fill(src, seq_id=0, n=11)
        p = src.extract_kv_blocks(0)
        assert set(p.slabs) == slab_keys
        dst = _llama(llama_model, kv_bits)
        dst.inject_kv_blocks(3, p)
        assert dst.manager.seq_len(3) == 11
        q = dst.extract_kv_blocks(3)
        assert _slabs_equal(p.slabs, q.slabs)
        dst.manager.check_consistency()

    def test_family_mismatch_typed(self, llama_model):
        src = _mlp(block_size=8)
        _fill(src, n=7)
        p = src.extract_kv_blocks(0)
        dst = _llama(llama_model)
        free0 = dst.manager.free_blocks
        with pytest.raises(KVMigrationError, match="engine"):
            dst.inject_kv_blocks(0, p)
        assert dst.manager.free_blocks == free0


# ---------------------------------------------------------------------------
# TP-sharded engines: per-shard export, partition pinning
# ---------------------------------------------------------------------------

class TestShardedRoundTrip:
    @pytest.mark.parametrize("kv_bits", [16, 8])
    def test_tp2_roundtrip_bitwise(self, kv_bits):
        src = shard_engine(_mlp(kv_bits=kv_bits), tp=2)
        _fill(src, seq_id=0, n=7)
        p = src.extract_kv_blocks(0)
        assert p.header["tp"] == 2
        assert set(p.slabs) == ({"p0", "p1"} if kv_bits == 8 else {"p0"})
        dst = shard_engine(_mlp(kv_bits=kv_bits), tp=2)
        dst.inject_kv_blocks(4, p)
        q = dst.extract_kv_blocks(4)
        assert _slabs_equal(p.slabs, q.slabs)
        dst.manager.check_consistency()

    def test_tp_mismatch_typed(self):
        src = shard_engine(_mlp(), tp=2)
        _fill(src, n=7)
        p = src.extract_kv_blocks(0)
        # a tp=2 payload must not inject into a single-chip engine...
        dst_plain = _mlp()
        with pytest.raises(KVMigrationError, match="tp"):
            dst_plain.inject_kv_blocks(0, p)
        # ...nor into a tp=4 one
        dst4 = shard_engine(_mlp(), tp=4)
        free0 = dst4.manager.free_blocks
        with pytest.raises(KVMigrationError, match="tp"):
            dst4.inject_kv_blocks(0, p)
        assert dst4.manager.free_blocks == free0


# ---------------------------------------------------------------------------
# scheduler-level import: a released session resumes bitwise, no prefill
# ---------------------------------------------------------------------------

class TestImportSession:
    def _run_reference(self, prompt, max_new):
        fe = ServingFrontend(_mlp(), stall_after=256)
        h = fe.submit(prompt, max_new_tokens=max_new)
        fe.run_until_idle()
        assert h.status is RequestStatus.FINISHED
        return h.tokens

    def test_shipped_session_resumes_bitwise(self):
        prompt = [5, 9, 13, 2, 40, 11]
        reference = self._run_reference(prompt, 8)

        fe1 = ServingFrontend(_mlp(), stall_after=256)
        h = fe1.submit(prompt, max_new_tokens=8)
        req = h._req
        while len(req.generated) < 3:
            fe1.step()
        # reading a running sequence's KV from outside a round: the round
        # in flight is committed first (`Scheduler.settle`)
        fe1.scheduler.settle()
        carried = list(req.generated)
        payload = fe1.scheduler.engine.extract_kv_blocks(req.seq_id)
        assert fe1.release(h)
        assert fe1.scheduler.kv_leaked_blocks() == 0

        fe2 = ServingFrontend(_mlp(), stall_after=256)
        prefills0 = monitor.get("serving.prefills")
        fe2.import_session(req, payload)
        fe2.run_until_idle()
        assert h.status is RequestStatus.FINISHED
        # the stream CONTINUED (tokens kept, no fold) and matches the
        # uninterrupted run bitwise
        assert req.generated[:len(carried)] == carried
        assert h.tokens == reference
        # no re-prefill happened on the importing engine
        assert monitor.get("serving.prefills") == prefills0
        assert fe2.scheduler.kv_leaked_blocks() == 0
        fe2.scheduler.engine.manager.check_consistency()

    def test_import_without_primitive_is_typed(self):
        class NoMigrationEngine(MLPLMEngine):
            extract_kv_blocks = None
            inject_kv_blocks = None

        src = _mlp()
        _fill(src, n=4)
        payload = src.extract_kv_blocks(0)
        fe = ServingFrontend(NoMigrationEngine(**MLP_KW), stall_after=256)
        h = fe.submit([1, 2, 3, 4], max_new_tokens=4)
        req = h._req
        fe.release(h)
        with pytest.raises(KVMigrationError):
            fe.import_session(req, payload)

    def test_oversized_payload_rejected_not_raised(self):
        """A context the target pool structurally cannot hold comes back
        terminal `prompt_too_long` BEFORE the pool is touched (load
        condition, not a typed migration error)."""
        src = _mlp()
        toks = _fill(src, n=20)
        payload = src.extract_kv_blocks(0)
        big = ServingFrontend(_mlp(), stall_after=256)
        h = big.submit(toks.tolist(), max_new_tokens=4)
        req = h._req
        big.release(h)
        tiny = ServingFrontend(_mlp(max_blocks_per_seq=4), stall_after=256)
        free0 = tiny.scheduler.engine.manager.free_blocks
        tiny.import_session(req, payload)
        assert req.status is RequestStatus.REJECTED
        assert req.finish_reason == "prompt_too_long"
        assert tiny.scheduler.engine.manager.free_blocks == free0
        tiny.scheduler.engine.manager.check_consistency()


# ---------------------------------------------------------------------------
# cross-replica prefix streaming (scheduler-level primitive reuse)
# ---------------------------------------------------------------------------

class TestPrefixStreaming:
    """`export_prefix`/`import_prefix`: the radix tree's full-block
    cached prefix rides the SAME migration payload as a handoff, and a
    published import makes the next local lease hit with a bitwise-
    identical continuation (cross-replica prefix reuse, ISSUE 17)."""

    PROMPT = list(range(1, 13))     # 12 tokens = 3 full blocks (bs=4)

    def _fe(self, **over):
        return ServingFrontend(_mlp(**over), prefix_cache=True,
                               stall_after=256)

    def _publish_on(self, fe, max_new=6):
        h = fe.submit(self.PROMPT, max_new_tokens=max_new)
        fe.run_until_idle()
        assert h.status is RequestStatus.FINISHED
        return h.tokens

    def test_export_import_roundtrip_bitwise(self):
        fe1, fe2 = self._fe(), self._fe()
        ref = self._publish_on(fe1)
        blocks, hit = fe1.scheduler.prefix_cache.match_export(self.PROMPT)
        assert hit == 12 and len(blocks) == 3   # full blocks, no -1 cap
        payload = fe1.scheduler.export_prefix(self.PROMPT)
        assert payload is not None
        assert payload.num_tokens == 12 and payload.num_blocks == 3

        free0 = fe2.scheduler.engine.manager.free_blocks
        assert fe2.scheduler.import_prefix(self.PROMPT, payload) == 12
        # the blocks now live as tree pins, not a sequence lease
        assert fe2.scheduler.engine.manager.free_blocks == free0 - 3
        assert fe2.scheduler.kv_leaked_blocks() == 0
        hit_tokens0 = monitor.get("serving.prefix_cache.hit_tokens")
        assert self._publish_on(fe2) == ref     # lease hits, bitwise
        assert monitor.get("serving.prefix_cache.hit_tokens") \
            - hit_tokens0 >= 8
        for fe in (fe1, fe2):
            fe.scheduler.engine.manager.check_consistency()

    def test_extraction_leaves_source_untouched(self):
        fe1 = self._fe()
        self._publish_on(fe1)
        mgr = fe1.scheduler.engine.manager
        free0 = mgr.free_blocks
        cache0 = np.asarray(fe1.scheduler.engine.pools[0]).copy()
        fe1.scheduler.export_prefix(self.PROMPT)
        assert mgr.free_blocks == free0         # transient lease freed
        assert np.array_equal(np.asarray(fe1.scheduler.engine.pools[0]),
                              cache0)
        mgr.check_consistency()

    def test_import_is_idempotent_and_capacity_safe(self):
        fe1, fe2 = self._fe(), self._fe()
        self._publish_on(fe1)
        payload = fe1.scheduler.export_prefix(self.PROMPT)
        assert fe2.scheduler.import_prefix(self.PROMPT, payload) == 12
        # already covered locally -> no second copy, no pool churn
        free1 = fe2.scheduler.engine.manager.free_blocks
        assert fe2.scheduler.import_prefix(self.PROMPT, payload) == 0
        assert fe2.scheduler.engine.manager.free_blocks == free1
        # a pool with no room refuses quietly (streams must not
        # pressure a loaded pool) -- num_blocks=4 leaves 3 free after
        # the pad guard, the 3-block payload needs them all... shrink
        # further: max_blocks_per_seq bounds the transient lease too
        tiny = ServingFrontend(_mlp(max_blocks_per_seq=2),
                               prefix_cache=True, stall_after=256)
        assert tiny.scheduler.import_prefix(self.PROMPT, payload) == 0
        tiny.scheduler.engine.manager.check_consistency()

    def test_cold_or_disabled_export_returns_none(self):
        cold = self._fe()
        assert cold.scheduler.export_prefix(self.PROMPT) is None
        off = ServingFrontend(_mlp(), stall_after=256)   # cache off
        assert off.scheduler.export_prefix(self.PROMPT) is None
        assert off.scheduler.import_prefix(
            self.PROMPT, object()) == 0

    def test_geometry_mismatch_propagates_typed(self):
        fe1 = self._fe()
        self._publish_on(fe1)
        payload = fe1.scheduler.export_prefix(self.PROMPT)
        other = ServingFrontend(_mlp(block_size=8), prefix_cache=True,
                                stall_after=256)
        free0 = other.scheduler.engine.manager.free_blocks
        with pytest.raises(KVMigrationError):
            other.scheduler.import_prefix(self.PROMPT, payload)
        assert other.scheduler.engine.manager.free_blocks == free0
        other.scheduler.engine.manager.check_consistency()
