"""Quantized serving runtime (ISSUE 14): int4 pack/unpack round trips,
the Pallas int4 gemm, observers under jit (bf16 inputs, bits=4
fake-quant), `serving.quant.quantize_engine` weight passes, int8 paged
KV pools (quantize-on-write, in-kernel dequant, scale-atomic COW),
quantized-vs-full-precision greedy agreement per engine, spec==plain
parity under quantization, zero-retrace steady state, and the
byte-auditable capacity telemetry (fragmentation + OOM dump schema).
"""
import json
import os

import numpy as np
import pytest

import paddle_tpu.observability as obs
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.framework import monitor
from paddle_tpu.nn import quant as Q
from paddle_tpu.observability import memory
from paddle_tpu.serving import (MLPLMEngine, NGramProposer, RequestStatus,
                                ServingFrontend, ServingMetrics,
                                SpecDecodeConfig, greedy_agreement,
                                quant_summary, quantize_engine)


@pytest.fixture(autouse=True)
def _clean_monitor():
    ServingMetrics.reset_monitor()
    yield
    ServingMetrics.reset_monitor()
    obs.disable()
    obs.reset()
    memory.configure(flight_dir="profiler_log", min_dump_interval_s=30.0)


def _finish_all(fe, prompts, max_new=6):
    hs = [fe.submit(p, max_new_tokens=max_new) for p in prompts]
    fe.run_until_idle(max_steps=2000)
    assert all(h.status is RequestStatus.FINISHED for h in hs), \
        [(h.status, h.finish_reason) for h in hs]
    return hs


def _prompts(n=6, vocab=256, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, int(rng.integers(3, 20))).tolist()
            for _ in range(n)]


# ---------------------------------------------------------------------------
# int4 pack/unpack + gemm (satellite 1) — enumerated, derived tolerances
# ---------------------------------------------------------------------------

class TestInt4:
    # every case exact by construction — no magic tolerances
    PACK_SHAPES = [(1, 2), (2, 4), (3, 8), (4, 16), (2, 3, 4)]

    @pytest.mark.parametrize("shape", PACK_SHAPES,
                             ids=[str(s) for s in PACK_SHAPES])
    def test_pack_unpack_roundtrip(self, shape):
        """Round trip is EXACT for every representable int4 value; the
        full [-8, 7] range is swept cyclically across each shape."""
        n = int(np.prod(shape))
        q = (np.arange(n, dtype=np.int64) % 16 - 8).astype(
            np.int8).reshape(shape)
        packed = np.asarray(Q.pack_int4(q))
        assert packed.shape == shape[:-1] + (shape[-1] // 2,)
        assert packed.dtype == np.int8
        back = np.asarray(Q.unpack_int4(packed))
        np.testing.assert_array_equal(back, q)

    def test_pack_all_nibble_pairs(self):
        """All 256 (lo, hi) nibble combinations survive the byte."""
        lo, hi = np.meshgrid(np.arange(-8, 8), np.arange(-8, 8))
        q = np.concatenate([lo.reshape(1, -1), hi.reshape(1, -1)],
                           axis=-1).astype(np.int8)    # [1, 512] split-half
        back = np.asarray(Q.unpack_int4(Q.pack_int4(q)))
        np.testing.assert_array_equal(back, q)

    def test_pack_odd_axis_raises(self):
        with pytest.raises(ValueError, match="even"):
            Q.pack_int4(np.zeros((2, 3), np.int8))

    def test_weight_quantize_int4_roundtrip_bound(self):
        """weight_quantize(int4) -> weight_dequantize error is bounded
        by half a quantization step PER CHANNEL (scale = absmax/7): the
        tolerance is derived from the stored scale, not asserted as a
        constant."""
        rng = np.random.default_rng(0)
        w = rng.normal(0, 1, (16, 24)).astype(np.float32)      # [K, N]
        wq, scale = Q.weight_quantize(Tensor(w), algo="weight_only_int4")
        back = np.asarray(Q.weight_dequantize(
            wq, scale, algo="weight_only_int4", out_dtype="float32")._data)
        step = np.asarray(scale._data)[None, :]                # [1, N]
        assert (np.abs(back - w) <= step / 2 + 1e-7).all()

    def test_dequant_matmul_int4_matches_unpacked(self):
        """The int4 execution path == the explicitly dequantized matmul,
        to f32 summation-order error."""
        import jax.numpy as jnp

        rng = np.random.default_rng(1)
        x = rng.normal(0, 1, (5, 16)).astype(np.float32)
        wq, scale = Q.weight_quantize(
            Tensor(rng.normal(0, 1, (16, 8)).astype(np.float32)),
            algo="weight_only_int4")
        wq, scale = wq._data, scale._data
        out = np.asarray(Q.dequant_matmul(jnp.asarray(x), wq, scale, "int4"))
        wf = np.asarray(Q.unpack_int4(wq)).astype(np.float32) \
            * np.asarray(scale)[:, None]
        # Both sides are f32 K-term dot products of the SAME dequantized
        # operands, summed in different orders (XLA's gemm vs numpy's).
        # Each lies within gamma_K * sum_i |x_i w_i| of the exact value
        # (gamma_K ~= K * u, u = 2**-24 the f32 unit roundoff), so they
        # differ by at most twice that — a bound relative to the summed
        # magnitudes, not to the result, which cancellation makes small.
        k = x.shape[1]
        bound = 2 * k * 2.0 ** -24 * (np.abs(x) @ np.abs(wf).T)
        assert (np.abs(out - x @ wf.T) <= bound).all()

    def test_quant_matmul_int4_kernel(self):
        """The Pallas packed-int4 gemm (interpreter mode on CPU) against
        the dequantized reference."""
        from paddle_tpu.framework import flags
        from paddle_tpu.ops.pallas import quant_matmul as qm

        old = flags.flag_value("pallas_interpret")
        flags.set_flags({"FLAGS_pallas_interpret": True})
        try:
            rng = np.random.default_rng(2)
            m, k, n = 8, 256, 128
            wq, scale = Q.weight_quantize(
                Tensor(rng.normal(0, 1, (k, n)).astype(np.float32)),
                algo="weight_only_int4")
            wq, scale = wq._data, scale._data
            x = rng.normal(0, 1, (m, k)).astype(np.float32)
            wf = np.asarray(Q.unpack_int4(wq)).astype(np.float32) \
                * np.asarray(scale)[:, None]
            ref = x @ wf.T
            out = np.asarray(qm.quant_matmul_int4(x, wq, scale))
            np.testing.assert_allclose(out, ref, atol=1e-4)
            assert qm.int4_supported((m, k), np.asarray(wq).shape, "int8")
            assert not qm.int4_supported((m, k + 2), np.asarray(wq).shape,
                                         "int8")
        finally:
            flags.set_flags({"FLAGS_pallas_interpret": old})


# ---------------------------------------------------------------------------
# observers under jit / on bf16 (satellite 3)
# ---------------------------------------------------------------------------

class TestObservers:
    def test_absmax_observer_bf16(self):
        import jax.numpy as jnp

        from paddle_tpu.quantization import AbsmaxObserver

        x = jnp.asarray([[-3.0, 1.5], [2.0, -0.5]], jnp.bfloat16)
        ob = AbsmaxObserver(quant_bits=8)
        ob.observe(x)
        assert ob.scale() == pytest.approx(3.0, rel=0.01)
        ob.observe(jnp.asarray([[4.0]], jnp.bfloat16))  # running max
        assert ob.scale() == pytest.approx(4.0, rel=0.01)

    def test_hist_observer_bf16(self):
        import jax.numpy as jnp

        from paddle_tpu.quantization import HistObserver

        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(0, 1, (64, 64)), jnp.bfloat16)
        ob = HistObserver(quant_bits=8, percent=0.999)
        ob.observe(x)
        s = ob.scale()
        a = np.abs(np.asarray(x, np.float32))
        # the percentile clip sits inside the observed range, above the
        # bulk of the mass
        assert 0 < s <= a.max() * 1.01
        assert s >= np.percentile(a, 90)

    def test_channel_absmax_observer(self):
        from paddle_tpu.quantization import ChannelAbsmaxObserver

        w1 = np.array([[1.0, -2.0], [0.5, 0.25]], np.float32)  # [N=2, K]
        w2 = np.array([[-3.0, 0.0], [0.1, 0.1]], np.float32)
        for bits, qmax in ((8, 127.0), (4, 7.0)):
            ob = ChannelAbsmaxObserver(quant_bits=bits)
            ob.observe(w1)
            ob.observe(w2)                       # running per-channel max
            np.testing.assert_allclose(ob.absmax(), [3.0, 0.5])
            np.testing.assert_allclose(ob.scales(),
                                       np.array([3.0, 0.5]) / qmax)
            assert ob.scale() == pytest.approx(3.0)

    def test_channel_observer_bf16_and_empty(self):
        import jax.numpy as jnp

        from paddle_tpu.quantization import ChannelAbsmaxObserver

        ob = ChannelAbsmaxObserver()
        with pytest.raises(RuntimeError, match="no data"):
            ob.scales()
        ob.observe(jnp.asarray([[1.5, -2.5]], jnp.bfloat16))
        assert ob.absmax().dtype == np.float32
        np.testing.assert_allclose(ob.absmax(), [2.5], rtol=0.01)

    @pytest.mark.parametrize("bits", [4, 8])
    def test_quant_dequant_under_jit(self, bits):
        """`quant_dequant` traces under jit with a traced scale; bits=4
        (previously only bits=8 was exercised anywhere) matches the
        manual symmetric fake-quant formula."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.quantization import quant_dequant

        x = jnp.asarray(np.linspace(-2, 2, 17), jnp.float32)
        scale = jnp.float32(2.0)
        out = jax.jit(lambda a, s: quant_dequant(a, s, bits=bits))(x, scale)
        qmax = float(2 ** (bits - 1) - 1)
        ref = np.clip(np.round(np.asarray(x) / 2.0 * qmax), -qmax,
                      qmax) * 2.0 / qmax
        np.testing.assert_allclose(np.asarray(out), ref, atol=1e-6)

    def test_quant_dequant_bits4_bf16_jit(self):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.quantization import quant_dequant

        x = jnp.asarray([0.4, -1.9], jnp.bfloat16)
        out = jax.jit(lambda a: quant_dequant(a, jnp.float32(2.0),
                                              bits=4))(x)
        assert np.isfinite(np.asarray(out, np.float32)).all()


# ---------------------------------------------------------------------------
# kv_quant primitives
# ---------------------------------------------------------------------------

class TestKvQuant:
    def test_quantize_roundtrip_bound(self):
        """Per-(token, head) symmetric int8: error bounded by half a
        step (amax / 254) — derived from the stored scale."""
        from paddle_tpu.inference import kv_quant

        rng = np.random.default_rng(0)
        x = rng.normal(0, 2, (5, 3, 16)).astype(np.float32)
        q, s = kv_quant.quantize_kv(x)
        back = np.asarray(kv_quant.dequantize_kv(np.asarray(q),
                                                 np.asarray(s)))
        step = np.asarray(s)[..., None]          # scale == amax/127
        assert (np.abs(back - x) <= step / 2 + 1e-7).all()

    def test_zero_vectors_exact(self):
        from paddle_tpu.inference import kv_quant

        q, s = kv_quant.quantize_kv(np.zeros((2, 4), np.float32))
        assert np.asarray(q).sum() == 0 and np.asarray(s).sum() == 0
        assert np.asarray(kv_quant.dequantize_kv(
            np.asarray(q), np.asarray(s))).sum() == 0

    def test_bytes_accounting(self):
        from paddle_tpu.inference import kv_quant

        # int8: data + one f32 per (head, slot); 16-bit native: 2B/elem
        assert kv_quant.kv_bytes_per_block(4, 8, 64, 8) \
            == 2 * (4 * 8 * 64 + 4 * 8 * 4)
        assert kv_quant.kv_bytes_per_block(4, 8, 64, 16, dtype_bytes=2) \
            == 2 * 4 * 8 * 64 * 2
        # per token = per block / block_size
        assert kv_quant.kv_bytes_per_token(4, 8, 64, 8) \
            == kv_quant.kv_bytes_per_block(4, 8, 64, 8) / 8

    def test_ragged_write_guard_slots_dropped(self):
        import jax.numpy as jnp

        from paddle_tpu.ops.pallas import paged_attention as pk

        NB, KVH, BS, D = 4, 2, 4, 8
        kq = jnp.zeros((NB, KVH, BS, D), jnp.int8)
        vq = jnp.zeros_like(kq)
        ks = jnp.zeros((NB, KVH, BS), jnp.float32)
        vs = jnp.zeros_like(ks)
        tables = np.zeros((1, 2), np.int32)
        lane = jnp.zeros((3,), jnp.int32)
        pos = jnp.asarray([0, -1, -1], jnp.int32)   # 2 guard slots
        k = jnp.ones((3, KVH, D), jnp.float32)
        kq, vq, ks, vs = pk.write_kv_to_cache_ragged(
            k, k, kq, vq, tables, lane, pos, ks, vs)
        # only position 0 of block 0 written; guard scales stay zero
        assert np.asarray(ks)[0, :, 0].min() > 0
        assert np.asarray(ks).sum() == np.asarray(ks)[0, :, 0].sum()


# ---------------------------------------------------------------------------
# quantize_engine + serving accuracy (the tentpole contract)
# ---------------------------------------------------------------------------

class TestQuantizeEngine:
    def test_validation(self):
        eng = MLPLMEngine(seed=1)
        with pytest.raises(ValueError, match="wbits"):
            quantize_engine(eng, wbits=2)
        quantize_engine(eng, wbits=8)
        with pytest.raises(ValueError, match="already quantized"):
            quantize_engine(eng, wbits=8)
        with pytest.raises(TypeError):
            quantize_engine(object())

    def test_kv_bits_validation(self):
        with pytest.raises(ValueError, match="kv_bits"):
            MLPLMEngine(kv_bits=12)

    @pytest.mark.parametrize("wbits", [8, 4])
    def test_mlp_agreement(self, wbits):
        q = quantize_engine(MLPLMEngine(seed=3, kv_bits=8), wbits=wbits)
        info = quant_summary(q)
        assert info["wbits"] == wbits and info["kv_bits"] == 8
        assert info["kv_bytes_per_token"] == q.kv_bytes_per_token()
        r = greedy_agreement(q, MLPLMEngine(seed=3), _prompts())
        assert r["agreement_tie_aware"] >= 0.99, r
        if wbits == 8:
            # strict agreement only binds where the perturbation is far
            # below typical logit gaps; the toy MLP's near-flat logits
            # make strict int4 agreement a coin-flip census (tie-aware
            # is the contract, max_logit_err the evidence)
            assert r["agreement"] >= 0.9, r
        # the logit perturbation stays well under one logit unit
        assert r["max_logit_err"] < (0.05 if wbits == 8 else 0.5), r

    def test_greedy_agreement_frees_lease_on_fault(self):
        """A raising dispatch must not strand the synthetic lease the
        agreement probe allocates (review regression: try/finally)."""
        eng = MLPLMEngine(seed=3)
        free = eng.manager.free_blocks

        def boom(*_a):
            raise RuntimeError("boom")

        eng.ragged_step = boom
        with pytest.raises(RuntimeError, match="boom"):
            greedy_agreement(eng, MLPLMEngine(seed=3), [[1, 2, 3]])
        assert eng.manager.free_blocks == free

    def test_kv8_only_agreement(self):
        r = greedy_agreement(MLPLMEngine(seed=3, kv_bits=8),
                             MLPLMEngine(seed=3), _prompts())
        assert r["agreement_tie_aware"] >= 0.99, r

    def test_quantized_serving_end_to_end(self):
        """Quantized MLP serving: every request finishes, steady state
        performs zero ragged/sample retraces after warmup, pool clean."""
        eng = quantize_engine(MLPLMEngine(seed=3, kv_bits=8), wbits=8)
        fe = ServingFrontend(eng)
        _finish_all(fe, _prompts(3))             # warmup traffic
        monitor.reset("serving.ragged_retraces")
        monitor.reset("serving.sample_retraces")
        _finish_all(fe, _prompts(6, seed=7))
        assert monitor.get("serving.ragged_retraces") == 0
        assert monitor.get("serving.sample_retraces") == 0
        assert fe.scheduler.kv_leaked_blocks() == 0
        eng.manager.check_consistency()

    def test_spec_plain_parity_quantized(self):
        """spec==plain token parity holds ON the quantized stack (both
        runs share the quantized engine config — greedy streams must be
        bitwise identical, the PR 4 invariant under quantization)."""
        rng = np.random.default_rng(0)
        prompts = []
        for i in range(6):
            phrase = rng.integers(1, 256, int(rng.integers(2, 4))).tolist()
            prompts.append((phrase * 5)[:int(rng.integers(6, 13))])

        def run(spec):
            eng = quantize_engine(MLPLMEngine(seed=3, kv_bits=8), wbits=8)
            fe = ServingFrontend(
                eng, spec=SpecDecodeConfig(NGramProposer(),
                                           num_draft_tokens=3)
                if spec else None)
            return [h.tokens for h in _finish_all(fe, prompts)]

        assert run(spec=True) == run(spec=False)

    def test_respawn_keeps_quant_pool(self):
        eng = MLPLMEngine(kv_bits=8)
        fresh = eng.respawn()
        assert fresh.kv_bits == 8 and fresh.pools[0].dtype == np.int8

    def test_quant_gauges_and_profiler_section(self):
        from paddle_tpu.profiler import profiler as prof_mod

        eng = quantize_engine(MLPLMEngine(seed=3, kv_bits=8), wbits=8)
        fe = ServingFrontend(eng)
        assert monitor.get("serving.quant.wbits") == 8
        assert monitor.get("serving.quant.kv_bits") == 8
        assert monitor.get("serving.kv_bytes_per_token") \
            == pytest.approx(eng.kv_bytes_per_token(), rel=0.01)
        _finish_all(fe, _prompts(2))
        text = "\n".join(
            prof_mod.Profiler._serving_summary_lines())
        assert "quant: weights int8, KV int8" in text


# ---------------------------------------------------------------------------
# COW with scale planes (prefix cache on the int8 pool)
# ---------------------------------------------------------------------------

class TestQuantCow:
    def test_cow_copies_scale_atomically(self):
        """Shared-prefix serving on an int8 pool: the divergent append
        COWs the shared block (q + scale move together), and the cached
        run's streams match the uncached quantized run's bitwise."""
        rng = np.random.default_rng(0)
        shared = rng.integers(1, 256, 13).tolist()
        prompts = [shared + rng.integers(1, 256, 3).tolist()
                   for _ in range(3)]

        def run(prefix_cache):
            eng = quantize_engine(MLPLMEngine(seed=3, kv_bits=8,
                                              num_blocks=96,
                                              max_blocks_per_seq=8),
                                  wbits=8)
            fe = ServingFrontend(eng, prefix_cache=prefix_cache)
            seedh = _finish_all(fe, [shared])    # publish the prefix
            toks = [h.tokens for h in _finish_all(fe, prompts)]
            sched = fe.scheduler
            assert sched.kv_leaked_blocks() == 0
            if prefix_cache:
                tree = sched.prefix_cache
                assert tree.stats()["hits"] > 0, tree.stats()
                assert eng.manager.cow_copies > 0, \
                    "divergent append into the shared block never COWed"
                eng.manager.check_consistency(
                    external=tree.block_ref_counts())
            return toks

        assert run(prefix_cache=True) == run(prefix_cache=False)


# ---------------------------------------------------------------------------
# telemetry: fragmentation bytes + OOM dump schema (satellite 2)
# ---------------------------------------------------------------------------

class TestCapacityTelemetry:
    def test_fragmentation_reports_byte_geometry(self):
        q = MLPLMEngine(kv_bits=8)
        f = MLPLMEngine(kv_bits=16)
        fq, ff = q.manager.fragmentation(), f.manager.fragmentation()
        assert fq["kv_bits"] == 8 and ff["kv_bits"] == 16
        assert fq["bytes_per_block"] == q.block_size * 32 + q.block_size * 4
        # int8 + scale vs f32: >= 2x blocks per byte for the MLP pool
        assert ff["bytes_per_block"] >= 2 * fq["bytes_per_block"]
        assert fq["pool_bytes"] == \
            fq["bytes_per_block"] * q.manager.num_blocks
        # leased bytes track leases
        q.manager.allocate(1, 5)
        snap = q.manager.fragmentation()
        assert snap["leased_bytes"] == \
            snap["leased_blocks"] * snap["bytes_per_block"]
        q.manager.free(1)

    def test_unregistered_manager_reports_none(self):
        from paddle_tpu.inference.cache import BlockCacheManager

        f = BlockCacheManager(4, 4, 2).fragmentation()
        assert f["kv_bits"] == 16
        assert f["bytes_per_block"] is None and f["pool_bytes"] is None

    def test_oom_dump_carries_kv_bits(self, tmp_path):
        """The PR 8 OOM forensics schema extended: the KV snapshot in
        the dump reports kv_bits/bytes_per_block/pool_bytes, so a
        capacity post-mortem reads byte truth off the artifact."""
        obs.enable()
        memory.configure(flight_dir=str(tmp_path), min_dump_interval_s=0.0)
        memory.reset()
        eng = MLPLMEngine(kv_bits=8)
        path = memory.dump_oom("kv_exhausted", manager=eng.manager,
                               force=True)
        assert path is not None
        lines = [json.loads(ln) for ln in open(path)]
        kv = lines[1]["memory"]["kv"][0]
        assert kv["kv_bits"] == 8
        assert kv["bytes_per_block"] and kv["pool_bytes"]


# ---------------------------------------------------------------------------
# the llama engine (one small model, shared across the class)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def llama_model():
    from paddle_tpu.models import llama_tiny

    m = llama_tiny(vocab=128, layers=2, hidden=64, heads=4, seq=256)
    m.eval()
    return m


def _llama_engine(model, kv_bits=16, wbits=None):
    from paddle_tpu.inference import LlamaInferenceEngine

    eng = LlamaInferenceEngine(model, max_batch_size=4, num_blocks=64,
                               block_size=8, max_blocks_per_seq=16,
                               kv_bits=kv_bits)
    if wbits is not None:
        quantize_engine(eng, wbits)
    return eng


class TestLlamaQuant:
    def test_agreement_int8(self, llama_model):
        prompts = _prompts(4, vocab=128, seed=2)
        r = greedy_agreement(_llama_engine(llama_model, 8, 8),
                             _llama_engine(llama_model), prompts)
        assert r["agreement_tie_aware"] >= 0.99, r
        assert r["agreement"] >= 0.9, r
        assert r["max_logit_err"] < 0.5, r

    def test_agreement_int4_weights(self, llama_model):
        prompts = _prompts(4, vocab=128, seed=2)
        r = greedy_agreement(_llama_engine(llama_model, 8, 4),
                             _llama_engine(llama_model), prompts)
        # int4 is coarser: the tie-aware gate still holds, the logit
        # error bound is the int4 step's
        assert r["agreement_tie_aware"] >= 0.99, r
        assert r["max_logit_err"] < 2.0, r

    def test_quantized_serving_zero_retraces(self, llama_model):
        eng = _llama_engine(llama_model, kv_bits=8, wbits=8)
        assert eng.quant_info() == {
            "wbits": 8, "kv_bits": 8,
            "kv_bytes_per_token": eng.kv_bytes_per_token()}
        fe = ServingFrontend(eng, prefill_chunk_tokens=16)
        prompts = _prompts(3, vocab=128, seed=4)
        _finish_all(fe, prompts, max_new=4)      # warmup
        monitor.reset("serving.ragged_retraces")
        monitor.reset("serving.sample_retraces")
        _finish_all(fe, _prompts(4, vocab=128, seed=5), max_new=4)
        assert monitor.get("serving.ragged_retraces") == 0
        assert monitor.get("serving.sample_retraces") == 0
        assert fe.scheduler.kv_leaked_blocks() == 0

    def test_weight_only_int4_ctor(self, llama_model):
        """`weight_only='int4'` at construction packs the stacked
        projections (the quantize_engine pass and the ctor share
        `_quantize_stacked`)."""
        from paddle_tpu.inference import LlamaInferenceEngine

        eng = LlamaInferenceEngine(llama_model, max_batch_size=2,
                                   num_blocks=16, block_size=8,
                                   max_blocks_per_seq=8,
                                   weight_only="int4")
        w = eng.params["qkv_w"]
        assert isinstance(w, dict) and "q4" in w
        assert eng.quant_info()["wbits"] == 4


# ---------------------------------------------------------------------------
# compiled-artifact gate (PR 12 hlo-audit covers the new hot path)
# ---------------------------------------------------------------------------

class TestHloAudit:
    def test_quant_executables_pass_committed_manifest(self):
        from paddle_tpu.analysis import hlo_audit

        report = hlo_audit.run_audit(
            only=["ragged_decode_quant", "quant_matmul"])
        for name, entry in report["executables"].items():
            assert not entry["findings"], (name, entry["findings"])
            assert entry["host_transfer_ops"] == 0
            assert entry["collective_ops"] == 0
        assert report["ok"]

    def test_bf16_scan_platform_gating(self):
        from paddle_tpu.analysis.hlo_audit import audit_text

        text = 'f32[4,4] dot(a, b)\n  x = f32[4,4] dot(c, d)\n'
        hlo = "ENTRY main {\n  y = " + text + "}\n"
        entry = {"declared_dtype": "bf16"}
        # strict (None platform): the upcast finding fires
        _a, findings = audit_text(hlo, entry)
        assert findings and "f32 gemm" in findings[0]
        # off-TPU: recorded as a skipped check, not a failure
        actuals, findings = audit_text(hlo, entry, platform="cpu")
        assert not findings
        assert "skipped on cpu" in actuals["declared_dtype_check"]
        # on TPU the scan binds
        _a, findings = audit_text(hlo, entry, platform="tpu")
        assert findings
