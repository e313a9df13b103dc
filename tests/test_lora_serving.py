"""Multi-tenant LoRA serving (ISSUE 18): the batched-gather epilogue
math, the paged adapter pool's lease/evict/refcount/pin discipline,
per-lane adapter mixing on ONE ragged engine with zero steady-state
retraces, priced (miss) vs free (resident) admission, quantized-base
greedy agreement with bf16 adapters (int8 AND int4 bases), tenant =
adapter SLO composition, fleet adapter-affinity, and the metrics /
profiler surfaces.
"""
import numpy as np
import pytest

import paddle_tpu.observability as obs
from paddle_tpu.framework import monitor
from paddle_tpu.serving import (AdapterError, AdapterPoolExhausted,
                                AdapterRankError, MLPLMEngine, NGramProposer,
                                RequestStatus, ServingFrontend,
                                ServingMetrics, SpecDecodeConfig,
                                UnknownAdapterError, attach_adapters,
                                greedy_agreement, quantize_engine,
                                slo_for_adapters)
from paddle_tpu.serving.lora import lora_mm, random_adapter


@pytest.fixture(autouse=True)
def _clean_monitor():
    ServingMetrics.reset_monitor()
    yield
    ServingMetrics.reset_monitor()
    obs.disable()
    obs.reset()


def _prompts(n=6, vocab=256, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, int(rng.integers(3, 14))).tolist()
            for _ in range(n)]


def _finish_all(fe, prompts, adapters=None, max_new=6):
    adapters = adapters or [None] * len(prompts)
    hs = [fe.submit(p, max_new_tokens=max_new, adapter=a)
          for p, a in zip(prompts, adapters)]
    fe.run_until_idle(max_steps=2000)
    assert all(h.status is RequestStatus.FINISHED for h in hs), \
        [(h.status, h.finish_reason) for h in hs]
    return hs


def _mlp_lora(seed=3, pool_slots=4, buckets=(2, 4, 8), **kw):
    return attach_adapters(MLPLMEngine(seed=seed, **kw),
                           pool_slots=pool_slots, rank_buckets=buckets)


# ---------------------------------------------------------------------------
# the epilogue math (the one formula everything rides)
# ---------------------------------------------------------------------------

class TestLoraMM:
    def test_matches_dense_reference(self):
        """y + (x @ A[ids]) @ B[ids] against per-row numpy — exact up to
        f32 accumulation order."""
        import jax.numpy as jnp

        rng = np.random.default_rng(0)
        S, K, R, N, T = 3, 8, 4, 6, 5
        x = rng.normal(0, 1, (T, K)).astype(np.float32)
        w = rng.normal(0, 1, (K, N)).astype(np.float32)
        la = rng.normal(0, 1, (S, K, R)).astype(np.float32)
        lb = rng.normal(0, 1, (S, R, N)).astype(np.float32)
        ids = np.array([0, 2, 1, 2, 0], np.int32)
        out = np.asarray(lora_mm(
            jnp.asarray(x), {"w": jnp.asarray(w), "la": jnp.asarray(la),
                             "lb": jnp.asarray(lb), "ids": jnp.asarray(ids)},
            lambda a, b: a @ b))
        ref = x @ w + np.stack([x[t] @ la[ids[t]] @ lb[ids[t]]
                                for t in range(T)])
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    def test_zero_slot_is_identity(self):
        import jax.numpy as jnp

        rng = np.random.default_rng(1)
        x = rng.normal(0, 1, (4, 8)).astype(np.float32)
        w = rng.normal(0, 1, (8, 6)).astype(np.float32)
        la = np.zeros((2, 8, 4), np.float32)
        lb = rng.normal(0, 1, (2, 4, 6)).astype(np.float32)  # B alone inert
        out = np.asarray(lora_mm(
            jnp.asarray(x), {"w": jnp.asarray(w), "la": jnp.asarray(la),
                             "lb": jnp.asarray(lb),
                             "ids": jnp.zeros((4,), jnp.int32)},
            lambda a, b: a @ b))
        np.testing.assert_allclose(out, x @ w, rtol=1e-6)


# ---------------------------------------------------------------------------
# the paged adapter pool (satellite 3)
# ---------------------------------------------------------------------------

class TestAdapterPool:
    def test_register_validation(self):
        eng = _mlp_lora()
        pool = eng.adapter_pool
        good = random_adapter(eng, rank=4, seed=0)
        assert pool.register("a", good) == 4          # bucket rank back
        with pytest.raises(AdapterError, match="already registered"):
            pool.register("a", good)
        pool.register("a", random_adapter(eng, rank=2, seed=1),
                      allow_update=True)
        assert pool.rank_of("a") == 2
        with pytest.raises(AdapterError, match="keys"):
            pool.register("bad", {"w1": good["w1"]})
        mixed = {k: (a, b) for k, (a, b) in
                 random_adapter(eng, rank=4, seed=2).items()}
        k0 = sorted(mixed)[0]
        a0, b0 = random_adapter(eng, rank=2, seed=2)[k0]
        mixed[k0] = (a0, b0)
        with pytest.raises(AdapterRankError, match="rank differs"):
            pool.register("mixed", mixed)
        with pytest.raises(AdapterRankError, match="exceeds"):
            pool.register("fat", random_adapter(eng, rank=16, seed=3))
        with pytest.raises(AdapterError, match="do not match"):
            pool.register("shape", {
                k: (np.zeros((3, 4), np.float32), np.zeros((4, 5),
                                                           np.float32))
                for k in eng._lora_targets})

    def test_rank_pads_to_bucket(self):
        eng = _mlp_lora(buckets=(2, 4, 8))
        pool = eng.adapter_pool
        assert pool.register("r3", random_adapter(eng, rank=3, seed=0)) == 4
        assert pool.rank_of("r3") == 3               # true rank kept
        # padded host factors carry the POOL rank axis (Rmax), zeros
        # beyond the true rank — gather shapes never depend on the rank
        a, b = pool._registry["r3"]["w1"]
        assert a.shape[-1] == pool.rank_max == 8
        assert b.shape[-2] == 8
        assert not a[..., 3:].any() and not b[..., 3:, :].any()

    def test_lease_refcount_and_lru_eviction(self):
        eng = _mlp_lora(pool_slots=2)
        pool = eng.adapter_pool
        for i in range(3):
            pool.register(f"ad{i}", random_adapter(eng, rank=2, seed=i))
        s0 = pool.lease("ad0")                        # miss
        assert pool.misses == 1 and pool.hits == 0
        assert pool.lease("ad0") == s0                # hit, refs=2
        assert pool.hits == 1
        pool.lease("ad1")
        pool.release("ad1")                           # idle but resident
        assert pool.is_resident("ad1")
        pool.lease("ad2")                             # evicts LRU idle ad1
        assert not pool.is_resident("ad1") and pool.evictions == 1
        assert pool.is_resident("ad0"), "leased adapter evicted"
        with pytest.raises(AdapterPoolExhausted):
            pool.lease("ad1")                         # ad0 + ad2 leased
        pool.release("ad0")
        pool.release("ad0")
        with pytest.raises(AdapterError, match="no lease"):
            pool.release("ad0")
        pool.check_consistency()

    def test_pin_survives_pressure_and_deregister_refusals(self):
        eng = _mlp_lora(pool_slots=2)
        pool = eng.adapter_pool
        for i in range(3):
            pool.register(f"ad{i}", random_adapter(eng, rank=2, seed=i))
        pool.pin("ad0")
        assert pool.is_resident("ad0") and pool.leases() == 0
        pool.lease("ad1")
        pool.release("ad1")
        pool.lease("ad2")                             # must evict ad1
        assert pool.is_resident("ad0"), "pinned adapter evicted"
        with pytest.raises(AdapterError, match="pinned"):
            pool.deregister("ad0")
        with pytest.raises(AdapterError, match="outstanding"):
            pool.deregister("ad2")
        pool.unpin("ad0")
        pool.deregister("ad0")                        # idle resident: evicts
        assert not pool.is_registered("ad0")
        with pytest.raises(UnknownAdapterError):
            pool.lease("ad0")
        pool.check_consistency()

    def test_zero_slot_never_allocated(self):
        eng = _mlp_lora(pool_slots=2)
        pool = eng.adapter_pool
        for i in range(2):
            pool.register(f"ad{i}", random_adapter(eng, rank=2, seed=i))
            assert pool.lease(f"ad{i}") < pool.pool_slots
        assert eng.zero_slot == pool.pool_slots
        pool.check_consistency()

    def test_failed_upload_never_leaks_a_slot(self):
        eng = _mlp_lora(pool_slots=2)
        pool = eng.adapter_pool
        pool.register("ad0", random_adapter(eng, rank=2, seed=0))
        orig = eng._upload_slot
        eng._upload_slot = lambda *_a: (_ for _ in ()).throw(
            RuntimeError("upload boom"))
        with pytest.raises(RuntimeError, match="upload boom"):
            pool.lease("ad0")
        eng._upload_slot = orig
        assert not pool.is_resident("ad0") and pool.leases() == 0
        pool.check_consistency()
        assert pool.lease("ad0") is not None          # slot came back

    def test_wrap_validation(self):
        import types

        eng = _mlp_lora()
        with pytest.raises(AdapterError, match="exactly once"):
            attach_adapters(eng)
        with pytest.raises(AdapterError, match="single-chip"):
            attach_adapters(types.SimpleNamespace(tpinfo={}))
        plain = MLPLMEngine(seed=3)
        plain.params = {"nope": None}
        with pytest.raises(AdapterError, match="parameter layout"):
            attach_adapters(plain)


# ---------------------------------------------------------------------------
# one engine, many tenants (the tentpole contract)
# ---------------------------------------------------------------------------

class TestMultiAdapterServing:
    def test_zero_slot_parity_with_plain_engine(self):
        """Requests WITHOUT an adapter through the LoRA engine are
        bitwise the plain engine's streams (the zero slot is exact)."""
        prompts = _prompts(5)
        plain = [h.tokens for h in
                 _finish_all(ServingFrontend(MLPLMEngine(seed=3)), prompts)]
        eng = _mlp_lora(seed=3)
        eng.adapter_pool.register("a", random_adapter(eng, rank=4, seed=0))
        wrapped = [h.tokens for h in
                   _finish_all(ServingFrontend(eng), prompts)]
        assert wrapped == plain

    def test_mixed_batch_matches_dedicated_engines(self):
        """Per-adapter parity: each tenant's stream in a MIXED batch on
        the shared engine == a dedicated engine serving that adapter
        alone (same base seed, same factors)."""
        prompts = _prompts(6, seed=5)
        adapters = [None, "ad0", "ad1", "ad0", None, "ad1"]
        shared = _mlp_lora(seed=3)
        for i in range(2):
            shared.adapter_pool.register(
                f"ad{i}", random_adapter(shared, rank=4, seed=i,
                                         scale=0.2))
        mixed = _finish_all(ServingFrontend(shared), prompts, adapters)
        for name, seed in (("ad0", 0), ("ad1", 1)):
            ded = _mlp_lora(seed=3, pool_slots=2)
            ded.adapter_pool.register(
                name, random_adapter(ded, rank=4, seed=seed, scale=0.2))
            idx = [i for i, a in enumerate(adapters) if a == name]
            want = [h.tokens for h in _finish_all(
                ServingFrontend(ded), [prompts[i] for i in idx],
                [name] * len(idx))]
            assert [mixed[i].tokens for i in idx] == want, name
        assert shared.adapter_pool.leases() == 0
        shared.adapter_pool.check_consistency()

    def test_adapter_actually_changes_logits(self):
        eng = _mlp_lora(seed=3)
        eng.adapter_pool.register("a",
                                  random_adapter(eng, rank=8, seed=0,
                                                 scale=0.5))
        eng.use_adapter("a")
        r = greedy_agreement(eng, MLPLMEngine(seed=3), _prompts(3))
        assert r["max_logit_err"] > 1e-3, \
            "adapter epilogue had no effect on the logits"
        eng.use_adapter(None)
        assert eng.adapter_pool.leases() == 0

    def test_zero_retraces_across_adapter_switches(self):
        """Adapter identity is DATA: after warmup, any mix of adapters
        (including ones never seen at trace time) re-dispatches the same
        executable — zero ragged/sample/switch retraces."""
        eng = _mlp_lora(seed=3, pool_slots=3)
        for i in range(4):
            eng.adapter_pool.register(
                f"ad{i}", random_adapter(eng, rank=2 + 2 * (i % 2), seed=i))
        fe = ServingFrontend(eng)
        _finish_all(fe, _prompts(3), ["ad0", None, "ad1"])   # warmup
        monitor.reset("serving.ragged_retraces")
        monitor.reset("serving.sample_retraces")
        monitor.reset("serving.lora.switch_retraces")
        _finish_all(fe, _prompts(6, seed=9),
                    ["ad2", "ad3", "ad0", None, "ad3", "ad1"])
        assert monitor.get("serving.ragged_retraces") == 0
        assert monitor.get("serving.sample_retraces") == 0
        assert monitor.get("serving.lora.switch_retraces") == 0
        assert fe.scheduler.kv_leaked_blocks() == 0
        eng.manager.check_consistency()

    def test_spec_plain_parity_with_adapters(self):
        rng = np.random.default_rng(0)
        prompts = []
        for _ in range(5):
            phrase = rng.integers(1, 256, int(rng.integers(2, 4))).tolist()
            prompts.append((phrase * 5)[:int(rng.integers(6, 13))])
        adapters = ["ad0", None, "ad1", "ad0", "ad1"]

        def run(spec):
            eng = _mlp_lora(seed=3)
            for i in range(2):
                eng.adapter_pool.register(
                    f"ad{i}", random_adapter(eng, rank=4, seed=i,
                                             scale=0.2))
            fe = ServingFrontend(
                eng, spec=SpecDecodeConfig(NGramProposer(),
                                           num_draft_tokens=3)
                if spec else None)
            return [h.tokens for h in _finish_all(fe, prompts, adapters)]

        assert run(spec=True) == run(spec=False)

    def test_quantized_base_serving_end_to_end(self):
        """bf16 adapters over the PR 14 int8 base (weights + KV) on the
        SAME ragged substrate: finishes, drains, zero leaks."""
        eng = attach_adapters(
            quantize_engine(MLPLMEngine(seed=3, kv_bits=8), wbits=8),
            pool_slots=3, rank_buckets=(2, 4))
        for i in range(3):
            eng.adapter_pool.register(
                f"ad{i}", random_adapter(eng, rank=2, seed=i))
        fe = ServingFrontend(eng)
        _finish_all(fe, _prompts(5), ["ad0", "ad1", None, "ad2", "ad0"])
        assert fe.scheduler.kv_leaked_blocks() == 0
        assert eng.adapter_pool.leases() == 0
        eng.adapter_pool.check_consistency()
        assert eng.quant_info()["wbits"] == 8

    def test_submit_rejections(self):
        eng = _mlp_lora(seed=3)
        eng.adapter_pool.register("a", random_adapter(eng, rank=2, seed=0))
        fe = ServingFrontend(eng)
        h = fe.submit([1, 2, 3], adapter="nope")
        assert h.status is RequestStatus.REJECTED
        assert h.finish_reason == "unknown_adapter"
        fe2 = ServingFrontend(MLPLMEngine(seed=3))
        h2 = fe2.submit([1, 2, 3], adapter="a")
        assert h2.status is RequestStatus.REJECTED
        assert h2.finish_reason == "no_adapter_pool"

    def test_respawn_carries_registry_and_pins(self):
        eng = _mlp_lora(seed=3, pool_slots=2)
        for i in range(2):
            eng.adapter_pool.register(
                f"ad{i}", random_adapter(eng, rank=2, seed=i))
        eng.adapter_pool.pin("ad0")
        eng.adapter_pool.lease("ad1")
        fresh = eng.respawn()
        pool = fresh.adapter_pool
        assert pool.is_registered("ad0") and pool.is_registered("ad1")
        assert pool.is_resident("ad0"), "pin did not re-pin on respawn"
        assert not pool.is_resident("ad1"), \
            "stale residency carried into the fresh pool"
        assert pool.leases() == 0, "stale lease crossed the respawn"
        pool.check_consistency()


# ---------------------------------------------------------------------------
# priced admission: resident = free, miss = budgeted (satellite 3)
# ---------------------------------------------------------------------------

class TestAdmissionPricing:
    def test_miss_budget_limits_loads_per_step(self):
        eng = _mlp_lora(seed=3, pool_slots=4)
        for i in range(3):
            eng.adapter_pool.register(
                f"ad{i}", random_adapter(eng, rank=2, seed=i))
        fe = ServingFrontend(eng)
        assert fe.scheduler.adapter_miss_loads_per_step == 1
        hs = [fe.submit(p, max_new_tokens=4, adapter=f"ad{i}")
              for i, p in enumerate(_prompts(3))]
        fe.step()
        # one priced load entered; the other two misses wait their round
        assert monitor.get("serving.lora.miss_loads") == 1
        assert sum(r is not None for r in fe.scheduler.slots) == 1
        fe.run_until_idle(max_steps=2000)
        assert all(h.status is RequestStatus.FINISHED for h in hs)
        assert monitor.get("serving.lora.miss_loads") == 3
        assert eng.adapter_pool.leases() == 0

    def test_resident_adapters_admit_unbudgeted(self):
        eng = _mlp_lora(seed=3, pool_slots=4)
        pool = eng.adapter_pool
        for i in range(3):
            pool.register(f"ad{i}", random_adapter(eng, rank=2, seed=i))
            pool.lease(f"ad{i}")
            pool.release(f"ad{i}")                   # warm: resident, idle
        loads = monitor.get("serving.lora.miss_loads")
        fe = ServingFrontend(eng)
        [fe.submit(p, max_new_tokens=4, adapter=f"ad{i}")
         for i, p in enumerate(_prompts(3))]
        fe.step()
        # ALL THREE admit in one round: resident leases are free hits
        assert sum(r is not None for r in fe.scheduler.slots) == 3
        assert monitor.get("serving.lora.miss_loads") == loads

    def test_pool_pressure_reaches_terminal_states(self):
        """Working set (3 adapters, all lanes busy) over a 1-slot pool:
        admission alternates AdapterPoolExhausted waits with completions
        — everything still finishes and the books drain."""
        eng = _mlp_lora(seed=3, pool_slots=1, buckets=(2,))
        for i in range(3):
            eng.adapter_pool.register(
                f"ad{i}", random_adapter(eng, rank=2, seed=i))
        fe = ServingFrontend(eng)
        _finish_all(fe, _prompts(6, seed=2),
                    [f"ad{i % 3}" for i in range(6)], max_new=4)
        assert eng.adapter_pool.leases() == 0
        assert monitor.get("serving.lora.evictions") > 0
        assert fe.scheduler.kv_leaked_blocks() == 0
        eng.adapter_pool.check_consistency()


# ---------------------------------------------------------------------------
# tenant = adapter (SLO composition) + fleet affinity (satellites)
# ---------------------------------------------------------------------------

class TestTenancyAndFleet:
    def test_slo_for_adapters_builds_classes(self):
        from paddle_tpu.serving.slo import SLOClass

        cfg = slo_for_adapters(["a", "b"], weight=2.0, kv_quota_blocks=8,
                               extra=[SLOClass("b", weight=9.0)])
        assert {"a", "b"} <= set(cfg.classes)        # + the default tier
        assert cfg.classes["a"].weight == 2.0
        assert cfg.classes["a"].kv_quota_blocks == 8
        assert cfg.classes["b"].weight == 9.0        # extra wins collision

    def test_frontend_maps_adapter_to_tenant(self):
        eng = _mlp_lora(seed=3)
        for i in range(2):
            eng.adapter_pool.register(
                f"ad{i}", random_adapter(eng, rank=2, seed=i))
        fe = ServingFrontend(eng, slo=slo_for_adapters(["ad0", "ad1"]))
        hs = _finish_all(fe, _prompts(2), ["ad0", "ad1"], max_new=4)
        assert [h._req.tenant for h in hs] == ["ad0", "ad1"]
        assert monitor.get("serving.tenant.ad0.admitted") >= 1

    def test_fleet_adapter_affinity(self):
        from paddle_tpu.serving import FleetRouter

        def factory():
            eng = _mlp_lora(seed=3, pool_slots=2, buckets=(2,),
                            num_blocks=64)
            eng.adapter_pool.register(
                "hot", random_adapter(eng, rank=2, seed=0))
            return eng

        r = FleetRouter(factory, num_replicas=2)
        try:
            reps = r.live_replicas
            # warm the adapter onto replica 1 only
            pool1 = reps[1].frontend.scheduler.engine.adapter_pool
            pool1.lease("hot")
            pool1.release("hot")
            loads = [rep.load() for rep in reps]
            assert loads[1]["resident_adapters"] == ["hot"]
            assert loads[0]["resident_adapters"] == []
            # placement prefers the hot pool at equal load
            targets = r._targets(None, set(), adapter="hot")
            assert targets[0].replica_id == reps[1].replica_id
            h = r.submit(_prompts(1)[0], max_new_tokens=3, adapter="hot")
            r.run_until_idle()
            assert h.status is RequestStatus.FINISHED
            assert h.replica_id == reps[1].replica_id
        finally:
            r.close()


# ---------------------------------------------------------------------------
# quantized-base greedy agreement (satellite 1): bf16 adapters over
# int8 AND int4 bases — the measured bounds documented in docs/SERVING.md
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def llama_model():
    import paddle_tpu as paddle
    from paddle_tpu.models import llama_tiny

    paddle.seed(7)
    m = llama_tiny(vocab=128, layers=2, hidden=64, heads=4, seq=256)
    m.eval()
    return m


def _llama_lora(model, kv_bits=16, wbits=None, seed=0):
    from paddle_tpu.inference import LlamaInferenceEngine

    eng = LlamaInferenceEngine(model, max_batch_size=4, num_blocks=64,
                               block_size=8, max_blocks_per_seq=16,
                               kv_bits=kv_bits)
    if wbits is not None:
        quantize_engine(eng, wbits)
    eng = attach_adapters(eng, pool_slots=2, rank_buckets=(4,))
    eng.adapter_pool.register("ft",
                              random_adapter(eng, rank=4, seed=seed,
                                             scale=0.1))
    eng.use_adapter("ft")
    return eng


class TestQuantBaseAgreement:
    def test_llama_int8_base_with_adapters(self, llama_model):
        """Same adapter over int8 vs full-precision base: quantization
        error does not grow through the LoRA epilogue (the bf16 factors
        are NOT quantized) — same bound as the adapterless int8 gate."""
        prompts = _prompts(4, vocab=128, seed=2)
        r = greedy_agreement(_llama_lora(llama_model, 8, 8),
                             _llama_lora(llama_model), prompts)
        assert r["agreement_tie_aware"] >= 0.99, r
        assert r["agreement"] >= 0.9, r
        assert r["max_logit_err"] < 0.5, r

    def test_llama_int4_base_with_adapters(self, llama_model):
        prompts = _prompts(4, vocab=128, seed=2)
        r = greedy_agreement(_llama_lora(llama_model, 8, 4),
                             _llama_lora(llama_model), prompts)
        # int4 is coarser: tie-aware still gates, the bound is int4's
        assert r["agreement_tie_aware"] >= 0.99, r
        assert r["max_logit_err"] < 2.0, r

    def test_llama_multi_adapter_serving(self, llama_model):
        """The stacked-projection path end-to-end: per-lane ids ride the
        lax.scan layers, zero retraces after warmup."""
        eng = _llama_lora(llama_model, 8, 8)
        eng.use_adapter(None)
        eng.adapter_pool.register(
            "ft2", random_adapter(eng, rank=4, seed=7, scale=0.1))
        fe = ServingFrontend(eng, prefill_chunk_tokens=16)
        _finish_all(fe, _prompts(2, vocab=128, seed=4),
                    ["ft", None], max_new=4)         # warmup
        monitor.reset("serving.ragged_retraces")
        monitor.reset("serving.lora.switch_retraces")
        _finish_all(fe, _prompts(3, vocab=128, seed=5),
                    ["ft2", "ft", None], max_new=4)
        assert monitor.get("serving.ragged_retraces") == 0
        assert monitor.get("serving.lora.switch_retraces") == 0
        assert fe.scheduler.kv_leaked_blocks() == 0
        assert eng.adapter_pool.leases() == 0


# ---------------------------------------------------------------------------
# telemetry surfaces (satellite 6)
# ---------------------------------------------------------------------------

class TestTelemetry:
    def test_bind_time_gauges_and_profiler_line(self):
        from paddle_tpu.profiler import profiler as prof_mod

        eng = _mlp_lora(seed=3, pool_slots=5, buckets=(2, 4))
        eng.adapter_pool.register("a", random_adapter(eng, rank=2, seed=0))
        fe = ServingFrontend(eng)
        assert monitor.get("serving.lora.pool_slots") == 5
        assert monitor.get("serving.lora.registered_adapters") == 1
        assert monitor.get("serving.lora.rank_max") == 4
        _finish_all(fe, _prompts(2), ["a", None], max_new=4)
        text = "\n".join(prof_mod.Profiler._serving_summary_lines())
        assert "LoRA:" in text and "miss loads" in text, text

    def test_per_adapter_ttft_histogram(self):
        eng = _mlp_lora(seed=3)
        eng.adapter_pool.register("a", random_adapter(eng, rank=2, seed=0))
        fe = ServingFrontend(eng)
        _finish_all(fe, _prompts(2), ["a", None], max_new=4)
        snap = monitor.snapshot()
        assert any(k.startswith("serving.lora.ttft_seconds.a")
                   for k in snap), "per-adapter TTFT never observed"

    def test_timeline_carries_adapter_attribution(self):
        obs.enable()
        try:
            eng = _mlp_lora(seed=3)
            eng.adapter_pool.register("a",
                                      random_adapter(eng, rank=2, seed=0))
            fe = ServingFrontend(eng)
            _finish_all(fe, _prompts(1), ["a"], max_new=3)
            evs = [e for e in obs.timeline.events()
                   if (e.meta or {}).get("adapter") == "a"]
            assert evs, "no timeline event attributed to the adapter"
        finally:
            obs.disable()
