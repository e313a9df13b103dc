"""TP-sharded serving (ISSUE 16): `shard_engine` layout walk + the
`ShardedEngine` dispatch surface on the 8-virtual-device CPU mesh.

Contracts under test:
- tp=1 sharded engine is BITWISE equal to the unsharded engine — raw
  ragged/verify logits and greedy AND stochastic token streams through
  the full scheduler;
- tp>1 keeps token parity through the scheduler (greedy + seeded
  stochastic: the in-program logit all-gather feeds the same fused
  sampler) and spec==plain parity holds under TP;
- quantized engines (int8/int4 weight-only, int8 KV) shard and keep
  >= 99% tie-aware greedy agreement vs the quantized single-chip stack;
- COW/radix semantics are unchanged (block ids logical — shared-prefix
  traffic matches single-chip tokens exactly);
- bad layouts (KVH % tp, mesh size, tp > devices, int4-odd shards,
  re-sharding) raise `ShardingConfigError` BEFORE any device
  allocation, leaving the base engine serviceable;
- the train-side `RowParallelLinear(overlap_tiles=...)` decomposition
  is numerically identical to the undecomposed layer.
"""
import numpy as np
import pytest

from paddle_tpu.framework import monitor
from paddle_tpu.ops.sampling import step_args
from paddle_tpu.serving import (MLPLMEngine, NGramProposer, RequestStatus,
                                ServingFrontend, ServingMetrics,
                                ShardedEngine, ShardingConfigError,
                                SpecDecodeConfig, greedy_agreement,
                                quantize_engine, shard_engine)

MLP_KW = dict(vocab_size=64, hidden=16, max_batch_size=4, num_blocks=32,
              block_size=4, max_blocks_per_seq=4, seed=3)


@pytest.fixture(autouse=True)
def _clean_monitor():
    ServingMetrics.reset_monitor()
    yield
    ServingMetrics.reset_monitor()


def _mlp(kv_bits=16, wbits=None, **over):
    eng = MLPLMEngine(**{**MLP_KW, "kv_bits": kv_bits, **over})
    if wbits is not None:
        quantize_engine(eng, wbits)
    return eng


def _ragged_batch(step):
    q = np.array([3, 1, 0, 2], np.int32)
    kv = np.array([3 + step, 1 + step, 0, 2 + step], np.int32)
    toks = ((np.arange(8, dtype=np.int32) * 7 + step * 3) % 40 + 1)
    tables = np.arange(16, dtype=np.int32).reshape(4, 4)
    return toks.astype(np.int32), q, kv, tables


def _run_steps(eng):
    """Three carried ragged steps + one verify window; raw logits."""
    outs = [np.asarray(eng.ragged_step(*_ragged_batch(s)))
            for s in range(3)]
    vt = (np.arange(8, dtype=np.int32) % 30 + 1).reshape(2, 4)
    outs.append(np.asarray(eng.verify_step(
        vt, np.array([8, 9], np.int32),
        np.arange(8, dtype=np.int32).reshape(2, 4))))
    return outs


def _prompts(n=6, vocab=64, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, int(rng.integers(3, 12))).tolist()
            for _ in range(n)]


def _serve_tokens(eng, prompts, spec=False, max_new=6):
    """Greedy + seeded-stochastic token streams through the frontend."""
    fe = ServingFrontend(
        eng, spec=SpecDecodeConfig(NGramProposer(), num_draft_tokens=3)
        if spec else None)
    hs = [fe.submit(p, max_new_tokens=max_new,
                    temperature=(0.7 if i % 2 else 0.0), seed=i)
          for i, p in enumerate(prompts)]
    fe.run_until_idle(max_steps=4000)
    assert all(h.status is RequestStatus.FINISHED for h in hs), \
        [(h.status, h.finish_reason) for h in hs]
    return [list(h.tokens) for h in hs]


def _assert_gemm_rounding(a, b):
    """`b` is `a` up to how a gemm rounds: a row-parallel gemm whose
    OUTPUT columns are tiled sums every dot product over the same K, but
    the backend blocks a narrower N differently (XLA's CPU gemm: `x @ w`
    against the concatenation of `x @ w[:, tile]` differs in the last bits,
    both as near the float64 product). Measured on the sandbox CPU in
    units of eps32 x the largest logit: 2.0 (MLP, one tiled gemm), 3.8
    (Llama, two layers of two). Greedy picks must agree everywhere."""
    bound = 32 * np.finfo(np.float32).eps * np.abs(a).max()
    assert np.abs(a - b).max() <= bound, (np.abs(a - b).max(), bound)
    assert (np.argmax(a, -1) == np.argmax(b, -1)).all()


# ---------------------------------------------------------------------------
# tp=1: bitwise with the gemms whole, gemm rounding with them tiled
# ---------------------------------------------------------------------------

class TestTp1Bitwise:
    @pytest.mark.parametrize("kv_bits", [16, 8])
    def test_raw_logits_bitwise(self, kv_bits):
        base = _run_steps(_mlp(kv_bits))
        whole = _run_steps(shard_engine(_mlp(kv_bits), tp=1,
                                        overlap_tiles=1))
        tiled = _run_steps(shard_engine(_mlp(kv_bits), tp=1,
                                        overlap_tiles=3))
        for a, b, c in zip(base, whole, tiled):
            assert np.array_equal(a, b)
            _assert_gemm_rounding(a, c)

    def test_scheduler_token_parity_greedy_and_stochastic(self):
        prompts = _prompts()
        base = _serve_tokens(_mlp(), prompts)
        tp1 = _serve_tokens(shard_engine(_mlp(), tp=1), prompts)
        assert base == tp1


# ---------------------------------------------------------------------------
# tp>1: numeric + token parity, overlap and sequential modes
# ---------------------------------------------------------------------------

class TestTpParity:
    @pytest.mark.parametrize("kv_bits,overlap", [(16, True), (16, False),
                                                 (8, True), (8, False)])
    def test_raw_logits_tp2(self, kv_bits, overlap):
        base = _run_steps(_mlp(kv_bits))
        tp2 = _run_steps(shard_engine(_mlp(kv_bits), tp=2, overlap=overlap,
                                      overlap_tiles=3))
        for a, b in zip(base, tp2):
            # float reduction order differs across shards; argmax (what
            # serving consumes) must agree everywhere
            assert np.allclose(a, b, atol=2e-4, rtol=2e-4)
            assert (np.argmax(a, -1) == np.argmax(b, -1)).all()

    @pytest.mark.parametrize("tp", [2, 4])
    def test_scheduler_token_parity(self, tp):
        prompts = _prompts()
        base = _serve_tokens(_mlp(), prompts)
        sh = _serve_tokens(shard_engine(_mlp(), tp=tp), prompts)
        assert base == sh

    def test_spec_equals_plain_under_tp(self):
        rng = np.random.default_rng(0)
        prompts = []
        for _ in range(5):
            phrase = rng.integers(1, 64, int(rng.integers(2, 4))).tolist()
            prompts.append((phrase * 5)[:int(rng.integers(6, 13))])
        spec = _serve_tokens(shard_engine(_mlp(), tp=2), prompts,
                             spec=True)
        plain = _serve_tokens(shard_engine(_mlp(), tp=2), prompts,
                              spec=False)
        assert spec == plain

    def test_shared_prefix_cow_parity(self):
        """Radix sharing + COW under TP: block ids stay logical, the
        sharded copy moves every chip's slice — shared-prefix greedy
        traffic must match single-chip tokens exactly."""
        prefix = list(range(1, 9))
        prompts = [prefix + [10 + i] for i in range(6)]
        base = _serve_tokens(_mlp(), prompts)
        sh = _serve_tokens(shard_engine(_mlp(), tp=2), prompts)
        assert base == sh

    def test_zero_retraces_steady_state(self):
        eng = shard_engine(_mlp(kv_bits=8), tp=2, overlap_tiles=3)
        fe = ServingFrontend(eng)
        hs = [fe.submit(p, max_new_tokens=4) for p in _prompts(3, seed=4)]
        fe.run_until_idle(max_steps=2000)
        monitor.reset("serving.ragged_retraces")
        monitor.reset("serving.sample_retraces")
        hs = [fe.submit(p, max_new_tokens=4) for p in _prompts(4, seed=5)]
        fe.run_until_idle(max_steps=2000)
        assert all(h.status is RequestStatus.FINISHED for h in hs)
        assert monitor.get("serving.ragged_retraces") == 0
        assert monitor.get("serving.sample_retraces") == 0
        assert fe.scheduler.kv_leaked_blocks() == 0


# ---------------------------------------------------------------------------
# quantized + TP (satellite: compose with PR 14)
# ---------------------------------------------------------------------------

class TestQuantizedTP:
    @pytest.mark.parametrize("wbits", [8, 4])
    def test_greedy_agreement_quantized(self, wbits):
        sh = shard_engine(_mlp(kv_bits=8, wbits=wbits), tp=2,
                          overlap_tiles=3)
        r = greedy_agreement(sh, _mlp(kv_bits=8, wbits=wbits), _prompts())
        assert r["agreement_tie_aware"] >= 0.99, r

    @pytest.mark.parametrize("wbits,overlap", [(8, True), (4, True),
                                               (4, False)])
    def test_raw_logits_quantized_tp2(self, wbits, overlap):
        base = _run_steps(_mlp(wbits=wbits))
        sh = _run_steps(shard_engine(_mlp(wbits=wbits), tp=2,
                                     overlap=overlap, overlap_tiles=3))
        for a, b in zip(base, sh):
            assert np.allclose(a, b, atol=2e-4, rtol=2e-4)
            assert (np.argmax(a, -1) == np.argmax(b, -1)).all()

    def test_quant_info_reports_per_chip_kv(self):
        sh = shard_engine(_mlp(kv_bits=8, wbits=4), tp=2)
        info = sh.quant_info()
        assert info["wbits"] == 4 and info["kv_bits"] == 8
        # per-chip KV bytes: the feature axis halves, the replicated
        # scale plane does not
        assert info["kv_bytes_per_token"] < \
            _mlp(kv_bits=8).kv_bytes_per_token()


# ---------------------------------------------------------------------------
# llama stack under TP
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def llama_model():
    from paddle_tpu.models import llama_tiny

    m = llama_tiny(vocab=64, layers=2, hidden=32, heads=4, seq=64,
                   num_key_value_heads=2)
    m.eval()
    return m


def _llama(model, kv_bits=16, wbits=None):
    from paddle_tpu.inference import LlamaInferenceEngine

    eng = LlamaInferenceEngine(model, max_batch_size=4, num_blocks=32,
                               block_size=4, max_blocks_per_seq=4,
                               kv_bits=kv_bits)
    if wbits is not None:
        quantize_engine(eng, wbits)
    return eng


class TestLlamaTP:
    def test_tp1_bitwise(self, llama_model):
        base = _run_steps(_llama(llama_model))
        whole = _run_steps(shard_engine(_llama(llama_model), tp=1,
                                        overlap_tiles=1))
        tiled = _run_steps(shard_engine(_llama(llama_model), tp=1,
                                        overlap_tiles=3))
        for a, b, c in zip(base, whole, tiled):
            assert np.array_equal(a, b)
            _assert_gemm_rounding(a, c)

    @pytest.mark.parametrize("kv_bits,wbits", [(16, None), (8, None),
                                               (16, 8), (8, 4)])
    def test_tp2_parity(self, llama_model, kv_bits, wbits):
        base = _run_steps(_llama(llama_model, kv_bits, wbits))
        sh = _run_steps(shard_engine(_llama(llama_model, kv_bits, wbits),
                                     tp=2, overlap_tiles=3))
        for a, b in zip(base, sh):
            assert np.allclose(a, b, atol=2e-4, rtol=2e-4)
            assert (np.argmax(a, -1) == np.argmax(b, -1)).all()

    def test_greedy_agreement_quantized_tp(self, llama_model):
        r = greedy_agreement(
            shard_engine(_llama(llama_model, 8, 8), tp=2),
            _llama(llama_model, 8, 8), _prompts(4, seed=2))
        assert r["agreement_tie_aware"] >= 0.99, r


# ---------------------------------------------------------------------------
# typed errors BEFORE allocation
# ---------------------------------------------------------------------------

class TestShardingConfigErrors:
    def test_kv_heads_indivisible(self, llama_model):
        eng = _llama(llama_model)          # kvh=2
        with pytest.raises(ShardingConfigError,
                           match="num_key_value_heads"):
            shard_engine(eng, tp=4)
        # the failed attempt left the base engine serviceable
        assert _run_steps(eng)[0].shape[-1] == 64

    def test_hidden_indivisible(self):
        with pytest.raises(ShardingConfigError, match="hidden"):
            shard_engine(_mlp(), tp=3)

    def test_tp_exceeds_devices(self):
        with pytest.raises(ShardingConfigError, match="visible devices"):
            shard_engine(_mlp(), tp=16)

    def test_mesh_size_mismatch(self):
        from paddle_tpu.distributed import ProcessMesh

        with pytest.raises(ShardingConfigError, match="mesh has"):
            shard_engine(_mlp(), mesh=ProcessMesh([0, 1, 2, 3], ["x"]),
                         tp=2, dp=1)

    def test_already_sharded(self):
        sh = shard_engine(_mlp(), tp=2)
        with pytest.raises(ShardingConfigError, match="already"):
            shard_engine(sh, tp=2)

    def test_degrees_below_one(self):
        with pytest.raises(ShardingConfigError, match=">= 1"):
            shard_engine(_mlp(), tp=0)

    def test_unrecognized_layout(self):
        class Weird:
            params = {"mystery": np.zeros((2, 2))}

        with pytest.raises(ShardingConfigError, match="unrecognized"):
            shard_engine(Weird(), tp=2)

    def test_int4_odd_shard_rejected(self):
        # hidden=18 -> per-shard feature slice 9 is odd: the split-half
        # int4 packing cannot split a byte across shards
        eng = _mlp(wbits=4, vocab_size=66, hidden=18)
        with pytest.raises(ShardingConfigError, match="int4"):
            shard_engine(eng, tp=2)


# ---------------------------------------------------------------------------
# observability surfaces
# ---------------------------------------------------------------------------

class TestShardedSurfaces:
    def test_tp_summary_and_cost_card(self):
        sh = shard_engine(_mlp(), tp=2, overlap_tiles=3)
        assert isinstance(sh, ShardedEngine)
        s = sh.tp_summary()
        assert s["tp"] == 2 and s["overlap"] and s["tiles"] == 3
        assert s["mesh"]["dim_names"] == ["dp", "tp"]
        fn, lead = sh.cost_card_args("ragged")
        sampled, _pools = fn(*lead, *step_args(*_ragged_batch(0)))
        assert np.asarray(sampled).shape == (2, 4)
        with pytest.raises(KeyError):
            sh.cost_card_args("prefill")

    def test_sequential_mode_returns_host_logits(self):
        sh = shard_engine(_mlp(), tp=2, overlap=False)
        out = sh.ragged_step(*_ragged_batch(0))
        assert isinstance(out, np.ndarray) and out.shape[-1] == 64


# ---------------------------------------------------------------------------
# train-side decomposition (RowParallelLinear overlap_tiles)
# ---------------------------------------------------------------------------

class TestRowParallelOverlapTiles:
    def test_tiled_forward_is_bitwise_equal(self):
        import paddle_tpu as paddle
        from paddle_tpu.distributed.fleet.layers.mpu.mp_layers import \
            RowParallelLinear

        rng = np.random.default_rng(0)
        w = rng.normal(size=(12, 9)).astype(np.float32)
        b = rng.normal(size=(9,)).astype(np.float32)
        x = paddle.to_tensor(rng.normal(size=(5, 12)).astype(np.float32))
        outs = []
        for tiles in (1, 3, 4):   # 4 clamps to 3 (largest divisor of 9)
            layer = RowParallelLinear(12, 9, overlap_tiles=tiles)
            layer.weight.set_value(paddle.to_tensor(w))
            layer.bias.set_value(paddle.to_tensor(b))
            outs.append(np.asarray(layer(x)))
        assert np.array_equal(outs[0], outs[1])
        assert np.array_equal(outs[0], outs[2])

    def test_tiled_no_bias(self):
        import paddle_tpu as paddle
        from paddle_tpu.distributed.fleet.layers.mpu.mp_layers import \
            RowParallelLinear

        rng = np.random.default_rng(1)
        w = rng.normal(size=(8, 6)).astype(np.float32)
        x = paddle.to_tensor(rng.normal(size=(3, 8)).astype(np.float32))
        a = RowParallelLinear(8, 6, has_bias=False, overlap_tiles=1)
        t = RowParallelLinear(8, 6, has_bias=False, overlap_tiles=2)
        a.weight.set_value(paddle.to_tensor(w))
        t.weight.set_value(paddle.to_tensor(w))
        _assert_gemm_rounding(np.asarray(a(x)), np.asarray(t(x)))
