"""Inference stack (L9) tests: paged-attention kernel, decode functionals,
Llama engine vs eager forward, Predictor over saved programs.

Reference test model: `test/legacy_test/test_block_multihead_attention.py`
(numeric parity of the paged path vs dense attention) and the predictor API
tests under `test/ir/inference/`.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.framework import flags


@pytest.fixture(autouse=True)
def _interpret_pallas():
    flags.set_flags({"FLAGS_pallas_interpret": True})
    yield
    flags.set_flags({"FLAGS_pallas_interpret": False})


def test_paged_attention_kernel_matches_ref(rng):
    from paddle_tpu.ops.pallas import paged_attention as pa

    B, H, KVH, D, BS, NB, MAXB = 2, 8, 4, 32, 16, 12, 4
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(NB, KVH, BS, D)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(NB, KVH, BS, D)), jnp.float32)
    tables = jnp.asarray(rng.permutation(NB)[:B * MAXB].reshape(B, MAXB),
                         jnp.int32)
    lens = jnp.asarray([37, 50], jnp.int32)
    ref = pa.paged_attention_ref(q, kc, vc, tables, lens)
    out = pa.paged_attention(q, kc, vc, tables, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_paged_attention_mha_group1(rng):
    """MHA (G=1) exercises the group-padding path."""
    from paddle_tpu.ops.pallas import paged_attention as pa

    B, H, D, BS, NB, MAXB = 2, 4, 16, 8, 10, 3
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(NB, H, BS, D)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(NB, H, BS, D)), jnp.float32)
    tables = jnp.asarray(rng.integers(0, NB, size=(B, MAXB)), jnp.int32)
    lens = jnp.asarray([9, 17], jnp.int32)
    ref = pa.paged_attention_ref(q, kc, vc, tables, lens)
    out = pa.paged_attention(q, kc, vc, tables, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def _window(pa, q, kc, vc, tables, lens, kernel):
    """`kernel` over a `q_len == S` window a lane (what a verify step
    packs): q [B, S, H, D] whose row i sits at position lens - S + i."""
    B, S = q.shape[:2]
    lane, pos = pa.ragged_metadata(jnp.full((B,), S, jnp.int32), lens, B * S)
    out = kernel(q.reshape((B * S,) + q.shape[2:]), kc, vc, tables, lens,
                 lane, pos)
    return out.reshape(q.shape)


def test_ragged_kernel_window_matches_per_row_decode(rng):
    """The ragged kernel at `q_len == S` == S single-query decode calls: row
    i (absolute position ctx_len - S + i) must equal `paged_attention` with
    the context truncated to ctx_len - S + i + 1 tokens, and the whole
    window the XLA reference."""
    from paddle_tpu.ops.pallas import paged_attention as pa

    B, S, H, KVH, D, BS, NB, MAXB = 2, 4, 8, 4, 32, 16, 12, 4
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(NB, KVH, BS, D)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(NB, KVH, BS, D)), jnp.float32)
    tables = jnp.asarray(rng.permutation(NB)[:B * MAXB].reshape(B, MAXB),
                         jnp.int32)
    lens = jnp.asarray([37, 50], jnp.int32)
    out = _window(pa, q, kc, vc, tables, lens, pa.paged_attention_ragged)
    ref = _window(pa, q, kc, vc, tables, lens, pa.paged_attention_ragged_ref)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    for i in range(S):
        row = pa.paged_attention(
            jnp.asarray(q[:, i]), kc, vc, tables, lens - (S - 1 - i))
        np.testing.assert_allclose(np.asarray(out[:, i]), np.asarray(row),
                                   atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(row), np.asarray(pa.paged_attention_ref(
                jnp.asarray(q[:, i]), kc, vc, tables, lens - (S - 1 - i))),
            atol=1e-5)


def test_ragged_kernel_window_mha_group1(rng):
    """MHA (G=1) exercises the kernel's group-padding path at q_len == S."""
    from paddle_tpu.ops.pallas import paged_attention as pa

    B, S, H, D, BS, NB, MAXB = 2, 3, 4, 16, 8, 10, 3
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(NB, H, BS, D)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(NB, H, BS, D)), jnp.float32)
    tables = jnp.asarray(rng.integers(0, NB, size=(B, MAXB)), jnp.int32)
    lens = jnp.asarray([9, 17], jnp.int32)
    ref = _window(pa, q, kc, vc, tables, lens, pa.paged_attention_ragged_ref)
    out = _window(pa, q, kc, vc, tables, lens, pa.paged_attention_ragged)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def _prompt_step(eng, prompt):
    """Allocate lanes 0..B-1 and run the equal-length `prompt` [B, S] as
    ONE ragged step: each lane's last row's logits [B, V]."""
    b, s = prompt.shape
    for i in range(b):
        eng.manager.allocate(i, s)
    lens = np.full((b,), s, np.int32)
    lg = np.asarray(eng.ragged_step(prompt.reshape(b * s), lens, lens,
                                    eng.manager.block_table_array(range(b))))
    return lg[s - 1::s]


def test_llama_verify_step_matches_sequential_decode():
    """One fixed-shape verify over S tokens reproduces S single-token
    (`q_len == 1`) ragged steps — to float rounding (the S-token window
    folds its pages into other online-softmax steps than a lone token
    does) and with every greedy pick equal: the greedy-parity foundation
    of the speculative path."""
    from paddle_tpu.inference import LlamaInferenceEngine
    from paddle_tpu.models.llama import llama_tiny

    paddle.seed(13)
    model = llama_tiny(vocab=64, layers=2, hidden=32, heads=4, seq=64)
    model.eval()

    def build():
        return LlamaInferenceEngine(model, max_batch_size=2, num_blocks=32,
                                    block_size=8, max_blocks_per_seq=6)

    rng = np.random.default_rng(2)
    prompt = rng.integers(1, 64, size=(2, 11)).astype(np.int32)
    S = 4

    seq = build()
    lg = _prompt_step(seq, prompt)
    toks = [np.argmax(lg, -1).astype(np.int32)]
    step_logits = []
    for _ in range(S):
        for b in range(2):
            seq.manager.append_token(b)
        lens = np.asarray([seq.manager.seq_len(0), seq.manager.seq_len(1)],
                          np.int32)
        lg = np.asarray(seq.ragged_step(
            toks[-1], np.ones(2, np.int32), lens,
            seq.manager.block_table_array([0, 1])))
        step_logits.append(lg)
        toks.append(np.argmax(lg, -1).astype(np.int32))

    ver = build()
    _prompt_step(ver, prompt)
    for b in range(2):
        ver.manager.append_tokens(b, S)
    vlg = np.asarray(ver.verify_step(
        np.stack(toks[:S], axis=1),
        np.asarray([ver.manager.seq_len(0), ver.manager.seq_len(1)],
                   np.int32),
        ver.manager.block_table_array([0, 1])))
    assert vlg.shape == (2, S, 64)
    for i in range(S):
        np.testing.assert_allclose(vlg[:, i], step_logits[i], atol=5e-6,
                                   rtol=1e-5)
        np.testing.assert_array_equal(np.argmax(vlg[:, i], -1),
                                      toks[i + 1])


def test_write_kv_then_decode_roundtrip(rng):
    """Prefill-write + decode attention == dense causal attention."""
    from paddle_tpu.ops.pallas import paged_attention as pa

    B, S, KVH, H, D, BS = 2, 12, 2, 4, 16, 8
    NB, MAXB = 8, 3
    k = jnp.asarray(rng.normal(size=(B, S, KVH, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KVH, D)), jnp.float32)
    kc = jnp.zeros((NB, KVH, BS, D), jnp.float32)
    vc = jnp.zeros((NB, KVH, BS, D), jnp.float32)
    tables = jnp.asarray([[0, 1, 2], [3, 4, 5]], jnp.int32)
    kc, vc = pa.write_kv_to_cache(k, v, kc, vc, tables,
                                  jnp.zeros((B,), jnp.int32))
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    lens = jnp.full((B,), S, jnp.int32)
    out = pa.paged_attention(q, kc, vc, tables, lens)
    # dense reference: repeat kv heads, full softmax over S tokens
    kr = jnp.repeat(k, H // KVH, axis=2)
    vr = jnp.repeat(v, H // KVH, axis=2)
    s = jnp.einsum("bhd,bshd->bhs", q, kr) / np.sqrt(D)
    p = jax.nn.softmax(s, axis=-1)
    ref = jnp.einsum("bhs,bshd->bhd", p, vr)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_masked_multihead_attention(rng):
    from paddle_tpu.incubate.nn import functional as incf

    B, H, D, MS = 2, 3, 8, 16
    cached = [5, 11]
    cache = np.zeros((2, B, H, MS, D), np.float32)
    for b in range(B):
        cache[:, b, :, :cached[b]] = rng.normal(
            size=(2, H, cached[b], D))
    x = rng.normal(size=(B, 3 * H * D)).astype(np.float32)
    out, new_cache = incf.masked_multihead_attention(
        paddle.Tensor(x), paddle.Tensor(cache),
        sequence_lengths=paddle.Tensor(np.asarray(cached, np.int32)))
    out = np.asarray(out._data)
    nc = np.asarray(new_cache._data)
    qkv = x.reshape(B, 3, H, D)
    for b in range(B):
        n = cached[b] + 1
        k = np.concatenate([cache[0, b, :, :cached[b]],
                            qkv[b, 1][:, None]], axis=1)
        v = np.concatenate([cache[1, b, :, :cached[b]],
                            qkv[b, 2][:, None]], axis=1)
        s = np.einsum("hd,hsd->hs", qkv[b, 0], k) / np.sqrt(D)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        ref = np.einsum("hs,hsd->hd", p, v).reshape(H * D)
        np.testing.assert_allclose(out[b], ref, atol=1e-4)
        # cache write landed at position cached[b]
        np.testing.assert_allclose(nc[0, b, :, cached[b]], qkv[b, 1],
                                   atol=1e-6)


def test_block_multihead_attention_prefill_then_decode(rng):
    """Paged prefill + decode equals dense causal attention on the full
    sequence (the reference kernel's correctness contract)."""
    from paddle_tpu.incubate.nn import functional as incf

    B, S, H, KVH, D, BS, NB, MAXB = 2, 6, 4, 2, 8, 4, 8, 3
    width = (H + 2 * KVH) * D
    kc = paddle.Tensor(np.zeros((NB, KVH, BS, D), np.float32))
    vc = paddle.Tensor(np.zeros((NB, KVH, BS, D), np.float32))
    tables = paddle.Tensor(np.asarray([[0, 1, 2], [3, 4, 5]], np.int32))
    qkv_pre = rng.normal(size=(B * S, width)).astype(np.float32)
    o, _, kc, vc = incf.block_multihead_attention(
        paddle.Tensor(qkv_pre), kc, vc,
        seq_lens_encoder=paddle.Tensor(np.full((B,), S, np.int32)),
        seq_lens_decoder=paddle.Tensor(np.zeros((B,), np.int32)),
        seq_lens_this_time=paddle.Tensor(np.full((B,), S, np.int32)),
        block_tables=tables, block_size=BS)
    qkv_dec = rng.normal(size=(B, width)).astype(np.float32)
    o2, _, kc2, vc2 = incf.block_multihead_attention(
        paddle.Tensor(qkv_dec), kc, vc,
        seq_lens_encoder=paddle.Tensor(np.zeros((B,), np.int32)),
        seq_lens_decoder=paddle.Tensor(np.full((B,), S, np.int32)),
        seq_lens_this_time=paddle.Tensor(np.ones((B,), np.int32)),
        block_tables=tables, block_size=BS)
    # dense reference over the full S+1 token sequence
    allq = np.concatenate([qkv_pre.reshape(B, S, -1, D),
                           qkv_dec.reshape(B, 1, -1, D)], axis=1)
    q = allq[:, :, :H]
    k = np.repeat(allq[:, :, H:H + KVH], H // KVH, axis=2)
    v = np.repeat(allq[:, :, H + KVH:], H // KVH, axis=2)
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    mask = np.tril(np.ones((S + 1, S + 1), bool))
    s = np.where(mask, s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ref = np.einsum("bhqk,bkhd->bqhd", p, v)
    np.testing.assert_allclose(np.asarray(o2._data).reshape(B, H, D),
                               ref[:, -1], atol=1e-4)


def test_llama_engine_prompt_step_matches_eager():
    """The fused scan-over-layers step over a whole prompt reproduces the
    eager model's logits — the VERDICT 'decode matches eager forward'
    gate."""
    from paddle_tpu.inference import LlamaInferenceEngine
    from paddle_tpu.models.llama import llama_tiny

    paddle.seed(7)
    model = llama_tiny(vocab=64, layers=2, hidden=32, heads=4, seq=32)
    model.eval()
    eng = LlamaInferenceEngine(model, max_batch_size=2, num_blocks=16,
                               block_size=8, max_blocks_per_seq=4)
    rng = np.random.default_rng(3)
    ids = rng.integers(0, 64, size=(2, 9)).astype(np.int32)
    logits = _prompt_step(eng, ids)
    eager = model(paddle.Tensor(ids))
    ref = np.asarray(eager._data)[:, -1, :]
    np.testing.assert_allclose(logits, ref, atol=2e-4, rtol=2e-4)
    eng.manager.free(0)
    eng.manager.free(1)


def test_llama_engine_generate_matches_eager_greedy():
    """Greedy generation with the paged cache matches token-by-token greedy
    decoding through the eager model (full-context recompute)."""
    from paddle_tpu.inference import GenerationConfig, LlamaInferenceEngine
    from paddle_tpu.models.llama import llama_tiny

    paddle.seed(11)
    model = llama_tiny(vocab=48, layers=2, hidden=32, heads=4, seq=48)
    model.eval()
    eng = LlamaInferenceEngine(model, max_batch_size=2, num_blocks=32,
                               block_size=8, max_blocks_per_seq=6)
    rng = np.random.default_rng(5)
    ids = rng.integers(0, 48, size=(2, 7)).astype(np.int32)
    n_new = 6
    out = eng.generate(ids, GenerationConfig(max_new_tokens=n_new))
    assert out.shape == (2, 7 + n_new)
    # eager greedy reference: recompute the full context each step
    cur = ids.copy()
    for _ in range(n_new):
        logits = np.asarray(model(paddle.Tensor(cur))._data)[:, -1, :]
        nxt = logits.argmax(-1).astype(np.int32)
        cur = np.concatenate([cur, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(out, cur)
    # cache pool fully returned
    assert eng.manager.free_blocks == 32


@pytest.mark.parametrize("kv_bits", [16, 8])
@pytest.mark.parametrize("path", ["kernel", "xla"])
def test_llama_ragged_step_never_copies_the_pool(kv_bits, path):
    """The compiled ragged step holds no temporary the size of even ONE
    layer's K pool, and every pool argument is aliased to its output: the
    pool is the layer loop's carry, written at `[layer, block, :, offset]`
    and read at `[layer, block]`, never sliced out, stacked back,
    reshaped or copied. The pool here dwarfs everything else in the step
    (6 tokens, hidden 32), so one stray copy of a layer breaks the bound;
    the xs/ys construction this replaced held eight layers' worth."""
    from paddle_tpu.inference import LlamaInferenceEngine
    from paddle_tpu.models.llama import llama_tiny
    from paddle_tpu.ops.sampling import step_args

    flags.set_flags({"FLAGS_pallas_interpret": path == "kernel"})
    paddle.seed(1)
    model = llama_tiny(vocab=64, layers=3, hidden=32, heads=4, seq=64)
    eng = LlamaInferenceEngine(model, max_batch_size=2, num_blocks=8192,
                               block_size=8, max_blocks_per_seq=4,
                               kv_bits=kv_bits)
    fn, lead = eng.cost_card_args("ragged")
    pools = lead[1]
    assert len(pools) == (4 if kv_bits == 8 else 2)
    compiled = fn.lower(*lead, *step_args(
        np.zeros(6, np.int32), np.zeros(2, np.int32),
        np.zeros(2, np.int32), np.zeros((2, 4), np.int32))).compile()
    mem = compiled.memory_analysis()
    one_layer_k = pools[0][0].nbytes
    assert mem.temp_size_in_bytes < one_layer_k, (
        mem.temp_size_in_bytes, one_layer_k)
    assert mem.alias_size_in_bytes == sum(p.nbytes for p in pools)


def test_block_cache_manager():
    from paddle_tpu.inference import BlockCacheManager

    m = BlockCacheManager(num_blocks=8, block_size=4, max_blocks_per_seq=4)
    m.allocate(0, 5)              # needs 2 blocks
    assert m.free_blocks == 6
    for _ in range(3):            # 5 -> 8 tokens, still 2 blocks
        m.append_token(0)
    assert m.free_blocks == 6
    m.append_token(0)             # 9th token -> 3rd block
    assert m.free_blocks == 5
    t = m.block_table_array([0])
    assert t.shape == (1, 4) and len(set(t[0][:3])) == 3
    m.free(0)
    assert m.free_blocks == 8
    with pytest.raises(ValueError):
        m.allocate(1, 100)     # exceeds max_blocks_per_seq
    m.allocate(1, 16)
    m.allocate(2, 16)          # pool now empty
    with pytest.raises(RuntimeError):
        m.allocate(3, 16)      # pool exhausted


def test_predictor_over_saved_program(tmp_path):
    """jit.save -> Config -> create_predictor -> handles -> run."""
    import paddle_tpu.inference as paddle_infer
    from paddle_tpu import jit, nn
    from paddle_tpu.jit.to_static import InputSpec

    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    path = str(tmp_path / "model")
    jit.save(net, path, input_spec=[InputSpec([2, 8], "float32")])

    cfg = paddle_infer.Config(path + ".pdmodel", path + ".pdiparams")
    predictor = paddle_infer.create_predictor(cfg)
    names = predictor.get_input_names()
    assert names == ["x0"]
    h = predictor.get_input_handle("x0")
    x = np.random.default_rng(0).normal(size=(2, 8)).astype(np.float32)
    h.copy_from_cpu(x)
    assert predictor.run()
    out_h = predictor.get_output_handle(predictor.get_output_names()[0])
    got = out_h.copy_to_cpu()
    ref = np.asarray(net(paddle.Tensor(x))._data)
    np.testing.assert_allclose(got, ref, atol=1e-5)
    # convenience list API
    got2 = predictor.run([x])[0]
    np.testing.assert_allclose(got2, ref, atol=1e-5)
