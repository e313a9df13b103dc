"""The serving step ends in its own tail (`ops/sampling.with_tail`): the NaN
screen, the gather of each lane's last hidden row, the output head over
those `B` rows and the sampler are the end of every engine's ONE compiled
round, and a scheduler round is that one program and one host fetch of
`[2, B]` int32: it makes no `[T, V]` array (docs/SERVING.md "One program,
one fetch"). Every packed row's logits come from a second program over the
same stack and head (`ops/sampling.all_rows`, `ragged_step`). Over the MLP
engine, a tiny Llama and a tiny DeepSeek-V3:

- the round's `sampled` is what the all-rows program's logits sample to on
  the host (`sample_tokens` over each lane's last row, its band finite), in
  a crafted mixed round and in every round of a served trace with greedy,
  temperature and top-k lanes across a preemption;
- `serving.step.programs` and `serving.step.fetches` rise by exactly one a
  round and nothing retraces as the batch's composition changes; the
  all-rows program counts its own traces and calls, 0 where nobody probes;
- the lowered round has no `[T, V]` array among its outputs;
- a NaN in an EARLY row of one lane's chunk fails that lane and no other;
- a token altered where it is now produced (inside the tail) is served.
"""
import functools

import jax.numpy as jnp
import numpy as np
import pytest

import jax

from paddle_tpu.framework import monitor
from paddle_tpu.inference import LlamaInferenceEngine
from paddle_tpu.inference import deepseek_v3_runner as dr
from paddle_tpu.inference import llama_runner as lr
from paddle_tpu.inference.deepseek_v3_runner import DeepseekV3InferenceEngine
from paddle_tpu.models import deepseek_v3 as dsv3
from paddle_tpu.models import llama_tiny
from paddle_tpu.ops import sampling
from paddle_tpu.ops.pallas.paged_attention import ragged_metadata
from paddle_tpu.ops.sampling import pack_lanes, sample_tokens, step_args
from paddle_tpu.resilience import faults
from paddle_tpu.serving import (MLPLMEngine, RequestStatus, ServingFrontend,
                                ServingMetrics)
from paddle_tpu.serving.engine import _mlp_head, _mlp_ragged_stack

VOCAB = 64
LANES, BLOCK, MAXB, CHUNK = 4, 4, 8, 8
DSV3 = dict(vocab_size=VOCAB, hidden_size=32, intermediate_size=64,
            moe_intermediate_size=16, num_hidden_layers=2,
            num_attention_heads=2, kv_lora_rank=16, q_lora_rank=None,
            qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
            n_routed_experts=4, n_shared_experts=1, num_experts_per_tok=2,
            first_k_dense_replace=1, routed_scaling_factor=2.0,
            norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=10000.0,
            rope_interleave=True, rope_scaling=None,
            max_position_embeddings=64, n_group=1, topk_group=1,
            scoring_func="sigmoid")
KINDS = ["mlp", "llama", "deepseek_v3"]


@pytest.fixture(scope="module")
def models():
    cfg = dsv3.DeepseekV3Config.from_hf(DSV3)
    llama = llama_tiny(vocab=VOCAB, layers=2, hidden=32, heads=2, seq=64)
    llama.eval()
    return {"llama": llama, "deepseek_v3": dsv3.DeepseekV3ForCausalLM(
        cfg, weights=dsv3.init_params(cfg, 3, jnp.float32, 0.08))}


def make_engine(kind, models, num_blocks=48):
    geom = dict(max_batch_size=LANES, num_blocks=num_blocks, block_size=BLOCK,
                max_blocks_per_seq=MAXB)
    if kind == "mlp":
        return MLPLMEngine(vocab_size=VOCAB, hidden=16, **geom)
    cls = LlamaInferenceEngine if kind == "llama" else \
        DeepseekV3InferenceEngine
    return cls(models[kind], **geom)


@pytest.fixture(autouse=True)
def _fresh_state():
    ServingMetrics.reset_monitor()
    faults.clear()
    yield
    faults.clear()


class CountingMetrics(ServingMetrics):
    rounds = 0

    def on_ragged_step(self, prefill_tokens, decode_lanes):
        super().on_ragged_step(prefill_tokens, decode_lanes)
        self.rounds += 1


def all_rows_round(eng, tokens, lanes, tables, temperature):
    """What a round samples, by the all-rows program and the host: every
    packed row's logits `[T, V]` and the `[2, B]` that `step_tail`'s
    contract makes of them (`sample_tokens` over each lane's last row;
    whether its whole band is finite). A token fed on the device is read
    out of the engine's `last_sampled` first."""
    tokens, lanes = np.array(tokens, np.int32), np.asarray(lanes)
    fed = np.flatnonzero(tokens < 0)
    if fed.size:
        tokens[fed] = np.asarray(eng.last_sampled)[0][-tokens[fed] - 1]
    logits = np.asarray(eng.ragged_step(tokens, lanes[:, 0], lanes[:, 1],
                                        tables))
    picked = sample_tokens(logits[lanes[:, 2]], temperature, lanes[:, 3],
                           lanes[:, 4], lanes[:, 5])
    finite = [np.isfinite(logits[row - q + 1:row + 1]).all()
              for q, row in lanes[:, [0, 2]]]
    return np.stack([picked, finite]).astype(np.int32), logits


class Recording:
    """An engine, remembering what every sampled step took and gave, beside
    what the all-rows program makes of the same step (its cache writes are
    indexed by position: made twice, they are made once)."""

    def __init__(self, inner):
        self._inner = inner
        self.rounds = []

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def sampled_step(self, tokens, lanes, tables, temperature):
        want, _logits = all_rows_round(self._inner, tokens, lanes, tables,
                                       temperature)
        sampled = self._inner.sampled_step(tokens, lanes, tables,
                                           temperature)
        self.rounds.append((np.array(lanes), np.array(temperature),
                            np.asarray(sampled), want))
        return sampled


# ---- one crafted round, at the engine ---------------------------------------
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("mode", ["greedy", "stochastic"])
def test_a_mixed_round_samples_what_sample_tokens_would(kind, mode, models):
    """Lane 0 decodes, lane 1 carries a mid chunk, lane 2 is empty, lane 3
    its prompt's final chunk."""
    eng = make_engine(kind, models)
    mgr = eng.manager
    rng = np.random.default_rng(5)
    T = 24

    def tables():
        return mgr.block_table_array([0, 1, 2, 3])

    q_lens = np.array([1, 5, 0, 3], np.int32)
    pre = np.array([6, 4, 0, 8], np.int32)            # cached before the round
    for lane in range(LANES):
        mgr.allocate(lane, int(pre[lane]))
    tokens = np.zeros((T,), np.int32)
    tokens[:pre.sum()] = rng.integers(1, VOCAB, pre.sum())
    eng.ragged_step(tokens, pre, pre, tables())       # fill the contexts
    for lane in range(LANES):
        mgr.append_tokens(lane, int(q_lens[lane]))
    tokens = np.zeros((T,), np.int32)
    tokens[:q_lens.sum()] = rng.integers(1, VOCAB, q_lens.sum())
    rows = np.array([0, 5, 0, 8], np.int32)
    temps = np.array([0.0, 0.9, 0.0, 0.7] if mode == "stochastic"
                     else [0.0] * 4, np.float32)
    lanes = pack_lanes(q_lens, pre + q_lens, rows, top_k=[0, 5, 0, 0],
                       seeds=[1, 22, 0, 333], draw_idx=[6, 0, 0, 0])
    want, logits = all_rows_round(eng, tokens, lanes, tables(), temps)
    picked, finite = sampled = np.asarray(
        eng.sampled_step(tokens, lanes, tables(), temps))
    assert sampled.dtype == np.int32 and sampled.shape == (2, LANES)
    np.testing.assert_array_equal(sampled, want)
    assert finite.tolist() == [1, 1, 1, 1]
    # greedy lanes are the argmax of their own last row, whatever the others
    for lane in (0, 2):
        assert picked[lane] == logits[rows[lane]].argmax()
    # the all-rows form is another program: it leaves the round's counter
    # where it was, and was traced once for this shape, whatever lanes hold
    names = ("serving.ragged_retraces", "serving.logits_retraces")
    assert [monitor.get(n) for n in names] == [1, 1]
    eng.ragged_step(tokens, np.zeros_like(q_lens), np.zeros_like(q_lens),
                    tables())
    assert [monitor.get(n) for n in names] == [1, 1]


# ---- every round of a served trace ------------------------------------------
def serve(eng, n_req=7, new_tokens=8, seed=0):
    fe = ServingFrontend(eng, prefill_chunk_tokens=CHUNK)
    rng = np.random.default_rng(seed)
    handles = []
    for i in range(n_req):
        kw = [dict(), dict(temperature=0.8, seed=11 + i),
              dict(temperature=1.1, top_k=4, seed=2**31 + i)][i % 3]
        handles.append(fe.submit(
            rng.integers(1, VOCAB, int(rng.integers(3, 20))).tolist(),
            max_new_tokens=new_tokens, **kw))
    fe.run_until_idle(max_steps=4000)
    assert all(h.status is RequestStatus.FINISHED for h in handles)
    return fe, handles


@pytest.mark.parametrize("kind", KINDS)
def test_every_served_round_matches_sample_tokens_across_a_preemption(
        kind, models):
    """Greedy, temperature and top-k requests over mixed rounds (decode
    lanes beside mid and final chunks and empty lanes); the tight pool
    preempts, and a preempted stochastic stream is the roomy pool's."""
    roomy = Recording(make_engine(kind, models, num_blocks=64))
    _, want = serve(roomy)
    tight = Recording(make_engine(kind, models, num_blocks=13))
    fe, got = serve(tight)
    assert monitor.get("serving.preemptions") > 0
    assert [h.tokens for h in got] == [h.tokens for h in want]
    shapes = set()
    for lanes, temps, sampled, want in roomy.rounds + tight.rounds:
        np.testing.assert_array_equal(sampled, want)
        assert sampled[1].all()
        q = lanes[:, 0]
        shapes.add((int((q == 1).sum()) > 0, int((q > 1).sum()) > 0,
                    int((q == 0).sum()) > 0, bool((temps > 0).any())))
    # decode lanes, chunks and empty lanes met in one round, greedy and not
    assert (True, True, True, True) in shapes


@pytest.mark.parametrize("kind", KINDS)
def test_a_round_is_one_program_and_one_fetch(kind, models):
    hook = CountingMetrics()
    fe = ServingFrontend(make_engine(kind, models), metrics=hook,
                         prefill_chunk_tokens=CHUNK)
    rng = np.random.default_rng(1)
    fe.submit(rng.integers(1, VOCAB, 5).tolist(), max_new_tokens=3)
    fe.run_until_idle()                                # compiled
    ServingMetrics.reset_monitor()
    names = ("serving.step.programs", "serving.step.fetches")
    hook.rounds = 0
    pending = [rng.integers(1, VOCAB, n).tolist() for n in (3, 17, 9, 4, 12)]
    handles = []
    while pending or not fe.scheduler.idle:
        if pending:                                    # a changing batch
            handles.append(fe.submit(pending.pop(), max_new_tokens=5,
                                     temperature=0.5 * (len(pending) % 2)))
        before = [monitor.get(n) for n in names] + [hook.rounds]
        fe.step()
        settled = hook.rounds - before.pop()
        launched, fetched = [monitor.get(n) - b
                             for n, b in zip(names, before)]
        # a step launches one round at most and settles one at most, the
        # one launched by the step before: one program, one fetch a round
        assert launched in (0, 1) and fetched == settled and settled in (0, 1)
        assert monitor.get(names[0]) - monitor.get(names[1]) \
            == (fe.scheduler._launched is not None)
    assert hook.rounds > 8
    assert monitor.get("serving.step.programs") == hook.rounds \
        == monitor.get("serving.step.fetches")
    assert monitor.get("serving.ragged_retraces") == 0
    assert monitor.get("serving.sample_retraces") == 0
    # nobody probed: the all-rows program was neither traced nor called
    assert hook.summary()["serving.step.all_rows_calls"] == 0 \
        == hook.summary()["serving.logits_retraces"]
    assert all(h.status is RequestStatus.FINISHED for h in handles)


def test_a_speculative_round_still_counts_three_programs_and_two_fetches():
    """`_decode_spec` keeps its NaN screen and its sampler as programs of
    their own over the verify window's `[B, S, V]` logits: the counters say
    what a round costs on either path."""
    from paddle_tpu.serving import NGramProposer, SpecDecodeConfig

    hook = CountingMetrics()
    fe = ServingFrontend(
        MLPLMEngine(vocab_size=VOCAB, hidden=16, max_batch_size=LANES,
                    num_blocks=48, block_size=BLOCK, max_blocks_per_seq=MAXB),
        metrics=hook, prefill_chunk_tokens=CHUNK,
        spec=SpecDecodeConfig(NGramProposer(), num_draft_tokens=2))
    fe.submit([1, 2, 3, 1, 2, 3, 1, 2], max_new_tokens=6)
    fe.run_until_idle()
    assert hook.rounds > 2
    assert monitor.get("serving.step.programs") == 3 * hook.rounds
    assert monitor.get("serving.step.fetches") == 2 * hook.rounds


# ---- the screen --------------------------------------------------------------
def test_the_tail_convicts_the_lane_whose_band_holds_the_nan():
    hidden = np.zeros((12, VOCAB), np.float32)
    hidden[np.arange(12), np.arange(12)] = 1.0         # row r's argmax is r
    q_lens = np.array([1, 5, 0, 3], np.int32)
    lanes = pack_lanes(q_lens, q_lens + 2)
    assert lanes[:, 2].tolist() == [0, 5, 5, 8]

    def tail(hidden, head=lambda state, rows, lane: rows):
        # the head sees the four sampled rows: the screen reads the bands
        step = sampling.with_tail(
            lambda tokens, q, kv, tables: (jnp.asarray(hidden),), head)
        return np.asarray(step(*sampling.call_arrays(
            np.zeros((12,), np.int32), lanes, np.zeros((4, 2)),
            np.zeros((4,))))[0])

    for row, lane in ((0, 0), (1, 1), (3, 1), (5, 1), (6, 3), (8, 3)):
        bad = hidden.copy()
        bad[row, 7] = np.nan if row % 2 else np.inf
        picked, finite = tail(bad)
        assert finite.tolist() == [int(i != lane) for i in range(4)], row
        ok = [i for i in (0, 1, 3) if i != lane]
        assert picked[ok].tolist() == lanes[ok, 2].tolist()
    # rows past the packed tokens (guard slots) convict nobody
    bad = hidden.copy()
    bad[9:] = np.nan
    assert tail(bad)[1].all()
    # a head that makes a lane's sampled row not finite convicts that lane;
    # the empty lane 2, whose row is lane 1's, reads finite all the same
    picked, finite = tail(hidden, lambda state, rows, lane: jnp.where(
        ((lane == 1) | (lane == 2))[:, None], jnp.inf, rows))
    assert finite.tolist() == [1, 0, 1, 1]
    assert picked[[0, 3]].tolist() == [0, 8]


POISON = VOCAB - 1


def halves(kind, eng):
    """An engine's `(stack, head, donated arguments)`: what its `_ragged`
    and its `_logits` were jitted from."""
    if kind == "mlp":
        return (functools.partial(_mlp_ragged_stack, block_size=BLOCK),
                _mlp_head, (1,))
    if kind == "llama":
        cfg = lr._StaticCfg(eng.config)
        return (functools.partial(lr._ragged_stack, cfg=cfg),
                functools.partial(lr._head, cfg=cfg), (1,))
    return (functools.partial(dr._ragged_stack, cfg=eng.config, narrow=True),
            functools.partial(dr._head, cfg=eng.config), (1, 2))


def poisoned_engine(kind="mlp", models=None):
    """An engine whose programs turn the final hidden row of every POISON
    token of a chunk to NaN: planted where the stack computes, before the
    tail. (Of a chunk: a decode lane may sample POISON itself.)"""
    eng = make_engine(kind, models)
    stack, head, donated = halves(kind, eng)

    def planted(*args):
        tokens, q_lens, kv_lens = args[-4:-1]
        hidden, *state = stack(*args)
        lane, _pos = ragged_metadata(q_lens, kv_lens, tokens.shape[0])
        bad = (tokens == POISON) & (q_lens[lane] > 1)
        return jnp.where(bad[:, None], jnp.nan, hidden), *state

    eng._ragged = jax.jit(sampling.with_tail(planted, head),
                          donate_argnums=donated)
    eng._logits = jax.jit(sampling.all_rows(planted, head),
                          donate_argnums=donated)
    return eng


@pytest.mark.parametrize("kind", KINDS)
def test_a_nan_in_an_early_row_of_a_chunk_fails_that_lane_only(kind, models):
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, POISON, n).tolist() for n in (6, 7, 5, 6)]
    clean = [h.tokens
             for h in _serve_prompts(poisoned_engine(kind, models), prompts)]
    prompts[2][1] = POISON                 # the chunk's second row of five
    handles = _serve_prompts(poisoned_engine(kind, models), prompts)
    assert handles[2].status is RequestStatus.FAILED
    assert handles[2].finish_reason == "nan_logits"
    assert handles[2].tokens == []
    for i in (0, 1, 3):
        assert handles[i].status is RequestStatus.FINISHED
        assert handles[i].tokens == clean[i]
    assert monitor.get("serving.isolated_faults.decode") == 1
    # the round's own flag convicted the lane: no probe replayed it
    assert monitor.get("serving.step.all_rows_calls") == 0


def _serve_prompts(eng, prompts, new_tokens=4):
    fe = ServingFrontend(eng, prefill_chunk_tokens=32)   # one chunk a prompt
    handles = [fe.submit(p, max_new_tokens=new_tokens) for p in prompts]
    fe.run_until_idle(max_steps=500)
    assert fe.scheduler.kv_leaked_blocks() == 0
    return handles


# ---- the injection sites, where they fire ------------------------------------
def test_the_decode_flag_and_the_sample_fault_fire_at_the_one_round():
    """`serve.decode` flag: the first live lane fails `nan_logits`, the
    rest are served clean. `serve.sample` raise: nothing is committed, the
    round replays, every stream is the clean one."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, VOCAB, n).tolist() for n in (4, 6, 5)]

    def run():
        eng = MLPLMEngine(vocab_size=VOCAB, hidden=16, max_batch_size=LANES,
                          num_blocks=48, block_size=BLOCK,
                          max_blocks_per_seq=MAXB)
        return _serve_prompts(eng, prompts, new_tokens=6)

    clean = [h.tokens for h in run()]
    faults.inject("serve.decode", after_n=2, times=1, action="flag")
    flagged = run()
    assert [h.status for h in flagged] == [
        RequestStatus.FAILED, RequestStatus.FINISHED, RequestStatus.FINISHED]
    assert flagged[0].finish_reason == "nan_logits"
    assert [h.tokens for h in flagged[1:]] == clean[1:]
    faults.clear()
    ServingMetrics.reset_monitor()
    faults.inject("serve.sample", after_n=2, times=1)
    replayed = run()
    assert [h.tokens for h in replayed] == clean
    assert monitor.get("serving.step_faults") == 1
    # the faulted round dispatched and was never fetched, nor the one
    # launched behind it, which had read its tokens on the device
    assert monitor.get("serving.step.programs") \
        == monitor.get("serving.step.fetches") + 2


# ---- the broken path, at the new seam -----------------------------------------
@pytest.mark.parametrize("kind", KINDS)
def test_a_token_altered_inside_the_tail_is_served(kind, models, monkeypatch):
    """The served token is the one the step's tail produces: shift it there
    and the served stream leaves `generate`'s, which samples on the host
    from the all-rows program's logits."""
    prompt = np.random.default_rng(4).integers(1, VOCAB, 9)

    def served():
        fe = ServingFrontend(make_engine(kind, models),
                             prefill_chunk_tokens=CHUNK)
        h = fe.submit(prompt.tolist(), max_new_tokens=6)
        fe.run_until_idle()
        return h.tokens

    want = make_engine(kind, models).generate(
        prompt[None], max_new_tokens=6)[0, len(prompt):].tolist()
    assert served() == want
    real = sampling.step_tail

    def off_by_one(logits, lanes, temperature):
        sampled = real(logits, lanes, temperature)
        return sampled.at[0].set((sampled[0] + 1) % VOCAB)

    monkeypatch.setattr(sampling, "step_tail", off_by_one)
    got = served()
    assert got != want and got[0] == (want[0] + 1) % VOCAB


# ---- the two programs, lowered and counted ----------------------------------
@pytest.mark.parametrize("kind", KINDS)
def test_the_lowered_round_returns_no_row_by_vocabulary_array(kind, models):
    """Ahead of any run: the round's outputs are `sampled [2, B]` and the
    engine's state; `[T, V]` float32 logits leave the all-rows program
    alone."""
    eng = make_engine(kind, models)
    T = LANES + CHUNK
    lead = (eng.params, eng.pools) if kind != "deepseek_v3" else (
        eng.params, eng.pool, eng.counters)
    q = np.array([1, CHUNK, 0, 1], np.int32)
    tables = np.zeros((LANES, MAXB), np.int32)
    arrays = step_args(np.zeros((T,), np.int32), q, q + 3, tables)
    outs = jax.tree.leaves(eng._ragged.lower(*lead, *arrays).out_info)
    assert (outs[0].shape, outs[0].dtype) == ((2, LANES), jnp.int32)
    assert all(o.shape != (T, VOCAB) for o in outs)
    outs = jax.tree.leaves(eng._logits.lower(
        *lead, *arrays[:1], q, q + 3, tables).out_info)
    assert (outs[0].shape, outs[0].dtype) == ((T, VOCAB), jnp.float32)


def test_the_audit_still_finds_the_sampler():
    from paddle_tpu.analysis import hlo_audit

    fn, args = hlo_audit.EXECUTABLES["sampler"]()
    assert fn.lower(*args).out_info.shape == (4, 1)


@pytest.mark.parametrize("kind", KINDS)
def test_a_probe_counts_as_no_retrace_of_the_round(kind, models):
    """`ragged_step` after served rounds is the all-rows program's first
    trace and not the round's: `serving.ragged_retraces` stays, and a run
    that never probes reads 0 for both of the all-rows counters."""
    eng = make_engine(kind, models)
    fe = ServingFrontend(eng, prefill_chunk_tokens=CHUNK)
    fe.submit([3, 1, 4, 1, 5, 9, 2, 6], max_new_tokens=4)
    fe.run_until_idle()
    names = ("serving.ragged_retraces", "serving.logits_retraces",
             "serving.step.all_rows_calls")
    assert [monitor.get(n) for n in names] == [1, 0, 0]
    assert monitor.get("serving.decode_retraces") == 1
    zeros = np.zeros((LANES,), np.int32)
    for _ in range(3):
        eng.ragged_step(np.zeros((LANES + CHUNK,), np.int32), zeros, zeros,
                        np.zeros((LANES, MAXB), np.int32))
    assert [monitor.get(n) for n in names] == [1, 1, 3]
    assert monitor.get("serving.decode_retraces") == 1
    # and the round is still the executable it was
    fe.submit([2, 7, 1, 8], max_new_tokens=3)
    fe.run_until_idle()
    assert [monitor.get(n) for n in names] == [1, 1, 3]
