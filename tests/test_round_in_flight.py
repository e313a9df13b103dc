"""A plain serving round is launched before the one before it is fetched
(docs/SERVING.md "A round in flight"): `Scheduler.step` launches round n+1
on tokens the device feeds itself (`ops/sampling.fed_token`, resolved in
`with_tail` from the engine's `last_sampled`) and THEN settles round n.

Over the engine kinds tier-1 builds at toy size (the MLP engine, a tiny
Llama, DeepSeek-V3, Cohere2-MoE with its windowed block group, and LoRA
lanes over the MLP engine):

- every stream is token for token what a driver serves that settles after
  every launch, greedy and seeded sampling alike;
- what cannot be known is discarded, never guessed: a lane that ends on
  EOS, is cancelled, preempted or convicted by the NaN flag has a successor
  in flight whose token is never read (`serving.step.wasted_lanes`), and
  nothing leaks;
- a dispatch fault and a fetch fault with a round in flight roll both
  rounds back and the replay serves the clean streams;
- a windowed block group never gives back a block that the rollback of two
  rounds needs;
- a settle from outside a step (a drain, a disaggregated handoff) can END a
  request whose token in flight was its EOS: it ends there and is never
  moved; a chunk that only has to be shorter to fit needs no settle;
- still ONE executable, one program and one fetch a round; the counters.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.framework import monitor
from paddle_tpu.inference import LlamaInferenceEngine
from paddle_tpu.inference.cache import BlockCacheManager
from paddle_tpu.inference.cohere2_moe_runner import Cohere2MoeInferenceEngine
from paddle_tpu.inference.deepseek_v3_runner import DeepseekV3InferenceEngine
from paddle_tpu.models import cohere2_moe as c2
from paddle_tpu.models import deepseek_v3 as dsv3
from paddle_tpu.models import llama_tiny
from paddle_tpu.observability import compile_trace
from paddle_tpu.ops import sampling
from paddle_tpu.resilience import faults
from paddle_tpu.serving import (DisaggRouter, FleetRouter, HandoffState,
                                MLPLMEngine, RequestStatus, ServingFrontend,
                                ServingMetrics, attach_adapters)
from paddle_tpu.serving.lora import random_adapter
from test_cohere2_moe import WINDOW, config as c2_config, \
    make_params as c2_params
from test_sampled_step import DSV3

VOCAB = 64
LANES, BLOCK, MAXB, CHUNK = 4, 4, 16, 8
KINDS = ["mlp", "llama", "deepseek_v3", "cohere2_moe", "lora"]


@pytest.fixture(scope="module")
def models():
    cfg = dsv3.DeepseekV3Config.from_hf(DSV3)
    llama = llama_tiny(vocab=VOCAB, layers=2, hidden=32, heads=2, seq=64)
    llama.eval()
    return {
        "llama": llama,
        "deepseek_v3": dsv3.DeepseekV3ForCausalLM(
            cfg, weights=dsv3.init_params(cfg, 3, jnp.float32, 0.08)),
        "cohere2_moe": c2.Cohere2MoeForCausalLM(c2_config(),
                                                weights=c2_params())}


def make_engine(kind, models, num_blocks=80):
    geom = dict(max_batch_size=LANES, num_blocks=num_blocks, block_size=BLOCK,
                max_blocks_per_seq=MAXB)
    if kind == "mlp":
        return MLPLMEngine(vocab_size=VOCAB, hidden=16, **geom)
    if kind == "lora":
        eng = attach_adapters(MLPLMEngine(vocab_size=VOCAB, hidden=16,
                                          **geom), pool_slots=4)
        for i in range(2):
            eng.adapter_pool.register(
                f"ad{i}", random_adapter(eng, rank=4, seed=i, scale=0.5))
        return eng
    cls = {"llama": LlamaInferenceEngine,
           "deepseek_v3": DeepseekV3InferenceEngine,
           "cohere2_moe": Cohere2MoeInferenceEngine}[kind]
    return cls(models[kind], **geom)


@pytest.fixture(autouse=True)
def _fresh_state():
    ServingMetrics.reset_monitor()
    monitor.reset_prefix("fleet.")
    faults.clear()
    yield
    faults.clear()


def requests(kind, n=7, new_tokens=8, sampled=False, seed=0, longest=20):
    """`submit` keywords of `n` requests: prompts shorter and longer than a
    chunk (and, for the windowed engine, than its window), every third on
    another adapter where the engine has adapters."""
    rng = np.random.default_rng(seed)
    if kind == "cohere2_moe":
        longest = WINDOW + 2 * CHUNK
    out = []
    for i in range(n):
        kw = dict(prompt_ids=rng.integers(
            1, VOCAB, int(rng.integers(3, longest))).tolist(),
            max_new_tokens=new_tokens + i % 3)
        if sampled:
            kw.update([dict(temperature=0.8, seed=11 + i),
                       dict(temperature=1.1, top_k=4, seed=2**31 + i),
                       dict()][i % 3])
        if kind == "lora" and i % 3:
            kw["adapter"] = f"ad{i % 3 - 1}"
        out.append(kw)
    return out


def serve(eng, reqs, settled=False, between=None):
    """Serve `reqs` to the end. `settled`: the driver settles after every
    launch, which is the scheduler that fetches every round before it
    plans the next. `between(fe, handles, step)` runs after every step."""
    return drive(ServingFrontend(eng, prefill_chunk_tokens=CHUNK), reqs,
                 settled, between)


def drive(fe, reqs, settled=False, between=None):
    eng = fe.scheduler.engine
    handles = [fe.submit(**kw) for kw in reqs]
    for step in range(4000):
        if fe.scheduler.idle:
            break
        fe.step()
        if settled:
            fe.scheduler.settle()
        if between is not None:
            between(fe, handles, step)
    assert fe.scheduler.idle
    assert fe.scheduler.kv_leaked_blocks() == 0
    eng.manager.check_consistency()
    assert eng.manager.num_seqs == 1            # the guard block's
    return fe, handles


def streams(handles):
    return [(h.status, h.finish_reason, h.tokens) for h in handles]


# ---- the same tokens ---------------------------------------------------------
@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("kind", KINDS)
def test_streams_are_the_settled_every_round_scheduler_s(kind, sampled,
                                                         models):
    reqs = requests(kind, sampled=sampled)
    _, want = serve(make_engine(kind, models), reqs, settled=True)
    assert monitor.get("serving.step.overlapped") == 0
    rounds_settled = monitor.get("serving.step.programs")
    ServingMetrics.reset_monitor()
    _, got = serve(make_engine(kind, models), reqs)
    assert streams(got) == streams(want)
    assert all(h.status is RequestStatus.FINISHED for h in got)
    # the work is the same work in another order: a freed slot is taken a
    # round later, so a few rounds more, and nearly all of them overlapped
    rounds = monitor.get("serving.step.programs")
    assert rounds_settled <= rounds <= rounds_settled + len(reqs)
    assert monitor.get("serving.step.fetches") == rounds
    assert monitor.get("serving.step.overlapped") >= rounds - 2
    assert monitor.get("serving.step.wasted_lanes") == 0


# ---- what cannot be known is discarded --------------------------------------
@pytest.mark.parametrize("kind", KINDS)
def test_a_lane_that_ends_on_eos_has_a_wasted_successor(kind, models):
    reqs = requests(kind, n=5)
    _, clean = serve(make_engine(kind, models), reqs, settled=True)
    # end two requests on a token they really emit, mid-stream
    for i in (1, 3):
        reqs[i]["eos_token_id"] = clean[i].tokens[3]
    ServingMetrics.reset_monitor()
    _, want = serve(make_engine(kind, models), reqs, settled=True)
    assert monitor.get("serving.step.wasted_lanes") == 0
    _, got = serve(make_engine(kind, models), reqs)
    assert streams(got) == streams(want)
    for i in (1, 3):
        assert got[i].finish_reason == "eos"
        assert len(got[i].tokens) <= 4
    # each EOS was met at a settle whose successor lane was in flight
    assert monitor.get("serving.step.wasted_lanes") == 2


@pytest.mark.parametrize("kind", KINDS)
def test_a_cancelled_lane_s_token_in_flight_is_never_read(kind, models):
    reqs = requests(kind, n=4, new_tokens=12)
    _, clean = serve(make_engine(kind, models), reqs, settled=True)

    def cancel_one(fe, handles, step):
        h = handles[2]
        if len(h.tokens) == 3 and not h.finished:
            assert fe.scheduler._launched is not None     # a token in flight
            assert fe.cancel(h)

    ServingMetrics.reset_monitor()
    _, got = serve(make_engine(kind, models), reqs, between=cancel_one)
    assert got[2].status is RequestStatus.CANCELLED
    assert got[2].tokens == clean[2].tokens[:3]
    for i in (0, 1, 3):
        assert streams(got)[i] == streams(clean)[i]
    assert monitor.get("serving.step.wasted_lanes") == 1


@pytest.mark.parametrize("kind", ["mlp", "llama", "deepseek_v3", "lora"])
def test_a_preempted_lane_replays_to_the_same_stream(kind, models):
    reqs = requests(kind, sampled=True)
    _, want = serve(make_engine(kind, models), reqs, settled=True)
    ServingMetrics.reset_monitor()
    _, got = serve(make_engine(kind, models, num_blocks=13), reqs)
    assert monitor.get("serving.preemptions") > 0
    assert streams(got) == streams(want)


@pytest.mark.parametrize("kind", KINDS)
def test_a_convicted_lane_s_successor_is_discarded(kind, models):
    reqs = requests(kind, n=4)
    _, clean = serve(make_engine(kind, models), reqs, settled=True)
    ServingMetrics.reset_monitor()
    # the flag poisons the first live lane of the round it is drawn for;
    # by the time that round is settled its successor is in flight
    faults.inject("serve.decode", after_n=4, times=1, action="flag")
    _, got = serve(make_engine(kind, models), reqs)
    failed = [h for h in got if h.status is RequestStatus.FAILED]
    assert len(failed) == 1 and failed[0].finish_reason == "nan_logits"
    for h, c in zip(got, clean):
        if h is failed[0]:
            assert h.tokens == c.tokens[:len(h.tokens)]
        else:
            assert (h.status, h.tokens) == (c.status, c.tokens)
    assert monitor.get("serving.isolated_faults.decode") == 1
    assert monitor.get("serving.step.wasted_lanes") == 1


def test_a_chunk_that_must_be_shorter_needs_no_settle():
    """Pool pressure with a round in flight: a prefill chunk that only has
    to SHRINK to fit moves no one else, so it is shortened where it stands
    and every round stays overlapped; a preemption would settle first."""
    def run(settled):
        ServingMetrics.reset_monitor()
        eng = MLPLMEngine(vocab_size=VOCAB, hidden=16, max_batch_size=LANES,
                          num_blocks=14, block_size=BLOCK,
                          max_blocks_per_seq=MAXB)
        fe = ServingFrontend(eng, prefill_chunk_tokens=CHUNK)
        rng = np.random.default_rng(0)
        chunks, step = [], eng.sampled_step

        def recording(tokens, lanes, *rest):
            chunks.extend(int(q) for q in np.asarray(lanes)[:, 0] if q > 3)
            return step(tokens, lanes, *rest)

        eng.sampled_step = recording
        hs = [fe.submit(rng.integers(1, VOCAB, 3).tolist(),
                        max_new_tokens=12) for _ in range(2)]
        for _ in range(10):
            fe.step()
            if settled:
                fe.scheduler.settle()
        hs.append(fe.submit(rng.integers(1, VOCAB, 24).tolist(),
                            max_new_tokens=4))
        while not fe.scheduler.idle:
            fe.step()
            if settled:
                fe.scheduler.settle()
        assert fe.scheduler.kv_leaked_blocks() == 0
        return streams(hs), chunks

    want, whole = run(settled=True)
    assert whole == [8, 8, 8]
    got, chunks = run(settled=False)
    assert got == want
    assert chunks == [8, 8, 4, 4]            # what the pool held, then the rest
    assert monitor.get("serving.preemptions") == 0
    assert monitor.get("serving.step.overlapped") \
        == monitor.get("serving.step.programs") - 1


# ---- faults with a round in flight ------------------------------------------
@pytest.mark.parametrize("site", ["serve.decode", "serve.sample"])
@pytest.mark.parametrize("kind", KINDS)
def test_a_step_fault_rolls_both_rounds_back_and_replays_clean(kind, site,
                                                               models):
    """`serve.decode` raises at a dispatch (the round before it is in
    flight: settled, then the failed launch undone); `serve.sample` at a
    fetch (the round behind it read tokens nobody will see: both go)."""
    reqs = requests(kind, sampled=True)
    _, clean = serve(make_engine(kind, models), reqs, settled=True)
    ServingMetrics.reset_monitor()

    def arm(fe, handles, step):
        if step in (3, 9):                  # a site holds one rule a time
            assert fe.scheduler._launched is not None
            faults.inject(site, times=1)

    _, got = serve(make_engine(kind, models), reqs, between=arm)
    assert streams(got) == streams(clean)
    assert monitor.get("serving.step_faults") == 2
    assert monitor.get("serving.engine_restarts") == 0
    unfetched = monitor.get("serving.step.programs") \
        - monitor.get("serving.step.fetches")
    # a failed dispatch launched nothing; a failed fetch strands its round
    # and the one behind it
    assert unfetched == (0 if site == "serve.decode" else 4)


# ---- windowed block groups ---------------------------------------------------
def test_a_windowed_group_keeps_what_a_two_round_rollback_needs():
    """Released by the committed length: the length before the append less
    what is still in flight of the sequence."""
    def grown(in_flight):
        mgr = BlockCacheManager(64, 4, 32, name="full",
                                further_groups=[("window", 64, 8)])
        mgr.allocate(0, 0)
        mgr.append_tokens(0, 20)                    # committed
        mgr.append_tokens(0, 8)                     # round n, in flight
        mgr.append_tokens(0, 8, in_flight=in_flight)   # round n+1
        return mgr

    held = grown(8)
    held.trim(0, 20)                                # both rounds undone
    held.check_consistency()
    assert held.seq_len(0) == 20
    with pytest.raises(ValueError, match="released behind its window"):
        grown(0).trim(0, 20)
    # and nothing is kept for longer than that: the next append, with the
    # two rounds committed, gives back what they held
    kept = grown(8)
    before = kept.seq_blocks(0, 1)
    kept.append_tokens(0, 1)
    assert kept.seq_blocks(0, 1) < before


def test_the_windowed_engine_survives_fetch_faults_past_its_window(models):
    reqs = requests("cohere2_moe", n=5, new_tokens=WINDOW)
    assert max(len(r["prompt_ids"]) for r in reqs) > WINDOW + CHUNK
    _, clean = serve(make_engine("cohere2_moe", models), reqs, settled=True)
    released = monitor.get("serving.kv.window_blocks_released")
    assert released > 0
    ServingMetrics.reset_monitor()

    def arm(fe, handles, step):         # prefill rounds and decode rounds
        if step % 7 == 3 and step < 42:
            faults.inject("serve.sample", times=1)

    _, got = serve(make_engine("cohere2_moe", models), reqs, between=arm)
    assert streams(got) == streams(clean)
    assert monitor.get("serving.step_faults") == 6
    assert monitor.get("serving.preemptions") == 0
    assert monitor.get("serving.kv.window_blocks_released") > 0


# ---- one executable, one program and one fetch a round ----------------------
@pytest.mark.parametrize("kind", KINDS)
def test_a_fed_round_is_the_same_executable(kind, models):
    eng = make_engine(kind, models)
    fe, _ = serve(eng, requests(kind, n=2, new_tokens=3))   # compiled
    ServingMetrics.reset_monitor()
    compiled = compile_trace.mark()
    fed = []
    step = eng.sampled_step

    def recording(tokens, *rest):
        fed.append(int((np.asarray(tokens) < 0).sum()))
        return step(tokens, *rest)

    eng.sampled_step = recording
    _, got = drive(fe, requests(kind, sampled=True, seed=1))
    assert all(h.status is RequestStatus.FINISHED for h in got)
    assert max(fed) > 1 and fed[0] == 0          # rounds fed and not
    assert monitor.get("serving.ragged_retraces") == 0
    assert compile_trace.mark() == compiled
    assert monitor.get("serving.step.programs") == len(fed) \
        == monitor.get("serving.step.fetches")


def test_a_token_is_fed_only_from_this_engine_s_last_step(models):
    """Whoever runs a sampled step of the engine between two of the
    scheduler's rounds (another scheduler) takes `last_sampled`: the
    scheduler sees it and settles before it launches. The all-rows program
    (a caller's `generate`, a probe) samples nothing and takes nothing."""
    reqs = requests("mlp", n=3)
    _, clean = serve(make_engine("mlp", models), reqs, settled=True)
    ServingMetrics.reset_monitor()

    def empty_step(eng):
        zeros = np.zeros((LANES,), np.int32)
        return (np.zeros((LANES + CHUNK,), np.int32), zeros, zeros,
                np.zeros((LANES, eng.manager.table_width), np.int32))

    def foreign_rows(fe, handles, step):
        if step % 3 == 0:
            eng = fe.scheduler.engine
            eng.ragged_step(*empty_step(eng))

    _, got = serve(make_engine("mlp", models), reqs, between=foreign_rows)
    assert streams(got) == streams(clean)
    assert monitor.get("serving.step.forced_settles") == 0
    assert monitor.get("serving.step.all_rows_calls") > 2
    ServingMetrics.reset_monitor()

    def foreign_step(fe, handles, step):
        if step % 3 == 0:
            eng = fe.scheduler.engine
            eng.sampled_step(*sampling.step_args(*empty_step(eng))[:4])

    _, got = serve(make_engine("mlp", models), reqs, between=foreign_step)
    assert streams(got) == streams(clean)
    rounds = monitor.get("serving.step.programs")
    assert 0 < monitor.get("serving.step.overlapped") < rounds - 2
    # each such settle is counted: a deployment that always lands there
    # (a wrapper that hides `last_sampled`) serves with no overlap
    forced = monitor.get("serving.step.forced_settles")
    assert forced > 0
    assert monitor.get("serving.step.overlapped") + forced <= rounds


# ---- a drain and a handoff settle first, and the settle may end the request ---
def _fleet_engine():
    return MLPLMEngine(vocab_size=VOCAB, hidden=16, max_batch_size=LANES,
                       num_blocks=48, block_size=BLOCK, max_blocks_per_seq=8,
                       seed=0)


def _fleet_prompts(n=6):
    rng = np.random.default_rng(5)
    return [rng.integers(1, VOCAB, int(rng.integers(2, 8))).tolist()
            for _ in range(n)]


def _fleet_reference(ps, eos, new_tokens=8):
    fe = ServingFrontend(_fleet_engine())
    hs = [fe.submit(p, max_new_tokens=new_tokens, eos_token_id=e)
          for p, e in zip(ps, eos)]
    fe.run_until_idle()
    return [(h.status, h.finish_reason, h.tokens) for h in hs]


def _first_at(tokens, k):
    """Whether `tokens[k]` shows there first: as an EOS it ends the stream
    at k + 1 tokens."""
    return tokens[k] not in tokens[:k]


def _fleet_clean(router):
    for rep in router.replicas:
        sch = rep.frontend.scheduler
        assert sch._launched is None
        assert sch.kv_leaked_blocks() == 0
        sch.engine.manager.check_consistency()


@pytest.mark.parametrize("relocate", [True, False],
                         ids=["relocated", "finish_in_place"])
def test_a_drain_with_an_eos_in_flight_ends_the_stream_there(relocate):
    """`drain_replica` settles the replica's round in flight before it
    lists whom to move: a request that the settle ended (its token in
    flight was its EOS) is not revived on another replica."""
    ps = _fleet_prompts()
    free = _fleet_reference(ps, [None] * len(ps))
    k = 3
    enders = [i for i, (_, _, t) in enumerate(free) if _first_at(t, k)]
    assert len(enders) >= 2
    eos = [t[k] if i in enders else None
           for i, (_, _, t) in enumerate(free)]
    want = _fleet_reference(ps, eos)
    r = FleetRouter(_fleet_engine, num_replicas=2)
    try:
        hs = [r.submit(p, max_new_tokens=8, eos_token_id=e)
              for p, e in zip(ps, eos)]
        drained = set()
        for _ in range(200):
            for i in enders:
                h = hs[i]
                # k tokens committed, the EOS still on the device
                if len(h.tokens) == k and not drained:
                    sch = r._rep(h.replica_id).frontend.scheduler
                    assert sch._launched is not None
                    drained.add(h.replica_id)
                    r.drain_replica(h.replica_id, relocate=relocate)
                    if relocate:        # settled before anyone is listed
                        assert h.finish_reason == "eos"
                        assert len(h.tokens) == k + 1
            if drained:
                break
            r.step()
        assert drained
        r.run_until_idle()
        assert [(h.status, h.finish_reason, h.tokens) for h in hs] == want
        for i in enders:
            assert hs[i].num_relocations == 0
        _fleet_clean(r)
    finally:
        r.close()


@pytest.mark.parametrize("fallback", [False, True],
                         ids=["shipped", "fold_fallback"])
def test_a_handoff_with_an_eos_in_flight_ends_the_stream_there(fallback):
    """A session leaves the prefill tier with its second token in flight;
    the pump settles first, and an answer that ends on that token ends on
    the prefill replica, whether the others ship their KV or fold."""
    ps = _fleet_prompts()
    free = _fleet_reference(ps, [None] * len(ps))
    enders = [i for i, (_, _, t) in enumerate(free) if _first_at(t, 1)][:3]
    assert len(enders) >= 2
    eos = [t[1] if i in enders else None
           for i, (_, _, t) in enumerate(free)]
    want = _fleet_reference(ps, eos)
    r = DisaggRouter(_fleet_engine, num_prefill=1, num_decode=1)
    try:
        if fallback:
            faults.inject("fleet.handoff", times=2)
        hs = [r.submit(p, max_new_tokens=8, eos_token_id=e)
              for p, e in zip(ps, eos)]
        r.run_until_idle()
        assert [(h.status, h.finish_reason, h.tokens) for h in hs] == want
        for i in enders:
            assert hs[i].finish_reason == "eos" and len(hs[i].tokens) == 2
            assert hs[i].num_relocations == 0
            assert r.handoff_state(hs[i]) is not HandoffState.DECODING
        assert monitor.get("fleet.handoffs") + monitor.get(
            "fleet.handoff_fallbacks") == len(ps) - len(enders)
        _fleet_clean(r)
    finally:
        r.close()


# ---- the counters and the contract of step() --------------------------------
def test_the_counters_and_what_step_returns(models):
    fe = ServingFrontend(make_engine("mlp", models),
                         prefill_chunk_tokens=CHUNK)
    sch = fe.scheduler
    h = fe.submit([5, 6, 7], max_new_tokens=3)
    assert not sch.idle
    assert fe.step() == 0 and h.tokens == []        # launched, not settled
    assert sch._launched is not None and sch.zero_progress_steps == 0
    assert fe.step() == 0 and len(h.tokens) == 1    # the prompt's round
    assert fe.step() == 1 and len(h.tokens) == 2    # the first decode round
    # the third token is the last by max_new_tokens: nothing was launched
    # behind it, and this step only settles
    assert sch._launched is not None and not sch.idle
    programs = monitor.get("serving.step.programs")
    assert fe.step() == 1 and h.finished and sch.idle
    assert monitor.get("serving.step.programs") == programs == 3
    assert monitor.get("serving.step.overlapped") == 2
    assert monitor.get("serving.step.overlap_share") == round(2 / 3, 4)
    assert monitor.get("serving.step.wasted_lanes") == 0
    assert monitor.get("serving.step.forced_settles") == 0
    # `settle` outside a step commits what is in flight and says how much
    h2 = fe.submit([9, 8], max_new_tokens=4)
    fe.step()
    fe.step()
    assert len(h2.tokens) == 1 and sch.settle() == 1 and len(h2.tokens) == 2
    assert sch.settle() == 0
    fe.run_until_idle()
    assert h2.tokens == serve(make_engine("mlp", models), [dict(
        prompt_ids=[9, 8], max_new_tokens=4)], settled=True)[1][0].tokens


def test_with_tail_feeds_a_lane_its_own_last_token():
    """`fed_token(b)` in a step's tokens is `fed[0, b]`, whatever else the
    buffer holds; no negative token, the step it always was."""
    seen = {}

    def stack(tokens, q_lens, kv_lens, tables):
        seen["tokens"] = tokens
        return jnp.zeros((tokens.shape[0], 8), jnp.float32),

    step = sampling.with_tail(stack, lambda state, rows, lane: rows)
    lanes = sampling.pack_lanes([1, 0, 1, 2], [3, 0, 5, 4])
    fed = np.array([[7, 1, 2, 3], [1, 1, 1, 1]], np.int32)
    tokens = np.array([sampling.fed_token(2), sampling.fed_token(0), 4, 6,
                       0, 0], np.int32)
    step(tokens, lanes, np.zeros((4, 2), np.int32),
         np.zeros((4,), np.float32), fed)
    assert np.asarray(seen["tokens"]).tolist() == [2, 7, 4, 6, 0, 0]
    assert sampling.call_arrays(tokens, lanes, np.zeros((4, 2)),
                                np.zeros((4,)))[4].tolist() \
        == [[0] * 4, [0] * 4]


def test_the_counters_of_a_benchmark_run_are_printed_after_its_line(tmp_path):
    """`tools/bench_counters.py` runs a checkout's unedited `benchmark/run.py`
    in its own process and prints the registry that run filled."""
    import json
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    (tmp_path / "benchmark").mkdir()
    (tmp_path / "benchmark" / "run.py").write_text(
        "import sys\n"
        "from paddle_tpu.framework import monitor\n"
        "monitor.inc('serving.step.programs', 3)\n"
        "monitor.inc('serving.step.forced_settles')\n"
        "monitor.inc('serving.prefills', 9)\n"
        "print('{\"args\": %d}' % len(sys.argv[1:]))\n"
        "sys.exit(4)\n")
    run = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "bench_counters.py"),
         str(tmp_path), "--workload", "a-cell", "--seed", "3000000001"],
        env=dict(os.environ, PYTHONPATH=root, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    line, counters = run.stdout.strip().splitlines()[-2:]
    assert run.returncode == 4                     # the benchmark's own
    assert json.loads(line) == {"args": 4}
    assert counters.startswith("COUNTERS ")
    assert json.loads(counters[len("COUNTERS "):]) == {
        "serving.step.forced_settles": 1, "serving.step.programs": 3}
