"""The two MoE engines run a step's row-wise work over the live prefix of the
packed buffer (`inference/live_prefix.py`): ONE executable whose width is
chosen on the device from `sum(q_lens)`. On the CPU at toy widths, with
`lanes + chunk > lanes` so that the switch is in the program:

- a backlog through `ServingFrontend` (chunked rounds, decode-only rounds, a
  round of few lanes beside a short chunk, a preemption and its replay) gives,
  greedy, token for token what `model_forward` (no cache, no `cond`) gives and
  what the same engine gives with no switch in its program;
- `serving.ragged_retraces` moves once over all of it;
- `narrow_steps`, `steps` and the gauge `serving.step.live_prefix_share` read
  what the rounds' `q_lens` say;
- the helper alone: row outputs are the whole function's on the live prefix
  and WHATEVER after it (the blank's own fill, here made NaN), the others are
  equal;
- nothing reads a blank row: the backlog served with every blank buffer full
  of NaN (`ops/pallas/_support.blank`, the one maker of them), by the XLA
  composites and by the kernels that take and leave the packed buffers as they
  are (the interpreter's unwritten output rows are NaN too), gives the same
  tokens and moves no fault, retrace or restart counter;
- the fault probe's one-lane replay and `verify_step` give the rows they gave.
"""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.framework import flags, monitor
from paddle_tpu.inference import live_prefix
from paddle_tpu.inference.cohere2_moe_runner import Cohere2MoeInferenceEngine
from paddle_tpu.inference.deepseek_v3_runner import DeepseekV3InferenceEngine
from paddle_tpu.models import cohere2_moe as c2
from paddle_tpu.models import deepseek_v3 as dsv3
from paddle_tpu.ops.pallas import _support
from paddle_tpu.serving import RequestStatus, ServingFrontend

LANES, CHUNK, BS, WIDTH, WINDOW = 4, 16, 8, 16, 24
T = LANES + CHUNK

KANANA = dsv3.DeepseekV3Config(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=3, num_attention_heads=4,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=8, n_shared_experts=2, num_experts_per_tok=2,
    first_k_dense_replace=1, routed_scaling_factor=2.448,
    max_position_embeddings=256)
CMDAPLUS = c2.Cohere2MoeConfig(
    vocab_size=256, hidden_size=64, intermediate_size=32,
    num_hidden_layers=3, num_attention_heads=8, num_key_value_heads=2,
    head_dim=16, num_experts=8, num_experts_per_tok=2, num_shared_experts=2,
    sliding_window=WINDOW, layer_types=(c2.SLIDING, c2.SLIDING, c2.FULL),
    max_position_embeddings=512, held_experts=(2, 4))

ARCHS = {
    "deepseek_v3": types.SimpleNamespace(
        mod=dsv3, cfg=KANANA, model=dsv3.DeepseekV3ForCausalLM,
        engine=DeepseekV3InferenceEngine),
    "cohere2_moe": types.SimpleNamespace(
        mod=c2, cfg=CMDAPLUS, model=c2.Cohere2MoeForCausalLM,
        engine=Cohere2MoeInferenceEngine),
}


@pytest.fixture(scope="module", params=sorted(ARCHS))
def arch(request):
    a = ARCHS[request.param]
    a.params = a.mod.init_params(a.cfg, 3, jnp.float32, 0.08)
    return a


def build(arch, num_blocks=LANES * WIDTH + 1):
    """An engine that remembers every step's `q_lens`."""
    class Recording(arch.engine):
        q_lens = None

        def sampled_step(self, tokens, lanes, tables, temperature):
            assert len(tokens) == T
            self.q_lens.append(np.asarray(lanes)[:, 0].copy())
            return super().sampled_step(tokens, lanes, tables, temperature)

    eng = Recording(arch.model(arch.cfg, weights=arch.params),
                    max_batch_size=LANES, num_blocks=num_blocks,
                    block_size=BS, max_blocks_per_seq=WIDTH)
    eng.q_lens = []
    return eng


# the chunk budget goes round the lanes in slot order; the last two prompts end
# in a round of two short chunks: `n_live <= lanes` with a chunk in it
LENGTHS = (5, 21, 18, 3, 34, 2)
NEW = 12


def serve(arch, num_blocks):
    rng = np.random.default_rng(39)
    prompts = [rng.integers(1, 256, n).tolist() for n in LENGTHS]
    eng = build(arch, num_blocks)
    fe = ServingFrontend(eng, prefill_chunk_tokens=CHUNK)
    handles = [fe.submit(p, max_new_tokens=NEW) for p in prompts]
    fe.run_until_idle()
    assert all(h.status is RequestStatus.FINISHED for h in handles)
    return eng, prompts, handles


def poison(shape, dtype):
    """`_support.blank` for a test: a buffer nobody has written reads NaN
    (the least integer, True), so whoever reads a row of it shows."""
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.full(shape, jnp.nan, dtype)
    if dtype == jnp.bool_:
        return jnp.ones(shape, dtype)
    return jnp.full(shape, jnp.iinfo(dtype).min, dtype)


@pytest.fixture(scope="module")
def backlog(arch):
    """The backlog served twice by the engine as it is (roomy, and in a pool
    so tight that lanes are preempted and replayed), with what the monitor
    and the engine counted, and once by the engine with no switch."""
    before = monitor.get("serving.ragged_retraces") or 0
    eng, prompts, roomy = serve(arch, LANES * WIDTH + 1)
    retraces = (monitor.get("serving.ragged_retraces") or 0) - before
    load = eng.expert_load()
    gauge = monitor.get("serving.step.live_prefix_share")
    tight_eng, _, tight = serve(arch, 10)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(live_prefix, "rowwise", lambda n_live, narrow, t:
                   dsv3.whole(t))
        _, _, unswitched = serve(arch, LANES * WIDTH + 1)
    return types.SimpleNamespace(
        eng=eng, prompts=prompts, roomy=roomy, tight=tight,
        tight_eng=tight_eng, unswitched=unswitched, retraces=retraces,
        load=load, gauge=gauge)


def test_the_backlog_holds_every_kind_of_round(backlog):
    sums = [int(q.sum()) for q in backlog.eng.q_lens]
    chunk = [bool((q > 1).any()) for q in backlog.eng.q_lens]
    assert any(s > LANES for s in sums), "a chunked round, wide"
    assert any(not c for c in chunk), "a decode-only round"
    assert any(c and s <= LANES for c, s in zip(chunk, sums)), \
        "few lanes beside a short chunk: narrow with a chunk in it"
    assert sum(h._req.num_preemptions for h in backlog.tight) > 0


def test_served_tokens_are_model_forwards(arch, backlog):
    forward = jax.jit(lambda ids: arch.mod.model_forward(arch.params, ids,
                                                         arch.cfg))
    for p, h in zip(backlog.prompts, backlog.roomy):
        ids = np.zeros((LENGTHS[4] + NEW,), np.int32)
        ids[:len(p) + NEW] = p + h.tokens
        want = np.argmax(np.asarray(forward(ids)), -1)
        assert want[len(p) - 1:len(p) + NEW - 1].tolist() == h.tokens


def test_served_tokens_are_the_unswitched_engines(backlog):
    assert [h.tokens for h in backlog.roomy] \
        == [h.tokens for h in backlog.unswitched]


def test_preempted_and_replayed_lanes_give_the_same_tokens(backlog):
    assert [h.tokens for h in backlog.tight] \
        == [h.tokens for h in backlog.roomy]


def test_one_executable_over_all_of_it(backlog):
    assert backlog.retraces == 1


def test_counters_read_what_the_rounds_q_lens_say(backlog):
    for eng, load in ((backlog.eng, backlog.load),
                      (backlog.tight_eng, backlog.tight_eng.expert_load())):
        narrow = sum(int(q.sum()) <= LANES for q in eng.q_lens)
        assert load["steps"] == len(eng.q_lens)
        assert load["narrow_steps"] == narrow
        assert 0 < narrow < load["steps"]
    load = backlog.load
    assert backlog.gauge == round(load["narrow_steps"] / load["steps"], 4)


FAULTS = ("serving.step_faults", "serving.isolated_faults",
          "serving.engine_restarts", "serving.state.restarts")


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["composites", "kernels"])
def test_nothing_reads_a_blank_row(arch, backlog, kernels):
    """ISSUE 49: a guard row of a packed buffer holds whatever. With every
    blank full of NaN the backlog (chunked, decode-only and short rounds)
    gives the tokens it gave, through the XLA composites and through the
    kernels' buffer-taking calls (the interpreter: a kernel's unwritten
    output rows are NaN there too); one executable, no fault, no restart."""
    before = {k: monitor.get(k) or 0 for k in FAULTS + (
        "serving.ragged_retraces",)}
    flags.set_flags({"pallas_interpret": kernels})
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(_support, "blank", poison)
            eng, _, served = serve(arch, LANES * WIDTH + 1)
    finally:
        flags.set_flags({"pallas_interpret": False})
    assert [h.tokens for h in served] == [h.tokens for h in backlog.roomy]
    assert all(h._req.num_preemptions == 0 for h in served)
    moved = {k: (monitor.get(k) or 0) - v for k, v in before.items()}
    assert moved == dict.fromkeys(FAULTS, 0) | {"serving.ragged_retraces": 1}
    load = eng.expert_load()
    assert load["steps"] == len(eng.q_lens) == backlog.load["steps"]
    assert load["tokens"].tolist() == backlog.load["tokens"].tolist()


@pytest.mark.parametrize("blank", ["zeros", "poisoned"])
@pytest.mark.parametrize("n_live", [LANES, LANES + 1])
def test_helper_alone(arch, n_live, blank, monkeypatch):
    """The expert layer's feed-forward as the wrapped function: `out` is a
    row output, `tokens_per_expert` is not."""
    if blank == "poisoned":
        monkeypatch.setattr(_support, "blank", poison)
    rng = np.random.default_rng(n_live)
    h = jnp.asarray(rng.standard_normal((T, arch.cfg.hidden_size)),
                    jnp.float32)
    live = jnp.arange(T) < n_live
    p = arch.mod.layer_params(arch.params, arch.cfg.num_hidden_layers - 1)

    k = arch.cfg.num_experts_per_tok

    held, mean_of = (arch.cfg.held, arch.cfg.num_shared_experts) \
        if arch.mod is c2 else (None, 1)

    def fn(h, live):
        """The layer's three steps as the engine runs them. `out [T, H]` and
        the sorted rows `xs [T * k, H]` are row outputs, `tokens_per_expert`
        is not."""
        (xs, order, keep, weights), (mine, sizes) = dsv3.moe_dispatch(
            h, p, arch.cfg, live, held, arch.mod.route)
        out = dsv3.moe_combine(h, dsv3.moe_experts(xs, mine, p), order, keep,
                               weights, p, mean_of)
        return (out, xs), sizes

    (want, want_xs), want_sizes = fn(h, live)
    (got, xs), sizes = jax.jit(lambda h, live, n: live_prefix.rowwise(
        n, LANES, T)(fn)(h, live))(h, live, jnp.int32(n_live))
    assert np.asarray(sizes).tolist() == np.asarray(want_sizes).tolist()
    assert int(np.asarray(sizes).sum()) == n_live * k
    assert got.shape == want.shape and xs.shape == want_xs.shape
    np.testing.assert_allclose(got[:n_live], want[:n_live], rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_array_equal(xs[:n_live * k], want_xs[:n_live * k])
    if n_live <= LANES:
        # past the prefix nobody computed a row: the blank's own fill
        fill = np.isnan if blank == "poisoned" else np.logical_not
        assert fill(np.asarray(got[LANES:])).all()
        assert fill(np.asarray(xs[LANES * k:])).all()
    else:
        # the whole function: a guard row still gets its shared experts
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        assert np.asarray(got[n_live:]).any()

    # no switch where the buffer is no wider than the prefix, or none asked
    def switches(narrow, t=T):
        return " cond[" in str(jax.make_jaxpr(lambda h, live: live_prefix.
                                              rowwise(jnp.int32(1), narrow,
                                                      t)(fn)(h, live))(
                                                          h, live))
    assert switches(LANES) and not switches(T) and not switches(None)


def test_probe_replay_and_verify_give_the_rows_they_gave(arch):
    """A one-lane replay as the scheduler's fault probe makes it (the lane's
    tokens first in the packed buffer, every other lane empty), narrow and
    wide, and a verify window: the rows of `model_forward`, and of the
    engine with no switch."""
    rng = np.random.default_rng(7)
    ids = rng.integers(1, 256, 30).astype(np.int32)
    want = np.asarray(arch.mod.model_forward(arch.params, jnp.asarray(ids),
                                             arch.cfg))

    def rows_of(eng):
        mgr, lane, sid, out = eng.manager, 2, 11, []
        fed = 0
        for n in (CHUNK, 3, 1):           # wide, narrow, a decode lane
            mgr.append_tokens(sid, n) if fed else mgr.allocate(sid, n)
            row = mgr.block_table_array([sid])[0]
            tables = np.zeros((LANES, len(row)), np.int32)
            tables[lane] = row
            tok = np.zeros((T,), np.int32)
            tok[:n] = ids[fed:fed + n]
            q = np.zeros((LANES,), np.int32)
            kv = np.zeros((LANES,), np.int32)
            q[lane], kv[lane] = n, fed + n
            for _ in range(2):            # the step, then its replay
                got = np.asarray(eng.ragged_step(tok, q, kv, tables))[:n]
            out.append(got)
            fed += n
        s = 5
        mgr.append_tokens(sid, s)
        got = np.asarray(eng.verify_step(
            ids[None, fed:fed + s], np.asarray([fed + s], np.int32),
            mgr.block_table_array([sid])))
        out.append(got[0])
        return np.concatenate(out)

    eng = build(arch)
    got = rows_of(eng)
    np.testing.assert_allclose(got, want[:len(got)], rtol=2e-4, atol=2e-5)
    assert (np.argmax(got, -1) == np.argmax(want[:len(got)], -1)).all()
    load = eng.expert_load()
    assert (load["steps"], load["narrow_steps"]) == (7, 4)  # verify: no switch
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(live_prefix, "rowwise", lambda n_live, narrow, t:
                   dsv3.whole(t))
        np.testing.assert_allclose(got, rows_of(build(arch)), rtol=1e-5, atol=1e-6)
