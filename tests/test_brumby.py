"""The Brumby architecture (`models/brumby.py`: power retention, one
recurrent state a sequence) and its serving engine
(`inference/brumby_runner.py`) at a small size on the CPU, held against the
benchmark's plain reference (`benchmark/reference/brumby_arch.py`, loaded by
path: it imports nothing of the program and is written in the ATTENTION form,
while the engine runs the recurrent and the chunked ones).

Float32 weights unless said, and SLOW gates (half-lives of 64 to 4,096 tokens,
`half_life_bias`): with a zero bias a random gate forgets in a token and no
test would see a state carried. Tolerances: float32 against float32 `highest`
differ in the order of sums, and the recurrent form sums a thousand decayed
outer products where the attention form sums scores: readings 2e-6 to 2e-5 on
logits of spread 0.5, so 2e-4 is ten times the noise. A state kept in
bfloat16 moves the same logits by 1e-2 (`test_a_bfloat16_state_fails`), fifty
times the tolerance; a token fed twice, a state leaked to the next tenant or
a chunk boundary's decay dropped move them by more.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.framework import flags, monitor
from paddle_tpu.inference import kv_migrate
from paddle_tpu.inference.brumby_runner import BrumbyInferenceEngine
from paddle_tpu.inference.cache import StateNotTrimmable
from paddle_tpu.models import brumby as bm
from paddle_tpu.ops.pallas import power_retention as pr
from paddle_tpu.resilience import faults
from paddle_tpu.serving import RequestStatus, ServingFrontend
from test_sampled_step import all_rows_round

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    path = os.path.join(ROOT, "benchmark", "reference", "brumby_arch.py")
    spec = importlib.util.spec_from_file_location("ref_brumby", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()

HF = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
          num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
          head_dim=16, rms_norm_eps=1e-6, rope_theta=1000000,
          max_position_embeddings=256, attention_bias=False, hidden_act="silu",
          rope_scaling=None, tie_word_embeddings=False, model_type="brumby",
          use_sliding_window=False, sliding_window=None)
CFG = bm.BrumbyConfig.from_hf(HF)
TOL = 2e-4
LANES, CHUNK = 4, 16


def make_params(dtype=jnp.float32, seed=5):
    """Weights large enough that the retention is not flat."""
    return {k: v if k.endswith("bias") else v.astype(dtype) for k, v in
            bm.init_params(CFG, seed, jnp.float32, 0.08).items()}


@pytest.fixture
def rng():
    return np.random.default_rng(45)


@pytest.fixture(params=[False, True], ids=["xla", "pallas_interpret"])
def interpret(request):
    flags.set_flags({"pallas_interpret": request.param})
    yield request.param
    flags.set_flags({"pallas_interpret": False})


class Recording(BrumbyInferenceEngine):
    """The engine, remembering every packed row's logits with the request
    and position it belongs to."""

    def __init__(self, *a, **k):
        super().__init__(*a, **k)
        self.rows, self.slots_of = [], None

    def sampled_step(self, tokens, lanes, tables, temperature):
        # every packed row's logits come from the all-rows program, and a
        # state cannot take a step twice: this engine's round IS that
        # program, sampled on the host as the tail would
        sampled, logits = all_rows_round(self, tokens, lanes, tables,
                                         temperature)
        sampled = self.last_sampled = jnp.asarray(sampled)
        cursor = 0
        for lane, (n, kv) in enumerate(lanes[:, :2]):
            req = self.slots_of()[lane]
            for j in range(int(n)):
                self.rows.append((req.req_id, int(kv) - int(n) + j,
                                  logits[cursor + j]))
            cursor += int(n)
        return sampled


def build(params=None, engine=BrumbyInferenceEngine, lanes=LANES, **kw):
    model = bm.BrumbyForCausalLM(CFG, weights=params or make_params())
    return engine(model, max_batch_size=lanes, **kw)


def serve(prompts, new_tokens, params=None, engine=Recording, **kw):
    eng = build(params, engine)
    fe = ServingFrontend(eng, prefill_chunk_tokens=CHUNK, **kw)
    if isinstance(eng, Recording):
        eng.slots_of = lambda: fe.scheduler.slots
    handles = [fe.submit(p, max_new_tokens=new_tokens) for p in prompts]
    fe.run_until_idle()
    assert all(h.status is RequestStatus.FINISHED for h in handles)
    eng.manager.check_consistency()
    return eng, fe, handles


def prompts_of(rng, lengths):
    return [rng.integers(1, HF["vocab_size"], n).tolist() for n in lengths]


def reference_logits(params, ids):
    f32 = {k: v.astype(jnp.float32) for k, v in params.items()}
    return np.asarray(jax.jit(lambda i: ref.forward(f32, i, HF))(
        jnp.asarray(ids, jnp.int32)))


# ---- the layer's three forms -----------------------------------------------------------
def _rows(rng, n, groups=2, d=16):
    return (jnp.asarray(rng.normal(size=(n, groups, d)), jnp.float32) * 0.4,
            jnp.asarray(rng.normal(size=(n, d)), jnp.float32),
            jnp.asarray(rng.normal(size=(n, d)), jnp.float32),
            jnp.asarray(-np.abs(rng.normal(size=(n,))) * 0.05, jnp.float32))


@pytest.mark.parametrize("d", [4, 16, 128])
def test_phi_is_the_squared_inner_product(rng, d):
    q, k = rng.normal(size=(2, 3, d)).astype(np.float32)
    got = jnp.sum(pr.phi(q) * pr.phi(k), axis=(-2, -1))
    np.testing.assert_allclose(got, np.sum(q * k, -1) ** 2, rtol=1e-4,
                               atol=1e-4)
    assert pr.phi(q).shape[-2:] == (d // 2 + 1, d)
    assert d * (d + 1) // 2 <= pr.feature_dim(d) <= d * (d + 1) // 2 + d // 2


@pytest.mark.parametrize("chunks", [(37,), (1,) * 37, (1, 8, 21, 7),
                                    (16, 16, 5), (36, 1)],
                         ids=["whole", "ones", "mixed", "ragged_last",
                              "last_of_one"])
def test_the_three_forms_agree(rng, chunks):
    """Recurrent (token by token), attention (one chunk from a zero state)
    and chunked (any chunking, a chunk of 1 and a ragged last one among
    them): one y, one state."""
    q, k, v, a = _rows(rng, 37)
    o = pr.n_offsets(16)
    zero = jnp.zeros((o, 16, 16)), jnp.zeros((o, 16))
    y_rec, S_rec, z_rec = bm.retention_recurrent(q, k, v, a, *zero, 1e-6)
    y_att, S_att, z_att = bm.retention_chunk(q, k, v, a, *zero, 1e-6)
    np.testing.assert_allclose(y_rec, y_att, atol=2e-5)
    S, z, ys, at = *zero, [], 0
    for n in chunks:
        y, S, z = bm.retention_chunk(q[at:at + n], k[at:at + n], v[at:at + n],
                                     a[at:at + n], S, z, 1e-6)
        ys.append(y)
        at += n
    np.testing.assert_allclose(jnp.concatenate(ys), y_att, atol=2e-5)
    for got in ((S, z), (S_att, z_att)):
        np.testing.assert_allclose(got[0], S_rec, atol=2e-5)
        np.testing.assert_allclose(got[1], z_rec, atol=2e-5)


def test_the_attention_form_is_the_reference_s(rng):
    """`retention_chunk` from a zero state against the reference's weights
    `exp(G_i - G_j) (q_i . k_j)^2`, written out here."""
    q, k, v, a = _rows(rng, 24, groups=1)
    y, _, _ = bm.retention_chunk(q, k, v, a, jnp.zeros((9, 16, 16)),
                                 jnp.zeros((9, 16)), 1e-6)
    G = np.cumsum(np.asarray(a, np.float64))
    w = np.tril(np.exp(G[:, None] - G[None, :])
                * (np.asarray(q[:, 0], np.float64) @ np.asarray(k).T) ** 2)
    want = w @ np.asarray(v) / (w.sum(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(y[:, 0], want, atol=2e-5)


# ---- the model and the engine against the reference ------------------------------------
def test_model_forward_is_the_reference(rng):
    params = make_params()
    ids = rng.integers(1, 256, 96)
    got = bm.model_forward(params, jnp.asarray(ids, jnp.int32), CFG)
    np.testing.assert_allclose(got, reference_logits(params, ids), atol=TOL)


def test_the_reference_blocked_is_the_reference_dense(rng, monkeypatch):
    params = make_params()
    ids = jnp.asarray(rng.integers(1, 256, 64), jnp.int32)
    dense = ref.forward(params, ids, HF, retain=ref.dense_retention)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 16)
    monkeypatch.setattr(ref, "MLP_ROWS", 32)
    np.testing.assert_allclose(ref.forward(params, ids, HF), dense, atol=1e-5)


def test_gate_bias_spans_the_half_lives():
    draws = np.asarray([-0.08, -0.02, 0.0, 0.02, 0.08], np.float32)
    keep = jax.nn.sigmoid(ref.gate_bias(draws))
    half = np.log(0.5) / np.log(np.asarray(keep, np.float64))
    assert 64 <= half[0] < 65 and 4000 < half[-1] <= 4096
    assert abs(half[2] - 512) < 2 and np.all(np.diff(half) > 0)
    np.testing.assert_allclose(
        bm.half_life_bias(half), np.asarray(ref.gate_bias(draws)), atol=2e-3)


def test_served_logits_match_reference(rng, interpret):
    """Prefill in chunks (several lanes' chunks in one step, a prompt of one
    token, one longer than three chunks), then decode through the state,
    against the reference's full forward: every row's LOGITS."""
    params = make_params()
    prompts = prompts_of(rng, [1, 53, 16, 7, 30])       # 5 requests, 4 lanes
    eng, _fe, handles = serve(prompts, 12, params)
    full = {h.request_id: reference_logits(params, p + h.tokens)
            for p, h in zip(prompts, handles)}
    assert len(eng.rows) == sum(len(p) + 11 for p in prompts)
    for req, pos, logits in eng.rows:
        np.testing.assert_allclose(logits, full[req][pos], atol=TOL)
    assert eng.state_resets() == 5 == monitor.get("serving.state.resets")


def test_a_bfloat16_state_fails(rng):
    """The tolerance holds the state's stated precision: rounding `S` and
    `z` to bfloat16 between steps moves the served logits far past it."""
    params = make_params()

    class Rounding(Recording):
        def sampled_step(self, *a):
            out = super().sampled_step(*a)
            S, z, *rest = self.state
            self.state = (S.astype(jnp.bfloat16).astype(jnp.float32),
                          z.astype(jnp.bfloat16).astype(jnp.float32), *rest)
            return out

    prompts = prompts_of(rng, [40])
    eng, _fe, handles = serve(prompts, 12, params, engine=Rounding)
    full = reference_logits(params, prompts[0] + handles[0].tokens)
    worst = max(float(np.abs(logits - full[pos]).max())
                for _req, pos, logits in eng.rows)
    assert worst > 10 * TOL, worst


def test_bfloat16_weights_stay_near_the_reference(rng):
    """The served precision: bf16 weights and activations over the float32
    state, against the float32 reference on the same bf16-valued weights."""
    params = make_params(jnp.bfloat16)
    prompts = prompts_of(rng, [44])
    eng, _fe, handles = serve(prompts, 8, params)
    full = reference_logits(params, prompts[0] + handles[0].tokens)
    got = np.stack([logits for _r, _p, logits in eng.rows])
    want = np.stack([full[pos] for _r, pos, _l in eng.rows])
    assert np.abs(got - want).max() < 0.1 * want.std()


# ---- the slot's life ---------------------------------------------------------------------
def step_args(eng, tokens, q_lens, kv_lens, slots):
    return eng.ragged_step(np.asarray(tokens, np.int32),
                           np.asarray(q_lens, np.int32),
                           np.asarray(kv_lens, np.int32),
                           np.asarray(slots, np.int32)[:, None])


def test_a_slot_s_next_tenant_starts_from_zero(rng, interpret):
    eng = build(lanes=2)
    a, b = prompts_of(rng, [9, 9])
    first = np.asarray(step_args(eng, a + [0] * 9, [9, 0], [9, 0],
                                 [1, 0]))[:9]
    step_args(eng, b + [0] * 9, [9, 0], [9, 0], [1, 0])     # b takes the slot
    again = np.asarray(step_args(eng, a + [0] * 9, [9, 0], [9, 0],
                                 [1, 0]))[:9]
    np.testing.assert_array_equal(first, again)
    assert eng.state_resets() == 3


def test_a_mismatched_length_flags_the_lane_and_leaves_the_state(rng,
                                                                 interpret):
    """A token replayed (or one skipped) is a fault the step reports through
    its NaN screen, never a silently doubled update; the other lane of the
    step is served."""
    eng = build(lanes=2)
    a, b = prompts_of(rng, [6, 5])
    step_args(eng, a + b + [0] * 7, [6, 5], [6, 5], [1, 2])
    before = jax.device_get(eng.state)
    # lane 0 replays its last token (kv_len 6 again); lane 1 decodes on
    logits = np.asarray(step_args(eng, [a[-1], 7] + [0] * 16, [1, 1], [6, 6],
                                  [1, 2]))
    assert np.isnan(logits[0]).all() and np.isfinite(logits[1]).all()
    after = jax.device_get(eng.state)
    np.testing.assert_array_equal(after[0][:, 1], before[0][:, 1])
    np.testing.assert_array_equal(after[1][:, 1], before[1][:, 1])
    assert list(after[2][1:3]) == [6, 6]
    assert np.abs(after[0][:, 2] - before[0][:, 2]).max() > 0
    # the sampled step's own flag row says so too
    sampled = eng.sampled_step(
        np.asarray([a[-1], 9] + [0] * 16, np.int32),
        np.asarray([[1, 6, 0, 0, 0, 0], [1, 7, 1, 0, 0, 0]], np.int32),
        np.asarray([[1], [2]], np.int32), np.zeros((2,), np.float32))
    assert list(np.asarray(sampled)[1]) == [0, 1]


def test_preemption_and_resume_give_the_same_tokens(rng):
    """A preempted request gives its slot back and re-prefills from position
    0 with the tokens it had: it ends with the tokens an undisturbed one
    gets."""
    prompts = prompts_of(rng, [20, 11])
    _eng, _fe, calm = serve(prompts, 10, engine=BrumbyInferenceEngine)
    eng = build()
    fe = ServingFrontend(eng, prefill_chunk_tokens=CHUNK)
    handles = [fe.submit(p, max_new_tokens=10) for p in prompts]
    for _ in range(5):
        fe.step()
    sched = fe.scheduler
    sched.settle()
    assert sched._preempt_one(exclude=None)
    assert eng.manager.free_blocks == LANES - 1            # its slot is back
    fe.run_until_idle()
    assert [h.tokens for h in handles] == [h.tokens for h in calm]
    assert sum(h._req.num_preemptions for h in handles) == 1
    eng.manager.check_consistency()


@pytest.mark.parametrize("site", ["serve.decode", "serve.sample"])
def test_a_forced_fault_restarts_the_lane(rng, site):
    """A failed round cannot be trimmed out of a state: its lanes restart
    from their tokens (counted), and end with the tokens of a run without
    the fault."""
    prompts = prompts_of(rng, [18, 5])
    _eng, _fe, calm = serve(prompts, 8, engine=BrumbyInferenceEngine)
    restarts = monitor.get("serving.state.restarts") or 0
    faulted = monitor.get("serving.step_faults") or 0
    eng = build()
    fe = ServingFrontend(eng, prefill_chunk_tokens=CHUNK)
    handles = [fe.submit(p, max_new_tokens=8) for p in prompts]
    for _ in range(4):
        fe.step()
    faults.clear()
    faults.inject(site, times=1)
    try:
        fe.run_until_idle()
    finally:
        faults.clear()
    assert [h.status for h in handles] == [RequestStatus.FINISHED] * 2
    assert [h.tokens for h in handles] == [h.tokens for h in calm]
    assert monitor.get("serving.state.restarts") >= restarts + 2
    assert monitor.get("serving.step_faults") == faulted + 1
    eng.manager.check_consistency()
    assert fe.scheduler.kv_leaked_blocks() == 0


def test_the_round_in_flight_equals_generate(rng):
    """The scheduler's rounds, each launched over the unfetched one before
    it, against `generate`'s host loop over the same engine class: token
    for token."""
    prompt = prompts_of(rng, [12])[0]
    overlapped = monitor.get("serving.step.overlapped") or 0
    _eng, _fe, (h,) = serve([prompt], 14, engine=BrumbyInferenceEngine)
    assert (monitor.get("serving.step.overlapped") or 0) > overlapped
    out = build().generate(np.asarray([prompt, prompt]), max_new_tokens=14)
    assert out.shape == (2, 26)
    assert out[0].tolist() == out[1].tolist() == prompt + h.tokens


def test_one_step_whatever_the_batch(rng):
    before = monitor.get("serving.ragged_retraces") or 0
    prompts = prompts_of(rng, [3, 40, 17, 1, 22, 9])
    _eng, _fe, got = serve(prompts, 6, engine=BrumbyInferenceEngine)
    assert monitor.get("serving.ragged_retraces") == before + 1
    # and the round (the head over the sampled rows) serves the tokens that
    # the all-rows program's logits sample to on the host (`Recording`)
    _eng, _fe, want = serve(prompts, 6)
    assert [h.tokens for h in got] == [h.tokens for h in want]
    assert monitor.get("serving.ragged_retraces") == before + 1


def test_the_step_keeps_its_state_in_place():
    """The compiled step aliases the whole donated state to its outputs.
    (That no second copy of it lies among the temporaries is shown where
    the kernels are in the program, compiled for the chip:
    tests/test_pallas_kernels.py.)"""
    from paddle_tpu.ops import sampling

    eng = build()
    args = sampling.step_args(np.zeros((LANES + CHUNK,), np.int32),
                              np.zeros((LANES,), np.int32),
                              np.zeros((LANES,), np.int32),
                              np.zeros((LANES, 1), np.int32))
    mem = eng._ragged.lower(eng.params, eng.state, *args).compile() \
        .memory_analysis()
    state_bytes = sum(x.nbytes for x in eng.state)
    assert mem.alias_size_in_bytes >= state_bytes


def test_gauges_say_what_a_sequence_holds(rng):
    eng, fe, _ = serve(prompts_of(rng, [5]), 2, engine=BrumbyInferenceEngine)
    per_seq = 2 * 2 * 9 * 16 * (16 + 1) * 4           # L x KV x O x d x (d+1)
    assert eng.state_bytes_per_seq() == per_seq
    assert monitor.get("serving.state.bytes_per_seq") == per_seq
    assert monitor.get("serving.kv_bytes_per_token") == 0
    assert monitor.get("serving.state.slots_in_use") == 0
    assert eng.manager.fragmentation()["bytes_per_block"] == per_seq
    assert eng.state[0].shape == (2, LANES + 1, 2, 9, 16, 16)


def test_the_longest_sequence_is_the_position_table_s(rng):
    eng = build(lanes=2, context_tokens=32)
    fe = ServingFrontend(eng, prefill_chunk_tokens=CHUNK)
    long = fe.submit(prompts_of(rng, [32])[0], max_new_tokens=4)
    assert long.status is RequestStatus.REJECTED
    fits = fe.submit(prompts_of(rng, [28])[0], max_new_tokens=9)
    fe.run_until_idle()
    assert fits.status is RequestStatus.FINISHED
    assert fits._req.finish_reason == "length_cap" and len(fits.tokens) == 5
    with pytest.raises(ValueError, match="positions of the model"):
        build(context_tokens=512)


# ---- what the engine refuses, by name ------------------------------------------------
def _refusals():
    from paddle_tpu.serving.lora import AdapterError, attach_adapters
    from paddle_tpu.serving.quant import quantize_engine
    from paddle_tpu.serving.spec import NGramProposer, SpecDecodeConfig
    from paddle_tpu.serving.tp import ShardingConfigError, shard_engine

    return {
        "quantize_engine": (TypeError, lambda e: quantize_engine(e, 8)),
        "shard_engine": (ShardingConfigError, lambda e: shard_engine(e, tp=2)),
        "attach_adapters": (AdapterError, attach_adapters),
        "kv_migrate.extract": (kv_migrate.KVMigrationError,
                               lambda e: e.extract_kv_blocks(0)),
        "kv_migrate.inject": (kv_migrate.KVMigrationError,
                              lambda e: e.inject_kv_blocks(0, None)),
        "prefix_cache": (ValueError,
                         lambda e: ServingFrontend(e, prefix_cache=True)),
        "speculation": (ValueError, lambda e: ServingFrontend(
            e, spec=SpecDecodeConfig(NGramProposer(), 2))),
        "verify_step": (NotImplementedError,
                        lambda e: e.verify_step(None, None, None)),
        "trim": (StateNotTrimmable, lambda e: (
            e.manager.allocate(0, 5), e.manager.trim(0, 4))),
    }


@pytest.mark.parametrize("transform", [
    "quantize_engine", "shard_engine", "attach_adapters",
    "kv_migrate.extract", "kv_migrate.inject", "prefix_cache", "speculation",
    "verify_step", "trim"])
def test_transforms_refuse_the_family_by_name(transform):
    error, call = _refusals()[transform]
    with pytest.raises(error, match="(?i)brumby|state"):
        call(build(lanes=2))


def test_config_refuses_what_it_does_not_compute():
    with pytest.raises(ValueError, match="attention_bias"):
        bm.BrumbyConfig.from_hf(dict(HF, attention_bias=True))
    with pytest.raises(ValueError, match="rope_scaling"):
        bm.BrumbyConfig.from_hf(dict(HF, rope_scaling={"type": "yarn"}))
    with pytest.raises(ValueError, match="retention_degree"):
        bm.BrumbyConfig.from_hf(dict(HF, retention_degree=4))
    assert CFG.scale == 0.25 and CFG.retention_eps == 1e-6


def test_package_import_loads_none_of_it():
    import subprocess
    import sys

    code = ("import sys, paddle_tpu, paddle_tpu.serving, "
            "paddle_tpu.inference.llama_runner; "
            "print([m for m in sys.modules if 'brumby' in m "
            "or 'power_retention' in m])")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=ROOT,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.stdout.strip() == "[]", out.stdout + out.stderr
