"""The Cohere2-MoE architecture (`models/cohere2_moe.py`, Command A+) and its
serving engine (`inference/cohere2_moe_runner.py`) at a small size on the
CPU, held against the benchmark's plain reference (`benchmark/reference/
cohere2_moe_arch.py`, loaded by path: it imports nothing of the program).

Float32 unless said. Logit tolerances: float32 against float32 `highest`
differ only in the order of sums (readings 1e-6 on logits of spread 0.5, so
1e-4 is a hundred times the noise and far under what a wrong mask, a key
read from a released block or a dropped expert moves).
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.framework import flags, monitor
from paddle_tpu.inference import kv_migrate
from paddle_tpu.inference.cohere2_moe_runner import Cohere2MoeInferenceEngine
from paddle_tpu.models import cohere2_moe as c2
from paddle_tpu.models import deepseek_v3 as dsv3
from paddle_tpu.serving import RequestStatus, ServingFrontend
from test_deepseek_v3 import routed_experts
from test_sampled_step import all_rows_round

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    path = os.path.join(ROOT, "benchmark", "reference", "cohere2_moe_arch.py")
    spec = importlib.util.spec_from_file_location("ref_cohere2_moe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()

WINDOW = 24
HF = dict(vocab_size=256, hidden_size=64, intermediate_size=32,
          num_hidden_layers=4, num_attention_heads=8, num_key_value_heads=2,
          head_dim=16, num_experts=8, num_experts_per_tok=2,
          num_shared_experts=2, norm_topk_prob=True, layer_norm_eps=1e-5,
          rope_theta=50000, sliding_window=WINDOW, logit_scale=1,
          layer_types=[c2.SLIDING] * 3 + [c2.FULL],
          max_position_embeddings=512, first_k_dense_replace=0,
          expert_selection_fn="sigmoid", use_parallel_block=True,
          shared_expert_combination_strategy="average",
          position_embedding_type="rope_gptj", rotary_pct=1,
          tie_word_embeddings=True, use_qk_norm=False, attention_bias=False)
HELD = (2, 4)                          # experts 2-5 of a router 8 wide


def hf(held=HELD):
    """The reference's configuration dict for a share `held`."""
    if held is None:
        return dict(HF)
    return dict(HF, num_experts=held[1], reduced={"num_experts": {
        "published": HF["num_experts"], "held": list(held)}})


def config(held=HELD):
    return c2.Cohere2MoeConfig.from_hf(HF, held_experts=held)


def make_params(held=HELD, dtype=jnp.float32, seed=3):
    """Weights large enough that routing and attention are not flat."""
    return {k: v.astype(dtype) for k, v in
            c2.init_params(config(held), seed, jnp.float32, 0.08).items()}


@pytest.fixture
def rng():
    return np.random.default_rng(38)


@pytest.fixture(params=[False, True], ids=["xla", "pallas_interpret"])
def interpret(request):
    flags.set_flags({"pallas_interpret": request.param})
    yield request.param
    flags.set_flags({"pallas_interpret": False})


class Recording(Cohere2MoeInferenceEngine):
    """The engine, remembering every packed row's logits with the request
    and position it belongs to."""

    def __init__(self, *a, **k):
        super().__init__(*a, **k)
        self.rows, self.slots_of = [], None

    def sampled_step(self, tokens, lanes, tables, temperature):
        # every packed row's logits come from the all-rows program, on the
        # same step (a cache write is indexed by position: made twice, it
        # is made once); the round itself must sample what they sample to
        want, logits = all_rows_round(self, tokens, lanes, tables,
                                      temperature)
        sampled = super().sampled_step(tokens, lanes, tables, temperature)
        np.testing.assert_array_equal(np.asarray(sampled), want)
        cursor = 0
        for lane, (n, kv) in enumerate(lanes[:, :2]):
            req = self.slots_of()[lane]
            for j in range(int(n)):
                self.rows.append((req.req_id, int(kv) - int(n) + j,
                                  logits[cursor + j]))
            cursor += int(n)
        return sampled


BS, CHUNK, LANES, WIDTH = 8, 16, 4, 16           # block, chunk, lanes, table
# what a lane holds of the window group at most: window + chunk + alignment
PER_LANE = (WINDOW + CHUNK - 2) // BS + 2


def serve(params, prompts, new_tokens, held=HELD, engine=Recording,
          num_blocks=LANES * WIDTH + 1, window_blocks=LANES * PER_LANE + 1):
    model = c2.Cohere2MoeForCausalLM(config(held), weights=params)
    eng = engine(model, max_batch_size=LANES, num_blocks=num_blocks,
                 block_size=BS, max_blocks_per_seq=WIDTH,
                 window_blocks=window_blocks)
    fe = ServingFrontend(eng, prefill_chunk_tokens=CHUNK)
    if isinstance(eng, Recording):
        eng.slots_of = lambda: fe.scheduler.slots
    handles = [fe.submit(p, max_new_tokens=new_tokens) for p in prompts]
    fe.run_until_idle()
    assert all(h.status is RequestStatus.FINISHED for h in handles)
    eng.manager.check_consistency()
    return eng, handles


def prompts_of(rng, lengths):
    return [rng.integers(1, HF["vocab_size"], n).tolist() for n in lengths]


def reference_rows(params, prompts, handles, rows, held=HELD):
    f32 = {k: v.astype(jnp.float32) for k, v in params.items()}
    ids = np.zeros((len(prompts), WIDTH * BS), np.int32)
    for r, (p, h) in enumerate(zip(prompts, handles)):
        ids[r, :len(p) + len(h.tokens)] = p + h.tokens
    forward = jax.jit(jax.vmap(lambda i: ref.forward(f32, i, hf(held))))
    full = dict(zip((h.request_id for h in handles), np.asarray(forward(ids))))
    return np.stack([full[r[0]][r[1]] for r in rows])


# prompts of 0.5x, 1x and 3x the window, one that a chunk straddles the window
# with, and one whose first page group is partly behind the window
LENGTHS = (WINDOW // 2, WINDOW, 3 * WINDOW, WINDOW + 5, 2 * WINDOW + 3)


def test_served_logits_match_reference(rng, interpret):
    """Prefill in chunks, then decode through both pools and across the
    window's edge, equals the reference's full forward pass; the window
    group released blocks on the way and no lane read one."""
    params = make_params()
    prompts = prompts_of(rng, LENGTHS)
    before = monitor.get("serving.kv.window_blocks_released") or 0
    eng, handles = serve(params, prompts, 12)
    got = np.stack([r[2] for r in eng.rows])
    want = reference_rows(params, prompts, handles, eng.rows)
    assert len(eng.rows) == sum(LENGTHS) + len(LENGTHS) * 11
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)
    released = monitor.get("serving.kv.window_blocks_released") - before
    assert released == eng.manager.blocks_released(1) > 0
    assert eng.manager.free_blocks_of(1) == eng.manager.num_blocks_of(1) - 1


def test_model_forward_is_the_reference(rng):
    for held in (HELD, None):
        params = make_params(held)
        ids = rng.integers(1, HF["vocab_size"], 3 * WINDOW)
        got = c2.Cohere2MoeForCausalLM(config(held), weights=params)(ids)
        want = ref.forward(params, jnp.asarray(ids), hf(held))
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)


def test_the_shares_add_up_to_the_uncut_layer(rng):
    """The guide's share test: the routed parts of all the shares, plus what
    every chip computes alike (attention, the shared experts) counted once,
    are the uncut reference layer."""
    whole = make_params(None)
    x = jnp.asarray(rng.normal(size=(40, HF["hidden_size"])), jnp.float32)
    cos, sin = ref.rope_tables(HF, 40)
    live = jnp.ones((40,), bool)
    for i, kind in enumerate(HF["layer_types"]):
        p = c2.layer_params(whole, i)
        want = ref.layer(x, p, HF, kind, cos, sin, moe=ref.every_expert_moe,
                         attend=ref.dense_attention)
        h = c2.layer_norm(x, p["input_layernorm.weight"], 1e-5)
        attn = c2.o_proj(c2.dense_attend(config(None), kind)(
            *c2.qkv(h, p, config(None), kind, cos, sin)), p, config(None),
            h.dtype)
        routed = 0.0
        for first in range(0, HF["num_experts"], 2):     # four chips of two
            cfg = config((first, 2))
            mine = dict(p, **{k: v[first:first + 2] for k, v in p.items()
                              if k.startswith("mlp.experts.")})
            experts, weights = c2.route(h, mine, cfg)
            part, sizes = routed_experts(h, experts, weights, live, mine,
                                         cfg, (first, 2))
            assert int(sizes.sum()) == 40 * HF["num_experts_per_tok"]
            routed = routed + part
        shared = dsv3.swiglu(h, *(p[k] for k in ref.SHARED)) \
            / HF["num_shared_experts"]
        np.testing.assert_allclose(x + attn + routed + shared, want,
                                   atol=2e-5, rtol=0)


def test_every_expert_held_is_the_program_there_was(rng):
    """The routed experts told that they hold every expert trace the
    program they traced before they could be told (Kanana's), and give the
    same bits."""
    from test_deepseek_v3 import CFG, make_params as kanana_params

    p = dsv3.layer_params(kanana_params(), 2)
    x = jnp.asarray(rng.normal(size=(24, CFG.hidden_size)), jnp.float32)
    live = jnp.arange(24) < 20
    experts, weights = dsv3.route(x, p, CFG)
    told = jax.jit(lambda x: routed_experts(
        x, experts, weights, live, p, CFG, (0, CFG.n_routed_experts)))
    plain = jax.jit(lambda x: routed_experts(x, experts, weights, live, p,
                                             CFG))
    for a, b in zip(told(x), plain(x)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    strip = lambda t: "\n".join(                              # noqa: E731
        l.split(" loc(")[0] for l in t.splitlines())
    assert strip(told.lower(x).as_text()) == strip(plain.lower(x).as_text())


def test_expert_load_counts_held_and_absent(rng):
    eng, _ = serve(make_params(), prompts_of(rng, (5, 37, 20)), 6,
                   engine=Cohere2MoeInferenceEngine)
    load = eng.expert_load()
    fed = 5 + 37 + 20 + 3 * 5
    assert (load["tokens"].sum(axis=1) == fed * 2).all()
    held = load["tokens"][:, HELD[0]:HELD[0] + HELD[1]]
    assert 0 < held.sum() < load["tokens"].sum()
    assert (load["touched"] <= load["steps"] * HELD[1]).all()
    assert monitor.get("serving.moe.expert_tokens") == held.sum()
    share = monitor.get("serving.moe.held_assignment_share")
    assert abs(share - held.sum() / load["tokens"].sum()) < 1e-3


def test_preemption_and_resume_give_the_same_tokens(rng):
    params = make_params()
    prompts = prompts_of(rng, (9, 9, 9, 9))
    _, roomy = serve(params, prompts, 30)
    _, tight = serve(params, prompts, 30, num_blocks=12)
    assert sum(h._req.num_preemptions for h in tight) > 0
    assert [h.tokens for h in tight] == [h.tokens for h in roomy]


def test_a_short_window_pool_preempts_and_names_its_group(rng):
    """The window group can run out on its own: the lanes then wait or are
    preempted as for the full group, and the tokens are the same."""
    params = make_params()
    prompts = prompts_of(rng, (30, 30, 30, 30))
    _, roomy = serve(params, prompts, 20)
    eng, tight = serve(params, prompts, 20, window_blocks=2 * PER_LANE + 1)
    assert [h.tokens for h in tight] == [h.tokens for h in roomy]
    mgr = eng.manager
    with pytest.raises(Exception, match="group 'window'"):
        for sid in range(100, 100 + LANES + 1):
            mgr.allocate(sid, PER_LANE * BS)


def test_one_step_whatever_the_batch(rng):
    before = monitor.get("serving.ragged_retraces") or 0
    serve(make_params(), prompts_of(rng, LENGTHS), 6)
    assert (monitor.get("serving.ragged_retraces") or 0) - before == 1


def test_generate_runs_over_both_pools(rng):
    params = make_params()
    model = c2.Cohere2MoeForCausalLM(config(), weights=params)
    eng = Cohere2MoeInferenceEngine(model, max_batch_size=2, num_blocks=33,
                                    block_size=BS, max_blocks_per_seq=WIDTH)
    ids = rng.integers(1, HF["vocab_size"], (2, 2 * WINDOW))
    out = eng.generate(ids, max_new_tokens=8)
    want = ref.forward(params, jnp.asarray(out[0]), hf())
    assert (np.argmax(np.asarray(want), -1)[2 * WINDOW - 1:-1]
            == out[0, 2 * WINDOW:]).all()
    assert eng.manager.num_seqs == 0
    eng.manager.check_consistency()


def test_gauges_tell_the_groups_apart(rng):
    eng, _ = serve(make_params(), prompts_of(rng, (5,)), 2,
                   engine=Cohere2MoeInferenceEngine)
    per_layer = 2 * HF["num_key_value_heads"] * HF["head_dim"] * 4
    assert eng.kv_bytes_per_token() == per_layer            # one full layer
    assert eng.kv_bytes_per_token("window") == 3 * per_layer
    assert monitor.get("serving.kv_bytes_per_token") == per_layer
    assert monitor.get("serving.kv_bytes_per_token.window") == 3 * per_layer
    mgr = eng.manager
    assert mgr.bytes_per_block_of(0) == per_layer * BS
    assert mgr.bytes_per_block_of(1) == 3 * per_layer * BS
    assert mgr.fragmentation(1)["window"] == WINDOW
    assert mgr.fragmentation()["bytes_per_block"] == per_layer * BS


# ---- what the engine refuses, by name ------------------------------------------------
def _engine():
    model = c2.Cohere2MoeForCausalLM(config(), weights=make_params())
    return Cohere2MoeInferenceEngine(model, max_batch_size=2, num_blocks=9,
                                     block_size=BS, max_blocks_per_seq=4)


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
    }


@pytest.mark.parametrize("transform", [
    "quantize_engine", "shard_engine", "attach_adapters",
    "kv_migrate.extract", "kv_migrate.inject", "prefix_cache", "speculation"])
def test_transforms_refuse_the_family_by_name(transform):
    error, call = _refusals()[transform]
    with pytest.raises(error, match="(?i)cohere2_?moe"):
        call(_engine())


def test_config_refuses_what_it_does_not_compute():
    with pytest.raises(ValueError, match="use_qk_norm"):
        c2.Cohere2MoeConfig.from_hf(dict(HF, use_qk_norm=True))
    with pytest.raises(ValueError, match="use_parallel_block"):
        c2.Cohere2MoeConfig.from_hf(dict(HF, use_parallel_block=False))
    with pytest.raises(ValueError, match="held experts"):
        c2.Cohere2MoeConfig.from_hf(HF, held_experts=(6, 4))
    with pytest.raises(ValueError, match="layer_types"):
        c2.Cohere2MoeConfig.from_hf(dict(HF, layer_types=["linear"] * 4))


def test_package_import_loads_none_of_it():
    import subprocess
    import sys

    code = ("import sys, paddle_tpu, paddle_tpu.serving, "
            "paddle_tpu.inference.llama_runner; "
            "print([m for m in sys.modules if 'cohere' in m])")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, check=True, cwd=ROOT,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.stdout.strip().splitlines()[-1] == "[]"
