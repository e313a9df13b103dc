"""The DeepSeek-V3 architecture (`models/deepseek_v3.py`) and its serving
engine (`inference/deepseek_v3_runner.py`) at a small size on the CPU, held
against the benchmark's plain reference (`benchmark/reference/
deepseek_v3_arch.py`, loaded by path: it imports nothing of the program).

Float32 unless said. Logit tolerances: float32 against float32 `highest`
differ only in the order of sums (readings 1e-6 on logits of spread 0.3, so
1e-4 is a hundred times the noise and a tenth of what a wrong mask or a
dropped token moves); bfloat16 and the int8 grid are measured against the
reference as the benchmark measures them, in units of a row's logit std.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.framework import flags
from paddle_tpu.inference import kv_migrate
from paddle_tpu.inference.deepseek_v3_runner import DeepseekV3InferenceEngine
from paddle_tpu.models import deepseek_v3 as dsv3
from paddle_tpu.ops.pallas import grouped_matmul as gm
from paddle_tpu.ops.pallas import paged_attention_mla as pm
from paddle_tpu.ops.pallas.paged_attention import ragged_metadata
from paddle_tpu.serving import RequestStatus, ServingFrontend
from test_sampled_step import all_rows_round

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    path = os.path.join(ROOT, "benchmark", "reference", "deepseek_v3_arch.py")
    spec = importlib.util.spec_from_file_location("ref_deepseek_v3", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()

HF = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
          moe_intermediate_size=32, num_hidden_layers=4, num_attention_heads=4,
          kv_lora_rank=32, q_lora_rank=None, qk_nope_head_dim=16,
          qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8,
          n_shared_experts=2, num_experts_per_tok=2, first_k_dense_replace=1,
          routed_scaling_factor=2.448, norm_topk_prob=True, rms_norm_eps=1e-6,
          rope_theta=10000.0, rope_interleave=True, rope_scaling=None,
          max_position_embeddings=256, n_group=1, topk_group=1,
          scoring_func="sigmoid")
CFG = dsv3.DeepseekV3Config.from_hf(HF)


def make_params(dtype=jnp.float32, seed=3):
    """Weights large enough that routing and attention are not flat: std
    0.08, drawn in float32 and rounded to `dtype`."""
    return {k: v.astype(dtype)
            for k, v in dsv3.init_params(CFG, seed, jnp.float32, 0.08).items()}


class Recording(DeepseekV3InferenceEngine):
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


def serve(params, prompts, new_tokens, num_blocks=4 * 8 + 1, engine=Recording):
    model = dsv3.DeepseekV3ForCausalLM(CFG, weights=params)
    eng = engine(model, max_batch_size=4, num_blocks=num_blocks,
                 block_size=16, max_blocks_per_seq=8)
    fe = ServingFrontend(eng, prefill_chunk_tokens=16)
    if isinstance(eng, Recording):
        eng.slots_of = lambda: fe.scheduler.slots
    handles = [fe.submit(p, max_new_tokens=new_tokens) for p in prompts]
    fe.run_until_idle()
    assert all(h.status is RequestStatus.FINISHED for h in handles)
    return eng, handles


def prompts_of(rng, lengths):
    return [rng.integers(1, CFG.vocab_size, n).tolist() for n in lengths]


def row_gap(got, want):
    """Median over rows of the rms difference in units of the row's std.
    The median, because a top-k choice that flips at a near-tie swaps an
    expert and moves a whole row (and its successors) by far more than the
    arithmetic does: rows like that carry the mean (0.029-0.034 in bf16)."""
    return float(np.median(np.sqrt(np.mean(np.square(got - want), -1))
                           / want.std(-1)))


def served_against_reference(dtype, rng, with_grid=False):
    """Requests through `ServingFrontend` (chunked prefill, then decode
    through the cache): every packed row's logits, the reference's logits at
    the same request and position, and the reference's on the int8 grid."""
    params = make_params(dtype)
    prompts = prompts_of(rng, (5, 37, 20, 50, 9))      # chunks of 16: 37, 50
    eng, handles = serve(params, prompts, 10)
    f32 = {k: v.astype(jnp.float32) for k, v in params.items()}
    # one compiled reference for every request: padded to one length (a
    # later token never reaches an earlier position)
    ids = np.zeros((len(prompts), 64), np.int32)
    for r, (p, h) in enumerate(zip(prompts, handles)):
        ids[r, :len(p) + len(h.tokens)] = p + h.tokens
    forward = jax.jit(jax.vmap(lambda i, q: ref.forward(f32, i, HF, q),
                               in_axes=(0, None)), static_argnums=1)
    full = dict(zip((h.request_id for h in handles),
                    np.asarray(forward(ids, None))))
    got = np.stack([r[2] for r in eng.rows])
    want = np.stack([full[r[0]][r[1]] for r in eng.rows])
    grid = None
    if with_grid:
        low = dict(zip(full, np.asarray(forward(ids, "int8"))))
        grid = np.stack([low[r[0]][r[1]] for r in eng.rows])
    assert len(eng.rows) == sum(len(p) for p in prompts) + 5 * 9
    return got, want, grid


def test_served_logits_match_reference(rng):
    got, want, _ = served_against_reference(jnp.float32, rng)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)


def test_bf16_passes_where_the_int8_grid_fails(rng):
    """bf16 weights, cache and arithmetic against the float32 reference on
    the same bf16-valued weights: readings 0.010-0.016 of a row's std over
    three seeds; the reference on an int8 grid reads 0.037-0.040. The limit
    lies between."""
    got, want, grid = served_against_reference(jnp.bfloat16, rng, True)
    limit = 0.025
    assert row_gap(got, want) < limit < row_gap(grid, want)


def test_absorbed_mla_is_the_expanded_form(rng):
    """The program's attention sub-block (absorbed: scores and values over
    the latent rows) against the reference's (expanded: `kv_b_proj` over
    every token, keys and values by head)."""
    p = dsv3.layer_params(make_params(), 1)
    x = jnp.asarray(rng.normal(size=(48, CFG.hidden_size)), jnp.float32)
    cos, sin = dsv3.rope_tables(CFG, 48)
    got = dsv3.mla_output(dsv3.dense_causal_attend(CFG)(
        *dsv3.mla_query(x, p, CFG, cos, sin)), p, CFG, x.dtype)
    want = ref.attention(x, p, HF, *ref.rope_tables(HF, 48))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


# ---- the kernel ---------------------------------------------------------------
L, NB, BS, DK, DV, H, B, W = 3, 40, 8, 40, 32, 4, 4, 6

KERNEL_CASES = {
    "decode only": ([1, 1, 1, 1], [5, 17, 48, 9]),
    "chunk and decode lanes": ([7, 1, 1, 9], [7, 16, 30, 41]),
    "guard lanes": ([1, 0, 13, 0], [5, 0, 33, 0]),
    "a context crossing a page edge": ([1, 2, 1, 5], [8, 9, 17, 12]),
}


@pytest.fixture
def interpret():
    flags.set_flags({"pallas_interpret": True})
    yield
    flags.set_flags({"pallas_interpret": False})


def _kernel_inputs(rng, q_lens, kv_lens, tokens=20):
    pool = jnp.asarray(rng.normal(size=(L, NB, BS, DK)), jnp.float32)
    tables = jnp.asarray(rng.permutation(NB)[:B * W].reshape(B, W), jnp.int32)
    q_lens, kv_lens = (jnp.asarray(a, jnp.int32) for a in (q_lens, kv_lens))
    lane, pos = ragged_metadata(q_lens, kv_lens, tokens)
    q = jnp.asarray(rng.normal(size=(tokens, H, DK)), jnp.float32)
    return q, pool, tables, kv_lens, lane, pos


@pytest.mark.parametrize("case", sorted(KERNEL_CASES) + ["a COW-copied block"])
def test_kernel_against_its_ref(case, rng, interpret):
    q_lens, kv_lens = KERNEL_CASES.get(case, KERNEL_CASES["decode only"])
    q, pool, tables, kv_lens, lane, pos = _kernel_inputs(rng, q_lens, kv_lens)
    assert pm.mla_supported(q.shape, pool.shape, pool.dtype, W, DV)
    want = pm.paged_attention_mla_ref(q, pool, 1, tables, kv_lens, lane, pos,
                                      DV, 0.3)
    if case == "a COW-copied block":
        # lane 2 reads a copy of its second block, as after a prefix hit
        spare = int(np.setdiff1d(np.arange(NB), np.asarray(tables))[0])
        pool = pool.at[:, spare].set(pool[:, tables[2, 1]])
        tables = tables.at[2, 1].set(spare)
    got = pm.paged_attention_mla(q, pool, 1, tables, kv_lens, lane, pos, DV,
                                 0.3)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
    live = int(np.sum(q_lens))
    assert not np.asarray(got[live:]).any(), "guard rows are exact zeros"


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_the_buffer_taking_call_answers_the_live_rows(case, rng, interpret,
                                                      monkeypatch):
    """ISSUE 49: `mla_prepare`, placed in a blank (here full of NaN),
    through `paged_attention_mla_packed`: the kernel's own buffer, equal to
    the public function on the live rows; a guard row and the spare chunk
    hold whatever (the interpreter's unwritten rows read NaN). The public
    function, made of the same two, still gives exact zeros there."""
    from test_live_prefix import poison

    from paddle_tpu.ops.pallas import _support

    q_lens, kv_lens = KERNEL_CASES[case]
    q, pool, tables, kv_lens, lane, pos = _kernel_inputs(rng, q_lens, kv_lens)
    want = pm.paged_attention_mla(q, pool, 1, tables, kv_lens, lane, pos, DV,
                                  0.3)
    monkeypatch.setattr(_support, "blank", poison)
    t = q.shape[0]
    buf = _support.place(pm.mla_prepare(q, pool), t, t)
    assert buf.shape == (t + 4, H, DK) and np.isnan(buf[t:]).all()
    got = pm.paged_attention_mla_packed(buf, pool, 1, tables, kv_lens, lane,
                                        pos, DV, 0.3)
    live = int(np.sum(q_lens))
    assert got.shape == (t + 4, H, DV)
    np.testing.assert_array_equal(got[:live], want[:live])
    again = pm.paged_attention_mla(q, pool, 1, tables, kv_lens, lane, pos, DV,
                                   0.3)
    np.testing.assert_array_equal(again, want)
    with pytest.raises(ValueError, match="placed"):
        pm.paged_attention_mla_packed(buf[:t], pool, 1, tables, kv_lens, lane,
                                      pos, DV, 0.3)


def test_kernel_lowers_for_tpu_at_kanana_width(monkeypatch):
    """Pallas' TPU block-shape checks at the cell's shape (no libtpu)."""
    from paddle_tpu.ops.pallas import _support

    monkeypatch.setattr(_support, "backend", lambda: "tpu")
    s = jax.ShapeDtypeStruct
    text = jax.jit(
        lambda q, pool, tb, kv, lane, pos: pm.paged_attention_mla(
            q, pool, 3, tb, kv, lane, pos, 512, 192 ** -0.5)
    ).trace(s((544, 32, 576), jnp.bfloat16), s((7, 65, 64, 640), jnp.bfloat16),
            s((32, 256), jnp.int32), s((32,), jnp.int32),
            s((544,), jnp.int32), s((544,), jnp.int32)
            ).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text and "paged_attention_mla" in text


GROUPED_CASES = {
    "ragged groups": (48, 64, 32, [3, 0, 10, 1, 0, 0, 7, 5]),
    "a group over several row tiles": (300, 64, 256, [0, 0, 200, 0, 0, 50, 0, 0]),
    "every row in one group": (40, 64, 32, [0, 0, 0, 40, 0, 0, 0, 0]),
}


@pytest.mark.parametrize("case", sorted(GROUPED_CASES))
def test_grouped_matmul_against_ragged_dot(case, rng, interpret):
    m, k, n, sizes = GROUPED_CASES[case]
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(len(sizes), k, n)), jnp.float32)
    sizes = jnp.asarray(sizes, jnp.int32)
    assert gm.supported(w.shape, w.dtype)
    got = jax.jit(gm.grouped_matmul)(x, w, sizes)
    want = jax.lax.ragged_dot(x, w, sizes,
                              precision=jax.lax.Precision.HIGHEST)
    live = int(sizes.sum())
    np.testing.assert_allclose(got[:live], want[:live], atol=1e-5, rtol=0)


@pytest.mark.parametrize("case", sorted(GROUPED_CASES))
def test_grouped_matmul_takes_the_buffer_as_it_is(case, rng, interpret,
                                                  monkeypatch):
    """ISSUE 49: `prepare`, the first rows of it placed in a blank (here
    full of NaN: what a decode round leaves past its lanes' assignments),
    through `grouped_matmul_packed`: whole row tiles in, whole row tiles
    out, the groups' rows the public function's; and a buffer that already
    is whole tiles passes through the public function untouched."""
    from test_live_prefix import poison

    from paddle_tpu.ops.pallas import _support

    m, k, n, sizes = GROUPED_CASES[case]
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(len(sizes), k, n)), jnp.float32)
    live = int(np.sum(sizes))
    sizes = jnp.asarray(sizes, jnp.int32)
    want = gm.grouped_matmul(x, w, sizes)
    monkeypatch.setattr(_support, "blank", poison)
    # the rows the experts were given, and nothing after them
    head = -(-live // 8) * 8
    buf = _support.place(gm.prepare(x[:head], w, m), head, m)
    assert buf.shape == (gm.buffer_rows(m), k) and buf.shape[0] % 8 == 0
    assert np.isnan(buf[head:]).all()
    got = gm.grouped_matmul_packed(buf, w, sizes)
    assert got.shape == (gm.buffer_rows(m), n)
    np.testing.assert_array_equal(got[:live], want[:live])
    np.testing.assert_array_equal(gm.grouped_matmul(buf, w, sizes), got)
    if gm.buffer_rows(m) != m:
        with pytest.raises(ValueError, match="placed"):
            gm.grouped_matmul_packed(x, w, sizes)


def test_grouped_matmul_lowers_for_tpu_at_kanana_width(monkeypatch):
    from paddle_tpu.ops.pallas import _support

    monkeypatch.setattr(_support, "backend", lambda: "tpu")
    s = jax.ShapeDtypeStruct
    for k, n in ((2048, 768), (768, 2048)):
        text = jax.jit(gm.grouped_matmul).trace(
            s((3264, k), jnp.bfloat16), s((128, k, n), jnp.bfloat16),
            s((128,), jnp.int32)).lower(lowering_platforms=("tpu",)).as_text()
        assert "tpu_custom_call" in text and "moe_grouped_matmul" in text


# ---- the expert layer ------------------------------------------------------------
def _every_expert(x, experts, weights, p):
    out = jnp.zeros_like(x)
    for ex in range(CFG.n_routed_experts):
        w = jnp.sum(jnp.where(experts == ex, weights, 0.0), axis=-1)
        out = out + w[:, None] * dsv3.swiglu(
            x, p["mlp.experts.gate_proj.weight"][ex],
            p["mlp.experts.up_proj.weight"][ex],
            p["mlp.experts.down_proj.weight"][ex])
    return out


def routed_experts(x, experts, weights, live, p, cfg, held=None):
    """The routed experts as a layer runs them: `dispatch`, `expert_ffn`,
    `combine`. `(out [T, H], tokens_per_expert [E])`."""
    (xs, order, keep), (mine, sizes) = dsv3.dispatch(x, experts, live, cfg,
                                                     held)
    return dsv3.combine(dsv3.expert_ffn(xs, mine, p), order, keep, weights,
                        x.dtype), sizes


@pytest.mark.parametrize("case", ["routed", "all to one expert", "guard rows"])
def test_grouped_experts_are_the_every_expert_form(case, rng):
    p = dsv3.layer_params(make_params(), 2)
    t, k = 24, CFG.num_experts_per_tok
    x = jnp.asarray(rng.normal(size=(t, CFG.hidden_size)), jnp.float32)
    experts, weights = dsv3.route(x, p, CFG)
    live = jnp.ones((t,), bool)
    if case == "all to one expert":
        experts = jnp.broadcast_to(jnp.asarray([5, 2], jnp.int32), (t, k))
    elif case == "guard rows":
        live = jnp.arange(t) < 17
    got, sizes = routed_experts(x, experts, weights, live, p, CFG)
    want = jnp.where(live[:, None], _every_expert(x, experts, weights, p), 0.0)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
    assert int(sizes.sum()) == int(live.sum()) * k, "no drop, no guard row"
    if case == "all to one expert":
        assert sizes.tolist() == [0, 0, t, 0, 0, t, 0, 0]
    assert not np.asarray(got[~live]).any()


def test_expert_load_counts_live_tokens(rng):
    eng, handles = serve(make_params(), prompts_of(rng, (5, 37, 20)), 6,
                         engine=DeepseekV3InferenceEngine)
    load = eng.expert_load()
    fed = sum(37 if i == 1 else (5, 0, 20)[i] for i in range(3)) + 3 * 5
    moe_layers = CFG.num_hidden_layers - CFG.first_k_dense_replace
    assert load["tokens"][0].sum() == 0, "the dense layer has no experts"
    assert (load["tokens"][1:].sum(axis=1)
            == fed * CFG.num_experts_per_tok).all()
    assert load["tokens"].sum() == fed * CFG.num_experts_per_tok * moe_layers
    assert (load["touched"][1:] <= load["steps"] * CFG.n_routed_experts).all()
    from paddle_tpu.framework import monitor

    assert monitor.get("serving.moe.expert_tokens") == load["tokens"].sum()
    assert monitor.get("serving.moe.load_max_over_mean") >= 1.0


def test_preemption_and_resume_give_the_same_tokens(rng):
    params = make_params()
    prompts = prompts_of(rng, (9, 9, 9, 9))
    _, roomy = serve(params, prompts, 14)
    _, tight = serve(params, prompts, 14, num_blocks=6)
    assert sum(h._req.num_preemptions for h in tight) > 0
    assert [h.tokens for h in tight] == [h.tokens for h in roomy]


def test_one_step_whatever_the_batch(rng):
    from paddle_tpu.framework import monitor

    before = monitor.get("serving.ragged_retraces") or 0
    serve(make_params(), prompts_of(rng, (5, 37, 20, 50, 9)), 6)
    assert (monitor.get("serving.ragged_retraces") or 0) - before == 1


# ---- what the engine refuses, by name ------------------------------------------------
def _engine():
    model = dsv3.DeepseekV3ForCausalLM(CFG, weights=make_params())
    return DeepseekV3InferenceEngine(model, max_batch_size=2, num_blocks=9,
                                     block_size=16, max_blocks_per_seq=4)


def _refusals():
    from paddle_tpu.serving.lora import AdapterError, attach_adapters
    from paddle_tpu.serving.quant import quantize_engine
    from paddle_tpu.serving.tp import ShardingConfigError, shard_engine

    return {
        "quantize_engine": (TypeError, lambda e: quantize_engine(e, 8)),
        "shard_engine": (ShardingConfigError, lambda e: shard_engine(e, tp=2)),
        "attach_adapters": (AdapterError, attach_adapters),
        "kv_migrate.extract": (kv_migrate.KVMigrationError,
                               lambda e: e.extract_kv_blocks(0)),
        "kv_migrate.inject": (kv_migrate.KVMigrationError,
                              lambda e: e.inject_kv_blocks(0, None)),
    }


@pytest.mark.parametrize("transform", [
    "quantize_engine", "shard_engine", "attach_adapters",
    "kv_migrate.extract", "kv_migrate.inject"])
def test_transforms_refuse_the_family_by_name(transform):
    error, call = _refusals()[transform]
    with pytest.raises(error, match="(?i)deepseek_?v3"):
        call(_engine())


def test_config_refuses_what_it_does_not_compute():
    with pytest.raises(ValueError, match="q_lora_rank"):
        dsv3.DeepseekV3Config.from_hf(dict(HF, q_lora_rank=1536))
    with pytest.raises(ValueError, match="n_group"):
        dsv3.DeepseekV3Config.from_hf(dict(HF, n_group=8))


def test_package_import_loads_none_of_it():
    import subprocess
    import sys

    code = ("import sys, paddle_tpu, paddle_tpu.serving, "
            "paddle_tpu.inference.llama_runner; "
            "print([m for m in sys.modules if 'deepseek' in m "
            "or m.endswith(('paged_attention_mla', 'grouped_matmul', 'megablox'))])")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, check=True, cwd=ROOT,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.stdout.strip().splitlines()[-1] == "[]"
