"""ONE engine shell (`inference/step_engine.StepEngine`): every engine class
is state + a stack + a head, and the `EngineCore` surface, the three
programs, `_run` and `cost_card_args` are the shell's, written once. A case
an engine class: a ninth copy of the surface fails here.
"""
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference import LlamaInferenceEngine, kv_migrate
from paddle_tpu.inference.brumby_runner import BrumbyInferenceEngine
from paddle_tpu.inference.cohere2_moe_runner import Cohere2MoeInferenceEngine
from paddle_tpu.inference.deepseek_v3_runner import DeepseekV3InferenceEngine
from paddle_tpu.inference.glm_moe_dsa_runner import GlmMoeDsaInferenceEngine
from paddle_tpu.inference.step_engine import StepEngine
from paddle_tpu.models import brumby as bm
from paddle_tpu.models import cohere2_moe as c2
from paddle_tpu.models import deepseek_v3 as dsv3
from paddle_tpu.models import glm_moe_dsa as glm
from paddle_tpu.models import llama_tiny
from paddle_tpu.ops import sampling
from paddle_tpu.serving import MLPLMEngine, attach_adapters, shard_engine
from paddle_tpu.serving.lora import LoRAEngine
from paddle_tpu.serving.tp import ShardedEngine
from test_brumby import HF as BRUMBY
from test_cohere2_moe import HF as COHERE2
from test_glm_moe_dsa import HF as GLM
from test_sampled_step import DSV3

LANES, BLOCK, MAXB, CHUNK = 4, 8, 8, 8
GEOM = dict(max_batch_size=LANES, num_blocks=LANES * MAXB + 1,
            block_size=BLOCK, max_blocks_per_seq=MAXB)


def _weights(module, cfg):
    return module.init_params(cfg, 3, jnp.float32, 0.08)


def _mlp():
    return MLPLMEngine(vocab_size=64, hidden=16, **GEOM)


def _llama():
    model = llama_tiny(vocab=64, layers=2, hidden=32, heads=2, seq=64)
    model.eval()
    return LlamaInferenceEngine(model, **GEOM)


def _deepseek_v3():
    cfg = dsv3.DeepseekV3Config.from_hf(DSV3)
    return DeepseekV3InferenceEngine(
        dsv3.DeepseekV3ForCausalLM(cfg, weights=_weights(dsv3, cfg)), **GEOM)


def _cohere2_moe():
    cfg = c2.Cohere2MoeConfig.from_hf(COHERE2, held_experts=(2, 4))
    return Cohere2MoeInferenceEngine(
        c2.Cohere2MoeForCausalLM(cfg, weights=_weights(c2, cfg)), **GEOM)


def _brumby():
    cfg = bm.BrumbyConfig.from_hf(BRUMBY)
    return BrumbyInferenceEngine(
        bm.BrumbyForCausalLM(cfg, weights=_weights(bm, cfg)),
        max_batch_size=LANES)


def _glm_moe_dsa():
    cfg = glm.GlmMoeDsaConfig.from_hf(GLM)
    return GlmMoeDsaInferenceEngine(
        glm.GlmMoeDsaForCausalLM(cfg, weights=_weights(glm, cfg)), **GEOM)


# class -> (how to build one, the state a step replaces, its own `_run`?)
ENGINES = {
    MLPLMEngine: (_mlp, ("pools",), False),
    LlamaInferenceEngine: (_llama, ("pools",), False),
    DeepseekV3InferenceEngine: (_deepseek_v3, ("pool", "counters"), False),
    Cohere2MoeInferenceEngine: (_cohere2_moe, ("pools", "counters"), False),
    BrumbyInferenceEngine: (_brumby, ("state",), False),
    GlmMoeDsaInferenceEngine: (_glm_moe_dsa, ("pools", "counters"), False),
    ShardedEngine: (lambda: shard_engine(_mlp(), tp=2), ("pools",), True),
    LoRAEngine: (lambda: attach_adapters(_mlp(), pool_slots=2), ("pools",),
                 False),
}
SURFACE = ("sampled_step", "ragged_step", "verify_step", "generate",
           "cost_card_args")


@pytest.mark.parametrize("cls", list(ENGINES), ids=lambda c: c.__name__)
def test_the_surface_is_the_shell_s(cls):
    build, donated, own_run = ENGINES[cls]
    assert issubclass(cls, StepEngine) and cls.DONATED == donated
    for name in SURFACE:
        assert inspect.getattr_static(cls, name) is \
            inspect.getattr_static(StepEngine, name), name
    # only the TP engine runs a program its own way (the observability
    # window, the sequential mode's host leg), over the shell's `_run`
    assert ("_run" in vars(cls)) is own_run
    for base in cls.__mro__[1:]:
        assert "_run" not in vars(base) or base is StepEngine
    # migration is `PagedPools`' or the family's one refusal
    migrates = issubclass(cls, kv_migrate.PagedPools)
    assert migrates == (cls.NO_MIGRATION is None)

    engine = build()
    fn, lead = engine.cost_card_args("decode")
    assert fn is engine._ragged and fn is engine.cost_card_args("ragged")[0]
    names = cls.LEADING or ("params", *donated)
    assert len(lead) == len(names)
    assert all(a is getattr(engine, n) for a, n in zip(lead, names))
    # the program donates exactly the declared state, and a step replaces it
    t = LANES + CHUNK
    width = engine.manager.table_width
    arrays = sampling.step_args(
        np.zeros(t, np.int32), np.zeros(LANES, np.int32),
        np.zeros(LANES, np.int32), np.zeros((LANES, width), np.int32))
    if isinstance(engine.last_sampled, jax.Array):      # TP: kept replicated
        arrays = arrays[:-1] + (engine.last_sampled,)
    infos = fn.lower(*lead, *arrays).args_info[0]
    for name, info in zip(names, infos):
        assert all(leaf.donated == (name in donated)
                   for leaf in jax.tree.leaves(info)), name
    before = [getattr(engine, n) for n in donated]
    sampled = engine.sampled_step(*arrays[:4])
    assert sampled is engine.last_sampled and sampled.shape == (2, LANES)
    for name, old in zip(donated, before):
        assert all(a.is_deleted() for a in jax.tree.leaves(old)), name
        assert not any(a.is_deleted()
                       for a in jax.tree.leaves(getattr(engine, name)))
    if cls.NO_VERIFY is None:
        assert engine.cost_card_args("verify")[0] is engine._verify
    else:
        with pytest.raises(NotImplementedError, match=cls.FAMILY):
            engine.verify_step(np.zeros((LANES, 2), np.int32),
                               np.full(LANES, 2, np.int32),
                               np.zeros((LANES, width), np.int32))
    if not migrates:
        with pytest.raises(kv_migrate.KVMigrationError, match=cls.FAMILY):
            engine.extract_kv_blocks(0)
