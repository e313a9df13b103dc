"""The lowered text of every engine family's step programs, written out so two
checkouts can be compared: the oracle of a change to the engine shell or to
`ops/sampling.py` that is to leave every compiled program as it is (PR 50).

The same `lowered.as_text()` is the same program and the same executable, so
where the texts of `_ragged` (the round's one program) are equal the
benchmark's numbers are the other checkout's by construction. (Not the same
compile-cache key where a step holds a Mosaic kernel: a kernel's body carries
the lines of its callers' frames, which this tool leaves out, so a line moved
in a runner file is a cache miss on the chip's first run.)

Usage:
    python3 tools/step_text.py <root of a checkout> <out dir> [--cells]

Without `--cells`: each of the five families (Llama, DeepSeek-V3, Cohere2-MoE,
Brumby, GLM-MoE-DSA) as a toy engine on the CPU (the `_ref` kernels' path),
built by its constructor. With `--cells`: each family at its benchmark
configuration's own shapes (`benchmark/configs/*.json`: widths, depth, pools,
lanes + chunk), lowered for a described v5e with the kernels taken as on a
TPU (the packed kernels' path). No array of that size is made: the
constructor runs under `jax.eval_shape` over abstract weights, and the
programs are lowered, not compiled.

Written a family and program: `<family>.<_ragged|_logits|_verify>.txt`, the
text, and `.scopes.txt`, the named-scope path of every op in program order
(the debug text less files and lines). Then `diff -r` the two directories.
Run it once a checkout, each in a process of its own (`JAX_PLATFORMS=cpu`).
"""
from __future__ import annotations

import json
import os
import re
import sys
import types

import numpy as np

TOYS = {
    "deepseek_v3": dict(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=16, num_hidden_layers=2, num_attention_heads=2,
        kv_lora_rank=16, q_lora_rank=None, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, n_routed_experts=4,
        n_shared_experts=1, num_experts_per_tok=2, first_k_dense_replace=1,
        routed_scaling_factor=2.0, norm_topk_prob=True, rms_norm_eps=1e-6,
        rope_theta=10000.0, rope_interleave=True, rope_scaling=None,
        max_position_embeddings=64, n_group=1, topk_group=1,
        scoring_func="sigmoid"),
    "cohere2_moe": dict(
        vocab_size=256, hidden_size=64, intermediate_size=32,
        num_hidden_layers=4, num_attention_heads=8, num_key_value_heads=2,
        head_dim=16, num_experts=8, num_experts_per_tok=2,
        num_shared_experts=2, norm_topk_prob=True, layer_norm_eps=1e-5,
        rope_theta=50000, sliding_window=24, logit_scale=1,
        layer_types=["sliding_attention"] * 3 + ["full_attention"],
        max_position_embeddings=512, first_k_dense_replace=0,
        expert_selection_fn="sigmoid", use_parallel_block=True,
        shared_expert_combination_strategy="average",
        position_embedding_type="rope_gptj", rotary_pct=1,
        tie_word_embeddings=True, use_qk_norm=False, attention_bias=False),
    "brumby": dict(
        vocab_size=256, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, rms_norm_eps=1e-6, rope_theta=1000000,
        max_position_embeddings=256, attention_bias=False, hidden_act="silu",
        rope_scaling=None, tie_word_embeddings=False, model_type="brumby",
        use_sliding_window=False, sliding_window=None),
    "glm_moe_dsa": dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_hidden_layers=5, num_attention_heads=4,
        kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=24, n_routed_experts=8,
        n_shared_experts=1, num_experts_per_tok=2, first_k_dense_replace=1,
        routed_scaling_factor=2.5, norm_topk_prob=True, rms_norm_eps=1e-5,
        rope_parameters={"rope_theta": 10000.0, "rope_type": "default"},
        rope_interleave=True, n_group=1, topk_group=1, scoring_func="sigmoid",
        index_n_heads=4, index_head_dim=16, index_topk=16,
        indexer_rope_interleave=True,
        indexer_types=["full", "shared", "shared", "shared", "full"]),
}
TOY_TOKENS = 4 + 8
CELLS = {"llama": "mistral-7b-v0.3-serve",
         "deepseek_v3": "kanana-2-30b-a3b-serve",
         "cohere2_moe": "command-a-plus-05-2026-serve",
         "brumby": "brumby-14b-base-serve", "glm_moe_dsa": "glm-5.2-serve"}


def _toy(family):
    """A toy engine of `family`, by its constructor: 4 lanes (and an 8-token
    chunk: `TOY_TOKENS` slots a step)."""
    import jax.numpy as jnp

    geom = dict(max_batch_size=4, num_blocks=33, block_size=8,
                max_blocks_per_seq=8)
    if family == "llama":
        from paddle_tpu.inference import LlamaInferenceEngine
        from paddle_tpu.models import llama_tiny

        model = llama_tiny(vocab=64, layers=2, hidden=32, heads=2, seq=64)
        model.eval()
        return LlamaInferenceEngine(model, **geom)
    hf = TOYS[family]
    if family == "deepseek_v3":
        from paddle_tpu.inference.deepseek_v3_runner import \
            DeepseekV3InferenceEngine as Engine
        from paddle_tpu.models import deepseek_v3 as m

        cfg = m.DeepseekV3Config.from_hf(hf)
        model = m.DeepseekV3ForCausalLM(cfg, weights=m.init_params(
            cfg, 3, jnp.float32, 0.08))
    elif family == "cohere2_moe":
        from paddle_tpu.inference.cohere2_moe_runner import \
            Cohere2MoeInferenceEngine as Engine
        from paddle_tpu.models import cohere2_moe as m

        cfg = m.Cohere2MoeConfig.from_hf(hf, held_experts=(2, 4))
        model = m.Cohere2MoeForCausalLM(cfg, weights=m.init_params(
            cfg, 3, jnp.float32, 0.08))
        geom["window_blocks"] = 4 * 6 + 1
    elif family == "brumby":
        from paddle_tpu.inference.brumby_runner import \
            BrumbyInferenceEngine as Engine
        from paddle_tpu.models import brumby as m

        cfg = m.BrumbyConfig.from_hf(hf)
        model = m.BrumbyForCausalLM(cfg, weights=m.init_params(
            cfg, 5, jnp.float32, 0.08))
        geom = dict(max_batch_size=4)
    else:
        from paddle_tpu.inference.glm_moe_dsa_runner import \
            GlmMoeDsaInferenceEngine as Engine
        from paddle_tpu.models import glm_moe_dsa as m

        cfg = m.GlmMoeDsaConfig.from_hf(hf)
        model = m.GlmMoeDsaForCausalLM(cfg, weights=m.init_params(
            cfg, 3, jnp.float32, 0.08))
    return Engine(model, **geom)


def _cell(family, root):
    """`(engine, tokens a step)`: `family`'s engine at its benchmark
    configuration's shapes, by its constructor under `jax.eval_shape` over
    abstract weights (what it holds are tracers: shapes and dtypes)."""
    import jax
    import jax.numpy as jnp

    with open(os.path.join(root, "benchmark", "configs",
                           CELLS[family] + ".json")) as f:
        hf = json.load(f)
    dep = hf["deployment"]
    lanes = dep["lanes"]

    def abstract(shapes):
        return {k: jax.ShapeDtypeStruct(
            tuple(s), jnp.float32 if kind == "bias" else jnp.bfloat16)
            for k, (s, kind) in shapes.items()}

    if family == "brumby":
        from paddle_tpu.inference.brumby_runner import \
            BrumbyInferenceEngine as Engine
        from paddle_tpu.models import brumby as m

        cfg = m.BrumbyConfig.from_hf(hf)
        model = m.BrumbyForCausalLM(cfg, weights=abstract(m.param_shapes(cfg)))
        geom = dict(max_batch_size=lanes, slots=dep["state_slots"] - 1,
                    context_tokens=dep["context_tokens"])
    else:
        per_seq = dep["context_tokens"] // dep["block_size"]
        geom = dict(max_batch_size=lanes, num_blocks=lanes * per_seq + 1,
                    block_size=dep["block_size"], max_blocks_per_seq=per_seq)
    if family == "llama":
        from paddle_tpu.inference import llama_runner as lr

        Engine = lr.LlamaInferenceEngine
        L, h, inter = (hf["num_hidden_layers"], hf["hidden_size"],
                       hf["intermediate_size"])
        nh, kvh = hf["num_attention_heads"], hf["num_key_value_heads"]
        d = hf.get("head_dim") or h // nh
        cfg = types.SimpleNamespace(
            num_hidden_layers=L, hidden_size=h, intermediate_size=inter,
            num_attention_heads=nh, num_key_value_heads=kvh, head_dim=d,
            rms_norm_eps=hf["rms_norm_eps"], tie_word_embeddings=False)
        model = types.SimpleNamespace(config=cfg)
        bf, f32 = jnp.bfloat16, jnp.float32
        stacked = {k: jax.ShapeDtypeStruct(s, t) for k, (s, t) in {
            "ln1": ((L, h), bf), "ln2": ((L, h), bf),
            "qkv_w": ((L, h, (nh + 2 * kvh) * d), bf),
            "o_w": ((L, nh * d, h), bf),
            "gate_up_w": ((L, h, 2 * inter), bf),
            "down_w": ((L, inter, h), bf),
            "embed": ((hf["vocab_size"], h), bf), "final_norm": ((h,), bf),
            "lm_head": ((h, hf["vocab_size"]), bf),
            "rope_cos": ((dep["context_tokens"], d // 2), f32),
            "rope_sin": ((dep["context_tokens"], d // 2), f32)}.items()}
        # the stacking is the only part of the build that reads the weights
        lr._stack_llama_params = lambda model, dtype: stacked
        geom["dtype"] = "bfloat16"
    elif family == "deepseek_v3":
        from paddle_tpu.inference.deepseek_v3_runner import \
            DeepseekV3InferenceEngine as Engine
        from paddle_tpu.models import deepseek_v3 as m

        cfg = m.DeepseekV3Config.from_hf(hf)
        model = m.DeepseekV3ForCausalLM(
            cfg, weights=abstract(m.param_shapes(cfg)))
    elif family == "cohere2_moe":
        from paddle_tpu.inference.cohere2_moe_runner import \
            Cohere2MoeInferenceEngine as Engine
        from paddle_tpu.models import cohere2_moe as m

        cfg = m.Cohere2MoeConfig.from_hf(hf)
        model = m.Cohere2MoeForCausalLM(
            cfg, weights=abstract(m.param_shapes(cfg)))
        geom.update(num_blocks=dep["full_blocks"],
                    window_blocks=dep["window_blocks"])
    elif family == "glm_moe_dsa":
        from paddle_tpu.inference.glm_moe_dsa_runner import \
            GlmMoeDsaInferenceEngine as Engine
        from paddle_tpu.models import glm_moe_dsa as m

        cfg = m.GlmMoeDsaConfig.from_hf(hf)
        model = m.GlmMoeDsaForCausalLM(
            cfg, weights=abstract(m.param_shapes(cfg)))
        geom["num_blocks"] = dep["blocks"]
    made = []
    jax.eval_shape(lambda: made.append(Engine(model, **geom)))
    return made[0], lanes + dep["prefill_chunk_tokens"]


def _scopes(lowered) -> str:
    """The named-scope path of every op, in program order: the debug text's
    `loc("...")` names, which carry no file and no line."""
    text = lowered.as_text(debug_info=True)
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    ops = re.findall(r"^\s*(?:%\S+ = )?\"?([\w.]+)\"?[ (].*loc\((#loc\d+)\)$",
                     text, re.M)
    return "\n".join(f"{op} {names.get(loc, '')}" for op, loc in ops) + "\n"


def main(argv) -> int:
    root, out = os.path.abspath(argv[0]), os.path.abspath(argv[1])
    cells = "--cells" in argv[2:]
    sys.path.insert(0, root)
    os.makedirs(out, exist_ok=True)
    import jax

    from paddle_tpu.ops import sampling
    from paddle_tpu.ops.pallas import _support

    # a Mosaic kernel's serialized body carries the file and line of its
    # callers' frames (the runner's, `ops/sampling.py`'s): with them in, a
    # line moved in a runner is a difference in every kernel of its step
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    place = lambda a: a
    platforms = None
    if cells:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2",
            chips_per_host_bounds=(2, 2, 1), num_slices=1)
        chip = SingleDeviceSharding(topo.devices[0])
        place = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)
        _support.backend = lambda: "tpu"
        platforms = ("tpu",)

    for family in CELLS:
        engine, t = _cell(family, root) if cells \
            else (_toy(family), TOY_TOKENS)
        b = engine.max_batch_size
        width = engine.manager.table_width
        ints = lambda *shape: np.zeros(shape, np.int32)
        calls = {
            "_ragged": sampling.step_args(ints(t), ints(b), ints(b),
                                          ints(b, width)),
            "_logits": (ints(t), ints(b), ints(b), ints(b, width)),
            "_verify": (ints(b, 3), ints(b), ints(b, width))}
        lead = engine.cost_card_args("decode")[1]
        for name, arrays in calls.items():
            fn = getattr(engine, name, None)
            if fn is None:
                continue
            args = jax.tree.map(place, (*lead, *arrays))
            traced = fn.trace(*args)
            lowered = traced.lower(lowering_platforms=platforms) \
                if platforms else traced.lower()
            stem = os.path.join(out, f"{family}.{name}")
            with open(stem + ".txt", "w") as f:
                f.write(lowered.as_text())
            with open(stem + ".scopes.txt", "w") as f:
                f.write(_scopes(lowered))
            print(f"{family}.{name}: {len(lowered.as_text())} bytes",
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
