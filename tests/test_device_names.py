"""Names on the device (docs/OBSERVABILITY.md, "Program spans and device
scopes"): every Pallas kernel is called with a literal `name=`, and the
serving step and the training step put the same `jax.named_scope` regions
around the same parts of the model, so a device trace reads by region.
Nothing here runs on a device: call sites are read as source, steps are
lowered and their text searched.
"""
import ast
import os
import re

import numpy as np
import pytest

import paddle_tpu
from paddle_tpu.framework import flags
from paddle_tpu.ops.pallas import _support

PKG = os.path.dirname(paddle_tpu.__file__)
# the regions both steps share, then what only one of them has
SHARED = {"llama.embed", "llama.layer", "llama.rms_norm", "llama.qkv",
          "llama.rope", "llama.attn", "llama.o_proj", "llama.mlp",
          "llama.head"}
# the serving step ends in its own NaN screen and sampler (`with_tail`)
SERVING = SHARED | {"llama.kv_write", "llama.nan_screen", "llama.feed"}
TRAINING = SHARED | {"llama.loss"}


def _kernel_call_sites():
    """(file, line, the `name=` node or None) of every call of
    `_support.pallas_call` in the package."""
    out = []
    for dirpath, _dirs, files in os.walk(PKG):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            with open(path) as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                if isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "pallas_call" \
                        and isinstance(node.func.value, ast.Name) \
                        and node.func.value.id == "_support":
                    name = next((k.value for k in node.keywords
                                 if k.arg == "name"), None)
                    out.append((os.path.relpath(path, PKG), node.lineno, name))
    return out


SITES = _kernel_call_sites()


def test_pallas_call_requires_a_name():
    with pytest.raises(TypeError, match="name"):
        _support.pallas_call(lambda x_ref, o_ref: None, out_shape=None)


@pytest.mark.parametrize("site", SITES, ids=[f"{f}:{n}" for f, n, _ in SITES])
def test_every_kernel_call_site_names_its_kernel(site):
    _file, _line, name = site
    assert isinstance(name, ast.Constant) and isinstance(name.value, str)
    assert re.fullmatch(r"[a-z][a-z0-9_]*", name.value)


def test_kernel_names_are_distinct_and_cover_the_main_path():
    names = [n.value for _f, _l, n in SITES]
    assert len(names) == len(set(names)) == 22
    assert {"paged_attention_ragged", "kv_write_ragged",
            "paged_attention_mla", "power_retention_update",
            "power_retention_chunk", "dsa_index_scores",
            "mla_sparse_attention",
            "moe_grouped_matmul", "flash_fwd",
            "flash_dq", "flash_dkv",
            "rms_norm", "fused_rope", "quant_matmul_int8",
            "quant_matmul_int4"} <= set(names)


def _regions(text):
    """The `llama.*` components of the scope paths in a lowered module's
    text: `loc("jit(step)/transpose(jvp(llama.layer))/llama.mlp/mul"(#loc7))`
    (inside a scan's body the path starts at the body)."""
    out = set()
    for path in re.findall(r'loc\("([^"]+)"\(#loc\d+\)\)', text):
        for part in path.split("/"):
            part = re.sub(r"^(?:\w+\()+|\)+$", "", part)
            if part.startswith("llama."):
                out.add(part)
    return out


@pytest.fixture(scope="module")
def tiny():
    from paddle_tpu.models import llama_tiny

    return llama_tiny(vocab=64, layers=2, hidden=32, heads=2, seq=64)


@pytest.fixture(scope="module")
def ragged_text(tiny):
    """The tiny ragged serving step, lowered with the kernel in it (the
    Pallas interpreter stands in for Mosaic off the TPU)."""
    from paddle_tpu.inference import LlamaInferenceEngine
    from paddle_tpu.ops.sampling import step_args

    tiny.eval()
    eng = LlamaInferenceEngine(tiny, max_batch_size=4, num_blocks=48,
                               block_size=4, max_blocks_per_seq=8)
    flags.set_flags({"FLAGS_pallas_interpret": True})
    try:
        lowered = eng._ragged.lower(eng.params, eng.pools, *step_args(
            np.zeros((12,), np.int32),
            np.zeros((4,), np.int32), np.zeros((4,), np.int32),
            np.zeros((4, 8), np.int32)))
    finally:
        flags.set_flags({"FLAGS_pallas_interpret": False})
    return lowered.as_text(debug_info=True)


@pytest.fixture(scope="module")
def train_text(tiny):
    import jax

    import bench

    tiny.train()
    step, *state = bench.build_train_step(tiny)
    ids = np.zeros((1, 32), np.int32)
    return jax.jit(step).lower(*state, 1.0, ids, ids).as_text(debug_info=True)


def test_serving_step_holds_its_regions(ragged_text):
    assert _regions(ragged_text) == SERVING
    assert "module @jit__ragged_fn" in ragged_text
    assert re.search(r"llama\.layer/llama\.attn/paged_attention_ragged",
                     ragged_text)
    assert re.search(r"llama\.layer/llama\.kv_write/kv_write_ragged",
                     ragged_text)
    # the tail: the screen and the sampler are regions of THIS module
    assert re.search(r'"jit\(_ragged_fn\)/llama\.nan_screen/', ragged_text)
    assert re.search(r'"jit\(_ragged_fn\)/sampler/', ragged_text)


def test_training_step_holds_its_regions(train_text):
    assert _regions(train_text) == TRAINING
    assert "module @jit_train_step" in train_text
    assert re.search(r'"jit\(train_step\)/adamw/', train_text)
    assert re.search(r"jvp\(llama\.layer\)/llama\.attn/", train_text)
    assert re.search(r"transpose\(jvp\(llama\.layer\)\)/llama\.mlp/",
                     train_text)


def test_serving_and_training_names_are_one_set(ragged_text, train_text):
    assert _regions(ragged_text) & _regions(train_text) == SHARED
    # no region nests deeper than llama.layer/<region>
    for text in (ragged_text, train_text):
        assert not re.search(
            r"llama\.(?!layer)[a-z_]+\)*/(?:[^\"/]+/)*(?:\w+\()*llama\.", text)


def test_sampler_and_nan_screen_are_scoped():
    import jax

    from paddle_tpu.ops import sampling
    from paddle_tpu.serving import MLPLMEngine, ServingFrontend

    z = np.zeros((2,), np.int32)
    text = sampling._jitted().lower(
        np.zeros((2, 1, 8), np.float32), np.zeros((2,), np.float32), z, z,
        z).as_text(debug_info=True)
    assert "module @jit__sample_fn" in text and '"jit(_sample_fn)/sampler' in text
    fe = ServingFrontend(MLPLMEngine(vocab_size=16, hidden=8,
                                     max_batch_size=2, num_blocks=8,
                                     block_size=4, max_blocks_per_seq=4))
    fe.scheduler._finite_rows(jax.numpy.zeros((2, 16)))
    text = fe.scheduler._finite_fn.lower(
        np.zeros((2, 16), np.float32)).as_text(debug_info=True)
    assert "llama.nan_screen" in text and "module @jit_nan_screen" in text
