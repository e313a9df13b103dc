"""The program's one compile record (`observability/compile_trace.py`), fed by
JAX's own events and always on, and the three stamps of a set-up that are not
JAX's (`startup.import_s`, `engine.build_s`, `serving.step.wall_s`).

- a fresh `jax.jit` function is ONE record with its three durations, `miss`
  under a cache directory and `hit` after `jax.clear_caches()`; the `jnp`
  functions it calls make no records of their own; a second shape of one name
  is a retrace; the record is bounded; installing twice records once; a
  compile on another thread is recorded;
- over the three engines at toy widths through `ServingFrontend`: the build is
  stamped, the step program's record is there by the name the benchmark's
  readers use, the step wall grows by every step and by no more than the wall
  around them, and a forced retrace carries the scheduler's cause;
- the train step is one record under the name the `.train` reader uses.
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.framework import monitor
from paddle_tpu.observability import compile_trace as ct
from paddle_tpu.serving import RequestStatus, ServingFrontend

STEP_PROGRAM = "_ragged_fn"      # benchmark/setup_record.py sums these
TRAIN_PROGRAM = "train_step"


@pytest.fixture(autouse=True, scope="module")
def _leave_no_record_behind():
    """The record is the process's: the retraces forced here must not be
    there when a later file of this worker asserts it saw none."""
    yield
    obs.reset()


def _records(name, since=0, until=float("inf")):
    return [r for r in ct.compiles()
            if r.name == name and since < r.seq <= until]


# ---- JAX's events -> one record ---------------------------------------------
@pytest.fixture
def cache_dir(tmp_path):
    """A persistent cache of this test's own that keeps every program."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = {"jax_compilation_cache_dir": str(tmp_path),
            "jax_persistent_cache_min_compile_time_secs": 0,
            "jax_persistent_cache_min_entry_size_bytes": -1}
    old = {k: getattr(jax.config, k) for k in keys}
    for k, v in keys.items():
        jax.config.update(k, v)
    cc.reset_cache()
    yield str(tmp_path)
    for k, v in old.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_one_record_miss_then_hit_from_the_cache(cache_dir):
    def rec_miss_then_hit(x):
        return jnp.tanh(x) * 3.0

    x = jnp.ones((5, 7))
    since = ct.mark()
    before = {k: monitor.get("compile." + k) for k in
              ("programs", "cache_hits", "cache_misses", "backend_s")}
    jax.jit(rec_miss_then_hit)(x).block_until_ready()
    (first,) = _records("rec_miss_then_hit", since)
    assert first.trace_s > 0 and first.lower_s > 0 and first.backend_s > 0
    assert first.cache == "miss" and first.retrieval_s == 0
    assert not first.is_retrace
    assert first.start < first.end <= time.time()
    assert first.end - first.start >= first.wall_s * 0.999
    jax.clear_caches()
    jax.jit(rec_miss_then_hit)(x).block_until_ready()
    _, second = _records("rec_miss_then_hit", since)
    assert second.cache == "hit" and second.retrieval_s > 0
    assert second.backend_s >= second.retrieval_s
    assert second.is_retrace, "the name compiled before in this process"
    moved = {k: monitor.get("compile." + k) - v for k, v in before.items()}
    assert moved["programs"] >= 2
    assert moved["cache_hits"] >= 1 and moved["cache_misses"] >= 1
    assert moved["backend_s"] >= first.backend_s + second.backend_s - 1e-9


def test_no_cache_directory_reads_off():
    def rec_cache_off(x):
        return x - 2.0

    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        jax.jit(rec_cache_off)(jnp.ones(3))
    finally:
        jax.config.update("jax_enable_compilation_cache", old)
        cc.reset_cache()
    (rec,) = _records("rec_cache_off")
    assert rec.cache == "off" and rec.backend_s > 0


def test_inner_jits_belong_to_the_outer_compile():
    def rec_outer(x):
        # each of these is a jitted `jnp` function, traced while the
        # outer one is: its event lies inside the outer trace's span
        return jnp.linalg.norm(x) + jnp.sum(jnp.sort(x)) + jnp.var(x)

    x = jnp.ones((6, 6))
    jax.block_until_ready(x)
    since = ct.mark()
    jax.jit(rec_outer)(x)
    made = [r for r in ct.compiles() if r.seq > since]
    assert [r.name for r in made] == ["rec_outer"], made
    assert made[0].trace_s > 0


def test_a_compile_inside_a_trace_belongs_to_the_outer_one():
    def rec_eager_inside(x):
        with jax.ensure_compile_time_eval():
            k = jnp.cumsum(jnp.arange(5.0))[-1]      # compiles, eagerly
        return x * k

    x = jnp.ones(4)
    since = ct.mark()
    programs = monitor.get("compile.programs")
    jax.jit(rec_eager_inside)(x)
    made = [r for r in ct.compiles() if r.seq > since]
    assert [r.name for r in made] == ["rec_eager_inside"], made
    assert monitor.get("compile.programs") == programs + 1


def test_second_shape_of_one_name_is_a_retrace():
    def rec_two_shapes(x):
        return x + 1.0

    f = jax.jit(rec_two_shapes)
    f(jnp.ones(3))
    f(jnp.ones(3))                     # JAX's own cache: no compile
    f(jnp.ones(4))
    recs = _records("rec_two_shapes")
    assert [r.is_retrace for r in recs] == [False, True]


def test_aot_lower_then_compile_is_one_record():
    def rec_aot(x):
        return x * x

    lowered = jax.jit(rec_aot).lower(jnp.ones(3))
    jax.jit(lambda x: x - 1)(jnp.ones(2))      # another compile in between
    lowered.compile()
    (rec,) = _records("rec_aot")
    assert rec.trace_s > 0 and rec.lower_s > 0 and rec.backend_s > 0


def test_the_record_is_bounded(monkeypatch):
    from collections import deque

    monkeypatch.setattr(ct, "_records", deque(maxlen=4))
    for n in range(1, 8):
        jax.jit(lambda x: x + 1)(jnp.ones(n))
    assert len(ct.compiles()) == 4
    assert ct._MAX_RECORDS == 1024


def test_installed_twice_records_once():
    def rec_installed_twice(x):
        return x / 2.0

    ct.install()
    ct.install()
    jax.jit(rec_installed_twice)(jnp.ones(3))
    assert len(_records("rec_installed_twice")) == 1


def test_a_compile_on_another_thread_is_recorded():
    def rec_other_thread(x):
        return jnp.exp(x)

    def rec_main_thread(x):
        return jnp.log(x)

    started, go = threading.Event(), threading.Event()

    def work():
        started.set()
        go.wait(10)
        jax.jit(rec_other_thread)(jnp.ones(3)).block_until_ready()

    t = threading.Thread(target=work)
    t.start()
    started.wait(10)
    go.set()
    jax.jit(rec_main_thread)(jnp.ones(3)).block_until_ready()
    t.join(60)
    (other,) = _records("rec_other_thread")
    (main,) = _records("rec_main_thread")
    assert other._tid != main._tid
    assert other.backend_s > 0 and main.backend_s > 0


def test_dispatch_attaches_what_only_it_knows():
    import paddle_tpu as paddle
    from paddle_tpu.core import dispatch

    dispatch.register_op("rec_t_attach", lambda x, *, k=1.0: x * k)
    obs.enable()
    try:
        t = paddle.to_tensor(np.ones((3, 5), np.float32))
        dispatch.apply("rec_t_attach", [t], {"k": 2.0})
        dispatch.apply("rec_t_attach", [t], {"k": 3.0})
    finally:
        obs.disable()
    first, second = [r for r in ct.compiles() if r.op == "rec_t_attach"]
    assert first.kind == second.kind == "fwd"
    assert first.cause is None and "static_arg k" in second.cause
    assert second.key[0] == "rec_t_attach" and second.backend_s > 0


def test_import_is_stamped():
    began, ended = ct.stamps()["startup.import"]
    assert 0 < ended - began < 600
    # (another test's `monitor.reset_all()` may have zeroed it since)
    assert monitor.get("startup.import_s") in (
        0, pytest.approx(ended - began))


def test_a_stamp_sets_the_value_and_the_stamps():
    t0 = time.time()
    time.sleep(0.01)
    ct.stamp("rec_test.phase", t0)
    began, ended = ct.stamps()["rec_test.phase"]
    assert began == t0 and ended <= time.time() and ended - began >= 0.01
    assert monitor.get("rec_test.phase_s") == pytest.approx(ended - began)
    ct.stamp("rec_test.phase", t0 - 5.0)      # the newest of a name stands
    assert monitor.get("rec_test.phase_s") >= 5.0
    assert ct.stamps()["rec_test.phase"][0] == t0 - 5.0


# ---- the three engines through ServingFrontend -------------------------------
KINDS = ["llama", "deepseek_v3", "cohere2_moe"]
LANES, BLOCK, MAXB, CHUNK = 4, 4, 16, 8
_SERVED = {}


def _engine(kind):
    geom = dict(max_batch_size=LANES, num_blocks=LANES * MAXB + 1,
                block_size=BLOCK, max_blocks_per_seq=MAXB)
    if kind == "llama":
        from paddle_tpu.inference import LlamaInferenceEngine
        from paddle_tpu.models import llama_tiny

        model = llama_tiny(vocab=64, layers=2, hidden=32, heads=2, seq=64)
        model.eval()
        return LlamaInferenceEngine(model, **geom)
    if kind == "deepseek_v3":
        from test_sampled_step import DSV3

        from paddle_tpu.inference.deepseek_v3_runner import \
            DeepseekV3InferenceEngine
        from paddle_tpu.models import deepseek_v3 as dsv3

        cfg = dsv3.DeepseekV3Config.from_hf(DSV3)
        return DeepseekV3InferenceEngine(dsv3.DeepseekV3ForCausalLM(
            cfg, weights=dsv3.init_params(cfg, 3, jnp.float32, 0.08)), **geom)
    from test_cohere2_moe import config, make_params

    from paddle_tpu.inference.cohere2_moe_runner import \
        Cohere2MoeInferenceEngine
    from paddle_tpu.models import cohere2_moe as c2

    return Cohere2MoeInferenceEngine(
        c2.Cohere2MoeForCausalLM(config(), weights=make_params()), **geom)


def _served(kind):
    """One engine of `kind`, built and served once a module: what the
    build and the first request left in the record and the monitor."""
    if kind not in _SERVED:
        since, t0 = ct.mark(), time.time()
        engine = _engine(kind)
        built = dict(build_s=monitor.get("engine.build_s"),
                     stamp=ct.stamps()["engine.build"], t0=t0, t1=time.time())
        fe = ServingFrontend(engine, prefill_chunk_tokens=CHUNK)
        h = fe.submit(list(range(1, CHUNK + 4)), max_new_tokens=3)
        fe.run_until_idle()
        assert h.status is RequestStatus.FINISHED
        _SERVED[kind] = dict(built, since=since, until=ct.mark(), fe=fe)
    return _SERVED[kind]


@pytest.mark.parametrize("kind", KINDS)
def test_engine_build_is_stamped(kind):
    s = _served(kind)
    began, ended = s["stamp"]
    assert s["t0"] <= began < ended <= s["t1"]
    assert s["build_s"] == pytest.approx(ended - began)


@pytest.mark.parametrize("kind", KINDS)
def test_step_program_record_by_the_readers_name(kind):
    s = _served(kind)
    (rec,) = _records(STEP_PROGRAM, s["since"], s["until"])
    assert rec.trace_s > 0 and rec.lower_s > 0 and rec.backend_s > 0
    assert rec.start > s["stamp"][1], "compiled by the first step, not the build"


@pytest.mark.parametrize("kind", KINDS)
def test_step_wall_grows_by_every_step_and_no_more_than_the_wall(kind):
    fe = _served(kind)["fe"]
    h = fe.submit(list(range(1, 6)), max_new_tokens=4)
    t0, walls = time.perf_counter(), [monitor.get("serving.step.wall_s")]
    while not h.finished:
        fe.step()
        walls.append(monitor.get("serving.step.wall_s"))
    around = time.perf_counter() - t0
    steps = np.diff(walls)
    assert len(steps) >= 4 and (steps > 0).all()
    assert steps.sum() <= around


@pytest.mark.parametrize("kind", KINDS)
def test_step_wall_is_the_rounds_own_whatever_now_the_caller_hands_in(kind):
    """A caller's `now` (a replayed clock, a deadline test's) decides the
    scheduling alone: the wall is stamped by the round itself."""
    fe = _served(kind)["fe"]
    h = fe.submit(list(range(1, 6)), max_new_tokens=3)
    t0, before = time.perf_counter(), monitor.get("serving.step.wall_s")
    steps = 0
    while not h.finished:
        fe.scheduler.step(now=time.perf_counter() - 1000.0)
        steps += 1
    grew = monitor.get("serving.step.wall_s") - before
    assert steps >= 3 and 0 < grew <= time.perf_counter() - t0


@pytest.mark.parametrize("kind", KINDS)
def test_forced_retrace_carries_the_schedulers_cause(kind):
    s = _served(kind)
    obs.enable()
    try:
        # the signature a later retrace is diffed against
        h = s["fe"].submit([1, 2, 3], max_new_tokens=2)
        s["fe"].run_until_idle()
        since = ct.mark()
        fe2 = ServingFrontend(_engine(kind), prefill_chunk_tokens=2 * CHUNK)
        h2 = fe2.submit(list(range(1, 2 * CHUNK + 4)), max_new_tokens=2)
        fe2.run_until_idle()
    finally:
        obs.disable()
    assert h.status is h2.status is RequestStatus.FINISHED
    (rec,) = _records(STEP_PROGRAM, since)
    assert rec.is_retrace and rec.kind == "serving"
    assert rec.op.startswith("serve.") and "shape" in rec.cause, rec
    assert any(c["cause"] == rec.cause for c in ct.retrace_causes())


# ---- the train step -----------------------------------------------------------
def test_train_step_is_one_record_under_the_readers_name():
    import bench
    from paddle_tpu.models import llama_tiny

    model = llama_tiny(vocab=64, layers=1, hidden=32, heads=2, seq=16)
    train_step, *state = bench.build_train_step(model)
    ids = jnp.asarray(np.arange(16, dtype=np.int32).reshape(1, 16) % 64)
    since = ct.mark()
    loss, *state = jax.jit(train_step, donate_argnums=(0, 1, 2))(
        *state, 1.0, ids, ids)
    assert np.isfinite(float(loss))
    (rec,) = _records(TRAIN_PROGRAM, since)
    assert rec.trace_s > 0 and rec.lower_s > 0 and rec.backend_s > 0
