"""`program_trace.py` and the readers built on it, against one small trace
recorded on the v5e (`tests/data/program_spans.xplane.pb`): a two-layer
Llama (hidden 512, 4 x 128 heads, 2 KV heads, vocab 1024, bf16) serving
three requests through `ServingFrontend` under the benchmark's `fe.step`
spans, then two steps of `bench.build_train_step` at 1 x 512 tokens under
`train_step` spans. Spans and scopes in it are the program's own.

Recorded with `python benchmark/tests/test_program_trace.py <out.pb>` on
the chip (the recipe is `record` below; it prints what the numbers here
were worked out from).
"""
import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
DATA = os.path.join(HERE, "data", "program_spans.xplane.pb")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)          # the readers import `program_trace`


def load(rel, name):
    spec = importlib.util.spec_from_file_location(name,
                                                  os.path.join(BENCH, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def record(out_path):
    """The recipe of the recorded trace (needs a TPU)."""
    import glob
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, os.path.dirname(BENCH))
    import bench
    from paddle_tpu.inference.llama_runner import LlamaInferenceEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import ServingFrontend

    def model(seq):
        return LlamaForCausalLM(LlamaConfig(
            vocab_size=1024, hidden_size=512, intermediate_size=1024,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=seq))

    m = model(256)
    m.eval()
    m.bfloat16()
    engine = LlamaInferenceEngine(m, max_batch_size=4, num_blocks=33,
                                  block_size=16, max_blocks_per_seq=8,
                                  dtype="bfloat16")
    fe = ServingFrontend(engine, prefill_chunk_tokens=16)
    fe.submit(list(range(1, 30)), max_new_tokens=3)
    fe.run_until_idle()                           # compiles, outside the trace

    t = model(512)
    t.train()
    t.bfloat16()
    train_step, *state = bench.build_train_step(t)
    step_fn = jax.jit(train_step, donate_argnums=(0, 1, 2))
    ids = jnp.asarray(np.random.default_rng(0).integers(1, 1024, (1, 512)),
                      jnp.int32)
    loss, *state = step_fn(*state, 1.0, ids, ids)
    jax.block_until_ready(loss)

    trace_dir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    span = jax.profiler.TraceAnnotation
    rng = np.random.default_rng(1)
    handles = [fe.submit(rng.integers(1, 1024, n).tolist(), max_new_tokens=k)
               for n, k in ((40, 4), (9, 6), (20, 2))]
    while not all(h.finished for h in handles):
        with span("fe.step"):
            fe.step()
    for i in (2, 3):
        with span("train_step"):
            loss, *state = step_fn(*state, float(i), ids, ids)
            jax.block_until_ready(loss)
    jax.profiler.stop_trace()
    (made,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    keep_planes(made, out_path)
    print("recorded", out_path, os.path.getsize(out_path), "bytes; requests",
          [h.request_id for h in handles])
    describe(out_path)


def keep_planes(src, dst, drop=("/host:metadata",)):
    """Copy an `.xplane.pb` without the planes named in `drop`: the
    `/host:metadata` plane holds every module's HLO proto (1.7 of the
    recording's 2.5 MB) and nothing here reads it. Planes are copied
    whole, byte for byte."""
    import program_trace

    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)

    with open(src, "rb") as f:
        space = memoryview(f.read())
    out = bytearray()
    for no, value in program_trace._fields(space):
        if no == 1 and any(program_trace._text(v) in drop
                           for n, v in program_trace._fields(value)
                           if n == 2):
            continue
        if isinstance(value, int):
            out += varint(no << 3) + varint(value)
        else:
            out += varint(no << 3 | 2) + varint(len(value)) + bytes(value)
    with open(dst, "wb") as f:
        f.write(out)


def describe(path):
    """What the expected values below were worked out from: every program
    span, and device time by scope, printed raw."""
    import program_trace
    import trace_reduce as tr

    pt = program_trace.ProgramTrace(path)
    for s in sorted(pt.spans, key=lambda s: s.start):
        print(f"  span {s.name} {s.start * 1e3:.4f}..{s.end * 1e3:.4f} ms "
              f"{s.ids} children {[c.name for c in s.children]}")
    reduced = tr.reduce(path, ("fe.step", "train_step"))
    print("  busy_s", reduced["busy_s"], "ops", len(reduced["ops"]))
    for name, sec in sorted(reduced["ops"].items(), key=lambda kv: -kv[1]):
        print(f"  op {sec * 1e6:10.2f} us  {tr.label(name)}  "
              f"[{pt.scopes.get(name) or 'none'}]")
    print("  by region", pt.by_region(reduced["ops"]))


if __name__ == "__main__":
    record(sys.argv[1])
    sys.exit(0)


# ---- the tests ---------------------------------------------------------------
import pytest  # noqa: E402

import program_trace  # noqa: E402
import trace_reduce as tr  # noqa: E402

@pytest.fixture(scope="module")
def pt():
    return program_trace.ProgramTrace(DATA)


@pytest.fixture(scope="module")
def rec(pt, monkeypatch_module):
    """A record as `run.py` hands it to the readers, over the recorded
    trace."""
    monkeypatch_module.setattr(program_trace, "newest_xplane", lambda: DATA)
    program_trace._OPEN.clear()
    return {"trace": tr.reduce(DATA, ("fe.step", "train_step")),
            "is_pallas": tr.is_pallas}


@pytest.fixture(scope="module")
def monkeypatch_module():
    mp = pytest.MonkeyPatch()
    yield mp
    mp.undo()


def reader(name):
    return load(f"layer_metrics/{name}.py", "reader_" + name).read


READERS = ["sched_host_ms_per_step", "idle_under_sched_ms_per_step",
           "admit_step_extra_ms", "lane_wait_p50_ms", "ragged_attn_share",
           "ragged_attn_roofline", "kv_write_share", "unscoped_device_share",
           "flash_share", "adamw_share", "head_share"]
MINI = os.path.join(HERE, "data", "mini.xplane.pb")


# ---- pieces that need no program span -----------------------------------------
def test_scope_paths_split_into_components():
    path = ("jit(train_step)/transpose(jvp(llama.layer))/llama.attn/"
            "flash_dq/pallas_call")
    assert program_trace.tokens(path) == [
        "train_step", "llama.layer", "llama.attn", "flash_dq", "pallas_call"]
    assert program_trace.region(path) == "llama.attn"
    assert program_trace.kernel(path) == "flash_dq"
    assert program_trace.region("jit(train_step)/adamw/mul") == "adamw"
    assert program_trace.region("jit(_ragged_fn)/while/body/add") is None
    assert program_trace.kernel("jit(f)/llama.mlp/dot_general") is None
    assert program_trace.region("") is None and program_trace.tokens("") == [""]
    assert program_trace.has("llama.kv_write")(
        "jit(_ragged_fn)/while/body/closed_call/llama.layer/llama.kv_write/"
        "dynamic_update_slice")
    assert not program_trace.has("llama.head")("jit(f)/llama.headroom/add")


def test_event_metadata_is_read_from_the_wire():
    """`mini.xplane.pb` (PR 24): a fused `tanh(x @ x) + 1` and the copies
    around it. The fusion's metadata carries `tf_op`, the copies' do not."""
    scopes = program_trace.op_scopes(MINI)
    fusion = [k for k in scopes if k.startswith("%fusion = ")]
    copies = [k for k in scopes if k.startswith("%copy-")]
    assert len(fusion) == 1 and len(copies) == 2
    assert scopes[fusion[0]] == "jit(<lambda>)/dot_general"
    assert [scopes[c] for c in copies] == ["", ""]
    # the names are the device events' own, which is what joins them
    reduced = tr.reduce(MINI, ("fe.step",))
    assert set(reduced["ops"]) <= set(scopes)


@pytest.mark.parametrize("name", READERS)
def test_no_program_span_no_value(name, monkeypatch):
    """A trace the program wrote neither span nor scope into (the parent
    of the PR that added them), and a run with no trace at all: every
    reader returns None and raises nothing."""
    monkeypatch.setattr(program_trace, "newest_xplane", lambda: MINI)
    program_trace._OPEN.clear()
    rec = {"trace": tr.reduce(MINI, ("fe.step", "poll")),
           "is_pallas": tr.is_pallas, "attn_bytes_traced": 1e6,
           "peaks": {"hbm_bytes_per_s": 8.19e11}}
    assert reader(name)(rec) is None
    assert reader(name)({"trace": None}) is None
    monkeypatch.setattr(program_trace, "newest_xplane", lambda: None)
    assert reader(name)(rec) is None
    program_trace._OPEN.clear()


# ---- the recorded trace: values worked out by hand from `describe`'s print ---
# (nine steps; ms on the profiler's clock, as printed to four decimals)
STEP_WALLS = [5.4575, 4.1048, 4.4337, 4.2954, 4.0918, 3.9761, 4.0282,
              4.2494, 3.8034]
# per step: sched.dispatch + sched.sample. The trace was recorded before
# PR 30, when a round also held a `sched.screen` span (0.7613-1.1260 ms);
# that name has left `program_trace.WAITING` (no cell's round emits it), so
# in THIS trace the screen's wait reads as the scheduler's own time
WAITING = [4.2075, 3.1863, 3.0079, 3.1373, 2.8876, 2.9048, 3.0154, 3.2567,
           2.8561]
US = 1e-6
BUSY_S = 0.001599123
# device time, us, summed by hand over `describe`'s operation lines
RAGGED_US, KV_WRITE_US, FLASH_US = 420.20, 65.68, 191.00
ADAMW_US, HEAD_LOSS_US, UNSCOPED_US = 9.65, 59.28, 363.31


def test_span_tree(pt):
    assert len(pt.spans) == 102
    assert [s.ids["step"] for s in pt.steps] == list(range(5, 14))
    fes = pt.named("fe.step")
    assert len(fes) == 9 and all(
        [c.name for c in fe.children] == ["sched.step"] for fe in fes)
    assert [s.name for s in pt.roots[:3]] == ["frontend.submit"] * 3
    assert [s.ids["req"] for s in pt.roots[:3]] == [1, 2, 3]
    first = pt.steps[0]
    assert [c.name for c in first.children] == [
        "sched.expire", "sched.admit", "sched.grow", "sched.pack",
        "sched.dispatch", "sched.screen", "sched.sample", "sched.commit"]
    admit = first.children[1]
    assert [(c.name, c.ids) for c in admit.children] == [
        ("sched.admit_one", {"req": 1, "prompt": 40, "prefix_hit": 0}),
        ("sched.admit_one", {"req": 2, "prompt": 9, "prefix_hit": 0}),
        ("sched.admit_one", {"req": 3, "prompt": 20, "prefix_hit": 0})]
    assert first.children[4].ids == {"phase": "decode", "prefill_tokens": 16,
                                     "decode_lanes": 0}
    # 16+16+16+16+5 prompt tokens; the lanes decoding in each step
    assert sum(s.ids["prefill_tokens"]
               for s in pt.named("sched.dispatch")) == 40 + 9 + 20
    assert [s.ids["decode_lanes"] for s in pt.named("sched.dispatch")] == [
        0, 0, 0, 1, 2, 3, 1, 1, 1]
    assert [(s.ids["req"], s.ids["status"])
            for s in pt.named("sched.finish")] == [
        (1, "finished"), (3, "finished"), (2, "finished")]


def test_walls_and_self_times(pt):
    assert [s.wall * 1e3 for s in pt.steps] == pytest.approx(STEP_WALLS,
                                                             abs=2e-4)
    assert [pt.waiting_s(s) * 1e3 for s in pt.steps] == pytest.approx(
        WAITING, abs=4e-4)
    first = pt.steps[0]
    # 5.4575 less expire .0067, admit .1332, grow .0115, pack .0348,
    # dispatch 1.0443, screen .9096, sample 3.1633, commit .0110
    assert first.self_time * 1e3 == pytest.approx(0.1431, abs=1e-3)
    for s in pt.steps:
        assert s.self_time > 0
        assert sum(c.wall for c in s.children) + s.self_time == \
            pytest.approx(s.wall, rel=1e-12)
    # the commit of the sixth step holds the two finishes
    assert [c.name for c in pt.steps[5].children[-1].children] == [
        "sched.finish", "sched.finish"]


def test_span_readers(rec):
    # (sum of walls 38.4403 less sum of waiting 28.4596) / 9 steps
    assert reader("sched_host_ms_per_step")(rec) == pytest.approx(
        (sum(STEP_WALLS) - sum(WAITING)) / 9, abs=1e-3)
    # steps 1, 6, 9 admit or finish: median 3.9761; the six others:
    # (4.1048 + 4.2494) / 2 = 4.1771
    assert reader("admit_step_extra_ms")(rec) == pytest.approx(
        3.9761 - 4.1771, abs=1e-3)
    # admit_one -> first_token: req 1 59.8701 - 45.9990, req 2 64.1819 -
    # 46.0426, req 3 68.3021 - 46.0706; the median is req 2's
    assert reader("lane_wait_p50_ms")(rec) == pytest.approx(18.1393, abs=1e-3)


def test_idle_under_the_scheduler(pt, rec):
    """Against `trace_reduce`'s own gaps (another union, another clock
    origin): idle inside each step less idle inside its waiting spans."""
    reduced = rec["trace"]
    (fe0_s, _), first_fe = reduced["spans"]["fe.step"][0], pt.named("fe.step")[0]
    shift = first_fe.start - fe0_s                 # reduce()'s t0

    def idle(a, b):
        return sum(min(e + shift, b) - max(s + shift, a)
                   for s, e in reduced["gaps"]
                   if e + shift > a and s + shift < b)

    want = 0.0
    for st in pt.steps:
        want += idle(st.start, st.end) - sum(
            idle(c.start, c.end) for c in st.children
            if c.name in program_trace.WAITING)
    got = reader("idle_under_sched_ms_per_step")(rec)
    assert got == pytest.approx(1e3 * want / 9, rel=1e-6)
    # nearly all of the scheduler's own time leaves the device idle at this
    # size (a step's device work is 0.1 ms), and never more than all of it
    assert 0.8 * 1.1090 < got <= reader("sched_host_ms_per_step")(rec)
    assert pt.gaps_outside_spans() == []
    assert pt.idle_s(pt.busy[0][0], pt.busy[0][1]) == pytest.approx(0.0)


def test_scope_of_each_operation(pt, rec):
    ops = rec["trace"]["ops"]
    by_label = {tr.label(k): k for k in ops}
    scope = lambda label: pt.scopes[by_label[label]]   # noqa: E731
    assert scope("paged_attention_ragged.8 (tpu_custom_call)") == (
        "jit(_ragged_fn)/while/body/closed_call/llama.layer/llama.attn/"
        "paged_attention_ragged/pallas_call")
    assert scope("flash_dq.2 (tpu_custom_call)") == (
        "jit(train_step)/transpose(jvp(llama.layer))/llama.attn/flash_dq/"
        "pallas_call")
    assert scope("fusion.135") == ("jit(_ragged_fn)/while/body/closed_call/"
                                   "llama.layer/llama.kv_write/scatter")
    # the scan's own slicing of the stacked pool: a path, but no region
    assert scope("copy.43") == "jit(_ragged_fn)/while/body/dynamic_slice"
    assert program_trace.region(scope("copy.43")) is None
    assert scope("copy-done.39") == ""
    # every Pallas call of the trace is told by its name
    kernels = {program_trace.kernel(pt.scopes[k]) for k in ops
               if tr.is_pallas(k)}
    assert kernels == {"paged_attention_ragged", "flash_fwd", "flash_dq",
                       "flash_dkv", "rms_norm"}
    # the printed lines, rounded to 0.01 us each, add up to 1599.38 us
    assert sum(ops.values()) == pytest.approx(1599.38 * US, rel=3e-4)
    assert set(ops) <= set(pt.scopes)


@pytest.mark.parametrize("name, micros", [
    ("ragged_attn_share", RAGGED_US), ("kv_write_share", KV_WRITE_US),
    ("flash_share", FLASH_US), ("adamw_share", ADAMW_US),
    ("head_share", HEAD_LOSS_US), ("unscoped_device_share", UNSCOPED_US)])
def test_share_readers(rec, name, micros, capsys):
    assert rec["trace"]["busy_s"] == pytest.approx(BUSY_S)
    assert reader(name)(rec) == pytest.approx(100.0 * micros * US / BUSY_S,
                                              rel=2e-3)
    if name == "unscoped_device_share":
        out = capsys.readouterr().out
        assert "device time by region" in out and "(unscoped) 22.7" in out
        assert "compare_select_fusion.24 0.0000 [jit(_ragged_fn)/jit(" in out


def test_roofline_by_name(rec):
    """The roofline's arithmetic over the kernel told by name: 1 MB needed
    at 819 GB/s is 1.221 us, over 420.2 us in the kernel (the train step's
    Pallas kernels, which are in the same trace, are not counted)."""
    rec = dict(rec, attn_bytes_traced=1e6, peaks={"hbm_bytes_per_s": 8.19e11})
    got = reader("ragged_attn_roofline")(rec)
    assert got == pytest.approx(100.0 * (1e6 / 8.19e11) / (RAGGED_US * US),
                                rel=1e-3)
