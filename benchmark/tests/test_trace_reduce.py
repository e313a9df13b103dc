"""`trace_reduce.py` against one small trace recorded on the v5e (three
calls of a jitted `tanh(x @ x) + 1` at 1024 x 1024 under `fe.step` spans,
5 ms sleeps under `poll` spans between them; `tests/data/mini.xplane.pb`)."""
import os

import pytest

from conftest import HERE, load

tr = load("trace_reduce.py", "benchmark_trace_reduce_t")


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce(os.path.join(HERE, "data", "mini.xplane.pb"),
                     ("fe.step", "poll", "submit"))


def test_busy_is_the_union_of_device_operations(reduced):
    assert reduced["devices"] == 1
    assert len(reduced["op_events"]) == 9          # 3 x (fusion, copy pair)
    assert reduced["busy_s"] == pytest.approx(3.79e-05, rel=0.01)
    # from the first `fe.step` span's start to the last `poll` span's end
    # (first to last device operation: 0.012863 s)
    assert reduced["window_s"] == pytest.approx(0.0196697, rel=0.001)
    assert sum(reduced["ops"].values()) == pytest.approx(reduced["busy_s"],
                                                         rel=0.01)
    top = tr.top_ops(reduced, 2)
    assert top[0][0] == "fusion" and top[0][1] == pytest.approx(3.7866e-05)


def test_host_spans_and_gaps(reduced):
    assert {k: len(v) for k, v in reduced["spans"].items()} == \
        {"fe.step": 3, "poll": 3}
    idle = dict(tr.idle_by_span(reduced))
    # the device waits while the host sleeps under `poll`, and before
    # its first operation and after its last
    assert sum(idle.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)
    assert idle["poll"] > 0.7 * sum(idle.values())


def test_leaf_time_does_not_count_a_parent_twice():
    ev = [(0, 100, "while"), (10, 40, "kernel"), (50, 90, "kernel")]
    out = tr._leaf_time(ev)
    assert out["kernel"] == pytest.approx(70e-9)
    assert out["while"] == pytest.approx(30e-9)
    assert tr._union([(s, e) for s, e, _ in ev])[0] == 100


def test_pallas_calls_are_told_by_their_target():
    name = ('%closed_call.10 = bf16[96,8,8,128] custom-call(s32[32] %a), '
            'custom_call_target="tpu_custom_call"')
    assert tr.is_pallas(name) and not tr.is_pallas("%fusion.1 = f32[] fusion()")
    assert tr.label(name) == "closed_call.10 (tpu_custom_call)"
