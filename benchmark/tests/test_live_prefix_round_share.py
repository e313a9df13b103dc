"""`live_prefix_round_share.*` (PR 39) over the recorded trace of
`test_program_trace.py`: nine rounds whose `sched.dispatch` ids are prefill
tokens 16, 16, 16, 16, 5, 0, 0, 0, 0 beside 0, 0, 0, 1, 2, 3, 1, 1, 1 decode
lanes."""
import os

import pytest

import program_trace
import trace_reduce as tr
from conftest import HERE, load

DATA = os.path.join(HERE, "data", "program_spans.xplane.pb")
MINI = os.path.join(HERE, "data", "mini.xplane.pb")
read = load("layer_metrics/live_prefix_round_share.py",
            "reader_live_prefix_round_share").read


@pytest.fixture
def rec(monkeypatch):
    monkeypatch.setattr(program_trace, "newest_xplane", lambda: DATA)
    program_trace._OPEN.clear()
    yield {"trace": tr.reduce(DATA, ("fe.step", "train_step")),
           "is_pallas": tr.is_pallas}
    program_trace._OPEN.clear()


@pytest.mark.parametrize("lanes, share", [(4, 4 / 9), (7, 5 / 9), (16, 8 / 9),
                                          (17, 1.0), (2, 3 / 9)])
def test_share_of_rounds_no_wider_than_the_lanes(rec, lanes, share):
    assert read(dict(rec, lanes=lanes)) == pytest.approx(100 * share)


def test_nothing_to_read_gives_none(rec, monkeypatch):
    assert read(rec) is None                      # a record without lanes
    assert read({"trace": None, "lanes": 4}) is None
    monkeypatch.setattr(program_trace, "newest_xplane", lambda: MINI)
    program_trace._OPEN.clear()
    assert read({"trace": tr.reduce(MINI, ("fe.step",)), "lanes": 4}) is None


def test_the_entries_name_cells_that_report_what_they_move():
    import json

    from conftest import ROOT

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # found by name: where they stand in the list is nobody's to pin
    mine = {m["name"]: m for m in bench["per_layer"]
            if m["name"].startswith("live_prefix_round_share.")}
    assert {n: m["workloads"] for n, m in mine.items()} == {
        "live_prefix_round_share.serve": ["kanana2-longctx-decode",
                                          "commandaplus-mixedctx-decode"],
        "live_prefix_round_share.docqa": ["kanana2-docqa-open"]}
    moved = {e["name"]: set(e.get("workloads", ())) for e in bench["end_to_end"]}
    for m in mine.values():
        assert m["layer"] == "engine step" and m["source"] == "program_span"
        assert set(m["workloads"]) <= moved[m["moves"]]
