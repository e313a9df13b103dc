"""`live_prefix_switch_share.*` (PR 39) over the recorded trace of
`test_program_trace.py`: what the log's table of regions prints under
`llama.layer`, as a share of the busy time."""
import os

import pytest

import program_trace
import trace_reduce as tr
from conftest import HERE, load

DATA = os.path.join(HERE, "data", "program_spans.xplane.pb")
read = load("layer_metrics/live_prefix_switch_share.py",
            "reader_live_prefix_switch_share").read


@pytest.fixture
def rec(monkeypatch):
    monkeypatch.setattr(program_trace, "newest_xplane", lambda: DATA)
    program_trace._OPEN.clear()
    yield {"trace": tr.reduce(DATA, ("fe.step", "train_step")),
           "is_pallas": tr.is_pallas}
    program_trace._OPEN.clear()


def test_reads_the_tables_llama_layer_row(rec):
    pt, trace = program_trace.of(rec), rec["trace"]
    table = pt.by_region(trace["ops"])
    assert read(rec) == pytest.approx(
        100.0 * table.get("llama.layer", 0.0) / trace["busy_s"])


def test_an_operation_directly_under_a_layer_counts_and_no_other(rec):
    pt, trace = program_trace.of(rec), rec["trace"]
    before = read(rec)
    name = next(k for k in trace["ops"]
                if program_trace.region(pt.scopes.get(k, "")) == "llama.attn"
                and not program_trace.kernel(pt.scopes[k]))
    pt.scopes[name] = "jit(_ragged_fn)/llama.layer/cond/branch_1_fun/pad"
    assert read(rec) == pytest.approx(
        before + 100.0 * trace["ops"][name] / trace["busy_s"])
    pt.scopes[name] = ("jit(_ragged_fn)/llama.layer/cond/branch_1_fun/"
                       "llama.mla_q/dot_general")
    assert read(rec) == pytest.approx(before)


def test_nothing_to_read_gives_none():
    assert read({"trace": None}) is None


def test_the_entries_name_cells_that_report_what_they_move():
    import json

    from conftest import ROOT

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # found by name: where they stand in the list is nobody's to pin
    mine = {m["name"]: m for m in bench["per_layer"]
            if m["name"].startswith("live_prefix_switch_share.")}
    assert {n: m["workloads"] for n, m in mine.items()} == {
        "live_prefix_switch_share.serve": ["kanana2-longctx-decode",
                                           "commandaplus-mixedctx-decode"],
        "live_prefix_switch_share.docqa": ["kanana2-docqa-open"]}
    moved = {e["name"]: set(e.get("workloads", ())) for e in bench["end_to_end"]}
    for m in mine.values():
        assert m["layer"] == "engine step" and m["source"] == "device_trace"
        assert m["better"] == "lower"
        assert set(m["workloads"]) <= moved[m["moves"]]
