"""`setup_*_s` (PR 41): the six readers over a synthetic compile record and
monitor, the entries in `BENCHMARK.json`, and the CPU rehearsal's traced walk
of the three rehearsed cells."""
import json
import os
import types

import pytest

import setup_record
from conftest import ROOT, load

QUANTITIES = ("import", "build", "trace", "lower", "compile", "fill")
READ = {q: load(f"layer_metrics/setup_{q}_s.py", f"reader_setup_{q}_s").read
        for q in QUANTITIES}
T0 = 1_000.0                         # the synthetic process's first stamp


def record(name, start, trace_s, lower_s, backend_s, cache="hit"):
    return types.SimpleNamespace(
        name=name, start=T0 + start, end=T0 + start + trace_s + lower_s
        + backend_s, trace_s=trace_s, lower_s=lower_s, backend_s=backend_s,
        wall_s=trace_s + lower_s + backend_s, cache=cache, is_retrace=False)


class Monitor:
    def __init__(self, **values):
        self.values = {k.replace("__", "."): v for k, v in values.items()}

    def get(self, name):
        return self.values.get(name, 0)


SERVE_RECORDS = [
    record("make_leaf", 1.0, 0.01, 0.02, 0.30),        # weights: before the build
    record("_stack", 5.2, 0.10, 0.05, 0.25),           # inside the constructor
    record("_ragged_fn", 7.0, 9.0, 5.0, 1.0),          # the first step compiles it
    record("convert_element_type", 22.5, 0.0, 0.001, 0.002),
    record("step", 90.0, 0.5, 0.2, 3.0, "miss"),       # the reference, after the window
]
SERVE_STAMPS = {"startup.import": (T0 + 0.1, T0 + 3.6),
                "engine.build": (T0 + 5.0, T0 + 6.5)}


@pytest.fixture
def serve(monkeypatch):
    """A serving cell's record: a 1.5 s build with 0.4 s of compiles inside
    it, a step program of 9 + 5 + 1 s, and 40 s of scheduler steps of which
    the window's own are 12 s."""
    monitor = Monitor(startup__import_s=3.5, engine__build_s=1.5,
                      serving__step__wall_s=40.0)
    monkeypatch.setattr(
        setup_record, "of", lambda rec: setup_record.Setup(
            monitor, SERVE_RECORDS, SERVE_STAMPS,
            setup_record.STEP_PROGRAM.get(rec["config"]["runner"],
                                          setup_record.SERVING_STEP)))
    return {"config": {"runner": "serve_deepseek_v3"},
            "step_ms": [20.0] * 600}


@pytest.mark.parametrize("quantity, value", [
    ("import", 3.5), ("build", 1.5 - 0.4), ("trace", 9.0), ("lower", 5.0),
    ("compile", 1.0), ("fill", 40.0 - 12.0 - 15.0)])
def test_serving_values(serve, quantity, value):
    assert READ[quantity](serve) == pytest.approx(value)


def test_the_references_names_are_left_out(serve):
    """`step` is the reference's jitted layer, compiled after the window;
    `make_leaf` the benchmark's weights: neither is the cell's program."""
    total = sum(r.trace_s for r in SERVE_RECORDS)
    assert READ["trace"](serve) == pytest.approx(9.0) and total > 9.5


def test_train_cell_sums_the_train_step(monkeypatch):
    records = [record("mean_loss", 1.0, 0.5, 0.3, 4.0, "miss"),   # reference
               record("train_step", 30.0, 2.0, 1.5, 0.8),
               record("_ragged_fn", 50.0, 9.0, 5.0, 1.0)]
    monkeypatch.setattr(setup_record, "of", lambda rec: setup_record.Setup(
        Monitor(startup__import_s=3.0), records,
        {"startup.import": (T0, T0 + 3.0)}, "train_step"))
    rec = {"config": {"runner": "train"}, "step_ms": [500.0] * 20}
    assert READ["trace"](rec) == pytest.approx(2.0)
    assert READ["lower"](rec) == pytest.approx(1.5)
    assert READ["compile"](rec) == pytest.approx(0.8)
    assert READ["import"](rec) == pytest.approx(3.0)
    assert READ["build"](rec) is None and READ["fill"](rec) is None


@pytest.mark.parametrize("quantity", QUANTITIES)
def test_nothing_to_read_gives_none(monkeypatch, quantity):
    # a program without the record (the parent of the PR that brought it)
    monkeypatch.setattr(setup_record, "of", lambda rec: None)
    assert READ[quantity]({"config": {"runner": "serve"},
                           "step_ms": [1.0]}) is None
    # a program with it in which nothing was stamped or compiled yet
    monkeypatch.setattr(setup_record, "of", lambda rec: setup_record.Setup(
        Monitor(), [], {}, "_ragged_fn"))
    assert READ[quantity]({"config": {"runner": "serve"},
                           "step_ms": [1.0]}) is None


def test_of_reads_the_program_and_an_older_program_reads_none(monkeypatch,
                                                              capsys):
    from paddle_tpu.observability import compile_trace

    monkeypatch.setattr(setup_record, "_printed", False)
    found = setup_record.of({"config": {"runner": "train"}})
    assert found.program == "train_step"
    assert found.value("startup.import_s") > 0
    assert "set-up: import" in capsys.readouterr().out
    assert setup_record.of({"config": {"runner": "serve"}}).program \
        == "_ragged_fn"
    assert capsys.readouterr().out == "", "the log's record is printed once"
    monkeypatch.delattr(compile_trace, "stamps")
    assert setup_record.of({"config": {"runner": "serve"}}) is None


def test_the_log_prints_the_whole_record(capsys):
    found = setup_record.Setup(Monitor(startup__import_s=3.5), SERVE_RECORDS,
                               SERVE_STAMPS, "_ragged_fn")
    setup_record._print(found, SERVE_RECORDS)
    out = capsys.readouterr().out
    assert "_ragged_fn: trace 9.000 s, lowering 5.000 s, backend 1.000 s, " \
           "cache hit" in out
    assert "step: trace 0.500 s" in out and "cache miss" in out
    assert "and 1 programs under 10 ms each" in out


# ---- the entries ---------------------------------------------------------------
# `.shared`: ONE entry a quantity over the cells its `workloads` lists, in
# the order of `workloads` in the file (PR 44 folded the single-cell
# `.cmdaplus` and `.docqa` twins into them).
SHARED = ["mistral7b-chat-open", "yi6b-train-4k", "mistral7b-batch-decode",
          "mistral7b-long-prefill", "kanana2-longctx-decode",
          "commandaplus-mixedctx-decode", "kanana2-docqa-open"]
SERVING = [c for c in SHARED if c != "yi6b-train-4k"]
BACKLOG = ["mistral7b-batch-decode", "kanana2-longctx-decode",
           "commandaplus-mixedctx-decode"]
ENTRIES = {
    "setup_import_s.shared": SHARED, "setup_build_s.shared": SERVING,
    "setup_trace_s.shared": SHARED, "setup_lower_s.shared": SHARED,
    "setup_compile_s.shared": SHARED, "setup_fill_s.shared": BACKLOG,
}


def test_the_entries_name_cells_that_exist_and_move_setup_s():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"] for w in bench["workloads"]}
    # found by name: where they stand in the list is nobody's to pin
    mine = [m for m in bench["per_layer"] if m["name"].startswith("setup_")]
    assert {m["name"]: m["workloads"] for m in mine} == ENTRIES
    assert [m["name"] for m in bench["per_layer"]
            if m["moves"] == "setup_s"] == [m["name"] for m in mine]
    run = load("run.py", "benchmark_run_setup_layers")
    for m in mine:
        assert set(m["workloads"]) <= cells
        assert (m["moves"], m["source"], m["better"], m["unit"],
                m["layer"]) == ("setup_s", "program_counter", "lower", "s",
                                "set-up")
        assert os.path.basename(run._reader(m["name"])) \
            == m["name"].rpartition(".")[0] + ".py"
    # every cell reads its import, its step program's three phases, and a
    # backlog cell its fill
    for cell in cells:
        read = {m["name"].partition(".")[0] for m in mine
                if cell in m["workloads"]}
        assert {"setup_import_s", "setup_trace_s", "setup_lower_s",
                "setup_compile_s"} <= read, cell
    for cell in BACKLOG:
        assert any(cell in m["workloads"] for m in mine
                   if m["name"].startswith("setup_fill_s.")), cell


# ---- the rehearsal ------------------------------------------------------------
@pytest.mark.parametrize("cell, quantities", [
    ("mistral7b-chat-open", ("import", "build", "trace", "lower", "compile")),
    ("mistral7b-batch-decode", QUANTITIES),
    ("yi6b-train-4k", ("import", "trace", "lower", "compile")),
    ("commandaplus-mixedctx-decode", QUANTITIES),
    ("kanana2-docqa-open", ("import", "build", "trace", "lower", "compile")),
])
def test_traced_walk_would_report_the_new_names(rehearse, monkeypatch, cell,
                                                quantities):
    monkeypatch.setattr(setup_record, "_printed", False)
    result, out = rehearse(cell, "--trace", "1")
    assert result["correct"] is True, out
    last = json.loads(out.strip().splitlines()[-1][len("REHEARSAL "):])
    mine = {n for n in last["would_report"] if n.startswith("setup_")}
    assert mine == {f"setup_{q}_s.shared" for q in quantities}
    for name in mine:
        assert result["metrics"][name]["value"] > 0, name
    assert "set-up: import" in out and "backend" in out
