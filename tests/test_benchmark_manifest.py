"""`BENCHMARK.json` itself, static and in milliseconds, in tier-1: what the
driver's contract refuses before a run (a `per_layer` over 128, a name twice
or out of form, an entry over a cell that does not report what it moves, a
reader or a data file that is not there) and the rule that keeps the list
from filling with twins: ONE folded entry a (quantity, end-to-end metric it
moves), and only single-cell entries beside it. The checks of
`benchmark/tests/test_manifest.py`, which no tier-1 run reaches, in the form
a later PR's appended entries cannot turn red: nothing here pins a count or a
position. Imports nothing of the benchmark and nothing that needs a chip."""
import collections
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
# the cells that report each end-to-end metric (`setup_s` lists none: all)
REPORTS = {e["name"]: e.get("workloads", CELLS) for e in MANIFEST["end_to_end"]}
PER_LAYER = MANIFEST["per_layer"]
FOLDED = {"gap_p95_ms": "gap", "serve_tok_s": "serve", "setup_s": "shared"}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def quantity(name):
    return name.rpartition(".")[0]


def reader(name):
    """`benchmark/run.py`'s rule: `layer_metrics/<name>.py`, or the file of
    the name up to its last dot."""
    for stem in (name, quantity(name)):
        path = os.path.join(BENCH, "layer_metrics", stem + ".py")
        if stem and os.path.exists(path):
            return path
    return None


def test_the_lists_are_within_the_contract():
    assert 1 <= len(PER_LAYER) <= 128
    assert 1 <= len(MANIFEST["configs"]) <= 24
    assert 1 <= len(CELLS) <= 24
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4)
    # 2 + 14 x cells runs of run_seconds + 60 s, 2 x 90 s a cell, 1200 spare
    runs = 2 + 14 * len(CELLS)
    assert runs * (MANIFEST["run_seconds"] + 60) + 180 * len(CELLS) + 1200 \
        <= 43200


@pytest.mark.parametrize("key", ["configs", "workloads", "end_to_end",
                                 "per_layer"])
def test_names_are_unique_and_well_formed(key):
    names = [x["name"] for x in MANIFEST[key]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)


def test_one_folded_entry_a_quantity_and_metric_moved():
    groups = collections.defaultdict(list)
    for m in PER_LAYER:
        groups[quantity(m["name"]), m["moves"]].append(m)
    for (q, moves), ms in groups.items():
        cells = [c for m in ms for c in m["workloads"]]
        assert len(cells) == len(set(cells)), (q, moves)
        if len(ms) > 1:
            folded = f"{q}.{FOLDED.get(moves)}"
            assert all(len(m["workloads"]) == 1 for m in ms
                       if m["name"] != folded), (q, moves)


@pytest.mark.parametrize("m", PER_LAYER, ids=lambda m: m["name"])
def test_entry(m):
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert quantity(m["name"]), "a metric is named <quantity>.<suffix>"
    assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert m["source"] in ("device_trace", "program_span", "program_counter",
                           "host_clock")
    assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    assert m["workloads"] and set(m["workloads"]) <= set(CELLS)
    assert len(m["workloads"]) == len(set(m["workloads"]))
    # every one of its cells reports the end-to-end metric the entry moves
    assert set(m["workloads"]) <= set(REPORTS[m["moves"]])
    assert reader(m["name"]), "no reader under benchmark/layer_metrics/"
    if "roofline" in m["name"] or "mfu" in m["name"]:
        assert m["unit"] == "%" and m["better"] == "higher"


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_and_has_its_files(cell):
    w = next(w for w in MANIFEST["workloads"] if w["name"] == cell)
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert NAME.match(w["traffic"])
    assert any(cell in cells and name != "setup_s"
               for name, cells in REPORTS.items())
    assert any(cell in m["workloads"] for m in PER_LAYER)
    for mix in (w["traffic"], "rehearsal-" + w["traffic"]):
        assert os.path.exists(os.path.join(BENCH, "traffic", mix + ".json"))
    config = next(c for c in MANIFEST["configs"] if c["name"] == w["config"])
    assert os.path.exists(os.path.join(ROOT, config["file"]))


@pytest.mark.parametrize("config", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_configuration_has_its_files(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert len(config["reduced"]) <= 16 and 1 <= len(config["why"]) <= 200
    assert config["file"].startswith(tuple(
        p + "/" for p in MANIFEST["paths"]))
    with open(os.path.join(ROOT, config["file"])) as f:
        body = json.load(f)
    assert set(config["reduced"]) == set(body.get("reduced", {}))
    for part in ("runners/" + body["runner"] + ".py",):
        assert os.path.exists(os.path.join(BENCH, part))
    size = os.path.basename(config["file"])
    assert sum(c["file"].endswith(size) for c in MANIFEST["configs"]) == 1


def test_every_configuration_is_used_and_every_reader_is_listed():
    assert {c["name"] for c in MANIFEST["configs"]} \
        == {w["config"] for w in MANIFEST["workloads"]}
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))
    listed = {n for m in PER_LAYER for n in (m["name"], quantity(m["name"]))}
    readers = {f[:-3] for f in os.listdir(os.path.join(BENCH, "layer_metrics"))
               if f.endswith(".py")}
    assert readers <= listed, "a reader that no entry finds"
