"""`BENCHMARK.json` itself, static and in milliseconds: what the driver's
contract refuses before a run (a full `per_layer`, a name twice, an entry over
a cell that does not report what it moves, a reader or a data file that is
not there), and the rule that keeps the list from filling with twins again:
ONE entry a (quantity, end-to-end metric it moves). Nothing here pins a
count or a position that a later PR's appended entries would turn red.
Imports nothing that needs a chip."""
import collections
import json
import os

import pytest

from conftest import BENCH, ROOT, load

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
# the cells that report each end-to-end metric (`setup_s` lists none: all)
REPORTS = {e["name"]: e.get("workloads", CELLS) for e in MANIFEST["end_to_end"]}
PER_LAYER = MANIFEST["per_layer"]
# a folded entry's suffix names what it moves; any other suffix is a cell's
# short name (`.open`, `.train`, `.kanana`, `.cmdaplus`, `.docqa`)
FOLDED = {"gap_p95_ms": "gap", "serve_tok_s": "serve", "setup_s": "shared"}
MOST = 128                                   # the contract's limit
RUN = load("run.py", "benchmark_run_manifest")


def quantity(name):
    return name.rpartition(".")[0]


def test_per_layer_is_within_the_contract():
    assert 1 <= len(PER_LAYER) <= MOST


@pytest.mark.parametrize("key", ["configs", "workloads", "end_to_end",
                                 "per_layer"])
def test_names_are_unique(key):
    names = [x["name"] for x in MANIFEST[key]]
    assert len(names) == len(set(names))


def test_one_entry_a_quantity_and_metric_moved():
    """No cell reads a quantity twice, and a (quantity, `moves`) has ONE
    entry over several cells, the folded one. A PR that adds a cell may edit
    no entry, so it appends single-cell `<quantity>.<cell's short name>`
    entries beside it; the next `benchmark` PR folds them. (The one pair of
    single-cell entries that stays apart has a reader each.)"""
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
    assert m["workloads"] and set(m["workloads"]) <= set(CELLS)
    assert len(m["workloads"]) == len(set(m["workloads"]))
    # every one of its cells reports the end-to-end metric the entry moves
    assert set(m["workloads"]) <= set(REPORTS[m["moves"]])
    assert os.path.exists(RUN._reader(m["name"]))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_and_has_its_files(cell):
    w = next(w for w in MANIFEST["workloads"] if w["name"] == cell)
    assert any(cell in cells and name != "setup_s"
               for name, cells in REPORTS.items())
    assert any(cell in m["workloads"] for m in PER_LAYER)
    for mix in (w["traffic"], "rehearsal-" + w["traffic"]):
        assert os.path.exists(os.path.join(BENCH, "traffic", mix + ".json"))
    config = next(c for c in MANIFEST["configs"] if c["name"] == w["config"])
    assert os.path.exists(os.path.join(ROOT, config["file"]))


def test_every_configuration_is_used_and_every_reader_is_listed():
    assert {c["name"] for c in MANIFEST["configs"]} \
        == {w["config"] for w in MANIFEST["workloads"]}
    listed = {n for m in PER_LAYER for n in (m["name"], quantity(m["name"]))}
    readers = {f[:-3] for f in os.listdir(os.path.join(BENCH, "layer_metrics"))
               if f.endswith(".py")}
    assert readers <= listed, "a reader that no entry finds"
