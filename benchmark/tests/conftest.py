"""The benchmark's own tests: run by hand, `python -m pytest benchmark/tests
-q`, on the CPU. They are no part of the repo's tier-1 suite (`tests/`)."""
import importlib.util
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)


def load(rel, name):
    spec = importlib.util.spec_from_file_location(name,
                                                  os.path.join(BENCH, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="session")
def preset_bench(tmp_path_factory):
    """`BENCHMARK.json` with every cell moved to a CPU-sized preset: its
    configuration becomes `presets/<size>-<runner>.json` and its traffic
    `traffic/rehearsal-<mix>.json`; cells and metrics stay as they are."""
    def build(size):
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
        runner = {}
        for c in bench["configs"]:
            with open(os.path.join(ROOT, c["file"])) as f:
                runner[c["name"]] = json.load(f)["runner"]
        bench["configs"] = [
            {"name": f"{size}-{r}", "source": "none", "reduced": [],
             "why": "rehearsal",
             "file": f"benchmark/tests/presets/{size}-{r}.json"}
            for r in sorted(set(runner.values()))]
        for w in bench["workloads"]:
            w["config"] = f"{size}-{runner[w['config']]}"
            w["traffic"] = "rehearsal-" + w["traffic"]
        path = tmp_path_factory.mktemp("presets") / f"BENCHMARK.{size}.json"
        path.write_text(json.dumps(bench))
        return str(path)
    return build


@pytest.fixture(scope="session")
def bench_run():
    """`benchmark/run.py` as a module: `bench_run.main([...])` is one run."""
    return load("run.py", "benchmark_run")


@pytest.fixture
def rehearse(bench_run, preset_bench, capsys):
    """One CPU walk-through of a cell at its tiny preset; returns the
    result and everything it printed."""
    tiny = preset_bench("tiny")

    def go(workload, *extra, seconds=2, seed=3000000019):
        result = bench_run.main([
            "--rehearse", "--bench-file", tiny, "--workload", workload,
            "--seed", str(seed), "--seconds", str(seconds), *extra])
        return result, capsys.readouterr().out
    return go
