"""The control, kept at a size a test run can hold (hidden 512, 4 layers,
vocab 4096; `presets/small-*.json`, whose limits were set from CPU readings
the same way the cells' were set from chip readings): a sound run is correct,
and the reference computed on an int8 grid in the program's place is not.
(`--control weights-int8`, the program serving int8-grid weights, separates
by 4.5 x at the cells' widths on the chip but not reliably at this size on a
CPU, so it is read on the chip only; the readings are in the configuration's
file.)"""
import pytest


@pytest.fixture
def small(bench_run, preset_bench, capsys):
    bench_file = preset_bench("small")

    def go(workload, *extra, seconds):
        result = bench_run.main([
            "--rehearse", "--bench-file", bench_file, "--workload", workload,
            "--seed", "3000000029", "--seconds", str(seconds), *extra])
        return result, capsys.readouterr().out
    return go


@pytest.mark.parametrize("control,correct", [
    (None, True), ("ref-int8", False)])
def test_serving(small, control, correct):
    extra = ("--control", control) if control else ()
    result, out = small("mistral7b-batch-decode", *extra, seconds=6)
    assert result["correct"] is correct, out
    assert result["failed"] == 0, "the control fails the comparison, not the run"


@pytest.mark.parametrize("control,correct", [(None, True), ("ref-int8", False)])
def test_training(small, control, correct):
    extra = ("--control", control) if control else ()
    result, out = small("yi6b-train-4k", *extra, seconds=1)
    assert result["correct"] is correct, out
