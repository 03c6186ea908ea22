"""``trace_reduce`` on a synthetic trace with known answers, and on a
recorded trace of LAMC fits on a TPU v5e (``testdata/``)."""

from pathlib import Path

import pytest

import trace_reduce

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"

SYNTHETIC = [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_fit", 100, 300]]},
        {"name": "XLA Ops", "events": [
            ["fusion.1", 100, 50], ["all-reduce.3", 160, 20],
            ["fusion.1", 170, 30], ["copy.2", 300, 100]]}]},
    {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [
            ["fusion.1", 100, 20], ["all-gather-start.1", 130, 40]]}]},
    {"name": "/host:CPU", "lines": [
        {"name": "python", "events": [
            ["bench.window", 0, 1000], ["bench.fit", 10, 500],
            ["PjitFunction(fit)", 20, 60], ["bench.fit", 520, 470],
            ["instant", 700, 0]]}]},
]


def test_busy_union_and_idle_share():
    red = trace_reduce.reduce(SYNTHETIC)
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["busiest"] == "/device:TPU:0"
    # [100,150] + [160,200] (two overlapping ops) + [300,400]
    assert red["busiest_busy_s"] == pytest.approx(190e-9)
    assert red["busy_s"]["/device:TPU:1"] == pytest.approx(60e-9)
    assert red["mean_busy_s"] == pytest.approx(125e-9)
    assert red["idle_share"] == pytest.approx(1 - 190 / 1000)
    assert red["devices"] == 2


def test_collectives_are_attributed_per_device():
    red = trace_reduce.reduce(SYNTHETIC)
    assert red["collective_s"]["/device:TPU:0"] == pytest.approx(20e-9)
    assert red["collective_s"]["/device:TPU:1"] == pytest.approx(40e-9)
    assert red["busiest_collective_s"] == pytest.approx(20e-9)


def test_longest_gaps_are_labelled_by_the_innermost_host_event():
    gaps = trace_reduce.reduce(SYNTHETIC)["breakdown"]["idle_gaps"]
    assert [g[0] for g in gaps] == ["bench.fit", "PjitFunction(fit)",
                                    "bench.fit", "bench.fit"]
    assert [g[1] for g in gaps] == pytest.approx(
        [600e-9, 100e-9, 100e-9, 10e-9])


def test_extra_spans_label_gaps_too():
    red = trace_reduce.reduce(SYNTHETIC, [("obs.plan", 600, 800)])
    assert red["breakdown"]["idle_gaps"][0][0] == "obs.plan"


def test_breakdown_lists_the_longest_ops_of_the_busiest_device():
    ops = trace_reduce.reduce(SYNTHETIC)["breakdown"]["device_ops"]
    assert ops[0] == ["copy.2", pytest.approx(100e-9)]
    assert ops[1] == ["fusion.1", pytest.approx(80e-9)]
    assert ops[2] == ["all-reduce.3", pytest.approx(20e-9)]


def test_no_device_operations_gives_nothing():
    assert trace_reduce.reduce([SYNTHETIC[2]]) is None


def test_json_round_trip(tmp_path):
    path = str(tmp_path / "t.json.gz")
    trace_reduce.save_json(SYNTHETIC, path)
    assert trace_reduce.load_json(path) == SYNTHETIC


def test_ops_are_known_by_their_hlo_instruction_name():
    text = "%fusion.9 = f32[8,2]{1,0} fusion(f32[8,2]{1,0} %all-reduce.1)"
    assert trace_reduce.op_name(text) == "fusion.9"
    planes = [{"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
        [text, 0, 10], ["%all-reduce.1 = f32[8,2]{1,0} all-reduce(...)", 10, 5]]}]}]
    red = trace_reduce.reduce(planes)
    # a fusion that reads a collective's result is no collective itself
    assert red["busiest_collective_s"] == pytest.approx(5e-9)
    assert [op for op, _ in red["breakdown"]["device_ops"]] == [
        "fusion.9", "all-reduce.1"]


def test_recorded_trace_of_three_fits_on_one_chip():
    """Recorded by ``record_trace.py --chips 1`` on a TPU v5e: three
    ``lamc_cocluster`` fits of an 8,192 x 2,048 matrix."""
    planes = trace_reduce.load_json(str(TESTDATA / "trace_1chip.json.gz"))
    red = trace_reduce.reduce(planes)
    assert red["devices"] == 1 and red["busiest"] == "/device:TPU:0"
    assert red["window_s"] == pytest.approx(0.01560804)
    assert red["busiest_busy_s"] == pytest.approx(0.005801597)
    assert red["idle_share"] == pytest.approx(1 - 0.005801597 / 0.01560804)
    assert red["busiest_collective_s"] == 0.0
    gaps = red["breakdown"]["idle_gaps"]
    assert len(gaps) == trace_reduce.TOP
    assert gaps[0] == ["$builtins min", pytest.approx(0.003483347)]
    assert "PjitFunction(_lamc_jit)" in [g[0] for g in gaps]
    assert sum(g[1] for g in gaps) <= red["window_s"] - red["busiest_busy_s"]
    ops = red["breakdown"]["device_ops"]
    assert ops[0] == ["while.165", pytest.approx(0.001038588)]
    assert all(" " not in op for op, _ in ops)
