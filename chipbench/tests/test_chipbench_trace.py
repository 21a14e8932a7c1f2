"""Trace reduction: busy union, idle share, attribution of device time to
the launching host span, top ops and the longest idle gaps."""
import json
import pathlib
import subprocess
import sys

import pytest

from chipbench import tracereduce

DATA = pathlib.Path(__file__).resolve().parent / "data"
W = tracereduce.WINDOW

# a hand-made trace, times in ns: window [0, 1000); the step span launches
# jit_step, the aggregate span launches jit_mean
HAND = {
    "host": [
        [W, 0, 1000],
        ["chipbench.round", 0, 900],
        ["chipbench.step", 0, 60], ["PjitFunction(step)", 20, 50],
        ["chipbench.aggregate", 600, 650], ["PjitFunction(mean)", 610, 640],
    ],
    "devices": {"0": {
        "modules": [["jit_step(1)", 100, 500], ["jit_mean(2)", 700, 800]],
        "ops": [["fusion.1", 100, 300], ["fusion.2", 250, 500],
                ["reduce.3", 700, 800]],
    }},
}


def test_busy_is_the_union_of_op_intervals():
    r = tracereduce.reduce(HAND)
    assert r["window_s"] == pytest.approx(1e-6)
    assert r["busy_s"] == pytest.approx(500e-9)       # [100,500) + [700,800)
    assert r["idle_share_worst"] == pytest.approx(0.5)


def test_device_time_goes_to_the_span_that_launched_it():
    r = tracereduce.reduce(HAND)
    assert r["device_s_by_span"]["step"] == pytest.approx(400e-9)
    assert r["device_s_by_span"]["aggregate"] == pytest.approx(100e-9)


def test_top_ops_and_longest_gaps_are_named():
    b = tracereduce.reduce(HAND)["breakdown"]
    assert b["device_ops"][0] == ["fusion.2", pytest.approx(250e-9)]
    # gaps, longest first: [500,700) and [800,1000) begin in the round's
    # own host code, [0,100) in the step span
    assert [g[0] for g in b["idle_gaps"]] == ["round", "round", "step"]
    assert b["idle_gaps"][0][1] == pytest.approx(200e-9)


def test_no_window_or_no_device_reads_nothing():
    assert tracereduce.reduce({"host": [], "devices": {}}) == {}
    assert tracereduce.reduce({"host": HAND["host"], "devices": {}}) == {}


def test_module_base_names():
    assert tracereduce.module_base("jit_step(123)") == "step"
    assert tracereduce.module_base("jit_mean.4") == "mean"


# slices of traces recorded on one v5e by the benchmark's traced runs
RECORDED = sorted(DATA.glob("trace_*.json"))


@pytest.mark.parametrize("path", RECORDED, ids=[p.stem for p in RECORDED])
def test_recorded_chip_trace_reduces_consistently(path):
    trace = json.loads(path.read_text())
    r = tracereduce.reduce(trace)
    # busy recomputed by brute force on a 1 us grid
    (w0, w1), = [(s, e) for n, s, e in trace["host"] if n == W]
    for dev, d in trace["devices"].items():
        grid = bytearray((w1 - w0) // 1000 + 1)
        for _, s, e in d["ops"]:
            for t in range(max(s, w0) // 1000 - w0 // 1000,
                           min(e, w1) // 1000 - w0 // 1000):
                grid[t] = 1
        assert r["per_device"][dev]["busy_s"] == pytest.approx(
            sum(grid) * 1e-6, rel=0.02)
    assert 0 <= r["idle_share_worst"] < 1
    top = r["breakdown"]["device_ops"]
    assert top and sum(t for _, t in top) <= r["busy_s"] * 1.000001
    if any(n.startswith("PjitFunction(step)") for n, _, _ in trace["host"]):
        assert r["device_s_by_span"].get("step", 0) > 0


def test_importing_the_reduction_loads_no_tpu_library():
    code = ("import sys; import chipbench.tracereduce; "
            "print(any('libtpu' in m for m in sys.modules))")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=str(DATA.parents[2]), check=True)
    assert out.stdout.strip() == "False"
