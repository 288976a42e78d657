"""The reduction from trace events to busy, idle, collective and exposed
time and the breakdown."""
import json
import os

import pytest

from bench import trace as TR

HERE = os.path.dirname(os.path.abspath(__file__))
EXCERPT = os.path.join(HERE, "data", "v5e_trace_excerpt.json")

# times in ns; window = first bench.data start .. last bench.sync end
SYNTH = {
    "devices": {
        "/device:TPU:0": {
            "ops": [["fusion.1", 0, 10], ["all-gather-start.2", 10, 12],
                    ["fusion.3", 12, 20], ["all-reduce.4", 20, 25],
                    ["while.9", 0, 25]],
            "async": [["copy-start.6", 0, 30]]},
        "/device:TPU:1": {
            "ops": [["fusion.1", 0, 20], ["collective-permute-done.5", 5, 8],
                    ["fusion.3", 24, 26]],
            # in flight under fusion.1, then alone from 20 to 22
            "async": [["collective-permute-start.7", 2, 22]]},
    },
    "host": [["bench.data", 0, 1], ["bench.dispatch", 1, 2],
             ["bench.sync", 2, 30], ["bench.data", 30, 31],
             ["bench.dispatch", 31, 32], ["bench.sync", 32, 40]],
}


def test_synthetic_reduction():
    r = TR.reduce(SYNTH)
    assert r["window_s"] == pytest.approx(40e-9)
    assert r["steps"] == 2
    d0, d1 = r["devices"]["/device:TPU:0"], r["devices"]["/device:TPU:1"]
    assert d0["busy_s"] == pytest.approx(25e-9)
    assert d0["collective_s"] == pytest.approx(7e-9)
    assert d0["exposed_s"] == pytest.approx(7e-9)      # nothing overlaps
    assert d1["busy_s"] == pytest.approx(22e-9)
    assert d1["collective_s"] == pytest.approx(20e-9)
    assert d1["exposed_s"] == pytest.approx(2e-9)      # 20..22 only
    assert TR.mean(r, "busy_s") == pytest.approx(23.5e-9)
    gaps = r["breakdown"]["idle_gaps"]
    # device 0 idles 25..40, device 1 20..24 and 26..40
    assert [g[1] for g in gaps] == pytest.approx([15e-9, 14e-9, 4e-9])
    assert gaps[0][0] == "bench.sync"
    assert gaps[2][0] == "bench.sync"
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(15e-9)      # (10 + 20) / 2
    assert list(ops)[0] == "fusion.1"
    assert "while.9" not in ops          # a loop's body is counted, not it


@pytest.mark.parametrize("name,coll", [
    ("all-gather-start.12", True), ("all-reduce.3", True),
    ("reduce-scatter", True), ("collective-permute-done.7", True),
    ("all-to-all.1", True), ("fusion.7", False), ("copy-start.2", False),
    ("all-reduce-scatter-fusion.1", True)])
def test_collective_names(name, coll):
    assert TR.is_collective(name) is coll


def test_recorded_v5e_excerpt():
    if not os.path.exists(EXCERPT):
        pytest.fail(f"missing recorded trace {EXCERPT}")
    with open(EXCERPT) as f:
        events = json.load(f)
    r = TR.reduce(events)
    # one step of the danube cell on one TPU v5e: 4,008 operations
    assert r["steps"] == 1
    assert len(r["devices"]) == 1
    d = next(iter(r["devices"].values()))
    assert 0.95 * r["window_s"] < d["busy_s"] <= r["window_s"]
    assert d["collective_s"] <= d["busy_s"]
    assert len(r["breakdown"]["device_ops"]) == 10
    assert all(not n.startswith("while") for n, _ in
               r["breakdown"]["device_ops"])
    assert TR.hlo_name("%fusion.12 = f32[4]{0} fusion(...)") == "fusion.12"
