"""The harness: found by file name, refuses the CPU, and a run at a small
size agrees with the plain reference."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

import tiny
from bench.run import BENCH, ROOT, resolve


def bench_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", [w["name"] for w in bench_json()["workloads"]])
def test_cell_pieces_found_by_name(cell):
    s = resolve(cell)
    assert s["workload"]["chips"] == s["config"]["chips"]
    mesh = s["config"]["mesh"]
    assert mesh[0] * mesh[1] == s["config"]["chips"]
    fam = s["config"]["family"]
    for part in (("traffic", s["traffic"]["kind"] + ".py"),
                 ("reference", fam + ".py"), ("flops", fam + ".py")):
        assert os.path.isfile(os.path.join(BENCH, *part)), part
    for m in s["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "metrics", m["name"] + ".py"))
    assert set(s["cellfile"]["limits"]) == {
        "loss_gap", "grad_gap", "change_gap", "window_nonfinite"}


def test_cpu_run_is_refused():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "danube-4l.train.plan", "--seed", "3000000019", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout == ""
    assert "nothing was run" in p.stderr


def test_small_run_agrees_with_reference():
    res = tiny.execute(tiny.spec("danube-4l.train.plan"))
    assert res["correct"] is True
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert res["failed"] == 0 and res["attempted"] > 3
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    # float32 on the CPU on both sides: the program is the reference to
    # rounding
    for name in ("loss_gap", "grad_gap", "change_gap"):
        assert res["checks"][name]["value"] < 1e-4, res["checks"]


def test_added_cell_needs_no_edit(tmp_path):
    """A cell with its own traffic and per-layer metric is added by files
    and entries alone, and runs."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = bench_json()
    after = json.loads(json.dumps(before))
    after["workloads"].append({
        "name": "dummy.train", "config": "h2o-danube-1.8b-4l",
        "traffic": "train_s32_b2", "chips": 1, "why": "test"})
    after["per_layer"].append({
        "name": "window_steps", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "train step",
        "moves": "train_tokens_per_s", "workloads": ["dummy.train"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(after))
    traffic = json.loads((tmp_path / "bench/traffic/train_s2048_b4.json")
                         .read_text())
    traffic.update(seq=32, batch=2)
    (tmp_path / "bench/traffic/train_s32_b2.json").write_text(
        json.dumps(traffic))
    (tmp_path / "bench/workloads/dummy.train.json").write_text(
        (tmp_path / "bench/workloads/danube-4l.train.plan.json").read_text())
    (tmp_path / "bench/metrics/window_steps.py").write_text(
        "def read(ctx):\n    return ctx['rec']['window_steps']\n")
    # every entry that was there is unchanged
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert after[key][:len(before[key])] == before[key]

    sp = importlib.util.spec_from_file_location(
        "bench_copy_run", tmp_path / "bench" / "run.py")
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    s = mod.resolve("dummy.train")
    small = tiny.SMALL[s["config"]["arch"]]
    s["config"]["overrides"] = dict(small)
    s["config"]["model"].update(small)
    import jax

    res = mod.execute(s, seed=7, seconds=0.2, trace=True,
                      devices=jax.devices()[:1], t0=0.0,
                      platform_peak={"bf16_flops": 1e12})
    assert res["correct"] is True
    assert res["metrics"]["window_steps"]["value"] >= 1
    assert "train_mfu" not in res["metrics"]    # not listed for this cell
