"""The check catches what it is for: each fault a cell can have, planted
under the timed path while the rest of a run is driven as usual, and the
control (the reference in bfloat16 put in the program's place), all judged
by the cell's own limits at a size a test run can hold."""
import json
import os
import subprocess
import sys

import pytest

import tiny
from bench import faults
from bench.check import judge, readings
from bench.run import BENCH, ROOT, load_module

DANUBE = "danube-4l.train.plan"
YI = "yi-34b-2l.train.tp4-plan"
SEED = 2**32 + 977

# run with four CPU devices: the yi cell's mesh spans them
YI_SCRIPT = """
import json, sys
sys.path[:0] = [{root!r}, {root!r} + "/src", {here!r}]
import tiny
from bench import faults
out = {{"sound": tiny.execute(tiny.spec({cell!r}), seed={seed})["correct"]}}
for name in ("state_unchanged", "half_batch", "exchange_left_out"):
    with faults.FAULTS[name]():
        out[name] = tiny.execute(tiny.spec({cell!r}), seed={seed})["correct"]
import test_bench_faults as t
out["control"] = t.control_correct({cell!r})
print(json.dumps(out))
"""


def control_correct(cell: str) -> bool:
    """Whether the bfloat16 reference at the default matmul precision,
    put in the program's place, passes the cell's check."""
    import jax
    import jax.numpy as jnp

    from bench.traffic.corpus import seed_key
    from bench.traffic.train import host_batches

    s = tiny.spec(cell)
    conf, traffic = s["config"], s["traffic"]
    ref = load_module(BENCH, "reference", conf["family"] + ".py")
    batches = host_batches(conf, traffic, SEED)[:traffic["checked_steps"]]
    devices = jax.devices()[:s["workload"]["chips"]]

    def run(**kw):
        return ref.train_readings(conf["model"], traffic["optimizer"],
                                  seed_key(SEED), batches, devices, **kw)

    ctl = run(dtype=jnp.bfloat16, precision="default")
    ok, _ = judge(readings(ctl, run()), s["cellfile"]["limits"])
    return ok


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_fault_is_caught_one_chip(fault):
    with faults.FAULTS[fault]():
        res = tiny.execute(tiny.spec(DANUBE), seed=SEED)
    assert res["correct"] is False, res["checks"]


def test_control_fails_one_chip():
    assert control_correct(DANUBE) is False


@pytest.fixture(scope="module")
def yi_results():
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", YI_SCRIPT.format(root=ROOT, here=here,
                                                cell=YI, seed=SEED)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_run_passes_four_chips(yi_results):
    assert yi_results["sound"] is True


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "exchange_left_out", "control"])
def test_fault_is_caught_four_chips(yi_results, fault):
    assert yi_results[fault] is False
