#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, as the only process on the cell's chips.
Everything that belongs to one cell is found by name:
``BENCHMARK.json`` names the cell's configuration, traffic and chips;
``bench/configs/<config>.json`` holds the model's sizes, the reference
family and the mesh; ``bench/traffic/<traffic>.json`` the traffic, whose
``kind`` names the window loop ``bench/traffic/<kind>.py``;
``bench/workloads/<cell>.json`` the plan and the limits of the check;
``bench/metrics/<metric>.py`` the reader of each per-layer metric;
``bench/reference/<family>.py`` and ``bench/flops/<family>.py`` the plain
reference and the operation count of a model family.

It never falls back to the CPU: on any other platform, or with fewer chips
than the cell asks for, it exits 2 and prints no result.  The compared
numbers of the check go to the end of stderr, each beside its limit; the
last line of stdout is the result as one JSON object.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# ``bench`` is imported as a package from the root; its own directory on
# the path would shadow standard modules (``trace``) by its files
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != BENCH]


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(*parts):
    path = os.path.join(*parts)
    name = "bench_" + os.path.relpath(path, BENCH).replace("/", "_") \
        .replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(cell: str, bench_json: str = None) -> dict:
    """Everything the run of ``cell`` needs, from the files named for it."""
    b = load_json(bench_json or os.path.join(ROOT, "BENCHMARK.json"))
    work = {w["name"]: w for w in b["workloads"]}
    if cell not in work:
        raise SystemExit(f"unknown workload {cell!r}; known: {sorted(work)}")
    w = work[cell]
    conf_entry = {c["name"]: c for c in b["configs"]}[w["config"]]

    def here(m):
        return "workloads" not in m or cell in m["workloads"]

    return {
        "cell": cell,
        "workload": w,
        "config": load_json(ROOT, conf_entry["file"]),
        "traffic": load_json(BENCH, "traffic", w["traffic"] + ".json"),
        "cellfile": load_json(BENCH, "workloads", cell + ".json"),
        "end_to_end": [m for m in b["end_to_end"] if here(m)],
        "per_layer": [m for m in b["per_layer"] if here(m)],
    }


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def execute(spec: dict, *, seed: int, seconds: float, trace: bool,
            devices, t0: float = T0, platform_peak=None) -> dict:
    """Drive the cell once on ``devices`` and build the result object."""
    from bench import trace as TR

    conf, traffic, cellfile = spec["config"], spec["traffic"], spec["cellfile"]
    kind = load_module(BENCH, "traffic", traffic["kind"] + ".py")
    trace_dir = None
    if trace:
        trace_dir = os.path.join(ROOT, "bench_out", spec["cell"], "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
    rec = kind.run(conf, traffic, cellfile, seed=seed, seconds=seconds,
                   trace_dir=trace_dir, log=say)
    setup_s = rec["window_start"] - t0
    say(f"set-up {setup_s:.3f} s: compile or cache load "
        f"{rec['compile_s']:.3f} s, tune {rec.get('tune_s', 0.0):.3f} s; "
        f"window {rec['window_steps']} steps in {rec['window_s']:.3f} s")
    say(f"LAG010 fallback sites: {len(rec['fallback_sites'])} "
        f"{' '.join(rec['fallback_sites']) or '-'}")
    say(f"peak_bytes_in_use (fullest chip) {rec['memory_peak_bytes']}")

    summary = {}
    if trace:
        from repro.analysis.ir import graph_from_hlo

        g = graph_from_hlo(rec.pop("hlo"))
        kinds = sorted({c.kind for c in g.collectives})
        say("compiled step collectives: " + (", ".join(
            f"{k} {g.count(k)}" for k in kinds) or "none"))
        summary = TR.reduce(TR.load(TR.xplane_file(trace_dir)))
        for dev, d in sorted(summary.get("devices", {}).items()):
            say(f"{dev}: busy {d['busy_s']:.4f} s of "
                f"{summary['window_s']:.4f} s, collective "
                f"{d['collective_s']:.4f} s, exposed {d['exposed_s']:.4f} s")

    ref = load_module(BENCH, "reference", conf["family"] + ".py")
    numbers = kind.check(rec, conf, traffic, seed, devices, ref)
    from bench.check import judge

    correct, shown = judge(numbers, cellfile["limits"])

    ctx = {"conf": conf, "traffic": traffic, "cell": cellfile, "rec": rec,
           "trace": summary, "chips": len(devices), "peak": platform_peak}
    if trace:
        metrics = {}
        for m in spec["per_layer"]:
            value = load_module(BENCH, "metrics", m["name"] + ".py").read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(kind.end_to_end(rec), setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"]}
    dev = devices[0]
    result = {
        "correct": correct,
        "attempted": traffic["checked_steps"] + rec["window_steps"],
        "failed": sum(not math.isfinite(x)
                      for x in rec["losses"] + rec["window_losses"]),
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices),
                   "memory_peak_bytes": rec["memory_peak_bytes"]},
    }
    if trace and summary:
        result["device"]["busy_s"] = TR.mean(summary, "busy_s")
        result["device"]["window_s"] = summary["window_s"]
        result["breakdown"] = summary["breakdown"]
    result["checks"] = shown
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = resolve(args.workload)
    chips = spec["workload"]["chips"]
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    from repro.launch.config import configure_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        say(f"bench: needs {chips} TPU chip(s); JAX has {len(devices)} "
            f"{devices[0].platform} device(s) ({devices[0].device_kind}); "
            "nothing was run")
        return 2
    from bench.peaks import peak

    platform_peak = peak(devices[0].device_kind)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    say(f"bench: {args.workload} seed {args.seed}; {chips} x "
        f"{devices[0].device_kind}; jax {jax.__version__}; compile cache "
        f"{configure_compile_cache()}")
    result = execute(spec, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), devices=devices[:chips],
                     platform_peak=platform_peak)
    for name, c in result["checks"].items():
        say(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
