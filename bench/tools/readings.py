#!/usr/bin/env python3
"""The readings that a cell's limits are set from, in one process.

    python3 bench/tools/readings.py <cell> <out.jsonl> <seed>... [--faults N]

For every seed, the program's checked steps (set-up and the first steps,
with a window of one step) against the float32 reference: the sound
reading.  For the first N seeds (default 3) also what the check's numbers
read for the control and for each fault the cell can have: the reference
in bfloat16 at the default matmul precision put in the program's place;
the reference with half of every batch left out; and, where the cell
spans several chips, the program with the exchange between chips left
out.  A step that returns its state unchanged reads 1 on ``change_gap``
by construction and is not run.  One JSON line a seed goes to
``out.jsonl``, with the leaves that read worst.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def worst(prog, ref, k=3):
    med = statistics.median(ref.values())
    gaps = {n: abs(prog.get(n, float("nan")) - r) / max(r, med)
            for n, r in ref.items()}
    return sorted(gaps.items(), key=lambda g: -g[1])[:k]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("out")
    ap.add_argument("seeds", type=int, nargs="+")
    ap.add_argument("--faults", type=int, default=3)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import jax.numpy as jnp

    from bench import faults
    from bench.check import readings
    from bench.run import BENCH, load_module, resolve
    from bench.traffic.corpus import seed_key
    from repro.launch.config import configure_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    configure_compile_cache()
    spec = resolve(args.cell)
    conf, traffic, cellfile = spec["config"], spec["traffic"], spec["cellfile"]
    kind = load_module(BENCH, "traffic", traffic["kind"] + ".py")
    ref = load_module(BENCH, "reference", conf["family"] + ".py")
    chips = spec["workload"]["chips"]
    devices = jax.devices()[:chips]
    half = slice(0, traffic["batch"] // 2)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    def program(seed):
        return kind.run(conf, traffic, cellfile, seed=seed, seconds=0.0,
                        log=log)

    def reference(seed, batches, **kw):
        return ref.train_readings(conf["model"], traffic["optimizer"],
                                  seed_key(seed), batches, devices, **kw)

    with open(args.out, "a") as out:
        for i, seed in enumerate(args.seeds):
            row = {"cell": args.cell, "seed": seed}
            t = time.perf_counter()
            rec = program(seed)
            row["program_s"] = time.perf_counter() - t
            t = time.perf_counter()
            want = reference(seed, rec["checked_batches"])
            row["reference_s"] = time.perf_counter() - t
            row["sound"] = readings(rec, want)
            row["losses"] = {"program": rec["losses"],
                             "reference": want["losses"]}
            row["worst"] = {"grad": worst(rec["grad"], want["grad"]),
                            "change": worst(rec["change"], want["change"])}
            if i < args.faults:
                batches = rec["checked_batches"]
                ctl = reference(seed, batches, dtype=jnp.bfloat16,
                                precision="default")
                row["control"] = readings(ctl, want)
                row["half_batch"] = readings(
                    reference(seed, batches, rows=half), want)
                if chips > 1:
                    with faults.exchange_left_out():
                        row["exchange_left_out"] = readings(program(seed),
                                                            want)
            out.write(json.dumps(row) + "\n")
            out.flush()
            log(json.dumps({k: row[k] for k in row
                            if k not in ("worst", "losses")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
