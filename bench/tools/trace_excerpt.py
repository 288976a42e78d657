#!/usr/bin/env python3
"""Show what a traced run's ``.xplane.pb`` holds, and cut an excerpt of it.

    python3 bench/tools/trace_excerpt.py <trace dir> [<out.json> [<steps>]]

Prints every plane with its lines, their event counts and a few event
names, so the reduction in ``bench/trace.py`` can be checked against a
real trace by eye.  With ``out.json`` it writes the events that
``trace.load`` keeps, cut to the first ``steps`` steps of the window
(default 1): the recorded data the tests of the reduction run on.
"""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv) -> int:
    sys.path[:0] = [ROOT]
    from jax.profiler import ProfileData

    from bench import trace as TR

    path = TR.xplane_file(argv[0])
    for plane in ProfileData.from_file(path).planes:
        print(f"plane {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            names = sorted({e.name for e in events})
            print(f"  line {line.name!r}: {len(events)} events, "
                  f"{len(names)} names, e.g. {names[:12]}")
    if len(argv) > 1:
        steps = int(argv[2]) if len(argv) > 2 else 1
        ev = TR.load(path)
        data = [h for h in ev["host"] if h[0] == "bench.data"]
        sync = [h for h in ev["host"] if h[0] == "bench.sync"]
        lo, hi = data[0][1], sync[steps - 1][2]
        cut = {"devices": {d: {k: [o for o in ops if o[2] > lo and o[1] < hi]
                               for k, ops in lines.items()}
                           for d, lines in ev["devices"].items()},
               "host": [h for h in ev["host"] if h[1] >= lo and h[2] <= hi]}
        with open(argv[1], "w") as f:
            json.dump(cut, f)
        print(f"excerpt of {steps} steps -> {argv[1]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
