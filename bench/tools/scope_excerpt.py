#!/usr/bin/env python3
"""Cut an excerpt of a traced run that ``bench/scopes.py`` can read.

    python3 bench/tools/scope_excerpt.py <trace dir> <out.json> [<steps>
        [<devices>]]

Writes the events that ``trace.load`` keeps, cut to the first ``steps``
steps of the window (default 1) and to ``devices`` (positions in the
sorted device planes, comma-separated; default all), with ``op_names``,
the ``op_name`` of each of their operations, and ``collectives``, those
of them that are collectives, from the compiled step's text that the
traced run's per-layer metrics left beside the trace
(``<trace dir>/step.hlo.txt``).  The recorded data the tests of
``scopes.scoped`` run on.
"""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv) -> int:
    sys.path[:0] = [ROOT]
    from bench import scopes as S
    from bench import trace as TR

    steps = int(argv[2]) if len(argv) > 2 else 1
    ev = TR.load(TR.xplane_file(argv[0]))
    data = [h for h in ev["host"] if h[0] == "bench.data"]
    sync = [h for h in ev["host"] if h[0] == "bench.sync"]
    lo, hi = data[0][1], sync[steps - 1][2]
    devs = sorted(ev["devices"])
    if len(argv) > 3:
        devs = [devs[int(i)] for i in argv[3].split(",")]
    cut = {"devices": {d: {k: [[n, int(s), int(e)] for n, s, e in ops
                               if e > lo and s < hi]
                           for k, ops in ev["devices"][d].items()}
                       for d in devs},
           "host": [h for h in ev["host"] if h[1] >= lo and h[2] <= hi]}
    with open(os.path.join(argv[0], "step.hlo.txt")) as f:
        text = f.read()
    names = S.op_names(text)
    kept = {o[0] for lines in cut["devices"].values()
            for ops in lines.values() for o in ops}
    cut["op_names"] = {n: names[n] for n in sorted(kept & set(names))}
    cut["collectives"] = sorted(kept & S.collective_names(text))
    with open(argv[1], "w") as f:
        json.dump(cut, f, separators=(",", ":"))
    print(f"excerpt of {steps} steps, {len(devs)} devices -> {argv[1]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
