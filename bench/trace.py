"""Reduction of a profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
events: for each device the operations of its ``XLA Ops`` line and of its
``Async XLA Ops`` line (the flight of async collectives and copies), under
their HLO names (``fusion.12``, ``all-gather-start.3``), and the host's
``bench.*`` spans.  ``reduce`` works on those events alone, so a test can
feed it a recorded excerpt.

- window: from the start of the first ``bench.data`` span to the end of
  the last ``bench.sync`` span, on the trace's clock.
- busy: the union of a device's ``XLA Ops`` intervals inside the window.
  A loop or call (``while``, ``conditional``, ``call``) is left out: its
  body's operations are events of their own, and its span covers the gaps
  between them.
- collective: the union of the device's collective operations
  (all-gather, all-reduce, reduce-scatter, collective-permute, all-to-all,
  with their async start and done) on both lines; exposed: the part of it
  in which no other ``XLA Ops`` operation runs on that device.
- idle gaps: the stretches of the window in which a device runs nothing,
  named by the innermost ``bench.*`` host span in progress at the gap's
  middle (``host`` where none is).
"""
from __future__ import annotations

import glob
import os
import re

COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all|"
    r"ragged-all-to-all|collective-broadcast)(-start|-done)?([.\-_]|$)")
OP_LINES = {"XLA Ops": "ops", "Async XLA Ops": "async"}
CONTAINERS = ("while", "conditional", "call")    # their bodies are events too
HOST_SPANS = ("bench.data", "bench.dispatch", "bench.sync")


def xplane_file(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def hlo_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> dict:
    """``{"devices": {plane: {"ops": [[name, start_ns, end_ns], ...],
    "async": [...]}}, "host": [[name, start_ns, end_ns], ...]}`` from an
    ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {OP_LINES[line.name]: sorted(
                ([hlo_name(e.name), e.start_ns, e.start_ns + e.duration_ns]
                 for e in line.events), key=lambda e: e[1])
                for line in plane.lines if line.name in OP_LINES}
            if lines.get("ops"):
                devices[plane.name] = {"ops": lines["ops"],
                                       "async": lines.get("async", [])}
        elif plane.name.startswith("/host:"):
            host += [[e.name, e.start_ns, e.start_ns + e.duration_ns]
                     for line in plane.lines for e in line.events
                     if e.name in HOST_SPANS]
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def _clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _minus(a, b):
    """Intervals of union ``a`` not covered by union ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.match(name))


def reduce(events: dict, top: int = 10) -> dict:
    """Per-device busy, collective and exposed seconds over the window,
    the window's length and step count, and the ``breakdown``."""
    host = events["host"]
    data = [h for h in host if h[0] == "bench.data"]
    sync = [h for h in host if h[0] == "bench.sync"]
    if not data or not sync or not events["devices"]:
        return {}
    lo, hi = data[0][1], sync[-1][2]
    per, op_time, gaps = {}, {}, []
    for dev, lines in events["devices"].items():
        ops = [[n, s, e] for n, s, e in lines["ops"]
               if e > lo and s < hi and not n.startswith(CONTAINERS)]
        busy = _union(_clip([[s, e] for _, s, e in ops], lo, hi))
        coll = _union(_clip([[s, e] for n, s, e in ops + lines["async"]
                             if is_collective(n)], lo, hi))
        other = _union(_clip([[s, e] for n, s, e in ops
                              if not is_collective(n)], lo, hi))
        per[dev] = {"busy_s": _length(busy) * 1e-9,
                    "collective_s": _length(coll) * 1e-9,
                    "exposed_s": _length(_minus(coll, other)) * 1e-9}
        for n, s, e in ops:
            op_time[n] = op_time.get(n, 0.0) + (min(e, hi) - max(s, lo))
        gaps += _minus([[lo, hi]], busy)
    n_dev = len(per)
    named = sorted(([_host_at(host, (s + e) / 2), (e - s) * 1e-9]
                    for s, e in gaps), key=lambda g: -g[1])[:top]
    ops_top = sorted(([n, t * 1e-9 / n_dev] for n, t in op_time.items()),
                     key=lambda o: -o[1])[:top]
    return {"window_s": (hi - lo) * 1e-9,
            "steps": sum(1 for h in host if h[0] == "bench.dispatch"
                         and lo <= h[1] < hi),
            "devices": per,
            "breakdown": {"device_ops": ops_top, "idle_gaps": named}}


def _host_at(host, t) -> str:
    inner = None
    for name, s, e in host:
        if s <= t < e and (inner is None or s >= inner[1]):
            inner = (name, s)
    return inner[0] if inner else "host"


def mean(summary: dict, key: str) -> float:
    devs = summary["devices"].values()
    return sum(d[key] for d in devs) / len(devs)
