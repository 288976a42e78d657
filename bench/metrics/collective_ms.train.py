"""Device milliseconds a step in collective operations, from the trace,
averaged over the cell's chips (``bench/trace.py``); nothing where the
step runs none."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["steps"]:
        return None
    devs = t["devices"].values()
    ms = 1e3 * sum(d["collective_s"] for d in devs) / len(devs) / t["steps"]
    return ms if ms > 0 else None
