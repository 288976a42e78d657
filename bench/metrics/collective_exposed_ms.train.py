"""Device milliseconds a step in collective operations while no other
operation runs on that chip, averaged over the cell's chips
(``bench/trace.py``); nothing where the step runs no collective."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["steps"]:
        return None
    devs = t["devices"].values()
    if not sum(d["collective_s"] for d in devs):
        return None
    return 1e3 * sum(d["exposed_s"] for d in devs) / len(devs) / t["steps"]
