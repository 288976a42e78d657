"""Share of the traced window in which a chip runs no operation, in
percent, averaged over the cell's chips (``bench/trace.py``)."""


def read(ctx):
    t = ctx["trace"]
    if not t:
        return None
    devs = t["devices"].values()
    return 100.0 * sum(1.0 - d["busy_s"] / t["window_s"] for d in devs) \
        / len(devs)
