"""Share of a chip's busy time in operations under neither a component
scope nor a ``site:`` scope, in percent, averaged over the cell's chips
(``bench/scopes.py``)."""
from bench import scopes as S


def read(ctx):
    t = S.read(ctx)
    if not t:
        return None
    devs = t["devices"].values()
    return 100.0 * sum(d["unscoped_s"] / d["busy_s"] for d in devs
                       if d["busy_s"]) / len(devs)
