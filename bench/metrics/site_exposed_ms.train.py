"""Device milliseconds a step in the collectives under any ``site:``
scope while no other operation runs on that chip, the rule of
``collective_exposed_ms.train`` kept to Lagom's sites, averaged over the
cell's chips (``bench/scopes.py``); nothing where no site's collective
runs."""
from bench import scopes as S


def read(ctx):
    t = S.read(ctx)
    if not t or not t["steps"]:
        return None
    devs = t["devices"].values()
    if not any(d["sites"] for d in devs):
        return None
    return 1e3 * sum(d["site_exposed_s"] for d in devs) / len(devs) \
        / t["steps"]
