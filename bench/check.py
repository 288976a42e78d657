"""The comparison that decides ``correct`` for a training cell.

The program and the plain reference each report, for the first checked
steps of one seed: the loss of each step, the norm of each leaf's first
gradient as the optimizer gets it (clipped), and the norm of each leaf's
change over the checked steps.  A stacked leaf (leading layer axis, under
``trunk/``) counts as one leaf a layer.

Numbers compared, each against its own limit:

- ``loss_gap``: the largest |program loss - reference loss| over the
  checked steps, in nats.
- ``grad_gap``: over the leaves, the largest gap between the program's
  gradient norm and the reference's, as a share of the reference's norm of
  that leaf or of the median leaf, whichever is larger.
- ``change_gap``: the same for the change of the parameters, over the
  leaves whose reference gradient is at least a thousandth of the median
  leaf's (a leaf with a gradient that is nought to rounding moves under
  Adam by round-off alone).
- ``window_nonfinite``: losses of the measured window that are not finite.
"""
from __future__ import annotations

import math
import statistics

import numpy as np

GRAD_FLOOR = 1e-3          # of the median leaf's reference gradient norm


def leaf_norms(tree):
    """Pytree of float32 norms: one a layer for leaves under ``trunk``
    (stacked on a leading layer axis), one for every other leaf.  Runs
    under ``jax.jit`` on the program's or the reference's arrays."""
    import jax
    import jax.numpy as jnp

    def one(path, x):
        x = x.astype(jnp.float32)
        stacked = _name(path).startswith("trunk/")
        axes = tuple(range(1, x.ndim)) if stacked else None
        return jnp.sqrt(jnp.sum(jnp.square(x), axis=axes))

    return jax.tree_util.tree_map_with_path(one, tree)


def diff_norms(a, b):
    """``leaf_norms`` of ``a - b``, leaf by leaf (under ``jax.jit``)."""
    import jax
    import jax.numpy as jnp

    return leaf_norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))


def _name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def flatten(norms) -> dict:
    """``{leaf name: norm}`` from a pytree of norms on the host; a stacked
    leaf's layers are ``name#i``."""
    import jax

    out = {}
    for path, v in jax.tree_util.tree_flatten_with_path(norms)[0]:
        v = np.asarray(v, np.float64)
        if v.ndim == 0:
            out[_name(path)] = float(v)
        else:
            for i, x in enumerate(v):
                out[f"{_name(path)}#{i}"] = float(x)
    return out


def _gaps(prog: dict, ref: dict, names) -> float:
    if set(prog) != set(ref):
        return math.inf
    med = statistics.median(ref.values())
    worst = 0.0
    for n in names:
        gap = abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30)
        worst = max(worst, gap if math.isfinite(gap) else math.inf)
    return worst


def readings(prog: dict, ref: dict) -> dict:
    """The compared numbers from two sets of readings, each
    ``{"losses": [...], "grad": {leaf: norm}, "change": {leaf: norm}}``;
    ``prog`` also has ``window_losses``."""
    if len(prog["losses"]) != len(ref["losses"]):
        loss_gap = math.inf
    else:
        loss_gap = max((abs(a - b) for a, b in zip(prog["losses"],
                                                    ref["losses"])),
                       default=math.inf)
        if not math.isfinite(loss_gap):
            loss_gap = math.inf
    med = statistics.median(ref["grad"].values())
    moved = [n for n, g in ref["grad"].items() if g >= GRAD_FLOOR * med]
    return {
        "loss_gap": loss_gap,
        "grad_gap": _gaps(prog["grad"], ref["grad"], ref["grad"]),
        "change_gap": _gaps(prog["change"], ref["change"], moved),
        "window_nonfinite": float(sum(
            not math.isfinite(x) for x in prog.get("window_losses", []))),
    }


def judge(numbers: dict, limits: dict) -> tuple:
    """``(correct, {name: {"value": v, "limit": l}})``: correct when every
    number is at most its limit."""
    shown = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    ok = all(numbers[k] <= limits[k] for k in limits)
    return ok, shown
