"""Activation sharding constraints.

GSPMD propagates input shardings, but propagation through scans, gathers
and reshapes is best-effort — production frameworks pin activations at
layer boundaries.  The launcher installs the mesh axes via ``use_axes``;
when no context is installed every helper is a no-op (single-device smoke
tests never see a mesh).
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Tuple

import jax
from jax import lax
from jax.sharding import PartitionSpec as P

_AXES: contextvars.ContextVar = contextvars.ContextVar("repro_mesh_axes",
                                                        default=None)


@contextlib.contextmanager
def use_axes(dp_axes: Tuple[str, ...], tp_axis: str, *, seq_shard: bool = False,
             tp_size: int = 16):
    """``seq_shard=True`` = sequence parallelism: (B,S,D) activations are
    additionally sharded over the model axis on S at layer boundaries, so
    per-layer saved residuals shrink by the TP degree (required for
    d_model≥8k training shapes; GSPMD inserts the AG/RS around attention)."""
    token = _AXES.set({"dp": tuple(dp_axes), "tp": tp_axis,
                       "seq_shard": seq_shard, "tp_size": tp_size})
    try:
        yield
    finally:
        _AXES.reset(token)


def axes():
    return _AXES.get()


def _dp(a):
    dp = a["dp"]
    return dp if len(dp) > 1 else dp[0]


def _constrain(x, spec: P):
    """Pin ``x`` to ``spec`` on the ambient mesh; a no-op only when no mesh
    is set (``jax.set_mesh``).  A spec the mesh rejects raises."""
    if jax.sharding.get_abstract_mesh().empty:
        return x
    return lax.with_sharding_constraint(x, spec)


def btd(x):
    """(B, S, D) activations: batch over data axes (+ seq over model when
    sequence parallelism is on)."""
    a = axes()
    if a is None or x.ndim != 3:
        return x
    s_ax = (a["tp"] if a.get("seq_shard")
            and x.shape[1] % a.get("tp_size", 16) == 0 else None)
    return _constrain(x, P(_dp(a), s_ax, None))


def btf(x):
    """(B, S, F) ff activations: batch over data, features over model."""
    a = axes()
    if a is None or x.ndim != 3:
        return x
    return _constrain(x, P(_dp(a), None, a["tp"]))


def ecd(x):
    """(E, cap, D) MoE expert buffers: experts over model (the EP a2a) and
    capacity slots over the data axes (tokens arrive data-sharded, so this
    keeps the buffer footprint per chip constant as TP degree shrinks)."""
    a = axes()
    if a is None or x.ndim != 3:
        return x
    return _constrain(x, P(a["tp"], _dp(a), None))


def logits(x):
    """(B, c, V) loss logits chunk: batch over data, vocab over model."""
    a = axes()
    if a is None or x.ndim != 3:
        return x
    return _constrain(x, P(_dp(a), None, a["tp"]))
