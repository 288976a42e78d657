"""Faults planted under the timed path, to show that the check catches
them (``tests/bench``) and to read what each one scores on the chip
(``bench/tools/readings.py``).  Each is a context manager that swaps one
function of the program for a broken one and puts it back on exit.  The
benchmark's own runs use none of them.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _swapped(module, name, fn):
    orig = getattr(module, name)
    setattr(module, name, fn(orig))
    try:
        yield
    finally:
        setattr(module, name, orig)


def state_unchanged():
    """The step returns the parameters and optimizer state it was given."""
    from repro.optim import adamw

    def broken(orig):
        def apply_updates(params, grads, state, cfg, lr_scale=1.0):
            _, _, metrics = orig(params, grads, state, cfg, lr_scale)
            return params, state, metrics
        return apply_updates

    return _swapped(adamw, "apply_updates", broken)


def half_batch():
    """The loss, and so the gradients, see only the first half of the
    batch: the mean is taken over the rest."""
    import jax

    from repro.models import model as M

    def broken(orig):
        def loss_and_metrics(cfg, p, batch, **kw):
            half = jax.tree.map(lambda a: a[: a.shape[0] // 2], batch)
            return orig(cfg, p, half, **kw)
        return loss_and_metrics

    return _swapped(M, "loss_and_metrics", broken)


def exchange_left_out():
    """The tensor-parallel MLP's reduce-scatter keeps each chip's own rows
    of its partial product and sums nothing across chips."""
    from jax import lax, shard_map

    from repro.models import dense

    def broken(orig):
        def mm_reduce_scatter(x, w, mesh, *, axis="model", x_spec, w_spec,
                              out_spec, num_chunks=None, site=None):
            def local(xl, wl):
                y = xl @ wl
                rows = y.shape[-2] // lax.axis_size(axis)
                return lax.dynamic_slice_in_dim(
                    y, lax.axis_index(axis) * rows, rows, axis=y.ndim - 2)
            return shard_map(local, mesh=mesh, in_specs=(x_spec, w_spec),
                             out_specs=out_spec)(x, w)
        return mm_reduce_scatter

    return _swapped(dense, "mm_reduce_scatter", broken)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "exchange_left_out": exchange_left_out}
