"""GPipe-style pipeline parallelism over a ``stage`` mesh axis.

Each device holds one stage's parameters; microbatches flow through the
ring via ``lax.ppermute`` (TPU: neighbor ICI transfers).  Fill+drain
schedule: S + M − 1 ticks for S stages × M microbatches.  The inter-stage
permutes are exactly the "permute" CommOps the Lagom tuner prices
(core.extract kind="pp"), overlapping each tick's transfer with the next
tick's stage compute.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.parallel.collectives import _warn_unchunked, runtime_for


def _chunked_ppermute(x, axis: str, perm, *, num_chunks: int, site: str):
    """Inter-stage activation transfer, optionally decomposed into
    ``num_chunks`` feature-dim ppermutes (tuned ``p2p`` knobs) so the next
    tick's compute can start on early chunks."""
    if num_chunks <= 1 or x.shape[-1] % num_chunks:
        if num_chunks > 1:
            _warn_unchunked(site, num_chunks,
                            f"the trailing activation dim ({x.shape[-1]})")
        return lax.ppermute(x, axis, perm)
    blocks = jnp.stack(jnp.split(x, num_chunks, axis=-1))
    ys = lax.map(lambda b: lax.ppermute(b, axis, perm), blocks)
    return jnp.concatenate(list(ys), axis=-1)


def _pipeline_local(params, x_mb, *, fn: Callable, axis: str, microbatches: int,
                    num_chunks: int = 1, site: str = "p2p"):
    """Per-device body.  params: this stage's params (leading stage dim of 1
    squeezed by shard_map).  x_mb: (M, mb, ...) microbatched input
    (replicated).  Returns (M, mb, ...) outputs (only the last stage's
    contribution is non-zero; caller psums over the stage axis)."""
    n = lax.axis_size(axis)
    stage = lax.axis_index(axis)
    M = microbatches
    params = jax.tree.map(lambda a: a[0], params)       # drop stage dim

    fwd = [(i, (i + 1) % n) for i in range(n)]          # stage i -> i+1

    def tick(t, carry):
        buf, ys = carry                                  # buf: (mb, ...) current input
        # stage 0 ingests microbatch t (when t < M); others use the permuted buf
        mb_idx = jnp.clip(t, 0, M - 1)
        inp = jnp.where(stage == 0,
                        x_mb[mb_idx].astype(buf.dtype), buf)
        out = fn(params, inp)
        # last stage emits microbatch t-(n-1) when valid
        emit_idx = jnp.clip(t - (n - 1), 0, M - 1)
        valid = (stage == n - 1) & (t >= n - 1) & (t - (n - 1) < M)
        ys = lax.dynamic_update_slice_in_dim(
            ys,
            jnp.where(valid, out, ys[emit_idx])[None],
            emit_idx, axis=0)
        buf = _chunked_ppermute(out, axis, fwd, num_chunks=num_chunks,
                                site=site)
        return (buf, ys)

    mb_shape = x_mb.shape[1:]
    buf0 = jnp.zeros(mb_shape, x_mb.dtype)
    out_shape = jax.eval_shape(fn, params, jax.ShapeDtypeStruct(mb_shape, x_mb.dtype))
    ys0 = jnp.zeros((M,) + out_shape.shape, out_shape.dtype)
    # buffers become stage-varying inside the loop (params vary)
    buf0 = lax.pcast(buf0, (axis,), to="varying")
    ys0 = lax.pcast(ys0, (axis,), to="varying")
    _, ys = lax.fori_loop(0, n + M - 1, tick, (buf0, ys0))
    # only the last stage's ys are real; zero elsewhere then psum outside
    ys = jnp.where(stage == n - 1, ys, jnp.zeros_like(ys))
    return lax.psum(ys, axis)


def pipeline_apply(fn: Callable, stage_params, x, *, mesh: Mesh,
                   axis: str = "stage", microbatches: int,
                   site: Optional[str] = None):
    """Run ``fn(stage_params_i, x)`` through an S-stage pipeline.

    stage_params: pytree with a leading stage dim (sharded over ``axis``).
    x: (M·mb, ...) global batch; reshaped to M microbatches.
    Returns (M·mb, ...) outputs, equivalent to applying the stages
    sequentially.  ``site`` addresses the inter-stage transfers in the
    active tuned plan (default the ``p2p`` site class): tuned chunk counts
    decompose each tick's ppermute into partial feature-dim transfers.
    """
    M = microbatches
    B = x.shape[0]
    assert B % M == 0
    x_mb = x.reshape((M, B // M) + x.shape[1:])
    p_specs = jax.tree.map(lambda a: P(axis, *([None] * (a.ndim - 1))),
                           stage_params)
    site = site or "p2p"
    rt = runtime_for(site, "p2p")
    local = partial(_pipeline_local, fn=fn, axis=axis, microbatches=M,
                    num_chunks=rt.num_chunks, site=site)
    out = shard_map(local, mesh=mesh,
                    in_specs=(p_specs, P()), out_specs=P())(stage_params, x_mb)
    return out.reshape((B,) + out.shape[2:])
