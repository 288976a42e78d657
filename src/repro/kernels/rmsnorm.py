"""Fused RMSNorm Pallas kernel (rows × features tiling).

Trivial but ubiquitous: every block norms through this on TPU.  Blocks of
(ROWS, D) stream through VMEM; the mean-square reduction and scale fuse
into one pass (vs. separate reduce + mul HLOs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS = 256


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(ms + eps)
                  * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm_pallas(x, scale, *, eps: float = 1e-5, interpret: bool):
    """RMSNorm over the last axis; ``interpret`` runs the Pallas
    interpreter (CPU only)."""
    orig_shape = x.shape
    D = x.shape[-1]
    xf = x.reshape(-1, D)
    T = xf.shape[0]
    rows = min(ROWS, T)
    pad = (-T) % rows
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(xf.shape[0] // rows,),
        in_specs=[pl.BlockSpec((rows, D), lambda i: (i, 0)),
                  pl.BlockSpec((D,), lambda i: (0,))],
        out_specs=pl.BlockSpec((rows, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xf.shape, x.dtype),
        interpret=interpret,
        name="rmsnorm",
    )(xf, scale)
    return out[:T].reshape(orig_shape)
