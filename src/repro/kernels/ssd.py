"""Mamba2 SSD Pallas TPU kernel — chunked scalar-identity state space.

Grid: (B·H, S/Q); chunk axis sequential with the (P,N) state in VMEM
scratch; B·H parallel.  Matmul-form block decomposition (Mamba-2 paper):
intra-chunk C·Bᵀ ⊙ decay-mask GEMM + inter-chunk state term — identical
math to ``ref.ssd_chunked_ref``.

Layout: the wrapper moves heads ahead of the sequence, (B,S,H,·) ->
(B,H,S,·), and squeezes batch and head out of every block, so the x/B/C
blocks end in (Q, P|N) — a sequence tile that is a multiple of 8 over the
whole head dim.  The per-head step sizes arrive as one (1, Q) row per
chunk (an array of shape (B,H,S/Q,1,Q)); the per-head scalars A and D as
(1, 1) blocks.  The chunk-local cumsum of dt·A, and its column form, are
triangular matmuls at full f32 precision, so the kernel needs no
transpose.

VMEM per grid step (Q=64, P=64, N=64 fp32): x/B/C blocks 3·Q·max(P,N)
= 48 KB, state P·N = 16 KB, L-mask Q·Q = 16 KB — minimal; the two GEMMs
(Q×N·Nᵀ and Q×Q @ Q×P) land on the MXU.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_F32 = jnp.float32


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=_F32)


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, s0_ref,
                y_ref, sf_ref, state):
    qi = pl.program_id(1)

    @pl.when(qi == 0)
    def _init():
        state[...] = s0_ref[...].astype(_F32)

    x = x_ref[...].astype(_F32)                      # (Q,P)
    dt_row = dt_ref[...].astype(_F32)                # (1,Q)
    A = a_ref[...]                                   # (1,1) per head
    Bm = b_ref[...].astype(_F32)                     # (Q,N)
    Cm = c_ref[...].astype(_F32)                     # (Q,N)
    D = d_ref[...]                                   # (1,1)
    Q = x.shape[0]

    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    incl = row >= col
    a_row = dt_row * A                               # (1,Q) log decay ≤ 0
    cum_row = _dot(a_row, incl.astype(_F32), ((1,), (1,)))    # (1,Q) inclusive
    cum = _dot(incl.astype(_F32), a_row, ((1,), (1,)))        # (Q,1)
    dt = _dot((row == col).astype(_F32), dt_row, ((1,), (1,)))  # (Q,1)
    h0 = state[...]                                  # (P,N)

    # inter-chunk: y_t += (C_t e^{cum_t}) · h0ᵀ
    y = _dot(Cm * jnp.exp(cum), h0, ((1,), (1,)))    # (Q,P)

    # intra-chunk: G[t,s] = (C_t·B_s) e^{cum_t − cum_s} dt_s   (s ≤ t)
    # exponent masked BEFORE exp: see ref.ssd_chunked_ref
    L = jnp.where(incl, jnp.exp(jnp.where(incl, cum - cum_row, 0.0)), 0.0)
    G = _dot(Cm, Bm, ((1,), (1,))) * L * dt_row
    y = y + _dot(G, x, ((1,), (0,))) + D * x

    # state: h = e^{cum_end} h0 + Σ_s e^{cum_end − cum_s} dt_s x_s ⊗ B_s
    cum_end = cum[Q - 1:Q]                           # (1,1)
    wgt = jnp.exp(cum_end - cum) * dt                # (Q,1)
    state[...] = jnp.exp(cum_end) * h0 + _dot(x * wgt, Bm, ((0,), (0,)))

    y_ref[...] = y.astype(y_ref.dtype)
    sf_ref[...] = state[...].astype(sf_ref.dtype)


def ssd_pallas(x, dt, A, Bm, Cm, D, state=None, *, chunk: int = 64,
               interpret: bool) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B,S,H,P); dt (B,S,H); A,D (H,); Bm,Cm (B,S,H,N) head-expanded.
    ``interpret`` runs the Pallas interpreter (CPU only)."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    assert S % chunk == 0
    nq = S // chunk
    if state is None:
        state = jnp.zeros((B, H, P, N), _F32)

    xt, bt, ct = (jnp.swapaxes(a, 1, 2) for a in (x, Bm, Cm))   # (B,H,S,·)
    dt_rows = jnp.swapaxes(dt, 1, 2).reshape(B, H, nq, 1, chunk)
    per_head = (lambda a: a.astype(_F32).reshape(H, 1, 1))

    sq = pl.Squeezed()

    def seq_spec(d):
        return pl.BlockSpec((sq, sq, chunk, d),
                            lambda bh, qi: (bh // H, bh % H, qi, 0))

    dt_spec = pl.BlockSpec((sq, sq, sq, 1, chunk),
                           lambda bh, qi: (bh // H, bh % H, qi, 0, 0))
    h_spec = pl.BlockSpec((sq, 1, 1), lambda bh, qi: (bh % H, 0, 0))
    st_spec = pl.BlockSpec((sq, sq, P, N), lambda bh, qi: (bh // H, bh % H, 0, 0))

    y, sf = pl.pallas_call(
        _ssd_kernel,
        grid=(B * H, nq),
        in_specs=[seq_spec(P), dt_spec, h_spec, seq_spec(N), seq_spec(N),
                  h_spec, st_spec],
        out_specs=[seq_spec(P), st_spec],
        out_shape=[jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
                   jax.ShapeDtypeStruct((B, H, P, N), _F32)],
        scratch_shapes=[_vmem((P, N), _F32)],
        interpret=interpret,
        compiler_params=None if interpret else _tpu_params(),
        name="ssd",
    )(xt, dt_rows, per_head(A), bt, ct, per_head(D), state)
    return jnp.swapaxes(y, 1, 2), sf


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


def _tpu_params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))
