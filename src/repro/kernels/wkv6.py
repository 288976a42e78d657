"""RWKV6 WKV Pallas TPU kernel — chunked matmul-form linear recurrence.

Grid: (B·H, S/Q).  The chunk axis is sequential ("arbitrary") so the (K,V)
state lives in a VMEM scratch carried across chunk iterations; the B·H axis
is parallel.  Within a chunk the recurrence is evaluated in matmul form
(MXU-friendly): intra-chunk attention-like matrix A[t,s] plus an
inter-chunk state term — identical math to ``ref.wkv6_chunked_ref``, whose
tests gate this kernel (interpret mode on CPU).

Layout: the wrapper moves heads ahead of the sequence, (B,S,H,K) ->
(B,H,S,K), and squeezes batch and head out of every block, so each block's
last two dims are (Q, K) or (V, K) — whole head dims over a sequence tile
that is a multiple of 8, the tiling the TPU compiler accepts.  The state
is carried transposed, (V, K), so the per-channel chunk decay scales it
along lanes as a row; the chunk-local cumsum of the log decay is a
triangular matmul at full f32 precision.

VMEM budget per grid step (Q=32, K=V=64, fp32):
  blocks r/k/v/w 4·Q·K = 32 KB, state K·V = 16 KB, decay tensor Q·Q·K
  = 256 KB, out Q·V = 8 KB — comfortably under the ~16 MB/core budget.
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


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sf_ref,
                 state):
    qi = pl.program_id(1)

    @pl.when(qi == 0)
    def _init():
        state[...] = s0_ref[...].astype(_F32)

    r = r_ref[...].astype(_F32)                    # (Q,K)
    k = k_ref[...].astype(_F32)
    v = v_ref[...].astype(_F32)                    # (Q,V)
    w = w_ref[...].astype(_F32)                    # log decay ≤ 0
    u = u_ref[...].astype(_F32)                    # (1,K)
    Q, K = r.shape
    strict = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
              > jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    cw = _dot(strict.astype(_F32), w, ((1,), (0,)))   # exclusive cumsum (Q,K)
    cw_end = cw[Q - 1:Q] + w[Q - 1:Q]              # (1,K)
    S0 = state[...]                                # (V,K) = Sᵀ

    # inter-chunk: y_t += (r_t ⊙ e^{cw_t}) · S0
    y = _dot(r * jnp.exp(cw), S0, ((1,), (1,)))    # (Q,V)

    # intra-chunk: A[t,s] = Σ_K r_t k_s e^{cw_t − cw_s − w_s}  (s<t), diag u
    # exponent masked BEFORE exp (≤ 0 where kept): see ref.wkv6_chunked_ref
    r3 = r[:, None, :]
    cw3 = cw[:, None, :]
    dmat = cw3 - (cw + w)[None]                    # (Q,Q,K)
    mask = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q, K), 0)
            > jax.lax.broadcasted_iota(jnp.int32, (Q, Q, K), 1))
    P = jnp.where(mask, jnp.exp(jnp.where(mask, dmat, 0.0)), 0.0)
    A = jnp.sum(r3 * k[None] * P, axis=-1)                     # (Q,Q)
    diag = jnp.sum(r * u * k, axis=-1, keepdims=True)          # (Q,1)
    y = y + _dot(A, v, ((1,), (0,))) + diag * v

    # state update: S = diag(e^{cw_end}) S0 + Σ_s e^{cw_end − cw_s − w_s} k_s v_sᵀ
    carry_k = k * jnp.exp(cw_end - cw - w)                     # (Q,K)
    state[...] = (jnp.exp(cw_end) * S0
                  + _dot(v, carry_k, ((0,), (0,))))            # (V,K)

    y_ref[...] = y.astype(y_ref.dtype)
    sf_ref[...] = state[...].astype(sf_ref.dtype)


def wkv6_pallas(r, k, v, w_log, u, state=None, *, chunk: int = 32,
                interpret: bool) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """r,k,v,w_log: (B,S,H,K); u: (H,K); state: (B,H,K,V) fp32 or None.
    ``interpret`` runs the Pallas interpreter (CPU only)."""
    B, S, H, K = r.shape
    V = v.shape[-1]
    assert S % chunk == 0
    nq = S // chunk
    if state is None:
        state = jnp.zeros((B, H, K, V), _F32)

    rt, kt, vt, wt = (jnp.swapaxes(a, 1, 2) for a in (r, k, v, w_log))

    sq = pl.Squeezed()

    def seq_spec(d):
        return pl.BlockSpec((sq, sq, chunk, d),
                            lambda bh, qi: (bh // H, bh % H, qi, 0))

    u_spec = pl.BlockSpec((sq, 1, K), lambda bh, qi: (bh % H, 0, 0))
    st_spec = pl.BlockSpec((sq, sq, V, K), lambda bh, qi: (bh // H, bh % H, 0, 0))

    y, sf = pl.pallas_call(
        _wkv6_kernel,
        grid=(B * H, nq),
        in_specs=[seq_spec(K), seq_spec(K), seq_spec(V), seq_spec(K),
                  u_spec, st_spec],
        out_specs=[seq_spec(V), st_spec],
        out_shape=[jax.ShapeDtypeStruct((B, H, S, V), v.dtype),
                   jax.ShapeDtypeStruct((B, H, V, K), _F32)],
        scratch_shapes=[_vmem((V, K), _F32)],
        interpret=interpret,
        compiler_params=None if interpret else _tpu_params(),
        name="wkv6",
    )(rt, kt, vt, wt, u.reshape(H, 1, K), jnp.swapaxes(state, 2, 3))
    return jnp.swapaxes(y, 1, 2), jnp.swapaxes(sf, 2, 3)


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


def _tpu_params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))
