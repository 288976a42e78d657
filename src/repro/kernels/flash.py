"""Causal flash-attention Pallas TPU kernel (GQA-aware), forward pass.

Grid: (B·Hq, Sq/QB, Sk/KB) with the KV axis sequential ("arbitrary") —
running max / denominator / accumulator live in VMEM scratch across KV
block iterations; (batch·head, q-block) axes are parallel.  Used for
inference prefill (the training path keeps the pure-JAX two-axis blockwise
attention in models/layers.py, which autodiffs); validated in interpret
mode against that reference.

Layout: the wrapper moves heads ahead of the sequence, (B,S,H,h) ->
(B,H,S,h), and the kernel blocks squeeze the batch and head axes, so each
block's last two dims are (QB|KB, h): a sequence tile that is a multiple
of 8 and the whole head dim — the tiling the TPU compiler accepts for any
head size (80 included).

VMEM per step (QB=KB=256, h=128, fp32): q/k/v blocks 3·256·128·4 = 384 KB,
acc 128 KB, m/l 2 KB — MXU-aligned (q·kᵀ is 256×128·128ᵀ).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *,
                  scale: float, qb: int, kb: int, causal: bool):
    ki = pl.program_id(2)
    qi = pl.program_id(1)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    # skip fully-masked blocks (k start beyond q end)
    run = (not causal) or (ki * kb <= qi * qb + qb - 1)

    @pl.when(run)
    def _step():
        q = q_ref[...].astype(jnp.float32)               # (qb,h)
        k = k_ref[...].astype(jnp.float32)               # (kb,h)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * qb + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 0)
            kpos = ki * kb + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev, l_prev = m_s[...], l_s[...]               # (qb,1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_s[...] = l_prev * corr + p.sum(axis=-1, keepdims=True)
        acc_s[...] = acc_s[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_s[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[...] = (acc_s[...] / jnp.maximum(l_s[...], 1e-20)
                      ).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, q_block: int = 256,
                    kv_block: int = 256, interpret: bool):
    """q (B,Sq,Hq,h); k,v (B,Sk,Hkv,h) with Hq % Hkv == 0 (GQA).
    ``interpret`` runs the Pallas interpreter (CPU only)."""
    B, Sq, Hq, h = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    qb = min(q_block, Sq)
    kb = min(kv_block, Sk)
    assert Sq % qb == 0 and Sk % kb == 0, "pad sequences to block multiples"
    scale = 1.0 / math.sqrt(h)

    qt, kt, vt = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))   # (B,H,S,h)
    sq = pl.Squeezed()
    q_spec = pl.BlockSpec((sq, sq, qb, h),
                          lambda b, qi, ki: (b // Hq, b % Hq, qi, 0))
    kv_spec = pl.BlockSpec((sq, sq, kb, h),
                           lambda b, qi, ki: (b // Hq, (b % Hq) // G, ki, 0))
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, qb=qb, kb=kb,
                          causal=causal),
        grid=(B * Hq, Sq // qb, Sk // kb),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, h), q.dtype),
        scratch_shapes=[_vmem((qb, 1), jnp.float32),
                        _vmem((qb, 1), jnp.float32),
                        _vmem((qb, h), jnp.float32)],
        interpret=interpret,
        compiler_params=None if interpret else _tpu_params(),
        name="flash_attention",
    )(qt, kt, vt)
    return jnp.swapaxes(out, 1, 2)


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


def _tpu_params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
