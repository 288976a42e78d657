"""Shared neural-net building blocks (pure JAX, params = nested dicts).

Covers: linear/norm primitives, RoPE (full / partial / M-RoPE), ALiBi,
learned positions, GQA attention with full-causal / sliding-window / cross
masks, memory-efficient blockwise (flash-style) attention, MLA
(DeepSeek-V2 latent attention) with compressed KV cache, SwiGLU / GELU
MLPs, and capacity-based mixture-of-experts with shared experts.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.parallel import constraints as CT

Params = Dict[str, Any]

# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------

def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


def init_linear(key, d_in: int, d_out: int, bias: bool = False, *,
                scale: float | None = None, dtype=jnp.float32) -> Params:
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    p = {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def init_norm(d: int, kind: str, dtype=jnp.float32) -> Params:
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def norm(p: Params, x: jnp.ndarray, kind: str, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * lax.rsqrt(var + eps)
        y = y * p["scale"] + p["bias"]
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * lax.rsqrt(ms + eps) * p["scale"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# positions
# ---------------------------------------------------------------------------

def rope_angles(positions: jnp.ndarray, rot_dim: int, theta: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """positions (...,) int32 -> cos/sin (..., rot_dim//2)."""
    half = rot_dim // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def _rotate(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x (..., rot_dim) with cos/sin (..., rot_dim//2); pair-split convention."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def apply_rope(q: jnp.ndarray, k: jnp.ndarray, positions: jnp.ndarray, *,
               head_dim: int, fraction: float = 1.0, theta: float = 10_000.0,
               mrope_sections: Tuple[int, ...] = ()) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """q (B,S,H,hd), k (B,S,KVH,hd); positions (B,S) int32 or (3,B,S) for M-RoPE."""
    rot = int(head_dim * fraction)
    rot -= rot % 2
    if mrope_sections:
        rot = 2 * sum(mrope_sections)
        cos_t, sin_t = rope_angles(positions, rot, theta)  # (3,B,S,rot/2)
        splits = [sum(mrope_sections[:i + 1]) for i in range(len(mrope_sections) - 1)]
        cos = jnp.concatenate([c[i] for i, c in enumerate(jnp.split(cos_t, splits, axis=-1))], axis=-1)
        sin = jnp.concatenate([s[i] for i, s in enumerate(jnp.split(sin_t, splits, axis=-1))], axis=-1)
    else:
        cos, sin = rope_angles(positions, rot, theta)      # (B,S,rot/2)
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]       # broadcast over heads

    def rope_one(x):
        xr, xp = x[..., :rot], x[..., rot:]
        xr = _rotate(xr.astype(jnp.float32), cos, sin).astype(x.dtype)
        return jnp.concatenate([xr, xp], axis=-1) if xp.shape[-1] else xr

    return rope_one(q), rope_one(k)


def alibi_slopes(num_heads: int) -> jnp.ndarray:
    exp = math.floor(math.log2(num_heads))
    base = 2.0 ** (-8.0 / (2 ** exp))
    slopes = [base ** (i + 1) for i in range(2 ** exp)]
    if len(slopes) < num_heads:  # non-power-of-two heads
        extra_base = 2.0 ** (-4.0 / (2 ** exp))
        slopes += [extra_base ** (2 * i + 1) for i in range(num_heads - len(slopes))]
    return jnp.array(slopes, jnp.float32)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

NEG_INF = -1e30
Q_BLOCK = 512      # query-axis chunk of the two-axis blockwise attention


def init_attention(key, cfg, d_in: int | None = None, dtype=jnp.float32) -> Params:
    d = d_in or cfg.d_model
    ks = jax.random.split(key, 6)
    p = {
        "q": init_linear(ks[0], d, cfg.q_dim, cfg.attn_bias, dtype=dtype),
        "k": init_linear(ks[1], d, cfg.kv_dim, cfg.attn_bias, dtype=dtype),
        "v": init_linear(ks[2], d, cfg.kv_dim, cfg.attn_bias, dtype=dtype),
        "o": init_linear(ks[3], cfg.q_dim, cfg.d_model, cfg.attn_bias, dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_norm(cfg.head_dim, "rmsnorm", dtype)
        p["k_norm"] = init_norm(cfg.head_dim, "rmsnorm", dtype)
    return p


def _gqa_scores_to_out(q, k, v, bias, scale):
    """Dense attention.  q (B,Sq,N,G,h); k,v (B,Sk,N,h); bias broadcastable to
    (B,N,G,Sq,Sk) additive mask (float32)."""
    logits = jnp.einsum("bqngh,bsnh->bngqs", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    logits = logits + bias
    w = jax.nn.softmax(logits, axis=-1)
    # accumulate in f32, return the QUERY dtype (the cache may be narrower,
    # e.g. fp8 KV caches for memory-bound decode)
    out = jnp.einsum("bngqs,bsnh->bqngh", w, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _kv_scan_attention(q, k, v, bias_fn, scale, kv_block: int, q0):
    """Online-softmax over KV blocks for one query chunk.

    q (B,Qb,N,G,h); k,v (B,Sk,N,h); bias_fn(q0, qlen, kv_start, kv_len) gives
    the additive mask block (broadcastable to (B,N,G,Qb,kv_len))."""
    B, Qb, N, G, h = q.shape
    Sk = k.shape[1]
    nblk = (Sk + kv_block - 1) // kv_block
    pad = nblk * kv_block - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(B, nblk, kv_block, N, h)
    vb = v.reshape(B, nblk, kv_block, N, h)
    qf = q.astype(jnp.float32)

    def step(carry, blk):
        m, l, acc = carry
        kblk, vblk, i = blk
        logits = jnp.einsum("bqngh,bsnh->bngqs", qf, kblk.astype(jnp.float32)) * scale
        mask = bias_fn(q0, Qb, i * kv_block, kv_block)
        if pad:  # mask out padded tail slots of the last block
            slot = i * kv_block + jnp.arange(kv_block)
            mask = mask + jnp.where(slot < Sk, 0.0, NEG_INF)
        logits = logits + mask
        m_new = jnp.maximum(m, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bngqs,bsnh->bngqh", p, vblk.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    carry = (jnp.full((B, N, G, Qb), NEG_INF, jnp.float32),
             jnp.zeros((B, N, G, Qb), jnp.float32),
             jnp.zeros((B, N, G, Qb, h), jnp.float32))
    vma = tuple(jax.typeof(qf).vma)
    if vma:     # inside shard_map the carries vary over the axes q does
        carry = tuple(lax.pcast(c, vma, to="varying") for c in carry)
    (m, l, acc), _ = lax.scan(
        step, carry,
        (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), jnp.arange(nblk)))
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return jnp.moveaxis(out, -2, 1).astype(v.dtype)  # (B,Qb,N,G,h)


def _blockwise_attention(q, k, v, bias_fn, scale, kv_block: int,
                         q_block: int = Q_BLOCK):
    """Flash-style attention chunked over BOTH axes: lax.map over query
    blocks (each rematted so backward recomputes per-chunk instead of
    stacking O(Sq·Sk) residuals) × online-softmax scan over KV blocks.
    Never materializes more than (q_block × kv_block) scores per head."""
    B, Sq, N, G, h = q.shape
    pad = (-Sq) % q_block
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    nq = q.shape[1] // q_block
    qb = jnp.moveaxis(q.reshape(B, nq, q_block, N, G, h), 1, 0)

    @jax.checkpoint
    def one_q(args):
        qc, qi = args
        return _kv_scan_attention(qc, k, v, bias_fn, scale, kv_block,
                                  qi * q_block)

    out = lax.map(one_q, (qb, jnp.arange(nq)))      # (nq,B,q_block,N,G,h)
    out = jnp.moveaxis(out, 0, 1).reshape(B, nq * q_block, N, G, h)
    return out[:, :Sq]


def _rope_qk(cfg, q, k, positions):
    """RoPE / M-RoPE / partial RoPE on q (B,S,N,G,h) and k (B,S,N,h); the
    identity for other position kinds.  Rotation is per head."""
    if cfg.pos_kind not in ("rope", "mrope"):
        return q, k
    B, Sq, N, G, h = q.shape
    qr, k = apply_rope(q.reshape(B, Sq, N * G, h), k, positions, head_dim=h,
                       fraction=cfg.rope_fraction, theta=cfg.rope_theta,
                       mrope_sections=cfg.mrope_sections if cfg.pos_kind == "mrope" else ())
    return qr.reshape(B, Sq, N, G, h), k


def _self_attend(q, k, v, positions, slopes, norms, *, cfg, causal: bool,
                 rope: bool, kv_block: int, blockwise_threshold: int):
    """Attention over whole sequences (train, cacheless prefill, cross):
    q (B,Sq,N,G,h); k,v (B,Sk,N,h); ``slopes`` (N,G) ALiBi slopes of these
    heads or None; ``norms`` the per-head q/k RMSNorm scales (may be
    empty).  Every head is independent, so this runs unchanged on a
    head shard inside ``shard_map``."""
    B, Sq, N, G, h = q.shape
    Sk = k.shape[1]
    if norms:
        q = norm(norms["q_norm"], q, "rmsnorm")
        k = norm(norms["k_norm"], k, "rmsnorm")
    if rope:
        q, k = _rope_qk(cfg, q, k, positions)
    scale = 1.0 / math.sqrt(h)
    if not causal:
        def bias_fn(q0, qlen, s0, slen):
            return jnp.zeros((1, 1, 1, 1, slen), jnp.float32)
    else:
        q_pos_full = positions if positions.ndim == 2 else positions[0]
        padq = (-Sq) % Q_BLOCK
        if padq:        # bias_fn may be sliced from padded query blocks
            q_pos_full = jnp.pad(q_pos_full, ((0, 0), (0, padq)))

        def bias_fn(q0, qlen, s0, slen):
            q_pos = lax.dynamic_slice_in_dim(q_pos_full, q0, qlen, axis=1)
            kpos = s0 + jnp.arange(slen)
            m = q_pos[:, :, None] >= kpos[None, None, :]
            if cfg.sliding_window:
                m &= q_pos[:, :, None] - kpos[None, None, :] < cfg.sliding_window
            b = jnp.where(m, 0.0, NEG_INF)            # (B,qlen,slen)
            b = b[:, None, None, :, :]
            if slopes is not None:
                dist = (kpos[None, None, :] - q_pos[:, :, None]).astype(jnp.float32)
                b = b + slopes.reshape(1, N, G, 1, 1) * dist[:, None, None, :, :]
            return b

    if Sk > blockwise_threshold or Sq * Sk > blockwise_threshold ** 2:
        return _blockwise_attention(q, k, v, bias_fn, scale, kv_block)
    return _gqa_scores_to_out(q, k, v, bias_fn(0, Sq, 0, Sk), scale)


def _head_parallel_axes(num_kv_heads: int, batch: int):
    """``(dp, tp)``: the data axes (a tuple) and the model axis for running
    self-attention head-parallel on the ambient mesh, or None where it
    cannot: no axes installed (``constraints.use_axes``), no mesh, a model
    axis of size 1, or KV heads the model axis does not divide (warned
    once, under the label ``attention.heads``, which no tuned site has).
    The batch goes over the data axes where it divides them, else ``dp``
    is empty and the batch stays whole."""
    a = CT.axes()
    mesh = jax.sharding.get_abstract_mesh()
    if a is None or mesh.empty or a["tp"] not in mesh.axis_names:
        return None
    tp = a["tp"]
    n = mesh.shape[tp]
    if n == 1:
        return None
    if num_kv_heads % n:
        from repro.parallel.collectives import warn_degraded

        warn_degraded("attention.heads",
                      f"{num_kv_heads} KV heads are not divisible by the "
                      f"{tp!r} axis ({n}); self-attention keeps GSPMD's "
                      "layout", stacklevel=4)
        return None
    dp = tuple(ax for ax in a["dp"] if ax in mesh.axis_names)
    if batch % math.prod(mesh.shape[ax] for ax in dp):
        dp = ()
    return dp, tp


def _shard_heads(core, dp, tp):
    """``core`` (``_self_attend``'s arguments) as one ``shard_map`` with
    the KV heads over ``tp`` and the batch over the axes ``dp``: each chip
    computes its own heads, and no collective runs inside attention."""
    b = dp or None

    def call(q, k, v, positions, slopes, norms):
        pos = (P(None, b, None) if positions is not None
               and positions.ndim == 3 else P(b, None))
        return jax.shard_map(
            core, axis_names={tp, *dp},
            in_specs=(P(b, None, tp, None, None), P(b, None, tp, None),
                      P(b, None, tp, None), pos, P(tp, None), P()),
            out_specs=P(b, None, tp, None, None),
        )(q, k, v, positions, slopes, norms)

    return call


def attention(p: Params, cfg, x: jnp.ndarray, positions, *,
              cache: Optional[Params] = None, x_kv: Optional[jnp.ndarray] = None,
              causal: bool = True, kv_block: int = 1024,
              blockwise_threshold: int = 2048) -> Tuple[jnp.ndarray, Optional[Params]]:
    """GQA attention.  Returns (out, updated_cache).

    * ``cache`` None  -> train/prefill over the whole sequence.
    * ``cache`` given -> decode: x is (B,1,D); KV appended into the cache
      (ring buffer when cfg.sliding_window > 0).
    * ``x_kv`` given  -> cross attention (no cache update of x_kv side).

    Self-attention without a cache runs head-parallel on a mesh whose
    model axis divides the KV heads (``_head_parallel_axes``): RoPE, the
    mask and the core in one ``shard_map``, between the column-sharded
    q/k/v and the row-sharded o projections.
    """
    B, Sq, _ = x.shape
    N, G, h = cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads, cfg.head_dim
    src = x if x_kv is None else x_kv

    q = linear(p["q"], x).reshape(B, Sq, N, G, h)
    k = linear(p["k"], src).reshape(B, src.shape[1], N, h)
    v = linear(p["v"], src).reshape(B, src.shape[1], N, h)
    is_cross = x_kv is not None

    if cache is None or is_cross:
        causal = causal and not is_cross
        rope = not is_cross and cfg.pos_kind in ("rope", "mrope")
        core = partial(_self_attend, cfg=cfg, causal=causal, rope=rope,
                       kv_block=kv_block,
                       blockwise_threshold=blockwise_threshold)
        slopes = (alibi_slopes(cfg.num_heads).reshape(N, G)
                  if causal and cfg.pos_kind == "alibi" else None)
        norms = {n: p[n] for n in ("q_norm", "k_norm") if n in p}
        positions = positions if rope or causal else None
        layout = None if is_cross else _head_parallel_axes(N, B)
        if layout is None:
            out = core(q, k, v, positions, slopes, norms)
            return linear(p["o"], out.reshape(B, Sq, N * G * h)), None
        out = _shard_heads(core, *layout)(q, k, v, positions, slopes, norms)
        # the o projection's partial sums are reduced into the layer
        # boundary's layout, so its cotangent arrives whole and the weight
        # gradients need no gather of the (B, S, D) activations
        return CT.btd(linear(p["o"], out.reshape(B, Sq, N * G * h))), None

    # ---- decode / cached prefill: append this step's K/V ------------------
    # Sq == 1 is the decode step; Sq > 1 is prefill-into-cache (only valid
    # for SWA when the whole segment fits the ring without wrap).
    if cfg.qk_norm:
        q = norm(p["q_norm"], q, "rmsnorm")
        k = norm(p["k_norm"], k, "rmsnorm")
    q, k = _rope_qk(cfg, q, k, positions)
    W = cache["k"].shape[1]
    t = cache["pos"]                       # scalar int32: tokens so far
    slot = t % W if cfg.sliding_window else t
    ck = lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
    cv = lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
    # slot_pos is per-sequence (B, W): serving engines invalidate each row's
    # right-padded prefill slots independently (slot_pos = -1)
    row = jnp.broadcast_to(t + jnp.arange(Sq, dtype=jnp.int32)[None, :], (B, Sq))
    spos = lax.dynamic_update_slice(cache["slot_pos"], row, (0, slot))
    new_cache = {"k": ck, "v": cv, "pos": t + Sq, "slot_pos": spos}
    q_pos = t + jnp.arange(Sq)                                    # (Sq,)
    valid = (spos[:, None, :] >= 0) & (spos[:, None, :] <= q_pos[None, :, None])
    if cfg.sliding_window:
        valid &= spos[:, None, :] > q_pos[None, :, None] - cfg.sliding_window
    bias = jnp.where(valid, 0.0, NEG_INF)[:, None, None, :, :]
    out = _gqa_scores_to_out(q, ck, cv, bias, 1.0 / math.sqrt(h))
    out = out.reshape(B, Sq, N * G * h)
    return linear(p["o"], out), new_cache


def init_kv_cache(cfg, batch: int, seq_len: int, dtype=jnp.float32) -> Params:
    """Pre-allocated decode cache.  SWA archs allocate only the window (that
    is the sub-quadratic memory story for long_500k)."""
    W = min(seq_len, cfg.sliding_window) if cfg.sliding_window else seq_len
    return {
        "k": jnp.zeros((batch, W, cfg.num_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, W, cfg.num_kv_heads, cfg.head_dim), dtype),
        "pos": jnp.zeros((), jnp.int32),
        "slot_pos": jnp.full((batch, W), -1, jnp.int32),
    }


# ---------------------------------------------------------------------------
# MLA — DeepSeek-V2 multi-head latent attention
# ---------------------------------------------------------------------------

def init_mla(key, cfg, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 8)
    d = cfg.d_model
    qk_hd = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    p = {}
    if cfg.q_lora_rank:
        p["q_a"] = init_linear(ks[0], d, cfg.q_lora_rank, dtype=dtype)
        p["q_a_norm"] = init_norm(cfg.q_lora_rank, "rmsnorm", dtype)
        p["q_b"] = init_linear(ks[1], cfg.q_lora_rank, cfg.num_heads * qk_hd, dtype=dtype)
    else:
        p["q"] = init_linear(ks[0], d, cfg.num_heads * qk_hd, dtype=dtype)
    p["kv_a"] = init_linear(ks[2], d, cfg.kv_lora_rank + cfg.qk_rope_head_dim, dtype=dtype)
    p["kv_a_norm"] = init_norm(cfg.kv_lora_rank, "rmsnorm", dtype)
    p["kv_b"] = init_linear(ks[3], cfg.kv_lora_rank,
                            cfg.num_heads * (cfg.qk_nope_head_dim + cfg.v_head_dim), dtype=dtype)
    p["o"] = init_linear(ks[4], cfg.num_heads * cfg.v_head_dim, d, dtype=dtype)
    return p


def mla_attention(p: Params, cfg, x: jnp.ndarray, positions, *,
                  cache: Optional[Params] = None, kv_block: int = 1024,
                  blockwise_threshold: int = 2048) -> Tuple[jnp.ndarray, Optional[Params]]:
    """MLA with the compressed (c_kv, k_rope) cache — the cache is rank-512
    per token, not per-head, which is the technique's point."""
    B, Sq, _ = x.shape
    H = cfg.num_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim

    if cfg.q_lora_rank:
        q = linear(p["q_b"], norm(p["q_a_norm"], linear(p["q_a"], x), "rmsnorm"))
    else:
        q = linear(p["q"], x)
    q = q.reshape(B, Sq, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]

    kv_a = linear(p["kv_a"], x)                              # (B,S,rank+dr)
    c_kv = norm(p["kv_a_norm"], kv_a[..., :cfg.kv_lora_rank], "rmsnorm")
    k_rope = kv_a[..., cfg.kv_lora_rank:][:, :, None, :]     # (B,S,1,dr)

    q_rope, k_rope = apply_rope(q_rope, k_rope, positions, head_dim=dr,
                                fraction=1.0, theta=cfg.rope_theta)

    new_cache = None
    if cache is not None:
        t = cache["pos"]
        c_kv = lax.dynamic_update_slice(cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), (0, t, 0))
        k_rope = lax.dynamic_update_slice(cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), (0, t, 0, 0))
        # per-sequence slot validity, same contract as the GQA cache: the
        # serving engines invalidate right-padded prefill slots per row
        row = jnp.broadcast_to(t + jnp.arange(Sq, dtype=jnp.int32)[None, :], (B, Sq))
        spos = lax.dynamic_update_slice(cache["slot_pos"], row, (0, t))
        new_cache = {"c_kv": c_kv, "k_rope": k_rope, "pos": t + Sq,
                     "slot_pos": spos}
        Sk = c_kv.shape[1]
        kmask = (spos[:, None, :] >= 0) & (
            spos[:, None, :] <= (t + jnp.arange(Sq))[None, :, None])  # (B,Sq,Sk)
    else:
        Sk = Sq
        kmask = None

    kv = linear(p["kv_b"], c_kv).reshape(B, Sk, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]

    scale = 1.0 / math.sqrt(dn + dr)
    if Sk > blockwise_threshold and cache is None:
        # prefill at long context: online-softmax over KV chunks, never
        # materializing the (Sq, Sk) score matrix.
        out = _mla_blockwise(q_nope, q_rope, k_nope, k_rope, v, scale, kv_block)
    else:
        logits = (jnp.einsum("bqhd,bshd->bhqs", q_nope.astype(jnp.float32), k_nope.astype(jnp.float32))
                  + jnp.einsum("bqhd,bsxd->bhqs", q_rope.astype(jnp.float32), k_rope.astype(jnp.float32))) * scale
        if cache is not None:
            bias = jnp.where(kmask, 0.0, NEG_INF)[:, None, :, :]
        else:
            q_pos = jnp.arange(Sq)
            bias = jnp.where(q_pos[:, None] >= jnp.arange(Sk)[None, :], 0.0, NEG_INF)[None, None]
        w = jax.nn.softmax(logits + bias, axis=-1)
        out = jnp.einsum("bhqs,bshd->bqhd", w, v.astype(jnp.float32)).astype(x.dtype)
    out = out.reshape(B, Sq, H * dv)
    return linear(p["o"], out), new_cache


def _mla_blockwise(q_nope, q_rope, k_nope, k_rope, v, scale, kv_block,
                   q_block: int = 512):
    """MLA prefill attention, chunked over query AND key blocks (same
    two-axis structure as _blockwise_attention)."""
    B, Sq, H, dn = q_nope.shape
    Sk = k_nope.shape[1]
    dv = v.shape[-1]
    nblk = (Sk + kv_block - 1) // kv_block
    pad = nblk * kv_block - Sk
    if pad:
        k_nope = jnp.pad(k_nope, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_rope = jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kn = jnp.moveaxis(k_nope.reshape(B, nblk, kv_block, H, dn), 1, 0)
    kr = jnp.moveaxis(k_rope.reshape(B, nblk, kv_block, 1, -1), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nblk, kv_block, H, dv), 1, 0)

    qpad = (-Sq) % q_block
    if qpad:
        q_nope = jnp.pad(q_nope, ((0, 0), (0, qpad), (0, 0), (0, 0)))
        q_rope = jnp.pad(q_rope, ((0, 0), (0, qpad), (0, 0), (0, 0)))
    nq = q_nope.shape[1] // q_block
    qn_b = jnp.moveaxis(q_nope.reshape(B, nq, q_block, H, dn), 1, 0)
    qr_b = jnp.moveaxis(q_rope.reshape(B, nq, q_block, H, -1), 1, 0)

    @jax.checkpoint
    def one_q(args):
        qn, qr, qi = args
        qn = qn.astype(jnp.float32)
        qr = qr.astype(jnp.float32)
        q_pos = qi * q_block + jnp.arange(q_block)

        def step(carry, blk):
            m, l, acc = carry
            knb, krb, vbb, i = blk
            logits = (jnp.einsum("bqhd,bshd->bhqs", qn, knb.astype(jnp.float32))
                      + jnp.einsum("bqhd,bsxd->bhqs", qr, krb.astype(jnp.float32))) * scale
            kpos = i * kv_block + jnp.arange(kv_block)
            mask = (q_pos[:, None] >= kpos[None, :]) & (kpos[None, :] < Sk)
            logits = logits + jnp.where(mask, 0.0, NEG_INF)[None, None]
            m_new = jnp.maximum(m, logits.max(axis=-1))
            pw = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + pw.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqs,bshd->bhqd", pw, vbb.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, q_block), jnp.float32)
        a0 = jnp.zeros((B, H, q_block, dv), jnp.float32)
        (m, l, acc), _ = lax.scan(step, (m0, l0, a0), (kn, kr, vb, jnp.arange(nblk)))
        out = acc / jnp.maximum(l, 1e-20)[..., None]
        return jnp.moveaxis(out, 1, 2).astype(v.dtype)   # (B,q_block,H,dv)

    out = lax.map(one_q, (qn_b, qr_b, jnp.arange(nq)))   # (nq,B,q_block,H,dv)
    out = jnp.moveaxis(out, 0, 1).reshape(B, nq * q_block, H, dv)
    return out[:, :Sq]


def init_mla_cache(cfg, batch: int, seq_len: int, dtype=jnp.float32) -> Params:
    return {
        "c_kv": jnp.zeros((batch, seq_len, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, seq_len, 1, cfg.qk_rope_head_dim), dtype),
        "pos": jnp.zeros((), jnp.int32),
        "slot_pos": jnp.full((batch, seq_len), -1, jnp.int32),
    }


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, kind: str, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 3)
    if kind == "swiglu":
        return {"gate": init_linear(ks[0], d_model, d_ff, dtype=dtype),
                "up": init_linear(ks[1], d_model, d_ff, dtype=dtype),
                "down": init_linear(ks[2], d_ff, d_model, dtype=dtype)}
    return {"up": init_linear(ks[0], d_model, d_ff, True, dtype=dtype),
            "down": init_linear(ks[1], d_ff, d_model, True, dtype=dtype)}


def mlp(p: Params, x: jnp.ndarray, kind: str) -> jnp.ndarray:
    if kind == "swiglu":
        return linear(p["down"], jax.nn.silu(linear(p["gate"], x)) * linear(p["up"], x))
    return linear(p["down"], jax.nn.gelu(linear(p["up"], x)))


# ---------------------------------------------------------------------------
# Mixture of Experts (capacity-based, sort-free scatter dispatch)
# ---------------------------------------------------------------------------

def moe_pad_experts(num_experts: int, ep_size: int) -> int:
    """Experts padded up to a multiple of the expert-parallel axis (e.g.
    qwen2-moe's 60 -> 64 on a 16-way axis).  Padded experts get -inf router
    logits and never receive tokens; documented in DESIGN.md."""
    return ((num_experts + ep_size - 1) // ep_size) * ep_size


def init_moe(key, cfg, *, ep_pad: int = 1, dtype=jnp.float32) -> Params:
    E = moe_pad_experts(cfg.num_experts, ep_pad)
    d, f = cfg.d_model, cfg.moe_d_ff
    ks = jax.random.split(key, 6)
    s = 1.0 / math.sqrt(d)
    p = {
        "router": init_linear(ks[0], d, cfg.num_experts, dtype=jnp.float32),
        "gate": (jax.random.normal(ks[1], (E, d, f), jnp.float32) * s).astype(dtype),
        "up": (jax.random.normal(ks[2], (E, d, f), jnp.float32) * s).astype(dtype),
        "down": (jax.random.normal(ks[3], (E, f, d), jnp.float32) / math.sqrt(f)).astype(dtype),
    }
    if cfg.num_shared_experts:
        sf = cfg.shared_d_ff or cfg.moe_d_ff * cfg.num_shared_experts
        p["shared"] = init_mlp(ks[4], d, sf, "swiglu", dtype)
        if cfg.shared_expert_gate:
            p["shared_gate"] = init_linear(ks[5], d, 1, dtype=dtype)
    return p


def _moe_ffn_explicit(p: Params, buf: jnp.ndarray, mesh, *, axis: str,
                      site: str) -> jnp.ndarray:
    """Expert FFN with the dispatch/combine all-to-alls made explicit: one
    shard_map over the expert axis — chunked a2a in (``{site}.a2a_disp``),
    per-device expert einsums on the local expert shard, chunked a2a out
    (``{site}.a2a_comb``).  Chunk counts resolve per-site against the
    active tuned plan, so two MoE layers can emit different a2a structure
    from one plan (the paper's per-site co-tuning made HLO-visible)."""
    from repro.parallel.collectives import _chunked_a2a_local, runtime_for

    nc_disp = runtime_for(f"{site}.a2a_disp", "a2a").num_chunks
    nc_comb = runtime_for(f"{site}.a2a_comb", "a2a").num_chunks

    def local(b, gate, up, down):
        # (E, cap/n, D) token-sharded -> (E/n, cap, D) expert-sharded
        b = _chunked_a2a_local(b, axis=axis, split_axis=0, concat_axis=1,
                               num_chunks=nc_disp, site=f"{site}.a2a_disp")
        h = jnp.einsum("ecd,edf->ecf", b, gate)
        h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", b, up)
        y = jnp.einsum("ecf,efd->ecd", h, down)
        # back to the token-sharded capacity layout for the combine gather
        return _chunked_a2a_local(y, axis=axis, split_axis=1, concat_axis=0,
                                  num_chunks=nc_comb, site=f"{site}.a2a_comb")

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(None, axis, None), P(axis, None, None),
                                 P(axis, None, None), P(axis, None, None)),
                       out_specs=P(None, axis, None))
    return fn(buf, p["gate"], p["up"], p["down"])


def moe_block(p: Params, cfg, x: jnp.ndarray, *, capacity_factor: float | None = None,
              mesh=None, axis: str = "model", site: str = "moe",
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k routed experts with capacity-bounded scatter dispatch + optional
    shared experts.  Returns (out, aux_loss).

    Dispatch: tokens are scattered into per-expert capacity buffers
    (E, cap, D) by position-within-expert (cumsum over the flat token axis);
    overflow tokens are dropped (their combine weight is zero).  Under EP
    sharding the (T,D)->(E,cap,D) scatter lowers to all-to-all.

    With ``mesh`` given, the expert FFN runs the *explicit* expert-parallel
    path instead of leaving the layout change to GSPMD: the dispatch and
    combine are real chunked all-to-alls whose chunk counts resolve against
    the active tuned plan at ``{site}.a2a_disp`` / ``{site}.a2a_comb``
    (numerically identical to the GSPMD path).
    """
    B, S, D = x.shape
    T = B * S
    E_real = cfg.num_experts
    E = p["gate"].shape[0]
    k = cfg.top_k
    cf = capacity_factor or cfg.capacity_factor
    cap = max(1, int(T * k * cf / E_real))
    xt = x.reshape(T, D)

    logits = linear(p["router"], xt.astype(jnp.float32))       # (T,E_real)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = lax.top_k(probs, k)                          # (T,k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # aux load-balance loss (Switch-style)
    me = probs.mean(axis=0)                                     # (E_real,)
    ce = jnp.zeros((E_real,)).at[top_e.reshape(-1)].add(1.0) / (T * k)
    aux = E_real * jnp.sum(me * ce)

    # position of each (token, slot) within its expert
    flat_e = top_e.reshape(-1)                                  # (T*k,)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)         # (T*k,E)
    pos_in_e = jnp.cumsum(onehot, axis=0) * onehot              # 1-based
    pos = pos_in_e.sum(-1) - 1                                  # (T*k,); >=cap -> overflow

    # scatter into capacity buffers via FLAT row indices + scatter-add:
    # overflow rows are clipped onto the last slot with zeroed updates, so
    # they contribute nothing (their combine weight is also zeroed below).
    # 1-D indices keep the XLA scatter compact — 2-D advanced indexing with
    # mode="drop"/"fill" materializes (T·k, D)-sized index tensors.
    # Under EP sharding the (T,D)->(E,cap,D) layout change is the all-to-all.
    keep = pos < cap
    row = jnp.clip(flat_e * cap + pos, 0, E * cap - 1)          # (T*k,)
    vals = jnp.repeat(xt, k, axis=0) * keep[:, None].astype(x.dtype)
    buf = jnp.zeros((E * cap, D), x.dtype).at[row].add(vals).reshape(E, cap, D)

    if mesh is not None:
        n = dict(mesh.shape).get(axis, 1)
        if E % n or cap % n:
            from repro.parallel.collectives import warn_degraded

            warn_degraded(
                site,
                f"expert buffer (E={E}, cap={cap}) is not divisible by the "
                f"{axis!r} axis ({n}); using the GSPMD expert layout "
                "instead of explicit all-to-alls",
                stacklevel=3)
            mesh = None
    if mesh is not None:
        y = _moe_ffn_explicit(p, buf, mesh, axis=axis, site=site)
    else:
        buf = CT.ecd(buf)      # expert-parallel layout: this IS the all-to-all
        h = jnp.einsum("ecd,edf->ecf", buf, p["gate"])
        h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", buf, p["up"])
        y = CT.ecd(jnp.einsum("ecf,efd->ecd", h, p["down"]))    # (E,cap,D)

    gathered = jnp.take(y.reshape(E * cap, D), row, axis=0)     # (T*k,D)
    w = (top_p.reshape(-1) * keep).astype(x.dtype)
    out = (gathered * w[:, None]).reshape(T, k, D).sum(axis=1)

    if "shared" in p:
        sh = mlp(p["shared"], xt, "swiglu")
        if "shared_gate" in p:
            sh = sh * jax.nn.sigmoid(linear(p["shared_gate"], xt))
        out = out + sh
    return out.reshape(B, S, D), aux


# ---------------------------------------------------------------------------
# embeddings / positions tables
# ---------------------------------------------------------------------------

def init_embedding(key, vocab: int, d: int, dtype=jnp.float32) -> Params:
    return {"table": (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)}


def embed(p: Params, tokens: jnp.ndarray) -> jnp.ndarray:
    return p["table"][tokens]


def unembed(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    return x @ p["table"].T
