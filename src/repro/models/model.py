"""Unified model API over every family in the zoo.

    params = init_params(cfg, rng)
    loss, metrics = loss_and_metrics(cfg, params, batch)          # train
    x, caches, aux = forward_hidden(cfg, params, batch)           # prefill
    caches = init_caches(cfg, batch_size, seq_len)                # serving
    logits, caches = decode_step(cfg, params, tokens, caches)     # decode

``batch``: {"tokens": (B,S) i32, "targets": (B,S) i32, "mask": (B,S) f32}
plus "frames" (B,enc_seq,D) for audio and "patches" (B,n_patch,D) for vlm
(frontends are stubs: precomputed embeddings).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import dense, layers as L, rwkv6, whisper, zamba2
from repro.parallel import constraints as CT

Params = Dict[str, Any]

N_PATCHES = 256          # vlm stub: one 16x16 image at the sequence head
_PATCH_GRID = 16

_TRUNKS = {
    "dense": dense, "moe": dense, "vlm": dense,
    "ssm": rwkv6, "hybrid": zamba2, "audio": whisper,
}


def _trunk(cfg):
    return _TRUNKS[cfg.family]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(cfg, rng, *, ep_pad: int = 1, dtype=None) -> Params:
    dtype = jnp.dtype(dtype or cfg.dtype)
    k_emb, k_trunk, k_head, k_pos = jax.random.split(rng, 4)
    p: Params = {"embed": L.init_embedding(k_emb, cfg.vocab_size, cfg.d_model, dtype)}
    if cfg.family in ("dense", "moe", "vlm"):
        p["trunk"] = dense.init_trunk(k_trunk, cfg, ep_pad=ep_pad, dtype=dtype)
    elif cfg.family == "ssm":
        p["trunk"] = rwkv6.init_trunk(k_trunk, cfg, dtype)
    elif cfg.family == "hybrid":
        p["trunk"] = zamba2.init_trunk(k_trunk, cfg, dtype)
    elif cfg.family == "audio":
        p["trunk"] = whisper.init_trunk(k_trunk, cfg, dtype)
        p["dec_pos"] = (jax.random.normal(k_pos, (cfg.max_seq_len, cfg.d_model),
                                          jnp.float32) * 0.02).astype(dtype)
    else:
        raise ValueError(cfg.family)
    p["ln_f"] = L.init_norm(cfg.d_model, cfg.norm_kind, dtype)
    if not cfg.tie_embeddings:
        p["head"] = L.init_linear(k_head, cfg.d_model, cfg.vocab_size, dtype=dtype)
    return p


# ---------------------------------------------------------------------------
# positions
# ---------------------------------------------------------------------------

def _positions(cfg, batch, B: int, S: int, t0) -> jnp.ndarray:
    """(B,S) int32, or (3,B,S) for M-RoPE."""
    base = t0 + jnp.arange(S, dtype=jnp.int32)
    pos = jnp.broadcast_to(base[None], (B, S))
    if cfg.pos_kind != "mrope":
        return pos
    if batch.get("patches") is None:
        return jnp.broadcast_to(pos[None], (3, B, S))
    # image patches occupy the first N_PATCHES slots at (t=0, h, w) grid
    # positions; text then continues from grid_max + 1 on all three axes.
    n = N_PATCHES
    gh = jnp.arange(n, dtype=jnp.int32) // _PATCH_GRID
    gw = jnp.arange(n, dtype=jnp.int32) % _PATCH_GRID
    text = _PATCH_GRID + jnp.arange(S - n, dtype=jnp.int32)
    pt = jnp.concatenate([jnp.zeros((n,), jnp.int32), text])
    ph = jnp.concatenate([gh, text])
    pw = jnp.concatenate([gw, text])
    grid = jnp.stack([pt, ph, pw])                       # (3,S)
    return jnp.broadcast_to(grid[:, None], (3, B, S)) + t0


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _embed_inputs(cfg, p, batch) -> jnp.ndarray:
    with jax.named_scope("embed"):
        x = L.embed(p["embed"], batch["tokens"])
        if cfg.family == "vlm" and batch.get("patches") is not None:
            n = batch["patches"].shape[1]
            x = jnp.concatenate([batch["patches"].astype(x.dtype), x[:, n:]],
                                axis=1)
    return x


def forward_hidden(cfg, p: Params, batch, caches: Optional[Params] = None, *,
                   remat: bool = False, backend: Optional[str] = None,
                   mesh=None
                   ) -> Tuple[jnp.ndarray, Optional[Params], jnp.ndarray]:
    """Runs the trunk over batch["tokens"].  If ``caches`` is given, this is a
    cached prefill (states/KV are filled; pass fresh caches).  ``mesh``
    opts dense-family trunks into the plan-aware explicit-collective path
    (``dense.trunk_fwd``); other families ignore it."""
    B, S = batch["tokens"].shape
    t0 = caches["pos"] if caches is not None else jnp.zeros((), jnp.int32)
    positions = _positions(cfg, batch, B, S, t0)
    x = _embed_inputs(cfg, p, batch)

    aux = jnp.zeros((), jnp.float32)
    if cfg.family == "audio":
        memory = whisper.encode(p["trunk"], cfg, batch["frames"].astype(x.dtype))
        x = x + jnp.take(p["dec_pos"], positions, axis=0)
        tc = caches["trunk"] if caches is not None else None
        x, new_tc = whisper.decode_trunk(p["trunk"], cfg, x, memory, positions,
                                         tc, remat=remat)
        new_caches = None if caches is None else {
            "trunk": new_tc, "pos": t0 + S, "memory": memory}
    else:
        kw = dict(remat=remat)
        if cfg.family in ("ssm", "hybrid"):
            kw["backend"] = backend
        tc = caches["trunk"] if caches is not None else None
        if cfg.family == "ssm":
            x, new_tc, aux = rwkv6.trunk_fwd(p["trunk"], cfg, x, positions, tc, **kw)
        elif cfg.family == "hybrid":
            x, new_tc, aux = zamba2.trunk_fwd(p["trunk"], cfg, x, positions, tc, **kw)
        else:
            if mesh is not None:
                kw["mesh"] = mesh
            x, new_tc, aux = dense.trunk_fwd(p["trunk"], cfg, x, positions, tc, **kw)
        new_caches = None if caches is None else {"trunk": new_tc, "pos": t0 + S}

    x = L.norm(p["ln_f"], x, cfg.norm_kind)
    return x, new_caches, aux


def _unembed(cfg, p, x):
    if cfg.tie_embeddings:
        return L.unembed(p["embed"], x)
    return L.linear(p["head"], x)


def logits(cfg, p: Params, batch, *, backend: Optional[str] = None):
    """(B,S,vocab) logits of the uncached forward pass — the reference that
    cached decoding and the loss are checked against."""
    x, _, _ = forward_hidden(cfg, p, batch, backend=backend)
    return _unembed(cfg, p, x)


# ---------------------------------------------------------------------------
# training loss (chunked cross-entropy: the full (B,S,V) logits tensor is
# never materialized — each chunk's logits are recomputed in the backward
# pass via jax.checkpoint)
# ---------------------------------------------------------------------------

def chunked_ce(cfg, p, x, targets, mask, *, chunk: int = 256):
    with jax.named_scope("loss"):
        return _chunked_ce(cfg, p, x, targets, mask, chunk)


def _chunked_ce(cfg, p, x, targets, mask, chunk):
    B, S, D = x.shape
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    n = x.shape[1] // chunk
    xc = jnp.moveaxis(x.reshape(B, n, chunk, D), 1, 0)
    tc = jnp.moveaxis(targets.reshape(B, n, chunk), 1, 0)
    mc = jnp.moveaxis(mask.reshape(B, n, chunk), 1, 0)

    @jax.checkpoint
    def body(tot, xs):
        xb, tb, mb = xs
        logits = CT.logits(_unembed(cfg, p, CT.btd(xb)).astype(jnp.float32))
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, tb[..., None], axis=-1)[..., 0]
        return tot + (((lse - tgt) * mb).sum()), None

    tot, _ = lax.scan(body, jnp.zeros((), jnp.float32), (xc, tc, mc))
    return tot / jnp.maximum(mask.sum(), 1.0)


def loss_and_metrics(cfg, p: Params, batch, *, remat: bool = True,
                     backend: Optional[str] = None, mesh=None):
    x, _, aux = forward_hidden(cfg, p, batch, remat=remat, backend=backend,
                               mesh=mesh)
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones(batch["targets"].shape, jnp.float32)
    if cfg.family == "vlm" and batch.get("patches") is not None:
        # patch positions carry no next-token target
        n = batch["patches"].shape[1]
        mask = mask.at[:, :n].set(0.0)
    ce = chunked_ce(cfg, p, x, batch["targets"], mask)
    loss = ce + cfg.router_aux_coef * aux
    return loss, {"ce": ce, "aux": aux, "loss": loss}


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def init_caches(cfg, batch: int, seq_len: int, dtype=None) -> Params:
    dtype = jnp.dtype(dtype or cfg.dtype)
    t = _trunk(cfg)
    caches: Params = {"trunk": t.init_trunk_caches(cfg, batch, seq_len, dtype),
                      "pos": jnp.zeros((), jnp.int32)}
    if cfg.family == "audio":
        caches["memory"] = jnp.zeros((batch, cfg.encoder_seq, cfg.d_model), dtype)
    return caches


def decode_step(cfg, p: Params, tokens: jnp.ndarray, caches: Params, *,
                backend: Optional[str] = None, mesh=None,
                pos_offset: Optional[jnp.ndarray] = None,
                ) -> Tuple[jnp.ndarray, Params]:
    """One token per sequence: tokens (B,1) -> logits (B,1,vocab).

    ``mesh`` opts dense-family trunks into the plan-aware sited decode
    path (explicit collectives at ``serve.layer{i}.*`` SiteIds, resolved
    against the active tuned plan; other families ignore it).
    ``pos_offset`` (B,) int32 subtracts a per-sequence gap from the shared
    position counter — how the fixed-batch engine keeps right-padded
    ragged prompts on their true positions (the pad gap sits between
    prefill and decode slots, which the per-row ``slot_pos`` mask already
    excludes)."""
    B = tokens.shape[0]
    t0 = caches["pos"]
    positions = _positions(cfg, {"tokens": tokens}, B, 1, t0)
    if pos_offset is not None:
        off = jnp.asarray(pos_offset, jnp.int32)
        positions = positions - (off[None, :, None] if positions.ndim == 3
                                 else off[:, None])
    x = L.embed(p["embed"], tokens)

    if cfg.family == "audio":
        x = x + jnp.take(p["dec_pos"], positions, axis=0)
        x, new_tc = whisper.decode_trunk(p["trunk"], cfg, x, caches["memory"],
                                         positions, caches["trunk"])
        new_caches = {"trunk": new_tc, "pos": t0 + 1, "memory": caches["memory"]}
    else:
        kw: Dict[str, Any] = {}
        if cfg.family in ("ssm", "hybrid"):
            kw["backend"] = backend
        if cfg.family == "ssm":
            x, new_tc, _ = rwkv6.trunk_fwd(p["trunk"], cfg, x, positions, caches["trunk"], **kw)
        elif cfg.family == "hybrid":
            x, new_tc, _ = zamba2.trunk_fwd(p["trunk"], cfg, x, positions, caches["trunk"], **kw)
        else:
            if mesh is not None:
                kw["mesh"] = mesh
            x, new_tc, _ = dense.trunk_fwd(p["trunk"], cfg, x, positions, caches["trunk"], **kw)
        new_caches = {"trunk": new_tc, "pos": t0 + 1}

    x = L.norm(p["ln_f"], x, cfg.norm_kind)
    return _unembed(cfg, p, x), new_caches
