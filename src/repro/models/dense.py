"""Generic decoder trunk: dense GQA / SWA / MLA attention + SwiGLU/GELU or
MoE feed-forward.  Covers the dense, moe and vlm families (and is reused as
the transformer block by whisper and zamba2).

Layers are *stacked* (leading L axis) and executed with ``lax.scan`` so the
compiled graph contains one layer body regardless of depth — essential to
keep the 512-device GSPMD dry-run compiles tractable.

Plan-aware (sited) path: passing ``mesh=`` to ``trunk_fwd`` unrolls the
stack into per-layer bodies whose feed-forward collectives are the
*explicit* chunked helpers (``ring_ag_matmul`` / ``mm_reduce_scatter`` /
the MoE all-to-alls), each addressed by a stable SiteId
(``tp.layer{i}.mlp``, ``ep.layer{j}.moe``; ``serve.layer{i}.mlp`` /
``serve.layer{i}.moe`` on the cached decode path).  Each site resolves its own
knobs against the active tuned plan (``collectives.runtime_for``), so one
``TunedPlan`` can legitimately drive two layers of the same model to emit
different chunk structure — the per-operator overlap decision flowing into
the emitted program, no hand-plumbed ``num_chunks`` anywhere.
"""
from __future__ import annotations

import warnings
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.parallel import constraints as CT
from repro.parallel.collectives import mm_reduce_scatter, ring_ag_matmul

Params = Dict[str, Any]


def init_layer(key, cfg, *, use_moe: bool, ep_pad: int = 1, dtype=jnp.float32) -> Params:
    k_attn, k_mlp = jax.random.split(key)
    p: Params = {"ln1": L.init_norm(cfg.d_model, cfg.norm_kind, dtype)}
    if cfg.attn_kind == "mla":
        p["attn"] = L.init_mla(k_attn, cfg, dtype)
    else:
        p["attn"] = L.init_attention(k_attn, cfg, dtype=dtype)
    if not cfg.parallel_block:
        p["ln2"] = L.init_norm(cfg.d_model, cfg.norm_kind, dtype)
    if use_moe:
        p["moe"] = L.init_moe(k_mlp, cfg, ep_pad=ep_pad, dtype=dtype)
    else:
        p["mlp"] = L.init_mlp(k_mlp, cfg.d_model, cfg.d_ff, cfg.mlp_kind, dtype)
    return p


def tp_mlp(p: Params, x: jnp.ndarray, kind: str, mesh, *, axis: str = "model",
           site: str = "tp.mlp") -> jnp.ndarray:
    """Explicit tensor-parallel MLP: the up projections are ring
    AllGather∘matmul over a sequence-sharded input (site ``{site}.ag``),
    the down projection matmul∘ReduceScatter (site ``{site}.rs``) — each
    site's chunk structure resolved independently against the active tuned
    plan.  Numerically identical to ``layers.mlp``."""
    ag = partial(ring_ag_matmul, mesh=mesh, axis=axis,
                 x_spec=P(None, axis, None), w_spec=P(None, axis),
                 out_spec=P(None, None, axis), site=f"{site}.ag")
    if kind == "swiglu":
        h = jax.nn.silu(ag(x, p["gate"]["w"])) * ag(x, p["up"]["w"])
    else:
        h = ag(x, p["up"]["w"])
        if "b" in p["up"]:
            h = h + p["up"]["b"]
        h = jax.nn.gelu(h)
    y = mm_reduce_scatter(h, p["down"]["w"], mesh, axis=axis,
                          x_spec=P(None, None, axis), w_spec=P(axis, None),
                          out_spec=P(None, axis, None), site=f"{site}.rs")
    if "b" in p["down"]:
        y = y + p["down"]["b"]
    return y


def serve_mlp(p: Params, x: jnp.ndarray, kind: str, mesh, *,
              axis: str = "model", site: str = "serve.mlp") -> jnp.ndarray:
    """Decode-shape plan-aware MLP.  ``tp_mlp`` chunks the sequence axis,
    which is length 1 at decode — so the in-flight batch is re-laid as
    that axis, (B, S, D) -> (1, B·S, D): the tuned chunk counts then
    decompose the collectives over the sequences in flight (serving's
    microbatch).  Position-wise MLP, so this is numerically the identity
    transform."""
    B, S, D = x.shape
    y = tp_mlp(p, x.reshape(1, B * S, D), kind, mesh, axis=axis, site=site)
    return y.reshape(B, S, D)


def layer_fwd(p: Params, cfg, x: jnp.ndarray, positions, cache: Optional[Params],
              *, use_moe: bool, mesh=None, axis: str = "model",
              site: str = "", serve: bool = False,
              ) -> Tuple[jnp.ndarray, Optional[Params], jnp.ndarray]:
    """One decoder layer.  ``mesh`` switches the feed-forward onto the
    explicit plan-aware collectives, with ``site`` the layer's SiteId
    prefix (``tp.layer{i}.mlp`` / ``ep.layer{j}.moe``, or
    ``serve.layer{i}.*`` when ``serve`` marks the decode-shape layout)."""
    def ff(q, v):
        with jax.named_scope("mlp"):
            if mesh is not None and not use_moe:
                if serve:
                    return serve_mlp(q, v, cfg.mlp_kind, mesh, axis=axis,
                                     site=site or "serve.mlp")
                return tp_mlp(q, v, cfg.mlp_kind, mesh, axis=axis,
                              site=site or "tp.mlp")
            return L.mlp(q, v, cfg.mlp_kind)

    x = CT.btd(x)
    h = L.norm(p["ln1"], x, cfg.norm_kind)
    with jax.named_scope("attention"):
        if cfg.attn_kind == "mla":
            attn_out, new_cache = L.mla_attention(p["attn"], cfg, h, positions,
                                                  cache=cache)
        else:
            attn_out, new_cache = L.attention(p["attn"], cfg, h, positions,
                                              cache=cache)

    aux = jnp.zeros((), jnp.float32)
    if cfg.parallel_block:           # phi-2 style: mlp reads the same norm
        x = x + attn_out + ff(p["mlp"], h)
    else:
        x = x + attn_out
        h2 = L.norm(p["ln2"], x, cfg.norm_kind)
        if use_moe:
            with jax.named_scope("moe"):
                ff_out, aux = L.moe_block(p["moe"], cfg, h2, mesh=mesh,
                                          axis=axis, site=site or "ep.moe")
        else:
            ff_out = ff(p["mlp"], h2)
        x = x + ff_out
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# stacked trunk
# ---------------------------------------------------------------------------

def _stack_init(init_one, keys):
    return jax.vmap(init_one)(keys)


def init_trunk(key, cfg, *, ep_pad: int = 1, dtype=jnp.float32) -> Params:
    """Two stacked segments: leading dense layers (MoE archs may start dense),
    then the homogeneous tail."""
    n_dense_head = cfg.first_dense_layers if cfg.is_moe else cfg.num_layers
    n_tail = cfg.num_layers - n_dense_head
    keys = jax.random.split(key, cfg.num_layers)
    p: Params = {}
    if n_dense_head:
        p["dense_layers"] = _stack_init(
            partial(init_layer, cfg=cfg, use_moe=False, dtype=dtype), keys[:n_dense_head])
    if n_tail:
        p["moe_layers"] = _stack_init(
            partial(init_layer, cfg=cfg, use_moe=True, ep_pad=ep_pad, dtype=dtype),
            keys[n_dense_head:])
    return p


def _run_segment(stacked: Params, cfg, x, positions, caches, *, use_moe: bool,
                 remat: bool) -> Tuple[jnp.ndarray, Optional[Params], jnp.ndarray]:
    if caches is None:
        def scan_fn(carry, lp):
            x, aux = carry
            def fn(q, v):
                return layer_fwd(q, cfg, v, positions, None, use_moe=use_moe)

            if remat:
                fn = jax.checkpoint(fn)
            x, _, a = fn(lp, x)
            return (x, aux + a), None
        (x, aux), _ = jax.lax.scan(scan_fn, (x, jnp.zeros((), jnp.float32)), stacked)
        return x, None, aux

    def scan_fn(carry, xs):
        x, aux = carry
        lp, lc = xs
        x, nc, a = layer_fwd(lp, cfg, x, positions, lc, use_moe=use_moe)
        return (x, aux + a), nc
    (x, aux), new_caches = jax.lax.scan(scan_fn, (x, jnp.zeros((), jnp.float32)),
                                        (stacked, caches))
    return x, new_caches, aux


def _sited_applicable(cfg, x, mesh, axis: str) -> Tuple[bool, str]:
    """Shape preconditions of the explicit collective helpers (shard_map
    needs exact divisibility; violations fall back to the scan path)."""
    if axis not in mesh.axis_names:
        return False, f"mesh has no {axis!r} axis"
    n = dict(mesh.shape)[axis]
    if x.shape[1] % n:
        return False, f"sequence length {x.shape[1]} not divisible by {n}"
    if cfg.d_ff and cfg.d_ff % n:
        return False, f"d_ff {cfg.d_ff} not divisible by {n}"
    return True, ""


def _sited_applicable_serve(cfg, x, mesh, axis: str) -> Tuple[bool, str]:
    """Decode-shape variant: ``serve_mlp`` re-lays (B, S, D) as
    (1, B·S, D), so the divisible axis is the whole in-flight token count,
    not the per-sequence length."""
    if axis not in mesh.axis_names:
        return False, f"mesh has no {axis!r} axis"
    n = dict(mesh.shape)[axis]
    if (x.shape[0] * x.shape[1]) % n:
        return False, (f"in-flight tokens {x.shape[0] * x.shape[1]} not "
                       f"divisible by {n}")
    if cfg.d_ff and cfg.d_ff % n:
        return False, f"d_ff {cfg.d_ff} not divisible by {n}"
    return True, ""


def _trunk_fwd_sited(p: Params, cfg, x, positions, mesh, *, axis: str,
                     remat: bool, caches=None):
    """Python-unrolled trunk: one body per layer so every layer's comm
    sites resolve independently against the active plan.  Without caches
    this is the train/prefill path (sites ``tp.layer{i}.mlp`` /
    ``ep.layer{j}.moe``, segment-local MoE indices — PR 5's convention);
    with caches it is the *serving* path, sites ``serve.layer{i}.mlp`` /
    ``serve.layer{i}.moe`` with global layer indices, matching
    ``core.extract.extract_decode_workload``.  Compile cost grows with
    depth, so this path is for tuned deployments, not the 512-device
    dry-run compiles."""
    aux_total = jnp.zeros((), jnp.float32)
    li = 0
    new_caches: Dict[str, Any] = {}
    for seg, use_moe in (("dense_layers", False), ("moe_layers", True)):
        if seg not in p:
            continue
        stacked = p[seg]
        n_seg = jax.tree.leaves(stacked)[0].shape[0]
        seg_cache = caches[seg] if caches is not None else None
        # every layer's slice is taken in one scope, so a trace reads the
        # slices, and any sum of the layers' gradients back into the
        # stacked buffers that XLA keeps as an operation of its own, under
        # ``layer_params``
        with jax.named_scope("layer_params"):
            lps = [jax.tree.map(lambda a: a[j], stacked) for j in range(n_seg)]
        layer_caches = []
        for j in range(n_seg):
            lp = lps[j]
            if caches is None:
                site = f"ep.layer{j}.moe" if use_moe else f"tp.layer{li}.mlp"
                lc = None
            else:
                kind = "moe" if use_moe else "mlp"
                site = f"serve.layer{li}.{kind}"
                lc = jax.tree.map(lambda a: a[j], seg_cache)

            def fl(q, v, c):
                return layer_fwd(q, cfg, v, positions, c, use_moe=use_moe,
                                 mesh=mesh, axis=axis, site=site,
                                 serve=caches is not None)

            if remat and caches is None:
                fl = jax.checkpoint(fl)
            x, nc, a = fl(lp, x, lc)
            if nc is not None:
                layer_caches.append(nc)
            aux_total = aux_total + a
            li += 1
        if layer_caches:
            # restack to the scan layout (leading L axis) so sited and
            # scan decode caches are interchangeable pytrees
            new_caches[seg] = jax.tree.map(
                lambda *leaves: jnp.stack(leaves), *layer_caches)
    return x, (new_caches or None), aux_total


def trunk_fwd(p: Params, cfg, x, positions, caches=None, *, remat: bool = False,
              mesh=None, tp_axis: str = "model"):
    """caches: None | {"dense_layers": stacked_cache, "moe_layers": stacked_cache}.

    ``mesh``: opt into the plan-aware sited path (explicit per-layer
    collectives addressed as ``tp.layer{i}.mlp`` / ``ep.layer{j}.moe`` for
    train/prefill, ``serve.layer{i}.mlp`` / ``serve.layer{i}.moe`` for
    cached decode/prefill; see module docstring).  Shapes that violate the
    explicit helpers' divisibility fall back to the scan path with a
    ``RuntimeWarning``."""
    if mesh is not None:
        if caches is None:
            ok, why = _sited_applicable(cfg, x, mesh, tp_axis)
        else:
            ok, why = _sited_applicable_serve(cfg, x, mesh, tp_axis)
        if not ok:
            warnings.warn(f"plan-aware trunk disabled: {why}; using the "
                          "GSPMD scan path", RuntimeWarning, stacklevel=2)
        else:
            return _trunk_fwd_sited(p, cfg, x, positions, mesh, axis=tp_axis,
                                    remat=remat, caches=caches)
    aux_total = jnp.zeros((), jnp.float32)
    new_caches: Dict[str, Any] = {}
    for seg, use_moe in (("dense_layers", False), ("moe_layers", True)):
        if seg not in p:
            continue
        seg_cache = caches[seg] if caches is not None else None
        x, nc, aux = _run_segment(p[seg], cfg, x, positions, seg_cache,
                                  use_moe=use_moe, remat=remat)
        if nc is not None:
            new_caches[seg] = nc
        aux_total = aux_total + aux
    return x, (new_caches or None), aux_total


def init_trunk_caches(cfg, batch: int, seq_len: int, dtype=jnp.float32) -> Params:
    """Stacked per-segment decode caches (leading L axis, matching scan xs)."""
    def one(cfg):
        if cfg.attn_kind == "mla":
            return L.init_mla_cache(cfg, batch, seq_len, dtype)
        return L.init_kv_cache(cfg, batch, seq_len, dtype)

    n_dense_head = cfg.first_dense_layers if cfg.is_moe else cfg.num_layers
    n_tail = cfg.num_layers - n_dense_head
    caches: Params = {}
    if n_dense_head:
        caches["dense_layers"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_dense_head,) + a.shape).copy(), one(cfg))
    if n_tail:
        caches["moe_layers"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_tail,) + a.shape).copy(), one(cfg))
    return caches
