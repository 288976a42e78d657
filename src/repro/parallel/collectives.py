"""Chunked, overlap-friendly collective matmuls (shard_map + lax.ppermute).

These are the TPU-native runtime artifacts that Lagom's tuned parameters
select (DESIGN.md §2):

  * ``C`` (chunk size)      -> ``num_chunks`` of each decomposed collective
  * ``Algorithm``           -> ``strategy``: "xla" (one fused collective,
                               scheduling left to XLA's latency-hiding
                               scheduler) | "ring" (explicit ppermute ring)
                               | "chunked" (scan of partial collectives)
  * ``NC`` (channels)       -> modeled in the simulator (DMA concurrency);
                               on real HW it maps to
                               ``--xla_tpu_scoped_vmem_limit_kib`` style
                               staging limits, which have no HLO footprint.

Every function has a dense reference (``*_ref``) used by the tests, and the
explicit variants are HLO-visible: the dry-run roofline counts their
collective-permute / reduce-scatter bytes, so tuned chunk counts actually
move the measured collective term.

Per-site plan addressing
------------------------

Every tunable collective call site carries a stable dotted **SiteId**
(e.g. ``fsdp.layer3.ag_params``, ``tp.layer1.mlp.rs``) derived from the
Workload IR names that ``core.extract`` emits.  A runtime plan is a
``{site_id: CollectiveRuntime}`` map (what ``session.TunedPlan.
runtime_plan()`` lowers to); ``runtime_for(site, cls)`` resolves a site
against the *active* plan by walking from most- to least-specific:

  exact site id -> each dotted prefix (``tp.layer1.mlp`` -> ``tp.layer1``
  -> ``tp``) -> the site *class* (``"ag"`` / ``"rs"`` / ``"ar"`` /
  ``"a2a"`` / ``"p2p"``) -> XLA defaults.

so one plan can legitimately drive two layers of the same model to emit
different chunk structure.  Plans are scoped: ``use_runtime_plan`` pushes
a plan for a ``with`` block (what ``TunedPlan.applied()`` uses — nested
scopes shadow, exits restore, exception-safe), while
``install_runtime_plan`` sets the process-wide base plan (the launchers'
``--tuned-plan`` / ``--plan-repo`` startup path).  The legacy
``set_runtime_plan`` remains as a deprecation shim over the latter.
"""
from __future__ import annotations

import contextlib
import contextvars
import warnings
from dataclasses import dataclass
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


@dataclass(frozen=True)
class CollectiveRuntime:
    """Runtime knobs for one collective site (what `core.apply` emits)."""
    strategy: str = "xla"        # xla | ring | chunked
    num_chunks: int = 1


@dataclass(frozen=True)
class SiteResolution:
    """One ``resolve_runtime`` consultation observed by
    ``record_site_resolutions`` — the ground truth the overlap verifier
    (``repro.analysis.overlap``) attributes emitted chunk structure with:
    plans are consumed at *trace* time, so the set of recorded rows is
    exactly the set of sites the traced program addressed, with the knobs
    and fallback tier each one actually received."""
    site: str
    cls: Optional[str]
    strategy: str
    num_chunks: int
    matched_key: str     # plan key that supplied the knobs ("" = default)
    tier: str            # "exact" | "prefix" | "class" | "default"


# Active runtime plans, each ``{site_id: CollectiveRuntime}``.  The base
# plan is process-wide (``install_runtime_plan`` — the launchers'
# ``--tuned-plan`` startup path); ``use_runtime_plan`` layers scoped plans
# over it (``TunedPlan.applied()``) in a ``ContextVar`` so concurrent
# threads/tasks cannot pop each other's scopes.  The *innermost* plan is
# the active one — scopes shadow rather than merge, so ``applied()`` means
# "exactly this plan", and exiting restores whatever was active before.
_BASE_PLAN: Dict[str, CollectiveRuntime] = {}
_SCOPED_PLANS: contextvars.ContextVar = contextvars.ContextVar(
    "repro_runtime_plans", default=())

_DEFAULT_RUNTIME = CollectiveRuntime()


def install_runtime_plan(plan: Optional[Dict[str, CollectiveRuntime]] = None,
                         ) -> None:
    """Install ``{site_id: CollectiveRuntime}`` as the process-wide base
    plan (replacing any previous one; ``None``/empty clears it).  Scoped
    plans pushed by ``use_runtime_plan`` shadow it while active."""
    global _BASE_PLAN
    _BASE_PLAN = dict(plan or {})


@contextlib.contextmanager
def use_runtime_plan(plan: Dict[str, CollectiveRuntime]):
    """Scope a runtime plan to a ``with`` block: inside, ``runtime_for``
    resolves against ``plan`` (shadowing any outer/base plan); on exit —
    normal or exceptional — the prior state is restored.  Nests, and is
    thread/async-safe (context-local, token-based restore)."""
    token = _SCOPED_PLANS.set(_SCOPED_PLANS.get() + (dict(plan),))
    try:
        yield
    finally:
        _SCOPED_PLANS.reset(token)


def set_runtime_plan(plan: Dict[str, CollectiveRuntime]) -> None:
    """Deprecated alias for ``install_runtime_plan`` (the pre-per-site
    process-global API).  Resolved knobs are bit-identical; prefer
    ``TunedPlan.applied()`` for scoped use."""
    warnings.warn(
        "set_runtime_plan is deprecated; use install_runtime_plan(plan) for "
        "a process-wide install or `with plan.applied(): ...` for a scoped "
        "one", DeprecationWarning, stacklevel=2)
    install_runtime_plan(plan)


def _active_plan() -> Dict[str, CollectiveRuntime]:
    scopes = _SCOPED_PLANS.get()
    return scopes[-1] if scopes else _BASE_PLAN


# Trace-time site-resolution recorder (context-local, like the scoped
# plans): while a ``record_site_resolutions`` block is active, every
# ``resolve_runtime`` call appends a ``SiteResolution`` row.  The overlap
# verifier traces a model builder inside this block to learn which sites
# the program consulted and what knobs each received — the sound way to
# attribute emitted scan/while chunk structure back to dotted SiteIds
# (builder call sites address plans at coarser granularity than the
# Workload IR site ids, so name matching alone is not enough).
_RESOLUTION_LOG: contextvars.ContextVar = contextvars.ContextVar(
    "repro_site_resolution_log", default=None)


@contextlib.contextmanager
def record_site_resolutions():
    """Record every ``resolve_runtime`` consultation in the ``with`` block.

    Yields the live list of ``SiteResolution`` rows (appended in call
    order, duplicates included — a builder may consult one site several
    times).  Nests: the innermost recorder captures the rows; outer
    recorders resume on exit.  Thread/async-safe (context-local)."""
    rows: list = []
    token = _RESOLUTION_LOG.set(rows)
    try:
        yield rows
    finally:
        _RESOLUTION_LOG.reset(token)


def active_runtime_plan() -> Dict[str, CollectiveRuntime]:
    """The innermost active plan (a copy)."""
    return dict(_active_plan())


def site_class(site: str) -> str:
    """First dotted component of a site id — the coarse bucket the legacy
    three-knob plans keyed on (``"ag"``/``"rs"``/``"ar"``/``"a2a"``/
    ``"p2p"`` for Workload IR comm names)."""
    return site.split(".", 1)[0]


def resolve_runtime(site: str, cls: Optional[str] = None,
                    ) -> Tuple[CollectiveRuntime, str, str]:
    """Resolve ``site`` against the active plan, reporting *how* it
    matched: ``(knobs, matched_key, tier)`` with ``tier`` one of
    ``"exact"`` (the full site id), ``"prefix"`` (a dotted prefix —
    ``acc.step3.rs_grads`` served by an ``acc`` entry), ``"class"`` (the
    ``cls`` fallback bucket), or ``"default"`` (XLA defaults,
    ``matched_key == ""``).  Resolution order: exact site id, then each
    dotted prefix (most to least specific), then ``cls``."""
    plan = _active_plan()
    rt, key, tier = _DEFAULT_RUNTIME, "", "default"
    if site:
        parts = site.split(".")
        for k in range(len(parts), 0, -1):
            pk = ".".join(parts[:k])
            if pk in plan:
                rt, key, tier = plan[pk], pk, ("exact" if k == len(parts)
                                               else "prefix")
                break
    if tier == "default" and cls is not None and cls in plan:
        rt, key, tier = plan[cls], cls, "class"
    log = _RESOLUTION_LOG.get()
    if log is not None:
        log.append(SiteResolution(site=site, cls=cls, strategy=rt.strategy,
                                  num_chunks=rt.num_chunks, matched_key=key,
                                  tier=tier))
    return rt, key, tier


def explain_runtime(site: str, cls: Optional[str] = None,
                    ) -> Tuple[CollectiveRuntime, str]:
    """Resolve ``site`` against the active plan; returns ``(knobs,
    matched_key)`` where ``matched_key`` is the plan key that supplied the
    knobs (``""`` = XLA defaults).  ``resolve_runtime`` additionally names
    the fallback tier that matched."""
    rt, key, _ = resolve_runtime(site, cls)
    return rt, key


def runtime_for(site: str, cls: Optional[str] = None) -> CollectiveRuntime:
    """The active knobs for a collective site.  ``site`` may be a full
    SiteId (``"fsdp.layer3.ag_params"``) or a bare site class (``"ag"``,
    ``"rs"``, ``"ar"``, ``"a2a"``, ``"p2p"``); ``cls`` is the fallback
    class a specific site degrades to when the plan has no entry at any
    of its prefixes.  XLA defaults when nothing matches."""
    return explain_runtime(site, cls)[0]


def site_scope(site: str):
    """The ``jax.named_scope`` a collective site's helper runs under:
    ``site:<SiteId>``.  It lands in the compiled HLO's ``op_name`` metadata
    of every collective and chunked matmul the helper emits (wrapped in
    ``transpose(...)`` in the backward pass), so a device trace can be read
    per site; it changes nothing that runs."""
    return jax.named_scope(f"site:{site}")


def _resolve_chunks(num_chunks, site: str, cls: Optional[str] = None) -> int:
    """Explicit ``num_chunks`` wins; ``None`` defers to the active plan."""
    return runtime_for(site, cls).num_chunks if num_chunks is None else num_chunks


class CollectiveDegradedWarning(RuntimeWarning):
    """A tuned site degrading to its monolithic/fallback collective at
    trace time.  Carries the same stable lint code as the static rule in
    ``repro.analysis.lint`` (``LAG010``: chunk count does not divide the
    payload) plus the resolved site id, so runtime warnings and static
    findings name the identical defect.  ``args[0]`` is the formatted
    message; ``site``/``code`` are machine-readable."""

    code = "LAG010"

    def __init__(self, message: str, *, site: str = ""):
        super().__init__(message)
        self.site = site


# Sites already warned about in this process: a degraded site warns once,
# not once per retrace (jit re-traces, vmap/grad passes and serving
# hot-swaps would otherwise repeat the identical message).  Tests reset
# via ``reset_degraded_warnings``.
_DEGRADED_WARNED: set = set()


def reset_degraded_warnings() -> None:
    """Clear the per-process ``CollectiveDegradedWarning`` dedupe state so
    the next degradation at any site warns again (test isolation)."""
    _DEGRADED_WARNED.clear()


def warn_degraded(site: str, detail: str, *, stacklevel: int = 3) -> None:
    """Emit the structured ``LAG010`` degradation warning for ``site``,
    once per (site, detail) per process.  ``detail`` finishes the sentence
    "collective site S: ..." — it should name what failed to divide and
    what the fallback emission is."""
    key = (site, detail)
    if key in _DEGRADED_WARNED:
        return
    _DEGRADED_WARNED.add(key)
    warnings.warn(
        CollectiveDegradedWarning(
            f"[{CollectiveDegradedWarning.code}] collective site {site!r}: "
            f"{detail}", site=site),
        stacklevel=stacklevel)


def _warn_unchunked(site: str, num_chunks: int, detail: str) -> None:
    """A tuned chunk count that does not divide the shard shape silently
    degrading to the monolithic collective is an audit hazard — name the
    site once at trace time instead."""
    warn_degraded(
        site,
        f"num_chunks={num_chunks} does not divide {detail}; emitting the "
        "unchunked collective for this site",
        stacklevel=4)


# ---------------------------------------------------------------------------
# all-gather ∘ matmul  (column-parallel matmul with sequence-sharded input)
#   x: (..., T, D) sharded on T over `axis`;  w: (D, F) sharded on F
#   y = allgather_T(x) @ w   -> (..., n*Tl, F_local)
# ---------------------------------------------------------------------------

def ag_matmul_ref(x, w):
    return x @ w


def _weight_grad(xs, gs, w):
    """``dw`` of ``xs @ w`` as one dot over every leading and row dim of
    ``xs`` (``(..., K)``) and ``gs`` (``(..., F)``): a single ``(K, F)``
    product instead of one per chunk summed into an accumulator."""
    dt = jnp.result_type(xs, gs)
    lead = tuple(range(xs.ndim - 1))
    dw = lax.dot_general(xs.astype(dt), gs.astype(dt), ((lead, lead), ((), ())))
    return dw.astype(w.dtype)


def _vary_like(vma, *arrays):
    """``arrays`` cast to vary over every manual axis in ``vma``, as the
    matmul's implicit cast would.  Done before a ``custom_vjp`` so that
    its weight cotangent has the weight's own type; the cast's transpose
    then sums over the added axes once, outside the chunk loop."""
    out = []
    for a in arrays:
        extra = tuple(ax for ax in vma if ax not in jax.typeof(a).vma)
        out.append(lax.pcast(a, extra, to="varying") if extra else a)
    return out


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _chunked_matmul(xs, w, num_chunks):
    """``xs @ w`` as ``num_chunks`` row blocks in a ``lax.map``, so the
    ring's next ``ppermute`` overlaps the blocks' matmuls.  Its backward
    forms ``dw`` once over all of ``xs``'s rows, not once per block."""
    blocks = jnp.stack(jnp.split(xs, num_chunks, axis=-2))
    ys = lax.map(lambda b: b @ w, blocks)
    return jnp.concatenate(list(ys), axis=-2)


def _chunked_matmul_fwd(xs, w, num_chunks):
    return _chunked_matmul(xs, w, num_chunks), (xs, w)


def _chunked_matmul_bwd(num_chunks, res, g):
    xs, w = res
    gb = jnp.stack(jnp.split(g, num_chunks, axis=-2))
    dxb = lax.map(lambda b: (b @ w.T).astype(xs.dtype), gb)
    return jnp.concatenate(list(dxb), axis=-2), _weight_grad(xs, g, w)


_chunked_matmul.defvjp(_chunked_matmul_fwd, _chunked_matmul_bwd)


def _ring_ag_matmul_local(x, w, *, axis: str, num_chunks: int, site: str = "ag"):
    """Per-device body: hold one sequence shard, rotate shards around the
    ring; each step multiplies the currently-held shard so communication of
    the next shard overlaps with this step's matmul.  The backward forms
    the weight-shard gradient once per held shard (``_chunked_matmul``)."""
    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    Tl = x.shape[-2]
    out_shape = x.shape[:-2] + (n * Tl, w.shape[-1])
    perm = [(j, (j - 1) % n) for j in range(n)]

    chunked = num_chunks > 1 and Tl % num_chunks == 0
    if num_chunks > 1 and not chunked:
        _warn_unchunked(site, num_chunks, f"the local sequence shard ({Tl})")

    # the accumulator varies over the same manual axes as the inputs
    vma = tuple(set(jax.typeof(x).vma) | set(jax.typeof(w).vma))
    if chunked:
        x, w = _vary_like(vma, x, w)

    def body(i, carry):
        x_cur, out = carry
        src = (idx + i) % n                 # whose shard we currently hold
        y = _chunked_matmul(x_cur, w, num_chunks) if chunked else x_cur @ w
        out = lax.dynamic_update_slice_in_dim(out, y, src * Tl, axis=-2)
        x_cur = lax.ppermute(x_cur, axis, perm)
        return (x_cur, out)

    out = lax.pcast(jnp.zeros(out_shape, x.dtype), vma, to="varying")
    _, out = lax.fori_loop(0, n, body, (x, out))
    return out


def ring_ag_matmul(x, w, mesh: Mesh, *, axis: str = "model",
                   x_spec: P, w_spec: P, out_spec: P,
                   num_chunks: int | None = None, site: str | None = None):
    site = site or "ag"
    num_chunks = _resolve_chunks(num_chunks, site, "ag")
    fn = shard_map(partial(_ring_ag_matmul_local, axis=axis,
                           num_chunks=num_chunks, site=site),
                   mesh=mesh, in_specs=(x_spec, w_spec), out_specs=out_spec)
    with site_scope(site):
        return fn(x, w)


# ---------------------------------------------------------------------------
# matmul ∘ reduce-scatter  (row-parallel matmul)
#   x: (..., T, Fl) F-sharded over `axis`; w: (Fl, D)
#   y = reduce_scatter_T( x @ w )  -> (..., T/n, D)
# ---------------------------------------------------------------------------

def mm_rs_ref(x, w):
    return x @ w


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _chunked_mm_rs(blocks, w, axis):
    """Each row block's ``b @ w`` reduce-scattered over ``axis`` in a
    ``lax.map``, so the scatter of block i overlaps the matmul of i+1.  Its
    backward gathers each block's cotangent in a ``lax.map`` beside that
    block's ``dx`` matmul, and forms ``dw`` once from all the blocks."""
    def one(b):
        y = b @ w
        return lax.psum_scatter(y, axis, scatter_dimension=y.ndim - 2, tiled=True)

    return lax.map(one, blocks)


def _chunked_mm_rs_fwd(blocks, w, axis):
    return _chunked_mm_rs(blocks, w, axis), (blocks, w)


def _chunked_mm_rs_bwd(axis, res, gys):
    blocks, w = res

    def one(gy):
        g = lax.all_gather(gy, axis, axis=gy.ndim - 2, tiled=True)
        return g, (g @ w.T).astype(blocks.dtype)

    gs, dblocks = lax.map(one, gys)
    # dw feeds only the optimizer, so XLA would defer it past the earlier
    # layers' backward and keep every layer's blocks and gathered
    # cotangents live (2.6 GB more on a 4-layer h2o-danube-1.8b step); the
    # barrier makes it complete before dx flows on
    return lax.optimization_barrier((dblocks, _weight_grad(blocks, gs, w)))


_chunked_mm_rs.defvjp(_chunked_mm_rs_fwd, _chunked_mm_rs_bwd)


def _mm_rs_local(x, w, *, axis: str, num_chunks: int, site: str = "rs"):
    n = lax.axis_size(axis)
    T = x.shape[-2]
    if num_chunks <= 1 or T % (num_chunks * n):
        if num_chunks > 1:
            _warn_unchunked(site, num_chunks,
                            f"the scatter tiling ({T} rows over {n} shards)")
        y = x @ w
        return lax.psum_scatter(y, axis, scatter_dimension=y.ndim - 2, tiled=True)
    # tile-aligned chunking: chunk i must contain rows {j·T/n + i·s ... } for
    # every destination shard j so the concatenated per-chunk scatters equal
    # the single full scatter.
    s = T // (n * num_chunks)
    lead = x.shape[:-2]
    xr = x.reshape(lead + (n, num_chunks, s, x.shape[-1]))
    blocks = jnp.moveaxis(xr, -3, 0)                     # (nc, ..., n, s, F)
    blocks = blocks.reshape((num_chunks,) + lead + (n * s, x.shape[-1]))

    vma = tuple(set(jax.typeof(x).vma) | set(jax.typeof(w).vma))
    blocks, w = _vary_like(vma, blocks, w)
    ys = _chunked_mm_rs(blocks, w, axis)
    return jnp.concatenate(list(ys), axis=-2)


def mm_reduce_scatter(x, w, mesh: Mesh, *, axis: str = "model",
                      x_spec: P, w_spec: P, out_spec: P,
                      num_chunks: int | None = None, site: str | None = None):
    site = site or "rs"
    num_chunks = _resolve_chunks(num_chunks, site, "rs")
    fn = shard_map(partial(_mm_rs_local, axis=axis, num_chunks=num_chunks,
                           site=site),
                   mesh=mesh, in_specs=(x_spec, w_spec), out_specs=out_spec)
    with site_scope(site):
        return fn(x, w)


# ---------------------------------------------------------------------------
# chunked all-to-all (MoE dispatch/combine)
#   x: (..., E, capl, D) with E sharded over `axis` on entry or exit
# ---------------------------------------------------------------------------

def _chunked_a2a_local(xl, *, axis: str, split_axis: int, concat_axis: int,
                       num_chunks: int, site: str = "a2a"):
    """Local body: one all_to_all, or ``num_chunks`` sequential a2a's over
    the trailing feature dim (reused by ``chunked_all_to_all`` and the
    explicit expert-parallel MoE FFN)."""
    with site_scope(site):
        if num_chunks <= 1 or xl.shape[-1] % num_chunks:
            if num_chunks > 1:
                _warn_unchunked(site, num_chunks,
                                f"the trailing feature dim ({xl.shape[-1]})")
            return lax.all_to_all(xl, axis, split_axis, concat_axis,
                                  tiled=True)
        blocks = jnp.stack(jnp.split(xl, num_chunks, axis=-1))
        ys = lax.map(lambda b: lax.all_to_all(b, axis, split_axis,
                                              concat_axis, tiled=True), blocks)
        return jnp.concatenate(list(ys), axis=-1)


def chunked_all_to_all(x, mesh: Mesh, *, axis: str = "model",
                       split_axis: int, concat_axis: int,
                       x_spec: P, out_spec: P, num_chunks: int | None = None,
                       site: str | None = None):
    """lax.all_to_all decomposed into ``num_chunks`` sequential a2a's over
    the trailing feature dim, so expert FFN compute on early chunks overlaps
    the transfer of later ones (the EP dual-batch pattern).  ``num_chunks=
    None`` (default) defers to the active tuned plan's knobs for ``site``
    (falling back to the ``a2a`` site class)."""
    site = site or "a2a"
    num_chunks = _resolve_chunks(num_chunks, site, "a2a")
    local = partial(_chunked_a2a_local, axis=axis, split_axis=split_axis,
                    concat_axis=concat_axis, num_chunks=num_chunks, site=site)
    fn = shard_map(local, mesh=mesh, in_specs=(x_spec,), out_specs=out_spec)
    return fn(x)


# ---------------------------------------------------------------------------
# plain helpers used by the trainer (gradient sync in explicit-DP mode)
# ---------------------------------------------------------------------------

def psum_tree(tree, axis: str):
    return jax.tree.map(lambda a: lax.psum(a, axis), tree)


def psum_tree_chunked(tree, axis: str, *, num_chunks: int | None = None,
                      site: str = "acc"):
    """``psum_tree`` decomposed into ``num_chunks`` sequential partial
    psums over each leaf's leading dim, so the reduce of early chunks
    overlaps whatever compute the scheduler has in flight — the ACCO
    accumulation-overlap gradient sync (``acc.step{k}.rs_grads`` sites)
    and the Streaming-DiLoCo outer sync (``outer.round{r}.sync.*``).
    ``num_chunks=None`` defers to the active tuned plan's knobs for
    ``site`` (falling back to the ``acc`` site class); leaves whose
    leading dim the chunk count does not divide (scalars included) reduce
    whole."""
    num_chunks = _resolve_chunks(num_chunks, site, site_class(site))

    def one(a):
        if num_chunks <= 1 or a.ndim == 0:
            return lax.psum(a, axis)
        if a.shape[0] % num_chunks:
            _warn_unchunked(site, num_chunks,
                            f"the leading dim ({a.shape[0]}) of a grad leaf")
            return lax.psum(a, axis)
        blocks = jnp.stack(jnp.split(a, num_chunks, axis=0))
        ys = lax.map(lambda b: lax.psum(b, axis), blocks)
        return jnp.concatenate(list(ys), axis=0)

    with site_scope(site):
        return jax.tree.map(one, tree)
