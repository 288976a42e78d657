"""Synthetic per-site exercisers: verify a plan with no model required.

``overlap.trace_and_verify`` needs a traced program that consults the
plan's sites.  The real programs (trainer, serving engines) are heavy and
shape-constrained; this module instead builds, for every tuned site in a
plan, a minimal ``shard_map`` program that calls the *production chunked
builder* for the site's collective kind at the site's exact SiteId —
``ring_ag_matmul`` for allgather sites, ``mm_reduce_scatter`` for
reducescatter, ``chunked_all_to_all`` for alltoall, ``psum_tree_chunked``
for allreduce, the pipeline's chunked ppermute for permute — with payload
shapes sized so the plan's resolved chunk count divides evenly.  Tracing
that program under the plan and judging it answers "does this artifact
materialize when its sites are exercised?" for any plan, which is what
``python -m repro.analysis verify-overlap`` and the CI gate run over the
zoo's tuned plans.

A DEGRADED/ABSENT verdict here is therefore a property of the *plan and
resolution machinery* (shadowed entries, nc > MAX payload, plan not
installed), never of payload divisibility — the exerciser removes that
variable by construction.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

from repro.analysis.overlap import OverlapReport, trace_and_verify
from repro.parallel import collectives as C

# Workload IR comm kind -> the site-class string its production builder
# resolves with (collectives.runtime_for's cls argument)
KIND_CLS = {"allgather": "ag", "reducescatter": "rs", "allreduce": None,
            "alltoall": "a2a", "permute": "p2p"}


def _site_specs(plan) -> List[Tuple[str, str, int]]:
    """(site, kind, resolved nc) per unique tuned site, resolved exactly
    as the exercisers will resolve at trace time."""
    rt = plan.runtime_plan()
    specs, seen = [], set()
    with C.use_runtime_plan(rt):
        for row in plan.sites:
            sid = row.get("site") or row["name"]
            if sid in seen or row["kind"] not in KIND_CLS:
                continue
            seen.add(sid)
            cls = KIND_CLS[row["kind"]] or C.site_class(sid)
            knobs, _key, tier = C.resolve_runtime(sid, cls)
            if tier == "default":
                continue       # untuned site: nothing to materialize
            specs.append((sid, row["kind"], knobs.num_chunks))
    return specs


def _exercise_one(mesh, sid: str, kind: str, nc: int, n: int):
    """One builder call at ``sid`` with shapes the resolved ``nc``
    divides.  Runs inside the traced function."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    nc = max(1, nc)
    if kind == "allgather":
        # x (n*nc, 4) T-sharded, w (4, n*2) F-sharded: local shard nc rows
        x = jnp.ones((n * nc, 4), jnp.float32)
        w = jnp.ones((4, n * 2), jnp.float32)
        return C.ring_ag_matmul(x, w, mesh, axis="x",
                                x_spec=P("x", None), w_spec=P(None, "x"),
                                out_spec=P(None, "x"), site=sid)
    if kind == "reducescatter":
        # x (n*nc, n*4) F-sharded: scatter tiling n*nc rows over n shards
        x = jnp.ones((n * nc, n * 4), jnp.float32)
        w = jnp.ones((n * 4, 8), jnp.float32)
        return C.mm_reduce_scatter(x, w, mesh, axis="x",
                                   x_spec=P(None, "x"), w_spec=P("x", None),
                                   out_spec=P("x", None), site=sid)
    if kind == "alltoall":
        # local (n, 2, nc): split axis 0 divisible by n, trailing by nc
        x = jnp.ones((n * n, 2, nc), jnp.float32)
        return C.chunked_all_to_all(x, mesh, axis="x", split_axis=0,
                                    concat_axis=1,
                                    x_spec=P("x", None, None),
                                    out_spec=P("x", None, None), site=sid)
    if kind == "allreduce":
        # leaf leading dim nc per device: every chunk divides
        g = jnp.ones((n * nc, 4), jnp.float32)

        def body(gl):
            return C.psum_tree_chunked({"g": gl}, "x", site=sid)["g"]

        return jax.shard_map(body, mesh=mesh, in_specs=(P("x", None),),
                             out_specs=P())(g)
    if kind == "permute":
        from repro.parallel.pipeline import _chunked_ppermute

        perm = [(j, (j + 1) % n) for j in range(n)]
        x = jnp.ones((n * 2, nc), jnp.float32)

        def body(xl):
            rt = C.runtime_for(sid, "p2p")
            return _chunked_ppermute(xl, "x", perm,
                                     num_chunks=rt.num_chunks, site=sid)

        return jax.shard_map(body, mesh=mesh, in_specs=(P("x", None),),
                             out_specs=P("x", None))(x)
    raise ValueError(f"no exerciser for comm kind {kind!r}")


def exercise_plan(plan, *, install: bool = True,
                  mesh=None) -> OverlapReport:
    """Trace one synthetic program exercising every tuned site of ``plan``
    (each through its production chunked builder, divisible payloads) and
    return the overlap verdicts.  ``install=False`` traces without the
    plan — the deliberate-ABSENT control.  ``mesh`` defaults to every
    local device on one ``"x"`` axis."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    if mesh is None:
        mesh = Mesh(np.asarray(jax.devices()), ("x",))
    (n,) = mesh.devices.shape
    specs = _site_specs(plan)

    def program():
        return [_exercise_one(mesh, sid, kind, nc, n)
                for sid, kind, nc in specs]

    return trace_and_verify(plan, program, install=install)


def exercise_and_report(plan, *, allow_degraded: bool = False,
                        label: str = "plan") -> Tuple[bool, str]:
    """(ok, printable report) — the verify-overlap CLI/CI-gate body."""
    report = exercise_plan(plan)
    ok = report.ok(allow_degraded=allow_degraded)
    text = report.format().replace("overlap[jaxpr]", f"overlap[{label}]", 1)
    return ok, text


__all__ = ["KIND_CLS", "exercise_and_report", "exercise_plan"]
