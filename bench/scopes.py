"""A traced run's device time read by the program's named scopes.

The program puts each collective site's helper under ``site:<SiteId>`` and
the train step's layers under the component scopes (``jax.named_scope``).
The compiled step's text carries them in each instruction's ``op_name``,
and the trace names each operation by its instruction, so every operation
of the window of ``bench/trace.py`` can be put down to the innermost site
and the innermost component on its path, in the backward pass when a
``transpose(`` wraps them (``scoped``).

The per-layer metrics that read it call ``read(ctx)``: once a traced
run it finds the run's trace, compiles the cell's step again as the run
compiled it (the persistent compilation cache holds it by then) to get
its text and the sites it resolved, writes the text beside the trace
(``step.hlo.txt``), and prints the step's instruction count, its busy
time by scope on each chip and one line per site and direction.

The scope names read here are written here, not imported from the
program: a scope renamed there shows as a metric going null instead of
silently following the rename.
"""
from __future__ import annotations

import glob
import os
import re
import sys

from bench import trace as TR

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMPONENTS = ("embed", "attention", "mlp", "moe", "loss", "optimizer",
              "layer_params")
SITE = "site:"
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OPCODE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?\s([a-z][\w\-]*)\(")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) .*\{\s*$")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OP_NAME = re.compile(r'\bmetadata=\{[^}]*?\bop_name="([^"]*)"')
_WRAPPED = re.compile(r"^(?:[\w\-]+\()*(.*?)\)*$")    # jvp(mlp) -> mlp


def instructions(hlo_text: str) -> set:
    """The names of the instructions in a compiled module's text."""
    return {m.group(1) for m in map(_INSTRUCTION.match, hlo_text.splitlines())
            if m}


def op_names(hlo_text: str) -> dict:
    """``{instruction name: op_name}`` of every instruction of a compiled
    module's text that carries an ``op_name``; both the ``%name = `` and
    the ``name = `` forms."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            o = _OP_NAME.search(line, m.end())
            if o:
                out[m.group(1)] = o.group(1)
    return out


def collective_names(hlo_text: str) -> set:
    """The instructions of a compiled module's text that are collectives
    by what they do, whatever their name: those whose opcode is one
    (``reduce_scatter.15``, which the JAX lowering named after its
    primitive and XLA left synchronous), and fusions or async wrappers
    that call a computation holding one (``fusion.18`` calling an
    ``all-reduce-scatter`` computation)."""
    holding, callers, out, comp = set(), [], set(), None
    for line in hlo_text.splitlines():
        m = _OPCODE.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            comp = c.group(1) if c else comp
            continue
        if TR.is_collective(m.group(2)):
            out.add(m.group(1))
            holding.add(comp)
        called = _CALLS.search(line)
        if called:
            callers.append((m.group(1), called.group(1)))
    return out | {name for name, c in callers if c in holding}


def scope_of(op_name: str):
    """``(site, component, backward)`` of an ``op_name`` path: the
    innermost ``site:`` scope (its SiteId) and the innermost of
    ``COMPONENTS`` (``None`` where there is none), and whether a
    ``transpose(`` wraps them.  The path's last part, the primitive, is
    no scope."""
    site = comp = None
    for part in op_name.split("/")[:-1]:
        name = _WRAPPED.match(part).group(1)
        if name.startswith(SITE):
            site = name[len(SITE):]
        elif name in COMPONENTS:
            comp = name
    return site, comp, "transpose(" in op_name


def _window(events: dict):
    """``(lo, hi)`` of the window of ``trace.reduce``, or ``None``."""
    host = events["host"]
    data = [h for h in host if h[0] == "bench.data"]
    sync = [h for h in host if h[0] == "bench.sync"]
    if not data or not sync or not events["devices"]:
        return None
    return data[0][1], sync[-1][2]


def scoped(events: dict, names: dict, collectives=()) -> dict:
    """The window of ``trace.reduce`` read by scope, per device, in
    seconds.

    Each busy operation (the ``XLA Ops`` line, loops and calls left out
    as in ``reduce``) goes to one part: ``collective_s[k]`` when it is a
    collective (by its name, as in ``reduce``, or in ``collectives``, the
    step's ``collective_names``), else ``compute_s[k]``; ``k`` is its
    innermost component, else ``"site"`` under a site alone, else
    ``"none"``.  Where operations overlap, the one that started first
    keeps the overlap, so the parts add up to ``busy_s``.  ``unscoped_s``:
    the operations with neither a component nor a site.
    ``sites[site][fwd|bwd]``: the union of the site's collectives on both
    lines (``collective_s``), the part of it in which no other operation
    runs on the device (``exposed_s``), and its collectives started
    (``ops``); ``site_exposed_s`` the same for all sites at once.  Empty
    when no operation names a scope (a program without them), or when
    there is no window."""
    tags = {n: scope_of(o) for n, o in names.items()}
    window = _window(events)
    if window is None or not any(s or c for s, c, _ in tags.values()):
        return {}
    lo, hi = window
    none = (None, None, False)
    collectives = set(collectives)

    def collective(name):
        return name in collectives or TR.is_collective(name)

    per = {}
    for dev, lines in events["devices"].items():
        ops = sorted(([n, s, e] for n, s, e in lines["ops"]
                      if e > lo and s < hi
                      and not n.startswith(TR.CONTAINERS)),
                     key=lambda o: o[1])
        keys = ("site", "none") + COMPONENTS
        parts = {"compute_s": dict.fromkeys(keys, 0.0),
                 "collective_s": dict.fromkeys(keys, 0.0)}
        unscoped, covered = 0.0, lo
        for n, s, e in ops:
            t = max(0.0, min(e, hi) - max(s, covered))
            covered = max(covered, min(e, hi))
            site, comp, _ = tags.get(n, none)
            part = "collective_s" if collective(n) else "compute_s"
            parts[part][comp or ("site" if site else "none")] += t * 1e-9
            if not (site or comp):
                unscoped += t * 1e-9
        other = TR._union(TR._clip([[s, e] for n, s, e in ops
                                    if not collective(n)], lo, hi))
        flights, started = {}, {}
        for line, count in ((ops, True), (lines["async"], False)):
            for n, s, e in line:
                site, _, bwd = tags.get(n, none)
                if not (site and collective(n)):
                    continue
                key = (site, "bwd" if bwd else "fwd")
                flights.setdefault(key, []).append([s, e])
                if count and s >= lo and "-done" not in n:
                    started[key] = started.get(key, 0) + 1
        sites = {}
        for (site, way), iv in sorted(flights.items()):
            u = TR._union(TR._clip(iv, lo, hi))
            sites.setdefault(site, {})[way] = {
                "collective_s": TR._length(u) * 1e-9,
                "exposed_s": TR._length(TR._minus(u, other)) * 1e-9,
                "ops": started.get((site, way), 0)}
        every = TR._union(TR._clip([i for iv in flights.values() for i in iv],
                                   lo, hi))
        busy = TR._union(TR._clip([[s, e] for _, s, e in ops], lo, hi))
        per[dev] = dict(parts, busy_s=TR._length(busy) * 1e-9,
                        unscoped_s=unscoped, sites=sites,
                        site_exposed_s=TR._length(TR._minus(every, other))
                        * 1e-9)
    steps = sum(1 for h in events["host"]
                if h[0] == "bench.dispatch" and lo <= h[1] < hi)
    return {"window_s": (hi - lo) * 1e-9, "steps": steps, "devices": per}


def per_site(scoped: dict) -> dict:
    """``{site: {fwd|bwd: {collective_ms, exposed_ms, ops}}}`` of
    ``scoped``, a step, averaged over its devices."""
    if not scoped or not scoped["steps"]:
        return {}
    n = len(scoped["devices"]) * scoped["steps"]
    out = {}
    for d in scoped["devices"].values():
        for site, ways in d["sites"].items():
            for way, v in ways.items():
                o = out.setdefault(site, {}).setdefault(
                    way, {"collective_ms": 0.0, "exposed_ms": 0.0, "ops": 0})
                o["collective_ms"] += 1e3 * v["collective_s"] / n
                o["exposed_ms"] += 1e3 * v["exposed_s"] / n
                o["ops"] += v["ops"] / n
    return out


def part_ms(scoped: dict, parts, *, collectives: bool = True):
    """Device milliseconds a step of the operations put down to ``parts``
    (components, ``"site"`` or ``"none"``), collectives among them unless
    ``collectives`` is false, averaged over the devices; ``None`` where
    none ran (the program has no such scope)."""
    if not scoped or not scoped["steps"]:
        return None
    kinds = ("compute_s", "collective_s") if collectives else ("compute_s",)
    devs = scoped["devices"].values()
    s = sum(d[k][p] for d in devs for k in kinds for p in parts)
    return 1e3 * s / len(devs) / scoped["steps"] if s > 0 else None


def compiled_step(conf: dict, traffic: dict, cell: dict):
    """The text of the cell's train step compiled as a run of
    ``bench/traffic/train.py`` compiles it (the same mesh, plan, layout
    and arguments, given as shapes), and ``{site: (strategy,
    num_chunks)}`` of the sites it resolved while tracing."""
    import contextlib
    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from bench.traffic.train import model_config
    from repro.launch.mesh import make_mesh
    from repro.models import model as M
    from repro.optim import adamw
    from repro.parallel import collectives as C
    from repro.parallel import constraints as CT
    from repro.parallel import sharding as SH
    from repro.train.trainer import TrainConfig, jit_train_step

    cfg = model_config(conf)
    o = traffic["optimizer"]
    mesh = make_mesh(tuple(conf["mesh"]), ("data", "model"))
    tcfg = TrainConfig(
        opt=adamw.AdamWConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"],
                              eps=o["eps"], weight_decay=o["weight_decay"],
                              clip_norm=o["clip_norm"]),
        warmup=o["warmup"], total_steps=o["total_steps"])
    with contextlib.ExitStack() as scope:
        scope.enter_context(jax.set_mesh(mesh))
        scope.enter_context(CT.use_axes(("data",), "model"))
        if cell["plan"] != "none":
            from repro.core import extract_workload, tune
            from repro.core.extract import parse_parallel

            scope.enter_context(tune(extract_workload(
                cfg, parse_parallel(cell["plan"]), seq=traffic["seq"],
                global_batch=traffic["batch"]), "tpu-v5e", seed=0).applied())
            tcfg = dataclasses.replace(tcfg, sited_mesh=mesh)
        shapes = jax.eval_shape(lambda k: M.init_params(cfg, k),
                                jax.random.key(0))
        params = jax.tree.map(
            lambda a, spec: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, spec)),
            shapes, SH.param_specs(shapes, mesh))
        rep = NamedSharding(mesh, P())
        count = jax.eval_shape(lambda: adamw.init_state(shapes))["count"]
        opt = {"mu": params, "nu": params, "count": jax.ShapeDtypeStruct(
            count.shape, count.dtype, sharding=rep)}
        shape = (traffic["batch"], traffic["seq"])
        batch = {k: jax.ShapeDtypeStruct(shape, dt, sharding=rep)
                 for k, dt in (("tokens", jnp.int32), ("targets", jnp.int32),
                               ("mask", jnp.float32))}
        with C.record_site_resolutions() as resolved:
            lowered = jit_train_step(cfg, tcfg, params, opt).lower(
                params, opt, batch, jnp.asarray(0, jnp.int32))
    return lowered.compile().as_text(), {
        r.site: (r.strategy, r.num_chunks) for r in resolved}


def latest_trace(root: str = ROOT):
    """The trace directory of ``bench_out/<cell>/trace`` whose
    ``.xplane.pb`` is newest: the traced run in progress wrote it last."""
    found = glob.glob(os.path.join(root, "bench_out", "*", "trace", "**",
                                   "*.xplane.pb"), recursive=True)
    if not found:
        return None
    newest = max(found, key=os.path.getmtime)
    return newest[:newest.index(os.sep + "trace" + os.sep) + 6]


def read(ctx: dict) -> dict:
    """``scoped`` of the traced run that ``ctx`` (the per-layer metrics'
    context, one a run) describes, computed by the first metric that asks
    and kept in ``ctx``; empty where the run has no device trace or its
    program no scopes."""
    if "scoped" not in ctx:
        trace_dir = latest_trace() if ctx.get("trace") else None
        ctx["scoped"] = _read(trace_dir, ctx) if trace_dir else {}
    return ctx["scoped"]


def _say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _read(trace_dir: str, ctx: dict) -> dict:
    hlo, resolved = compiled_step(ctx["conf"], ctx["traffic"], ctx["cell"])
    with open(os.path.join(trace_dir, "step.hlo.txt"), "w") as f:
        f.write(hlo)
    events = TR.load(TR.xplane_file(trace_dir))
    known = instructions(hlo)
    ops = [o for lines in events["devices"].values() for o in lines["ops"]
           if not o[0].startswith(TR.CONTAINERS)]
    unknown = sum(e - s for n, s, e in ops if n not in known) / max(
        sum(e - s for _, s, e in ops), 1)
    _say(f"compiled step instructions: {len(known)}; share of the traced "
         f"operations' time in others: {100 * unknown:.3f}%")
    if unknown > 0.01:      # not the step that ran
        return {}
    out = scoped(events, op_names(hlo), collective_names(hlo))
    for dev, d in sorted(out.get("devices", {}).items()):
        ms = 1e3 / out["steps"]
        _say(f"{dev} by scope, ms a step: busy {d['busy_s'] * ms:.3f}; " +
             "; ".join(f"{part[:-2]} " + ", ".join(
                 f"{k} {v * ms:.3f}" for k, v in d[part].items() if v)
                 for part in ("compute_s", "collective_s")) +
             f"; unscoped {d['unscoped_s'] * ms:.3f}")
    sited = per_site(out)
    for site in sorted(set(sited) | set(resolved)):
        how, chunks = resolved.get(site, ("-", "-"))
        knobs = f"; resolved {how}, num_chunks {chunks}"
        if site not in sited:
            _say(f"site {site}: no collective in the trace{knobs}")
        for way, v in sorted(sited.get(site, {}).items()):
            _say(f"site {site} {way}: collective {v['collective_ms']:.3f} "
                 f"ms, exposed {v['exposed_ms']:.3f} ms, {v['ops']:g} "
                 f"collective ops a step{knobs}")
    return out
