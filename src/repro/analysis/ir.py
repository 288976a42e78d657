"""Collective/compute op-graph extraction from traced jaxprs and
post-SPMD HLO text.

This is the mechanical layer of ``repro.analysis``: it does not know
about plans or sites, it only answers "what collective ops does this
artifact contain, inside which loops, with which trip counts, next to
which compute".  The overlap verifier (``analysis.overlap``) attributes
that structure back to dotted SiteIds via the active runtime plan's
trace-time resolution log; the dry-run roofline
(``launch.dryrun.parse_collective_bytes``) delegates its byte accounting
to :func:`collective_bytes` so both front ends share one op table.

The op table (:data:`COLLECTIVE_OPS`) maps the canonical Workload IR
comm kinds (``workload.COMM_KINDS``) to their spellings in each artifact:

====================  ============================  =======================
kind                  post-SPMD HLO opcode(s)       jaxpr primitive(s)
====================  ============================  =======================
``allgather``         ``all-gather``                ``all_gather``
``allreduce``         ``all-reduce``                ``psum`` / ``psum2``
``reducescatter``     ``reduce-scatter``            ``reduce_scatter``
``alltoall``          ``all-to-all``                ``all_to_all``
``permute``           ``collective-permute``        ``ppermute``
====================  ============================  =======================

Every HLO opcode also appears in async form as ``<op>-start`` /
``<op>-done`` pairs; the walkers count the ``-start`` (or the bare op)
and skip the ``-done`` so async pairs are never double-counted.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

# canonical kind -> artifact spellings.  Inside a shard_map body jax 0.9
# binds ``lax.psum`` as ``psum_invariant``; ``psum``/``psum2`` are its
# spellings elsewhere.
COLLECTIVE_OPS: Dict[str, Dict[str, Tuple[str, ...]]] = {
    "allgather": {"hlo": ("all-gather",), "jaxpr": ("all_gather",)},
    "allreduce": {"hlo": ("all-reduce",), "jaxpr": ("psum", "psum2", "psum_invariant")},
    "reducescatter": {
        "hlo": ("reduce-scatter",),
        "jaxpr": ("reduce_scatter", "psum_scatter"),
    },
    "alltoall": {"hlo": ("all-to-all",), "jaxpr": ("all_to_all",)},
    "permute": {"hlo": ("collective-permute",), "jaxpr": ("ppermute",)},
}

# flat reverse lookups
HLO_COLLECTIVE_KIND: Dict[str, str] = {
    op: kind for kind, spec in COLLECTIVE_OPS.items() for op in spec["hlo"]
}
JAXPR_COLLECTIVE_KIND: Dict[str, str] = {
    p: kind for kind, spec in COLLECTIVE_OPS.items() for p in spec["jaxpr"]
}

# the overlap-eligible compute ops (what a chunk loop interleaves with)
JAXPR_COMPUTE_PRIMS = ("dot_general", "conv_general_dilated")
HLO_COMPUTE_OPS = ("dot", "convolution", "fusion")

ASYNC_SUFFIXES = ("-start", "-done")

_HLO_DTYPE_BYTES = {
    "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f64": 8,
    "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8, "f8e4m3": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2,
}

# one collective instruction: optional tuple-open paren before the result
# shape (async starts return tuples), then the opcode with an optional
# async suffix, immediately followed by its operand list
_HLO_COLLECTIVE_RE = re.compile(
    r"=\s*\(?\s*([a-z0-9]+)\[([\d,]*)\][^=]*?"
    r"\b(" + "|".join(sorted(HLO_COLLECTIVE_KIND, key=len, reverse=True))
    + r")(-start|-done)?\(")


@dataclass(frozen=True)
class CollectiveOp:
    """One collective instruction in the artifact."""

    kind: str        # canonical kind (COLLECTIVE_OPS key)
    raw: str         # primitive/opcode as spelled in the artifact
    bytes: float = 0.0   # result bytes (HLO only; 0.0 for jaxpr ops)
    trip: int = 1    # innermost enclosing loop trip (1 = not in a loop)
    depth: int = 0   # loop nesting depth


@dataclass(frozen=True)
class ChunkLoop:
    """One loop (jaxpr ``scan``/``while``, HLO ``while``) summarized by
    what one iteration of its body contains — the shape the overlap
    verifier matches tuned chunk counts against."""

    trip: int                    # trip count; 0 = not statically known
    kinds: Tuple[str, ...]       # collective kinds in the body (sorted)
    n_collectives: int           # collective ops per iteration
    has_compute: bool            # dot/conv (HLO: fusion) in the body
    depth: int                   # nesting depth of the loop itself
    source: str = "scan"         # "scan" | "while"


@dataclass
class OpGraph:
    """The extracted collective/compute structure of one artifact."""

    source: str                          # "jaxpr" | "hlo"
    collectives: List[CollectiveOp] = field(default_factory=list)
    loops: List[ChunkLoop] = field(default_factory=list)
    compute_ops: int = 0

    def count(self, kind: str) -> int:
        """Number of collective ops of ``kind`` (loop bodies count once —
        multiply by ``trip`` for dynamic instances)."""
        return sum(1 for c in self.collectives if c.kind == kind)

    def chunk_loops(self, kind: Optional[str], *, trip: Optional[int] = None,
                    has_compute: Optional[bool] = None) -> List[ChunkLoop]:
        """Loops whose body contains a ``kind`` collective (``kind=None``:
        compute-only loops with no collective at all), optionally filtered
        by exact ``trip`` and by whether the body also computes."""
        out = []
        for lp in self.loops:
            if kind is None:
                if lp.kinds or not lp.has_compute:
                    continue
            elif kind not in lp.kinds:
                continue
            if trip is not None and lp.trip != trip:
                continue
            if has_compute is not None and lp.has_compute != has_compute:
                continue
            out.append(lp)
        return out


# ---------------------------------------------------------------------------
# jaxpr walker
# ---------------------------------------------------------------------------

@dataclass
class _BodyStats:
    kinds: set = field(default_factory=set)
    n_collectives: int = 0
    compute: int = 0

    def merge(self, other: "_BodyStats") -> None:
        self.kinds |= other.kinds
        self.n_collectives += other.n_collectives
        self.compute += other.compute


def _sub_jaxprs(params: Dict):
    """Every sub-jaxpr reachable from one equation's params (pjit bodies,
    shard_map bodies, cond branches, custom-derivative calls, ...)."""
    for v in params.values():
        vs = v if isinstance(v, (list, tuple)) else (v,)
        for w in vs:
            if hasattr(w, "eqns"):            # raw Jaxpr
                yield w
            elif hasattr(w, "jaxpr"):         # ClosedJaxpr
                yield w.jaxpr

def _walk_jaxpr(jaxpr, depth: int, trip: int, g: OpGraph) -> _BodyStats:
    stats = _BodyStats()
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim in ("scan", "while"):
            body = eqn.params["jaxpr"] if prim == "scan" else (
                eqn.params["body_jaxpr"])
            body = body.jaxpr if hasattr(body, "jaxpr") else body
            length = int(eqn.params.get("length") or 0) if prim == "scan" else 0
            inner = _walk_jaxpr(body, depth + 1, length or trip, g)
            g.loops.append(ChunkLoop(
                trip=length, kinds=tuple(sorted(inner.kinds)),
                n_collectives=inner.n_collectives,
                has_compute=inner.compute > 0, depth=depth, source=prim))
            stats.merge(inner)
        elif prim in JAXPR_COLLECTIVE_KIND:
            kind = JAXPR_COLLECTIVE_KIND[prim]
            g.collectives.append(CollectiveOp(
                kind=kind, raw=prim, trip=trip or 1, depth=depth))
            stats.kinds.add(kind)
            stats.n_collectives += 1
        elif prim in JAXPR_COMPUTE_PRIMS:
            stats.compute += 1
        else:
            for sub in _sub_jaxprs(eqn.params):
                stats.merge(_walk_jaxpr(sub, depth, trip, g))
    return stats


def graph_from_jaxpr(jaxpr) -> OpGraph:
    """Extract the op graph from a (closed) jaxpr — typically
    ``jax.make_jaxpr(fn)(*args)`` of a plan-aware model builder.  Loop
    bodies are walked recursively through every higher-order primitive
    (``pjit``, ``shard_map``, ``scan``, ``while``, ``cond``, custom
    derivative calls); ``lax.map``/``lax.fori_loop`` appear as ``scan``
    with a static ``length``, which is exactly where tuned chunk counts
    materialize."""
    inner = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
    g = OpGraph(source="jaxpr")
    top = _walk_jaxpr(inner, 0, 0, g)
    g.compute_ops = top.compute
    return g


# ---------------------------------------------------------------------------
# HLO text walker
# ---------------------------------------------------------------------------

# header = name + parameter list + "->" + result type + "{".  The parameter
# list may itself contain parenthesized tuple types (while bodies take the
# loop carry as one tuple param), so only the prefix is matched and the
# "->"/"{" tail is checked separately.
_HLO_COMP_HEAD = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(")
_HLO_WHILE = re.compile(
    r"\bwhile\(.*?\bcondition=%?([\w.\-]+).*?\bbody=%?([\w.\-]+)"
    r"|\bwhile\(.*?\bbody=%?([\w.\-]+).*?\bcondition=%?([\w.\-]+)")
_HLO_CALL_REFS = re.compile(
    r"\b(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_HLO_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_HLO_CONST_INT = re.compile(r"\bconstant\((\d+)\)")


def _hlo_computations(hlo_text: str) -> Dict[str, List[str]]:
    """Split HLO text into ``{computation_name: [instruction lines]}``."""
    comps: Dict[str, List[str]] = {}
    cur: Optional[List[str]] = None
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if cur is None:
            m = _HLO_COMP_HEAD.match(line)
            if m and "->" in line and stripped.endswith("{"):
                cur = comps.setdefault(m.group(1), [])
        elif stripped.startswith("}"):
            cur = None
        elif stripped:
            cur.append(stripped)
    return comps


def _shape_bytes(dtype: str, shape: str) -> float:
    if dtype not in _HLO_DTYPE_BYTES:
        return 0.0
    n = 1
    for d in shape.split(","):
        if d.strip().isdigit():
            n *= int(d)
    return float(n * _HLO_DTYPE_BYTES[dtype])


def _line_collectives(line: str):
    """(kind, raw, bytes) for each counted collective on one instruction
    line — async ``-done`` halves are skipped (their ``-start`` counted)."""
    for m in _HLO_COLLECTIVE_RE.finditer(line):
        dtype, shape, base, suffix = m.groups()
        if suffix == "-done":
            continue
        yield (HLO_COLLECTIVE_KIND[base], base + (suffix or ""),
               _shape_bytes(dtype, shape))


def _line_has_compute(line: str) -> bool:
    return any(f" {op}(" in line or f"= {op}(" in line
               for op in HLO_COMPUTE_OPS)


def _while_refs(line: str):
    m = _HLO_WHILE.search(line)
    if not m:
        return None
    cond, body, body2, cond2 = m.groups()
    return (cond or cond2), (body or body2)


def _comp_closure(name: str, comps: Dict[str, List[str]],
                  seen: Optional[set] = None) -> List[str]:
    """Instruction lines of ``name`` plus every computation it references
    (nested whiles, fusions, reducers), cycle-safe."""
    seen = set() if seen is None else seen
    if name in seen or name not in comps:
        return []
    seen.add(name)
    lines = list(comps[name])
    for line in comps[name]:
        for ref in _HLO_CALL_REFS.findall(line):
            lines += _comp_closure(ref, comps, seen)
        bm = _HLO_BRANCHES.search(line)
        if bm:
            for ref in bm.group(1).split(","):
                lines += _comp_closure(ref.strip().lstrip("%"), comps, seen)
    return lines


def _while_trip(cond_lines: List[str]) -> int:
    """Best-effort trip count of a counted HLO while loop: the largest
    integer constant in its condition computation (a scan-lowered loop
    compares the induction variable against the trip count there).
    0 when the bound is not statically visible."""
    consts = [int(x) for line in cond_lines
              for x in _HLO_CONST_INT.findall(line)]
    return max(consts) if consts else 0


def graph_from_hlo(hlo_text: str) -> OpGraph:
    """Extract the op graph from post-SPMD HLO text
    (``compiled.as_text()``).  Every ``while`` instruction becomes a
    :class:`ChunkLoop` summarizing its body's transitive collective and
    compute content, with the trip count recovered from the loop
    condition when XLA kept it statically visible; collectives inside
    loop bodies carry that trip, top-level ones ``trip=1``."""
    comps = _hlo_computations(hlo_text)
    g = OpGraph(source="hlo")

    # while nesting: body computations reachable from other whiles' bodies
    whiles = []           # (cond_name, body_name)
    for lines in comps.values():
        for line in lines:
            refs = _while_refs(line)
            if refs:
                whiles.append(refs)
    body_names = {b for _, b in whiles}
    depth_of: Dict[str, int] = {}

    def depth_for(body: str, seen=()) -> int:
        if body in depth_of:
            return depth_of[body]
        if body in seen:
            return 0
        d = 0
        for cond2, body2 in whiles:
            if body2 == body:
                continue
            closure = set()
            _comp_closure(body2, comps, closure)
            if body in closure:
                d = max(d, depth_for(body2, seen + (body,)) + 1)
        depth_of[body] = d
        return d

    for cond_name, body_name in whiles:
        body_lines = _comp_closure(body_name, comps)
        kinds: set = set()
        n_coll = 0
        compute = False
        for line in body_lines:
            for kind, _raw, _b in _line_collectives(line):
                kinds.add(kind)
                n_coll += 1
            compute = compute or _line_has_compute(line)
        g.loops.append(ChunkLoop(
            trip=_while_trip(comps.get(cond_name, [])),
            kinds=tuple(sorted(kinds)), n_collectives=n_coll,
            has_compute=compute, depth=depth_for(body_name), source="while"))

    # collectives: entry + every computation, annotated with the loop they
    # live in (if any)
    trip_of_body = {b: _while_trip(comps.get(c, [])) for c, b in whiles}
    for name, lines in comps.items():
        in_loop = name in body_names
        trip = trip_of_body.get(name, 0) if in_loop else 1
        dep = (depth_of.get(name, 0) + 1) if in_loop else 0
        for line in lines:
            if _line_has_compute(line):
                g.compute_ops += 1
            for kind, raw, nbytes in _line_collectives(line):
                g.collectives.append(CollectiveOp(
                    kind=kind, raw=raw, bytes=nbytes,
                    trip=trip or 1, depth=dep))
    return g


# ---------------------------------------------------------------------------
# dry-run byte accounting (shared with launch.dryrun)
# ---------------------------------------------------------------------------

def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum result bytes of every collective in (post-SPMD) HLO text, keyed
    by base opcode plus a total ``"count"``.  Recognizes the full family
    including async ``-start``/``-done`` pairs, counting each async pair
    once (on its ``-start``) — the dry-run roofline's collective term."""
    out: Dict[str, float] = {op: 0.0 for op in HLO_COLLECTIVE_KIND}
    out["count"] = 0
    for line in hlo_text.splitlines():
        for _kind, raw, nbytes in _line_collectives(line):
            base = raw
            for suf in ASYNC_SUFFIXES:
                if base.endswith(suf):
                    base = base[: -len(suf)]
            out[base] += nbytes
            out["count"] += 1
    return out
