"""Chunked overlapped collectives vs dense references, on an 8-device host
mesh (spawned in a subprocess so the main test session keeps 1 device)."""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.parallel import collectives as C
from repro.launch.mesh import make_test_mesh

mesh = make_test_mesh((2, 4), ("data", "model"))
rng = jax.random.PRNGKey(0)
x = jax.random.normal(rng, (2, 16, 32))
w = jax.random.normal(rng, (32, 64))
for nc in (1, 2, 4):
    y = C.ring_ag_matmul(x, w, mesh, axis="model",
                         x_spec=P("data", "model", None), w_spec=P(None, "model"),
                         out_spec=P("data", None, "model"), num_chunks=nc)
    assert float(jnp.abs(y - x @ w).max()) < 1e-4, ("ring_ag", nc)

xf = jax.random.normal(rng, (2, 16, 64))
wf = jax.random.normal(rng, (64, 32))
for nc in (1, 2, 4):
    y = C.mm_reduce_scatter(xf, wf, mesh, axis="model",
                            x_spec=P("data", None, "model"), w_spec=P("model", None),
                            out_spec=P("data", "model", None), num_chunks=nc)
    assert float(jnp.abs(y - xf @ wf).max()) < 1e-3, ("mm_rs", nc)

xa = jax.random.normal(rng, (8, 4, 16))
ref = None
for nc in (1, 2, 4):
    y = C.chunked_all_to_all(xa, mesh, axis="model", split_axis=1, concat_axis=0,
                             x_spec=P("model", None, None),
                             out_spec=P("model", None, None), num_chunks=nc)
    ref = y if ref is None else ref
    assert float(jnp.abs(y - ref).max()) < 1e-6, ("a2a", nc)

# sharding rules produce valid NamedShardings on this mesh
from repro.configs import get_smoke_config
from repro.models import model as M
from repro.parallel import sharding as SH
cfg = get_smoke_config("h2o-danube-1.8b")
params = M.init_params(cfg, rng)
spec = SH.param_specs(params, mesh)
from jax.sharding import NamedSharding
sharded = jax.device_put(params, jax.tree.map(lambda s: NamedSharding(mesh, s), spec))
assert jax.tree.all(jax.tree.map(lambda a: bool(jnp.isfinite(a).all()), sharded))
print("SUBPROCESS_OK")
"""


@pytest.mark.slow
def test_collectives_on_8_devices():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=560)
    assert "SUBPROCESS_OK" in out.stdout, out.stdout + out.stderr


# ---------------------------------------------------------------------------
# resolve_runtime precedence edge cases (in-process: resolution is pure)
# ---------------------------------------------------------------------------

from repro.parallel import collectives as C  # noqa: E402


def test_resolve_classless_acc_and_outer_sites():
    """``acc.*``/``outer.*`` sites have no legacy class bucket — their
    first dotted component doubles as both prefix entry and the ``cls``
    fallback, so both routes must land on the same entry (and report the
    tier of whichever matched first: prefix)."""
    rt = C.CollectiveRuntime
    plan = {"acc": rt("chunked", 4), "outer": rt("ring", 2)}
    with C.use_runtime_plan(plan):
        for sid, want in (("acc.step3.rs_grads", plan["acc"]),
                          ("outer.round1.sync.w", plan["outer"])):
            cls = C.site_class(sid)
            knobs, key, tier = C.resolve_runtime(sid, cls)
            assert (knobs, key, tier) == (want, cls, "prefix"), sid
            # the class route alone (site unknown) still resolves
            knobs, key, tier = C.resolve_runtime("", cls)
            assert (knobs, key, tier) == (want, cls, "class"), sid


def test_resolve_exact_beats_prefix_beats_class_with_empty_class():
    rt = C.CollectiveRuntime
    exact, prefix, klass = rt("ring", 8), rt("ring", 4), rt("chunked", 2)
    plan = {"a.b.c": exact, "a.b": prefix, "": klass}
    with C.use_runtime_plan(plan):
        assert C.resolve_runtime("a.b.c", "")[1:] == ("a.b.c", "exact")
        assert C.resolve_runtime("a.b.d", "")[1:] == ("a.b", "prefix")
        # nothing dotted matches: the empty-string class entry is a real
        # key, not the "no match" sentinel
        knobs, key, tier = C.resolve_runtime("z.y", "")
        assert (knobs, key, tier) == (klass, "", "class")
        # empty site + empty class: the site loop never runs, class wins
        assert C.resolve_runtime("", "")[2] == "class"
        # cls=None opts out entirely -> XLA default, matched_key ""
        knobs, key, tier = C.resolve_runtime("z.y", None)
        assert tier == "default" and key == "" and knobs.num_chunks == 1


def test_resolve_prefix_shadowed_by_exhaustive_exact_entries():
    """When every site under a prefix also has an exact entry, the prefix
    entry is never the winning key for those sites — it only serves
    *novel* siblings (the first-wins ``setdefault`` lowering depends on
    this to stay bit-identical to pre-per-site plans)."""
    rt = C.CollectiveRuntime
    exacts = {f"tp.layer{i}.mlp.ag": rt("ring", i + 2) for i in range(3)}
    plan = dict(exacts)
    plan["tp"] = rt("chunked", 16)
    with C.use_runtime_plan(plan):
        for sid, want in exacts.items():
            knobs, key, tier = C.resolve_runtime(sid, "ag")
            assert (knobs, key, tier) == (want, sid, "exact")
        # a sibling with no exact entry falls through to the prefix
        knobs, key, tier = C.resolve_runtime("tp.layer9.mlp.ag", "ag")
        assert (knobs, key, tier) == (plan["tp"], "tp", "prefix")


# ---------------------------------------------------------------------------
# gradients of the chunked sited matmuls, and where their weight gradient is
# formed (4 host devices in a subprocess; a (1, 4) ring and danube's (1, 1))
# ---------------------------------------------------------------------------

_GRAD_SCRIPT = r"""
import json
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import warnings
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.parallel import collectives as C


def weight_dots(jaxpr, shape, trips=1):
    # dot_generals whose output has the local weight-shard's shape (or its
    # transpose, as JAX's own transpose rule forms it), each counted once
    # per iteration of the scans around it
    n = 0
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        assert prim != "while", "a gradient loop with no static trip count"
        inner = trips * (eqn.params["length"] if prim == "scan" else 1)
        if (prim == "dot_general"
                and tuple(eqn.outvars[0].aval.shape) in (shape, shape[::-1])):
            n += trips
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                if hasattr(sub, "eqns"):
                    n += weight_dots(sub, shape, inner)
                elif hasattr(sub, "jaxpr") and hasattr(sub.jaxpr, "eqns"):
                    n += weight_dots(sub.jaxpr, shape, inner)
    return n


def rel(a, b):
    return float(jnp.abs(a - b).max() / jnp.abs(b).max())


key = jax.random.split(jax.random.PRNGKey(0), 6)
helpers = {
    # name: (helper, x, w, x_spec, w_spec, out_spec, weight-shard spec dim)
    "ring_ag": (C.ring_ag_matmul, jax.random.normal(key[0], (2, 16, 32)),
                jax.random.normal(key[1], (32, 64)), P("data", "model", None),
                P(None, "model"), P("data", None, "model"), 1),
    "mm_rs": (C.mm_reduce_scatter, jax.random.normal(key[2], (2, 16, 64)),
              jax.random.normal(key[3], (64, 32)), P("data", None, "model"),
              P("model", None), P("data", "model", None), 0),
}
out = {}
for shape in ((1, 4), (1, 1)):
    mesh = jax.make_mesh(shape, ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2,
                         devices=jax.devices()[:shape[0] * shape[1]])
    n = shape[1]
    for name, (fn, x, w, xs, ws, os_, dim) in helpers.items():
        ct = jax.random.normal(key[4], (x.shape[0], x.shape[1], w.shape[1]))
        ref = jax.grad(lambda x, w: jnp.sum((x @ w) * ct), (0, 1))(x, w)
        local = list(w.shape)
        local[dim] //= n
        for nc in (1, 2, 4, 3):
            C.reset_degraded_warnings()
            f = lambda x, w: fn(x, w, mesh, axis="model", x_spec=xs,
                                w_spec=ws, out_spec=os_, num_chunks=nc)
            loss = lambda x, w: jnp.sum(f(x, w) * ct)
            with warnings.catch_warnings(record=True) as caught:
                warnings.simplefilter("always")
                y = jax.jit(f)(x, w)
                dx, dw = jax.jit(jax.grad(loss, (0, 1)))(x, w)
                jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1)))(x, w)
            out[f"{name}-{shape[0]}x{shape[1]}-nc{nc}"] = {
                "y": rel(y, x @ w), "dx": rel(dx, ref[0]), "dw": rel(dw, ref[1]),
                "weight_dots": weight_dots(jaxpr.jaxpr, tuple(local)),
                "n": n, "lag010": sum(getattr(c.message, "code", "") == "LAG010"
                                      for c in caught)}
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def grad_results():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", _GRAD_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=240)
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("RESULT ")]
    assert lines, out.stdout + out.stderr
    return json.loads(lines[-1][len("RESULT "):])


_GRAD_CASES = [f"{h}-{m}-nc{nc}" for h in ("ring_ag", "mm_rs")
               for m in ("1x4", "1x1") for nc in (1, 2, 4, 3)]


@pytest.mark.parametrize("case", _GRAD_CASES)
def test_chunked_matmul_value_and_grads(grad_results, case):
    """Value and both gradients of the sited helper match the plain
    product's within f32 rounding, for divisible chunk counts and the
    non-dividing count that falls back to the unchunked path."""
    r = grad_results[case]
    assert r["y"] < 1e-5 and r["dx"] < 1e-5 and r["dw"] < 1e-5, r
    # 3 divides neither a shard of 4 rows, 16 rows over 4 shards, nor
    # 16 rows on one shard: the fallback warns (LAG010); the rest do not
    divides = not case.endswith("nc3")
    assert r["lag010"] == (0 if divides else 1), r


@pytest.mark.parametrize("case", _GRAD_CASES)
def test_weight_grad_formed_once_per_held_shard(grad_results, case):
    """The backward forms the weight-shard gradient once per shard the
    ring holds (n), and once for the reduce-scatter, whatever the chunk
    count: never once per chunk."""
    r = grad_results[case]
    want = r["n"] if case.startswith("ring_ag") else 1
    assert r["weight_dots"] == want, r
