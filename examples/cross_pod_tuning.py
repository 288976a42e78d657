"""Cross-pod hierarchical tuning: two TPU pods over a slow wide-area
fabric, gradient-accumulation overlap (ACCO) as tunable ``acc.*`` sites.

1. Builds the hierarchical workload: llama3-8b FSDP across 2 pods with 4
   accumulation steps — step k's grad reduce (pod-local reduce-scatter +
   cross-pod all-reduce) overlaps microbatch k+1's compute.
2. Tunes it twice: against the ``two_pod`` topology (per-tier pricing)
   and against the bare island profile (fabric-blind flat model).
3. Evaluates both plans on the *hierarchical* simulator: the
   topology-aware tune must win, and its trace must show the grad reduce
   hidden under the next microbatch's compute.
4. Installs the topology-tuned plan and runs the real chunked-psum
   gradient sync under ``shard_map`` — the ``acc.step0.rs_grads`` site
   picks its chunk count up from the plan.

    PYTHONPATH=src python examples/cross_pod_tuning.py
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import (
    ParallelPlan,
    Simulator,
    extract_workload,
    tune,
    two_pod,
)

# the wan fabric's bandwidth/latency terms are far from the island's, so
# a fabric-blind tune visibly mis-provisions the overlap window
topo = two_pod("tpu-v5e", "wan")
cfg = get_config("llama3-8b")
pp = ParallelPlan(kind="fsdp", dp=8, pods=2, accum_steps=4)
wl = extract_workload(cfg, pp, seq=2048, global_batch=64, layers=4)
acc_sites = [c.site_id for g in wl.groups if g.name.startswith("acc.") for c in g.comms]
print(
    f"workload {wl.name}: {len(wl.groups)} groups, "
    f"{len(acc_sites)} accumulation comm sites on topology {topo.name}"
)

tuned = tune(wl, topology=topo)  # per-tier pricing
flat = tune(wl, "tpu-v5e")  # fabric-blind baseline
assert tuned.hardware == topo.name and tuned.topology["fingerprint"]

# both plans judged on the fabric-aware simulator — the deployment model
sim = Simulator(topo)
z_hier = sim.profile(wl, tuned.configs).Z
z_flat = sim.profile(wl, flat.configs).Z
print(
    f"hierarchical simulator: topology-tuned {z_hier * 1e3:.2f} ms vs "
    f"flat-model plan {z_flat * 1e3:.2f} ms "
    f"({z_flat / z_hier:.2f}x)"
)
assert z_hier < z_flat, "topology-aware tune must beat the flat-model plan"

# the cross-pod reduce carries its own config, distinct from intra-pod
site_of = {(s["group"], s["comm"]): s.get("site") or s["name"] for s in tuned.sites}
cfg_by_site = {site_of[k]: v for k, v in tuned.configs.items()}
ar = cfg_by_site["acc.step0.ar_grads"]
intra = next(v for s, v in sorted(cfg_by_site.items()) if s.startswith("fsdp."))
print(
    f"acc.step0.ar_grads (inter-pod): nc={ar.nc} chunk_kb={ar.chunk_kb}; "
    f"intra-pod fsdp site: nc={intra.nc} chunk_kb={intra.chunk_kb}"
)
assert ar != intra, "cross-pod sites must tune independently"

# the trace shows the reduce hidden under the next microbatch's compute
m = tuned.evaluate(wl)
acc0 = next(g for g in m.groups if g.name == "acc.step0")
hidden = acc0.X + acc0.Y - acc0.Z
print(
    f"acc.step0 busy windows: comm {acc0.X * 1e3:.2f} ms + compute "
    f"{acc0.Y * 1e3:.2f} ms in a {acc0.Z * 1e3:.2f} ms makespan -> "
    f"{hidden / acc0.X:.0%} of the grad reduce overlapped"
)
assert hidden > 0, "accumulation reduce must overlap next-mb compute"

# execution path: the tuned acc knobs reach the real chunked psum
from repro.core.apply import activate
from repro.launch.mesh import make_mesh
from repro.parallel import collectives as C

activate(tuned)
knobs, src = C.explain_runtime("acc.step0.rs_grads")
print(
    f"site acc.step0.rs_grads -> {knobs.strategy}/x{knobs.num_chunks} "
    f"(matched plan key {src!r})"
)

mesh = make_mesh((8,), ("dp",))
# leading dim sized from the resolved chunk count so the tuned knobs always
# divide evenly (an indivisible payload would degrade, LAG010)
grads = {
    "w": jax.random.normal(jax.random.PRNGKey(0), (8 * knobs.num_chunks, 16, 32))
}
from jax.sharding import PartitionSpec as P

from jax import shard_map


def sync(g):
    # no num_chunks — the active plan's acc.step0.rs_grads knobs apply
    return C.psum_tree_chunked(g, "dp", site="acc.step0.rs_grads")


fn = shard_map(sync, mesh=mesh, in_specs=({"w": P("dp")},), out_specs={"w": P("dp")})
ref = shard_map(
    lambda g: C.psum_tree(g, "dp"),
    mesh=mesh,
    in_specs=({"w": P("dp")},),
    out_specs={"w": P("dp")},
)
ok = bool(jnp.allclose(fn(grads)["w"], ref(grads)["w"]))
print(f"chunked accumulation psum (x{knobs.num_chunks}) matches monolithic: {ok}")
assert ok

# overlap verifier: the tuned chunk structure is really in the trace —
# MATERIALIZED under the plan, ABSENT when the plan is not installed
from repro.analysis.overlap import trace_and_verify

report = trace_and_verify(tuned, fn, grads)
v = next(x for x in report.verdicts if x.site == "acc.step0.rs_grads")
print(f"overlap verdict for acc.step0.rs_grads: {v.verdict} ({v.detail})")
assert v.verdict == "MATERIALIZED", report.format()

C.install_runtime_plan({})  # drop the activated plan: the ABSENT control
off = trace_and_verify(tuned, fn, grads, install=False)
v_off = next(x for x in off.verdicts if x.site == "acc.step0.rs_grads")
print(f"without the plan installed: {v_off.verdict}")
assert v_off.verdict == "ABSENT", off.format()
