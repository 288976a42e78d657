#!/usr/bin/env python3
"""Compile a cell's timed step, and its reference's row step, for a
described TPU v5e host (``v5e:2x2``, no chip attached), and print each
program's memory per device and the step's collective counts.

    JAX_PLATFORMS=cpu python3 bench/tools/compile_rehearsal.py <cell>

Nothing runs: the numbers are the compiler's, never a time.  JAX's
persistent compilation cache is off, since an entry compiled for a
described chip cannot be read back without one.
"""
from __future__ import annotations

import dataclasses
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def describe(tag, compiled):
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    print(f"[{tag}] per device: arguments {ma.argument_size_in_bytes}, "
          f"outputs {ma.output_size_in_bytes}, aliased "
          f"{ma.alias_size_in_bytes}, temporaries {ma.temp_size_in_bytes}; "
          f"total {total} ({total / 1e9:.2f} GB)", flush=True)


def main(cell: str) -> int:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from bench.run import load_module, resolve
    from bench.traffic.train import model_config
    from repro.analysis.ir import graph_from_hlo
    from repro.core import extract_workload, tune
    from repro.core.extract import parse_parallel
    from repro.models import model as M
    from repro.optim import adamw
    from repro.parallel import constraints as CT
    from repro.parallel import sharding as SH
    from repro.train.trainer import TrainConfig, jit_train_step

    jax.config.update("jax_enable_compilation_cache", False)
    spec = resolve(cell)
    conf, traffic, cellfile = spec["config"], spec["traffic"], spec["cellfile"]
    cfg = model_config(conf)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    devs = topo.devices[:spec["workload"]["chips"]]
    mesh = Mesh(np.asarray(devs).reshape(conf["mesh"]), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    key = jax.random.PRNGKey(0)
    o = traffic["optimizer"]
    B, S = traffic["batch"], traffic["seq"]

    def sds(tree, shard):
        return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=s), tree, shard)

    tcfg = TrainConfig(opt=adamw.AdamWConfig(lr=o["lr"]), warmup=o["warmup"],
                       total_steps=o["total_steps"])
    with jax.set_mesh(mesh), CT.use_axes(("data",), "model"):
        shapes = jax.eval_shape(lambda k: M.init_params(cfg, k), key)
        p_shard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                               SH.param_specs(shapes, mesh))
        rep = NamedSharding(mesh, P())
        params = sds(shapes, p_shard)
        opt = {"mu": sds(shapes, p_shard), "nu": sds(shapes, p_shard),
               "count": jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)}
        batch = {k: jax.ShapeDtypeStruct((B, S), dt, sharding=rep)
                 for k, dt in (("tokens", jnp.int32), ("targets", jnp.int32),
                               ("mask", jnp.float32))}
        step = jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)
        scope = None
        if cellfile["plan"] != "none":
            plan = tune(extract_workload(cfg, parse_parallel(cellfile["plan"]),
                                         seq=S, global_batch=B), "tpu-v5e",
                        seed=0)
            scope = plan.applied()
            scope.__enter__()
            tcfg = dataclasses.replace(tcfg, sited_mesh=mesh)
        t = time.perf_counter()
        compiled = jit_train_step(cfg, tcfg, params, opt).lower(
            params, opt, batch, step).compile()
        print(f"[step] compiled in {time.perf_counter() - t:.1f} s")
        if scope is not None:
            scope.__exit__(None, None, None)
    describe("step", compiled)
    g = graph_from_hlo(compiled.as_text())
    print("[step] collectives: " + ", ".join(
        f"{k} {g.count(k)}" for k in sorted({c.kind for c in g.collectives})))

    ref = load_module(ROOT, "bench", "reference", conf["family"] + ".py")
    with jax.default_matmul_precision("highest"):
        f = ref.programs(conf["model"], o, key, devs)
        p = sds(f["shapes"], f["p_shard"])
        row = [jax.ShapeDtypeStruct((S,), dt, sharding=f["rep"])
               for dt in (jnp.int32, jnp.int32, jnp.float32)]
        n = jax.ShapeDtypeStruct((), jnp.float32, sharding=f["rep"])
        t = time.perf_counter()
        c = f["grad_row"].lower(p, p, *row, n).compile()
        print(f"[reference] row step compiled in "
              f"{time.perf_counter() - t:.1f} s")
        describe("reference row step", c)
        c = f["update"].lower(p, p, p, p, n, n).compile()
        describe("reference update", c)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
