"""Window loop for training traffic.

Set-up builds what ``launch/train.py`` builds for a run with ``--mesh``
and a tuned plan: the cell's mesh, parameters laid out by
``parallel/sharding.param_specs``, the plan tuned for the cell's layout by
``core.tune`` and installed with ``.applied()`` (``TrainConfig.sited_mesh``
set as the launcher sets it), and the step of
``train/trainer.jit_train_step``.  The weights and AdamW moments are made
on the chips from the seed, in their shardings, by one jitted call each.

The step is compiled once, then driven through the first checked steps by
the same call and feed as the window (each on a batch of its own), then
handed to the window.  Each window step does what ``train_loop`` does:
next batch, dispatch, wait for the outputs and read the loss.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
import warnings

from bench.check import diff_norms, flatten, leaf_norms, readings
from bench.traffic.corpus import batches, seed_key


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file, checked
    against every size the file states."""
    from repro.configs import get_config

    cfg = get_config(conf["arch"]).replace(**conf["overrides"])
    wrong = {k: (getattr(cfg, k), v) for k, v in conf["model"].items()
             if getattr(cfg, k) != v}
    if wrong:
        raise ValueError(f"{conf['arch']}: the program's config differs from "
                         f"the file (program, file): {wrong}")
    return cfg


def host_batches(conf: dict, traffic: dict, seed: int):
    return batches(seed, traffic["pool"], vocab=conf["model"]["vocab_size"],
                   seq=traffic["seq"], batch=traffic["batch"],
                   **traffic["corpus"])


def run(conf: dict, traffic: dict, cell: dict, *, seed: int, seconds: float,
        trace_dir=None, log=print) -> dict:
    """Set up, drive the checked steps, measure the window.  Returns the
    readings; every device buffer of the program is released on return."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.models import model as M
    from repro.optim import adamw
    from repro.parallel import collectives as C
    from repro.parallel import constraints as CT
    from repro.parallel import sharding as SH
    from repro.train.trainer import TrainConfig, jit_train_step

    cfg = model_config(conf)
    o = traffic["optimizer"]
    rec = {"tokens_per_step": traffic["batch"] * traffic["seq"]}
    hb = host_batches(conf, traffic, seed)
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

            t = time.perf_counter()
            plan = tune(extract_workload(cfg, parse_parallel(cell["plan"]),
                                         seq=traffic["seq"],
                                         global_batch=traffic["batch"]),
                        "tpu-v5e", seed=0)
            rec["tune_s"] = time.perf_counter() - t
            log(f"plan {cell['plan']} tuned on tpu-v5e in {rec['tune_s']:.3f} s "
                f"(host): {plan.profile_count} profiles")
            scope.enter_context(plan.applied())
            tcfg = dataclasses.replace(tcfg, sited_mesh=mesh)

        key = seed_key(seed)
        shapes = jax.eval_shape(lambda k: M.init_params(cfg, k), key)
        p_shard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                               SH.param_specs(shapes, mesh))
        rep = NamedSharding(mesh, P())
        init = jax.jit(lambda k: M.init_params(cfg, k), out_shardings=p_shard)
        init_opt = jax.jit(lambda: adamw.init_state(shapes),
                           out_shardings={"mu": p_shard, "nu": p_shard,
                                          "count": rep})
        params, opt_state = init(key), init_opt()
        feed = [jax.device_put(b, rep) for b in hb]

        t = time.perf_counter()
        C.reset_degraded_warnings()
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always", C.CollectiveDegradedWarning)
            step_fn = jit_train_step(cfg, tcfg, params, opt_state).lower(
                params, opt_state, feed[0], jnp.asarray(0, jnp.int32)
            ).compile()
        rec["compile_s"] = time.perf_counter() - t
        rec["fallback_sites"] = sorted({
            w.message.site for w in caught
            if isinstance(w.message, C.CollectiveDegradedWarning)})
        rec["hlo"] = step_fn.as_text() if trace_dir else None

        def step(i):
            nonlocal params, opt_state
            with jax.profiler.TraceAnnotation("bench.data"):
                batch = feed[i % len(feed)]
                s = jnp.asarray(i, jnp.int32)
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                out = step_fn(params, opt_state, batch, s)
            with jax.profiler.TraceAnnotation("bench.sync"):
                params, opt_state, metrics = jax.block_until_ready(out)
                return float(metrics["loss"])

        checked = traffic["checked_steps"]
        norms = jax.jit(leaf_norms)
        losses = []
        for i in range(checked):
            losses.append(step(i))
            if i == 0:
                scale = 1.0 / (1.0 - o["b1"])
                rec["grad"] = {k: v * scale for k, v in flatten(
                    jax.device_get(norms(opt_state["mu"]))).items()}
        rec["losses"] = losses
        rec["change"] = flatten(jax.device_get(
            jax.jit(diff_norms)(params, init(key))))

        if trace_dir:
            jax.profiler.start_trace(trace_dir)
        window = []
        t_w = time.perf_counter()
        while True:
            window.append(step(checked + len(window)))
            t_end = time.perf_counter()
            if t_end - t_w >= seconds:
                break
        if trace_dir:
            jax.profiler.stop_trace()
        rec.update(window_start=t_w, window_s=t_end - t_w,
                   window_steps=len(window), window_losses=window)
        rec["memory_peak_bytes"] = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in mesh.devices.flat)
        rec["checked_batches"] = hb[:checked]
        del params, opt_state, feed, step_fn
    return rec


def check(rec: dict, conf: dict, traffic: dict, seed: int, devices, ref,
          **kw) -> dict:
    """The compared numbers: the plain reference ``ref`` over the same seed
    and checked batches, against the program's readings in ``rec``."""
    want = ref.train_readings(conf["model"], traffic["optimizer"],
                              seed_key(seed), rec["checked_batches"],
                              devices, **kw)
    return readings(rec, want)


def end_to_end(rec: dict) -> dict:
    """All tokens of the window's steps over the window's wall time."""
    return {"train_tokens_per_s": rec["window_steps"] * rec["tokens_per_step"]
            / rec["window_s"]}
