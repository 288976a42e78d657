"""Training loop: jitted train_step (pjit/GSPMD) with optional Domino-style
dual-microbatch interleave (the TP/EP overlap pattern the paper tunes).

``make_train_step`` builds the function the dry-run lowers: params/opt-state
sharded by ``parallel.sharding`` rules, batch over the data axes, loss via
chunked cross-entropy, gradients averaged implicitly by GSPMD.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import model as M
from repro.optim import adamw, schedules
from repro.train import metrics as MET


@dataclass
class TrainConfig:
    opt: adamw.AdamWConfig = adamw.AdamWConfig()
    schedule: str = "warmup_cosine"
    warmup: int = 100
    total_steps: int = 10_000
    remat: bool = True
    microbatches: int = 1      # >1: dual-batch interleave (EP/TP overlap)
    grad_accum: int = 1        # sequential microbatches (memory ceiling)
    accum_axis: Optional[str] = None   # ACCO accumulation overlap: with
                                       # grad_accum > 1, unroll the
                                       # microbatch loop and reduce batch
                                       # k's grads over this named dp mesh
                                       # axis (chunked psum at site
                                       # acc.step{k}.rs_grads) while k+1's
                                       # compute runs; requires the step to
                                       # execute under shard_map/pmap with
                                       # the axis bound
    backend: Optional[str] = None   # kernel backend override
    sited_mesh: Optional[Any] = None   # plan-aware explicit collectives:
                                       # per-layer sites resolve against the
                                       # active TunedPlan (dense families)


def make_train_step(cfg, tcfg: TrainConfig):
    """Returns train_step(params, opt_state, batch, step) ->
    (params, opt_state, metrics)."""
    sched = getattr(schedules, tcfg.schedule)

    def loss_fn(params, batch):
        loss, metrics = M.loss_and_metrics(cfg, params, batch,
                                           remat=tcfg.remat,
                                           backend=tcfg.backend,
                                           mesh=tcfg.sited_mesh)
        return loss, metrics

    def train_step(params, opt_state, batch, step):
        if tcfg.grad_accum > 1 and tcfg.accum_axis:
            # ACCO accumulation overlap: the microbatch loop is
            # Python-unrolled so each step k is static — its grad reduce
            # resolves the tuned knobs at site acc.step{k}.rs_grads at
            # trace time and is issued before microbatch k+1's compute,
            # letting XLA's latency-hiding scheduler pull the collective
            # under it (the paper's Pattern 2, lifted to the accumulation
            # loop).  Per-microbatch reduce (not accumulate-then-reduce)
            # is what creates the K overlap windows the acc.* sites tune.
            from repro.parallel import collectives

            n = tcfg.grad_accum
            mbs = [jax.tree.map(lambda a: a[i::n], batch) for i in range(n)]
            gsum = None
            tot_loss = jnp.zeros((), jnp.float32)
            metrics = None
            for k, b in enumerate(mbs):
                (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, b)
                g = collectives.psum_tree_chunked(
                    g, tcfg.accum_axis, site=f"acc.step{k}.rs_grads")
                g = jax.tree.map(lambda x: x.astype(jnp.float32), g)
                gsum = g if gsum is None else jax.tree.map(jnp.add, gsum, g)
                tot_loss = tot_loss + l
                metrics = m
            scale = n * jax.lax.axis_size(tcfg.accum_axis)
            grads = jax.tree.map(lambda a: a / scale, gsum)
            loss = tot_loss / n
        elif tcfg.grad_accum > 1:
            # sequential gradient accumulation via scan: bounds live
            # activations to one microbatch; grads accumulate in f32.
            n = tcfg.grad_accum
            mb = jax.tree.map(
                lambda a: a.reshape((n, a.shape[0] // n) + a.shape[1:]), batch)

            def accum(carry, b):
                gsum, lsum = carry
                (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(params, b)
                gsum = jax.tree.map(
                    lambda s, x: s + x.astype(jnp.float32), gsum, g)
                return (gsum, lsum + l), m

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, tot_loss), metrics = jax.lax.scan(
                accum, (g0, jnp.zeros((), jnp.float32)), mb)
            grads = jax.tree.map(lambda a: a / n, grads)
            loss = tot_loss / n
            metrics = jax.tree.map(lambda a: a[-1], metrics)
        elif tcfg.microbatches > 1:
            # dual-batch interleave: split along batch; XLA's scheduler
            # overlaps microbatch i's collectives with i+1's compute.
            n = tcfg.microbatches
            parts = [jax.tree.map(lambda a: a[i::n], batch) for i in range(n)]
            grads = None
            tot_loss = 0.0
            metrics = None
            for p_ in parts:
                (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(params, p_)
                grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
                tot_loss = tot_loss + l
                metrics = m
            grads = jax.tree.map(lambda a: a / n, grads)
            loss = tot_loss / n
        else:
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch)
        with jax.named_scope("optimizer"):
            lr_scale = sched(step, warmup=tcfg.warmup, total=tcfg.total_steps)
            params, opt_state, opt_metrics = adamw.apply_updates(
                params, grads, opt_state, tcfg.opt, lr_scale)
        metrics = dict(metrics, **opt_metrics, loss=loss)
        return params, opt_state, metrics

    return train_step


def _shardings(tree):
    return jax.tree.map(lambda a: a.sharding, tree)


def _committed(tree):
    return jax.device_put(tree, _shardings(tree))


def jit_train_step(cfg, tcfg: TrainConfig, params, opt_state):
    """The step ``train_loop`` runs, for state laid out as ``params`` and
    ``opt_state`` (arrays or ``ShapeDtypeStruct``s with shardings).  The
    state is donated, and leaves the step laid out as it entered, so step
    1 reuses step 0's executable."""
    return jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0, 1),
                   out_shardings=(_shardings(params), _shardings(opt_state),
                                  None))


def train_loop(cfg, tcfg: TrainConfig, data_iter, *, steps: int,
               rng=None, params=None, log_every: int = 10,
               callback=None) -> Tuple[Any, Dict[str, list]]:
    """Training loop: the launcher's (``params`` may arrive sharded
    over a mesh) and the examples'.

    ``history["step_time"][i]`` is the host wall time of step ``i`` from
    its dispatch to ``block_until_ready`` on its outputs (batch
    preparation excluded); step 0 includes tracing and compilation.
    ``params`` is consumed (donated)."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    if params is None:
        params = M.init_params(cfg, rng)
    params = _committed(params)
    opt_state = _committed(adamw.init_state(params))
    step_fn = jit_train_step(cfg, tcfg, params, opt_state)
    history: Dict[str, list] = {"loss": [], "step_time": []}
    tracker = None
    for step in range(steps):
        batch = {k: jnp.asarray(v) for k, v in next(data_iter).items()}
        t0 = time.perf_counter()
        params, opt_state, metrics = jax.block_until_ready(
            step_fn(params, opt_state, batch, jnp.asarray(step)))
        dt = time.perf_counter() - t0
        loss = float(metrics["loss"])
        if tracker is None:
            tracker = MET.Tracker(
                int(batch["tokens"].shape[0] * batch["tokens"].shape[1]))
        m = tracker.update(dt)
        history["loss"].append(loss)
        history["step_time"].append(dt)
        if callback:
            callback(step, metrics)
        if log_every and step % log_every == 0:
            print(f"step {step:5d}  loss {loss:.4f}  "
                  f"grad_norm {float(metrics['grad_norm']):.3f}  "
                  f"tok/s {m['tokens_per_s']:.0f}")
    return params, history
