"""Training launcher.

Single-host smoke scale by default; ``--mesh`` activates the pjit/GSPMD
path with the production sharding rules (works on any device count — on
real TPU pods the same flags apply, device count comes from the runtime).

    PYTHONPATH=src python -m repro.launch.train --arch h2o-danube-1.8b \
        --smoke --steps 50

``main`` returns ``(params, history)``.  The mesh (``--mesh``) and a
``--tuned-plan`` are scoped to the call, so one process can run it
several times, with and without them.
"""
from __future__ import annotations

import argparse
import contextlib

import jax

from repro.configs import get_config, get_smoke_config
from repro.data.pipeline import DataConfig, SyntheticCorpus
from repro.launch.config import configure_compile_cache
from repro.launch.plan import load_tuned_plan, resolve_plan_repo
from repro.models import model as M
from repro.optim import adamw
from repro.parallel import constraints as CT
from repro.parallel import sharding as SH
from repro.train import checkpoint
from repro.train.trainer import TrainConfig, train_loop


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=None,
                    help="JSON run config (CLI flags override file values)")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (2 layers, d_model<=256)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--mesh", default=None,
                    help="e.g. 2x4 -> (data=2, model=4) pjit mesh")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--tuned-plan", default=None,
                    help="saved session.TunedPlan JSON: lowered to per-site "
                         "collective runtime knobs and installed for this "
                         "run (every explicit chunked-collective site, "
                         "incl. the plan-aware model builders' per-layer "
                         "tp.layer*/ep.layer* sites on the --mesh path)")
    ap.add_argument("--plan-repo", default=None,
                    help="PlanRepository directory: auto-resolve a stored "
                         "plan matching this launch's (workload "
                         "fingerprint, hardware) with zero tuning work; "
                         "falls back to untuned with a warning on a miss "
                         "(--tuned-plan, if also given, wins)")
    ap.add_argument("--plan-parallel", default="fsdp:8",
                    help="parallel spec the repo lookup fingerprints the "
                         "workload under: kind[:degree[:microbatches]], "
                         "e.g. fsdp:8, tp:4, ep:16, pp:4:8")
    ap.add_argument("--plan-hardware", default="tpu-v5e",
                    help="hardware profile name for the repo lookup key")
    ap.add_argument("--pods", type=int, default=1,
                    help="pod count of the hierarchical topology this run "
                         "spans; >1 makes the plan lookup key the topology "
                         "name (<island>-x<pods>-<fabric>) and marks "
                         "cross-pod sites in the rebuilt workload")
    ap.add_argument("--inter-pod", default="dcn",
                    help="inter-pod fabric joining the pods (core.topology "
                         "built-ins: dcn, wan, pcie-switch)")
    ap.add_argument("--accumulate", type=int, default=0,
                    help="ACCO gradient-accumulation steps: sets grad_accum "
                         "and registers acc.step*.{rs,ar}_grads sites in "
                         "the plan lookup so a cross-pod tune's "
                         "accumulation-overlap knobs apply")
    ap.add_argument("--outer-sync", type=int, default=0,
                    help="streamed outer-loop sync fragments (Streaming "
                         "DiLoCo): registers outer.round*.sync.* sites in "
                         "the plan lookup (needs --pods > 1)")
    args = ap.parse_args(argv)
    configure_compile_cache()

    if args.config:
        from repro.launch.config import load_run_config, merge_cli, resolve_model
        run = merge_cli(load_run_config(args.config), args, defaults=dict(
            steps=100, seq=256, batch=8, lr=3e-4, grad_accum=1,
            mesh=None, ckpt=None, log_every=10))
        if args.arch:
            run["arch"] = args.arch
        for k, v in run.items():
            if hasattr(args, k) and k != "overrides":
                setattr(args, k, v)
        cfg = resolve_model(run)
    else:
        assert args.arch, "--arch or --config required"
        cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.accumulate > 1:
        # ACCO: the scan-accumulation path trains correctly everywhere;
        # the unrolled accum_axis path needs a shard_map-bound named axis
        # (see train.trainer.TrainConfig), which this GSPMD launcher does
        # not provide — the acc.* sites still shape the plan lookup below.
        args.grad_accum = args.accumulate
    plan_active = False
    plan_scope = contextlib.nullcontext()
    if args.tuned_plan:
        plan_scope = load_tuned_plan(args.tuned_plan,
                                     expect_arch=cfg.name).applied()
        plan_active = True
    elif args.plan_repo:
        plan_hw = args.plan_hardware
        if args.pods > 1:
            from repro.core import topology
            plan_hw = topology.hierarchical(args.plan_hardware, args.pods,
                                            args.inter_pod).name
        rt = resolve_plan_repo(args.plan_repo, cfg,
                               parallel=args.plan_parallel,
                               hardware=plan_hw,
                               seq=args.seq, global_batch=args.batch,
                               pods=args.pods,
                               accum_steps=max(1, args.accumulate),
                               outer_frags=args.outer_sync)
        plan_active = rt is not None
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                    global_batch=args.batch)
    data = iter(SyntheticCorpus(dc))
    tcfg = TrainConfig(opt=adamw.AdamWConfig(lr=args.lr),
                       warmup=max(5, args.steps // 10),
                       total_steps=args.steps, grad_accum=args.grad_accum)

    with contextlib.ExitStack() as scope:
        # the plan binds at trace time, which happens inside train_loop
        scope.enter_context(plan_scope)
        params = None
        if args.mesh:
            shape = tuple(int(x) for x in args.mesh.split("x"))
            axes = ("data", "model")[:len(shape)]
            from jax.sharding import NamedSharding

            from repro.launch.mesh import make_mesh
            mesh = make_mesh(shape, axes)
            scope.enter_context(jax.set_mesh(mesh))
            scope.enter_context(CT.use_axes(("data",), "model"))
            if plan_active and "model" in axes:
                # an installed plan reaches the emitted program through the
                # plan-aware trunk: per-layer explicit collectives whose
                # sites resolve against it (falls back inside the model on
                # indivisible shapes)
                from dataclasses import replace as dc_replace
                tcfg = dc_replace(tcfg, sited_mesh=mesh)
            params = M.init_params(cfg, jax.random.PRNGKey(0))
            p_spec = SH.param_specs(params, mesh)
            params = jax.device_put(
                params, jax.tree.map(lambda s: NamedSharding(mesh, s), p_spec))
        params, history = train_loop(cfg, tcfg, data, steps=args.steps,
                                     params=params, log_every=args.log_every)

    if args.ckpt:
        checkpoint.save(args.ckpt, params, step=args.steps)
        print(f"checkpoint written to {args.ckpt}")
    if history["loss"]:
        print(f"final loss {history['loss'][-1]:.4f} "
              f"(first {history['loss'][0]:.4f})")
    return params, history


if __name__ == "__main__":
    main()
