#!/usr/bin/env python3
"""Chip smoke test: the training and decode path of h2o-danube-1.8b at its
published widths (depth cut to 4 layers, ``runs/h2o_danube_1p8b_4layer.json``)
on a TPU, through the launchers' own entry points, then every Pallas
kernel compiled at the widths of a zoo model.

    python3 chip_smoke.py                # one chip: train, decode, tuned
                                         # plan, kernels
    python3 chip_smoke.py --four-chips   # four chips: 1x4 TP mesh, XLA
                                         # default vs the tuned tp:4 plan

It runs in one process and starts none.  It never falls back to the CPU:
when JAX's first device is not a TPU it names the device and exits 1,
and any failed check raises.  The CPU backend serves only as the float32
reference of the loss check.  Every result line names its phase; the last
line of stdout is one JSON object naming the device:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""
from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys
import time
import warnings

HERE = os.path.dirname(os.path.abspath(__file__))
RUN_CONFIG = os.path.join(HERE, "runs", "h2o_danube_1p8b_4layer.json")
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

# Tolerances, each with its reason.
# The TPU runs float32 matmuls at its default precision (one bf16 pass,
# about 2^-9 relative per operand), the CPU reference at full float32.
# Over 4 layers that stays within a few percent of the logit scale and
# averages out in the loss, while an error in the model's math (mask,
# positions, cache) moves logits by O(1) of their scale.
LOSS_ATOL = 1e-2          # |loss_tpu - loss_cpu| at batch 1 x seq 256
LOGITS_RTOL = 5e-2        # max |logit diff| over max |logit|
# Two programs of the same math on the chip (scan trunk vs the sited
# shard_map trunk, 1 or 4 chips) differ only in fusion and reduction
# order; 8 AdamW steps keep that well inside this.
TRAIN_LOSS_ATOL = 1e-2
# A greedy decode token must be the argmax of the uncached forward up to
# the same bf16-pass noise: its logit within this share of the row's
# largest |logit| of the maximum.  A broken cache picks tokens O(1) below.
DECODE_RTOL = 5e-2
# Kernels vs their jnp reference at full precision, as max |diff| over
# max |ref|.  rmsnorm has no matmul; the others may run bf16 passes.
KERNEL_RTOL = {"rmsnorm": 1e-4, "wkv6": 2e-2, "ssd": 2e-2, "flash": 2e-2}

# (kernel, shapes) at the widths of a zoo model
KERNEL_WIDTHS = {
    "rmsnorm": dict(rows=(4, 2048), d=2560),                 # h2o-danube-1.8b
    "wkv6": dict(B=2, S=1024, H=32, K=64),                   # rwkv6-1.6b
    "ssd": dict(B=1, S=1024, H=112, P=64, N=64),             # zamba2-7b
    "flash": dict(B=1, S=2048, Hq=32, Hkv=8, h=80),          # h2o-danube-1.8b
}


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def summarize(phase, cfg, params, history, batch_tokens):
    import jax

    from repro.train import metrics as MET

    losses = history["loss"]
    check(all(map(math.isfinite, losses)), f"{phase}: finite losses {losses}")
    steady = statistics.median(history["step_time"][1:])
    dev = jax.devices()[0]
    n = sum(leaf.size for leaf in jax.tree.leaves(params))
    chips = len(jax.tree.leaves(params)[0].sharding.device_set)
    peak = MET.peak_flops(dev.device_kind)
    mfu = (None if peak is None else
           MET.mfu(cfg, batch_tokens, steady, chips=chips, peak=peak))
    say(phase, f"device {dev.device_kind} x{chips}; params {n}")
    say(phase, f"set-up (first step: trace + compile + run) "
               f"{history['step_time'][0]:.3f} s")
    say(phase, f"steady step median {steady * 1e3:.2f} ms over "
               f"{len(losses) - 1} steps; {batch_tokens / steady:.0f} tok/s; "
               f"MFU {'not measured' if mfu is None else f'{mfu:.4f}'}")
    say(phase, "step ms " + " ".join(f"{t * 1e3:.1f}"
                                     for t in history["step_time"]))
    say(phase, "losses " + " ".join(f"{x:.4f}" for x in losses))


def _run_tokens(run_config):
    with open(run_config) as f:
        run = json.load(f)
    return run["batch"] * run["seq"]


def phase_train(run_config):
    import jax

    from repro.launch import train
    from repro.launch.config import load_run_config, resolve_model

    cfg = resolve_model(load_run_config(run_config))
    params, history = train.main(["--config", run_config])
    summarize("train", cfg, params, history, _run_tokens(run_config))
    stats = jax.devices()[0].memory_stats() or {}
    say("train", f"peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
                 f"(memory_stats: {dict(sorted(stats.items()))})")
    return cfg, params, history


def phase_reference(cfg, params, seq=256):
    """One forward at batch 1 on the chip vs float32 on the CPU backend."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data.pipeline import DataConfig, SyntheticCorpus
    from repro.models import model as M

    b = SyntheticCorpus(DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                                   global_batch=1, seed=1)).batch(0)

    def fwd(p, batch):
        loss, _ = M.loss_and_metrics(cfg, p, batch, remat=False)
        return loss, M.logits(cfg, p, batch)

    batch = {k: jnp.asarray(v) for k, v in b.items()}
    loss_d, logits_d = jax.jit(fwd)(params, batch)
    cpu = jax.devices("cpu")[0]
    with jax.default_matmul_precision("highest"):
        loss_c, logits_c = jax.jit(fwd)(jax.device_put(params, cpu),
                                        jax.device_put(batch, cpu))
    logits_d, logits_c = np.asarray(logits_d), np.asarray(logits_c)
    dl = abs(float(loss_d) - float(loss_c))
    rel = float(np.abs(logits_d - logits_c).max() / np.abs(logits_c).max())
    say("reference", f"batch 1 x seq {seq}: loss chip {float(loss_d):.6f} "
                     f"cpu-f32 {float(loss_c):.6f} |diff| {dl:.2e} "
                     f"(tol {LOSS_ATOL}); logits max|diff|/max|ref| "
                     f"{rel:.2e} (tol {LOGITS_RTOL})")
    check(np.isfinite(logits_d).all(), "reference: finite chip logits")
    check(dl <= LOSS_ATOL, "reference: loss vs float32 CPU forward")
    check(rel <= LOGITS_RTOL, "reference: logits vs float32 CPU forward")


def tune_tp4_plan(cfg, run_config):
    """Tune the tp:4 plan in-process and save it under ``OUT_DIR``."""
    with open(run_config) as f:
        run = json.load(f)
    from repro.core import extract_workload, tune
    from repro.core.extract import parse_parallel

    t0 = time.perf_counter()
    plan = tune(extract_workload(cfg, parse_parallel("tp:4"), seq=run["seq"],
                                 global_batch=run["batch"]),
                "tpu-v5e", seed=0)
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, f"{cfg.name}_tp4.json")
    plan.save(path)
    say("plan", f"tuned tp:4 on tpu-v5e in {time.perf_counter() - t0:.1f} s "
                f"(host): {plan.profile_count} profiles -> {path}")
    return path


def train_under_plan(phase, cfg, run_config, mesh, plan_path, want_losses):
    """One run through ``--mesh MESH --tuned-plan``, its losses checked
    against ``want_losses``; returns the history."""
    from repro.launch import train
    from repro.parallel import collectives as C

    C.reset_degraded_warnings()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always", C.CollectiveDegradedWarning)
        params, history = train.main(["--config", run_config, "--mesh", mesh,
                                      "--tuned-plan", plan_path])
    degraded = sorted({w.message.site for w in caught
                       if isinstance(w.message, C.CollectiveDegradedWarning)})
    summarize(phase, cfg, params, history, _run_tokens(run_config))
    say(phase, f"{len(degraded)} site(s) fell back with LAG010: "
               f"{' '.join(degraded) or '-'}")
    del params
    compare_losses(phase, "tuned plan", history["loss"], "untuned",
                   want_losses)
    return history


def compare_losses(phase, a_name, a, b_name, b):
    d = max(abs(x - y) for x, y in zip(a, b))
    say(phase, f"losses {a_name} vs {b_name}: max |diff| {d:.2e} "
               f"(tol {TRAIN_LOSS_ATOL})")
    check(len(a) == len(b) and d <= TRAIN_LOSS_ATOL,
          f"{phase}: {a_name} vs {b_name} losses")


def phase_decode(cfg, params, *, requests=8, prompt_len=128, new=32):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import model as M
    from repro.serving import make_engine

    rs = np.random.default_rng(0)
    prompts = [rs.integers(0, cfg.vocab_size, size=prompt_len).astype(np.int32)
               for _ in range(requests)]
    engine = make_engine(cfg, params, mode="fixed", batch_size=requests,
                         max_seq=prompt_len + new)
    t0 = time.perf_counter()
    outs = np.asarray(engine.generate(prompts, max_new=new), np.int32)
    say("decode", f"generate {requests} x ({prompt_len} prompt + {new} new) "
                  f"incl. compile {time.perf_counter() - t0:.3f} s")
    check(outs.shape == (requests, new), f"decode: output shape {outs.shape}")
    check(((outs >= 0) & (outs < cfg.vocab_size)).all(), "decode: token ids")
    probe = engine.throughput_probe()
    say("decode", f"throughput_probe: {probe['tokens_per_s']:.1f} tok/s "
                  f"({probe['s_per_token'] * 1e3:.3f} ms/step, batch {requests})")
    # greedy tokens vs the argmax of the uncached forward over the same text
    full = np.concatenate([np.stack(prompts), outs[:, :-1]], axis=1)
    lg = np.asarray(jax.jit(lambda p, t: M.logits(cfg, p, {"tokens": t}))(
        params, jnp.asarray(full)))[:, prompt_len - 1:]
    chosen = np.take_along_axis(lg, outs[..., None], axis=-1)[..., 0]
    gap = float(((lg.max(-1) - chosen) / np.abs(lg).max(-1)).max())
    say("decode", f"greedy tokens vs uncached forward: worst logit gap "
                  f"{gap:.2e} of max|logit| (tol {DECODE_RTOL})")
    check(np.isfinite(lg).all() and gap <= DECODE_RTOL,
          "decode: cached greedy decode vs uncached forward")


def kernel_inputs(name, w, key):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(key, 6)
    if name == "rmsnorm":
        return (jax.random.normal(ks[0], w["rows"] + (w["d"],)),
                jnp.linspace(0.5, 1.5, w["d"]))
    if name == "wkv6":
        shp = (w["B"], w["S"], w["H"], w["K"])
        r, k, v = (jax.random.normal(ks[i], shp) for i in range(3))
        w_log = -jnp.exp(jax.random.normal(ks[3], shp) * 0.5)
        return r, k, v, w_log, jax.random.normal(ks[4], (w["H"], w["K"])) * 0.1
    if name == "ssd":
        B, S, H, P, N = w["B"], w["S"], w["H"], w["P"], w["N"]
        return (jax.random.normal(ks[0], (B, S, H, P)),
                jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))),
                -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3),
                jax.random.normal(ks[3], (B, S, H, N)),
                jax.random.normal(ks[4], (B, S, H, N)),
                jnp.ones((H,)))
    B, S = w["B"], w["S"]
    return (jax.random.normal(ks[0], (B, S, w["Hq"], w["h"])),
            jax.random.normal(ks[1], (B, S, w["Hkv"], w["h"])),
            jax.random.normal(ks[2], (B, S, w["Hkv"], w["h"])))


def attention_ref(q, k, v):
    import jax
    import jax.numpy as jnp

    S, h = q.shape[1], q.shape[-1]
    G = q.shape[2] // k.shape[2]
    kk, vv = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / math.sqrt(h)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vv)


def phase_kernels(widths=KERNEL_WIDTHS, *, interpret=False):
    """Each Pallas kernel compiled once (``interpret`` only for a CPU
    rehearsal) against its chunked/ref jnp form at full precision."""
    import jax
    import numpy as np

    from repro.kernels import ref
    from repro.kernels.flash import flash_attention
    from repro.kernels.rmsnorm import rmsnorm_pallas
    from repro.kernels.ssd import ssd_pallas
    from repro.kernels.wkv6 import wkv6_pallas

    kernels = {
        "rmsnorm": (lambda x, s: rmsnorm_pallas(x, s, interpret=interpret),
                    ref.rmsnorm_ref),
        "wkv6": (lambda *a: wkv6_pallas(*a, chunk=32, interpret=interpret)[0],
                 lambda *a: ref.wkv6_chunked_ref(*a, chunk=32)[0]),
        "ssd": (lambda *a: ssd_pallas(*a, chunk=64, interpret=interpret)[0],
                lambda *a: ref.ssd_chunked_ref(*a, chunk=64)[0]),
        "flash": (lambda q, k, v: flash_attention(q, k, v, interpret=interpret),
                  attention_ref),
    }
    for i, (name, w) in enumerate(widths.items()):
        kern, reference = kernels[name]
        args = kernel_inputs(name, w, jax.random.PRNGKey(i))
        t0 = time.perf_counter()
        fn = jax.jit(kern).lower(*args).compile()
        t_compile = time.perf_counter() - t0
        out = jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        t_run = time.perf_counter() - t0
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(reference)(*args))
        out = np.asarray(out)
        err = float(np.abs(out - want).max() / np.abs(want).max())
        shape = " ".join(f"{k}={v}" for k, v in w.items())
        say("kernels", f"{name} ({shape}): compile {t_compile:.2f} s, "
                       f"one call {t_run * 1e3:.3f} ms, max|diff|/max|ref| "
                       f"{err:.2e} (tol {KERNEL_RTOL[name]})")
        check(np.isfinite(out).all() and err <= KERNEL_RTOL[name],
              f"kernels: {name} vs its reference")


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------

def one_chip(run_config=RUN_CONFIG):
    cfg, params, hist = phase_train(run_config)
    phase_reference(cfg, params)
    phase_decode(cfg, params)
    del params      # the tuned-plan run needs the chip's memory for its own
    plan = tune_tp4_plan(cfg, run_config)
    train_under_plan("plan-1x1", cfg, run_config, "1x1", plan, hist["loss"])
    phase_kernels()


def four_chips(run_config=RUN_CONFIG):
    import jax

    from repro.launch import train
    from repro.launch.config import load_run_config, resolve_model

    check(len(jax.devices()) >= 4, f"--four-chips needs 4 devices, found "
                                   f"{len(jax.devices())}")
    cfg = resolve_model(load_run_config(run_config))
    tokens = _run_tokens(run_config)
    p1, h1 = train.main(["--config", run_config])
    summarize("1-chip", cfg, p1, h1, tokens)
    del p1
    p4, h4 = train.main(["--config", run_config, "--mesh", "1x4"])
    summarize("1x4-xla", cfg, p4, h4, tokens)
    say("1x4-xla", "peak_bytes_in_use per device " + " ".join(
        str((d.memory_stats() or {}).get("peak_bytes_in_use"))
        for d in jax.devices()))
    spans = {len(leaf.sharding.device_set) for leaf in jax.tree.leaves(p4)}
    say("1x4-xla", f"parameter leaves span {sorted(spans)} device(s)")
    check(spans == {4}, "1x4: every parameter lives on the 4-device mesh")
    del p4
    compare_losses("1x4-xla", "1x4 xla", h4["loss"], "1-chip", h1["loss"])
    plan = tune_tp4_plan(cfg, run_config)
    ht = train_under_plan("1x4-plan", cfg, run_config, "1x4", plan, h4["loss"])
    compare_losses("1x4-plan", "1x4 plan", ht["loss"], "1-chip", h1["loss"])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip TP path (1x4 mesh, XLA default "
                         "vs the tuned tp:4 plan) and what it is compared with")
    args = ap.parse_args(argv)

    # the float32 reference runs on JAX's CPU backend in this process
    plats = os.environ.get("JAX_PLATFORMS")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    sys.path.insert(0, os.path.join(HERE, "src"))
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX's first device is {dev.device_kind!r} "
              f"(platform {dev.platform!r}), not a TPU; nothing was run",
              file=sys.stderr)
        return 1
    from repro.launch.config import configure_compile_cache

    say("setup", f"compile cache {configure_compile_cache()}; jax "
                 f"{jax.__version__}; {len(jax.devices())} x {dev.device_kind}")
    if args.four_chips:
        four_chips()
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
