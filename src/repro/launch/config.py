"""JSON run-config loader for the launchers.

    PYTHONPATH=src python -m repro.launch.train --config runs/smoke.json

A run config is a flat JSON object whose keys mirror the launcher flags
(``arch``, ``steps``, ``seq``, ``batch``, ``lr``, ``grad_accum``, ``mesh``,
``smoke``, ``ckpt``) plus optional ``overrides`` applied to the
ModelConfig (e.g. {"sliding_window": 8192}) and a free-text ``comment``
(what the run cuts from the published model, and why).  CLI flags win
over file values; ``overrides`` compose via ModelConfig.replace.

``configure_compile_cache`` is the launchers' one place for JAX's
persistent compilation cache.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict

import jax

from repro.configs import get_config, get_smoke_config
from repro.configs.base import ModelConfig

# the checkout's root: <root>/src/repro/launch/config.py
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def configure_compile_cache() -> str:
    """Keep JAX's persistent compilation cache at a fixed path and return
    it.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache is ``<checkout>/.jax_cache``
    (git-ignored).  Called from the launchers' ``main()``, never on
    import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path

_LAUNCH_KEYS = ("arch", "steps", "seq", "batch", "lr", "grad_accum",
                "mesh", "smoke", "ckpt", "log_every")


def load_run_config(path: str) -> Dict[str, Any]:
    with open(path) as f:
        raw = json.load(f)
    unknown = set(raw) - set(_LAUNCH_KEYS) - {"overrides", "comment"}
    if unknown:
        raise ValueError(f"unknown run-config keys: {sorted(unknown)}")
    return raw


def resolve_model(run_cfg: Dict[str, Any]) -> ModelConfig:
    arch = run_cfg["arch"]
    cfg = get_smoke_config(arch) if run_cfg.get("smoke") else get_config(arch)
    overrides = run_cfg.get("overrides") or {}
    if overrides:
        cfg = cfg.replace(**overrides)
    return cfg


def merge_cli(run_cfg: Dict[str, Any], args, *, defaults: Dict[str, Any]):
    """File value unless the CLI flag was explicitly set (differs from its
    argparse default)."""
    out = dict(run_cfg)
    for k, dflt in defaults.items():
        v = getattr(args, k, None)
        if v is not None and v != dflt:
            out[k] = v
        out.setdefault(k, dflt)
    return out
