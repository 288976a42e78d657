"""Batched serving engine: continuous-batching-lite decode loop with
prefill-into-cache and greedy/temperature sampling.

``serve_step`` (one token against a seq_len cache) is the function the
decode-shape dry-runs lower; the Engine wraps it for the runnable examples.

Plan-aware serving: pass ``plan=`` (a ``TunedPlan``) or ``repo=`` (a
``PlanRepository``) and the engine decodes under that plan's per-site
collective runtimes at the ``serve.layer{i}.*`` SiteIds — applied through
the scoped plan stack per batch, with compiled steps cached per plan
digest so ``set_plan`` hot-swaps between batches retrace instead of
reusing stale chunk structure.

Fault-aware serving: ``fault_schedule=`` arms per-site drift detection
(``serving.health``) — each decoded token advances the batch clock, and a
site whose observed cost drifts past ``health_tolerance`` for
``health_window`` consecutive batches is demoted mid-generate to its
fallback knobs via a transactional plan swap (the demoted plan's step is
retraced before commit; failure rolls back).  ``health_events`` /
``health_report()`` expose the structured degradation log.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as M
from repro.serving.plans import DEFAULT_BAND, PlanBinding
from repro.serving.types import Request

__all__ = ["Engine", "Request", "make_serve_step"]


def _make_retune(binding, retune):
    """Lower the engines' ``retune=`` kwarg to a ``core.retune``
    ``RetuneService``: ``None``/``False`` off, ``True`` defaults, a dict
    of service kwargs, or an already-built service."""
    if not retune:
        return None
    from repro.core.retune import RetuneService

    if isinstance(retune, RetuneService):
        return retune
    opts = {} if retune is True else dict(retune)
    return RetuneService(binding, **opts)


def make_serve_step(cfg, *, backend: Optional[str] = None, mesh=None):
    """serve_step(params, tokens (B,1), caches[, pos_offset (B,)]) ->
    (next (B,1), caches).  ``mesh`` opts dense families into the sited
    explicit-collective decode path (``serve.layer{i}.*``)."""
    def serve_step(params, tokens, caches, pos_offset=None):
        logits, caches = M.decode_step(cfg, params, tokens, caches,
                                       backend=backend, mesh=mesh,
                                       pos_offset=pos_offset)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        return nxt, caches
    return serve_step


def _invalidate_pad_slots(caches, lens: jnp.ndarray):
    """Mark right-pad KV slots dead per row: ``slot_pos`` leaves are
    (..., B, W); slots at index >= the row's true length get -1 so decode
    never attends to them."""
    def fix(path, leaf):
        if str(getattr(path[-1], "key", "")) != "slot_pos":
            return leaf
        idx = jnp.arange(leaf.shape[-1])
        keep = idx[None, :] < lens[:, None]          # (B, W)
        return jnp.where(keep, leaf, -1)
    return jax.tree_util.tree_map_with_path(fix, caches)


class Engine:
    """Fixed-batch decode engine (the examples' serving driver)."""

    def __init__(self, cfg, params, *, batch_size: int, max_seq: int,
                 backend: Optional[str] = None, plan=None, repo=None,
                 plan_hardware: str = "tpu-v5e", plan_parallel=None,
                 plan_band: float = DEFAULT_BAND, mesh=None,
                 fault_schedule=None, health_window: int = 3,
                 health_tolerance: float = 0.25, retune=None,
                 plan_lint: str = "error"):
        self.cfg = cfg
        self.params = params
        self.batch = batch_size
        self.max_seq = max_seq
        self.backend = backend
        self._binding = PlanBinding(cfg, plan=plan, repo=repo,
                                    hardware=plan_hardware,
                                    parallel=plan_parallel, band=plan_band,
                                    max_seq=max_seq, lint=plan_lint)
        if fault_schedule is not None:
            self._binding.attach_faults(fault_schedule,
                                        tolerance=health_tolerance,
                                        window=health_window)
        self.retune_service = _make_retune(self._binding, retune)
        if mesh is None and self._binding.bound and cfg.family in (
                "dense", "moe", "vlm"):
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((jax.device_count(),), ("model",))
        self.mesh = mesh
        self._fns: Dict[tuple, Tuple] = {}     # plan digest -> (step, prefill)

    # ------------------------------------------------------------------
    def set_plan(self, plan) -> None:
        """Hot-swap the tuned plan between batches (TunedPlan, path to its
        JSON, runtime dict, or None to unpin)."""
        self._binding.set_plan(plan)

    @property
    def plan_stats(self) -> Dict[str, int]:
        return dict(self._binding.stats)

    @property
    def health_events(self) -> List[Dict]:
        """Structured degradation log: drift detections, demotions (with
        rollback status) and band-widening events, in order."""
        return list(self._binding.events)

    def health_report(self) -> str:
        return self._binding.health_report()

    @property
    def telemetry(self):
        """The binding's live ``SiteTelemetry`` ring buffer (one row of
        observed per-site costs per served batch)."""
        return self._binding.telemetry

    def _compiled(self, rt) -> Tuple:
        """The (step, prefill) pair traced under plan ``rt`` — cached per
        plan digest so a hot-swap retraces instead of reusing the old
        chunk structure."""
        key = self._binding.digest(rt)
        if key not in self._fns:
            cfg, backend, mesh = self.cfg, self.backend, self.mesh
            with self._binding.scope(rt):
                step = jax.jit(make_serve_step(cfg, backend=backend, mesh=mesh))
                prefill = jax.jit(
                    lambda p, b, c: M.forward_hidden(cfg, p, b, c,
                                                     backend=backend,
                                                     mesh=mesh)[1])
            self._fns[key] = (step, prefill)
        return self._fns[key]

    # ------------------------------------------------------------------
    def generate(self, prompts: List[np.ndarray], *, max_new: int = 32,
                 frames: Optional[np.ndarray] = None) -> List[List[int]]:
        assert len(prompts) == self.batch
        rt = self._binding.resolve(self.batch)
        step, prefill = self._compiled(rt)
        plen = max(len(p) for p in prompts)
        toks = np.zeros((self.batch, plen), np.int32)
        lens = np.asarray([len(p) for p in prompts], np.int32)
        for i, p in enumerate(prompts):    # right-pad; causal mask + per-row
            toks[i, :len(p)] = p           # slot_pos invalidation keep pads out
        with self._binding.scope(rt):
            caches = M.init_caches(self.cfg, self.batch, self.max_seq)
            if self.cfg.family == "audio":
                assert frames is not None
                caches["memory"] = jnp.asarray(frames)
            # the prompt's last token is decoded, not prefilled: its decode
            # step yields the first new token
            if plen > 1:
                batch = {"tokens": jnp.asarray(toks[:, :plen - 1])}
                caches = self._prefill_ragged(prefill, batch, caches, lens - 1)
            # decode each row from its true last token; the shared position
            # counter sits at plen - 1, so subtract each row's pad gap.
            cur = jnp.asarray(toks[np.arange(self.batch), lens - 1][:, None])
            offs = jnp.asarray(plen - lens, jnp.int32)
            outs: List[List[int]] = [[] for _ in range(self.batch)]
            for _ in range(max_new):
                t0 = time.perf_counter()
                cur, caches = step(self.params, cur, caches, offs)
                row = np.asarray(cur)[:, 0]          # device sync
                dt = time.perf_counter() - t0
                for i, t in enumerate(row):
                    outs[i].append(int(t))
                drifted = self._binding.health_tick(dt)
                if drifted:
                    # drift-scoped online re-tune first (zero-downtime plan
                    # swap between tokens); when the service declines —
                    # rate-limited, budget spent, or not armed — fall back
                    # to transactional demotion: the new plan's step is
                    # traced before the swap commits, then decode continues.
                    # Plans bind at trace time, so the enclosing scope
                    # (entered under the old plan) cannot leak in.
                    retuned = (self.retune_service.handle(drifted)
                               if self.retune_service is not None else None)
                    if retuned is None:
                        self._binding.demote(drifted, apply=self._compiled)
                    step, _ = self._compiled(self._binding.current)
        return outs

    def _prefill_ragged(self, prefill, batch, caches, lens: np.ndarray):
        caches = prefill(self.params, batch, caches)
        if self.cfg.family in ("ssm", "hybrid"):
            # recurrent states absorb right padding; equal-length prompts
            # only (same limitation as the continuous engine's admits).
            assert len(set(lens.tolist())) == 1, \
                "ssm/hybrid serving needs equal-length prompts"
            return caches
        return _invalidate_pad_slots(caches, jnp.asarray(lens))

    # ------------------------------------------------------------------
    def throughput_probe(self, *, steps: int = 8) -> Dict[str, float]:
        rt = self._binding.resolve(self.batch)
        step, _ = self._compiled(rt)
        with self._binding.scope(rt):
            caches = M.init_caches(self.cfg, self.batch, self.max_seq)
            if self.cfg.family == "audio":
                caches["memory"] = jnp.zeros(
                    (self.batch, self.cfg.encoder_seq, self.cfg.d_model))
            cur = jnp.zeros((self.batch, 1), jnp.int32)
            offs = jnp.zeros((self.batch,), jnp.int32)
            cur, caches = step(self.params, cur, caches, offs)   # compile
            jax.block_until_ready(cur)
            t0 = time.perf_counter()
            for _ in range(steps):
                cur, caches = step(self.params, cur, caches, offs)
            jax.block_until_ready(cur)
        dt = (time.perf_counter() - t0) / steps
        return {"s_per_token": dt, "tokens_per_s": self.batch / dt}
