"""Continuous batching: per-slot caches + request queue.

The fixed-batch Engine decodes in lockstep (one shared position counter).
This engine vmaps the single-sequence decode over a slot axis, so every
slot has its own position/cache state; finished slots are refilled from the
queue without disturbing the others — the standard continuous-batching
serving loop, built on the same ``model.decode_step``.

Plan-aware serving: with ``repo=`` the engine re-resolves the tuned plan at
admit time — the in-flight batch shape drifts as requests arrive and
finish, and the repository's tolerance band (exact fingerprint first, then
nearest same-structure shape) picks the plan for the current shape.  With
``plan=`` the plan is pinned; ``set_plan`` hot-swaps it between ticks.
Compiled steps are cached per plan digest, so a swap retraces rather than
reusing chunk structure from the previous plan.

Fault-aware serving mirrors the fixed-batch engine: ``fault_schedule=``
arms per-site drift detection, and a flagged site is demoted between
ticks via a transactional plan swap — the loop naturally picks up the
degraded plan's compiled step on its next iteration.
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

__all__ = ["ContinuousEngine", "Request"]


class ContinuousEngine:
    """``slots`` independent sequences decoded as one vmapped batch."""

    def __init__(self, cfg, params, *, slots: int, max_seq: int,
                 eos_id: Optional[int] = None, plan=None, repo=None,
                 plan_hardware: str = "tpu-v5e", plan_parallel=None,
                 plan_band: float = DEFAULT_BAND, mesh=None,
                 fault_schedule=None, health_window: int = 3,
                 health_tolerance: float = 0.25, retune=None,
                 plan_lint: str = "error"):
        assert cfg.family != "audio", "continuous engine is decoder-only"
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_seq = max_seq
        self.eos_id = eos_id
        self._binding = PlanBinding(cfg, plan=plan, repo=repo,
                                    hardware=plan_hardware,
                                    parallel=plan_parallel, band=plan_band,
                                    max_seq=max_seq, lint=plan_lint)
        if fault_schedule is not None:
            self._binding.attach_faults(fault_schedule,
                                        tolerance=health_tolerance,
                                        window=health_window)
        from repro.serving.engine import _make_retune
        self.retune_service = _make_retune(self._binding, retune)
        if mesh is None and self._binding.bound and cfg.family in (
                "dense", "moe", "vlm"):
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((jax.device_count(),), ("model",))
        self.mesh = mesh

        # per-slot caches: the B axis of one shared pytree acts as the slot
        # axis; decode is vmapped over it so each slot keeps its own pos.
        self.caches = jax.vmap(lambda _: M.init_caches(cfg, 1, max_seq))(
            jnp.arange(slots))

        self._fns: Dict[tuple, Tuple] = {}     # plan digest -> (step, prefill)
        self._active: Dict[int, Request] = {}      # slot -> request
        self._queue: List[Request] = []
        self._cur = jnp.zeros((slots,), jnp.int32)
        self._resolved_n: Optional[int] = None     # batch size last resolved

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
        """Structured degradation log (drift / demotion / band events)."""
        return list(self._binding.events)

    def health_report(self) -> str:
        return self._binding.health_report()

    @property
    def telemetry(self):
        """The binding's live ``SiteTelemetry`` ring buffer."""
        return self._binding.telemetry

    def _compiled(self, rt) -> Tuple:
        key = self._binding.digest(rt)
        if key in self._fns:
            return self._fns[key]
        cfg, mesh = self.cfg, self.mesh

        def step_one(params, tok, cache):
            logits, cache = M.decode_step(cfg, params, tok[None, None], cache,
                                          mesh=mesh)
            nxt = jnp.argmax(logits[0, -1]).astype(jnp.int32)
            return nxt, cache

        def prefill_one(params, toks, length, cache):
            # right-padded prompt: clamp pos back to the true length and
            # invalidate padded KV slots (slot_pos = -1) so decode never
            # attends to them.  NOTE: SSM/hybrid states absorb padding during
            # a padded prefill — those families need length-bucketed admits
            # (documented limitation of this demo engine).
            _, cache, _ = M.forward_hidden(cfg, params, {"tokens": toks[None]},
                                           cache, mesh=mesh)

            def fix(path, leaf):
                name = str(getattr(path[-1], "key", ""))
                if name == "slot_pos":        # (..., W)
                    idx = jnp.arange(leaf.shape[-1])
                    return jnp.where(idx < length, leaf, -1)
                return leaf

            cache = jax.tree_util.tree_map_with_path(fix, cache)
            return dict(cache, pos=length.astype(jnp.int32))

        with self._binding.scope(rt):
            step = jax.jit(jax.vmap(step_one, in_axes=(None, 0, 0)))
            prefill = jax.jit(jax.vmap(prefill_one, in_axes=(None, 0, 0, 0)))
        self._fns[key] = (step, prefill)
        return self._fns[key]

    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        self._queue.append(req)

    def _admit(self, prefill) -> None:
        free = [s for s in range(self.slots) if s not in self._active]
        admits = []
        while free and self._queue:
            slot = free.pop(0)
            req = self._queue.pop(0)
            self._active[slot] = req
            admits.append((slot, req))
        if not admits:
            return
        # the prompt's last token is decoded, not prefilled: its decode
        # step yields the first new token
        plen = max(len(r.prompt) for _, r in admits) - 1
        toks = np.zeros((len(admits), plen), np.int32)
        lens = np.zeros((len(admits),), np.int32)
        for i, (_, r) in enumerate(admits):
            toks[i, :len(r.prompt) - 1] = r.prompt[:-1]
            lens[i] = len(r.prompt) - 1
        filled = jax.vmap(lambda _: M.init_caches(self.cfg, 1, self.max_seq))(
            jnp.arange(len(admits)))
        if plen:
            filled = prefill(self.params, jnp.asarray(toks),
                             jnp.asarray(lens), filled)
        # scatter the admitted slots' caches / current tokens into place
        slot_ids = jnp.asarray([s for s, _ in admits])
        self.caches = jax.tree.map(
            lambda all_, new: all_.at[slot_ids].set(new), self.caches, filled)
        last = jnp.asarray([int(r.prompt[-1]) for _, r in admits], jnp.int32)
        self._cur = self._cur.at[slot_ids].set(last)

    # ------------------------------------------------------------------
    def run(self, *, max_ticks: int = 1000) -> List[Request]:
        """Drive until queue + active slots drain; returns finished requests."""
        done: List[Request] = []
        for _ in range(max_ticks):
            if not self._active and not self._queue:
                break
            # admissions change the in-flight shape, so re-resolve the plan
            # (repo-bound engines may land on a different banded hit) before
            # tracing/looking up this tick's compiled functions.  Only the
            # shape matters, so an unchanged batch size keeps its plan.
            n_after = max(1, min(self.slots,
                                 len(self._active) + len(self._queue)))
            if n_after != self._resolved_n:
                self._binding.resolve(n_after)
                self._resolved_n = n_after
            rt = self._binding.current
            step, prefill = self._compiled(rt)
            with self._binding.scope(rt):
                self._admit(prefill)
                if not self._active:
                    break
                t0 = time.perf_counter()
                nxt, self.caches = step(self.params, self._cur, self.caches)
                nxt.block_until_ready()
                dt = time.perf_counter() - t0
            drifted = self._binding.health_tick(dt)
            if drifted:
                # online re-tune first; demote when the service declines.
                # Either way the loop re-fetches the compiled step from
                # the swapped plan on the next tick (zero dropped tokens).
                retuned = (self.retune_service.handle(drifted)
                           if self.retune_service is not None else None)
                if retuned is None:
                    self._binding.demote(drifted, apply=self._compiled)
            self._cur = nxt
            finished = []
            for slot, req in self._active.items():
                t = int(nxt[slot])
                req.out.append(t)
                if len(req.out) >= req.max_new or t == self.eos_id:
                    finished.append(slot)
            for slot in finished:
                done.append(self._active.pop(slot))
        return done
