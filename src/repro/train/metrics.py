"""Training metrics: analytic step FLOPs and MFU accounting.

MFU = model FLOPs (6·N_active·tokens, no remat credit) / wall / peak —
the MaxText/PaLM convention.  The peak comes from ``PEAK_BF16_FLOPS``,
keyed by the device's ``device_kind``; a device that is not in the table
has no MFU (``None``: not measured), never a borrowed peak.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

# Dense bf16 peak per chip, keyed by ``jax.Device.device_kind``.
# TPU v5e: 197 TFLOP/s (Google Cloud documentation, "TPU v5e").
PEAK_BF16_FLOPS: Dict[str, float] = {
    "TPU v5 lite": 197e12,
}


def peak_flops(device_kind: str) -> Optional[float]:
    """The table's peak for ``device_kind``, or ``None`` when the device is
    not in it (then MFU is not measured)."""
    return PEAK_BF16_FLOPS.get(device_kind)


@dataclass
class StepFlops:
    model: float        # 6·N_active·tokens (the MFU numerator)
    executed: float     # incl. remat recompute (8·N_active·tokens)


def train_step_flops(cfg, tokens: int, *, remat: bool = True) -> StepFlops:
    n = cfg.param_count(active_only=True)
    return StepFlops(model=6.0 * n * tokens,
                     executed=(8.0 if remat else 6.0) * n * tokens)


def mfu(cfg, tokens: int, step_seconds: float, *, peak: float,
        chips: int = 1) -> float:
    f = train_step_flops(cfg, tokens)
    return f.model / max(step_seconds, 1e-12) / (chips * peak)


class Tracker:
    """Rolling window over step times; used by the train loop."""

    def __init__(self, tokens_per_step: int, *, window: int = 20):
        self.tokens = tokens_per_step
        self.window = window
        self.times: list = []

    def update(self, step_seconds: float) -> Dict[str, float]:
        self.times.append(step_seconds)
        recent = self.times[-self.window:]
        avg = sum(recent) / len(recent)
        return {"step_s": step_seconds, "tokens_per_s": self.tokens / avg}
