"""Public jit'd entry points for the kernel layer.

``backend``:
  * "ref"      — naive per-step jnp scan (exact oracle)
  * "chunked"  — chunked matmul-form jnp (same algorithm as the Pallas kernel;
                 the default: MXU-friendly, sub-quadratic activation memory)
  * "pallas"   — the Pallas TPU kernel, compiled for the chip; on a CPU
                 backend the Pallas interpreter runs it instead
                 (``pallas_interpret`` is the one place that decides)

The model code always calls these wrappers; the dry-run path uses "chunked"
(pure jnp lowers on any backend), tests sweep all three against "ref".
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref

_DEFAULT = "chunked"


def set_default_backend(name: str) -> None:
    global _DEFAULT
    assert name in ("ref", "chunked", "pallas")
    _DEFAULT = name


def default_backend() -> str:
    return _DEFAULT


def pallas_interpret(interpret: bool | None = None) -> bool:
    """Whether a Pallas kernel runs in the interpreter: ``None`` decides
    from the default backend — the interpreter on a CPU backend only —
    and an explicit ``True`` is refused on a TPU, so no path reaches the
    chip with the interpreter."""
    backend = jax.default_backend()
    if interpret is None:
        return backend == "cpu"
    if interpret and backend == "tpu":
        raise ValueError("the Pallas interpreter is not run on a TPU backend")
    return interpret


def _pad_seq(a, mult):
    S = a.shape[1]
    pad = (-S) % mult
    if pad:
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
    return a, S


def wkv6(r, k, v, w_log, u, state=None, *, backend: str | None = None,
         chunk: int = 32, interpret: bool | None = None):
    """RWKV6 WKV. r,k,v,w_log (B,S,H,K); u (H,K) -> y (B,S,H,V), state (B,H,K,V)."""
    backend = backend or _DEFAULT
    if backend == "ref" or r.shape[1] == 1:
        return _ref.wkv6_ref(r, k, v, w_log, u, state)
    if backend == "chunked":
        (r, S0), (k, _), (v, _), (w_log, _) = (_pad_seq(a, chunk) for a in (r, k, v, w_log))
        y, st = _ref.wkv6_chunked_ref(r, k, v, w_log, u, state, chunk=chunk)
        return y[:, :S0], st
    from repro.kernels import wkv6 as _pk
    (r, S0), (k, _), (v, _), (w_log, _) = (_pad_seq(a, chunk) for a in (r, k, v, w_log))
    y, st = _pk.wkv6_pallas(r, k, v, w_log, u, state, chunk=chunk,
                            interpret=pallas_interpret(interpret))
    return y[:, :S0], st


def ssd(x, dt, A, Bm, Cm, D, state=None, *, backend: str | None = None,
        chunk: int = 64, interpret: bool | None = None):
    """Mamba2 SSD. x (B,S,H,P); dt (B,S,H); A,D (H,); Bm,Cm (B,S,H,N)."""
    backend = backend or _DEFAULT
    if backend == "ref" or x.shape[1] == 1:
        return _ref.ssd_ref(x, dt, A, Bm, Cm, D, state)
    if backend == "chunked":
        (x, S0), (dt, _), (Bm, _), (Cm, _) = (_pad_seq(a, chunk) for a in (x, dt, Bm, Cm))
        y, st = _ref.ssd_chunked_ref(x, dt, A, Bm, Cm, D, state, chunk=chunk)
        return y[:, :S0], st
    from repro.kernels import ssd as _pk
    (x, S0), (dt, _), (Bm, _), (Cm, _) = (_pad_seq(a, chunk) for a in (x, dt, Bm, Cm))
    y, st = _pk.ssd_pallas(x, dt, A, Bm, Cm, D, state, chunk=chunk,
                           interpret=pallas_interpret(interpret))
    return y[:, :S0], st


def rmsnorm(x, scale, *, backend: str | None = None, eps: float = 1e-5,
            interpret: bool | None = None):
    backend = backend or _DEFAULT
    if backend in ("ref", "chunked"):
        return _ref.rmsnorm_ref(x, scale, eps)
    from repro.kernels import rmsnorm as _pk
    return _pk.rmsnorm_pallas(x, scale, eps=eps,
                              interpret=pallas_interpret(interpret))
