"""Per-kernel correctness: Pallas (interpret mode, on the CPU) and
chunked-matmul forms vs the naive per-step jnp oracle, swept over shapes
and dtypes.  Every Pallas call here names ``interpret=True``: the tests run
on a CPU backend, and the chip compile is pinned by test_tpu_compile.py."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.ssd import ssd_pallas
from repro.kernels.wkv6 import wkv6_pallas


def _wkv_inputs(B, S, H, K, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    r, k, v = (jax.random.normal(ks[i], (B, S, H, K), dtype) for i in range(3))
    w_log = -jnp.exp(jax.random.normal(ks[3], (B, S, H, K)) * 0.5).astype(jnp.float32)
    u = (jax.random.normal(ks[4], (H, K)) * 0.1).astype(dtype)
    return r, k, v, w_log, u


@pytest.mark.parametrize("B,S,H,K", [(1, 32, 1, 8), (2, 64, 3, 16), (2, 96, 2, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wkv6_pallas_matches_ref(B, S, H, K, dtype):
    r, k, v, w_log, u = _wkv_inputs(B, S, H, K, dtype)
    y_ref, s_ref = ref.wkv6_ref(r, k, v, w_log, u)
    y, s = wkv6_pallas(r, k, v, w_log, u, chunk=32, interpret=True)
    scale_y = float(jnp.abs(y_ref.astype(jnp.float32)).max()) or 1.0
    rtol = 3e-2 if dtype == jnp.bfloat16 else 1e-3
    assert jnp.abs(y.astype(jnp.float32) - y_ref.astype(jnp.float32)).max() < rtol * scale_y
    assert jnp.abs(s - s_ref).max() < rtol * max(1.0, float(jnp.abs(s_ref).max()))


def test_wkv6_chunked_matches_ref_with_state():
    r, k, v, w_log, u = _wkv_inputs(2, 64, 2, 16, jnp.float32)
    y1, s1 = ref.wkv6_ref(r, k, v, w_log, u)
    # split into two halves with state carry
    ya, sa = ref.wkv6_chunked_ref(r[:, :32], k[:, :32], v[:, :32], w_log[:, :32], u, chunk=16)
    yb, sb = ref.wkv6_chunked_ref(r[:, 32:], k[:, 32:], v[:, 32:], w_log[:, 32:], u,
                                  state=sa, chunk=16)
    assert jnp.abs(jnp.concatenate([ya, yb], 1) - y1).max() < 1e-3
    assert jnp.abs(sb - s1).max() < 1e-3


def _ssd_inputs(B, S, H, P, N, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))).astype(jnp.float32)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, H, N), dtype)
    Cm = jax.random.normal(ks[4], (B, S, H, N), dtype)
    D = jnp.ones((H,))
    return x, dt, A, Bm, Cm, D


@pytest.mark.parametrize("B,S,H,P,N", [(1, 32, 1, 4, 8), (2, 64, 3, 8, 16), (1, 128, 2, 16, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_pallas_matches_ref(B, S, H, P, N, dtype):
    x, dt, A, Bm, Cm, D = _ssd_inputs(B, S, H, P, N, dtype)
    y_ref, s_ref = ref.ssd_ref(x, dt, A, Bm, Cm, D)
    y, s = ssd_pallas(x, dt, A, Bm, Cm, D, chunk=32, interpret=True)
    scale_y = float(jnp.abs(y_ref.astype(jnp.float32)).max()) or 1.0
    rtol = 3e-2 if dtype == jnp.bfloat16 else 1e-3
    assert jnp.abs(y.astype(jnp.float32) - y_ref.astype(jnp.float32)).max() < rtol * scale_y
    assert jnp.abs(s - s_ref).max() < rtol * max(1.0, float(jnp.abs(s_ref).max()))


def test_ssd_state_continuation():
    x, dt, A, Bm, Cm, D = _ssd_inputs(2, 64, 2, 8, 16, jnp.float32)
    y1, s1 = ref.ssd_ref(x, dt, A, Bm, Cm, D)
    ya, sa = ref.ssd_chunked_ref(x[:, :32], dt[:, :32], A, Bm[:, :32], Cm[:, :32], D, chunk=16)
    yb, sb = ref.ssd_chunked_ref(x[:, 32:], dt[:, 32:], A, Bm[:, 32:], Cm[:, 32:], D,
                                 state=sa, chunk=16)
    assert jnp.abs(jnp.concatenate([ya, yb], 1) - y1).max() < 1e-3
    assert jnp.abs(sb - s1).max() < 1e-3


@pytest.mark.parametrize("shape", [(4, 64), (2, 7, 128), (3, 5, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_pallas(shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), shape, dtype)
    scale = jnp.linspace(0.5, 1.5, shape[-1])
    y = rmsnorm_pallas(x, scale, interpret=True)
    y_ref = ref.rmsnorm_ref(x, scale)
    assert jnp.abs(y.astype(jnp.float32) - y_ref.astype(jnp.float32)).max() < 2e-2


def test_ops_dispatch_backends():
    r, k, v, w_log, u = _wkv_inputs(1, 64, 2, 16, jnp.float32)
    outs = [ops.wkv6(r, k, v, w_log, u, backend=b, interpret=True)[0]
            for b in ("ref", "chunked", "pallas")]
    for o in outs[1:]:
        assert jnp.abs(o - outs[0]).max() < 1e-3
    x, dt, A, Bm, Cm, D = _ssd_inputs(1, 64, 2, 8, 16, jnp.float32)
    outs = [ops.ssd(x, dt, A, Bm, Cm, D, backend=b, interpret=True)[0]
            for b in ("ref", "chunked", "pallas")]
    for o in outs[1:]:
        assert jnp.abs(o - outs[0]).max() < 1e-3


def test_pallas_interpret_is_decided_from_the_backend(monkeypatch):
    # the tests run on a CPU backend: the interpreter is the default there
    assert ops.pallas_interpret() is True
    assert ops.pallas_interpret(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.pallas_interpret() is False
    with pytest.raises(ValueError, match="TPU"):
        ops.pallas_interpret(True)


def test_ops_pad_non_multiple_seq():
    r, k, v, w_log, u = _wkv_inputs(1, 50, 2, 16, jnp.float32)   # 50 % 32 != 0
    y_ref, s_ref = ref.wkv6_ref(r, k, v, w_log, u)
    y, s = ops.wkv6(r, k, v, w_log, u, backend="chunked", chunk=32)
    assert y.shape == y_ref.shape
    assert jnp.abs(y - y_ref).max() < 1e-3
    assert jnp.abs(s - s_ref).max() < 1e-3


@pytest.mark.parametrize("B,S,Hq,Hkv,h", [(1, 64, 2, 2, 16), (2, 128, 4, 2, 32),
                                           (1, 96, 6, 3, 64)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_pallas(B, S, Hq, Hkv, h, causal):
    import math
    from repro.kernels.flash import flash_attention
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, h))
    k = jax.random.normal(ks[1], (B, S, Hkv, h))
    v = jax.random.normal(ks[2], (B, S, Hkv, h))
    o = flash_attention(q, k, v, causal=causal, q_block=32, kv_block=32,
                        interpret=True)
    G = Hq // Hkv
    kk, vv = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / math.sqrt(h)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s, -1e30)
    ref_o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vv)
    assert jnp.abs(o - ref_o).max() < 1e-4
