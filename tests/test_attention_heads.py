"""Self-attention run head-parallel over the model axis (one ``shard_map``
with the KV heads on ``model``) against the same call with no mesh: values
and the gradients of the q/k/v/o weights, on a 4-device host mesh (spawned
in a subprocess so the main test session keeps 1 device)."""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import json, os, warnings
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
import jax.numpy as jnp
import numpy as np
from repro.configs.base import ModelConfig
from repro.launch.mesh import make_test_mesh
from repro.models import layers as L
from repro.parallel import collectives as C
from repro.parallel import constraints as CT

BASE = dict(name="t", family="dense", num_layers=1, d_model=64,
            num_heads=8, num_kv_heads=4, head_dim=16, d_ff=128,
            vocab_size=64)
# case -> (config fields, mesh, seq, attention keyword arguments)
CASES = {
    "rope_gqa_blockwise": (dict(), (1, 4), 520,
                           dict(kv_block=128, blockwise_threshold=64)),
    "dense_qk_norm": (dict(qk_norm=True, rope_fraction=0.5), (1, 4), 32, {}),
    "sliding_window": (dict(sliding_window=16), (1, 4), 96,
                       dict(kv_block=32, blockwise_threshold=32)),
    "alibi_mha": (dict(num_kv_heads=8, pos_kind="alibi", attn_bias=True),
                  (1, 4), 40, {}),
    "mrope": (dict(pos_kind="mrope", mrope_sections=(4, 2, 2)), (2, 2), 48,
              {}),
    "bidirectional": (dict(pos_kind="learned"), (2, 2), 32,
                      dict(causal=False)),
    "heads_indivisible": (dict(num_heads=6, num_kv_heads=2), (1, 4), 32, {}),
}
B = 4


def run(name):
    fields, shape, S, kw = CASES[name]
    cfg = ModelConfig(**{**BASE, **fields})
    key = jax.random.PRNGKey(7)
    kp, kx = jax.random.split(key)
    p = L.init_attention(kp, cfg)
    if "q_norm" in p:          # scales away from 1, so their gradient counts
        p["q_norm"]["scale"] = 1.0 + 0.1 * jnp.arange(cfg.head_dim) / cfg.head_dim
    x = jax.random.normal(kx, (B, S, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    if cfg.pos_kind == "mrope":   # three different position streams
        pos = jnp.stack([pos, pos // 3, (pos * 7) % S])

    def loss(p):
        out, _ = L.attention(p, cfg, x, pos, **kw)
        return jnp.sum(out * jnp.cos(out)), out

    f = jax.jit(jax.value_and_grad(loss, has_aux=True))
    (_, want), want_g = f(p)
    mesh = make_test_mesh(shape, ("data", "model"))
    C.reset_degraded_warnings()
    with jax.set_mesh(mesh), CT.use_axes(("data",), "model"), \
            warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        jaxpr = str(jax.make_jaxpr(loss)(p))
        f = jax.jit(jax.value_and_grad(loss, has_aux=True))
        (_, got), got_g = f(p)
        f(p)
    labels = [w.message.site for w in caught
              if isinstance(w.message, C.CollectiveDegradedWarning)]

    def gap(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))

    return {"out": gap(got, want),
            "grads": {k: gap(got_g[k]["w"], want_g[k]["w"])
                      for k in ("q", "k", "v", "o")}
            | {k: gap(got_g[k]["scale"], want_g[k]["scale"])
               for k in ("q_norm", "k_norm") if k in p},
            "shard_map": "shard_map" in jaxpr, "labels": labels}


print(json.dumps({name: run(name) for name in CASES}))
"""

CASES = ["rope_gqa_blockwise", "dense_qk_norm", "sliding_window",
         "alibi_mha", "mrope", "bidirectional", "heads_indivisible"]


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stdout + out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
@pytest.mark.parametrize("case", CASES)
def test_head_parallel_attention_matches_unsharded(results, case):
    r = results[case]
    assert r["out"] < 1e-5, r
    assert all(g < 1e-5 for g in r["grads"].values()), r
    if case == "heads_indivisible":
        # 2 KV heads on a 4-way model axis: GSPMD's layout, warned once
        assert not r["shard_map"] and r["labels"] == ["attention.heads"], r
    else:
        assert r["shard_map"] and r["labels"] == [], r
