"""Substrate: optimizer, schedules, checkpointing, trainer, serving engine."""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.data.pipeline import DataConfig, SyntheticCorpus
from repro.models import model as M
from repro.optim import adamw, schedules
from repro.serving.engine import Engine
from repro.train import checkpoint
from repro.train.trainer import TrainConfig, make_train_step, train_loop


def test_adamw_minimizes_quadratic():
    params = {"w": jnp.array([5.0, -3.0])}
    state = adamw.init_state(params)
    cfg = adamw.AdamWConfig(lr=0.2, weight_decay=0.0)
    for _ in range(120):
        grads = jax.grad(lambda p: jnp.sum((p["w"] - 1.0) ** 2))(params)
        params, state, _ = adamw.apply_updates(params, grads, state, cfg)
    assert jnp.abs(params["w"] - 1.0).max() < 0.05


def test_grad_clipping():
    params = {"w": jnp.zeros(3)}
    state = adamw.init_state(params)
    cfg = adamw.AdamWConfig(clip_norm=1.0)
    _, _, m = adamw.apply_updates(params, {"w": jnp.ones(3) * 1e6}, state, cfg)
    assert float(m["grad_norm"]) > 1e5      # reported pre-clip


def test_schedule_shapes():
    s = schedules.warmup_cosine(jnp.arange(0, 1000, 100), warmup=100, total=1000)
    assert float(s[0]) == 0.0
    assert float(s.max()) <= 1.0


def test_checkpoint_roundtrip():
    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
            "b": {"c": jnp.ones((4,), jnp.int32)}}
    with tempfile.TemporaryDirectory() as d:
        checkpoint.save(d, tree, step=7)
        restored, step = checkpoint.restore(d, tree)
        assert step == 7
        assert np.array_equal(np.asarray(restored["a"]), np.asarray(tree["a"]))
        checkpoint.save(d, tree, step=8)
        checkpoint.save(d, tree, step=9)
        _, step = checkpoint.restore(d, tree)
        assert step == 9


def test_checkpoint_corrupt_falls_back_to_earlier_step():
    import os
    import pytest
    tree = {"a": jnp.arange(4.0), "b": jnp.ones((2,), jnp.int32)}
    with tempfile.TemporaryDirectory() as d:
        checkpoint.save(d, tree, step=1)
        checkpoint.save(d, tree, step=2)
        checkpoint.save(d, tree, step=3)
        # truncate the newest checkpoint's arrays mid-write
        with open(os.path.join(d, "step_00000003", "arrays.npz"), "wb") as f:
            f.write(b"PK\x03\x04 torn write")
        with pytest.warns(RuntimeWarning, match="falling back"):
            restored, step = checkpoint.restore(d, tree)
        assert step == 2
        assert np.array_equal(np.asarray(restored["a"]), np.asarray(tree["a"]))
        # explicit-step restores fall back the same way
        with open(os.path.join(d, "step_00000002", "manifest.json"), "w") as f:
            f.write("{not json")
        with pytest.warns(RuntimeWarning, match="falling back"):
            _, step = checkpoint.restore(d, tree, step=2)
        assert step == 1
        # every candidate corrupt -> a clear error naming what was tried
        with open(os.path.join(d, "step_00000001", "arrays.npz"), "wb") as f:
            f.write(b"")
        with pytest.warns(RuntimeWarning):
            with pytest.raises(FileNotFoundError, match="no intact checkpoint"):
                checkpoint.restore(d, tree, step=1)


def test_training_reduces_loss():
    cfg = get_smoke_config("stablelm-3b")
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=64, global_batch=4)
    tcfg = TrainConfig(warmup=3, total_steps=25)
    _, hist = train_loop(cfg, tcfg, iter(SyntheticCorpus(dc)), steps=25,
                         log_every=0)
    assert np.mean(hist["loss"][-5:]) < np.mean(hist["loss"][:5]) - 0.2


def test_grad_accum_matches_full_batch():
    cfg = get_smoke_config("h2o-danube-1.8b")
    rng = jax.random.PRNGKey(0)
    params = M.init_params(cfg, rng)
    opt = adamw.init_state(params)
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=4)
    batch = {k: jnp.asarray(v) for k, v in SyntheticCorpus(dc).batch(0).items()}
    s1 = jax.jit(make_train_step(cfg, TrainConfig(warmup=1, total_steps=10)))
    s2 = jax.jit(make_train_step(cfg, TrainConfig(warmup=1, total_steps=10,
                                                  grad_accum=2)))
    p1, _, m1 = s1(params, opt, batch, jnp.asarray(0))
    p2, _, m2 = s2(params, opt, batch, jnp.asarray(0))
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 2e-2
    d = jax.tree.reduce(lambda a, b: max(a, float(jnp.abs(b).max())),
                        jax.tree.map(lambda x, y: x - y, p1, p2), 0.0)
    assert d < 5e-3     # same update up to microbatch loss-normalization noise


def test_engine_generate_and_probe():
    cfg = get_smoke_config("stablelm-3b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, batch_size=2, max_seq=48)
    prompts = [np.array([1, 2, 3], np.int32), np.array([4, 5, 6], np.int32)]
    outs = eng.generate(prompts, max_new=4)
    assert len(outs) == 2 and all(len(o) == 4 for o in outs)
    assert all(0 <= t < cfg.vocab_size for o in outs for t in o)
    # greedy decode is deterministic
    outs2 = eng.generate(prompts, max_new=4)
    assert outs == outs2


def test_constrain_skips_only_without_a_mesh():
    import pytest
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.parallel import constraints as CT

    x = jnp.ones((2, 4, 8))
    assert CT._constrain(x, P("nope")) is x          # no mesh set: no-op
    with jax.set_mesh(make_mesh((1, 1), ("data", "model"))):
        with pytest.raises(ValueError, match="nope"):
            CT._constrain(x, P("nope"))              # a bad spec surfaces
        with CT.use_axes(("data",), "model"):
            assert jax.jit(CT.btd)(x).shape == x.shape


def test_compile_cache_dir_is_fixed(monkeypatch):
    import os

    from repro.launch import config

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        jax.config.update("jax_compilation_cache_dir", was)
        assert config.configure_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == was   # left to JAX
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = config.configure_compile_cache()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_train_main_returns_what_it_ran_and_scopes_plan_and_mesh(tmp_path):
    from repro.core import extract_workload, tune
    from repro.core.extract import parse_parallel
    from repro.launch import train
    from repro.parallel import collectives as C

    cfg = get_smoke_config("h2o-danube-1.8b")
    plan = tune(extract_workload(cfg, parse_parallel("tp:4"), seq=32,
                                 global_batch=2), "tpu-v5e", seed=0)
    plan.save(str(tmp_path / "plan.json"))
    base = ["--arch", "h2o-danube-1.8b", "--smoke", "--steps", "2",
            "--seq", "32", "--batch", "2", "--log-every", "0"]
    p0, h0 = train.main(base)
    p1, h1 = train.main(base + ["--mesh", "1x1", "--tuned-plan",
                                str(tmp_path / "plan.json")])
    assert len(h0["loss"]) == len(h1["loss"]) == len(h1["step_time"]) == 2
    np.testing.assert_allclose(h1["loss"], h0["loss"], atol=1e-4)
    assert set(h0) == set(h1) == {"loss", "step_time"}
    assert jax.tree.structure(p0) == jax.tree.structure(p1)
    # neither the plan nor the mesh outlives the call
    assert C.active_runtime_plan() == {}
    assert jax.sharding.get_abstract_mesh().empty
