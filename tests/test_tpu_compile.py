"""Compiles for a described TPU v5e (no chip attached): every Pallas kernel
at the widths of a zoo model, and the 4-layer h2o-danube-1.8b train step
that ``chip_smoke.py`` runs, which must fit one chip's 16 GB.

The topology is described inside a module fixture, never at import: only
the worker that runs these tests loads the TPU compiler.  JAX's persistent
compilation cache is off around the compiles, since an entry compiled for
a described chip cannot be read back here.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash import flash_attention
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.ssd import ssd_pallas
from repro.kernels.wkv6 import wkv6_pallas

RUN_CONFIG = os.path.join(os.path.dirname(__file__), "..", "runs",
                          "h2o_danube_1p8b_4layer.json")
HBM_BYTES = 16e9        # one TPU v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


# kernel -> (call with interpret off, argument shapes at a zoo model's widths)
KERNELS = {
    "rmsnorm": (lambda x, s: rmsnorm_pallas(x, s, interpret=False),
                (_f32(4, 2048, 2560), _f32(2560))),            # h2o-danube
    "wkv6": (lambda *a: wkv6_pallas(*a, interpret=False),
             tuple(_f32(2, 1024, 32, 64) for _ in range(4))
             + (_f32(32, 64),)),                               # rwkv6-1.6b
    "ssd": (lambda *a: ssd_pallas(*a, interpret=False),
            (_f32(1, 1024, 112, 64), _f32(1, 1024, 112), _f32(112),
             _f32(1, 1024, 112, 64), _f32(1, 1024, 112, 64),
             _f32(112))),                                      # zamba2-7b
    "flash": (lambda q, k, v: flash_attention(q, k, v, interpret=False),
              (_f32(1, 2048, 32, 80), _f32(1, 2048, 8, 80),
               _f32(1, 2048, 8, 80))),                         # h2o-danube
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    compiled = jax.jit(fn).lower(*_on(one_chip, shapes)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_h2o_4layer_train_step_fits_one_v5e(one_chip):
    from repro.launch.config import load_run_config, resolve_model
    from repro.models import model as M
    from repro.optim import adamw
    from repro.train.trainer import TrainConfig, jit_train_step

    run = load_run_config(RUN_CONFIG)
    cfg = resolve_model(run)
    B, S = run["batch"], run["seq"]
    params = _on(one_chip, jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0))))
    opt = _on(one_chip, jax.eval_shape(adamw.init_state, params))
    batch = _on(one_chip, {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
                           "targets": jax.ShapeDtypeStruct((B, S), jnp.int32),
                           "mask": _f32(B, S)})
    step = _on(one_chip, jax.ShapeDtypeStruct((), jnp.int32))
    tcfg = TrainConfig(total_steps=run["steps"])
    compiled = jit_train_step(cfg, tcfg, params, opt).lower(
        params, opt, batch, step).compile()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert m.alias_size_in_bytes > 0            # the state is donated
    assert total < HBM_BYTES, f"{total / 1e9:.2f} GB"
