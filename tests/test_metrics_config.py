"""Metrics (MFU accounting) and the JSON run-config loader."""
import json
import tempfile

import pytest

from repro.configs import get_config
from repro.launch.config import load_run_config, resolve_model
from repro.train import metrics as MET


def test_train_step_flops_and_mfu():
    cfg = get_config("llama3-8b")
    tokens = 4096 * 256
    f = MET.train_step_flops(cfg, tokens)
    assert f.model == pytest.approx(6 * cfg.param_count(active_only=True) * tokens)
    assert f.executed > f.model
    # perfect-efficiency sanity: executing model flops at peak -> MFU ~0.75
    peak = MET.peak_flops("TPU v5 lite")
    ideal_t = f.executed / (256 * peak)
    assert 0.70 < MET.mfu(cfg, tokens, ideal_t, chips=256, peak=peak) < 0.78


def test_peak_table_is_keyed_by_device_kind():
    assert MET.peak_flops("TPU v5 lite") == 197e12
    # a device outside the table has no peak, so its MFU is not measured
    assert MET.peak_flops("cpu") is None
    cfg = get_config("phi2-2b")
    assert MET.mfu(cfg, 1024, 1.0, peak=MET.peak_flops("TPU v5 lite")) > 0
    # the train loop's tracker reports step time and tokens/s, no MFU
    assert MET.Tracker(tokens_per_step=1024).update(1.0) == {
        "step_s": 1.0, "tokens_per_s": 1024.0}


def test_tracker_window():
    tr = MET.Tracker(tokens_per_step=1024, window=3)
    for t in (1.0, 1.0, 2.0, 2.0, 2.0):
        m = tr.update(t)
    assert m["step_s"] == 2.0
    assert m["tokens_per_s"] == pytest.approx(512.0)


def test_run_config_roundtrip():
    raw = {"arch": "h2o-danube-1.8b", "smoke": True, "steps": 5,
           "overrides": {"sliding_window": 16}}
    with tempfile.NamedTemporaryFile("w", suffix=".json", delete=False) as f:
        json.dump(raw, f)
        path = f.name
    run = load_run_config(path)
    cfg = resolve_model(run)
    assert cfg.sliding_window == 16
    assert cfg.num_layers <= 2        # smoke reduction applied


def test_run_config_rejects_unknown_keys():
    with tempfile.NamedTemporaryFile("w", suffix=".json", delete=False) as f:
        json.dump({"arch": "yi-34b", "typo_key": 1}, f)
        path = f.name
    with pytest.raises(ValueError):
        load_run_config(path)
