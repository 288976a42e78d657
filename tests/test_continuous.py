"""Continuous-batching engine: per-slot positions, ragged prompts, refill."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import model as M
from repro.serving.continuous import ContinuousEngine, Request
from repro.serving.engine import Engine

CFG = get_smoke_config("stablelm-3b")
PARAMS = M.init_params(CFG, jax.random.PRNGKey(0))


def test_matches_lockstep_engine():
    rs = np.random.default_rng(0)
    p = rs.integers(0, CFG.vocab_size, size=6).astype(np.int32)
    ref = Engine(CFG, PARAMS, batch_size=2, max_seq=48).generate([p, p], max_new=4)[0]
    eng = ContinuousEngine(CFG, PARAMS, slots=1, max_seq=48)
    eng.submit(Request(0, p, max_new=4))
    assert eng.run()[0].out == ref


def test_ragged_prompts_isolated_slots():
    """Each ragged request must produce the same tokens as a solo run."""
    rs = np.random.default_rng(1)
    prompts = [rs.integers(0, CFG.vocab_size, size=n).astype(np.int32)
               for n in (3, 7, 5)]
    solo = []
    for p in prompts:
        e = ContinuousEngine(CFG, PARAMS, slots=1, max_seq=48)
        e.submit(Request(0, p, max_new=3))
        solo.append(e.run()[0].out)
    eng = ContinuousEngine(CFG, PARAMS, slots=3, max_seq=48)
    for i, p in enumerate(prompts):
        eng.submit(Request(i, p, max_new=3))
    done = {r.rid: r.out for r in eng.run()}
    for i in range(3):
        assert done[i] == solo[i], i


def test_slot_refill_more_requests_than_slots():
    rs = np.random.default_rng(2)
    eng = ContinuousEngine(CFG, PARAMS, slots=2, max_seq=48)
    for i in range(5):
        eng.submit(Request(i, rs.integers(0, CFG.vocab_size, size=4 + i).astype(np.int32),
                           max_new=2 + i % 3))
    done = eng.run()
    assert sorted(r.rid for r in done) == [0, 1, 2, 3, 4]
    assert all(len(r.out) == 2 + r.rid % 3 for r in done)


@pytest.mark.parametrize("arch,lens", [("stablelm-3b", (15, 9)),
                                       ("h2o-danube-1.8b", (15, 15))])
def test_greedy_decode_matches_uncached_forward(arch, lens):
    """Both engines' greedy tokens are the argmax of the uncached forward
    over prompt + generated text.  h2o's smoke window is 16, so decoding
    past it wraps the ring-buffer cache; its prompts are of one length,
    because the fixed engine's ring runs on the batch's shared position
    counter, where a shorter row's pad gap counts against its window."""
    cfg = get_smoke_config(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rs = np.random.default_rng(3)
    prompts = [rs.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in lens]
    fixed = Engine(cfg, params, batch_size=2, max_seq=32).generate(
        prompts, max_new=6)
    cont = ContinuousEngine(cfg, params, slots=2, max_seq=32)
    for i, p in enumerate(prompts):
        cont.submit(Request(i, p, max_new=6))
    assert {r.rid: r.out for r in cont.run()} == dict(enumerate(fixed))
    for p, out in zip(prompts, fixed):
        text = np.concatenate([p, np.asarray(out[:-1], np.int32)])
        lg = M.logits(cfg, params, {"tokens": jnp.asarray(text[None])})
        assert np.asarray(lg[0, len(p) - 1:]).argmax(-1).tolist() == out
