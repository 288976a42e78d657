"""A benchmark cell cut to a size a test run can hold on the CPU: every
file of the cell as committed, with the model's sizes and the traffic's
shape made small.  The limits of the check stay the cell's own."""
import copy

import jax

SMALL = {
    "h2o-danube-1.8b": dict(num_layers=2, d_model=128, num_heads=4,
                            num_kv_heads=1, head_dim=32, d_ff=256,
                            vocab_size=512, sliding_window=16),
    "yi-34b": dict(num_layers=2, d_model=256, num_heads=8, num_kv_heads=4,
                   head_dim=32, d_ff=512, vocab_size=512),
}


def spec(cell: str, *, seq: int = 64, batch: int = 4) -> dict:
    from bench.run import resolve

    s = copy.deepcopy(resolve(cell))
    conf = s["config"]
    small = SMALL[conf["arch"]]
    conf["overrides"] = dict(small)
    conf["model"].update(small)
    s["traffic"].update(seq=seq, batch=batch, pool=4)
    return s


def execute(s: dict, *, seed: int = 2**33 + 5, trace: bool = False):
    from bench.run import execute as run

    n = s["workload"]["chips"]
    return run(s, seed=seed, seconds=0.3, trace=trace,
               devices=jax.devices()[:n], t0=0.0,
               platform_peak={"bf16_flops": 1e12})
