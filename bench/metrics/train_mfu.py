"""The whole training step's share of the chips' bf16 peak, in percent:
the model FLOPs of a step (``bench/flops/<family>.py``) times the window's
steps, over the window's host seconds times chips times the peak
(``bench/peaks.py``)."""
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(ctx):
    import importlib.util

    conf, traffic, rec = ctx["conf"], ctx["traffic"], ctx["rec"]
    path = os.path.join(HERE, "flops", conf["family"] + ".py")
    spec = importlib.util.spec_from_file_location("bench_flops", path)
    flops = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(flops)
    per_step = flops.train_step_flops(conf["model"], seq=traffic["seq"],
                                      batch=traffic["batch"])
    return 100.0 * per_step * rec["window_steps"] / (
        rec["window_s"] * ctx["chips"] * ctx["peak"]["bf16_flops"])
