"""The benchmark's model-FLOP count against counts made by hand."""
import pytest

from bench.flops import dense
from bench.run import resolve

DANUBE = "danube-4l.train.plan"
YI = "yi-34b-2l.train.tp4-plan"


def model(cell):
    return resolve(cell)["config"]["model"]


def test_danube_counts():
    m = model(DANUBE)
    # per layer: q,o 2*2560*2560 + k,v 2*2560*640 + mlp 3*2560*6912;
    # 4 layers + head 2560*32000; the input embedding is a lookup
    assert dense.matmul_params(m) == 4 * (13_107_200 + 3_276_800
                                          + 53_084_160) + 81_920_000
    # 6*N*tokens + 12*L*(heads*head_dim)*S*tokens at seq 2048, batch 4
    want = 6 * 359_792_640 * 8192 + 12 * 4 * 2560 * 2048 * 8192
    assert dense.train_step_flops(m, seq=2048, batch=4) == want
    assert want == pytest.approx(1.974e13, rel=1e-3)


@pytest.mark.parametrize("batch,approx", [(4, 1.663e14), (2, 8.316e13)])
def test_yi_counts(batch, approx):
    m = model(YI)
    # per layer: q,o 2*7168*7168 + k,v 2*7168*1024 + mlp 3*7168*20480;
    # 2 layers + head 7168*64000
    n = 2 * (102_760_448 + 14_680_064 + 440_401_920) + 458_752_000
    assert dense.matmul_params(m) == n == 1_574_436_864
    tokens = 4096 * batch
    want = 6 * n * tokens + 12 * 2 * 7168 * 4096 * tokens
    assert dense.train_step_flops(m, seq=4096, batch=batch) == want
    assert want == pytest.approx(approx, rel=1e-3)


def test_cells_use_the_counted_shapes():
    t = resolve(YI)["traffic"]
    assert (t["seq"], t["batch"]) == (4096, 2)
    t = resolve(DANUBE)["traffic"]
    assert (t["seq"], t["batch"]) == (2048, 4)
