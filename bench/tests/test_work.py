"""Each kernel's logical work against a count by hand at a tiny shape."""

from types import SimpleNamespace

import pytest

from bench import roofline

CFG = {"n": 1000, "d": 5, "classes": 1, "q_db": 0.01}
# 3 chain-iterations over the traced stretch, 40 likelihood queries
TRACED = {"chain_iters": 3, "queries": 40}
PEAKS = {"flops_per_s": 100.0, "bytes_per_s": 10.0}


def ctx(flymc=True, kernel_ns=None):
    trace = {"kernel_ns": kernel_ns or {}, "window_ns": 2e9}
    return SimpleNamespace(cfg=CFG, traced=TRACED, flymc=flymc, peaks=PEAKS,
                           trace=trace)


def test_bright_glm_counts_rows_asked_for():
    # 40 rows x (5 features + t + xi in, delta out) x 4 bytes; 2*5 flops
    assert roofline.work("bright_glm").cost(ctx()) == (40 * 10, 40 * 32)


def test_z_candidates_counts_one_partition_pass():
    # 3 x (1000 int32 read + 10 expected candidates written)
    flops, nbytes = roofline.work("z_candidates").cost(ctx())
    assert flops == 0 and nbytes == pytest.approx(3 * (4000 + 40))


def test_step_adds_the_partition_for_flymc_only():
    rows = 40 * (5 * 4 + 8)
    assert roofline.work("step").cost(ctx()) == (400, rows + 3 * 4000)
    assert roofline.work("step").cost(ctx(flymc=False)) == (400, rows)


def test_kernel_share_is_least_time_over_kernel_time():
    # bright_glm: bytes bound 1280 / 10 = 128 s, flops 400 / 100 = 4 s
    c = ctx(kernel_ns={"bright_glm": 256e9})
    assert roofline.kernel_share(c, "bright_glm") == pytest.approx(50.0)
    assert roofline.kernel_share(c, "z_candidates") is None  # no events
