"""Roofline arithmetic: operations and bytes per SGD update and per
top-k batch, and the least time against the v5e peaks."""
import json
from pathlib import Path

import pytest

from bench.metrics import costs

PEAKS = json.loads((Path(__file__).parents[1] / "peaks.json").read_text())


def test_sgd_update_cost_at_k100():
    flops, nbytes = costs.sgd_update(100)
    assert flops == 1201          # 2k + (5k + 1) + 5k
    assert nbytes == 1612         # 4 rows of 100 float32, 3 scalars


def test_sgd_update_counts_the_reference_ops():
    """Count the ops of the paper's update one by one."""
    k = 7
    dot = k + k                   # products, sums (the first adds to 0)
    w_new = k + k + k + k + k + 1  # -err*h, lam*w, +, lr*, w-, -err
    h_new = 5 * k
    assert costs.sgd_update(k)[0] == dot + w_new + h_new


def test_topk_batch_cost():
    flops, nbytes = costs.topk_batch(64, 624_961, 100)
    assert flops == 2 * 64 * 624_961 * 100
    assert nbytes == 624_961 * 100 * 4


def test_v5e_peaks_and_least_time():
    v5e = PEAKS["TPU v5 lite"]
    assert v5e["flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    f, b = costs.sgd_update(100)
    # memory-bound: 8.54M updates move 13.8 GB, 16.8 ms at 819 GB/s
    t = costs.least_time(8_539_136 * f, 8_539_136 * b, v5e)
    assert t == pytest.approx(8_539_136 * 1612 / 819e9)
    assert t == pytest.approx(0.0168, rel=0.01)
    # one microbatch of 64 users over the Yahoo! Music catalog is
    # bound by reading H: 250 MB at 819 GB/s
    f, b = costs.topk_batch(64, 624_961, 100)
    assert costs.least_time(f, b, v5e) == pytest.approx(b / 819e9)


def test_roofline_readers_use_the_costs():
    from bench.metrics import sgd_roofline, topk_roofline
    v5e = PEAKS["TPU v5 lite"]
    sec = 1e9
    rec = {
        "window_ns": (0.0, 10 * sec), "cut": False,
        "trace": {"devices": {"0": {
            "modules": [["jit_train(1)", 1 * sec, 2 * sec],
                        ["jit_train(1)", 4 * sec, 2 * sec],
                        ["jit__topk_pallas", 7 * sec, 0.5 * sec],
                        ["jit_take", 7.6 * sec, 0.1 * sec]],
            "ops": {}}}, "host": []},
        "counters": {"nnz": 1000, "n_queries": 40, "n_batches": 1},
        "cfg": {"k": 100, "n": 1000}, "peaks": v5e}
    got = sgd_roofline.read(rec)
    want = 100 * 2 * 1000 * 1612 / 819e9 / 4.0
    assert got == pytest.approx(want)
    got = topk_roofline.read(rec)
    want = 100 * (1000 * 100 * 4 / 819e9) / 0.6
    assert got == pytest.approx(want)
    # a cut trace holds part of an epoch and no count of its updates
    assert sgd_roofline.read(dict(rec, cut=True)) is None
