"""The comparison that decides ``correct`` fails when the timed path is
broken underneath, and passes when it is not.

Each case drives a whole run of a tiny cell on the CPU (the harness's
look for a chip skipped) with one fault planted in the program's timed
path, and sees ``correct`` come out false: a training step that returns
its state unchanged; half of the ratings left out of the epoch; an
answer altered where the scorer produces it; the control, the program's
own lower-precision (bf16) factor storage.
"""
from __future__ import annotations

import numpy as np
import pytest

from bench.tests.cells import run_tiny


@pytest.mark.parametrize("workload", ["netflix-train", "yahoo-train"])
def test_train_cell_is_correct_when_sound(workload):
    out = run_tiny(workload)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1


def test_serve_cell_is_correct_when_sound():
    out = run_tiny("yahoo-serve")
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] == 100


def test_bf16_control_fails():
    """The control: the program's bf16 factor storage (fp32 compute), the
    nearest precision below the float32 the configuration states."""
    out = run_tiny("netflix-train", dtype="bfloat16")
    assert not out["correct"]
    assert out["checks"]["change_gap"]["value"] > \
        out["checks"]["change_gap"]["limit"]


def test_state_left_unchanged_fails(monkeypatch):
    from repro.core import nomad

    def frozen(self, epochs, test=None, verbose=False, **kw):
        return [(self.epoch_idx, 1.0)]
    monkeypatch.setattr(nomad.NomadRingEngine, "train", frozen)
    out = run_tiny("netflix-train")
    assert not out["correct"]
    assert out["checks"]["change_gap"]["value"] >= 0.99


def test_half_the_ratings_left_out_fails(monkeypatch):
    from repro.core import partition

    real = partition.epoch_stream

    def half(br):
        R, C, V, M = real(br)
        M = M.copy()
        M[1::2] = False
        return R, C, V, M
    monkeypatch.setattr(partition, "epoch_stream", half)
    out = run_tiny("netflix-train")
    assert not out["correct"]
    assert out["checks"]["factor_err"]["value"] > \
        out["checks"]["factor_err"]["limit"]


def test_altered_answer_fails(monkeypatch):
    from repro.serve import server as srv

    real = srv.topk_scores

    def altered(*a, **kw):
        s, i = real(*a, **kw)
        return s, i.at[0, 0].set((i[0, 0] + 1) % a[1].shape[0])
    monkeypatch.setattr(srv, "topk_scores", altered)
    out = run_tiny("yahoo-serve")
    assert not out["correct"]
    assert out["checks"]["rank_gap"]["value"] > 1.0


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_seed_sets_the_data(seed):
    from bench.gen.ratings import ratings
    from bench.tests.cells import tiny
    _, cell, _ = tiny("netflix-train", seed=seed)
    (r1, c1, v1), t1 = ratings(seed, cell.cfg)
    (r2, c2, v2), t2 = ratings(seed, cell.cfg)
    (r3, _, v3), t3 = ratings(seed + 1, cell.cfg)
    assert np.array_equal(c1, c2) and np.array_equal(v1, v2)
    # the same sizes for every seed, in another order
    assert len(r1) == len(r3) and len(t1[0]) == len(t3[0])
    assert not np.array_equal(v1, v3)


def test_serve_control_fails():
    """The serving control: the dense reference in the scorer's place,
    computed from bfloat16 factors (``bench/control.py``)."""
    from bench import control
    from bench.drivers import serve
    from bench.tests.cells import tiny
    _, cell, _ = tiny("yahoo-serve")
    out = control.serve_cases(cell)
    assert out["control"]["score_err"] > serve.LIMITS["score_err"]
    assert out["altered"]["rank_gap"] > serve.LIMITS["rank_gap"]


def test_train_readings_of_the_half_epoch_fault():
    """``bench/control.py`` reads the half-epoch fault through the
    reference put in the program's place."""
    from bench import control
    from bench.drivers import train
    from bench.tests.cells import tiny
    _, cell, driver = tiny("netflix-train")
    out = control.train_cases(cell, driver)
    assert out["half"]["change_gap"] > train.LIMITS["change_gap"]
    assert out["control"]["change_gap"] > train.LIMITS["change_gap"]
