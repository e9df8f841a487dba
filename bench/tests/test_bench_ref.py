"""The plain references: the SGD epoch equals a serial float64 replay
in the declared order, refuses an order that is not one epoch, and the
top-k judge reads sound answers at most 1 and wrong ones far above."""
import jax
import numpy as np
import pytest

from bench.ref import sgd as ref
from bench.ref import topk as rtopk


def _serial(W, H, rows, cols, vals, order, lr, lam):
    W, H = W.astype(np.float64).copy(), H.astype(np.float64).copy()
    for g in order:
        i, j, a = rows[g], cols[g], vals[g]
        w, h = W[i].copy(), H[j].copy()
        err = a - w @ h
        W[i] = w - lr * (-err * h + lam * w)
        H[j] = h - lr * (-err * w + lam * h)
    return W, H


def _epoch(p=3, m=40, n=30, per=25, seed=0):
    """Ratings in p x p cells of disjoint row and column blocks, and the
    ring order over them (step s: worker q runs block (q + s) % p)."""
    rng = np.random.default_rng(seed)
    rows, cols, runs = [], [], np.zeros((p, p), np.int64)
    order = []
    for s in range(p):
        for q in range(p):
            b = (q + s) % p
            r = rng.integers(0, m // p, per) * p + q
            c = rng.integers(0, n // p, per) * p + b
            order.extend(range(len(rows), len(rows) + per))
            rows.extend(r)
            cols.extend(c)
            runs[q, s] = per
    rows, cols = np.asarray(rows), np.asarray(cols)
    vals = rng.standard_normal(len(rows)).astype(np.float32)
    perm = rng.permutation(len(rows))        # store in another order
    inv = np.argsort(perm)
    return (rows[perm], cols[perm], vals[perm], inv[np.asarray(order)],
            runs, m, n)


def test_epoch_matches_serial_float64_replay():
    rows, cols, vals, order, runs, m, n = _epoch()
    W0, H0 = ref.init_factors(jax.random.key(1), m, n, 8)
    W0n, H0n = np.asarray(W0), np.asarray(H0)
    R, C, V, M = ref.slot_stream(order, runs, rows, cols, vals, m, n)
    assert R.shape[0] % ref.SLOT_QUANTUM == 0
    W1, H1 = ref.sgd_epoch(W0, H0, *(a.reshape(-1) for a in (R, C, V, M)),
                           0.05, 0.1, p=runs.shape[0])
    Wr, Hr = _serial(W0n, H0n, rows, cols, vals, order, 0.05, 0.1)
    np.testing.assert_allclose(np.asarray(W1), Wr, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(H1), Hr, rtol=1e-5, atol=1e-6)


def test_order_errors_are_refused():
    rows, cols, vals, order, runs, m, n = _epoch()
    bad = order.copy()
    bad[0] = bad[1]                          # one rating twice
    with pytest.raises(ref.OrderError):
        ref.slot_stream(bad, runs, rows, cols, vals, m, n)
    with pytest.raises(ref.OrderError):      # runs that do not cover it
        ref.slot_stream(order, runs - 1, rows, cols, vals, m, n)
    # runs of one step that share a column
    clash = cols.copy()
    clash[order[runs[0, 0]]] = clash[order[0]]
    with pytest.raises(ref.OrderError):
        ref.slot_stream(order, runs, rows, clash, vals, m, n)


def test_step_size_is_eq_11():
    assert ref.step_size(0.012, 0.05, 0) == 0.012
    assert ref.step_size(0.012, 0.05, 4) == pytest.approx(
        0.012 / (1 + 0.05 * 8))


def test_topk_judge():
    rng = np.random.default_rng(0)
    W = rng.standard_normal((6, 16)).astype(np.float32)
    H = rng.standard_normal((500, 16)).astype(np.float32)
    x = W.astype(np.float64) @ H.astype(np.float64).T
    ids = np.argsort(-x, axis=1, kind="stable")[:, :10]
    scores = np.take_along_axis(x, ids, 1).astype(np.float32)
    err, gap = rtopk.judge(W, H, ids, scores, 10)
    assert np.all(err <= 1) and np.all(gap <= 1)
    wrong = ids.copy()
    wrong[2, 3] = ids[2, -1] + 1 if ids[2, -1] + 1 < 500 else 0
    wrong[2, 3] = np.argsort(-x[2])[-1]      # the worst item, served
    err, gap = rtopk.judge(W, H, wrong, scores, 10)
    assert gap[2] > 100 and err[2] > 100
    dup = ids.copy()
    dup[1, 1] = dup[1, 0]
    err, gap = rtopk.judge(W, H, dup, scores, 10)
    assert np.isinf(gap[1])
