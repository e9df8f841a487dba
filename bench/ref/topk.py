"""Plain reference of a served top-k answer: float64 dense scores of the
benchmark's own factors, and the two numbers a served answer is judged
by.

For one user with exact scores ``x`` (float64) and per-item error bound
``b`` of a float32 dot product in any summation order
(``gamma_{k+1} sum_i |w_i h_i|``, with ``gamma_j = j u / (1 - j u)``):

* ``score_err`` -- the largest ``|served score - x[id]| / b[id]`` over
  the served items: how far a served score lies from the exact one, in
  units of what float32 arithmetic may lose;
* ``rank_gap``  -- the largest ``(x[j] - x[i]) / (b[j] + b[i])`` over a
  served item ``i`` and an item ``j`` that beats it without being
  served, or that is served after it: above 1, the answer leaves out or
  misorders an item by more than both scores' bounds.  A sound answer
  reads at most 1 (near-ties may swap).

Ids that repeat or fall outside the catalog read as infinite.
"""
from __future__ import annotations

import numpy as np

U32 = 2.0 ** -24          # unit roundoff of float32


def _judge_user(ids, scores, x, b, k_top: int):
    n = x.shape[0]
    ids = np.asarray(ids, np.int64)
    if (len(ids) != k_top or len(set(ids.tolist())) != len(ids)
            or ids.min() < 0 or ids.max() >= n):
        return np.inf, np.inf
    xs, bs = x[ids], b[ids]
    score_err = float(np.max(np.abs(np.asarray(scores, np.float64) - xs)
                             / bs))
    gap = -np.inf
    # served order: a later served item must not beat an earlier one
    for i in range(len(ids) - 1):
        gap = max(gap, float(np.max((xs[i + 1:] - xs[i])
                                    / (bs[i + 1:] + bs[i]))))
    out = np.ones(n, bool)
    out[ids] = False
    if out.any():
        xo, bo = x[out], b[out]
        top = min(4 * k_top, len(xo))             # the strongest left out
        j = np.argpartition(-xo, top - 1)[:top]
        for i in range(len(ids)):
            gap = max(gap, float(np.max((xo[j] - xs[i]) / (bo[j] + bs[i]))))
    return score_err, gap


def judge(W_u, H, ids, scores, k_top: int, chunk: int = 32):
    """``(score_err, rank_gap)`` arrays, one entry per user row of
    ``W_u`` (float32 rows of the benchmark's W) against ``H``."""
    W_u = np.asarray(W_u, np.float64)
    H64 = np.asarray(H, np.float64)
    Habs = np.abs(H64)
    j = W_u.shape[1] + 1
    gamma = j * U32 / (1 - j * U32)
    errs, gaps = [], []
    for lo in range(0, len(W_u), chunk):
        w = W_u[lo: lo + chunk]
        x = w @ H64.T
        b = gamma * (np.abs(w) @ Habs.T)
        b = np.maximum(b, np.finfo(np.float64).tiny)
        for u in range(len(w)):
            e, g = _judge_user(ids[lo + u], scores[lo + u], x[u], b[u],
                               k_top)
            errs.append(e)
            gaps.append(g)
    return np.asarray(errs), np.asarray(gaps)
