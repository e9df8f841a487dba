"""The seeded generators draw the distribution copied from
``data/synthetic.py``: Pareto(1.5) user degrees floored at 1 and capped
at n, Pareto(1.2) item weights, N(0, I/k) true factors and N(0, 0.1^2)
noise."""
import numpy as np
import pytest

from bench.gen import factors as gfac
from bench.gen import ratings as grat

CFG = {"m": 20000, "n": 800, "nnz": 200000, "k": 16,
       "assumed": {"test_frac": 0.01, "noise": 0.1,
                   "user_degree_pareto": 1.5, "item_weight_pareto": 1.2,
                   "degree_seed": 11}}


@pytest.fixture(scope="module")
def data():
    return grat.ratings(2**31 + 99, CFG)


def test_degrees_floor_cap_and_tail(data):
    (r, c, v), (tr, tc, tv) = data
    rows = np.concatenate([r, tr])
    deg = np.bincount(rows, minlength=CFG["m"])
    assert deg.min() >= 1 and deg.max() <= CFG["n"]
    # flooring raises the count; degrees round down, so near the request
    assert 0.8 * CFG["nnz"] < len(rows) < 1.2 * CFG["nnz"]
    # Pareto(1.5): P(d > x * median) falls like x^-1.5
    med = np.median(deg)
    tail = np.mean(deg > 4 * med) / np.mean(deg > 2 * med)
    assert 2 ** -1.5 * 0.6 < tail < 2 ** -1.5 * 1.6


def test_items_heavy_tailed(data):
    (r, c, v), _ = data
    cnt = np.sort(np.bincount(c, minlength=CFG["n"]))[::-1]
    # Pareto(1.2) weights: the top 1% of items hold far more than 1%
    assert cnt[: CFG["n"] // 100].sum() > 0.05 * cnt.sum()


def test_noise_and_factor_scale(data):
    """Ratings are <w, h> + N(0, 0.1^2) with w, h ~ N(0, I/k): their
    variance is 1/k + 0.01."""
    (r, c, v), _ = data
    k = CFG["k"]
    assert np.var(v) == pytest.approx(1 / k + 0.01, rel=0.1)
    W, H = gfac.factors(5, 4000, 300, k)
    assert np.var(np.asarray(W)) == pytest.approx(1 / k, rel=0.05)
    assert np.var(np.asarray(H)) == pytest.approx(1 / k, rel=0.05)


def test_split_and_sizes(data):
    (r, c, v), (tr, tc, tv) = data
    total = len(r) + len(tr)
    assert len(tr) == int(total * CFG["assumed"]["test_frac"])
    (r2, _, _), (tr2, _, _) = grat.ratings(12345, CFG)
    assert len(r2) == len(r) and len(tr2) == len(tr)
    assert r.dtype == np.int32 and v.dtype == np.float32
