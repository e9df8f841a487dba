"""Reusable tolerance tier (DESIGN.md §13).

The repo's default correctness currency is **bitwise** equality against
a serial oracle.  Approximation features (low-precision factor storage,
int8 serving quantization, future ANN retrieval / gradient compression)
deliberately break it, so they assert against *bounds* instead — but
principled ones, derived from the storage format, not hand-tuned
``atol`` soup:

* :func:`assert_factors_close` — elementwise error vs. the fp32 oracle
  bounded by ``C * eps(policy) * sqrt(n_updates)`` relative to the
  oracle's magnitude: each update commits one rounding of relative size
  ``eps``, and independent roundings accumulate as a random walk.  ``C``
  absorbs the constant factors (gather/scatter rounding, the regression
  term); the *shape* of the bound — linear in eps, sqrt in updates — is
  what the tier pins down, so a bug that breaks accumulation (e.g.
  accumulating in bf16 instead of fp32) blows the bound by orders of
  magnitude rather than sliding under a slack atol.
* :func:`assert_convergence_equivalent` — a low-precision run must reach
  the same held-out RMSE as the fp32 run within a relative band, and
  must actually have converged (final < initial).  Precision changes the
  arithmetic, not the optimization problem.
* :func:`assert_topk_within_bound` — serving top-k against the float64
  dense scores: every score within the f32 dot product's forward error
  bound (:func:`dot_error_bound`), and a selection that no item left
  out, nor any reordering of the served ones, beats by more than that
  bound.  Device matmuls sum in shape- and backend-dependent order, so
  this is the contract the scorers can keep everywhere.
* :func:`assert_bitwise` — the existing currency, importable from the
  same place so a test file can state both regimes side by side.

Every helper takes plain arrays; nothing here imports the engine.
"""
from __future__ import annotations

import numpy as np

__all__ = ["EPS", "rmse", "rel_err_in_eps", "assert_bitwise",
           "assert_factors_close", "assert_convergence_equivalent",
           "dot_error_bound", "assert_topk_within_bound"]

# machine epsilon (unit roundoff) per storage policy
EPS = {
    "fp32": 2.0 ** -24, "float32": 2.0 ** -24,
    "bf16": 2.0 ** -9, "bfloat16": 2.0 ** -9,
    "fp16": 2.0 ** -11, "float16": 2.0 ** -11,
}


def _f64(a) -> np.ndarray:
    # bfloat16 numpy arrays (ml_dtypes) upcast fine via astype
    return np.asarray(a).astype(np.float64)


def rmse(a, b) -> float:
    a, b = _f64(a), _f64(b)
    return float(np.sqrt(np.mean((a - b) ** 2)))


def rel_err_in_eps(approx, oracle, policy: str) -> float:
    """Max elementwise error in units of the policy's eps, relative to
    ``1 + |oracle|`` (absolute near zero, relative at magnitude)."""
    a, o = _f64(approx), _f64(oracle)
    return float(np.max(np.abs(a - o) / (1.0 + np.abs(o))) / EPS[policy])


def assert_bitwise(a, b, what: str = "arrays"):
    """The repo's default: byte-for-byte equality."""
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape, \
        f"{what}: dtype/shape mismatch {a.dtype}{a.shape} vs {b.dtype}{b.shape}"
    assert np.array_equal(a.view(np.uint8), b.view(np.uint8)), \
        f"{what}: not bitwise-identical"


def assert_factors_close(approx, oracle, *, dtype_policy: str,
                         n_updates: int, c: float = 16.0,
                         what: str = "factors"):
    """Bound the low-precision factor drift against the fp32 oracle.

    ``n_updates`` is how many SGD updates touched a row (use the mean
    ``nnz / rows`` — the walk length).  The bound is
    ``c * eps * sqrt(n_updates)`` per unit of oracle magnitude.
    """
    eps = EPS[dtype_policy]
    bound = c * eps * np.sqrt(max(float(n_updates), 1.0))
    a, o = _f64(approx), _f64(oracle)
    err = float(np.max(np.abs(a - o) / (1.0 + np.abs(o))))
    assert err <= bound, (
        f"{what}: max relative error {err:.3e} exceeds "
        f"{c} * eps({dtype_policy}) * sqrt({n_updates}) = {bound:.3e}")
    return err


def assert_convergence_equivalent(trace_lowp, trace_fp32, *,
                                  rel: float = 0.05,
                                  what: str = "held-out RMSE"):
    """Same optimization outcome: the low-precision run's final RMSE is
    within ``rel`` of the fp32 run's, and it actually descended."""
    lo, fp = _f64(trace_lowp).ravel(), _f64(trace_fp32).ravel()
    assert lo.size and fp.size, f"{what}: empty trace"
    assert lo[-1] < lo[0], \
        f"{what}: low-precision run did not descend ({lo[0]} -> {lo[-1]})"
    gap = abs(lo[-1] - fp[-1])
    assert gap <= rel * fp[-1], (
        f"{what}: final gap {gap:.4g} exceeds {rel:.0%} of fp32 final "
        f"{fp[-1]:.4g}")
    return gap


def dot_error_bound(A, B, scale=None):
    """Elementwise bound on ``|fl32(A @ B.T) - (A @ B.T)|`` for any
    summation order: ``gamma_{k+1} * sum_i |a_i b_i|`` (times ``|scale|``
    per column when a per-item scale multiplies the dot afterwards), with
    ``gamma_j = j u / (1 - j u)`` and ``u = eps(fp32)`` — k product
    roundings and additions, plus one for the scale."""
    A, B = np.abs(_f64(A)), np.abs(_f64(B))
    j = A.shape[-1] + 1
    u = EPS["fp32"]
    bound = (j * u / (1 - j * u)) * (A @ B.T)
    if scale is not None:
        bound = bound * np.abs(_f64(scale))[None, :]
    return bound


def assert_topk_within_bound(ids, scores, W_u, H, *, h_scale=None,
                             what: str = "top-k"):
    """A served top-k against the float64 dense scores of ``W_u @ H.T``.

    With ``b`` the :func:`dot_error_bound` and ``x`` the exact scores,
    per user row: ids are distinct catalog rows; every served score is
    within ``b`` of ``x``; consecutive served items are in score order
    up to ``b``; and no item left out beats the weakest served one by
    more than the two bounds.  So ids equal the dense argsort's except
    where exact scores lie within the bound of each other.  Returns the
    largest score error in units of its bound."""
    ids = np.asarray(ids).astype(np.int64)
    s = _f64(scores)
    x = _f64(W_u) @ _f64(H).T
    if h_scale is not None:
        x = x * _f64(h_scale)[None, :]
    b = dot_error_bound(W_u, H, h_scale)
    n = x.shape[1]
    worst = 0.0
    for u in range(ids.shape[0]):
        got = ids[u]
        assert len(set(got.tolist())) == len(got) and got.min() >= 0 \
            and got.max() < n, f"{what}: user {u} ids {got} invalid"
        xs, bs = x[u, got], b[u, got]
        err = np.abs(s[u] - xs)
        assert np.all(err <= bs), (
            f"{what}: user {u} score error {err.max():.3e} exceeds its "
            f"bound {bs[np.argmax(err - bs)]:.3e}")
        worst = max(worst, float(np.max(err / np.maximum(bs, 1e-300))))
        assert np.all(xs[:-1] + bs[:-1] >= xs[1:] - bs[1:]), (
            f"{what}: user {u} served order {got} contradicts the exact "
            "scores beyond the bound")
        out = np.ones(n, bool)
        out[got] = False
        if out.any():
            best_out = np.max(x[u, out] - b[u, out])
            assert np.min(xs + bs) >= best_out, (
                f"{what}: user {u} left out an item scoring "
                f"{best_out:.6g} (minus bound) above a served one")
    return worst
