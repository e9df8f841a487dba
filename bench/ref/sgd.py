"""Plain reference of one NOMAD epoch: serial SGD (the paper's eqs. 9
and 10) over the training ratings in a given order.

The program declares the serial order its epoch is equivalent to (a
permutation of the training ratings) and how that order splits into
steps of ``p`` runs that touch pairwise-disjoint rows and columns.  The
reference trusts neither: :func:`slot_stream` checks that the order
applies every training rating exactly once and that the runs of each
step are disjoint, then lays the runs side by side.  Updates of
disjoint runs commute, so running the ``t``-th update of every run of a
step together is exactly the serial order.  The update itself is
written out here, in float32, from the benchmark's own data and initial
factors; nothing the program computed enters it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: the slot count is padded to a multiple of this, so that seeds whose
#: streams differ by a few slots share one compiled reference
SLOT_QUANTUM = 1 << 16


class OrderError(ValueError):
    """The declared order is not one valid epoch of the ratings."""


def init_factors(key, m: int, n: int, k: int):
    """Algorithm 1, lines 4-5: W, H ~ UniformReal(0, 1/sqrt(k)), from a
    split of ``key``."""
    kw, kh = jax.random.split(key)
    scale = 1.0 / np.sqrt(k)
    return (jax.random.uniform(kw, (m, k), jnp.float32, maxval=scale),
            jax.random.uniform(kh, (n, k), jnp.float32, maxval=scale))


def step_size(alpha: float, beta: float, t: int) -> float:
    """Eq. (11): s_t = alpha / (1 + beta t^1.5)."""
    return alpha / (1.0 + beta * t ** 1.5)


def _disjoint(step, run, key) -> bool:
    """Within each step, every value of ``key`` belongs to one run."""
    o = np.lexsort((key, step))
    s, r, k = step[o], run[o], key[o]
    same = (s[1:] == s[:-1]) & (k[1:] == k[:-1])
    return not np.any(same & (r[1:] != r[:-1]))


def slot_stream(order, run_len, rows, cols, vals, m: int, n: int):
    """Check the declared epoch and lay it out as ``(slots, p)`` arrays.

    ``order`` is a permutation of ``range(len(rows))``; ``run_len[q, s]``
    is the length of worker ``q``'s run in step ``s``, the runs following
    each other step by step and, within a step, worker by worker.
    Returns ``(r, c, v, mask)`` of shape ``(slots, p)``, padded to a
    multiple of :data:`SLOT_QUANTUM` slots with masked entries.  (Pass
    them to :func:`sgd_epoch` flattened: on a TPU a ``p``-wide minor
    dimension is padded to 128 lanes.)
    """
    order = np.asarray(order, np.int64)
    run_len = np.asarray(run_len, np.int64)
    nnz = len(rows)
    if order.shape != (nnz,) or np.any(
            np.bincount(order, minlength=nnz) != 1):
        raise OrderError("the order does not apply every training rating "
                         "exactly once")
    p, n_steps = run_len.shape
    if run_len.sum() != nnz or np.any(run_len < 0):
        raise OrderError("the runs do not cover the order")
    # run id of each position: runs are step-major, worker-minor
    lens = run_len.T.reshape(-1)                    # (n_steps * p,)
    run = np.repeat(np.arange(lens.size), lens)
    step = run // p
    worker = run % p
    r, c = np.asarray(rows)[order], np.asarray(cols)[order]
    if np.any((r < 0) | (r >= m) | (c < 0) | (c >= n)):
        raise OrderError("rating indices outside the matrix")
    if not (_disjoint(step, run, r) and _disjoint(step, run, c)):
        raise OrderError("runs of one step share a row or a column")
    starts = np.concatenate([[0], np.cumsum(lens)[:-1]])
    rank = np.arange(nnz) - starts[run]
    step_len = run_len.max(axis=0)                  # (n_steps,)
    step_off = np.concatenate([[0], np.cumsum(step_len)[:-1]])
    slot = step_off[step] + rank
    slots = int(step_len.sum())
    slots += -slots % SLOT_QUANTUM
    R = np.zeros((slots, p), np.int32)
    C = np.zeros((slots, p), np.int32)
    V = np.zeros((slots, p), np.float32)
    M = np.zeros((slots, p), bool)
    R[slot, worker] = r
    C[slot, worker] = c
    V[slot, worker] = np.asarray(vals, np.float32)[order]
    M[slot, worker] = True
    return R, C, V, M


@functools.partial(jax.jit, static_argnames=("p",),
                   donate_argnums=(0, 1))
def sgd_epoch(W, H, R, C, V, M, lr, lam, *, p: int):
    """Serial SGD over the flattened slot stream (``p`` entries a slot):
    each slot's updates touch distinct rows and columns, so doing them
    together is doing them one after the other."""
    m, n = W.shape[0], H.shape[0]
    lr = jnp.asarray(lr, W.dtype)
    lam = jnp.asarray(lam, W.dtype)

    def slot(t, WH):
        W, H = WH
        r, c, a, keep = (jax.lax.dynamic_slice_in_dim(x, t * p, p)
                         for x in (R, C, V, M))
        a = a.astype(W.dtype)
        w, h = W[r], H[c]
        err = a[:, None] - jnp.sum(w * h, axis=-1, keepdims=True)
        w_new = w - lr * (-err * h + lam * w)
        h_new = h - lr * (-err * w + lam * h)
        W = W.at[jnp.where(keep, r, m)].set(w_new, mode="drop")
        H = H.at[jnp.where(keep, c, n)].set(h_new, mode="drop")
        return W, H

    return jax.lax.fori_loop(0, R.shape[0] // p, slot, (W, H))


@jax.jit
def heldout_rmse(W, H, r, c, v):
    pred = jnp.sum(W[r] * H[c], axis=-1)
    return jnp.sqrt(jnp.mean((v - pred) ** 2))
