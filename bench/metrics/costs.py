"""Operations and bytes of the algorithm's own work, from shapes.

One SGD update of rank ``k`` (``kernels/ref.py`` ``sgd_pair``, the
paper's eqs. 9 and 10)::

    err   = a - <w, h>                 2k FLOPs (k products, k sums)
    w'    = w - lr (-err h + lam w)    5k + 1
    h'    = h - lr (-err w + lam h)    5k

so ``12k + 1`` FLOPs; it reads and writes one row of W and one of H
(``4 k s`` bytes for ``s``-byte elements) and reads two int32 indices and
one float32 rating (12 bytes).

One top-k batch of ``U`` users over ``n`` items (``serve/topk.py``):
``2 U n k`` FLOPs for the scores, and H read once (``n k s`` bytes).

The least time of a piece of work is the larger of its FLOPs over the
peak FLOP/s and its bytes over the peak HBM bandwidth.  The FLOP peak in
``bench/peaks.json`` is the bf16 matrix-unit peak, above what float32
arithmetic reaches, so the FLOP bound is never overstated.
"""
from __future__ import annotations


def sgd_update(k: int, itemsize: int = 4):
    """``(flops, bytes)`` of one rank-``k`` SGD update."""
    return 12 * k + 1, 4 * k * itemsize + 12


def topk_batch(users: float, n: int, k: int, itemsize: int = 4):
    """``(flops, bytes)`` of scoring ``users`` users against all of H."""
    return 2 * users * n * k, n * k * itemsize


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    return max(flops / peaks["flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
