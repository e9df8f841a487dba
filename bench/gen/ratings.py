"""Netflix-shaped synthetic ratings, made on the device from a seed.

The distribution is the §5.5 protocol as ``data/synthetic.py`` draws it:

* user degrees: Pareto(1.5) (classical, x >= 1) rescaled to the mean
  ``nnz / m``, capped at ``n`` and floored at 1 (so fewer ratings come
  out than are requested);
* items: each rating's column is drawn with probability proportional to
  a Pareto(1.2) weight per item;
* true factors ``N(0, I/k)`` for users and items, and ratings
  ``<w_i, h_j> + N(0, noise^2)``;
* a held-out split of ``test_frac`` of the ratings.

Every seed gets the same positions: the degree sequence, the item
weights, which user rates which item and the held-out split are drawn
once from the configuration's ``degree_seed``; each ``--seed`` draws the
true factors and the noise, so the values differ.  So every seed packs
to the same stream, the program's compiled epoch is found in the cache
after a checkout's first run, and every seed does the same work.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 1 << 20           # ratings per chunk of the value computation


@functools.partial(jax.jit, static_argnames=("m", "n", "nnz", "a_user",
                                             "a_item"))
def _degrees(key, *, m, n, nnz, a_user, a_item):
    ku, ki = jax.random.split(key)
    raw = jax.random.pareto(ku, a_user, (m,), jnp.float32)
    deg = raw / jnp.mean(raw) * (nnz / m)
    deg = jnp.maximum(1, jnp.minimum(deg, n).astype(jnp.int32))
    w = jax.random.pareto(ki, a_item, (n,), jnp.float32)
    return deg, w


@functools.partial(jax.jit, static_argnames=("m", "n", "total"))
def _pattern(key, deg, w, *, m, n, total):
    """Row and column of every rating, in a random order (the held-out
    ones are the first ``n_test``)."""
    kc, ks = jax.random.split(key)
    rows = jnp.repeat(jnp.arange(m, dtype=jnp.int32), deg,
                      total_repeat_length=total)
    cdf = jnp.cumsum(w / jnp.sum(w))
    u = jax.random.uniform(kc, (total,), jnp.float32)
    cols = jnp.minimum(jnp.searchsorted(cdf, u * cdf[-1]),
                       n - 1).astype(jnp.int32)
    perm = jax.random.permutation(ks, total)
    return rows[perm], cols[perm]


@functools.partial(jax.jit, static_argnames=("m", "n", "k"))
def _values(key, rows, cols, noise, *, m, n, k):
    """``<w_i, h_j> + N(0, noise^2)`` for every rating, true factors drawn
    from ``key``."""
    kw, kh, kn = jax.random.split(key, 3)
    W = jax.random.normal(kw, (m, k), jnp.float32) / math.sqrt(k)
    H = jax.random.normal(kh, (n, k), jnp.float32) / math.sqrt(k)
    total = rows.shape[0]
    pad = -total % CHUNK
    rc = jnp.pad(rows, (0, pad)).reshape(-1, CHUNK)
    cc = jnp.pad(cols, (0, pad)).reshape(-1, CHUNK)
    dots = jax.lax.map(lambda rc_cc: jnp.sum(W[rc_cc[0]] * H[rc_cc[1]],
                                             axis=-1), (rc, cc))
    return dots.reshape(-1)[:total] + noise * jax.random.normal(
        kn, (total,), jnp.float32)


def ratings(seed: int, cfg: dict, device=None):
    """``(train, test)`` COO triples as host NumPy arrays (int32, int32,
    float32) for the configuration ``cfg`` (keys ``m``, ``n``, ``nnz``,
    ``k`` and ``assumed``), drawn on ``device``."""
    from .seeds import seed31
    a = cfg["assumed"]
    m, n, k = int(cfg["m"]), int(cfg["n"]), int(cfg["k"])
    dev = device or jax.devices()[0]
    with jax.default_device(dev):
        key = jax.random.key(int(a["degree_seed"]))
        deg, w = _degrees(key, m=m, n=n, nnz=int(cfg["nnz"]),
                          a_user=float(a["user_degree_pareto"]),
                          a_item=float(a["item_weight_pareto"]))
        total = int(jnp.sum(deg))
        n_test = int(total * float(a["test_frac"]))
        rows, cols = _pattern(jax.random.fold_in(key, 1), deg, w, m=m, n=n,
                              total=total)
        vals = _values(jax.random.key(seed31(seed, 1)), rows, cols,
                       jnp.float32(a["noise"]), m=m, n=n, k=k)
        rows, cols, vals = (np.asarray(x) for x in (rows, cols, vals))
    return ((rows[n_test:], cols[n_test:], vals[n_test:]),
            (rows[:n_test], cols[:n_test], vals[:n_test]))
