"""Serving factors: W (m, k) and H (n, k), both N(0, I/k), made on the
device in one jitted call from the seed."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("m", "n", "k"))
def _factors(key, *, m, n, k):
    kw, kh = jax.random.split(key)
    W = jax.random.normal(kw, (m, k), jnp.float32) / math.sqrt(k)
    H = jax.random.normal(kh, (n, k), jnp.float32) / math.sqrt(k)
    return W, H


def factors(seed: int, m: int, n: int, k: int, device=None):
    from .seeds import seed31
    with jax.default_device(device or jax.devices()[0]):
        return _factors(jax.random.key(seed31(seed, 2)), m=m, n=n, k=k)
