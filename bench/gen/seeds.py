"""Seeds: every number the benchmark draws comes from ``--seed``.

``--seed`` may be any whole number up to a little over 2**31; JAX keys
and the program's own ``seed`` field take a 31-bit integer, so the seed
is mixed through NumPy's ``SeedSequence`` first (distinct seeds give
distinct streams).
"""
from __future__ import annotations

import numpy as np


def seed31(seed: int, stream: int = 0) -> int:
    """A 31-bit integer drawn from ``(seed, stream)``."""
    state = np.random.SeedSequence([int(seed) % (1 << 63), stream])
    return int(state.generate_state(1, np.uint32)[0] >> 1)


def rng(seed: int, stream: int = 0) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(
        [int(seed) % (1 << 63), stream]))
