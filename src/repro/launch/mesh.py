"""Production mesh construction.

Functions (not module-level constants) so importing this module never
touches jax device state.  Hardware target: TPU v5e — 197 TFLOP/s bf16,
819 GB/s HBM, ~50 GB/s/link ICI; 256 chips/pod as a 16x16 (data, model)
mesh, two pods for the multi-pod config.
"""
from __future__ import annotations

import jax


# TPU v5e constants used by the roofline analysis (benchmarks/roofline.py)
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link


def _mk(shape, axes):
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_test_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh over however many (host) devices a test session has."""
    return _mk((n_data, n_model), ("data", "model"))


def make_mc_mesh(p: int):
    """1-D worker ring for the matrix-completion engine."""
    return _mk((p,), ("workers",))
