"""Tiny versions of the benchmark's cells for tests on the CPU: the
same drivers, references and checks as a chip run, at sizes a test run
holds, with the harness's look for a chip skipped.  A cell is named by
its configuration and traffic files, so a cell not (yet) in
``BENCHMARK.json`` is tested the same way."""
from __future__ import annotations

import jax

from bench import run

PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SIZES = {
    "train": dict(m=3000, n=400, nnz=30000),
    "serve": dict(m=5000, n=3000),
}
CELLS = {
    "netflix-train": ("netflix", "train-epochs"),
    "yahoo-serve": ("yahoo-music", "serve-open-80"),
    "yahoo-train": ("yahoo-music", "train-epochs"),
}


def tiny(workload: str, seed: int = 2**31 + 17, seconds: float = 1.0,
         **cfg_over):
    """``(bench, cell, driver)`` for ``workload`` cut to a tiny size."""
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    config, mix = CELLS[workload]
    cfg = run.load_json(run.BENCH / "configs" / f"{config}.json")
    traffic = run.load_json(run.BENCH / "traffic" / f"{mix}.json")
    driver = run.load_module(run.BENCH / "drivers" /
                             f"{traffic['driver']}.py",
                             f"bench_driver_{traffic['driver']}")
    cfg = dict(cfg, **SIZES[traffic["driver"]], **cfg_over)
    traffic = dict(traffic, rate_per_s=100.0, check_sample=64)
    cell = run.Cell(name=workload, cfg=cfg, traffic=traffic, seed=seed,
                    seconds=seconds, trace=False,
                    devices=jax.devices()[: int(cfg["chips"])],
                    peaks=PEAKS)
    return bench, cell, driver


def run_tiny(workload: str, **kw) -> dict:
    bench, cell, driver = tiny(workload, **kw)
    out = run.run_cell(cell, driver, bench)
    out["counters"] = cell.counters
    return out
