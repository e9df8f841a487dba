#!/usr/bin/env python3
"""Find the serving knee once, on the chip: the highest offered rate at
which completed requests keep up with offered requests through the
window.

    python bench/sweep.py --workload yahoo-serve --seed 7 --seconds 8 \\
        --rates 500,1000,2000,4000

Sets the cell up once (as ``bench/run.py`` does) and runs the serve
driver's open-loop window at each rate in turn.  For each rate it prints
one JSON line: offered and completed-by-close counts, p50/p99 from due
time, the p99 of the first and the last tenth of the requests (a
growing backlog shows as the last tenth far above the first), and how
late the generator ran.  The knee goes into the traffic file by hand;
``PERF.md`` records the sweep.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, requests/s")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import numpy as np

    from bench import run
    bench, w, _, cfg, traffic, driver = run.prepare(args.workload)
    devices, peaks = run.device_check(int(w["chips"]),
                                      run.load_json(ROOT / "bench" /
                                                    "peaks.json"))
    run.enable_compile_cache()
    cell = run.Cell(name=args.workload, cfg=cfg, traffic=traffic,
                    seed=args.seed, seconds=args.seconds, trace=False,
                    devices=devices, peaks=peaks)
    st = driver.setup(cell)
    m = int(cfg["m"])
    for rate in (float(r) for r in args.rates.split(",")):
        count = int(round(rate * args.seconds))
        st["due"] = np.sort(st["rng"].uniform(0.0, args.seconds, count))
        st["users"] = st["rng"].integers(0, m, (count, 1))
        t = time.perf_counter()
        res = driver.window(cell, st)
        lat = st["lat_ms"]
        tenth = max(count // 10, 1)
        c = cell.counters
        print(json.dumps({
            "rate": rate, "offered": count,
            "failed": res["failed"],
            "p50_ms": res["metrics"]["serve_p50_ms"],
            "p99_ms": res["metrics"]["serve_p99_ms"],
            "batch_users": c["n_queries"] / max(c["n_batches"], 1),
            "late_p99_ms": c["late_p99_ms"],
            "wall_s": time.perf_counter() - t,
            "done_by_close": st["done_by_close"],
            "first_tenth_p99_ms": float(np.percentile(lat[:tenth], 99)),
            "last_tenth_p99_ms": float(np.percentile(lat[-tenth:], 99))}),
            flush=True)
    driver.release(cell, st)
    return 0


if __name__ == "__main__":
    sys.exit(main())
