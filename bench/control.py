#!/usr/bin/env python3
"""Readings of the control and of planted faults, at a cell's own size,
for setting the limits that decide ``correct`` (PERF.md).  The
benchmark's own runs never run this.

    python bench/control.py --workload netflix-train --seeds 11,12,13

Prints one JSON line per seed and case with the compared numbers.

Training cells:

* ``control``: the program with its own lower-precision path switched on
  (``NomadConfig(dtype_policy="bf16")``: bf16 factor storage, fp32
  arithmetic), the nearest precision below the configuration's float32;
* ``half``: the plain reference put in the program's place with every
  other update of the epoch left out;
* ``unchanged`` (a step that returns its state unchanged) reads 1 on
  ``change_gap`` by its definition and needs no run.

Serving cells, on ``check_sample`` users drawn from the seed:

* ``control``: the dense reference put in the scorer's place, computed
  from bfloat16 factors with float32 sums;
* ``altered``: the float32 dense answer with one served id changed.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def train_cases(cell, driver):
    import jax.numpy as jnp
    import numpy as np

    from bench.ref import sgd as ref

    bf = dict(cell.cfg, dtype="bfloat16")
    cell.cfg, fp = bf, cell.cfg
    st = driver.setup(cell)
    driver.release(cell, st)
    out = {"control": dict((n, v) for n, v, _ in driver.check(cell, st))}
    cell.cfg = fp
    # the reference with every other update left out, in the program's
    # place (its factors in the program's shard layout)
    br, problem = st["br"], st["problem"]
    m, n, k = int(fp["m"]), int(fp["n"]), int(fp["k"])
    R, C, V, M = ref.slot_stream(br.schedule_order(), br.nnz_cell,
                                 problem.rows, problem.cols, problem.vals,
                                 m, n)
    M = M.copy()
    M[1::2] = False
    import jax
    from bench.gen.seeds import seed31
    key = jax.random.key(seed31(cell.seed, 0))
    W1, H1 = ref.sgd_epoch(*ref.init_factors(key, m, n, k),
                           *(jnp.asarray(a.reshape(-1)) for a in (R, C, V, M)),
                           ref.step_size(float(fp["alpha"]),
                                         float(fp["beta"]), 0),
                           float(fp["lam"]), p=br.p)
    half = dict(st)
    half["Ws1"] = np.where((br.row_of >= 0)[..., None],
                           np.asarray(W1)[np.maximum(br.row_of, 0)], 0)
    half["Hs1"] = np.where((br.col_of >= 0)[..., None],
                           np.asarray(H1)[np.maximum(br.col_of, 0)], 0)
    half["rmse1"] = float(ref.heldout_rmse(
        W1, H1, *(jnp.asarray(a) for a in problem.test)))
    half["finite1"] = True
    del W1, H1
    W0, H0, Wr, Hr = driver.reference_epoch1(cell, st)
    out["half"] = dict((nm, v) for nm, v, _ in
                       driver.compare(cell, half, W0, H0, Wr, Hr))
    return out


def serve_cases(cell):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench.gen.factors import factors
    from bench.gen.seeds import rng
    from bench.ref.topk import judge

    cfg = cell.cfg
    m, n, k = int(cfg["m"]), int(cfg["n"]), int(cfg["k"])
    W, H = factors(cell.seed, m, n, k, cell.devices[0])
    users = rng(cell.seed, 4).integers(0, m, int(cell.traffic[
        "check_sample"]))
    Wu = W[users]
    out = {}
    for case, dt in (("control", jnp.bfloat16), ("altered", jnp.float32)):
        s = jnp.dot(Wu.astype(dt), H.astype(dt).T,
                    preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST)
        sc, ids = jax.lax.top_k(s, 10)
        sc, ids = np.asarray(sc), np.asarray(ids).copy()
        if case == "altered":
            ids[0, 0] = (ids[0, 0] + 1) % n
        errs, gaps = judge(np.asarray(Wu), np.asarray(H), ids, sc, 10)
        out[case] = {"score_err": float(errs.max()),
                     "rank_gap": float(gaps.max())}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import run
    bench, w, _, cfg, traffic, driver = run.prepare(args.workload)
    devices, peaks = run.device_check(int(w["chips"]), run.load_json(
        ROOT / "bench" / "peaks.json"))
    run.enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = run.Cell(name=args.workload, cfg=cfg, traffic=traffic,
                        seed=seed, seconds=0.0, trace=False,
                        devices=devices, peaks=peaks)
        if traffic["driver"] == "train":
            cases = train_cases(cell, driver)
        else:
            cases = serve_cases(cell)
        for case, nums in cases.items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "case": case, **nums}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
