"""End-to-end NOMAD training driver (the paper's workload).

Trains a matrix-completion model on Netflix-shaped synthetic data through
``repro.api.solve`` with asynchronous checkpointing and deterministic
resume: each checkpoint round is a ``solve(..., warm_start=...)`` call, and
because the step-size schedule continues from ``FitResult.epochs_done``,
the chunked run is bitwise-identical to an uninterrupted one.

    pip install -e .           # once, from the repo root
    python examples/train_mc.py --scale 2e-3 --epochs 20
    # full Netflix-scale (needs a real cluster / lots of RAM):
    python examples/train_mc.py --scale 1.0 --k 100
"""
import argparse
import dataclasses
import os
import time

import numpy as np

from repro import api
from repro.checkpoint import AsyncCheckpointer, restore_checkpoint
from repro.core.stepsize import PowerSchedule
from repro.launch.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=2e-3,
                    help="fraction of full Netflix size")
    ap.add_argument("--k", type=int, default=32)
    ap.add_argument("--p", type=int, default=8, help="NOMAD workers")
    ap.add_argument("--epochs", type=int, default=20)
    ap.add_argument("--lam", type=float, default=0.01)
    ap.add_argument("--alpha", type=float, default=0.012 * 8)
    ap.add_argument("--beta", type=float, default=0.05)
    ap.add_argument("--ckpt-dir", default="/tmp/nomad_mc_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=5)
    ap.add_argument("--impl", default="wave",
                    choices=["xla", "pallas", "auto", "wave", "wave_pallas"],
                    help="block-update kernel (wave = conflict-free "
                         "vectorized path, DESIGN.md §3)")
    args = ap.parse_args()
    if args.ckpt_every < 1:
        ap.error("--ckpt-every must be >= 1")
    enable_compile_cache()

    # scale users linearly and keep Netflix's ~37 ratings/user so the
    # problem stays well-determined at laptop scale
    m = max(500, int(2_649_429 * args.scale))
    n = max(200, int(17_770 * args.scale))
    problem = api.MCProblem.synthetic(m, n, 37 * m, k=args.k, seed=0,
                                      noise=0.1, test_frac=0.05,
                                      split_seed=1)
    print(f"dataset: m={m} n={n} nnz={problem.nnz} "
          f"(Netflix x {args.scale:g})")

    config = api.NomadConfig(
        k=args.k, lam=args.lam, epochs=args.ckpt_every, seed=0, p=args.p,
        kernel=args.impl,
        stepsize=PowerSchedule(alpha=args.alpha, beta=args.beta))

    # key the checkpoint dir by problem signature so a re-run with a
    # different --scale starts fresh instead of restoring stale shapes;
    # the 'wh' tag separates this full-factor {W,H} format from the old
    # sharded {Ws,Hs} checkpoints, which are not compatible
    ckpt_dir = os.path.join(args.ckpt_dir,
                            f"m{m}_n{n}_k{args.k}_p{args.p}_wh")
    ckpt = AsyncCheckpointer(ckpt_dir)
    state_like = {"W": np.zeros((m, args.k), np.float32),
                  "H": np.zeros((n, args.k), np.float32)}
    restored, step = restore_checkpoint(ckpt_dir, state_like)
    warm = None
    if restored is not None:
        warm = api.FitResult(
            W=restored["W"], H=restored["H"],
            trace_epochs=np.asarray([]), trace_rmse=np.asarray([]),
            epochs_done=step)
        print(f"resumed from epoch {step}")

    t0 = time.time()
    done = int(warm.epochs_done) if warm is not None else 0
    result = warm
    while done < args.epochs:
        rounds = min(args.ckpt_every, args.epochs - done)
        cfg = dataclasses.replace(config, epochs=rounds)
        result = api.solve(problem, cfg, warm_start=result)
        done = int(result.epochs_done)
        for e, r in result.trace:
            print(f"epoch {e:3d}  test RMSE {r:.4f}  "
                  f"({(time.time() - t0):.1f}s)")
        ckpt.save(done, {"W": result.W, "H": result.H})
    ckpt.wait()
    print("done.")


if __name__ == "__main__":
    main()
