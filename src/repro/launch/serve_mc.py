"""Matrix-completion serving CLI: checkpoint -> live top-k server.

The MC twin of the LM CLI in ``repro.launch.serve``: boots a
:class:`repro.serve.RecServer` from the newest *committed*
``save_fit_result`` checkpoint (or trains a demo problem first), then
drives a client load against it and reports queries/s with p50/p99
latency — optionally while a concurrent :class:`repro.api.StreamingSession`
keeps publishing fresh factor versions (the hot-swap path).

    nomad-serve-mc --demo --smoke                 # console script
    python -m repro.launch.serve_mc --ckpt-dir /tmp/nomad_mc_ckpt \
        --queries 2000 --hot-swap 3
"""
from __future__ import annotations

import argparse
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Tuple

import numpy as np


def run_load(server, user_pool: int, n_queries: int, *, clients: int = 4,
             users_per_query: int = 1, seed: int = 0,
             ) -> Tuple[float, float, float]:
    """Drive ``n_queries`` requests from ``clients`` threads; returns
    ``(queries_per_s, p50_ms, p99_ms)`` measured submit -> result.
    Shared by this CLI and ``benchmarks/serve_bench.py``."""
    rng = np.random.default_rng(seed)
    requests = rng.integers(0, user_pool, (n_queries, users_per_query))
    lat = np.zeros(n_queries)

    def one(i):
        t0 = time.perf_counter()
        server.recommend(requests[i])
        lat[i] = time.perf_counter() - t0

    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=clients) as pool:
        list(pool.map(one, range(n_queries)))
    dt = time.perf_counter() - t0
    return n_queries / dt, float(np.percentile(lat, 50) * 1e3), \
        float(np.percentile(lat, 99) * 1e3)


def _train_demo(args) -> Tuple[object, object]:
    """Train a small problem (and checkpoint it) so the server has
    something to boot from; returns (problem, result)."""
    from .. import api
    from ..checkpoint import save_fit_result
    from ..core.stepsize import PowerSchedule

    problem = api.MCProblem.synthetic(args.m, args.n, args.nnz, k=args.k,
                                      seed=0, noise=0.05, test_frac=0.1)
    config = api.NomadConfig(
        k=args.k, p=args.p, lam=0.05, epochs=args.epochs, seed=0,
        kernel=args.impl,
        stepsize=PowerSchedule(alpha=0.08, beta=0.05))
    t0 = time.perf_counter()
    result = api.solve(problem, config)
    print(f"trained m={args.m} n={args.n} nnz={problem.nnz} for "
          f"{args.epochs} epochs in {time.perf_counter() - t0:.1f}s "
          f"(rmse {result.rmse[-1]:.4f})")
    if args.ckpt_dir:
        save_fit_result(args.ckpt_dir, int(result.epochs_done), result)
        print(f"checkpointed to {args.ckpt_dir}")
    return problem, result


def _hot_swap_loop(store, problem, result, rounds: int, stop: threading.Event,
                   seed: int = 1):
    """The streaming-update thread: a StreamingSession over the trained
    problem, publishing every round's factors to the live store."""
    from .. import api
    sess = api.StreamingSession(problem, result.config, warm_start=result)
    store.attach(sess)
    rng = np.random.default_rng(seed)
    for r in range(rounds):
        if stop.is_set():
            break
        cnt = max(16, problem.nnz // 100)
        m_new, n_new = rng.integers(1, 4), rng.integers(0, 2)
        m, n = sess.problem.m + m_new, sess.problem.n + n_new
        sess.arrive(rows=rng.integers(0, m, cnt),
                    cols=rng.integers(0, n, cnt),
                    vals=rng.normal(size=cnt).astype(np.float32),
                    m_new=int(m_new), n_new=int(n_new), epochs=1)
        print(f"  hot-swap round {r + 1}/{rounds}: published version "
              f"{store.version} (m={m}, n={n})")


def main():
    ap = argparse.ArgumentParser(
        description="Serve matrix-completion top-k recommendations")
    ap.add_argument("--ckpt-dir", default="",
                    help="boot from the newest committed checkpoint here")
    ap.add_argument("--demo", action="store_true",
                    help="train a synthetic problem first (checkpointed "
                         "to --ckpt-dir when set)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes + query count (CI)")
    ap.add_argument("--m", type=int, default=20_000)
    ap.add_argument("--n", type=int, default=4_000)
    ap.add_argument("--nnz", type=int, default=200_000)
    ap.add_argument("--k", type=int, default=16)
    ap.add_argument("--p", type=int, default=8)
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--impl", default="xla",
                    choices=["auto", "xla", "pallas", "wave",
                             "wave_pallas"],
                    help="kernel policy; its serve_impl picks the "
                         "XLA or Pallas top-k scorer")
    ap.add_argument("--top-k", type=int, default=10)
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--item-tile", type=int, default=4096)
    ap.add_argument("--queries", type=int, default=2000)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--hot-swap", type=int, default=0, metavar="ROUNDS",
                    help="run this many concurrent partial_fit rounds "
                         "while serving (requires --demo)")
    args = ap.parse_args()
    if args.smoke:
        args.m, args.n, args.nnz = 600, 150, 6_000
        args.epochs, args.queries = 1, 200
    if not args.demo and not args.ckpt_dir:
        ap.error("pass --ckpt-dir (boot) and/or --demo (train first)")
    if args.hot_swap and not args.demo:
        ap.error("--hot-swap needs --demo (the updater trains on the "
                 "demo problem)")

    from ..serve import FactorStore, RecServer, ServeConfig
    from .compile_cache import enable_compile_cache

    enable_compile_cache()
    problem = result = None
    if args.demo:
        problem, result = _train_demo(args)
        store = FactorStore.from_fit_result(result)
    else:
        store = FactorStore.from_checkpoint(args.ckpt_dir)
        print(f"booted from {args.ckpt_dir} step {store.boot_step} "
              f"(m={store.view().m}, n={store.view().n})")

    cfg = ServeConfig(top_k=args.top_k, max_batch=args.max_batch,
                      max_wait_ms=args.max_wait_ms,
                      item_tile=args.item_tile, kernel=args.impl)
    server = RecServer(store, cfg)
    v0 = store.version
    stop = threading.Event()
    swapper = None
    if args.hot_swap:
        swapper = threading.Thread(
            target=_hot_swap_loop,
            args=(store, problem, result, args.hot_swap, stop),
            daemon=True)
    with server:
        server.recommend([0])           # warm the jit caches
        if swapper is not None:
            swapper.start()
        qps, p50, p99 = run_load(server, store.view().m, args.queries,
                                 clients=args.clients)
        stop.set()
        if swapper is not None:
            swapper.join()
    swaps = store.version - v0
    print(f"{args.queries} queries (top-{cfg.top_k}, "
          f"{server.n_batches} microbatches, {swaps} hot-swaps): "
          f"{qps:.0f} q/s, p50 {p50:.2f} ms, p99 {p99:.2f} ms")


if __name__ == "__main__":
    main()
