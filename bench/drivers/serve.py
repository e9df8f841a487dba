"""Open-loop serving through ``RecServer.submit``.

Set-up makes W and H, both N(0, I/k), on the device from the seed
(``bench/gen/factors.py``), publishes them to a ``FactorStore``, starts
a ``RecServer`` with the default ``ServeConfig`` and scores one batch of
every bucket size from 1 to ``max_batch`` so that nothing compiles in
the window.

The window sends ``rate * seconds`` single-user requests at arrival
times drawn uniformly over the window (a Poisson process given its
count, so every seed offers the same number of requests), users uniform
over ``m``.  Each request's latency runs from its due time to the moment
its answer is set.  A request that errors, or has no answer by
``grace_s`` after the window, is failed.

The check judges a sample of the answered requests, drawn from the
seed, against float64 dense scores of the benchmark's own factors
(``bench/ref/topk.py``).
"""
from __future__ import annotations

import gc
import sys
import threading
import time

import numpy as np

#: compared numbers and their limits (PERF.md, "How correct is decided")
LIMITS = {
    "score_err": 1.0,
    "rank_gap": 1.0,
}

#: the top-k scorer's module name in a trace, one run per microbatch
SCORER_PROGRAM = r"topk"


def setup(cell):
    import jax

    from repro.serve import FactorStore, RecServer, ServeConfig

    from bench.gen.factors import factors
    from bench.gen.seeds import rng

    cfg, tr = cell.cfg, cell.traffic
    m, n, k = int(cfg["m"]), int(cfg["n"]), int(cfg["k"])
    with cell.span("generate"):
        W, H = factors(cell.seed, m, n, k, cell.devices[0])
    store = FactorStore()
    with cell.span("publish"), jax.default_device(cell.devices[0]):
        # published from the host, as a training result's factors are:
        # the generator's own device arrays may carry another on-device
        # layout, which every scoring call would then copy
        W, H = np.asarray(W), np.asarray(H)
        store.publish(W, H)
    server = RecServer(store, ServeConfig())
    g = rng(cell.seed, 3)
    count = int(round(float(tr["rate_per_s"]) * cell.seconds))
    due = np.sort(g.uniform(0.0, cell.seconds, count))
    users = g.integers(0, m, (count, int(tr["users_per_request"])))
    with cell.span("warm"):
        b = 1
        while b <= server.config.max_batch:
            server.score(np.arange(b) % m)
            b *= 2
    server.start()
    return {"W": W, "H": H, "store": store, "server": server, "due": due,
            "users": users, "rng": g}


def window(cell, st):
    server, due, users = st["server"], st["due"], st["users"]
    count = len(due)
    done = np.full(count, np.nan)
    sent = np.zeros(count)
    futs = [None] * count
    q0, b0 = server.n_queries, server.n_batches
    finished = threading.Semaphore(0)

    def on_done(i):
        def cb(_):
            done[i] = time.perf_counter()
            finished.release()
        return cb

    t0 = time.perf_counter()
    for i in range(count):
        wait = t0 + due[i] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        sent[i] = time.perf_counter()
        futs[i] = server.submit(users[i])
        futs[i].add_done_callback(on_done(i))
    close = t0 + cell.seconds
    wait = close - time.perf_counter()
    if wait > 0:
        time.sleep(wait)
    window_s = time.perf_counter() - t0
    deadline = close + float(cell.traffic["grace_s"])
    answered = 0
    while answered < count:
        if not finished.acquire(timeout=max(deadline - time.perf_counter(),
                                            0.0)):
            break
        answered += 1
    st["futs"] = futs
    failed = 0
    for f in futs:
        if not f.done() or f.exception() is not None:
            failed += 1
    lat = (done - (t0 + due)) * 1e3
    ok = np.isfinite(lat)
    late = (sent - (t0 + due)) * 1e3
    cell.counters.update(
        n_queries=server.n_queries - q0, n_batches=server.n_batches - b0,
        requests=count, late_p99_ms=float(np.percentile(late, 99)),
        late_max_ms=float(late.max()), window_s=window_s)
    print(f"serve: {count} requests, generator late p99 "
          f"{np.percentile(late, 99):.3f} ms max {late.max():.3f} ms, "
          f"{server.n_batches - b0} batches", file=sys.stderr,
          flush=True)
    # a failed request misses every latency limit
    lat = np.where(ok, lat, np.inf)
    st["lat_ms"], st["done_by_close"] = lat, int(np.sum(done <= close))
    return {"metrics": {"serve_p99_ms": float(np.percentile(lat, 99)),
                        "serve_p50_ms": float(np.percentile(lat, 50))},
            "attempted": count, "failed": failed, "window_s": window_s,
            "programs": {SCORER_PROGRAM: server.n_batches - b0}}


def release(cell, st):
    st["server"].stop()
    st["server"] = st["store"] = None
    gc.collect()


def check(cell, st):
    """Judge a seeded sample of the answers (``bench/ref/topk.py``)."""
    from bench.ref.topk import judge

    futs, users = st["futs"], st["users"]
    good = [i for i, f in enumerate(futs)
            if f.done() and f.exception() is None]
    size = min(int(cell.traffic["check_sample"]), len(good))
    pick = np.sort(st["rng"].choice(np.asarray(good, np.int64), size,
                                    replace=False))
    recs = [futs[i].result() for i in pick]
    u = np.concatenate([users[i] for i in pick])
    ids = np.concatenate([r.items for r in recs])
    scores = np.concatenate([r.scores for r in recs])
    k_top = ids.shape[1]
    W_u = st["W"][u]
    H = st["H"]
    st["W"] = st["H"] = None
    errs, gaps = judge(W_u, H, ids, scores, k_top)
    cell.counters.update(checked=int(len(u)))
    return [("score_err", float(np.max(errs)), LIMITS["score_err"]),
            ("rank_gap", float(np.max(gaps)), LIMITS["rank_gap"])]
