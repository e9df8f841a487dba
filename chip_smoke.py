#!/usr/bin/env python3
"""Bring-up check: NOMAD's main path on one TPU v5e at Netflix widths.

Run from the root of a checkout, on a machine with one TPU chip:

    python chip_smoke.py                 # train, reference, serve
    python chip_smoke.py --four-chips    # SPMD ring on a v5e:2x2 only

Phases, all in this one process (any failure exits non-zero):

* device    -- ``jax.devices()`` must be TPU; anything else fails here,
               before any work and without a result line.
* train     -- Netflix-shaped power-law ratings from ``--seed`` at the
               full m=2,649,429 x n=17,770 with k=100; ``api.solve`` with
               ``NomadConfig(k=100, p=8)`` (fused XLA stream driver) for
               two 1-epoch calls, the second warm-started from the
               first.  Checks the divergence sentinel, finite factors
               and a held-out RMSE that falls at every recorded epoch.
* reference -- Netflix x 1e-3: one epoch on the chip against the float64
               serial replay (``core.serial.replay_np``) of the packed
               schedule order, within ``tolerance.assert_factors_close``.
* serve     -- ``RecServer`` over the trained factors with the default
               kernel (Pallas top-k on TPU) answers single-user requests
               for seeded user ids; every answer is checked against the
               float64 dense scores (``tolerance.assert_topk_within_bound``)
               and the serving program must hold a ``tpu_custom_call``.

``--four-chips`` runs only the SPMD ring (``solve(..., mesh=
make_mc_mesh(4))``, shard_map + ppermute) at the same widths with p=4,
checks that W's shards sit on four devices, and compares it with the
single-device p=4 run on the same seed.

Printed times are bring-up timings of one run (host clock, each ended
by a host sync), not benchmark numbers.  The last line of stdout is one
JSON object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
K = 100                       # the paper's rank for Netflix (Table 1)
P_ONE, P_FOUR = 8, 4          # workers on one chip / on the 2x2 mesh
#: ratings requested for the one-chip train phase.  Netflix has
#: 99,072,112; scaled up from a 10M-rating run on one v5e host
#: (generation 41 s, packing 17 s, ~9 s per epoch), the full count would
#: take ~13 min, so the count is cut (m, n and k stay full) and the cut
#: is printed as ``reduced``
RATINGS_ONE = 30_000_000
RATINGS_FOUR = 10_000_000     # for --four-chips, where each second is x4
REQUESTS = 256                # single-user requests in the serve phase


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(phase: str, msg: str) -> None:
    raise SystemExit(f"FAIL {phase}: {msg}")


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def phase_device(want: int):
    """Versions and the device; refuse anything but ``want`` TPU chips."""
    import importlib.metadata

    import jax
    import jaxlib
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    log(f"versions: jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
        f"libtpu {libtpu}")
    devs = jax.devices()
    d = devs[0]
    log(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    if d.platform != "tpu":
        fail("device", f"no TPU (platform {d.platform!r})")
    if len(devs) != want:
        fail("device", f"need {want} TPU chip(s), found {len(devs)}")
    return devs


def netflix_problem(api, netflix, ratings: int, seed: int):
    """Full-width Netflix-shaped problem with a 1% held-out split."""
    (problem, gen_s) = timed(lambda: api.MCProblem.synthetic(
        netflix.m, netflix.n, ratings, k=K, seed=seed, noise=0.1,
        test_frac=0.01, split_seed=seed + 1))
    n_test = len(problem.test[0])
    log(f"data: m={netflix.m} n={netflix.n} k={K}; ratings requested "
        f"{ratings}, generated {problem.nnz + n_test} (the generator "
        f"floors power-law degrees), train {problem.nnz}, held out "
        f"{n_test}; host generation {gen_s:.1f} s")
    if ratings < netflix.nnz:
        log(f"reduced: ratings requested {ratings} of Netflix's "
            f"{netflix.nnz} (m, n and k are full)")
    return problem


def initial_rmse(problem, seed: int) -> float:
    """Held-out RMSE at Algorithm 1's seeded initial factors."""
    import jax

    from repro.core.objective import init_factors, rmse
    W0, H0 = init_factors(jax.random.key(seed), problem.m, problem.n, K)
    return float(rmse(W0, H0, *problem.test))


def phase_train(args, api, netflix):
    import jax

    problem = netflix_problem(api, netflix, RATINGS_ONE, args.seed)
    cfg = api.NomadConfig(k=K, p=P_ONE, lam=netflix.lam, epochs=1,
                          seed=args.seed)
    _, pack_s = timed(lambda: problem.packed(
        P_ONE, balanced=cfg.balanced, waves=False))
    rmse0 = initial_rmse(problem, args.seed)

    compile_s = [0.0]

    def on_event(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += secs
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        r1, first_s = timed(lambda: api.solve(problem, cfg))
        compile1 = compile_s[0]
        r2, second_s = timed(lambda: api.solve(problem, cfg,
                                               warm_start=r1))
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    log(f"train timings (bring-up, not benchmark): host pack "
        f"{pack_s:.1f} s; first call (epoch 1, backend compile "
        f"{compile1:.1f} s of it) {first_s:.1f} s; epoch 2 call "
        f"{second_s:.1f} s (each call includes building the slot stream "
        f"and moving the factors to and from the host; factors end on "
        f"the host, so each time ends synced)")
    for i, r in enumerate((r1, r2), 1):
        if not r.extras["divergence"]["finite"]:
            fail("train", f"divergence sentinel tripped in epoch {i}")
    if not (np.isfinite(r2.W).all() and np.isfinite(r2.H).all()):
        fail("train", "non-finite factors")
    trace = [rmse0, float(r1.trace_rmse[-1]), float(r2.trace_rmse[-1])]
    log("train: held-out RMSE by epoch " + " -> ".join(
        f"{e}:{x:.6f}" for e, x in enumerate(trace)))
    if not all(b < a for a, b in zip(trace, trace[1:])):
        fail("train", f"held-out RMSE did not fall every epoch: {trace}")
    return r2


def phase_reference(args, api, netflix, tol):
    import jax

    from repro.configs.nomad_mf import scaled
    from repro.core.objective import init_factors
    from repro.core.serial import replay_np
    small = scaled(netflix, 1e-3)
    problem = api.MCProblem.synthetic(small.m, small.n, small.nnz, k=K,
                                      seed=args.seed, noise=0.1,
                                      test_frac=0.0)
    cfg = api.NomadConfig(k=K, p=P_ONE, lam=small.lam, epochs=1,
                          seed=args.seed)
    res = api.solve(problem, cfg)
    order = problem.packed(P_ONE, balanced=cfg.balanced).schedule_order()
    W0, H0 = init_factors(jax.random.key(args.seed), small.m, small.n, K)
    (Wr, Hr), replay_s = timed(lambda: replay_np(
        np.asarray(W0, np.float64), np.asarray(H0, np.float64),
        problem.rows, problem.cols, problem.vals, order,
        cfg.make_stepsize()(0), small.lam))
    nnz = problem.nnz
    err = {}
    for name, got, want, rows in (("W", res.W, Wr, small.m),
                                  ("H", res.H, Hr, small.n)):
        try:
            err[name] = tol.assert_factors_close(
                got, want, dtype_policy="fp32", n_updates=nnz / rows,
                what=name)
        except AssertionError as e:
            fail("reference", str(e))
        bound = 16 * tol.EPS["fp32"] * np.sqrt(max(nnz / rows, 1.0))
        log(f"reference: {name} max relative error vs float64 replay "
            f"{err[name]:.3e} (bound {bound:.3e} = 16 eps32 "
            f"sqrt({nnz / rows:.1f}))")
    log(f"reference: m={small.m} n={small.n} ratings={nnz}, 1 epoch on "
        f"the chip vs the serial replay ({replay_s:.1f} s on the host): "
        f"within bound")


def phase_serve(args, result, tol):
    import jax
    import jax.numpy as jnp

    from repro.serve import FactorStore, RecServer, ServeConfig
    from repro.serve.topk import topk_dense_oracle, topk_scores
    store = FactorStore.from_fit_result(result)
    cfg = ServeConfig(top_k=10)
    server = RecServer(store, cfg)
    if cfg.kernel.serve_impl != "pallas":
        fail("serve", f"default serving kernel is "
                      f"{cfg.kernel.serve_impl!r}, not the Pallas top-k")
    view = store.view()
    text = jax.jit(lambda W_u, H: topk_scores(
        W_u, H, cfg.top_k, policy=cfg.kernel,
        item_tile=cfg.item_tile)).lower(
        jnp.zeros((1, view.k), jnp.float32), view.H).as_text()
    if "tpu_custom_call" not in text:
        fail("serve", "serving program holds no tpu_custom_call "
                      "(interpret mode?)")
    rng = np.random.default_rng(args.seed)
    users = rng.choice(view.m, REQUESTS, replace=False)
    lat = np.zeros(len(users))

    def one(i):
        t0 = time.perf_counter()
        rec = server.recommend([users[i]], timeout=120)
        lat[i] = time.perf_counter() - t0
        return rec

    clients = 4
    with server:
        # compile every batch bucket the clients can form (1, 2, 4 users)
        # before the timed load
        _, warm_s = timed(lambda: [server.score(users[:b])
                                   for b in (1, 2, clients)])
        with ThreadPoolExecutor(max_workers=clients) as pool:
            recs, wall = timed(lambda: list(pool.map(one,
                                                     range(len(users)))))
    ids = np.concatenate([r.items for r in recs])
    scores = np.concatenate([r.scores for r in recs])
    W_u = np.asarray(result.W)[users]
    H = np.asarray(result.H)
    try:
        worst = tol.assert_topk_within_bound(ids, scores, W_u, H,
                                             what="served top-10")
    except AssertionError as e:
        fail("serve", str(e))
    _, oracle_ids = topk_dense_oracle(W_u, H, cfg.top_k)
    same = int(np.sum(ids == oracle_ids))
    rest = (f", the other {ids.size - same} near-ties within the bound"
            if same < ids.size else "")
    log(f"serve: {len(recs)} single-user requests, {server.n_batches} "
        f"microbatches; every answer matches the float64 dense oracle "
        f"within the f32 score bound (largest error {worst:.3f} of its "
        f"bound); ids equal to the dense argsort at {same}/{ids.size} "
        f"positions{rest}")
    log(f"serve timings (bring-up, not benchmark): warm-up of batch "
        f"buckets 1, 2, {clients} (compile) {warm_s:.2f} s; "
        f"{len(users) / wall:.1f} q/s, p50 "
        f"{np.percentile(lat, 50) * 1e3:.2f} ms, p99 "
        f"{np.percentile(lat, 99) * 1e3:.2f} ms with {clients} client "
        f"threads")


def phase_four_chips(args, api, netflix):
    from repro.launch.mesh import make_mc_mesh
    problem = netflix_problem(api, netflix, RATINGS_FOUR, args.seed)
    cfg = api.NomadConfig(k=K, p=P_FOUR, lam=netflix.lam, epochs=2,
                          seed=args.seed)
    mesh = make_mc_mesh(P_FOUR)
    # the cold start solve() runs: pack, engine, factors placed on the mesh
    eng, _ = api._nomad_cold_start(problem, cfg, mesh, None)
    shards = eng.Ws.addressable_shards
    devices = {s.device for s in shards}
    log(f"four-chips: W {eng.Ws.shape} in {len(shards)} shards of "
        f"{shards[0].data.shape} on devices "
        f"{sorted(d.id for d in devices)}")
    if len(devices) != P_FOUR:
        fail("four-chips", f"W's shards sit on {len(devices)} device(s)")
    del eng, shards
    spmd, spmd_s = timed(lambda: api.solve(problem, cfg, mesh=mesh))
    local, local_s = timed(lambda: api.solve(problem, cfg))
    log(f"four-chips timings (bring-up, not benchmark): SPMD p=4 "
        f"{spmd_s:.1f} s, one-device p=4 {local_s:.1f} s (2 epochs each, "
        f"compile included)")
    for name, r in (("SPMD", spmd), ("local", local)):
        if not r.extras["divergence"]["finite"]:
            fail("four-chips", f"{name} divergence sentinel tripped")
        log(f"four-chips: {name} held-out RMSE by epoch " + " -> ".join(
            f"{e}:{x:.6f}" for e, x in zip(r.trace_epochs, r.trace_rmse)))
    try:
        # the tolerance tests/test_distributed.py holds SPMD vs local to
        np.testing.assert_allclose(spmd.W, local.W, rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(spmd.H, local.H, rtol=2e-5, atol=2e-6)
    except AssertionError as e:
        fail("four-chips", f"SPMD p=4 differs from local p=4: {e}")
    log(f"four-chips: SPMD p=4 == local p=4 within rtol 2e-5, atol 2e-6 "
        f"(max |dW| {np.max(np.abs(spmd.W - local.W)):.3e}, max |dH| "
        f"{np.max(np.abs(spmd.H - local.H)):.3e})")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the SPMD ring on a v5e:2x2 against "
                         "the one-device p=4 run")
    args = ap.parse_args()

    devs = phase_device(4 if args.four_chips else 1)
    sys.path[:0] = [os.path.join(ROOT, "src"), os.path.join(ROOT, "tests")]
    import tolerance as tol

    from repro import api
    from repro.configs.nomad_mf import NETFLIX
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")

    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips(args, api, NETFLIX)
    else:
        result = phase_train(args, api, NETFLIX)
        phase_reference(args, api, NETFLIX, tol)
        phase_serve(args, result, tol)
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
