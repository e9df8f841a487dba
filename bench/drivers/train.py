"""Training epochs through the program's own entry points.

Set-up makes the ratings on the device from the seed
(``bench/gen/ratings.py``), packs them with ``MCProblem.packed`` (span
``pack``), builds the engine and Algorithm 1's initial factors with
``api._nomad_cold_start`` -- the cold start ``api.solve`` runs -- and
runs epoch 1 through ``NomadRingEngine.train(1, test,
dispatch="fused")``, the window's own call, keeping the factors it
produced.  The window repeats that call epoch after epoch until
``--seconds`` have passed; the rate counts every whole epoch over all
the time they took.

The check replays epoch 1 with the plain reference
(``bench/ref/sgd.py``) in the serial order the packing declares, from
the benchmark's own ratings and initial factors, and compares the
program's epoch-1 factors and held-out RMSE with it.
"""
from __future__ import annotations

import gc
import sys
import time

import numpy as np

#: compared numbers and their limits (PERF.md, "How correct is decided")
LIMITS = {
    "factor_err": 1e-3,
    "change_gap": 1e-4,
    "loss_gap": 1e-4,
}

_DTYPE_POLICY = {"float32": "fp32", "bfloat16": "bf16"}


def setup(cell):
    from repro import api
    from repro.core.stepsize import PowerSchedule
    from repro.launch.mesh import make_mc_mesh

    from bench.gen.ratings import ratings
    from bench.gen.seeds import seed31

    cfg = cell.cfg
    with cell.span("generate"):
        train, test = ratings(cell.seed, cfg, cell.devices[0])
    problem = api.MCProblem(rows=train[0], cols=train[1], vals=train[2],
                            m=int(cfg["m"]), n=int(cfg["n"]), test=test)
    ncfg = api.NomadConfig(
        k=int(cfg["k"]), p=int(cfg["p"]), lam=float(cfg["lam"]), epochs=1,
        seed=seed31(cell.seed, 0),
        stepsize=PowerSchedule(float(cfg["alpha"]), float(cfg["beta"])),
        dtype_policy=_DTYPE_POLICY[cfg["dtype"]])
    mesh = make_mc_mesh(ncfg.p) if int(cfg["chips"]) > 1 else None
    pol = ncfg.kernel
    with cell.span("pack"):
        br = problem.packed(ncfg.p, balanced=ncfg.balanced, waves=pol.wave,
                            sub_blocks=pol.sub_blocks,
                            schedule=ncfg.schedule,
                            schedule_seed=ncfg.schedule_seed)
    with cell.span("cold_start"):
        eng, start = api._nomad_cold_start(problem, ncfg, mesh, None)
        eng.epoch_idx = int(start)
    # the window's own call, in the same context (a default-device
    # context would be part of the compiled program's cache key)
    with cell.span("epoch1"):
        trace1 = eng.train(1, problem.test, dispatch="fused")
    Ws1, Hs1 = np.asarray(eng.Ws), np.asarray(eng.Hs)
    stream = getattr(eng, "_stream", None)
    slots = None if stream is None else int(stream[0].shape[0]) // ncfg.p
    cell.counters.update(nnz=problem.nnz, p=ncfg.p, slots=slots,
                         n_test=len(problem.test[0]))
    return {"problem": problem, "br": br, "eng": eng,
            "Ws1": Ws1, "Hs1": Hs1, "rmse1": float(trace1[-1][1]),
            "finite1": bool(eng.last_finite)}


#: the epoch program's module name in a trace, one run per epoch
EPOCH_PROGRAM = r"jit_train\b"

#: epochs in a traced window: the profiler records every op of the
#: stream's per-slot loop, and its device buffer holds about one epoch
#: of them at these sizes (PERF.md); a longer trace loses the rest
TRACED_EPOCHS = 1


def window(cell, st):
    eng, test = st["eng"], st["problem"].test
    epochs, finite = 0, True
    t0 = time.perf_counter()
    while True:
        with cell.span("epoch"):
            eng.train(1, test, dispatch="fused")
        epochs += 1
        finite = finite and bool(eng.last_finite)
        if cell.trace and epochs >= TRACED_EPOCHS:
            break
        if time.perf_counter() - t0 >= cell.seconds:
            break
    dt = time.perf_counter() - t0
    cell.counters.update(window_epochs=epochs, window_s=dt)
    rate = st["problem"].nnz * epochs / dt
    return {"metrics": {"train_updates_per_s": rate}, "attempted": epochs,
            "failed": 0 if finite else epochs, "window_s": dt,
            "programs": {EPOCH_PROGRAM: epochs}}


def release(cell, st):
    st["eng"] = None
    gc.collect()


def reference_epoch1(cell, st):
    """The plain reference's epoch 1 and its initial factors, on the
    first device: ``(W0, H0, W1, H1)``."""
    import jax
    import jax.numpy as jnp

    from bench.gen.seeds import seed31
    from bench.ref import sgd as ref

    cfg, br, problem = cell.cfg, st["br"], st["problem"]
    m, n, k, p = int(cfg["m"]), int(cfg["n"]), int(cfg["k"]), br.p
    R, C, V, M = ref.slot_stream(br.schedule_order(), br.nnz_cell,
                                 problem.rows, problem.cols, problem.vals,
                                 m, n)
    key = jax.random.key(seed31(cell.seed, 0))
    with jax.default_device(cell.devices[0]):
        W1, H1 = ref.sgd_epoch(
            *ref.init_factors(key, m, n, k),
            *(jnp.asarray(a.reshape(-1)) for a in (R, C, V, M)),
            ref.step_size(float(cfg["alpha"]), float(cfg["beta"]), 0),
            float(cfg["lam"]), p=p)
        W0, H0 = ref.init_factors(key, m, n, k)
    return W0, H0, W1, H1


def compare(cell, st, W0, H0, W1, H1):
    """``[(name, value, limit), ...]``: the program's epoch-1 factors and
    held-out RMSE against the reference's (see PERF.md)."""
    import jax
    import jax.numpy as jnp

    from bench.ref import sgd as ref

    br, problem = st["br"], st["problem"]
    with jax.default_device(cell.devices[0]):
        errs, prog_norm, ref_norm = [], [], []
        for got, want, init, where in ((st["Ws1"], W1, W0, br.row_of),
                                       (st["Hs1"], H1, H0, br.col_of)):
            valid = jnp.asarray(where >= 0)[..., None]
            idx = jnp.asarray(np.maximum(where, 0))
            got = jnp.asarray(got, jnp.float32)
            want, init = want[idx], init[idx]
            rel = jnp.abs(got - want) / (1.0 + jnp.abs(want))
            errs.append(float(jnp.max(jnp.where(valid, rel, 0.0))))
            for out, a in ((prog_norm, got), (ref_norm, want)):
                sq = jnp.sum(jnp.where(valid, (a - init) ** 2, 0.0),
                             axis=-1)
                out.append(float(np.sqrt(np.sum(np.asarray(sq, np.float64)))))
        scale = max(np.median(ref_norm), 1e-30)
        gap = float(max(abs(a - b) / max(b, scale)
                        for a, b in zip(prog_norm, ref_norm)))
        rmse_ref = float(ref.heldout_rmse(
            W1, H1, *(jnp.asarray(a) for a in problem.test)))
    cell.counters.update(rmse1_program=st["rmse1"], rmse1_reference=rmse_ref,
                         change_norm_program=prog_norm,
                         change_norm_reference=ref_norm)
    loss_gap = abs(st["rmse1"] - rmse_ref) / rmse_ref
    if not st["finite1"]:
        loss_gap = float("inf")
    return [("factor_err", max(errs), LIMITS["factor_err"]),
            ("change_gap", gap, LIMITS["change_gap"]),
            ("loss_gap", loss_gap, LIMITS["loss_gap"])]


def check(cell, st):
    from bench.ref.sgd import OrderError
    t = time.perf_counter()
    try:
        W0, H0, W1, H1 = reference_epoch1(cell, st)
    except OrderError as e:
        print(f"reference: {e}", file=sys.stderr, flush=True)
        return [("order_valid", 1.0, 0.0)]
    out = compare(cell, st, W0, H0, W1, H1)
    cell.counters["reference_s"] = time.perf_counter() - t
    print(f"reference: epoch 1 replayed and compared in "
          f"{cell.counters['reference_s']:.3f} s; held-out RMSE program "
          f"{st['rmse1']!r}, reference {cell.counters['rmse1_reference']!r}",
          file=sys.stderr, flush=True)
    return out
