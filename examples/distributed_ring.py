"""NOMAD-pattern ring collectives on 8 (host) devices:

  * the SPMD ring matrix-completion engine (via ``api.solve`` with a
    mesh) vs. its single-device twin,
  * ring_ag_matmul / ring_rs_matmul vs. GSPMD references.

This file sets the placeholder device count itself — run it directly:

    pip install -e .           # once, from the repo root
    python examples/distributed_ring.py
"""
import os

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import api
from repro.core.stepsize import PowerSchedule
from repro.distributed import ring
from repro.launch.mesh import make_mc_mesh

p = 8
mesh = make_mc_mesh(p)
print(f"devices: {jax.device_count()}, mesh: {mesh}")

# --- ring collective matmuls ------------------------------------------
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
w = jnp.asarray(rng.normal(size=(32, 48)), jnp.float32)
ag = jax.jit(jax.shard_map(
    lambda xb, wl: ring.ring_ag_matmul(xb, wl, "workers"), mesh=mesh,
    in_specs=(P("workers", None), P(None, "workers")),
    out_specs=P(None, "workers")))
err = float(jnp.max(jnp.abs(ag(x, w) - x @ w)))
print(f"ring all-gather matmul max err: {err:.2e}")

# --- SPMD NOMAD ring engine through the front door --------------------
m, n, k = 256, 64, 16
rows = rng.integers(0, m, 4000)
cols = rng.integers(0, n, 4000)
Wt = rng.normal(size=(m, k)) / np.sqrt(k)
Ht = rng.normal(size=(n, k)) / np.sqrt(k)
vals = np.sum(Wt[rows] * Ht[cols], -1) + 0.02 * rng.normal(size=4000)

problem = api.MCProblem(rows=rows, cols=cols, vals=vals, m=m, n=n,
                        test=(rows, cols, vals))
config = api.NomadConfig(k=k, lam=0.01, epochs=10, p=p,
                         stepsize=PowerSchedule(alpha=0.1, beta=0.01))
spmd = api.solve(problem, config, mesh=mesh)    # real ppermute collectives
local = api.solve(problem, config)              # single-device emulation
print(f"SPMD ring engine on {p} devices: train RMSE after 10 epochs: "
      f"{spmd.rmse[-1]:.4f} (local twin: {local.rmse[-1]:.4f}, "
      f"max |dW|: {np.max(np.abs(spmd.W - local.W)):.2e})")
