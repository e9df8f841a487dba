#!/usr/bin/env python3
"""Compile each cell's programs at the cell's sizes for a described TPU
v5e (one chip) and print their
``memory_analysis()``.  Needs no chip: the TPU compiler compiles for a
topology that is described, not attached.  Nothing runs, so this says
nothing about results or times.

    JAX_PLATFORMS=cpu PYTHONPATH=src python bench/rehearse.py [cell ...]

Programs: the rating generator (``bench/gen/ratings.py``) or the factor
generator, the fused stream driver (``_local_train_stream``) at the
slot count a cell's stream about has (``--slots``), the plain
reference epoch, and the Pallas top-k scorer at 1 and 64 users.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
HBM = 16 << 30


def report(name, lowered):
    c = lowered.compile()
    ma = c.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    print(json.dumps({
        "program": name, "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes, "total_gib": total / 2**30,
        "fits": total <= HBM,
        "kernel": "tpu_custom_call" in c.as_text()}), flush=True)
    return total <= HBM


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cells", nargs="*")
    ap.add_argument("--slots", type=int, default=1_200_000,
                    help="slots of the fused stream to compile for")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import run
    from bench.gen import factors as gfac
    from bench.gen import ratings as grat
    from bench.ref import sgd as ref

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def s(shape, dt, sh=one):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    bench = run.load_json(ROOT / "BENCHMARK.json")
    cells = args.cells or [w["name"] for w in bench["workloads"]]
    ok = True
    for name in cells:
        w, c = run.cell_spec(bench, name)
        cfg = run.load_json(ROOT / c["file"])
        traffic = run.load_json(ROOT / "bench" / "traffic" /
                                f"{w['traffic']}.json")
        m, n, k, p = (int(cfg[x]) for x in ("m", "n", "k", "p"))
        key = s((), jax.random.key(0).dtype)
        if traffic["driver"] == "train":
            total = int(cfg["nnz"])
            n_test = int(total * cfg["assumed"]["test_frac"])
            ok &= report(f"{name}: rating positions", grat._pattern.lower(
                key, s((m,), jnp.int32), s((n,), jnp.float32), m=m, n=n,
                total=total))
            ok &= report(f"{name}: rating values", grat._values.lower(
                key, s((total,), jnp.int32), s((total,), jnp.int32),
                s((), jnp.float32), m=m, n=n, k=k))
            flat = args.slots * p
            data = (s((flat,), jnp.int32), s((flat,), jnp.int32),
                    s((flat,), jnp.float32), s((flat,), jnp.bool_))
            ok &= report(f"{name}: reference epoch", ref.sgd_epoch.lower(
                s((m, k), jnp.float32), s((n, k), jnp.float32), *data,
                0.01, 0.05, p=p))
            from repro.core import nomad
            from repro.kernels.policy import KernelPolicy
            ml, nl = -(-m // p), -(-n // p)
            ok &= report(f"{name}: fused stream epoch",
                         nomad._local_train_stream.lower(
                             s((p, ml, k), jnp.float32),
                             s((p, nl, k), jnp.float32), data,
                             s((1,), jnp.float32), s((1,), jnp.int32),
                             0.05, s((n_test,), jnp.int32),
                             s((n_test,), jnp.int32),
                             s((n_test,), jnp.float32),
                             policy=KernelPolicy(impl="xla"),
                             entry=None, n_rec=1))
        else:
            ok &= report(f"{name}: factor generator", gfac._factors.lower(
                key, m=m, n=n, k=k))
            from repro.serve import topk
            for users in (1, 64):
                ok &= report(f"{name}: top-k scorer, {users} users",
                             topk._topk_pallas.lower(
                                 s((users, k), jnp.float32),
                                 s((n, k), jnp.float32), None, k_top=10,
                                 item_tile=4096, interpret=False))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
