"""Dry-run of the NOMAD matrix-completion ring engine itself on the
production mesh — the cell most representative of the paper's technique.

The full Netflix / Yahoo / Hugewiki problems (Table 2) are lowered as
ShapeDtypeStructs against a 256-worker (single-pod) or 512-worker
(multi-pod) ring: one epoch = p ring steps of (sequential block SGD +
collective-permute of the nomadic H block), exactly DESIGN.md §2.

    PYTHONPATH=src python -m repro.launch.dryrun_mc --dataset netflix
    PYTHONPATH=src python -m repro.launch.dryrun_mc --dataset netflix \
        --multi-pod --sub-blocks 4
"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse   # noqa: E402
import json       # noqa: E402
import time       # noqa: E402

import jax                    # noqa: E402
import jax.numpy as jnp       # noqa: E402
import numpy as np            # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from ..configs import nomad_mf              # noqa: E402
from ..core.nomad import _spmd_epoch_fn     # noqa: E402
from ..core.partition import sub_block_starts  # noqa: E402
from ..kernels.policy import KernelPolicy   # noqa: E402
from .hlo_analysis import collective_summary  # noqa: E402
from .mesh import make_mc_mesh              # noqa: E402
from .dryrun import ARTIFACT_DIR            # noqa: E402


def mc_cell_specs(cfg: nomad_mf.MFConfig, p: int, mesh,
                  sub_blocks: int = 1):
    """ShapeDtypeStructs for one ring epoch on dataset ``cfg``.

    With ``sub_blocks > 1`` the rating arrays carry the pack-time
    pre-partitioned per-sub-block layout ``(p, p, sub_blocks, sub_max)``
    (cols localized to the sub-block) consumed by ``_spmd_epoch_fn``.
    """
    m_local = -(-cfg.m // p)
    n_local = -(-cfg.n // p)
    # nnz-balanced packing gives ~nnz/p^2 per cell (+25% slack)
    max_nnz = max(1, int(cfg.nnz / (p * p) * 1.25))
    if sub_blocks > 1:
        data_shape = (p, p, sub_blocks,
                      max(1, int(max_nnz / sub_blocks * 1.25)))
    else:
        data_shape = (p, p, max_nnz)
    sh = lambda spec: NamedSharding(mesh, spec)
    W = jax.ShapeDtypeStruct((p, m_local, cfg.k), jnp.float32,
                             sharding=sh(P("workers")))
    H = jax.ShapeDtypeStruct((p, n_local, cfg.k), jnp.float32,
                             sharding=sh(P("workers")))
    rows = jax.ShapeDtypeStruct(data_shape, jnp.int32,
                                sharding=sh(P("workers")))
    cols = jax.ShapeDtypeStruct(data_shape, jnp.int32,
                                sharding=sh(P("workers")))
    vals = jax.ShapeDtypeStruct(data_shape, jnp.float32,
                                sharding=sh(P("workers")))
    mask = jax.ShapeDtypeStruct(data_shape, jnp.bool_,
                                sharding=sh(P("workers")))
    lr = jax.ShapeDtypeStruct((), jnp.float32)
    return (W, H, rows, cols, vals, mask, lr), max_nnz


def run_mc_cell(dataset: str, multi_pod: bool, sub_blocks: int = 1,
                tag: str = "", save_hlo: bool = False,
                impl: str = "xla") -> dict:
    cfg = {"netflix": nomad_mf.NETFLIX, "yahoo": nomad_mf.YAHOO,
           "hugewiki": nomad_mf.HUGEWIKI}[dataset]
    p = 512 if multi_pod else 256
    mesh = make_mc_mesh(p)
    if impl not in ("xla", "pallas"):
        raise ValueError(
            f"dry-run models the sequential impls only, got {impl!r} "
            "(the wave layout's shape is data-dependent)")
    policy = KernelPolicy(impl=impl, sub_blocks=sub_blocks)
    epoch_fn = _spmd_epoch_fn(p, "workers", cfg.lam, policy,
                              sub_starts=sub_block_starts(-(-cfg.n // p),
                                                          sub_blocks))
    pspec = P("workers")
    # check_vma off: pallas_call has no replication rule under shard_map,
    # and the dry-run only lowers/compiles (no numerics to protect)
    fn = jax.shard_map(
        epoch_fn, mesh=mesh,
        in_specs=(pspec, pspec, pspec, pspec, pspec, pspec, P()),
        out_specs=(pspec, pspec), check_vma=False)
    sds, max_nnz = mc_cell_specs(cfg, p, mesh, sub_blocks)
    rec = {"arch": f"nomad_mc_{dataset}", "shape": f"epoch_p{p}",
           "mesh": "ring512" if multi_pod else "ring256",
           "kind": "mc_epoch", "tag": tag, "impl": impl,
           "sub_blocks": sub_blocks, "max_nnz_per_cell": max_nnz}
    t0 = time.time()
    lowered = jax.jit(fn, donate_argnums=(0, 1)).lower(*sds)
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 1)
    mem = compiled.memory_analysis()
    rec["memory"] = {
        k: int(getattr(mem, k))
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes")
        if hasattr(mem, k)}
    ca = compiled.cost_analysis()
    rec["cost"] = {k: float(v) for k, v in ca.items()
                   if isinstance(v, (int, float)) and
                   k in ("flops", "bytes accessed", "transcendentals")}
    hlo = compiled.as_text()
    rec["collectives"] = collective_summary(hlo, p)
    # analytic: one epoch touches every rating once: 8k flops per rating
    # (2 dots + 2 axpy-ish vector ops of length k), wire = H circulating
    # p times
    rec["analytic"] = {
        "model_flops": float(10 * cfg.k * cfg.nnz),
        "wire_bytes_ring": float(4 * cfg.k * cfg.n * (p - 1)),
        "params_total": (cfg.m + cfg.n) * cfg.k,
        "tokens": cfg.nnz,
    }
    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    suffix = f"_{tag}" if tag else ""
    path = os.path.join(
        ARTIFACT_DIR, f"nomad_mc_{dataset}__{rec['shape']}{suffix}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    if save_hlo:
        with open(path.replace(".json", ".hlo"), "w") as f:
            f.write(hlo)
    rec["artifact"] = path
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="netflix",
                    choices=["netflix", "yahoo", "hugewiki", "all"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--sub-blocks", type=int, default=1)
    # wave impls are excluded: their (n_waves, wave_width) layout is
    # data-dependent (wave count tracks the max row/col degree per cell),
    # which a shape-only dry-run cannot model honestly
    ap.add_argument("--impl", default="xla", choices=["xla", "pallas"])
    ap.add_argument("--tag", default="")
    ap.add_argument("--save-hlo", action="store_true")
    args = ap.parse_args()
    names = (["netflix", "yahoo", "hugewiki"] if args.dataset == "all"
             else [args.dataset])
    for name in names:
        rec = run_mc_cell(name, args.multi_pod, args.sub_blocks,
                          tag=args.tag, save_hlo=args.save_hlo,
                          impl=args.impl)
        print(f"OK nomad_mc/{name} p{512 if args.multi_pod else 256} "
              f"sub{args.sub_blocks}: compile {rec['compile_s']}s, "
              f"wire {rec['collectives']['wire_bytes_per_device']/1e6:.2f}"
              f" MB/dev, temp {rec['memory']['temp_size_in_bytes']/1e9:.2f}"
              f" GB/dev", flush=True)


if __name__ == "__main__":
    main()
