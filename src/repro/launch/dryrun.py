"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be the very first two lines — before any other import — because jax
locks the device count on first initialization:
"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from typing import Optional  # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402

from .. import configs                        # noqa: E402
from ..distributed.sharding import make_ctx   # noqa: E402
from ..models.config import ModelConfig       # noqa: E402
from ..optim import adamw as optim            # noqa: E402
from . import mesh as mesh_mod, specs         # noqa: E402
from .hlo_analysis import collective_summary  # noqa: E402
from .train import make_train_step            # noqa: E402
from .serve import make_prefill, make_decode_step  # noqa: E402

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "artifacts", "dryrun")


def _mesh_devices(multi_pod: bool) -> int:
    return 512 if multi_pod else 256


def build_mesh(multi_pod: bool):
    n = _mesh_devices(multi_pod)
    devs = jax.devices()
    assert len(devs) >= n, (
        f"need {n} devices; run with "
        f"XLA_FLAGS=--xla_force_host_platform_device_count=512")
    return mesh_mod.make_production_mesh(multi_pod=multi_pod)


def lower_cell(cfg: ModelConfig, shape: dict, mesh, *,
               opt_overrides: Optional[dict] = None,
               cfg_overrides: Optional[dict] = None,
               train_kwargs: Optional[dict] = None):
    """Build and lower the cell's step function.  Returns `lowered`."""
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    ctx = make_ctx(mesh)
    kind = shape["kind"]
    if kind == "train":
        opt_cfg = optim.AdamWConfig(**(opt_overrides or {}))
        state_sds = specs.train_state_struct(cfg, ctx, opt_cfg)
        batch_sds = specs.batch_struct(cfg, shape, ctx)
        fn = make_train_step(cfg, ctx, opt_cfg, **(train_kwargs or {}))
        lowered = jax.jit(fn, donate_argnums=0).lower(
            state_sds, batch_sds)
    elif kind == "prefill":
        ps, pspecs = specs.sharded_params_specs(cfg, ctx)
        params_sds = jax.tree.map(
            lambda s, sp: specs._sds(s, ctx, sp), ps, pspecs)
        batch_sds = specs.batch_struct(cfg, shape, ctx)
        fn = make_prefill(cfg, ctx)
        lowered = jax.jit(fn).lower(params_sds, batch_sds)
    else:  # decode
        ps, pspecs = specs.sharded_params_specs(cfg, ctx)
        params_sds = jax.tree.map(
            lambda s, sp: specs._sds(s, ctx, sp), ps, pspecs)
        batch_sds = specs.batch_struct(cfg, shape, ctx)
        B, S = shape["global_batch"], shape["seq_len"]
        cache_sds = specs.cache_struct(cfg, B, S, ctx)
        pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
        fn = make_decode_step(cfg, ctx)
        lowered = jax.jit(fn, donate_argnums=2).lower(
            params_sds, batch_sds, cache_sds, pos_sds)
    return lowered, cfg


def run_cell(arch: str, shape_name: str, multi_pod: bool, *,
             save_hlo: bool = False, opt_overrides=None, cfg_overrides=None,
             tag: str = "", probe_depth: bool = True,
             train_kwargs=None) -> dict:
    cfg = configs.get_config(arch)
    shape = configs.SHAPES[shape_name]
    mesh = build_mesh(multi_pod)
    n_dev = _mesh_devices(multi_pod)

    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "kind": shape["kind"], "tag": tag}
    t0 = time.time()
    lowered, cfg = lower_cell(cfg, shape, mesh,
                              opt_overrides=opt_overrides,
                              cfg_overrides=cfg_overrides,
                              train_kwargs=train_kwargs)
    rec["lower_s"] = round(time.time() - t0, 1)
    t0 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 1)

    mem = compiled.memory_analysis()
    rec["memory"] = {
        k: int(getattr(mem, k))
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes")
        if hasattr(mem, k)}
    ca = compiled.cost_analysis()
    rec["cost"] = {k: float(v) for k, v in ca.items()
                   if isinstance(v, (int, float)) and
                   k in ("flops", "bytes accessed", "optimal_seconds",
                         "utilization operand 0 {}", "transcendentals")}
    hlo = compiled.as_text()
    rec["hlo_bytes"] = len(hlo)
    rec["collectives"] = collective_summary(hlo, n_dev)
    rec["analytic"] = analytic_model(cfg, shape, n_dev)
    if probe_depth:
        # reuse a previous probe when available (the 1/2-period compiles
        # are the expensive part and are invariant to collective-analysis
        # fixes)
        prev = _existing_artifact(arch, shape_name, rec["mesh"], tag)
        if prev and "cost_corrected" in prev:
            rec["cost_corrected"] = prev["cost_corrected"]
        else:
            rec["cost_corrected"] = depth_probe(
                cfg, shape, mesh, rec["cost"],
                opt_overrides=opt_overrides, cfg_overrides=cfg_overrides,
                train_kwargs=train_kwargs)

    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    suffix = f"_{tag}" if tag else ""
    path = os.path.join(
        ARTIFACT_DIR, f"{arch}__{shape_name}__{rec['mesh']}{suffix}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    if save_hlo:
        with open(path.replace(".json", ".hlo"), "w") as f:
            f.write(hlo)
    rec["artifact"] = path
    return rec


def _existing_artifact(arch, shape_name, mesh_s, tag):
    suffix = f"_{tag}" if tag else ""
    path = os.path.join(
        ARTIFACT_DIR, f"{arch}__{shape_name}__{mesh_s}{suffix}.json")
    if os.path.exists(path):
        try:
            with open(path) as f:
                return json.load(f)
        except Exception:   # noqa: BLE001
            return None
    return None


def depth_probe(cfg: ModelConfig, shape: dict, mesh, cost_full: dict, *,
                opt_overrides=None, cfg_overrides=None,
                train_kwargs=None) -> dict:
    """cost_analysis counts while-loop bodies once; recover the true
    per-device totals by compiling 1-period and 2-period variants:
    body = c2 - c1, outside = 2*c1 - c2, total = outside + n_periods*body.
    """
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    n_per = cfg.n_periods
    costs = []
    for periods in (1, 2):
        n_layers = cfg.n_prologue + periods * cfg.period
        ov = dict(cfg_overrides or {})
        # force every loop out of the HLO so cost_analysis counts each
        # layer: unrolled layer scan, single-block attention, loop-free
        # SSM chunking
        ov.update(n_layers=n_layers, scan_unroll=True,
                  attn_chunk=shape["seq_len"],
                  ssm_chunk=shape["seq_len"])
        lowered, _ = lower_cell(configs.get_config(cfg_alias(cfg.name)),
                                shape, mesh, opt_overrides=opt_overrides,
                                cfg_overrides=ov, train_kwargs=train_kwargs)
        costs.append(lowered.compile().cost_analysis())
    out = {}
    for key in ("flops", "bytes accessed", "transcendentals"):
        c1 = float(costs[0].get(key, 0.0))
        c2 = float(costs[1].get(key, 0.0))
        body = max(c2 - c1, 0.0)
        outside = max(2 * c1 - c2, 0.0)
        out[key] = outside + n_per * body
        out[key + " (1-period)"] = c1
    out["n_periods"] = n_per
    return out


def cfg_alias(name: str) -> str:
    """Map a config's display name back to its registry id."""
    return name.replace(".", "_").replace("-", "_")


def analytic_model(cfg: ModelConfig, shape: dict, n_dev: int) -> dict:
    """MODEL_FLOPS = 6 N D (dense) / 6 N_active D (MoE) + attention term."""
    S, B = shape["seq_len"], shape["global_batch"]
    kind = shape["kind"]
    D_tok = B * S if kind in ("train", "prefill") else B
    N = cfg.param_count()
    N_act = cfg.active_param_count()
    mult = 6 if kind == "train" else 2
    flops = mult * N_act * D_tok
    # causal attention score+value FLOPs (not in 6ND):
    attn_layers = sum(1 for i in range(cfg.n_layers)
                      if cfg.layer_kind(i) == "attn")
    if kind in ("train", "prefill"):
        flops += mult * attn_layers * 2 * B * cfg.n_heads * \
            (S * S // 2) * cfg.head_dim
    else:
        flops += 2 * attn_layers * 2 * B * cfg.n_heads * S * cfg.head_dim
    return {"params_total": N, "params_active": N_act,
            "model_flops": float(flops), "tokens": D_tok}


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--no-probe", action="store_true",
                    help="skip the 1/2-period flop-correction compiles")
    ap.add_argument("--skip-existing", action="store_true",
                    help="skip cells whose artifact JSON already exists")
    args = ap.parse_args()

    cells = []
    if args.all:
        cells = [(a, s) for a, s, skip in configs.cells() if not skip]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    failures = 0
    for multi_pod in meshes:
        for arch, shape_name in cells:
            label = f"{arch} x {shape_name} x " \
                    f"{'2x16x16' if multi_pod else '16x16'}"
            mesh_s = "2x16x16" if multi_pod else "16x16"
            suffix = f"_{args.tag}" if args.tag else ""
            art = os.path.join(
                ARTIFACT_DIR,
                f"{arch}__{shape_name}__{mesh_s}{suffix}.json")
            if args.skip_existing and os.path.exists(art):
                want_probe = (not args.no_probe)
                with open(art) as f:
                    have = json.load(f)
                if (not want_probe) or "cost_corrected" in have:
                    print(f"SKIP {label} (artifact exists)", flush=True)
                    continue
            try:
                rec = run_cell(arch, shape_name, multi_pod,
                               save_hlo=args.save_hlo, tag=args.tag,
                               probe_depth=not args.no_probe)
                mem_gb = rec["memory"].get("argument_size_in_bytes", 0) \
                    / 1e9
                tmp_gb = rec["memory"].get("temp_size_in_bytes", 0) / 1e9
                print(f"OK   {label}: compile {rec['compile_s']}s, "
                      f"args {mem_gb:.2f} GB/dev, temp {tmp_gb:.2f} GB/dev,"
                      f" wire {rec['collectives']['wire_bytes_per_device']/1e6:.1f} MB/dev",
                      flush=True)
            except Exception as e:  # noqa: BLE001
                failures += 1
                print(f"FAIL {label}: {e}", flush=True)
                traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
