"""Multi-device tests: run in subprocesses with a forced host-device count
(the main pytest process must keep the real single device — see
conftest.py).  Each subprocess asserts internally and exits nonzero on
failure."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(body: str, n_dev: int = 8, timeout: int = 480):
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count={n_dev}")
        import sys
        sys.path.insert(0, {SRC!r})
        import jax, jax.numpy as jnp, numpy as np
        assert jax.device_count() == {n_dev}
    """) + textwrap.dedent(body)
    res = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=timeout)
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nERR:\n{res.stderr}"
    return res.stdout


def test_ring_matmuls_match_references():
    run_sub("""
        from jax.sharding import PartitionSpec as P
        shard_map = jax.shard_map
        from repro.distributed import ring
        from repro.launch.mesh import make_mc_mesh
        mesh = make_mc_mesh(8)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(32, 48)), jnp.float32)

        ag = jax.jit(shard_map(
            lambda xb, wl: ring.ring_ag_matmul(xb, wl, "workers"),
            mesh=mesh, in_specs=(P("workers", None), P(None, "workers")),
            out_specs=P(None, "workers")))
        got = ag(x, w)
        np.testing.assert_allclose(got, x @ w, rtol=1e-5, atol=1e-5)

        rs = jax.jit(shard_map(
            lambda xl, wl: ring.ring_rs_matmul(xl, wl, "workers"),
            mesh=mesh, in_specs=(P(None, "workers"), P("workers", None)),
            out_specs=P("workers", None)))
        got2 = rs(x, w)
        np.testing.assert_allclose(got2, x @ w, rtol=1e-4, atol=1e-4)
        print("ring matmuls ok")
    """)


def test_spmd_nomad_engine_matches_local():
    run_sub("""
        from repro.core import nomad, partition, objective
        from repro.core.stepsize import PowerSchedule
        from repro.launch.mesh import make_mc_mesh
        rng = np.random.default_rng(0)
        m, n, k, p = 64, 32, 8, 8
        nnz = 600
        rows = rng.integers(0, m, nnz); cols = rng.integers(0, n, nnz)
        vals = rng.normal(size=nnz)
        br = partition.pack(rows, cols, vals, m, n, p)
        W0, H0 = objective.init_factors_np(0, m, n, k)
        W0 = W0.astype(np.float32); H0 = H0.astype(np.float32)
        sched = PowerSchedule(alpha=0.03, beta=0.0)

        local = nomad.NomadRingEngine(br=br, k=k, lam=0.01, stepsize=sched)
        local.init_factors(W0, H0)
        local.run_epoch(); local.run_epoch()
        Wl, Hl = local.factors()

        mesh = make_mc_mesh(p)
        spmd = nomad.NomadRingEngine(br=br, k=k, lam=0.01, stepsize=sched,
                                     mesh=mesh)
        spmd.init_factors(W0, H0)
        spmd.run_epoch(); spmd.run_epoch()
        Ws, Hs = spmd.factors()
        np.testing.assert_allclose(Ws, Wl, rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(Hs, Hl, rtol=2e-5, atol=2e-6)
        print("spmd ring == local emulation")
    """)


def test_spmd_sub_block_pipeline_matches_local():
    """The pre-partitioned sub_blocks>1 pipeline (pack-time split, localized
    cols, sub_starts slicing) must reproduce the whole-cell local engine."""
    run_sub("""
        from repro.core import nomad, partition, objective
        from repro.core.stepsize import PowerSchedule
        from repro.launch.mesh import make_mc_mesh
        rng = np.random.default_rng(1)
        m, n, k, p = 48, 36, 6, 4
        nnz = 700
        rows = rng.integers(0, m, nnz); cols = rng.integers(0, n, nnz)
        vals = rng.normal(size=nnz)
        W0, H0 = objective.init_factors_np(0, m, n, k)
        W0 = W0.astype(np.float32); H0 = H0.astype(np.float32)
        sched = PowerSchedule(alpha=0.03, beta=0.0)

        local = nomad.NomadRingEngine(
            br=partition.pack(rows, cols, vals, m, n, p),
            k=k, lam=0.01, stepsize=sched)
        local.init_factors(W0, H0)
        local.run_epoch()
        Wl, Hl = local.factors()

        mesh = make_mc_mesh(p)
        for sub in (2, 3):
            br = partition.pack(rows, cols, vals, m, n, p, sub_blocks=sub)
            spmd = nomad.NomadRingEngine(br=br, k=k, lam=0.01,
                                         stepsize=sched, sub_blocks=sub,
                                         mesh=mesh)
            spmd.init_factors(W0, H0)
            spmd.run_epoch()
            Ws, Hs = spmd.factors()
            # sub-block-major execution reorders within cells; equal up to
            # fp noise of the reordered-but-equivalent update stream
            np.testing.assert_allclose(Ws, Wl, rtol=2e-4, atol=2e-5)
            np.testing.assert_allclose(Hs, Hl, rtol=2e-4, atol=2e-5)
        print("spmd sub-block pipeline == local")
    """, n_dev=4)


def test_spmd_general_schedule_matches_local():
    """The unrolled per-step-ppermute SPMD path (random / balanced /
    sim-compiled schedules) must reproduce the local executor, including
    under sub-block pipelining."""
    run_sub("""
        from repro.core import nomad, partition, objective
        from repro.core.schedule import OwnershipSchedule
        from repro.core.stepsize import PowerSchedule
        from repro.launch.mesh import make_mc_mesh
        rng = np.random.default_rng(2)
        m, n, k, p = 48, 24, 6, 4
        nnz = 500
        rows = rng.integers(0, m, nnz); cols = rng.integers(0, n, nnz)
        vals = rng.normal(size=nnz)
        W0, H0 = objective.init_factors_np(0, m, n, k)
        W0 = W0.astype(np.float32); H0 = H0.astype(np.float32)
        sched = PowerSchedule(alpha=0.03, beta=0.0)
        mesh = make_mc_mesh(p)
        for spec, sub in (("random", 1), ("balanced", 1), ("random", 2)):
            kw = dict(schedule=spec, schedule_seed=3)
            local = nomad.NomadRingEngine(
                br=partition.pack(rows, cols, vals, m, n, p, **kw),
                k=k, lam=0.01, stepsize=sched)
            local.init_factors(W0, H0)
            local.run_epoch(); local.run_epoch()
            Wl, Hl = local.factors()
            br = partition.pack(rows, cols, vals, m, n, p,
                                sub_blocks=sub, **kw)
            spmd = nomad.NomadRingEngine(br=br, k=k, lam=0.01,
                                         stepsize=sched, mesh=mesh,
                                         sub_blocks=sub)
            spmd.init_factors(W0, H0)
            spmd.run_epoch(); spmd.run_epoch()
            Ws, Hs = spmd.factors()
            rtol, atol = (2e-4, 2e-5) if sub > 1 else (2e-5, 2e-6)
            np.testing.assert_allclose(Ws, Wl, rtol=rtol, atol=atol)
            np.testing.assert_allclose(Hs, Hl, rtol=rtol, atol=atol)
        print("spmd general schedules == local")
    """, n_dev=4)


def test_spmd_fused_dispatch_bitwise_matches_loop():
    """The fused SPMD driver (shard_mapped epoch inside a jitted scan
    over epochs, donated factor shards, on-device trace) must reproduce
    the per-epoch loop dispatch bit for bit — W, H and trace — across
    kernels and schedules (DESIGN.md §9)."""
    run_sub("""
        import dataclasses
        from repro import api
        from repro.core.stepsize import PowerSchedule
        from repro.launch.mesh import make_mc_mesh
        rng = np.random.default_rng(3)
        m, n, p = 48, 24, 4
        nnz = 400
        rows = rng.integers(0, m, nnz); cols = rng.integers(0, n, nnz)
        vals = rng.normal(size=nnz)
        test = (rng.integers(0, m, 40), rng.integers(0, n, 40),
                rng.normal(size=40))
        problem = api.MCProblem(rows=rows, cols=cols, vals=vals, m=m,
                                n=n, test=test)
        mesh = make_mc_mesh(p)
        for impl in ("xla", "wave"):
            for spec in ("ring", "random", "balanced"):
                cfg = api.NomadConfig(
                    k=4, lam=0.01, epochs=3, p=p, kernel=impl,
                    schedule=spec, schedule_seed=2,
                    stepsize=PowerSchedule(alpha=0.05, beta=0.02))
                loop = api.solve(problem, dataclasses.replace(
                    cfg, dispatch="loop"), mesh=mesh)
                fused = api.solve(problem, cfg, mesh=mesh)
                assert np.array_equal(loop.W, fused.W), (impl, spec)
                assert np.array_equal(loop.H, fused.H), (impl, spec)
                assert loop.trace == fused.trace, (impl, spec)
        # the pipelined sub-block path shares the fused driver too
        cfg = api.NomadConfig(k=4, lam=0.01, epochs=2, p=p,
                              kernel="xla", sub_blocks=2,
                              stepsize=PowerSchedule(alpha=0.05,
                                                     beta=0.02))
        loop = api.solve(problem, dataclasses.replace(cfg,
                                                      dispatch="loop"),
                         mesh=mesh)
        fused = api.solve(problem, cfg, mesh=mesh)
        assert np.array_equal(loop.W, fused.W)
        assert loop.trace == fused.trace
        print("spmd fused == spmd loop, bitwise")
    """, n_dev=4)


def test_shard_map_moe_matches_local():
    run_sub("""
        import dataclasses
        from repro import configs
        from repro.models import moe
        from repro.distributed.sharding import make_ctx
        from repro.launch.mesh import make_test_mesh
        cfg = dataclasses.replace(
            configs.get_smoke_config("qwen3_moe_30b_a3b"),
            capacity_factor=8.0)
        mesh = make_test_mesh(2, 4)
        ctx = make_ctx(mesh)
        p = moe.moe_init(jax.random.key(0), cfg, jnp.float32)
        x = jnp.asarray(np.random.default_rng(0).normal(
            size=(4, 8, cfg.d_model)), jnp.float32)
        out_local, aux_l = moe.moe_apply(p, x, cfg, None)
        out_spmd, aux_s = jax.jit(
            lambda pp, xx: moe.moe_apply(pp, xx, cfg, ctx))(p, x)
        np.testing.assert_allclose(np.asarray(out_spmd),
                                   np.asarray(out_local),
                                   rtol=2e-4, atol=2e-5)
        # aux_loss is a nonlinear statistic of each dp shard's token
        # subset, so the pmean differs from the global value by O(1/T_loc)
        np.testing.assert_allclose(float(aux_s["aux_loss"]),
                                   float(aux_l["aux_loss"]),
                                   rtol=0.3, atol=0.1)
        print("shard_map moe == local")
    """)


def test_sharded_train_step_matches_single_device():
    run_sub("""
        import dataclasses
        from repro import configs
        from repro.distributed.sharding import make_ctx
        from repro.launch import specs
        from repro.launch.mesh import make_test_mesh
        from repro.launch.train import make_train_step, init_state
        from repro.optim.adamw import AdamWConfig
        cfg = configs.get_smoke_config("qwen2_5_32b")
        opt_cfg = AdamWConfig(lr=1e-3)
        rng = np.random.default_rng(0)
        batch = {
            "inputs": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)),
                                  jnp.int32),
            "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)),
                                  jnp.int32)}
        state = init_state(jax.random.key(0), cfg, opt_cfg)

        s1, m1 = jax.jit(make_train_step(cfg, None, opt_cfg))(state, batch)

        mesh = make_test_mesh(2, 4)
        ctx = make_ctx(mesh)
        s2, m2 = jax.jit(make_train_step(cfg, ctx, opt_cfg))(state, batch)
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3, (
            float(m1["loss"]), float(m2["loss"]))
        for a, b in zip(jax.tree.leaves(s1["params"]),
                        jax.tree.leaves(s2["params"])):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=5e-3, atol=5e-4)
        print("sharded train step == single device")
    """)


def test_dryrun_production_meshes_tiny_arch():
    """The real dryrun entry point, on the real 16x16 and 2x16x16 meshes
    (512 host devices), with a reduced arch injected for speed."""
    run_sub("""
        from repro.launch import dryrun
        from repro import configs
        import repro.launch.specs as specs
        mesh = dryrun.build_mesh(multi_pod=True)
        assert mesh.shape == {"pod": 2, "data": 16, "model": 16}
        cfg = configs.get_smoke_config("qwen2_5_32b")
        import dataclasses
        cfg = dataclasses.replace(cfg, dtype="bfloat16",
                                  vocab_size=1024, remat=True)
        shape = dict(seq_len=256, global_batch=64, kind="train")
        lowered, _ = dryrun.lower_cell(cfg, shape, mesh)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes > 0
        cost = compiled.cost_analysis()
        assert cost.get("flops", 0) > 0
        print("multi-pod dryrun ok:", int(mem.temp_size_in_bytes / 1e6),
              "MB temp")
    """, n_dev=512, timeout=560)


def test_manual_tp_collectives_match_gspmd():
    """The §Perf C1/C2 paths (bf16-psum row-parallel matmuls, vocab-
    parallel embedding, 2D-TP decode) must be numerically equivalent to
    the GSPMD baseline."""
    run_sub("""
        import dataclasses
        from repro import configs
        from repro.distributed.sharding import make_ctx
        from repro.launch.mesh import make_test_mesh
        from repro.launch.train import make_train_step, init_state
        from repro.launch.serve import make_decode_step
        from repro.models import transformer as T
        from repro.optim.adamw import AdamWConfig

        cfg_g = configs.get_smoke_config("qwen2_5_32b")
        cfg_m = dataclasses.replace(cfg_g, tp_collectives="manual")
        opt_cfg = AdamWConfig(lr=1e-3)
        rng = np.random.default_rng(0)
        batch = {"inputs": jnp.asarray(
                     rng.integers(0, cfg_g.vocab_size, (4, 16)), jnp.int32),
                 "labels": jnp.asarray(
                     rng.integers(0, cfg_g.vocab_size, (4, 16)), jnp.int32)}
        state = init_state(jax.random.key(0), cfg_g, opt_cfg)
        mesh = make_test_mesh(2, 4)
        ctx = make_ctx(mesh)
        s1, m1 = jax.jit(make_train_step(cfg_g, ctx, opt_cfg))(state, batch)
        s2, m2 = jax.jit(make_train_step(cfg_m, ctx, opt_cfg))(state, batch)
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4
        for a, b in zip(jax.tree.leaves(s1["params"]),
                        jax.tree.leaves(s2["params"])):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=5e-3, atol=1e-3)
        params = s1["params"]
        tok = batch["inputs"][:, :1]
        lg, _ = jax.jit(make_decode_step(cfg_g, ctx))(
            params, {"inputs": tok}, T.init_cache(cfg_g, 4, 32),
            jnp.int32(0))
        lm, _ = jax.jit(make_decode_step(cfg_m, ctx))(
            params, {"inputs": tok}, T.init_cache(cfg_m, 4, 32),
            jnp.int32(0))
        np.testing.assert_allclose(np.asarray(lg, np.float32),
                                   np.asarray(lm, np.float32),
                                   rtol=1e-3, atol=1e-4)
        print("manual TP == gspmd (train + decode)")
    """)


def test_decode_flash_lse_combination_is_exact():
    """Seq-sharded decode attention == single-device decode attention."""
    run_sub("""
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.models.attention import decode_attention
        from repro.launch.mesh import make_test_mesh
        rng = np.random.default_rng(0)
        B, Hq, Hkv, S, D = 2, 4, 2, 64, 16
        q = jnp.asarray(rng.normal(size=(B, Hq, D)) * 0.5, jnp.float32)
        kc = jnp.asarray(rng.normal(size=(B, S, Hkv, D)) * 0.5, jnp.float32)
        vc = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
        ref = decode_attention(q, kc, vc, 47)

        mesh = make_test_mesh(1, 8)
        sh = NamedSharding(mesh, P(None, "model", None, None))
        kc_s = jax.device_put(kc, sh)
        vc_s = jax.device_put(vc, sh)
        out = jax.jit(decode_attention, static_argnums=())(
            q, kc_s, vc_s, 47)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        print("seq-sharded flash-decode exact")
    """)
