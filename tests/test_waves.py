"""Serializability and coverage properties of the conflict-free wave path.

The wave layout (DESIGN.md §3) must (a) be genuinely conflict-free — no
row or column repeated within a wave, (b) cover every rating exactly once,
and (c) execute the *same* serial ordering as the sequential oracle, so
``block_sgd_waves``/``nomad_sgd_waves_block`` match ``block_sgd_ref`` to
float32 tolerance.  Hypothesis drives the shapes where available; a
seed-parametrized subset always runs so the property is checked even
without hypothesis installed.
"""
import jax.numpy as jnp
import numpy as np
import pytest
import strategies
from hypothesis_compat import given, settings

from repro.core import partition as P
from repro.kernels import ref
from repro.kernels.nomad_sgd import nomad_sgd_waves_block


def _check_waves_match_ref(seed, m_t, n_t, k, nnz, pallas=False):
    rng = np.random.default_rng(seed)
    W, H, rows, cols, vals = strategies.random_cell(rng, m_t, n_t, k, nnz)
    pre = np.lexsort((rows, cols))           # pack()'s within-cell order
    order, wr, wc, wv, wm, _ = P.pack_cell_waves(
        rows[pre], cols[pre], vals[pre])
    seq = pre[order]                          # the shared serial ordering
    Wr, Hr = ref.block_sgd_ref(
        W, H, jnp.asarray(rows[seq], jnp.int32),
        jnp.asarray(cols[seq], jnp.int32), jnp.asarray(vals[seq]),
        jnp.ones(nnz, bool), 0.01, 0.05)
    args = (W, H, jnp.asarray(wr), jnp.asarray(wc), jnp.asarray(wv),
            jnp.asarray(wm), 0.01, 0.05)
    if pallas:
        Ww, Hw = nomad_sgd_waves_block(*args, wave_chunk=4, interpret=True)
    else:
        Ww, Hw = ref.block_sgd_waves(*args)
    np.testing.assert_allclose(Ww, Wr, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(Hw, Hr, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("seed,m_t,n_t,k,nnz", [
    (0, 16, 8, 4, 37),
    (1, 32, 16, 100, 200),    # k=100 -> lane padding in the Pallas variant
    (2, 64, 32, 8, 513),
    (3, 8, 8, 32, 1),
])
def test_block_sgd_waves_matches_sequential_oracle(seed, m_t, n_t, k, nnz):
    _check_waves_match_ref(seed, m_t, n_t, k, nnz, pallas=False)


@pytest.mark.parametrize("seed,m_t,n_t,k,nnz", [
    (0, 16, 8, 4, 37),
    (1, 32, 16, 100, 200),
])
def test_pallas_wave_kernel_matches_sequential_oracle(seed, m_t, n_t, k,
                                                      nnz):
    _check_waves_match_ref(seed, m_t, n_t, k, nnz, pallas=True)


@settings(max_examples=15, deadline=None)
@given(**strategies.WAVE_CELL)
def test_block_sgd_waves_property(seed, k, nnz):
    _check_waves_match_ref(seed, 24, 12, k, nnz, pallas=False)


def _check_pack_waves(seed, p, m, n, nnz, sub_blocks=1):
    rows, cols, vals = strategies.coo_problem(seed, m, n, nnz)
    br = P.pack(rows, cols, vals, m, n, p, sub_blocks=sub_blocks)

    # every rating appears exactly once across all waves of all cells
    wg = br.wave_gid
    assert np.array_equal(np.sort(wg[wg >= 0]), np.arange(nnz))
    assert np.array_equal(br.wave_mask, wg >= 0)
    for q in range(p):
        for s in range(p):
            for w in range(br.n_waves):
                msk = br.wave_mask[q, s, w]
                r = br.wave_rows[q, s, w][msk]
                c = br.wave_cols[q, s, w][msk]
                # conflict-free: no row or col repeated within a wave
                assert len(np.unique(r)) == len(r)
                assert len(np.unique(c)) == len(c)
            # the sequential arrays are stored wave-major: flattening the
            # wave layout reproduces the cell's serial gid order exactly
            g_seq = br.gid[q, s][br.mask[q, s]]
            g_wave = br.wave_gid[q, s][br.wave_mask[q, s]]
            assert np.array_equal(g_seq, g_wave)
    # wave_cnt agrees with the mask
    assert np.array_equal(br.wave_cnt, br.wave_mask.sum(axis=-1))


@pytest.mark.parametrize("seed,p,m,n,nnz,sub", [
    (0, 4, 40, 20, 300, 1),
    (1, 1, 30, 30, 500, 1),
    (2, 3, 25, 13, 150, 2),
    (3, 2, 60, 8, 400, 1),   # skinny: col degrees dominate wave count
])
def test_pack_wave_layout_is_conflict_free_partition(seed, p, m, n, nnz,
                                                     sub):
    _check_pack_waves(seed, p, m, n, nnz, sub_blocks=sub)


@settings(max_examples=15, deadline=None)
@given(**strategies.PACK_SHAPE)
def test_pack_wave_layout_property(seed, p, m, n, nnz, sub):
    # more sub-blocks than a worker's item block has columns is refused
    rows, cols, vals = strategies.coo_problem(seed, m, n, nnz)
    n_local = P.pack(rows, cols, vals, m, n, p).n_local
    if sub > 1 and n_local // sub == 0:
        with pytest.raises(ValueError, match="sub_blocks"):
            P.pack(rows, cols, vals, m, n, p, sub_blocks=sub)
        return
    _check_pack_waves(seed, p, m, n, nnz, sub_blocks=sub)


def test_sub_block_partition_covers_cells_exactly():
    """sub_blocks>1 pre-partition: each cell's ratings appear exactly once
    across sub-blocks, with cols localized to [0, hi-lo)."""
    rng = np.random.default_rng(5)
    m, n, p, nnz, sub = 50, 24, 3, 600, 3
    rows = rng.integers(0, m, nnz)
    cols = rng.integers(0, n, nnz)
    br = P.pack(rows, cols, rng.normal(size=nnz), m, n, p, sub_blocks=sub)
    assert br.sub_nnz.sum() == nnz
    assert np.array_equal(br.sub_nnz, br.sub_mask.sum(axis=-1))
    for q in range(p):
        for s in range(p):
            assert br.sub_nnz[q, s].sum() == br.nnz_cell[q, s]
            for sbi in range(sub):
                msk = br.sub_mask[q, s, sbi]
                c = br.sub_cols[q, s, sbi][msk]
                lo, hi = br.sub_starts[sbi], br.sub_starts[sbi + 1]
                assert np.all(c >= 0) and np.all(c < hi - lo)


def test_wave_engine_matches_sequential_engine(tiny_mc_problem):
    """The ring engine under impl='wave' reproduces impl='xla' (same serial
    ordering, vectorized execution)."""
    from repro.core import nomad, objective
    from repro.core.stepsize import PowerSchedule
    pr = tiny_mc_problem
    rows, cols, vals = pr["train"]
    m, n, k = pr["m"], pr["n"], pr["k"]
    W0, H0 = objective.init_factors_np(0, m, n, k)
    br = P.pack(rows, cols, vals, m, n, 4)

    outs = {}
    for impl in ("xla", "wave"):
        eng = nomad.NomadRingEngine(
            br=br, k=k, lam=0.01,
            stepsize=PowerSchedule(alpha=0.02, beta=0.0), impl=impl)
        eng.init_factors(W0.astype(np.float32), H0.astype(np.float32))
        eng.run_epoch()
        eng.run_epoch()
        outs[impl] = eng.factors()
    np.testing.assert_allclose(outs["wave"][0], outs["xla"][0],
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(outs["wave"][1], outs["xla"][1],
                               rtol=2e-4, atol=2e-5)


def test_wave_engine_matches_serial_replay(tiny_mc_problem):
    """One wave epoch == serial replay of ring_order() — the wave path
    realizes exactly the packed serial linearization."""
    from repro.core import nomad, objective, serial
    from repro.core.stepsize import PowerSchedule
    pr = tiny_mc_problem
    rows, cols, vals = pr["train"]
    m, n, k = pr["m"], pr["n"], pr["k"]
    W0, H0 = objective.init_factors_np(0, m, n, k)
    W0f, H0f = W0.astype(np.float32), H0.astype(np.float32)
    br = P.pack(rows, cols, vals, m, n, 4)
    eng = nomad.NomadRingEngine(
        br=br, k=k, lam=0.01,
        stepsize=PowerSchedule(alpha=0.02, beta=0.0), impl="wave")
    eng.init_factors(W0f, H0f)
    eng.run_epoch()
    W1, H1 = eng.factors()
    Wr, Hr = serial.replay_jax(W0f, H0f, rows, cols, vals,
                               br.ring_order(), 0.02, 0.01)
    np.testing.assert_allclose(np.asarray(Wr), W1, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(Hr), H1, rtol=2e-5, atol=2e-6)


def test_wave_impl_requires_wave_layout():
    from repro.core import nomad
    from repro.core.stepsize import PowerSchedule
    rng = np.random.default_rng(0)
    br = P.pack(rng.integers(0, 10, 50), rng.integers(0, 6, 50),
                rng.normal(size=50), 10, 6, 2, waves=False)
    with pytest.raises(ValueError, match="wave layout"):
        nomad.NomadRingEngine(br=br, k=4, lam=0.01,
                              stepsize=PowerSchedule(), impl="wave")
