"""Fused on-device training driver (DESIGN.md §9): the fused dispatch
must be bitwise-equal to the per-epoch loop — W, H and trace — across
kernels, executors, schedules, trace cadences and program-block sizes;
warm starts must cross dispatch boundaries bitwise; buffer donation must
change nothing; and the engine's eval memo must key on array content,
not tuple identity."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import strategies
from hypothesis_compat import given, settings
from repro import api
from repro.core import nomad, objective
from repro.core import partition as part
from repro.core.stepsize import PowerSchedule


def _problem(seed=0, m=40, n=24, nnz=300, n_test=40):
    rows, cols, vals = strategies.coo_problem(seed, m, n, nnz)
    rng = np.random.default_rng((seed, 0xD12))
    test = (rng.integers(0, m, n_test), rng.integers(0, n, n_test),
            rng.normal(size=n_test))
    return api.MCProblem(rows=rows, cols=cols, vals=vals, m=m, n=n,
                        test=test)


def _cfg(**kw):
    base = dict(k=4, lam=0.01, epochs=3, p=4, seed=0,
                stepsize=PowerSchedule(alpha=0.05, beta=0.02))
    base.update(kw)
    return api.NomadConfig(**base)


def _assert_bitwise(a, b):
    assert np.array_equal(a.W, b.W)
    assert np.array_equal(a.H, b.H)
    assert a.trace == b.trace


# --------------------------------------------------------------------- #
# fused == loop, bitwise, across the kernel x schedule grid              #
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("impl", ["xla", "wave"])
@pytest.mark.parametrize("spec", ["ring", "random", "balanced"])
def test_fused_bitwise_equals_loop(impl, spec):
    problem = _problem()
    cfg = _cfg(kernel=impl, schedule=spec, schedule_seed=3)
    loop = api.solve(problem, dataclasses.replace(cfg, dispatch="loop"))
    fused = api.solve(problem, cfg)
    _assert_bitwise(loop, fused)


def test_fused_block_boundaries_are_bitwise():
    """Chunking the fused scan into fuse_epochs-sized device programs
    must not change anything: each block resumes the learning-rate array
    from epoch_idx exactly as one big program would."""
    problem = _problem(seed=1)
    cfg = _cfg(kernel="wave", epochs=5)
    loop = api.solve(problem, dataclasses.replace(cfg, dispatch="loop"))
    for fe in (1, 2, 3, None):
        fused = api.solve(problem, dataclasses.replace(cfg,
                                                       fuse_epochs=fe))
        _assert_bitwise(loop, fused)


def test_record_every_cadence_matches_and_always_records_final():
    """Both dispatches record every record_every-th epoch plus the final
    one; at record_every=1 that is the historical every-epoch trace."""
    problem = _problem(seed=2)
    for re_ in (1, 2, 3, 5):
        cfg = _cfg(kernel="xla", epochs=5, record_every=re_)
        loop = api.solve(problem, dataclasses.replace(cfg,
                                                      dispatch="loop"))
        fused = api.solve(problem, cfg)
        _assert_bitwise(loop, fused)
        want = sorted({e for e in range(1, 6) if e % re_ == 0} | {5})
        assert [e for e, _ in fused.trace] == want


def test_warm_start_crosses_dispatch_boundaries_bitwise():
    """Resuming a fused run with a loop run (and vice versa) mid-chain
    equals the uninterrupted run of either dispatch."""
    problem = _problem(seed=3)
    mk = lambda e, d: _cfg(kernel="wave", epochs=e, dispatch=d)
    full = api.solve(problem, mk(6, "loop"))
    for first, second in (("fused", "loop"), ("loop", "fused")):
        half = api.solve(problem, mk(3, first))
        resumed = api.solve(problem, mk(3, second), warm_start=half)
        assert np.array_equal(full.W, resumed.W)
        assert np.array_equal(full.H, resumed.H)
        assert half.trace + resumed.trace == full.trace
        assert resumed.epochs_done == 6


def test_steps_driver_matches_loop_too():
    """The step-scan fused fallback (the driver the Pallas impls use)
    must be bitwise-equal to the loop as well — it shares the epoch body
    by construction."""
    problem = _problem(seed=4)
    cfg = _cfg(kernel="xla", epochs=4)
    eng, _ = api._nomad_cold_start(problem, cfg, None, None)
    loop_tr = eng.train(4, test=problem.test, dispatch="loop")
    Wl, Hl = eng.factors()

    eng2, _ = api._nomad_cold_start(problem, cfg, None, None)
    lrs = jnp.asarray(cfg.stepsize.values(0, 4), jnp.float32)
    rec_pos = jnp.asarray(np.arange(4, dtype=np.int32))
    ridx, cidx, tvals = eng2._eval_args(problem.test)
    data = (*eng2._cell_data(), eng2._perm_src)
    Ws, Hs, tr, ok = nomad._local_train_steps(
        eng2.Ws, eng2.Hs, data, lrs, rec_pos, eng2.lam, ridx, cidx,
        tvals, policy=eng2.policy, entry=eng2._entry, n_rec=4)
    assert bool(ok)
    eng2.Ws, eng2.Hs = Ws, Hs
    Wf, Hf = eng2.factors()
    assert np.array_equal(Wl, Wf)
    assert np.array_equal(Hl, Hf)
    assert [r for _, r in loop_tr] == [float(x) for x in np.asarray(tr)]


@settings(max_examples=8, deadline=None)
@given(**strategies.DISPATCH)
def test_dispatch_equivalence_property(seed, p, impl, spec, record_every,
                                       fuse_epochs):
    problem = _problem(seed=seed, m=30, n=18, nnz=200, n_test=25)
    cfg = _cfg(p=p, kernel=impl, schedule=spec, schedule_seed=seed,
               record_every=record_every, fuse_epochs=fuse_epochs)
    loop = api.solve(problem, dataclasses.replace(
        cfg, dispatch="loop", fuse_epochs=None))
    fused = api.solve(problem, cfg)
    _assert_bitwise(loop, fused)


# --------------------------------------------------------------------- #
# the flattened epoch stream                                             #
# --------------------------------------------------------------------- #

def test_epoch_stream_slots_are_conflict_free_and_complete():
    """Every stream slot's active entries touch pairwise-distinct global
    rows and columns (what makes the batched slot exactly sequential),
    and the stream covers every rating exactly once in schedule order."""
    problem = _problem(seed=5, m=30, n=20, nnz=250)
    br = problem.packed(4, waves=True, schedule="random", schedule_seed=1)
    R, C, V, M = part.epoch_stream(br)
    for t in range(R.shape[0]):
        act = M[t]
        assert len(np.unique(R[t][act])) == act.sum()
        assert len(np.unique(C[t][act])) == act.sum()
    # value multiset: each rating's value appears exactly as often as in
    # the packed cells (stream = reordering of the same real entries)
    assert sorted(V[M].tolist()) == sorted(br.vals[br.mask].tolist())
    assert M.sum() == br.mask.sum()


def _two_scatter_epoch(Ws, Hs, data, lr, lam, policy):
    """The stream epoch with W and H kept apart: per slot a gather and a
    drop-mode scatter of each (the slot body before the one table)."""
    from repro.kernels import ref as kref
    rows, cols, vals, mask = data
    p, _, k = Ws.shape
    Wf, Hf = Ws.reshape(-1, k), Hs.reshape(-1, k)
    P, Q = Wf.shape[0], Hf.shape[0]
    cd = policy.compute_dtype
    lr = jnp.asarray(lr, dtype=cd or Wf.dtype)
    lam = jnp.asarray(lam, dtype=cd or Wf.dtype)
    if policy.wave:
        pair = lambda *a: kref.sgd_pair_batch(*a, compute_dtype=cd)  # noqa: E731
    else:
        pair = jax.vmap(lambda *a: kref.sgd_pair(*a, compute_dtype=cd),
                        in_axes=(0, 0, 0, None, None))

    def slot(t, carry):
        Wf, Hf = carry
        r, c, v, m = (jax.lax.dynamic_slice_in_dim(a, t * p, p)
                      for a in (rows, cols, vals, mask))
        w_new, h_new = pair(Wf[r], Hf[c], v, lr, lam)
        return (Wf.at[jnp.where(m, r, P)].set(w_new, mode="drop"),
                Hf.at[jnp.where(m, c, Q)].set(h_new, mode="drop"))

    Wf, Hf = jax.lax.fori_loop(0, rows.shape[0] // p, slot, (Wf, Hf))
    return Wf.reshape(Ws.shape), Hf.reshape(Hs.shape)


@pytest.mark.parametrize("dtype_policy", ["fp32", "bf16"])
@pytest.mark.parametrize("impl", ["xla", "wave"])
def test_one_table_stream_epoch_equals_two_scatters(impl, dtype_policy):
    """The stream epoch's one factor table (one gather and one scatter
    of 2p rows per slot) is bitwise the slot body that gathers and
    scatters W and H apart, on a stream whose masked lanes read the
    rows and columns of live lanes in the same slot and carry values."""
    problem = _problem(seed=12)
    cfg = _cfg(kernel=impl, dtype_policy=dtype_policy)
    eng, _ = api._nomad_cold_start(problem, cfg, None, None)
    R, C, V, M = part.epoch_stream(eng.br)
    shadowed = 0
    for t in range(R.shape[0]):
        live, dead = np.flatnonzero(M[t]), np.flatnonzero(~M[t])
        if len(live) and len(dead):
            R[t, dead], C[t, dead] = R[t, live[0]], C[t, live[0]]
            V[t, dead] = 7.0
            shadowed += 1
    assert shadowed >= 3
    data = tuple(jnp.asarray(a.reshape(-1)) for a in (R, C, V, M))
    lr = jnp.asarray(0.05, jnp.float32)
    one = jax.jit(nomad._stream_epoch_body,
                  static_argnames=("policy", "entry"))(
        eng.Ws, eng.Hs, data, lr, eng.lam, policy=eng.policy, entry=None)
    two = jax.jit(_two_scatter_epoch, static_argnames=("policy",))(
        eng.Ws, eng.Hs, data, lr, eng.lam, policy=eng.policy)
    for a, b, before in zip(one, two, (eng.Ws, eng.Hs)):
        assert a.dtype == b.dtype == before.dtype
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))
        assert not np.array_equal(np.asarray(a, np.float32),
                                  np.asarray(before, np.float32))


def test_fused_accepts_call_only_stepsize():
    """A duck-typed __call__-only step-size schedule (no .values) that
    worked on the loop path keeps working — and stays bitwise — on the
    fused path."""
    class CallOnly:
        def __call__(self, t):
            return 0.05 / (1.0 + 0.02 * t)

    problem = _problem(seed=9)
    cfg = _cfg(kernel="xla", stepsize=None)
    loop = api.solve(problem, dataclasses.replace(cfg, dispatch="loop"))
    fused = api.solve(problem, cfg)
    _assert_bitwise(loop, fused)  # sanity on the default schedule
    eng, _ = api._nomad_cold_start(problem, cfg, None, None)
    eng.stepsize = CallOnly()
    fused_tr = eng.train(3, test=problem.test, dispatch="fused")
    eng2, _ = api._nomad_cold_start(problem, cfg, None, None)
    eng2.stepsize = CallOnly()
    loop_tr = eng2.train(3, test=problem.test, dispatch="loop")
    assert fused_tr == loop_tr
    W1, H1 = eng.factors()
    W2, H2 = eng2.factors()
    assert np.array_equal(W1, W2)
    assert np.array_equal(H1, H2)


def test_fused_dispatch_validation():
    with pytest.raises(ValueError, match="dispatch"):
        api.NomadConfig(dispatch="jit")
    with pytest.raises(ValueError, match="fuse_epochs"):
        api.NomadConfig(fuse_epochs=0)
    with pytest.raises(ValueError, match="record_every"):
        api.NomadConfig(record_every=0)


# --------------------------------------------------------------------- #
# donation is a bitwise no-op                                            #
# --------------------------------------------------------------------- #

def test_donated_epoch_jit_is_bitwise_noop():
    """The donated per-epoch jit must produce exactly what a fresh
    non-donating jit of the same body produces (donation only recycles
    buffers; on backends without support it is ignored)."""
    problem = _problem(seed=6)
    cfg = _cfg(kernel="wave", epochs=3)
    eng, _ = api._nomad_cold_start(problem, cfg, None, None)
    Ws0 = np.array(eng.Ws)
    Hs0 = np.array(eng.Hs)
    eng.train(3, test=problem.test, dispatch="loop")
    Wd, Hd = eng.factors()

    plain = jax.jit(nomad._local_epoch_body,
                    static_argnames=("policy",))
    Ws, Hs = jnp.asarray(Ws0), jnp.asarray(Hs0)
    rows, cols, vals, mask = eng._cell_data()
    for e in range(3):
        lr = jnp.asarray(cfg.stepsize(e), dtype=Ws.dtype)
        Ws, Hs = plain(Ws, Hs, rows, cols, vals, mask,
                       eng._perm_src, lr, eng.lam, policy=eng.policy,
                       entry=eng._entry)
    W, H = part.unshard_factors(np.asarray(Ws), np.asarray(Hs), eng.br)
    assert np.array_equal(Wd, W)
    assert np.array_equal(Hd, H)


# --------------------------------------------------------------------- #
# eval-args memo keys on content                                         #
# --------------------------------------------------------------------- #

def test_eval_args_memo_hits_on_equal_test_tuples():
    problem = _problem(seed=7)
    cfg = _cfg(kernel="xla")
    eng, _ = api._nomad_cold_start(problem, cfg, None, None)
    t = problem.test
    args = eng._eval_args(t)
    # a freshly-built tuple around the same arrays must hit
    assert eng._eval_args((t[0], t[1], t[2])) is args
    # freshly-built but equal arrays must hit too (StreamingSession
    # rebuilds its merged_test arrays every round)
    copies = tuple(np.array(a) for a in t)
    assert eng._eval_args(copies) is args
    # different content must miss
    other = (copies[0], copies[1], copies[2] + 1.0)
    new_args = eng._eval_args(other)
    assert new_args is not args
    # ... and the miss re-primes the memo for the new content: an
    # equal-content rebuild now hits the NEW device args object
    assert eng._eval_args(tuple(np.array(a) for a in other)) is new_args


def test_eval_args_memo_survives_engine_train_roundtrip():
    """train() -> eval_rmse on an equal tuple performs no re-upload (the
    memoized device args object is reused)."""
    problem = _problem(seed=8)
    cfg = _cfg(kernel="xla")
    eng, _ = api._nomad_cold_start(problem, cfg, None, None)
    eng.train(2, test=problem.test, dispatch="fused")
    args = eng._eval_cache[1]
    rebuilt = tuple(np.array(a) for a in problem.test)
    r = eng.eval_rmse(rebuilt)
    assert eng._eval_cache[1] is args
    assert r == pytest.approx(float(eng.eval_rmse(problem.test)))


# --------------------------------------------------------------------- #
# integration: streaming sessions run fused by default, bitwise          #
# --------------------------------------------------------------------- #

def test_streaming_session_fused_matches_loop_chain():
    base, script = strategies.arrival_script(11, 30, 20, 250, 2)
    test = (np.arange(5) % 30, np.arange(5) % 20, np.ones(5))
    mk = lambda d: _cfg(kernel="wave", epochs=2, dispatch=d)
    results = {}
    for d in ("loop", "fused"):
        problem = api.MCProblem(rows=base[0], cols=base[1], vals=base[2],
                                m=30, n=20, test=test)
        sess = api.StreamingSession(problem, mk(d))
        sess.fit()
        for b in script:
            res = sess.arrive(**b)
        results[d] = res
    _assert_bitwise(results["loop"], results["fused"])
