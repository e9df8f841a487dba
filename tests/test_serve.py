"""Serving tier: top-k exactness, hot-swap atomicity, microbatching.

The two contracts the subsystem stands on (DESIGN.md §11):

* **Exactness** — both top-k scorer implementations (XLA scan and the
  Pallas tile kernel) select exactly over the scores they compute.
  Against the float64 dense argsort oracle that means: bitwise for
  integer-valued factors, *including engineered score ties* (resolved
  to the smaller item id, deterministically), and within the f32 dot
  product's error bound (``tolerance.assert_topk_within_bound``) for
  general floats, whose summation order depends on shape and backend.
* **Atomicity** — queries racing a publisher always score against one
  consistent factor version: scores entirely from version v or v+1,
  never a mix, with the response's version stamp vouching for which.
"""
import threading
import time

import numpy as np
import pytest
import strategies
import tolerance as tol
from hypothesis_compat import given, settings

from repro.kernels.policy import KernelPolicy
from repro.serve import (FactorStore, FactorView, RecServer, ServeConfig,
                         topk_dense_oracle, topk_scores)


def _check_exact(seed, users, items, k_rank, k_top, item_tile, ties, impl):
    W_u, H = strategies.topk_case(seed, users, items, k_rank, ties)
    k_top = min(k_top, items)
    s, i = topk_scores(W_u, H, k_top, policy=impl, item_tile=item_tile)
    if not ties:
        tol.assert_topk_within_bound(i, s, W_u, H)
        return
    es, ei = topk_dense_oracle(W_u, H, k_top)
    np.testing.assert_array_equal(np.asarray(i), ei)
    np.testing.assert_array_equal(np.asarray(s), es)


# --------------------------------------------------------------------- #
# Top-k exactness vs the dense oracle                                    #
# --------------------------------------------------------------------- #

@settings(max_examples=40, deadline=None)
@given(**strategies.TOPK)
def test_topk_matches_oracle_property(seed, users, items, k_rank, k_top,
                                      item_tile, ties, impl):
    _check_exact(seed, users, items, k_rank, k_top, item_tile, ties, impl)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("seed,users,items,k_rank,k_top,item_tile,ties", [
    (0, 4, 64, 8, 10, 16, False),       # tile divides catalog
    (1, 4, 53, 8, 10, 16, False),       # ragged last tile
    (2, 1, 7, 1, 7, 4, False),          # k_top == catalog
    (3, 8, 40, 16, 1, 64, False),       # single tile covers all
    (4, 6, 60, 3, 12, 16, True),        # engineered ties
    (5, 5, 33, 4, 33, 8, True),         # ties + full-catalog k_top
])
def test_topk_matches_oracle_seeded(seed, users, items, k_rank, k_top,
                                    item_tile, ties, impl):
    _check_exact(seed, users, items, k_rank, k_top, item_tile, ties, impl)


def test_topk_tie_break_is_smaller_id():
    """All-equal scores: the top-k must be items [0..k) in order."""
    W_u = np.ones((3, 4), np.float32)
    H = np.ones((20, 4), np.float32)
    for impl in ("xla", "pallas"):
        s, i = topk_scores(W_u, H, 5, policy=impl, item_tile=8)
        np.testing.assert_array_equal(
            np.asarray(i), np.tile(np.arange(5, dtype=np.int32), (3, 1)))
        np.testing.assert_array_equal(np.asarray(s),
                                      np.full((3, 5), 4, np.float32))


def test_topk_validates():
    W_u = np.ones((2, 4), np.float32)
    H = np.ones((10, 4), np.float32)
    with pytest.raises(ValueError, match="k_top"):
        topk_scores(W_u, H, 0)
    with pytest.raises(ValueError, match="k_top"):
        topk_scores(W_u, H, 11)
    with pytest.raises(ValueError, match="item_tile"):
        topk_scores(W_u, H, 3, item_tile=0)
    with pytest.raises(ValueError, match="rank mismatch"):
        topk_scores(W_u, np.ones((10, 5), np.float32), 3)


def test_serve_impl_policy_mapping():
    from repro.kernels.ops import on_tpu
    assert KernelPolicy.coerce("xla").serve_impl == "xla"
    assert KernelPolicy.coerce("wave").serve_impl == "xla"
    assert KernelPolicy.coerce("pallas").serve_impl == "pallas"
    assert KernelPolicy.coerce("wave_pallas").serve_impl == "pallas"
    assert KernelPolicy.coerce("auto").serve_impl == \
        ("pallas" if on_tpu() else "xla")


# --------------------------------------------------------------------- #
# FactorStore: versions, catalog maps, boot                              #
# --------------------------------------------------------------------- #

def _wh(m, n, k=4, fill=1.0):
    return (np.full((m, k), fill, np.float32),
            np.full((n, k), fill, np.float32))


def test_store_versions_are_monotone():
    store = FactorStore()
    with pytest.raises(RuntimeError, match="no published factors"):
        store.view()
    assert store.version is None
    for v in range(5):
        view = store.publish(*_wh(6, 3))
        assert view.version == v == store.version
    assert store.view().m == 6 and store.view().n == 3


def test_store_publish_validates():
    store = FactorStore()
    with pytest.raises(ValueError, match="W and H"):
        store.publish(np.ones((4, 3), np.float32),
                      np.ones((5, 2), np.float32))
    with pytest.raises(ValueError, match="W and H"):
        store.publish(np.ones(4, np.float32), np.ones((5, 4), np.float32))


def test_view_pins_its_version_across_publishes():
    """A reader holding a view keeps scoring the same factors no matter
    how many publishes happen meanwhile (the in-flight-query guarantee,
    stronger than the two-slot cycle alone)."""
    store = FactorStore()
    store.publish(*_wh(4, 3, fill=1.0))
    pinned = store.view()
    for v in range(1, 5):
        store.publish(*_wh(4, 3, fill=float(v + 1)))
    assert pinned.version == 0
    np.testing.assert_array_equal(np.asarray(pinned.W),
                                  np.ones((4, 4), np.float32))
    assert store.view().version == 4


def test_catalog_maps_translate_and_reject():
    W, H = _wh(3, 4)
    view = FactorView(version=0, W=W, H=H,
                      user_ids=np.array([30, 10, 20]),
                      item_ids=np.array([7, 5, 6, 9]))
    np.testing.assert_array_equal(view.user_rows([10, 30, 20]), [1, 0, 2])
    with pytest.raises(KeyError, match="99"):
        view.user_rows([10, 99])
    np.testing.assert_array_equal(view.item_catalog(np.array([2, 0])),
                                  [6, 7])
    # identity default: out-of-range users are unknown, rows pass through
    plain = FactorView(version=0, W=W, H=H)
    np.testing.assert_array_equal(plain.user_rows([2, 0]), [2, 0])
    with pytest.raises(KeyError):
        plain.user_rows([3])
    with pytest.raises(ValueError, match="shape"):
        FactorView(version=0, W=W, H=H, user_ids=np.array([1, 2]))
    with pytest.raises(ValueError, match="duplicate"):
        FactorView(version=0, W=W, H=H, user_ids=np.array([1, 1, 2]))


# --------------------------------------------------------------------- #
# Hot-swap atomicity                                                     #
# --------------------------------------------------------------------- #

def test_hot_swap_atomicity_under_concurrent_publisher():
    """Readers racing a publisher never see mixed versions.  Version v
    publishes constant factors scoring k * (v+1) for *every* (user,
    item) pair — so a single torn element anywhere in a response's
    score matrix would betray itself, and the stamp must vouch for the
    one version the whole response came from."""
    k, m, n = 4, 8, 16
    store = FactorStore()
    store.publish(*_wh(m, n, fill=1.0))
    server = RecServer(store, ServeConfig(top_k=3, max_batch=8,
                                          max_wait_ms=0.5))
    stop = threading.Event()
    failures = []

    def publisher():
        v = 1
        while not stop.is_set():
            W = np.full((m, k), 1.0, np.float32)
            H = np.full((n, k), float(v + 1), np.float32)
            store.publish(W, H)
            v += 1
            time.sleep(0.001)

    def client(cseed):
        rng = np.random.default_rng(cseed)
        for _ in range(60):
            rec = server.recommend(rng.integers(0, m, 2))
            expect = k * (rec.version + 1.0)
            if not np.all(rec.scores == expect):
                failures.append((rec.version, rec.scores.copy()))

    pub = threading.Thread(target=publisher, daemon=True)
    with server:
        pub.start()
        clients = [threading.Thread(target=client, args=(c,))
                   for c in range(4)]
        for t in clients:
            t.start()
        for t in clients:
            t.join()
        stop.set()
        pub.join()
    assert not failures, f"mixed-version responses: {failures[:3]}"
    assert store.version > 0          # the race actually happened


def test_session_subscribe_publishes_each_round(tiny_mc_problem):
    from repro import api
    pr = tiny_mc_problem
    rows, cols, vals = pr["train"]
    problem = api.MCProblem(rows=rows, cols=cols, vals=vals, m=pr["m"],
                            n=pr["n"], test=pr["test"])
    sess = api.StreamingSession(problem,
                                api.NomadConfig(k=pr["k"], p=2, epochs=1))
    store = FactorStore()
    cb = store.attach(sess)
    res = sess.fit()
    assert store.version == 0
    np.testing.assert_array_equal(np.asarray(store.view().W), res.W)
    res2 = sess.arrive(rows=[0, 1], cols=[0, 1], vals=[0.5, -0.5],
                       m_new=2, epochs=1)
    assert store.version == 1
    assert store.view().m == pr["m"] + 2
    np.testing.assert_array_equal(np.asarray(store.view().W), res2.W)
    sess.unsubscribe(cb)
    sess.fit()
    assert store.version == 1          # detached: no further publishes
    with pytest.raises(TypeError, match="callable"):
        sess.subscribe("not-a-callback")


def test_session_warm_start_round_matches_inline(tiny_mc_problem):
    """A warm_start session (the checkpoint-boot serving path) continues
    bitwise where an in-process session would: its first arrive equals
    the same arrive on the session that trained the factors."""
    from repro import api
    pr = tiny_mc_problem
    rows, cols, vals = pr["train"]
    problem = api.MCProblem(rows=rows, cols=cols, vals=vals, m=pr["m"],
                            n=pr["n"], test=pr["test"])
    cfg = api.NomadConfig(k=pr["k"], p=2, epochs=1, seed=3)
    inline = api.StreamingSession(problem, cfg)
    res = inline.fit()
    batch = dict(rows=[1, 2], cols=[3, 4], vals=[0.3, -0.2], epochs=1)
    a = inline.arrive(**batch)
    warm = api.StreamingSession(problem, cfg, warm_start=res)
    b = warm.arrive(**batch)
    np.testing.assert_array_equal(a.W, b.W)
    np.testing.assert_array_equal(a.H, b.H)
    with pytest.raises(TypeError, match="warm_start"):
        api.StreamingSession(problem, cfg, warm_start="nope")


# --------------------------------------------------------------------- #
# RecServer: microbatching front end                                     #
# --------------------------------------------------------------------- #

def _rand_store(m=20, n=12, k=4, seed=0):
    rng = np.random.default_rng(seed)
    store = FactorStore()
    store.publish(rng.normal(size=(m, k)).astype(np.float32),
                  rng.normal(size=(n, k)).astype(np.float32))
    return store


def test_server_answers_match_sync_score():
    """Microbatched answers agree with the synchronous scorer.  The two
    run different batch shapes, so both are held to the dense bound and
    their scores to each other within the sum of the bounds."""
    store = _rand_store()
    server = RecServer(store, ServeConfig(top_k=5, max_batch=8,
                                          max_wait_ms=1.0, item_tile=4))
    with server:
        futs = [server.submit([u, (u + 3) % 20]) for u in range(10)]
        recs = [f.result(timeout=30) for f in futs]
    oracle = server.score(np.arange(20))
    W = np.asarray(store.view().W)
    H = np.asarray(store.view().H)
    tol.assert_topk_within_bound(oracle.items, oracle.scores, W, H)
    bound = tol.dot_error_bound(W, H)
    for u0, rec in enumerate(recs):
        assert rec.version == 0
        users = [u0, (u0 + 3) % 20]
        tol.assert_topk_within_bound(rec.items, rec.scores, W[users], H)
        for j, u in enumerate(users):
            slack = bound[u, rec.items[j]] + bound[u, oracle.items[u]]
            assert np.all(np.abs(rec.scores[j] - oracle.scores[u])
                          <= slack)
    assert server.n_queries == 20
    # the batching window must have merged at least some requests
    assert server.n_batches <= 10


def test_server_request_validation():
    store = _rand_store()
    server = RecServer(store, ServeConfig(top_k=3, max_batch=4))
    with pytest.raises(RuntimeError, match="not started"):
        server.submit([1])
    with server:
        with pytest.raises(ValueError, match="empty"):
            server.submit([])
        with pytest.raises(ValueError, match="max_batch"):
            server.submit([0, 1, 2, 3, 4])
        fut = server.submit([0, 19])
        assert fut.result(timeout=30).items.shape == (2, 3)
        # unknown user: the future carries the error, server survives
        with pytest.raises(KeyError):
            server.recommend([99], timeout=30)
        assert server.recommend([0], timeout=30).version == 0
    with pytest.raises(RuntimeError, match="already started"):
        with server:
            server.start()


def test_server_topk_clamped_to_catalog():
    store = _rand_store(n=3)
    server = RecServer(store, ServeConfig(top_k=10))
    with server:
        rec = server.recommend([0])
    assert rec.items.shape == (1, 3)    # catalog smaller than top_k


def test_serve_config_validates():
    for bad in (dict(top_k=0), dict(max_batch=0), dict(max_wait_ms=-1),
                dict(item_tile=0)):
        with pytest.raises(ValueError):
            ServeConfig(**bad)
    assert isinstance(ServeConfig(kernel="wave").kernel, KernelPolicy)


def test_server_growth_exposes_new_users(tiny_mc_problem):
    """End to end: train -> serve -> partial_fit with user growth; the
    new version serves users the old one rejects, while a pinned old
    view still rejects them (maps are per-version)."""
    from repro import api
    pr = tiny_mc_problem
    rows, cols, vals = pr["train"]
    problem = api.MCProblem(rows=rows, cols=cols, vals=vals, m=pr["m"],
                            n=pr["n"], test=pr["test"])
    sess = api.StreamingSession(problem,
                                api.NomadConfig(k=pr["k"], p=2, epochs=1))
    store = FactorStore.from_fit_result(sess.fit())
    server = RecServer(store, ServeConfig(top_k=3))
    new_user = pr["m"]                  # first id past the trained range
    with server:
        old = store.view()
        with pytest.raises(KeyError):
            server.recommend([new_user], timeout=30)
        sess.subscribe(store.publish_result)
        sess.arrive(rows=[new_user], cols=[0], vals=[1.0], m_new=1,
                    epochs=1)
        rec = server.recommend([new_user], timeout=30)
        assert rec.version == 1 and rec.items.shape == (1, 3)
        with pytest.raises(KeyError):
            server.score([new_user], view=old)


# --------------------------------------------------------------------- #
# exact candidate filtering (already-rated exclusion)                    #
# --------------------------------------------------------------------- #

def _filtered_oracle(W_u, H, k_top, exclude):
    """Dense argsort oracle with exclusions, same deterministic
    smaller-id tie rule as topk_dense_oracle."""
    scores = np.asarray(W_u, np.float32) @ np.asarray(H, np.float32).T
    n = H.shape[0]
    out_i = np.full((len(W_u), k_top), n, np.int32)
    out_s = np.full((len(W_u), k_top), -np.inf, np.float32)
    for u in range(len(W_u)):
        sc = scores[u].copy()
        if len(exclude[u]):
            sc[np.asarray(exclude[u], np.int64)] = -np.inf
        order = np.argsort(-sc, kind="stable")     # ties -> smaller id
        order = order[sc[order] > -np.inf][:k_top]
        out_i[u, :len(order)] = order
        out_s[u, :len(order)] = sc[order]
    return out_s, out_i


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("seed,ties", [(0, False), (1, True), (2, True)])
def test_topk_filtered_matches_dense_oracle(seed, ties, impl):
    from repro.serve import topk_scores_filtered
    rng = np.random.default_rng(seed)
    W_u, H = strategies.topk_case(seed, 12, 40, 6, ties)
    exclude = [rng.choice(40, size=rng.integers(0, 15), replace=False)
               for _ in range(12)]
    s, i = topk_scores_filtered(W_u, H, 6, exclude=exclude, policy=impl,
                                item_tile=16)
    if not ties:
        # each user's answer is the bounded top-k of its admissible items
        for u in range(12):
            keep = np.setdiff1d(np.arange(40), exclude[u])
            pos = np.searchsorted(keep, np.asarray(i)[u])
            assert np.array_equal(keep[pos], np.asarray(i)[u])
            tol.assert_topk_within_bound(pos[None], np.asarray(s)[u][None],
                                         W_u[u:u + 1], H[keep])
        return
    es, ei = _filtered_oracle(W_u, H, 6, exclude)
    np.testing.assert_array_equal(np.asarray(i), ei)
    np.testing.assert_array_equal(np.asarray(s), es)


def test_topk_filtered_exhausted_user_pads_with_sentinel():
    """A user whose exclusions leave fewer than k_top admissible items
    pads with the sentinel id n / -inf score."""
    from repro.serve import topk_scores_filtered
    W_u, H = strategies.topk_case(4, 3, 8, 4, False)
    exclude = [np.arange(6), np.array([], np.int64), np.arange(8)]
    s, i = topk_scores_filtered(W_u, H, 4, exclude=exclude, policy="xla",
                                item_tile=4)
    assert np.all(np.asarray(i)[0, 2:] == 8)       # only 2 admissible
    assert np.all(np.isneginf(np.asarray(s)[0, 2:]))
    assert np.all(np.asarray(i)[1] < 8)            # unfiltered user full
    assert np.all(np.asarray(i)[2] == 8)           # fully rated user
    es, ei = _filtered_oracle(W_u, H, 4, exclude)
    np.testing.assert_array_equal(np.asarray(i), ei)


def test_server_filter_rated_excludes_published_map(tiny_mc_problem):
    """publish(rated=...) + ServeConfig(filter_rated=True): no user is
    ever recommended an item they already rated, and the survivors
    match the filtered dense oracle exactly."""
    rng = np.random.default_rng(9)
    m, n, k = 30, 50, 6
    W = rng.normal(size=(m, k)).astype(np.float32)
    H = rng.normal(size=(n, k)).astype(np.float32)
    u_rows = rng.integers(0, m, 300)
    i_rows = rng.integers(0, n, 300)
    store = FactorStore()
    view = store.publish(W, H, rated=(u_rows, i_rows))
    srv = RecServer(store, ServeConfig(top_k=5, filter_rated=True,
                                       item_tile=16))
    users = [0, 7, 19]
    rec = srv.score(users)
    exclude = [np.unique(i_rows[u_rows == u]) for u in users]
    es, ei = _filtered_oracle(W[users], H, 5, exclude)
    np.testing.assert_array_equal(rec.items, ei)   # identity catalogs
    for j, u in enumerate(users):
        assert not set(rec.items[j].tolist()) & set(exclude[j].tolist())
    # filter off on the same store: rated items come back
    plain = RecServer(store, ServeConfig(top_k=5, item_tile=16)).score(users)
    assert any(set(plain.items[j].tolist()) & set(exclude[j].tolist())
               for j in range(len(users)))


def test_view_rated_csr_validates():
    rng = np.random.default_rng(0)
    W = rng.normal(size=(4, 3)).astype(np.float32)
    H = rng.normal(size=(6, 3)).astype(np.float32)
    store = FactorStore()
    view = store.publish(W, H, rated=(np.array([0, 0, 2]),
                                      np.array([1, 5, 3])))
    assert [a.tolist() for a in view.rated_for(np.arange(4))] == \
        [[1, 5], [], [3], []]
    with pytest.raises(ValueError, match="rated"):
        FactorView(W=view.W, H=view.H, version=1,
                   rated_indptr=np.array([0, 1]), rated_items=None)


# --------------------------------------------------------------------- #
# int8 quantized publish + scoring                                       #
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_quantized_publish_scores_exactly(impl):
    """publish(quantize='int8') + RecServer.score must select exactly
    what the quantized dense scores select, up to the f32 score bound:
    dequantized user rows against int8 H with the per-row scale applied
    after the dot (scale-after-sum)."""
    from repro.serve import quantize_int8
    rng = np.random.default_rng(3)
    m, n, k = 10, 33, 5
    W = rng.normal(size=(m, k)).astype(np.float32) * 2
    H = rng.normal(size=(n, k)).astype(np.float32)
    store = FactorStore()
    view = store.publish(W, H, quantize="int8")
    assert view.quantized and str(np.asarray(view.H).dtype) == "int8"
    srv = RecServer(store, ServeConfig(top_k=4, item_tile=8, kernel=impl))
    rec = srv.score(np.arange(m))
    Wq, sw = quantize_int8(W)
    Hq, sh = quantize_int8(H)
    Wdq = Wq.astype(np.float32) * sw[:, None]
    tol.assert_topk_within_bound(rec.items, rec.scores, Wdq, Hq,
                                 h_scale=sh)


def test_quantize_int8_contract():
    from repro.serve import quantize_int8
    A = np.array([[0.0, 0.0], [1.0, -2.0], [127.5, 0.5]], np.float32)
    q, s = quantize_int8(A)
    assert q.dtype == np.int8 and s.dtype == np.float32
    assert np.all(q[0] == 0) and s[0] == 1.0       # zero row: scale guard
    assert np.max(np.abs(q), axis=1).tolist() == [0, 127, 127]
    np.testing.assert_allclose(q.astype(np.float32) * s[:, None], A,
                               atol=np.max(np.abs(A)) / 254 + 1e-7)


# --------------------------------------------------------------------- #
# Integrity layer (DESIGN.md §14): request deadlines + publish guard     #
# --------------------------------------------------------------------- #

def test_serve_timeout_config_validates():
    import dataclasses as _dc
    with pytest.raises(ValueError):
        ServeConfig(timeout_ms=0)
    with pytest.raises(ValueError):
        ServeConfig(timeout_ms=-5.0)
    assert ServeConfig().timeout_ms is None
    assert _dc.replace(ServeConfig(), timeout_ms=50.0).timeout_ms == 50.0


def test_expired_request_is_shed_with_typed_error():
    """A request that out-waits timeout_ms in the queue fails fast with
    ServeTimeout instead of being served stale."""
    import time as _time

    from repro.serve import ServeTimeout
    store = _rand_store()
    srv = RecServer(store, ServeConfig(top_k=3, timeout_ms=0.001,
                                       max_wait_ms=0.0))
    with srv:
        _time.sleep(0.01)           # let the worker block on get()
        fut = srv.submit([1, 2])
        with pytest.raises(ServeTimeout):
            fut.result(timeout=5)
        assert srv.n_shed == 2
    # generous deadline: everything is served
    srv2 = RecServer(store, ServeConfig(top_k=3, timeout_ms=60_000.0))
    with srv2:
        rec = srv2.recommend([0, 1], timeout=30)
        assert rec.items.shape == (2, 3)
        assert srv2.n_shed == 0


def test_shed_request_never_counts_as_answered():
    import time as _time

    from repro.serve import ServeTimeout
    store = _rand_store()
    srv = RecServer(store, ServeConfig(top_k=3, timeout_ms=0.001,
                                       max_wait_ms=0.0))
    with srv:
        _time.sleep(0.01)
        fut = srv.submit([4])
        with pytest.raises(ServeTimeout):
            fut.result(timeout=5)
        assert srv.n_queries == 0 and srv.n_batches == 0


def test_publish_refuses_non_finite_factors():
    rng = np.random.default_rng(0)
    W = rng.normal(size=(10, 3)).astype(np.float32)
    H = rng.normal(size=(6, 3)).astype(np.float32)
    Wbad = W.copy()
    Wbad[2, 1] = np.nan
    Hbad = H.copy()
    Hbad[0, 0] = np.inf
    store = FactorStore()
    with pytest.raises(ValueError, match="non-finite W"):
        store.publish(Wbad, H)
    with pytest.raises(ValueError, match="non-finite H"):
        store.publish(W, Hbad)
    # a poisoned publish must not advance the version
    assert store.version is None
    store.publish(W, H)
    assert store.version == 0
    with pytest.raises(ValueError):
        store.publish(Wbad, H, quantize="int8")   # caught pre-quantize
    assert store.version == 0
