"""Per-kernel validation: Pallas (interpret mode) vs the pure-jnp oracle,
swept over shapes and dtypes, plus hypothesis property tests on the
kernel contracts.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.kernels import ref
from repro.kernels.nomad_sgd import nomad_sgd_block
from repro.kernels.flash_attn import flash_attention


def _mk_block(rng, m_t, n_t, k, nnz, dtype):
    W = jnp.asarray(rng.normal(size=(m_t, k)), dtype)
    H = jnp.asarray(rng.normal(size=(n_t, k)), dtype)
    rows = jnp.asarray(rng.integers(0, m_t, nnz), jnp.int32)
    cols = jnp.asarray(rng.integers(0, n_t, nnz), jnp.int32)
    vals = jnp.asarray(rng.normal(size=nnz), dtype)
    mask = jnp.asarray(rng.random(nnz) < 0.85)
    return W, H, rows, cols, vals, mask


@pytest.mark.parametrize("m_t,n_t,k,nnz,chunk", [
    (16, 8, 4, 37, 16),       # tiny, ragged tail chunk
    (32, 16, 100, 200, 64),   # k=100 -> exercises 128-lane padding
    (64, 32, 128, 513, 256),  # k already lane-aligned, odd nnz
    (8, 8, 32, 7, 1024),      # nnz < chunk
])
def test_nomad_sgd_kernel_matches_ref(m_t, n_t, k, nnz, chunk):
    rng = np.random.default_rng(k * 1000 + nnz)
    W, H, rows, cols, vals, mask = _mk_block(rng, m_t, n_t, k, nnz,
                                             jnp.float32)
    Wr, Hr = ref.block_sgd_ref(W, H, rows, cols, vals, mask, 0.01, 0.05)
    Wk, Hk = nomad_sgd_block(W, H, rows, cols, vals, mask, 0.01, 0.05,
                             chunk=chunk, interpret=True)
    np.testing.assert_allclose(Wk, Wr, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(Hk, Hr, rtol=2e-5, atol=2e-6)


def test_nomad_sgd_kernel_bf16():
    rng = np.random.default_rng(7)
    W, H, rows, cols, vals, mask = _mk_block(rng, 32, 16, 64, 128,
                                             jnp.bfloat16)
    Wr, Hr = ref.block_sgd_ref(W, H, rows, cols, vals, mask, 0.01, 0.05)
    Wk, Hk = nomad_sgd_block(W, H, rows, cols, vals, mask, 0.01, 0.05,
                             chunk=64, interpret=True)
    np.testing.assert_allclose(np.asarray(Wk, np.float32),
                               np.asarray(Wr, np.float32),
                               rtol=5e-2, atol=5e-2)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000),
       k=st.sampled_from([8, 32, 100]),
       nnz=st.integers(1, 300))
def test_nomad_sgd_kernel_property(seed, k, nnz):
    rng = np.random.default_rng(seed)
    W, H, rows, cols, vals, mask = _mk_block(rng, 24, 12, k, nnz,
                                             jnp.float32)
    # keep the trajectory convergent: with a tiny tile and many repeat
    # updates per row a large lr diverges and fp noise amplifies
    # unboundedly, which tests numerics of a regime nobody runs
    W, H = W * 0.3, H * 0.3
    lr = 0.005
    Wr, Hr = ref.block_sgd_ref(W, H, rows, cols, vals, mask, lr, 0.01)
    Wk, Hk = nomad_sgd_block(W, H, rows, cols, vals, mask, lr, 0.01,
                             chunk=128, interpret=True)
    np.testing.assert_allclose(Wk, Wr, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(Hk, Hr, rtol=1e-4, atol=1e-5)


def test_nomad_sgd_masked_entries_are_noops():
    rng = np.random.default_rng(3)
    W, H, rows, cols, vals, _ = _mk_block(rng, 16, 8, 16, 50, jnp.float32)
    mask = jnp.zeros(50, bool)
    Wk, Hk = nomad_sgd_block(W, H, rows, cols, vals, mask, 0.1, 0.1,
                             chunk=32, interpret=True)
    np.testing.assert_array_equal(Wk, W)
    np.testing.assert_array_equal(Hk, H)


# ------------------------------------------------------------------ #
# Flash attention kernel                                               #
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("B,Hq,Hkv,S,D,bq,bk,causal", [
    (1, 2, 1, 256, 64, 128, 128, True),
    (2, 4, 2, 256, 128, 64, 128, True),
    (1, 4, 4, 128, 128, 128, 128, False),   # MHA, non-causal
    (2, 8, 2, 512, 64, 256, 256, True),     # GQA group 4
])
def test_flash_attention_matches_dense(B, Hq, Hkv, S, D, bq, bk, causal):
    rng = np.random.default_rng(B * S + Hq)
    q = jnp.asarray(rng.normal(size=(B, Hq, S, D)) * 0.3, jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, D)) * 0.3, jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), jnp.float32)
    o_ref = ref.flash_attention_ref(q, k, v, causal=causal)
    o = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                        interpret=True)
    np.testing.assert_allclose(o, o_ref, rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.normal(size=(1, 2, 256, 64)) * 0.3, jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 2, 256, 64)) * 0.3, jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.bfloat16)
    o_ref = ref.flash_attention_ref(q, k, v, causal=True)
    o = flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                        interpret=True)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_chunked_attention_matches_dense():
    from repro.models.attention import chunked_attention
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(2, 4, 128, 32)) * 0.5, jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 2, 128, 32)) * 0.5, jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 2, 128, 32)), jnp.float32)
    o_ref = ref.flash_attention_ref(q, k, v, causal=True)
    o = chunked_attention(q, k, v, causal=True, chunk=32)
    np.testing.assert_allclose(o, o_ref, rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------- #
# occupancy grid wave kernel                                             #
# --------------------------------------------------------------------- #

def _mk_wave_cells(seed, p, m_t, n_t, k, nnz):
    """p cells sharing one conflict-free wave layout (same rows/cols,
    per-cell factors and values — conflict-freedom is index-only)."""
    from repro.core.partition import pack_cell_waves
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, m_t, nnz)
    cols = rng.integers(0, n_t, nnz)
    pre = np.lexsort((rows, cols))
    base_vals = rng.normal(size=nnz).astype(np.float32)
    _, wr, wc, _, wm, _ = pack_cell_waves(rows[pre], cols[pre],
                                          base_vals[pre])
    n_waves, width = wr.shape
    Ws = jnp.asarray(rng.normal(size=(p, m_t, k)), jnp.float32)
    Hs = jnp.asarray(rng.normal(size=(p, n_t, k)), jnp.float32)
    wvs = jnp.asarray(rng.normal(size=(p, n_waves, width)), jnp.float32)
    wrs = jnp.broadcast_to(jnp.asarray(wr), (p, n_waves, width))
    wcs = jnp.broadcast_to(jnp.asarray(wc), (p, n_waves, width))
    wms = jnp.broadcast_to(jnp.asarray(wm), (p, n_waves, width))
    return Ws, Hs, wrs, wcs, wvs, wms


@pytest.mark.parametrize("seed,p,k,nnz,wave_chunk", [
    (0, 2, 8, 90, 4),
    (1, 4, 100, 200, 8),     # k=100 -> lane padding; chunk divides unevenly
    (2, 3, 16, 31, 16),      # wave_chunk > n_waves: single ragged chunk
])
def test_grid_kernel_matches_single_program_bitwise(seed, p, k, nnz,
                                                    wave_chunk):
    """Interpreter-mode equivalence gate for the occupancy grid path:
    grid over (cell, wave_chunk) must equal the vmapped single-program
    wave kernel *bitwise* — same update arithmetic, different schedule —
    so the new Pallas formulation is CI-verifiable without a GPU."""
    from repro.kernels.nomad_sgd import (nomad_sgd_waves_block,
                                         nomad_sgd_waves_grid)
    Ws, Hs, wrs, wcs, wvs, wms = _mk_wave_cells(seed, p, 24, 12, k, nnz)
    Wg, Hg = nomad_sgd_waves_grid(Ws, Hs, wrs, wcs, wvs, wms, 0.01, 0.05,
                                  wave_chunk=wave_chunk, interpret=True)
    Wv, Hv = jax.vmap(
        lambda W, H, r, c, v, m: nomad_sgd_waves_block(
            W, H, r, c, v, m, 0.01, 0.05, wave_chunk=wave_chunk,
            interpret=True)
    )(Ws, Hs, wrs, wcs, wvs, wms)
    assert np.array_equal(np.asarray(Wg), np.asarray(Wv))
    assert np.array_equal(np.asarray(Hg), np.asarray(Hv))


def test_block_sgd_cells_forced_grid_matches_vmap():
    """ops.block_sgd_cells with block_rows forcing the grid path equals
    the historical vmap-of-kernel dispatch (and the wave XLA oracle)."""
    from repro.kernels import ops
    from repro.kernels.policy import KernelPolicy
    Ws, Hs, wrs, wcs, wvs, wms = _mk_wave_cells(3, 3, 16, 8, 8, 60)
    grid_pol = KernelPolicy(impl="wave_pallas", wave_chunk=4,
                            block_rows=64)      # forces wants_grid on CPU
    vmap_pol = KernelPolicy(impl="wave_pallas", wave_chunk=4,
                            block_rows=-1)      # forces the fallback
    Wg, Hg = ops.block_sgd_cells(Ws, Hs, wrs, wcs, wvs, wms, 0.01, 0.05,
                                 policy=grid_pol)
    Wv, Hv = ops.block_sgd_cells(Ws, Hs, wrs, wcs, wvs, wms, 0.01, 0.05,
                                 policy=vmap_pol)
    assert np.array_equal(np.asarray(Wg), np.asarray(Wv))
    assert np.array_equal(np.asarray(Hg), np.asarray(Hv))
    Wr, Hr = jax.vmap(
        lambda W, H, r, c, v, m: ref.block_sgd_waves(W, H, r, c, v, m,
                                                     0.01, 0.05)
    )(Ws, Hs, wrs, wcs, wvs, wms)
    np.testing.assert_allclose(Wg, Wr, rtol=2e-5, atol=2e-6)


def test_grid_kernel_accum_fp32_tracks_fp32_oracle():
    """bf16 storage + fp32 accumulation in the grid kernel stays near
    the fp32 trajectory (bounded, not bitwise — tolerance tier)."""
    import tolerance as tol
    from repro.kernels.nomad_sgd import nomad_sgd_waves_grid
    Ws, Hs, wrs, wcs, wvs, wms = _mk_wave_cells(4, 2, 24, 12, 16, 120)
    Wf, Hf = nomad_sgd_waves_grid(Ws, Hs, wrs, wcs, wvs, wms, 0.01, 0.05,
                                  wave_chunk=4, interpret=True)
    Wb, Hb = nomad_sgd_waves_grid(
        Ws.astype(jnp.bfloat16), Hs.astype(jnp.bfloat16), wrs, wcs,
        wvs.astype(jnp.bfloat16), wms, 0.01, 0.05, wave_chunk=4,
        interpret=True, accum_fp32=True)
    assert Wb.dtype == jnp.bfloat16
    tol.assert_factors_close(Wb, Wf, dtype_policy="bf16",
                             n_updates=120 / 24, what="W")
    tol.assert_factors_close(Hb, Hf, dtype_policy="bf16",
                             n_updates=120 / 12, what="H")


def test_grid_kernel_compiled_on_accelerator(requires_gpu):
    """On a real accelerator the grid kernel must lower (no interpret)
    and agree with the XLA wave oracle."""
    Ws, Hs, wrs, wcs, wvs, wms = _mk_wave_cells(5, 2, 16, 8, 8, 60)
    from repro.kernels.nomad_sgd import nomad_sgd_waves_grid
    Wg, Hg = nomad_sgd_waves_grid(Ws, Hs, wrs, wcs, wvs, wms, 0.01, 0.05,
                                  wave_chunk=4, interpret=False)
    Wr, Hr = jax.vmap(
        lambda W, H, r, c, v, m: ref.block_sgd_waves(W, H, r, c, v, m,
                                                     0.01, 0.05)
    )(Ws, Hs, wrs, wcs, wvs, wms)
    np.testing.assert_allclose(Wg, Wr, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(Hg, Hr, rtol=2e-5, atol=2e-6)


# --------------------------------------------------------------------- #
# compiled-kernel refusals and dispatch rules                            #
# --------------------------------------------------------------------- #

def _shapes(lead, m_t, n_t, k, ratings, dtype=jnp.float32):
    S = jax.ShapeDtypeStruct
    return (S(lead + (m_t, k), dtype), S(lead + (n_t, k), dtype),
            S(lead + ratings, jnp.int32), S(lead + ratings, jnp.int32),
            S(lead + ratings, dtype), S(lead + ratings, jnp.bool_))


_KERNELS = {
    "block": ((), (4096,)),
    "waves_block": ((), (64, 8)),
    "waves_grid": ((4,), (64, 8)),
}


@pytest.mark.parametrize("kernel", sorted(_KERNELS))
def test_compiled_kernel_refuses_netflix_cell(kernel):
    """A compiled Pallas SGD kernel asked to hold one worker's Netflix
    shard (331k rows at p=8) raises before lowering, naming VMEM."""
    from repro.kernels import nomad_sgd
    lead, ratings = _KERNELS[kernel]
    fn = getattr(nomad_sgd, "nomad_sgd_" + kernel)
    args = _shapes(lead, 331_179, 2_222, 100, ratings)
    with pytest.raises(ValueError, match="VMEM"):
        jax.eval_shape(lambda *a: fn(*a, 0.01, 0.05, interpret=False),
                       *args)


@pytest.mark.parametrize("kernel", sorted(_KERNELS))
def test_compiled_kernel_refuses_packed_dtype(kernel):
    from repro.kernels import nomad_sgd
    lead, ratings = _KERNELS[kernel]
    fn = getattr(nomad_sgd, "nomad_sgd_" + kernel)
    args = _shapes(lead, 64, 32, 100, ratings, jnp.bfloat16)
    with pytest.raises(ValueError, match="float32"):
        jax.eval_shape(lambda *a: fn(*a, 0.01, 0.05, interpret=False,
                                     accum_fp32=True), *args)


@pytest.mark.parametrize("tpu", [False, True])
def test_dispatch_rules_pick_only_what_compiles(monkeypatch, tpu):
    """``auto`` trains with the XLA update on every backend, and the
    grid kernel is chosen only for cells whose tiles fit VMEM."""
    from repro.kernels import ops
    from repro.kernels.policy import KernelPolicy
    monkeypatch.setattr(ops, "on_tpu", lambda: tpu)
    assert ops._resolve(KernelPolicy(impl="auto"), "auto", 1024, 8)[1] \
        == "xla"
    pol = KernelPolicy(impl="wave_pallas")
    assert pol.wants_grid(4096, 1024, 100) == tpu
    assert not pol.wants_grid(331_179, 2_222, 100)
    assert KernelPolicy(impl="auto").serve_impl == \
        ("pallas" if tpu else "xla")


# --------------------------------------------------------------------- #
# the stream epoch kernel (kernels/nomad_stream.py), TPU interpreter     #
# --------------------------------------------------------------------- #

def _stream_case(seed, p, m_local, n_local, slots, k=100):
    """A conflict-free epoch stream over home-placed (p, m_local, k) /
    (p, n_local, k) shards: lane q updates rows of W shard q, a slot's
    columns are distinct, column 0 is hot (in some lane of 3 slots in
    4), about 15% of entries are masked, and every 7th slot is fully
    masked.  Few rows per shard, so W rows repeat within and across
    chunks."""
    rng = np.random.default_rng(seed)
    Q = p * n_local
    R = (rng.integers(0, m_local, (slots, p))
         + m_local * np.arange(p)).astype(np.int32)
    C = np.stack([rng.permutation(Q)[:p] for _ in range(slots)])
    hot = rng.random(slots) < 0.75
    lane = rng.integers(0, p, slots)
    for t in np.flatnonzero(hot):
        C[t, C[t] == 0] = C[t, lane[t]]
        C[t, lane[t]] = 0
    M = rng.random((slots, p)) < 0.85
    M[::7] = False
    V = rng.normal(size=(slots, p)).astype(np.float32)
    Ws = jnp.asarray(0.3 * rng.normal(size=(p, m_local, k)), jnp.float32)
    Hs = jnp.asarray(0.3 * rng.normal(size=(p, n_local, k)), jnp.float32)
    return Ws, Hs, (R, C.astype(np.int32), V, M)


def _xla_stream_epoch(Ws, Hs, stream, lr, lam, impl):
    from repro.core import nomad
    from repro.kernels.policy import KernelPolicy
    data = tuple(jnp.asarray(a.reshape(-1)) for a in stream)
    return jax.jit(nomad._stream_epoch_body,
                   static_argnames=("policy", "entry"))(
        Ws, Hs, data, jnp.float32(lr), lam,
        policy=KernelPolicy(impl=impl), entry=None)


def _repeat_gaps(stream, B):
    """Chunk distance between consecutive live updates of each W row."""
    R, M = stream[0].reshape(-1), stream[3].reshape(-1)
    e = np.flatnonzero(M)
    e = e[np.argsort(R[e], kind="stable")]
    same = R[e][1:] == R[e][:-1]
    return (e[1:][same] // B - e[:-1][same] // B).tolist()


def _kernel_stream_epoch(Ws, Hs, stream, lr, lam, chunk, interpret=True):
    from repro.kernels import nomad_stream
    p, _, k = Ws.shape
    sm = nomad_stream.stream_map(*stream, Hs.shape[0] * Hs.shape[1], chunk)
    W, H = nomad_stream.stream_epoch(
        Ws.reshape(-1, k), Hs.reshape(-1, k),
        tuple(jnp.asarray(a) for a in sm[:4]), jnp.float32(lr), lam,
        p=p, chunk=chunk, interpret=interpret)
    return W.reshape(Ws.shape), H.reshape(Hs.shape), sm


@pytest.mark.parametrize("impl", ["xla", "wave"])
@pytest.mark.parametrize("seed,p,slots,chunk", [
    (0, 4, 45, 4),    # 12 chunks, the last holding 1 slot
    (1, 8, 30, 2),    # chunks of 16 entries over 6 rows a shard
    (2, 2, 61, 8),    # ragged: 61 = 7 x 8 + 5
])
def test_stream_kernel_matches_xla_stream(impl, seed, p, slots, chunk):
    """The kernel's epoch equals the XLA stream's within 1e-6 (the dot
    is reduced over 128 lanes in another order), on streams with a hot
    column, fully masked slots, a ragged last chunk and W rows that
    repeat within a chunk and across adjacent ones."""
    Ws, Hs, stream = _stream_case(seed, p, 6, 5, slots)
    W_x, H_x = _xla_stream_epoch(Ws, Hs, stream, 0.05, 0.02, impl)
    W_k, H_k, sm = _kernel_stream_epoch(Ws, Hs, stream, 0.05, 0.02, chunk)
    gaps = set(_repeat_gaps(stream, chunk * p))
    assert {0, 1} <= gaps           # repeats within and across chunks
    assert sm.reuses > 0 and sm.loads > 0
    for got, want, before in ((W_k, W_x, Ws), (H_k, H_x, Hs)):
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
        assert not np.array_equal(np.asarray(got), np.asarray(before))


def test_stream_kernel_dma_timing_and_races():
    """The result does not depend on when the interpreter lands a DMA
    (at its start or at its wait), and the race detector finds no
    access racing a DMA."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call
    from jax.experimental.pallas import tpu as pltpu
    Ws, Hs, stream = _stream_case(3, 4, 6, 5, 24)
    outs = []
    for mode in ("eager", "on_wait"):
        params = pltpu.InterpretParams(dma_execution_mode=mode,
                                       detect_races=True)
        outs.append(_kernel_stream_epoch(Ws, Hs, stream, 0.05, 0.02, 2,
                                         interpret=params)[:2])
        assert not interpret_pallas_call.races.races_found
    for a, b in zip(*outs):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def _replay_map(sm, live, p, chunk, window, nbuf):
    """Replay the kernel's schedule of DMAs and buffer reads on the map,
    with versions instead of values (a version is the stream index of
    the update that wrote it; -1 is the epoch's initial row).  Asserts
    that every live read sees its row's previous update, that no load is
    issued while a write-back of its row is in flight, and returns the
    version of each row in HBM at the end."""
    B = chunk * p
    n = len(sm.row)
    n_chunks = max(1, -(-n // B))
    hbm, latest, buf = {}, {}, {}
    writes = {}                      # chunk -> [(row, version)]
    in_flight = set()

    def issue_loads(c):
        for e in range(c * B, min(n, (c + 1) * B)):
            if sm.wmap[e] & 1:
                row = int(sm.row[e])
                assert row not in in_flight, "load races a write-back"
                buf[(c % nbuf) * B + e % B] = hbm.get(row, -1)

    def wait_writes(c):
        for row, version in writes.pop(c, []):
            hbm[row] = version
            in_flight.discard(row)

    for c in range(n_chunks):
        if c == 0:
            issue_loads(0)
        if c >= window:
            wait_writes(c - window)
        if c + 1 < n_chunks:
            issue_loads(c + 1)
        for e in range(c * B, min(n, (c + 1) * B)):
            own = (c % nbuf) * B + e % B
            if live[e]:
                row = int(sm.row[e])
                assert buf.get(int(sm.wmap[e]) >> 2) == latest.get(row, -1)
                latest[row] = e
                buf[own] = e
            else:
                buf[own] = None
        writes[c] = []
        for e in range(c * B, min(n, (c + 1) * B)):
            if sm.wmap[e] & 2:
                row = int(sm.row[e])
                writes[c].append((row, buf[(c % nbuf) * B + e % B]))
                in_flight.add(row)
        if c == n_chunks - 1:
            for back in range(window - 1, -1, -1):
                if c - back >= 0:
                    wait_writes(c - back)
    assert not writes and not in_flight
    return hbm, latest


@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 10_000), p=st.sampled_from([1, 2, 3, 8]),
       m_local=st.integers(1, 9), slots=st.integers(1, 80),
       chunk=st.integers(1, 6))
def test_stream_map_replay_property(seed, p, m_local, slots, chunk):
    """Against a naive replay of the kernel's DMA schedule: every read
    sees its row's latest write, no load races a write-back, and every
    row's last write of the epoch reaches HBM."""
    from repro.kernels import nomad_stream
    _, _, stream = _stream_case(seed, p, m_local, 4, slots, k=1)
    sm = nomad_stream.stream_map(*stream, p * 4, chunk)
    live = stream[3].reshape(-1)
    hbm, latest = _replay_map(sm, live, p, chunk, nomad_stream.WINDOW,
                              nomad_stream.NBUF)
    assert hbm == latest
    assert sm.loads + sm.reuses == live.sum()
    assert (sm.wmap[~live] & 3 == 0).all()
    assert (sm.col[~live] == p * 4).all()
    assert (sm.wmap[live] >> 2 < nomad_stream.NBUF * chunk * p).all()


@pytest.mark.parametrize("platform,mesh,impl,dtype_policy,n_items,want", [
    ("tpu", None, "xla", "fp32", 19_912, True),     # netflix-train's H
    ("tpu", None, "wave", "fp32", 19_912, True),
    ("tpu", None, "xla", "fp32", 624_961, False),   # Yahoo! Music's H
    ("tpu", None, "xla", "bf16", 19_912, False),
    ("tpu", "mesh", "xla", "fp32", 19_912, False),
    ("cpu", None, "xla", "fp32", 19_912, False),
    ("tpu", None, "pallas", "fp32", 19_912, False),
])
def test_stream_kernel_path_rule(platform, mesh, impl, dtype_policy,
                                 n_items, want):
    """The fused driver takes the epoch kernel on a TPU, without a mesh,
    for a stream impl over float32 factors whose H fits VMEM; every
    other case keeps the XLA stream."""
    from repro.core import nomad
    from repro.kernels.policy import KernelPolicy
    policy = KernelPolicy(impl=impl, dtype_policy=dtype_policy)
    dtype = policy.storage_dtype
    p, k = 8, 100
    assert nomad._takes_kernel(platform, mesh, policy, dtype, p,
                               -(-n_items // p), k) is want
