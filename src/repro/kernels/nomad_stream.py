"""Pallas TPU kernel for one whole epoch of the fused stream.

The fused driver's XLA stream (``core/nomad.py`` ``_stream_epoch_body``)
runs one slot of ``p`` conflict-free updates per ``fori_loop`` iteration:
about ten small XLA ops a slot, each paying a fixed cost far above its
few KB of work.  This kernel runs the same packed stream, in the same
serial order, as one ``pallas_call`` (``nomad_stream_epoch``):

* **H resident in VMEM.**  All of H (lane-padded, plus one dummy row that
  masked entries write) is copied into VMEM at the first grid step and
  back at the last, so the hot items' serial chains never leave VMEM.
* **W in HBM, rows moved by DMA.**  W stays in HBM (``pl.ANY``, aliased
  in place).  Each chunk of ``chunk`` slots has a buffer of
  ``chunk * p`` rows in VMEM, one row per entry; :data:`NBUF` buffers
  rotate.  While chunk ``c`` computes, chunk ``c + 1``'s rows are being
  loaded, and chunk ``c``'s write-backs start when it ends.
* **The stream in SMEM chunks**, one chunk per grid step, as the
  :mod:`.nomad_sgd` kernels stream theirs; chunk ``c + 1``'s rows and
  map come in beside chunk ``c``'s to issue the prefetch.

**The reuse map** (:func:`stream_map`, built on the host once per
packing) says for each entry where its W row comes from, and whether
the entry writes the row back.  Where the row's previous update lies at
most :data:`WINDOW` chunks back, the entry reads it from that update's
buffer row and nothing touches HBM; otherwise it is loaded from HBM.
An entry writes its row back exactly when the row's next update loads
it.  So a row written back by chunk ``c`` is next loaded by chunk
``c + WINDOW + 1`` or later, whose prefetch is issued at the start of
chunk ``c + WINDOW``, after that step has waited for chunk
``c + WINDOW - 2``'s write-backs: **no W row is read from HBM while a
newer value of it is in VMEM or in a write-back still in flight.**  A
buffer is reloaded only when no later chunk reads it: chunk ``c`` reads
buffers of chunks ``c - WINDOW .. c`` and the prefetch writes chunk
``c + 1``'s, so ``NBUF = WINDOW + 2``.

The update is :func:`repro.kernels.ref.sgd_pair`'s arithmetic on
128-lane rows (padding lanes start at zero and stay zero): a slot's
``p`` rows are loaded, updated and then stored, which is exact since a
slot's live rows and columns are pairwise distinct.  The dot is reduced
over lanes in another order than XLA's, so results agree with the XLA
stream to rounding, not bit for bit.

W is lane-padded for the call because Mosaic moves a row of an f32
table only as whole 128-lane tiles: a one-row DMA out of a
``(P, 100)`` table is refused ("slice shape along dimension 1 must be
aligned to tiling (128)").  :func:`stream_epoch` pads and unpads.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .nomad_sgd import CHUNK_GRANULE, LANE, VMEM_BYTES

#: how many chunks back an entry may take its W row from VMEM
WINDOW = 2

#: W row buffers in VMEM: the ``WINDOW`` chunks an entry may read back
#: from, the chunk computing and the chunk being prefetched
NBUF = WINDOW + 2

#: slots per chunk (rounded up so a chunk's ``chunk * p`` entries fill
#: whole :data:`CHUNK_GRANULE` tiles of the streamed SMEM blocks)
CHUNK_SLOTS = 128

#: VMEM the kernel may plan for: three quarters of one core's, leaving
#: the compiler its own scratch
VMEM_BUDGET = VMEM_BYTES * 3 // 4

_LOAD, _WRITE = 1, 2           # flag bits of a map entry; src = map >> 2


def chunk_slots(p: int, chunk: int = CHUNK_SLOTS, *,
                granule: int = CHUNK_GRANULE) -> int:
    """``chunk`` rounded up so that ``chunk * p`` is a multiple of
    ``granule`` (the tiling of a streamed rank-1 int32 block)."""
    g = granule // math.gcd(granule, p)
    return -(-max(chunk, 1) // g) * g


def _h_rows(q_rows: int) -> int:
    """Rows of the resident H: the table plus one dummy row for masked
    entries, rounded up to the 8-row sublane tile."""
    return -(-(q_rows + 1) // 8) * 8


def vmem_bytes(q_rows: int, k: int, p: int, chunk: int) -> int:
    """VMEM the kernel's blocks take: the resident H and the ``NBUF`` W
    row buffers of ``chunk * p`` rows, 128-lane f32 rows."""
    kp = -(-max(k, 1) // LANE) * LANE
    return (_h_rows(q_rows) + NBUF * chunk * p) * kp * 4


def fits_vmem(q_rows: int, k: int, p: int) -> bool:
    """Whether H of ``q_rows`` rows and the W buffers of
    :func:`chunk_slots` fit :data:`VMEM_BUDGET`."""
    return vmem_bytes(q_rows, k, p, chunk_slots(p)) <= VMEM_BUDGET


class StreamMap(NamedTuple):
    """The kernel's epoch stream: per entry, in serial order,

    * ``row``: W's table row (0 where masked),
    * ``col``: H's table row (``q_rows``, the dummy row, where masked),
    * ``val``: the rating (0 where masked),
    * ``wmap``: ``src << 2 | write << 1 | load``, where ``src`` is the W
      buffer row the entry reads (its own unless it reuses an earlier
      entry's), ``load`` that its row comes from HBM into its own buffer
      row, ``write`` that its updated row goes back to HBM.

    ``loads`` and ``reuses`` count the live entries of each kind."""
    row: np.ndarray
    col: np.ndarray
    val: np.ndarray
    wmap: np.ndarray
    loads: int
    reuses: int


def stream_map(R, C, V, M, q_rows: int, chunk: int) -> StreamMap:
    """Build the kernel's stream and reuse map from the ``(slots, p)``
    arrays of ``partition.epoch_stream``, for chunks of ``chunk``
    slots (see the module docstring for the rule)."""
    p = R.shape[1]
    B = chunk * p
    live = np.asarray(M).reshape(-1)
    row = np.asarray(R, np.int32).reshape(-1)
    n = row.size
    # a live entry's own buffer row: buffer (chunk % NBUF), position in
    # the chunk
    idx = np.arange(n, dtype=np.int64)
    own = (idx // B % NBUF) * B + idx % B
    # consecutive updates of each W row, in serial order: a stable sort
    # by row keeps each row's entries in stream order
    e = np.flatnonzero(live)
    order = np.argsort(row[e], kind="stable")
    e, r = e[order], row[e][order]
    same = r[1:] == r[:-1]
    prev, nxt = e[:-1][same], e[1:][same]
    near = nxt // B - prev // B <= WINDOW
    src = own.copy()
    src[nxt[near]] = own[prev[near]]
    load = live.copy()
    load[nxt[near]] = False
    write = live.copy()
    write[prev[near]] = False
    wmap = (src << 2) | (write.astype(np.int64) * _WRITE) | load
    return StreamMap(
        row=np.where(live, row, 0).astype(np.int32),
        col=np.where(live, np.asarray(C).reshape(-1), q_rows)
        .astype(np.int32),
        val=np.where(live, np.asarray(V).reshape(-1), 0)
        .astype(np.float32),
        wmap=wmap.astype(np.int32),
        loads=int(load.sum()), reuses=int(near.sum()))


def _kernel(scal_ref, row_ref, col_ref, val_ref, map_ref, nrow_ref,
            nmap_ref, W_in, H_in, W_hbm, H_hbm, hbuf, wbuf, lsem, wsem,
            hsem, cnt, *, p):
    """One grid step: chunk ``c`` of the epoch (module docstring)."""
    del W_in, H_in                    # aliased: W_hbm and H_hbm hold them
    c = pl.program_id(0)
    n = pl.num_programs(0)
    B = row_ref.shape[0]
    b = c % NBUF

    def row_dma(src, dst, sem):
        return pltpu.make_async_copy(src, dst, sem)

    def issue_loads(rref, mref, buf):
        """Start the HBM loads of one chunk into buffer ``buf``."""
        def body(e, count):
            load = mref[e] & _LOAD

            @pl.when(load != 0)
            def _():
                row_dma(W_hbm.at[pl.ds(rref[e], 1)],
                        wbuf.at[pl.ds(buf * B + e, 1)], lsem.at[buf]).start()
            return count + load
        cnt[buf] = jax.lax.fori_loop(0, B, body, 0)

    def wait_loads(buf):
        dma = row_dma(W_hbm.at[pl.ds(0, 1)], wbuf.at[pl.ds(0, 1)],
                      lsem.at[buf])
        jax.lax.fori_loop(0, cnt[buf], lambda i, x: (dma.wait(), x)[1], 0)

    def wait_writes(buf):
        dma = row_dma(wbuf.at[pl.ds(0, 1)], W_hbm.at[pl.ds(0, 1)],
                      wsem.at[buf])
        jax.lax.fori_loop(0, cnt[NBUF + buf],
                          lambda i, x: (dma.wait(), x)[1], 0)

    @pl.when(c == 0)
    def _first():
        h_in = row_dma(H_hbm, hbuf, hsem)
        h_in.start()
        issue_loads(row_ref, map_ref, 0)
        h_in.wait()

    @pl.when(c >= WINDOW)
    def _():
        wait_writes((c - WINDOW) % NBUF)

    @pl.when(c + 1 < n)
    def _():
        issue_loads(nrow_ref, nmap_ref, (c + 1) % NBUF)

    wait_loads(b)

    lr = scal_ref[0]
    lam = scal_ref[1]
    base = b * B

    def slot(t, carry):
        es = [t * p + q for q in range(p)]
        cols = [col_ref[e] for e in es]
        # every row of the slot is read before any is written: the
        # slot's live rows and columns are pairwise distinct, and masked
        # entries write only their own buffer row and H's dummy row
        ws = [wbuf[pl.ds(map_ref[e] >> 2, 1), :] for e in es]
        hs = [hbuf[pl.ds(j, 1), :] for j in cols]
        news = []
        for e, w, h in zip(es, ws, hs):
            err = val_ref[e] - jnp.sum(w * h, axis=-1, keepdims=True)
            news.append((w - lr * (-err * h + lam * w),
                         h - lr * (-err * w + lam * h)))
        for e, j, (w_new, h_new) in zip(es, cols, news):
            wbuf[pl.ds(base + e, 1), :] = w_new
            hbuf[pl.ds(j, 1), :] = h_new
        return carry

    jax.lax.fori_loop(0, B // p, slot, 0)

    def write(e, count):
        w = (map_ref[e] & _WRITE) >> 1

        @pl.when(w != 0)
        def _():
            row_dma(wbuf.at[pl.ds(base + e, 1)],
                    W_hbm.at[pl.ds(row_ref[e], 1)], wsem.at[b]).start()
        return count + w
    cnt[NBUF + b] = jax.lax.fori_loop(0, B, write, 0)

    @pl.when(c == n - 1)
    def _last():
        # chunks before c - WINDOW + 1 were waited at their own steps
        for back in range(1, WINDOW):
            @pl.when(c >= back)
            def _():
                wait_writes((c - back) % NBUF)
        wait_writes(b)
        h_out = row_dma(hbuf, H_hbm, hsem)
        h_out.start()
        h_out.wait()


def _smem(block, index_map):
    return pl.BlockSpec(block, index_map, memory_space=pltpu.SMEM)


@functools.partial(jax.jit, static_argnames=("p", "chunk", "interpret"))
def stream_epoch(W, H, data, lr, lam, *, p: int, chunk: int,
                 interpret=False):
    """One epoch of the fused stream in one kernel call.

    W: (P, k), H: (Q, k) float32 factor tables; ``data`` the
    ``(row, col, val, wmap)`` arrays of a :class:`StreamMap` built for
    ``chunk`` slots of ``p`` entries.  Returns the updated ``(W, H)``.
    ``interpret``: ``False`` compiles for the TPU; ``True`` or a
    ``pltpu.InterpretParams`` runs the TPU interpreter (CPU tests).
    """
    if W.dtype != jnp.float32 or H.dtype != jnp.float32:
        raise ValueError("nomad_stream_epoch needs float32 factor tables")
    P, k = W.shape
    Q = H.shape[0]
    kp = -(-k // LANE) * LANE
    Qs = _h_rows(Q)
    B = chunk * p
    row, col, val, wmap = data
    n = row.shape[0]
    n_chunks = max(1, -(-n // B))
    pad = n_chunks * B - n
    # padding entries are no-ops: no load, no write-back, H's dummy row
    row, col, val, wmap = (jnp.pad(a, (0, pad), constant_values=v)
                           for a, v in ((row, 0), (col, Q), (val, 0),
                                        (wmap, 0)))
    Wp = jnp.pad(W, ((0, 0), (0, kp - k)))
    Hp = jnp.pad(H, ((0, Qs - Q), (0, kp - k)))
    scal = jnp.stack([jnp.asarray(lr, jnp.float32),
                      jnp.asarray(lam, jnp.float32)])
    cur = _smem((B,), lambda c: (c,))
    nxt = _smem((B,), lambda c: (jnp.minimum(c + 1, n_chunks - 1),))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    need = vmem_bytes(Q, k, p, chunk)
    if interpret is True:
        interpret = pltpu.InterpretParams()
    Wp, Hp = pl.pallas_call(
        functools.partial(_kernel, p=p),
        grid=(n_chunks,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  cur, cur, cur, cur, nxt, nxt, hbm, hbm],
        out_specs=[hbm, hbm],
        out_shape=[jax.ShapeDtypeStruct(Wp.shape, Wp.dtype),
                   jax.ShapeDtypeStruct(Hp.shape, Hp.dtype)],
        scratch_shapes=[pltpu.VMEM((Qs, kp), jnp.float32),
                        pltpu.VMEM((NBUF * B, kp), jnp.float32),
                        pltpu.SemaphoreType.DMA((NBUF,)),
                        pltpu.SemaphoreType.DMA((NBUF,)),
                        pltpu.SemaphoreType.DMA(()),
                        pltpu.SMEM((2 * NBUF,), jnp.int32)],
        input_output_aliases={7: 0, 8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=need + (8 << 20)),
        interpret=interpret or False,
        name="nomad_stream_epoch",
    )(scal, row, col, val, wmap, row, wmap, Wp, Hp)
    return Wp[:, :k], Hp[:Q, :k]
