"""NOMAD SPMD engine — the deployable TPU implementation.

TPU adaptation of Algorithm 1 (see DESIGN.md §2/§8): W shards are
owner-fixed on the worker mesh axis, H blocks are *nomadic* and hop
between workers via ``jax.lax.ppermute``.  Which hops happen when is
data, not code: the engine executes any
``core.schedule.OwnershipSchedule`` — the canonical ring rotation
(default; bitwise-preserves the historical behavior), compiled
uniform-random routing (Alg. 1 line 22), queue-aware balanced routing
(§3.3), or a schedule compiled from an async-simulator run
(``OwnershipSchedule.from_sim_log``).  One epoch = ``schedule.n_steps``
steps; at step s worker q holds block ``schedule.table[s, q]`` and
applies its cell iff ``schedule.active[s, q]``; every rating is applied
exactly once per epoch with a well-defined serial-equivalent ordering
(``BlockedRatings.schedule_order``).

Two executors share the same math:

* ``run_epoch_spmd``   — shard_map over a real device axis; the ppermute is
  a genuine inter-chip collective.  This is what the multi-pod config runs.
  The ring keeps its historical scan + constant-shift collective; general
  schedules unroll the step loop so each step's permutation is a static
  ``ppermute`` pattern.
* ``run_epoch_local``  — single-device emulation: the schedule step becomes
  an outer ``lax.scan``, the per-worker block updates a ``vmap`` (cells
  within a step touch disjoint rows/cols so this is exact), and the
  permute a per-step gather on the worker dimension (the ring instance is
  exactly the old ``jnp.roll(Hs, 1)``).  Bitwise-identical results; used
  for tests and CPU runs.

The per-block update is ``kernels.ops.block_sgd`` driven by a
``kernels.policy.KernelPolicy``: ``'xla'``/``'pallas'`` run the rating
list strictly sequentially; ``'wave'``/``'wave_pallas'`` run the
conflict-free wave-vectorized path (DESIGN.md §3) over the
``(n_waves, wave_width)`` layout from ``partition.pack`` — the same serial
ordering, executed ~wave_width updates per step.

Overlap: with ``sub_blocks > 1`` the H block is split into sub-blocks whose
permutes are issued as soon as each sub-block's updates finish, while the
next sub-block's compute proceeds — the double-buffered pipeline that gives
NOMAD its non-blocking-communication property on TPU (the XLA latency-
hiding scheduler turns the independent permute+compute pairs into
collective-permute-start/done around the compute).  The per-sub-block
rating lists are pre-partitioned at pack time (``BlockedRatings.sub_*``),
so each sub-block processes only its own ratings instead of re-scanning
the cell's full padded list with a mask.

Per-epoch evaluation stays on device: ``train`` gathers test predictions
directly from the ``(p, m_local, k)`` factor shards with a jit'd sharded
RMSE, so no epoch transfers the factors to the host (the seed's
``factors()`` round-trip).

Dispatch (DESIGN.md §9): ``train(dispatch="loop")`` is the historical
per-epoch Python loop — one device program dispatch plus one blocking
``float(rmse)`` host sync per epoch, which at small problem sizes costs
~8x the SGD compute itself.  ``dispatch="fused"`` lifts the whole call
into a single jitted ``lax.scan`` over epochs (``_local_train`` /
``_spmd_train``): the learning-rate array is precomputed on the host
(``PowerSchedule.values``), the held-out RMSE trace is recorded on
device into a preallocated array at ``record_every`` cadence, and the
factor shards are donated so epochs update in place — one host sync per
``fuse_epochs`` block instead of per epoch, bitwise-identical results.
The public entry point is ``repro.api.solve(problem,
NomadConfig(...))``; ``fit`` survives as a deprecation shim that
forwards to it.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import obs
from . import partition as part
from .schedule import OwnershipSchedule
from .stepsize import PowerSchedule
from ..kernels import ops as kops
from ..kernels import ref as kref
from ..kernels.policy import KernelPolicy


def _local_epoch_body(Ws, Hs, rows, cols, vals, mask, perm_src, lr, lam,
                      policy: KernelPolicy, entry):
    """Single-device schedule-epoch emulation (shared trace body).

    Ws: (p, m_local, k)   Hs: (p, n_local, k) where Hs[q] is the block
    *currently held* by worker q.  rows/cols/vals/mask are indexed
    [step, worker, ...] — *step-major*, the scan axis leading: flat
    (n_steps, p, max_nnz) lists for the sequential impls, (n_steps, p,
    n_waves, wave_width) wave layouts for the wave impls
    (``partition.step_major_cells``; the seed paid a ``jnp.swapaxes``
    copy of every rating array inside every epoch dispatch instead).
    ``perm_src`` is the schedule's (n_steps, p) post-step gather
    (``OwnershipSchedule.perm_sources``; the ring rows are all the
    ``+1`` shift, making the scan body exactly the old ``jnp.roll``),
    ``entry`` the optional pre-epoch gather from the home placement to
    ``table[0]`` (``None`` for the ring — idle slots of a general
    schedule are empty cells, so they run as exact no-ops).

    This is the one epoch trace shared by the per-epoch jit
    (:func:`_local_epoch`) and the fused multi-epoch driver
    (:func:`_local_train`), which is what makes their bitwise equality
    hold by construction rather than by accident.
    """
    if entry is not None:
        Hs = jnp.take(Hs, entry, axis=0)

    def sched_step(carry, step_data):
        Ws, Hs = carry
        r, c, v, m, psrc = step_data  # data (p, ...), psrc (p,)
        # a step's p cells are conflict-free: block_sgd_cells runs them
        # as one occupancy grid kernel on accelerators, or the bitwise
        # historical vmap-of-block_sgd everywhere else
        Ws, Hs = kops.block_sgd_cells(Ws, Hs, r, c, v, m, lr, lam,
                                      policy=policy)
        # ownership transfer: worker q's next block comes from psrc[q]
        Hs = jnp.take(Hs, psrc, axis=0)
        return (Ws, Hs), ()

    (Ws, Hs), _ = jax.lax.scan(sched_step, (Ws, Hs),
                               (rows, cols, vals, mask, perm_src))
    # the last perm_src row routes every block back home
    return Ws, Hs


#: per-epoch jit of :func:`_local_epoch_body`.  ``Ws``/``Hs`` are donated:
#: the caller always overwrites its references with the outputs, so the
#: input shards can be updated in place instead of copied every epoch
#: (a no-op on backends without donation support, e.g. CPU — bitwise
#: identity is asserted in tests/test_driver.py).
_local_epoch = functools.partial(
    jax.jit, static_argnames=("policy",),
    donate_argnums=(0, 1))(_local_epoch_body)


def _stream_epoch_body(Ws, Hs, data, lr, lam, policy: KernelPolicy,
                       entry):
    """One epoch over the globalized flat stream
    (``partition.epoch_stream``): a single scan of conflict-free
    ``p``-wide slots against one factor table, the flattened
    home-placement W rows followed by the H rows — no per-step
    permutation, no entry gather, no worker vmap.

    Each slot batches up to ``p`` concurrent updates whose rows and
    columns are pairwise disjoint (the generalized-diagonal invariant),
    so the slot's one ``2p``-row gather -> update -> one drop-mode
    scatter is exactly a sequential execution of the slot; slots run
    in the packed serial order.  Bitwise equality with the loop path
    holds per kernel because the slot update reproduces the loop
    path's own batching: the wave impls' slot is a width-``p``
    ``sgd_pair_batch`` (the op ``block_sgd_waves`` applies per wave),
    the sequential impls' a
    worker-vmapped ``sgd_pair`` (the op the worker-vmapped
    ``block_sgd_ref`` scan applies per rating — ``dot`` and
    ``sum(w * h)`` reductions are not interchangeable bit for bit).
    The stream runs ``sum_s max_q nnz_cell(q, s)`` cheap slots instead
    of ``n_steps x global_max`` padded kernel iterations, which is
    where the kernel-vs-engine throughput gap at skewed shapes lives.
    Only the pure-XLA impls stream (``'xla'``/``'wave'``); the Pallas
    kernels own their inner loop, so their fused driver keeps the
    step-scan epoch (``entry`` is unused here but keeps the driver
    signature uniform).

    ``data`` holds the ``(slots, p)`` stream arrays flattened to 1-D,
    and each slot is a dynamic slice of ``p`` entries: a ``(slots, p)``
    array fed to the scan as ``xs`` would be tiled with its ``p``-wide
    minor dimension padded to 128 lanes on TPU (16x the bytes at p=8,
    past the 16 GB of one v5e at the Netflix shape).
    """
    rows, cols, vals, mask = data
    p, m_local, k = Ws.shape
    n_local = Hs.shape[1]
    P, Q = p * m_local, p * n_local
    # one factor table, H's rows at offset P: with W and H apart, TPU
    # keeps the (small) H in VMEM and its scatter stages a copy of all
    # of H on every slot; one table lives in HBM and is written in place
    T = jnp.concatenate([Ws.reshape(P, k), Hs.reshape(Q, k)])
    cd = policy.compute_dtype            # None on the fp32 bitwise path
    lr = jnp.asarray(lr, dtype=cd or T.dtype)
    lam = jnp.asarray(lam, dtype=cd or T.dtype)
    if policy.wave:
        pair = functools.partial(kref.sgd_pair_batch, compute_dtype=cd)
    else:
        pair = jax.vmap(
            functools.partial(kref.sgd_pair, compute_dtype=cd),
            in_axes=(0, 0, 0, None, None))

    # the named scopes reach the compiled program only as op_name
    # metadata, so a profiler trace can split the slot's device time
    # into index slicing, the row gather, the update and the scatter
    def slot(t, T):
        with jax.named_scope("slot.index"):
            r, c, v, m = (jax.lax.dynamic_slice_in_dim(a, t * p, p)
                          for a in (rows, cols, vals, mask))
            idx = jnp.concatenate([r, c + P])
        with jax.named_scope("slot.gather"):
            g = T[idx]
        with jax.named_scope("slot.sgd"):
            w_new, h_new = pair(g[:p], g[p:], v, lr, lam)
        # W and H rows lie in disjoint ranges of T, and a slot's live
        # rows and columns are pairwise distinct, so the 2p live indices
        # are unique and this one scatter is exactly the two it replaces
        with jax.named_scope("slot.scatter"):
            live = jnp.concatenate([m, m])
            T = T.at[jnp.where(live, idx, P + Q)].set(
                jnp.concatenate([w_new, h_new]), mode="drop")
        return T

    T = jax.lax.fori_loop(0, rows.shape[0] // p, slot, T)
    return T[:P].reshape(p, m_local, k), T[P:].reshape(p, n_local, k)


def _steps_epoch_body(Ws, Hs, data, lr, lam, policy: KernelPolicy,
                      entry):
    """:func:`_local_epoch_body` adapted to the fused driver's
    ``data``-tuple signature (``data`` = step-major cell arrays plus the
    schedule's per-step permutation)."""
    rows, cols, vals, mask, perm_src = data
    return _local_epoch_body(Ws, Hs, rows, cols, vals, mask, perm_src,
                             lr, lam, policy, entry)


def _fused_driver(epoch_body):
    """Build a fused multi-epoch training driver around an epoch body:
    one device program for a whole block of epochs (DESIGN.md §9).

    ``lrs`` is the host-precomputed per-epoch learning-rate array
    (``PowerSchedule.values`` — bitwise the loop path's per-epoch
    scalars) and ``rec_pos[e]`` the slot of epoch ``e``'s held-out RMSE
    in the preallocated ``(n_rec,)`` trace (``-1`` = not recorded).
    Evaluation is the same flat-index gather as :func:`_sharded_rmse`,
    executed on device inside the scan, so the only host synchronization
    for the entire block is the caller reading the returned trace —
    versus one blocking ``float(...)`` per epoch on the loop path.
    ``Ws``/``Hs`` are donated: epochs update the factor shards in place.

    The driver also carries the on-device divergence sentinel
    (DESIGN.md §14): a single ``ok`` flag AND-folded across epochs with
    the all-finiteness of both factor blocks.  NaN/Inf is absorbing
    through SGD updates, so one flag per block is exact — the returned
    ``ok`` is False iff any epoch in the block produced a non-finite
    entry.  It rides the existing scan carry: no extra host sync.
    """
    @functools.partial(jax.jit, static_argnames=("policy", "n_rec"),
                       donate_argnums=(0, 1))
    def train(Ws, Hs, data, lrs, rec_pos, lam, ridx, cidx, tvals,
              policy: KernelPolicy = KernelPolicy(impl="xla"),
              entry=None, n_rec: int = 0):
        trace = jnp.zeros((n_rec,), dtype=jnp.float32)
        ok = jnp.array(True)

        def epoch(carry, inp):
            Ws, Hs, trace, ok = carry
            lr, pos = inp
            Ws, Hs = epoch_body(Ws, Hs, data, lr, lam, policy, entry)
            ok &= jnp.isfinite(Ws).all() & jnp.isfinite(Hs).all()
            if n_rec:
                trace = jax.lax.cond(
                    pos >= 0,
                    lambda tr: tr.at[pos].set(
                        _sharded_rmse_body(Ws, Hs, ridx, cidx, tvals)),
                    lambda tr: tr, trace)
            return (Ws, Hs, trace, ok), ()

        (Ws, Hs, trace, ok), _ = jax.lax.scan(epoch, (Ws, Hs, trace, ok),
                                              (lrs, rec_pos))
        return Ws, Hs, trace, ok

    return train


#: fused local drivers: the globalized flat stream for the pure-XLA
#: impls, the step-scan epoch (kops.block_sgd dispatch, Pallas included)
#: for the rest — both bitwise-equal to the per-epoch loop path.
_local_train_stream = _fused_driver(_stream_epoch_body)
_local_train_steps = _fused_driver(_steps_epoch_body)

#: impls whose fused local driver consumes the flattened epoch stream
_STREAM_IMPLS = ("xla", "wave")


def _spmd_epoch_fn(p: int, axis: str, lam: float, policy: KernelPolicy,
                   sub_starts=None, sched: Optional[OwnershipSchedule] = None):
    """Per-shard epoch body for shard_map (one worker's view).

    With ``policy.sub_blocks > 1`` the rating arrays are the
    *pre-partitioned* per-sub-block lists from
    ``partition.pack(..., sub_blocks=...)`` (shape
    ``(1, n_steps, sub_blocks, sub_max_nnz)``, cols already localized to
    the sub-block), so every sub-block touches only its own ratings — the
    seed's masked re-scan of the full ``max_nnz`` list per sub-block
    multiplied epoch compute by ``sub_blocks``.

    The ring schedule keeps the historical ``lax.scan`` over steps with
    one constant-shift collective (bitwise-preserving).  A general
    ``OwnershipSchedule`` unrolls the (short) step loop so every step's
    ownership transfer is its own static ``ppermute`` pattern — the
    sub-block pipelining applies per step exactly as for the ring.
    """
    sub_blocks = policy.sub_blocks

    if sched is None or sched.is_ring:
        perm = [(i, (i + 1) % p) for i in range(p)]

        def epoch(W, Hblk, rows, cols, vals, mask, lr):
            # W: (1, m_local, k) -> squeeze; data: (1, p, ...)
            W = W[0]
            Hblk = Hblk[0]

            def ring_step(carry, step_data):
                W, Hblk = carry
                r, c, v, m = step_data
                if sub_blocks == 1:
                    W, Hblk = kops.block_sgd(W, Hblk, r, c, v, m, lr, lam,
                                             policy=policy)
                    Hblk = jax.lax.ppermute(Hblk, axis, perm)
                else:
                    # r/c/v/m: (sub_blocks, sub_max_nnz).  Permute each
                    # sub-block as soon as its updates are done so XLA
                    # can overlap the collective with the next
                    # sub-block's compute.
                    outs = []
                    for s in range(sub_blocks):
                        lo = int(sub_starts[s])
                        hi = int(sub_starts[s + 1])
                        Hsub = Hblk[lo:hi]
                        W, Hsub = kops.block_sgd(
                            W, Hsub, r[s], c[s], v[s], m[s], lr, lam,
                            policy=policy)
                        outs.append(jax.lax.ppermute(Hsub, axis, perm))
                    Hblk = jnp.concatenate(outs, axis=0)
                return (W, Hblk), ()

            (W, Hblk), _ = jax.lax.scan(
                ring_step, (W, Hblk), (rows[0], cols[0], vals[0], mask[0]))
            return W[None], Hblk[None]

        return epoch

    pairs = sched.ppermute_pairs()
    ent = sched.entry_sources()
    entry_pairs = (None if ent is None
                   else [(int(ent[q]), q) for q in range(p)])
    n_steps = sched.n_steps

    def epoch(W, Hblk, rows, cols, vals, mask, lr):
        W = W[0]
        Hblk = Hblk[0]
        if entry_pairs is not None:
            Hblk = jax.lax.ppermute(Hblk, axis, entry_pairs)
        for s in range(n_steps):
            r, c, v, m = rows[0, s], cols[0, s], vals[0, s], mask[0, s]
            if sub_blocks == 1:
                W, Hblk = kops.block_sgd(W, Hblk, r, c, v, m, lr, lam,
                                         policy=policy)
                Hblk = jax.lax.ppermute(Hblk, axis, pairs[s])
            else:
                outs = []
                for sb in range(sub_blocks):
                    lo = int(sub_starts[sb])
                    hi = int(sub_starts[sb + 1])
                    Hsub = Hblk[lo:hi]
                    W, Hsub = kops.block_sgd(
                        W, Hsub, r[sb], c[sb], v[sb], m[sb], lr, lam,
                        policy=policy)
                    outs.append(jax.lax.ppermute(Hsub, axis, pairs[s]))
                Hblk = jnp.concatenate(outs, axis=0)
        return W[None], Hblk[None]

    return epoch


def _sharded_rmse_body(Ws, Hs, ridx, cidx, vals):
    """Test RMSE straight off the (p, m_local, k)/(p, n_local, k) factor
    shards.  ``ridx``/``cidx`` are flat shard indices
    (owner * local_size + local), so the gather reads exactly the same
    float values the unshard + full-matrix path would — no host
    round-trip, and under a mesh XLA inserts the gather collective.
    Shared by the per-epoch jit below and the fused drivers' on-device
    trace recording."""
    k = Ws.shape[-1]
    wi = Ws.reshape(-1, k)[ridx]
    hj = Hs.reshape(-1, k)[cidx]
    # evaluate in fp32 regardless of factor storage (a no-op cast for
    # fp32 shards, so the historical trace stays bitwise)
    pred = jnp.sum(wi.astype(jnp.float32) * hj.astype(jnp.float32),
                   axis=-1)
    return jnp.sqrt(jnp.mean((vals.astype(jnp.float32) - pred) ** 2))


_sharded_rmse = jax.jit(_sharded_rmse_body)


def _record_slots(epochs: int, record_every: int, have_test: bool):
    """Which epochs of a ``train(epochs, ...)`` call record a held-out
    RMSE: every ``record_every``-th epoch plus always the final one
    (1-based offsets within the call).  The single source of the
    trace-recording rule — the loop path tests membership per epoch, the
    fused drivers precompute the slot array from it, so both dispatches
    record identical traces by construction."""
    if not have_test:
        return []
    return [i for i in range(1, epochs + 1)
            if i % record_every == 0 or i == epochs]


@dataclasses.dataclass
class NomadRingEngine:
    """Internal executor behind ``repro.api.solve``: owns the packed
    blocks and the factor shards.  (Direct construction still works and
    is what the distributed tests do.)

    Executes the ``OwnershipSchedule`` its packing was laid out for
    (``br.schedule``; the ring by default — the class name predates the
    schedule IR).  ``stepsize`` is the per-epoch SGD step-size schedule,
    eq. (11).
    """
    br: part.BlockedRatings
    k: int
    lam: float
    stepsize: PowerSchedule
    impl: str = "xla"         # legacy: 'xla'|'pallas'|'auto'|'wave'|'wave_pallas'
    sub_blocks: int = 1
    mesh: Optional[Mesh] = None    # if given, run shard_map on axis 'workers'
    policy: Optional[KernelPolicy] = None  # overrides impl/sub_blocks

    #: divergence sentinel (DESIGN.md §14): False once any train() call
    #: left a non-finite entry in the factor shards.  Fused dispatch
    #: folds the check into the scan carry (no extra host sync); the
    #: loop path checks once per train() call — exact either way, since
    #: NaN/Inf is absorbing through SGD updates.
    last_finite: bool = True

    def __post_init__(self):
        if self.policy is None:
            self.policy = KernelPolicy.coerce(self.impl,
                                              sub_blocks=self.sub_blocks)
        else:
            self.impl = self.policy.impl
            self.sub_blocks = self.policy.sub_blocks
        self.epoch_idx = 0
        self._load_pack(self.br)

    def _load_pack(self, br: part.BlockedRatings):
        """(Re)load the packed rating arrays onto the device(s); shared by
        construction and :meth:`grow`."""
        self.br = br
        self.sched = br.schedule or OwnershipSchedule.ring(br.p)
        self._perm_src = jnp.asarray(self.sched.perm_sources())
        ent = self.sched.entry_sources()
        self._entry = None if ent is None else jnp.asarray(ent)
        self._eval_cache = None
        self._stream = None     # fused-driver stream, built on first use
        self._stream_counts = None
        # local executor: cell arrays are loaded lazily by _cell_data()
        # (the default fused dispatch for the pure-XLA impls only reads
        # the epoch stream — don't keep a second, padded device copy of
        # the ratings alive unless a loop/Pallas dispatch needs it).
        # Layout validation still happens here, at construction.
        self.policy.check_packed(br, pipelined=self.mesh is not None)
        self.rows = self.cols = self.vals = self.mask = None
        if self.mesh is not None:
            axis = self.mesh.axis_names[0]
            fn = _spmd_epoch_fn(br.p, axis, self.lam, self.policy,
                                br.sub_starts, self.sched)
            pspec = P(axis)
            epoch_shard = jax.shard_map(
                fn, mesh=self.mesh,
                in_specs=(pspec, pspec, pspec, pspec, pspec, pspec, P()),
                out_specs=(pspec, pspec))
            self._spmd_epoch = jax.jit(epoch_shard, donate_argnums=(0, 1))
            # fused SPMD driver: the shard_mapped per-step epoch inside
            # the shared _fused_driver scan (ppermute is a real
            # collective, so the step structure stays; trace recording
            # runs on the global sharded arrays, where XLA inserts the
            # same gather collective the per-epoch _sharded_rmse does)
            self._spmd_train = _fused_driver(
                lambda Ws, Hs, data, lr, lam, policy, entry:
                    epoch_shard(Ws, Hs, *data, lr))
            src = self.policy.cell_arrays(br, pipelined=True)
            sh = NamedSharding(self.mesh, pspec)
            self.rows, self.cols, self.vals, self.mask = (
                jax.device_put(jnp.asarray(a), sh) for a in src)

    def _cell_data(self):
        """Step-major device cell arrays for the local step-scan
        executors (scan axis leading, transposed once here instead of
        per epoch dispatch), built on first use.  On a mesh the same
        attributes hold the eagerly-loaded *worker-major* sharded
        arrays (the SPMD path always consumes them), so this accessor
        is local-executor-only."""
        assert self.mesh is None, (
            "_cell_data() serves the local step-scan executors; a mesh "
            "engine's rows/cols/vals/mask are worker-major shards")
        if self.rows is None:
            src = self.policy.cell_arrays(self.br, pipelined=False,
                                          step_major=True)
            self.rows, self.cols, self.vals, self.mask = map(
                jnp.asarray, src)
        return self.rows, self.cols, self.vals, self.mask

    def grow(self, br_new: part.BlockedRatings, *, seed: int = 0,
             W_new=None, H_new=None):
        """Swap in an extended packing (from ``partition.repack_delta``)
        and grow the factor shards for the new rows/items.

        Existing W/H entries are preserved bit for bit (they are gathered
        off the old shards and re-scattered into the new layout, which is
        exact); rows for the ``br_new.m - br.m`` new users and
        ``br_new.n - br.n`` new items initialize from
        ``objective.grow_factors`` (or the explicit ``W_new``/``H_new``).
        ``epoch_idx`` is untouched, so the step-size schedule resumes
        exactly where the previous arrival batch left it.
        """
        br_old = self.br
        if br_new.m < br_old.m or br_new.n < br_old.n:
            raise ValueError(
                f"grow() cannot shrink: ({br_new.m}, {br_new.n}) < "
                f"({br_old.m}, {br_old.n})")
        if not (np.array_equal(br_new.row_owner[: br_old.m],
                               br_old.row_owner)
                and np.array_equal(br_new.col_block[: br_old.n],
                                   br_old.col_block)):
            raise ValueError(
                "grow() needs a sticky extension of the current partition "
                "(existing row/col assignments unchanged); use "
                "partition.repack_delta")
        from .objective import grow_factors
        W, H = self.factors()
        m_new = br_new.m - br_old.m
        n_new = br_new.n - br_old.n
        # default both sides to the seeded draw; an explicit W_new/H_new
        # overrides only its own side (the other keeps the draw, so a
        # one-sided override never silently changes the documented init)
        W2, H2 = grow_factors(W, H, m_new, n_new, seed=seed)
        if W_new is not None:
            W_new = np.asarray(W_new, W.dtype)
            if W_new.shape != (m_new, self.k):
                raise ValueError(
                    f"W_new must have shape ({m_new}, {self.k}), got "
                    f"{W_new.shape}")
            W2 = np.concatenate([W, W_new])
        if H_new is not None:
            H_new = np.asarray(H_new, H.dtype)
            if H_new.shape != (n_new, self.k):
                raise ValueError(
                    f"H_new must have shape ({n_new}, {self.k}), got "
                    f"{H_new.shape}")
            H2 = np.concatenate([H, H_new])
        self._load_pack(br_new)
        self.init_factors(W2, H2)

    def migrate(self, br_new: part.BlockedRatings, *,
                mesh: Union[Optional[Mesh], str] = "keep"):
        """Swap in a re-packing for a *different worker set* (from
        ``partition.repack_transition``) — the engine half of an elastic
        resize / failure recovery.

        The global factors are gathered off the old shards and
        re-scattered into the new layout; no arithmetic touches them, so
        every surviving row's and item's W/H values are preserved bit
        for bit (only their shard placement changes).  ``epoch_idx`` is
        untouched: the step-size schedule continues across the
        transition, which is what makes an elastic run's history
        exactly serializable epoch by epoch.  Pass ``mesh=`` (a Mesh or
        ``None``) to re-target the SPMD executor onto the new worker
        set's device mesh; the default keeps the current mesh (local
        emulation, where worker count is purely a layout property).
        """
        if (br_new.m, br_new.n) != (self.br.m, self.br.n):
            raise ValueError(
                f"migrate() cannot change the problem shape: "
                f"({br_new.m}, {br_new.n}) != ({self.br.m}, {self.br.n})")
        W, H = self.factors()
        if mesh != "keep":
            self.mesh = mesh
        if self.mesh is not None and self.mesh.devices.size != br_new.p:
            raise ValueError(
                f"mesh has {self.mesh.devices.size} devices but the new "
                f"packing wants p={br_new.p}; pass a re-packed mesh")
        self._load_pack(br_new)
        self.init_factors(W, H)

    def init_factors(self, W0: np.ndarray, H0: np.ndarray):
        self.last_finite = True     # fresh factors, fresh sentinel
        Ws, Hs = part.shard_factors(W0, H0, self.br)
        # mixed policies store the shards low-precision (fp32 policies
        # take the historical no-cast path)
        sd = self.policy.storage_dtype if self.policy.mixed else None
        self.Ws = jnp.asarray(Ws, dtype=sd)
        self.Hs = jnp.asarray(Hs, dtype=sd)
        if self.mesh is not None:
            sh = NamedSharding(self.mesh, P(self.mesh.axis_names[0]))
            self.Ws = jax.device_put(self.Ws, sh)
            self.Hs = jax.device_put(self.Hs, sh)

    def run_epoch(self):
        # the update accumulates in compute_dtype under a mixed policy,
        # so lr must be materialized there (a bf16-rounded lr would leak
        # storage precision into the fp32 accumulation)
        lr = jnp.asarray(self.stepsize(self.epoch_idx),
                         dtype=self.policy.compute_dtype or self.Ws.dtype)
        lam = self.lam
        if self.mesh is None:
            rows, cols, vals, mask = self._cell_data()
            self.Ws, self.Hs = _local_epoch(
                self.Ws, self.Hs, rows, cols, vals, mask,
                self._perm_src, lr, lam, policy=self.policy,
                entry=self._entry)
        else:
            self.Ws, self.Hs = self._spmd_epoch(
                self.Ws, self.Hs, self.rows, self.cols, self.vals,
                self.mask, lr)
        self.epoch_idx += 1

    def factors(self):
        return part.unshard_factors(np.asarray(self.Ws), np.asarray(self.Hs),
                                    self.br)

    # ------------------------------------------------------------------ #
    def _eval_args(self, test):
        """Device-resident (ridx, cidx, vals) for the sharded RMSE;
        memoized per test set so train() pays the host->device copy of
        the (small) index arrays once, not per call.

        The memo key is the *content* of the test tuple — component
        arrays matched by identity first, then by value — not the tuple
        object itself: ``StreamingSession`` / repeated ``solve()`` calls
        rebuild an equal ``(rows, cols, vals)`` tuple around the same
        (or equal) arrays every round, and keying on tuple identity made
        every such round silently re-upload the eval indices."""
        key = tuple(np.asarray(a) for a in test)
        if self._eval_cache is not None:
            cached, args = self._eval_cache
            if len(cached) == len(key) and all(
                    a is b or (a.shape == b.shape and a.dtype == b.dtype
                               and np.array_equal(a, b))
                    for a, b in zip(cached, key)):
                return args
        br = self.br
        rows, cols = key[0], key[1]
        ridx = (br.row_owner[rows].astype(np.int64) * br.m_local
                + br.row_local[rows])
        cidx = (br.col_block[cols].astype(np.int64) * br.n_local
                + br.col_local[cols])
        args = (jnp.asarray(ridx), jnp.asarray(cidx),
                jnp.asarray(key[2], jnp.float32))
        if self.mesh is not None:
            rep = NamedSharding(self.mesh, P())
            args = tuple(jax.device_put(a, rep) for a in args)
        self._eval_cache = (key, args)
        return args

    def eval_rmse(self, test) -> float:
        """Test RMSE without leaving the device (no factors() round-trip).

        At epoch boundaries every nomadic H block is back home (every
        schedule's final transition routes block b to worker b —
        ``OwnershipSchedule.perm_sources``), so shard q holds exactly
        block q and the flat-index gather reads the same values as the
        unsharded matrix.
        """
        ridx, cidx, vals = self._eval_args(test)
        return float(_sharded_rmse(self.Ws, self.Hs, ridx, cidx, vals))

    def train(self, epochs: int, test=None, verbose=False, *,
              record_every: int = 1, dispatch: str = "loop",
              fuse_epochs: Optional[int] = None):
        """Run ``epochs`` epochs, recording the held-out RMSE every
        ``record_every`` epochs (plus always the final one).

        ``dispatch`` selects the driver (DESIGN.md §9):

        * ``"loop"``  — the historical per-epoch Python loop: one device
          dispatch plus one blocking ``float(rmse)`` sync per epoch.
        * ``"fused"`` — the whole call (or ``fuse_epochs``-sized blocks
          of it) as a single jitted ``lax.scan`` over epochs with the
          learning-rate array precomputed on the host
          (``PowerSchedule.values``) and the trace recorded on device:
          one host sync per block.  Bitwise-identical W/H/trace to the
          loop path (asserted across kernels, executors and schedules in
          tests/test_driver.py).  With ``verbose`` and no explicit
          ``fuse_epochs``, blocks default to one epoch so the progress
          prints stay live.

        Returns the legacy ``[(epoch_idx, rmse), ...]`` trace list.
        """
        epochs = int(epochs)
        if record_every < 1:
            raise ValueError(
                f"record_every must be >= 1, got {record_every}")
        if dispatch not in ("loop", "fused"):
            raise ValueError(
                f"dispatch={dispatch!r} not in ('loop', 'fused')")
        obs.count("train.calls")
        obs.count("train.epochs", epochs)
        with obs.span("repro.train", epochs=epochs):
            if dispatch == "fused":
                return self._train_fused(epochs, test, verbose,
                                         record_every, fuse_epochs)
            return self._train_loop(epochs, test, verbose, record_every)

    def _train_loop(self, epochs: int, test, verbose, record_every: int):
        """Loop dispatch: one device program and one host sync per
        epoch."""
        recs = set(_record_slots(epochs, record_every, test is not None))
        with obs.span("repro.train.stage"):
            eval_args = self._eval_args(test) if recs else None
        trace = []
        for i in range(1, epochs + 1):
            with obs.span("repro.train.dispatch"):
                self.run_epoch()
            if i in recs:
                with obs.span("repro.train.sync"):
                    r = float(_sharded_rmse(self.Ws, self.Hs, *eval_args))
                trace.append((self.epoch_idx, r))
                if verbose:
                    print(f"epoch {self.epoch_idx}: test rmse {r:.4f}")
        # divergence sentinel: non-finite entries are absorbing through
        # SGD updates, so one end-of-call check is exact (and the only
        # extra sync the loop path pays)
        if epochs > 0:
            with obs.span("repro.train.sync"):
                self.last_finite = bool(jnp.isfinite(self.Ws).all()
                                        & jnp.isfinite(self.Hs).all())
        return trace

    @property
    def stream_counts(self):
        """``(slots, updates)`` of the fused driver's epoch stream: its
        length in ``p``-wide slots and its real (unmasked) entries, the
        updates one epoch applies.  None until a fused call builds it."""
        return self._stream_counts

    def _build_stream(self):
        """The flat epoch stream (``partition.epoch_stream``) on the
        device.  With tracing on, the upload span waits for the copy to
        land, so it holds the transfer and not only its enqueue."""
        with obs.span("repro.stream.build"):
            R, C, V, M = part.epoch_stream(self.br)
        with obs.span("repro.stream.upload"):
            self._stream = tuple(jnp.asarray(a.reshape(-1))
                                 for a in (R, C, V, M))
            if obs.enabled():
                jax.block_until_ready(self._stream)
        self._stream_counts = (int(R.shape[0]), int(np.count_nonzero(M)))
        obs.count("stream.slots", self._stream_counts[0])
        obs.count("stream.updates", self._stream_counts[1])

    def _train_fused(self, epochs: int, test, verbose,
                     record_every: int, fuse_epochs: Optional[int]):
        """Fused dispatch: epochs run in ``fuse_epochs``-sized device
        programs (default: all of them in one).  A block boundary is
        also a bitwise-exact resume point — the learning-rate array is
        re-derived from ``epoch_idx`` per block, exactly as a
        warm-started loop run would re-derive its scalars."""
        if fuse_epochs is not None and fuse_epochs < 1:
            raise ValueError(
                f"fuse_epochs must be >= 1 (or None), got {fuse_epochs}")
        # verbose promises live per-epoch progress, but prints can only
        # happen at block boundaries — default to one-epoch blocks then
        # (an explicit fuse_epochs wins; bitwise-identical either way)
        block = fuse_epochs or (1 if verbose else max(epochs, 1))
        start = self.epoch_idx
        recs = _record_slots(epochs, record_every, test is not None)
        with obs.span("repro.train.stage"):
            if recs:
                ridx, cidx, tvals = self._eval_args(test)
            else:
                ridx = cidx = jnp.zeros(0, jnp.int32)
                tvals = jnp.zeros(0, jnp.float32)
        trace = []
        done = 0
        # duck-typed __call__-only schedules (anything that worked on
        # the loop path) fall back to per-epoch evaluation — which is
        # all PowerSchedule.values does anyway
        values = getattr(self.stepsize, "values",
                         lambda start, count: np.asarray(
                             [self.stepsize(start + i)
                              for i in range(count)], dtype=np.float64))
        while done < epochs:
            c = min(block, epochs - done)
            with obs.span("repro.train.stage"):
                lrs = jnp.asarray(values(self.epoch_idx, c),
                                  dtype=self.policy.compute_dtype
                                  or self.Ws.dtype)
                chunk_recs = [i for i in recs if done < i <= done + c]
                pos = np.full(c, -1, dtype=np.int32)
                for j, i in enumerate(chunk_recs):
                    pos[i - done - 1] = j
                rec_pos = jnp.asarray(pos)
            stream = (self.mesh is None
                      and self.policy.impl in _STREAM_IMPLS)
            if stream and self._stream is None:
                self._build_stream()
            # a first call traces and compiles inside the dispatch span
            with obs.span("repro.train.dispatch"):
                if stream:
                    self.Ws, self.Hs, tr, ok = _local_train_stream(
                        self.Ws, self.Hs, self._stream, lrs, rec_pos,
                        self.lam, ridx, cidx, tvals, policy=self.policy,
                        entry=self._entry, n_rec=len(chunk_recs))
                elif self.mesh is None:
                    data = (*self._cell_data(), self._perm_src)
                    self.Ws, self.Hs, tr, ok = _local_train_steps(
                        self.Ws, self.Hs, data, lrs, rec_pos, self.lam,
                        ridx, cidx, tvals, policy=self.policy,
                        entry=self._entry, n_rec=len(chunk_recs))
                else:
                    data = (self.rows, self.cols, self.vals, self.mask)
                    self.Ws, self.Hs, tr, ok = self._spmd_train(
                        self.Ws, self.Hs, data, lrs, rec_pos, self.lam,
                        ridx, cidx, tvals, policy=self.policy,
                        n_rec=len(chunk_recs))
            self.epoch_idx += c
            done += c
            with obs.span("repro.train.sync"):
                tr = np.asarray(tr)        # the block's single host sync
                self.last_finite = bool(ok)   # rides the same sync
            for j, i in enumerate(chunk_recs):
                trace.append((start + i, float(tr[j])))
                if verbose:
                    print(f"epoch {start + i}: test rmse {tr[j]:.4f}")
        return trace


_fit_deprecation_warned = False


def fit(rows, cols, vals, m, n, k, p, *, lam=0.05,
        schedule: Optional[PowerSchedule] = None, epochs=10, seed=0,
        test=None, mesh=None, impl="xla", balanced=True, sub_blocks=1,
        verbose=False):
    """Deprecated one-call NOMAD matrix completion.

    Thin shim over ``repro.api.solve(problem, NomadConfig(...))`` — same
    arguments, bitwise-identical ``(W, H, trace)``.  New code should build
    an ``MCProblem`` and call ``solve`` (which also returns timings and a
    resumable ``FitResult``).
    """
    global _fit_deprecation_warned
    if not _fit_deprecation_warned:
        warnings.warn(
            "nomad.fit() is deprecated; use repro.api.solve(problem, "
            "NomadConfig(...)) instead", DeprecationWarning, stacklevel=2)
        _fit_deprecation_warned = True
    from ..api import MCProblem, NomadConfig, solve
    problem = MCProblem(rows=rows, cols=cols, vals=vals, m=m, n=n,
                        test=test)
    config = NomadConfig(k=k, lam=lam, epochs=epochs, seed=seed,
                         stepsize=schedule, p=p, kernel=impl,
                         balanced=balanced, sub_blocks=sub_blocks)
    res = solve(problem, config, mesh=mesh, verbose=verbose)
    return res.W, res.H, res.trace
