"""Pallas TPU kernels for the NOMAD block SGD update.

TPU adaptation of the paper's compute hot spot (Algorithm 1, lines 16-21):
sequential stochastic gradient updates over the ratings of one
(worker x item-block) cell.  The paper exploits L3-cache locality by
aligning per-thread memory to cache lines (§3.5); the TPU analogue is
explicit HBM->VMEM blocking:

  * the W tile (m_tile x k) and H tile (n_tile x k) stay *resident in VMEM*
    across the whole grid (constant index_map, in/out aliased),
  * the rating stream (rows/cols/vals/mask) is blocked along nnz and
    streamed through SMEM chunk by chunk (the grid dimension): every
    update reads its row/column index and value as scalars, then loads
    and stores one 128-lane factor row with a dynamic ``pl.ds(i, 1)``
    slice,
  * k is padded to 128 (VPU lane width); padding columns start at zero and
    provably stay zero under the SGD update, so results equal the k<=128
    reference exactly.

All three kernels run one shared update body (:func:`_sgd_chunk`):

  * ``nomad_sgd_block`` — the cell's flat rating list, strictly in order;
    NOMAD's serializability is preserved bit-for-bit.
  * ``nomad_sgd_waves_block`` — the conflict-free *wave* layout from
    ``partition.pack`` (DESIGN.md §3), flattened wave-major.  Within a
    wave no row or column repeats, so running its lanes one after the
    other is exactly the batched wave update.
  * ``nomad_sgd_waves_grid`` — a batch of conflict-free cells in one
    ``pallas_call`` with grid ``(cell, chunk)``.

The resident tiles bound the cell shape a kernel can hold:
:func:`vmem_bytes` is the VMEM the blocks take, which must fit
:data:`VMEM_BYTES`.  Compiled (``interpret=False``) calls check it up
front and raise ``ValueError`` for a cell that does not fit, instead of
handing Mosaic a kernel it refuses; a bf16/fp16 factor tile is refused
the same way, since Mosaic cannot lower a one-row dynamic slice of a
packed dtype.  Interpret mode (CPU) has neither limit.  At the Netflix
shape one worker's W shard alone (331k rows at p=8) is beyond VMEM, so
these kernels serve small cells; the main path runs the XLA stream.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref as _ref

LANE = 128

#: VMEM of one TPU v5e TensorCore (bytes); the compiler refuses a kernel
#: whose blocks exceed it
VMEM_BYTES = 128 << 20

#: copies of the resident factor tiles a kernel holds in VMEM, rounded up
#: from the compiler's own accounting for v5e: the single-program kernels
#: keep one aliased copy (limit measured at ~200k rows of 128 lanes; 2
#: leaves margin), the grid kernel double-buffers its per-cell input and
#: output blocks (limit measured at ~34k rows; 8 leaves margin)
_TILE_COPIES = {False: 2, True: 8}


def vmem_bytes(m_tile: int, n_tile: int, k: int, *,
               grid: bool = False) -> int:
    """VMEM the resident f32 W/H blocks of one kernel program take: both
    tiles lane-padded to 128, times the copies the kernel keeps."""
    kp = -(-max(k, 1) // LANE) * LANE
    return _TILE_COPIES[grid] * (m_tile + n_tile) * kp * 4


def fits_vmem(m_tile: int, n_tile: int, k: int, *,
              grid: bool = False) -> bool:
    """Whether a cell's f32 factor tiles fit one core's VMEM."""
    return vmem_bytes(m_tile, n_tile, k, grid=grid) <= VMEM_BYTES


def _check_compiled(m_tile, n_tile, k, dtype, interpret, name, grid=False):
    """Refuse, before lowering, a compiled kernel Mosaic cannot hold."""
    if interpret:
        return
    if jnp.dtype(dtype) != jnp.float32:
        raise ValueError(
            f"{name}: compiled kernel needs float32 factor tiles, got "
            f"{jnp.dtype(dtype).name} (Mosaic cannot prove a one-row "
            "dynamic slice of a packed dtype aligned); use impl='xla' or "
            "'wave'")
    need = vmem_bytes(m_tile, n_tile, k, grid=grid)
    if need > VMEM_BYTES:
        raise ValueError(
            f"{name}: resident factor tiles ({m_tile} + {n_tile} rows x "
            f"k={k}) need {need / 2**20:.1f} MiB of VMEM > "
            f"{VMEM_BYTES / 2**20:.0f} MiB; use impl='xla' or 'wave' for "
            "cells this large")


def _sgd_chunk(scalars_ref, rows_ref, cols_ref, vals_ref, mask_ref,
               W_in_ref, H_in_ref, W_ref, H_ref, *, cdtype, step_axis):
    """One grid step: apply a chunk of sequential SGD updates in VMEM.

    The rating refs are SMEM blocks of the chunk (indices and the int32
    mask as scalars; values carried as f32 and cast to ``cdtype``, which
    is exact because they were rounded to ``cdtype`` by the wrapper).
    With ``cdtype`` wider than the factor tiles (mixed precision), each
    update upcasts the two rows, runs the SGD step in ``cdtype`` and
    downcasts on store — one rounding per touched row per update,
    matching the :mod:`..kernels.ref` ``compute_dtype`` contract.
    """
    # On the first step of a cell, copy the (aliased) inputs into the
    # outputs; later steps keep updating the same resident VMEM block.
    @pl.when(pl.program_id(step_axis) == 0)
    def _init():
        W_ref[...] = W_in_ref[...]
        H_ref[...] = H_in_ref[...]

    lr = scalars_ref[0].astype(cdtype)
    lam = scalars_ref[1].astype(cdtype)
    sd = W_ref.dtype

    def body(t, carry):
        @pl.when(mask_ref[t] != 0)
        def _update():
            i = rows_ref[t]
            j = cols_ref[t]
            a = vals_ref[t].astype(cdtype)
            w = W_ref[pl.ds(i, 1), :].astype(cdtype)
            h = H_ref[pl.ds(j, 1), :].astype(cdtype)
            err = a - jnp.sum(w * h, axis=-1, keepdims=True)
            W_ref[pl.ds(i, 1), :] = (w - lr * (-err * h + lam * w)).astype(sd)
            H_ref[pl.ds(j, 1), :] = (h - lr * (-err * w + lam * h)).astype(sd)
        return carry

    jax.lax.fori_loop(0, rows_ref.shape[0], body, 0)


#: rating-chunk granule: XLA tiles a rank-1 int32 array of 1024 or more
#: entries by 1024, and a streamed block must match that tiling
CHUNK_GRANULE = 1024


def _chunk(size: int) -> int:
    """Rating-chunk length: ``size`` rounded up to :data:`CHUNK_GRANULE`
    (chunk boundaries do not change the serial order, so this is
    free)."""
    return -(-max(size, 1) // CHUNK_GRANULE) * CHUNK_GRANULE


def _prep(W, H, rows, cols, vals, mask, lr, lam, chunk, accum_fp32):
    """Pad k to the lane width and the rating axis (last) to a chunk
    multiple with masked no-ops; rating arrays become int32 / f32 SMEM
    operands.  Returns the padded operands, the chunk count and the
    compute dtype."""
    dtype = W.dtype
    cdtype = jnp.float32 if accum_fp32 else dtype
    k = W.shape[-1]
    nnz = rows.shape[-1]
    k_pad = (-k) % LANE
    nnz_pad = (-nnz) % chunk
    lead = [(0, 0)] * (W.ndim - 1)
    rpad = [(0, 0)] * (rows.ndim - 1) + [(0, nnz_pad)]
    Wp = jnp.pad(W, lead + [(0, k_pad)])
    Hp = jnp.pad(H, lead + [(0, k_pad)])
    rows_p = jnp.pad(rows.astype(jnp.int32), rpad)
    cols_p = jnp.pad(cols.astype(jnp.int32), rpad)
    vals_p = jnp.pad(vals.astype(cdtype).astype(jnp.float32), rpad)
    mask_p = jnp.pad(mask.astype(jnp.int32), rpad)
    scalars = jnp.array([lr, lam], dtype=cdtype).astype(jnp.float32)
    n_chunks = max(1, (nnz + nnz_pad) // chunk)
    ops = (scalars, rows_p, cols_p, vals_p, mask_p, Wp, Hp)
    return ops, n_chunks, cdtype


def _smem(block_shape, index_map):
    return pl.BlockSpec(block_shape, index_map, memory_space=pltpu.SMEM)


@functools.partial(
    jax.jit,
    static_argnames=("chunk", "interpret", "accum_fp32"))
def nomad_sgd_block(W, H, rows, cols, vals, mask, lr, lam, *,
                    chunk: int = 1024, interpret: bool = True,
                    accum_fp32: bool = False):
    """Pallas-accelerated NOMAD block update.  Same contract as
    :func:`repro.kernels.ref.block_sgd_ref`.

    ``interpret=True`` (default here) runs the kernel body in Python on CPU
    — the validation mode for this repo; on real TPU pass ``False``.
    ``accum_fp32`` enables the mixed-precision path (fp32 accumulation
    over low-precision factor storage).
    """
    m_tile, k = W.shape
    n_tile = H.shape[0]
    _check_compiled(m_tile, n_tile, k, W.dtype, interpret,
                    "nomad_sgd_block")
    chunk = _chunk(chunk)
    ops, n_chunks, cdtype = _prep(W, H, rows, cols, vals, mask, lr, lam,
                                  chunk, accum_fp32)
    kp = ops[-1].shape[-1]
    stream = _smem((chunk,), lambda s: (s,))
    w_spec = pl.BlockSpec((m_tile, kp), lambda s: (0, 0))   # resident
    h_spec = pl.BlockSpec((n_tile, kp), lambda s: (0, 0))   # resident
    W_out, H_out = pl.pallas_call(
        functools.partial(_sgd_chunk, cdtype=cdtype, step_axis=0),
        grid=(n_chunks,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),     # lr, lam
                  stream, stream, stream, stream, w_spec, h_spec],
        out_specs=[w_spec, h_spec],
        out_shape=[jax.ShapeDtypeStruct((m_tile, kp), W.dtype),
                   jax.ShapeDtypeStruct((n_tile, kp), W.dtype)],
        input_output_aliases={5: 0, 6: 1},
        interpret=interpret,
    )(*ops)
    return W_out[:, :k], H_out[:, :k]


@functools.partial(
    jax.jit,
    static_argnames=("wave_chunk", "interpret", "accum_fp32"))
def nomad_sgd_waves_block(W, H, rows, cols, vals, mask, lr, lam, *,
                          wave_chunk: int = 8, interpret: bool = True,
                          accum_fp32: bool = False):
    """Pallas NOMAD block update over the conflict-free wave layout.
    Same contract as :func:`repro.kernels.ref.block_sgd_waves`:
    rows/cols/vals/mask are (n_waves, wave_width) wave layouts from
    ``partition.pack``.

    The waves are flattened wave-major (the cell's serial order) and
    streamed ``wave_chunk`` waves per grid step through the sequential
    update body; padded lanes are masked no-ops.
    """
    wave_width = rows.shape[-1]
    flat = [a.reshape(-1) for a in (rows, cols, vals, mask)]
    return nomad_sgd_block(W, H, *flat, lr, lam,
                           chunk=wave_chunk * wave_width,
                           interpret=interpret, accum_fp32=accum_fp32)


@functools.partial(
    jax.jit,
    static_argnames=("wave_chunk", "interpret", "accum_fp32"))
def nomad_sgd_waves_grid(Ws, Hs, rows, cols, vals, mask, lr, lam, *,
                         wave_chunk: int = 8, interpret: bool = True,
                         accum_fp32: bool = False):
    """Occupancy-oriented grid formulation of the wave kernel: one
    ``pallas_call`` updates a whole batch of conflict-free cells.

    Ws: (p, m_tile, k)  Hs: (p, n_tile, k); rows/cols/vals/mask:
    (p, n_waves, wave_width) — the ``p`` cells of one schedule step,
    whose W shards and H blocks are pairwise disjoint (the
    generalized-diagonal invariant), batched along a leading axis.

    The grid is ``(p, n_chunks)``: cells fill the outer dimension, and
    each cell's wave stream is cut into ``wave_chunk``-wave chunks along
    the inner one, with the cell's factor blocks resident across its
    chunks (the inner grid dimension iterates fastest, so each block is
    written back once, when the cell advances).  Per-cell semantics are
    identical to ``nomad_sgd_waves_block`` — asserted bitwise in
    tests/test_kernels.py.
    """
    p, m_tile, k = Ws.shape
    n_tile = Hs.shape[1]
    _check_compiled(m_tile, n_tile, k, Ws.dtype, interpret,
                    "nomad_sgd_waves_grid", grid=True)
    chunk = _chunk(wave_chunk * rows.shape[-1])
    flat = [a.reshape(p, -1) for a in (rows, cols, vals, mask)]
    ops, n_chunks, cdtype = _prep(Ws, Hs, *flat, lr, lam, chunk,
                                  accum_fp32)
    # cell c's padded stream is chunks [c * n_chunks, (c + 1) * n_chunks)
    # of one flat rank-1 array
    ops = (ops[0], *(a.reshape(-1) for a in ops[1:5]), *ops[5:])
    kp = ops[-1].shape[-1]
    stream = _smem((chunk,), lambda c, s: (c * n_chunks + s,))
    w_spec = pl.BlockSpec((None, m_tile, kp), lambda c, s: (c, 0, 0))
    h_spec = pl.BlockSpec((None, n_tile, kp), lambda c, s: (c, 0, 0))
    W_out, H_out = pl.pallas_call(
        functools.partial(_sgd_chunk, cdtype=cdtype, step_axis=1),
        grid=(p, n_chunks),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),     # lr, lam
                  stream, stream, stream, stream, w_spec, h_spec],
        out_specs=[w_spec, h_spec],
        out_shape=[jax.ShapeDtypeStruct((p, m_tile, kp), Ws.dtype),
                   jax.ShapeDtypeStruct((p, n_tile, kp), Ws.dtype)],
        input_output_aliases={5: 0, 6: 1},
        interpret=interpret,
    )(*ops)
    return W_out[:, :, :k], H_out[:, :, :k]


block_sgd_ref = _ref.block_sgd_ref  # re-export for convenience
block_sgd_waves = _ref.block_sgd_waves  # re-export for convenience
