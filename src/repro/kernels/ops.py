"""Jit'd public wrappers around the Pallas kernels.

Dispatch policy: ``impl='auto'`` resolves to the XLA update
(:mod:`repro.kernels.ref`) on every backend — it is the one lowering
that holds a Netflix-width cell (the Pallas kernels keep whole factor
tiles in VMEM; see :mod:`.nomad_sgd`).  The Pallas kernels run only when
chosen; they compile on TPU, run in ``interpret=True`` mode (Python
emulation — correct, slow) on any other backend, and refuse with a
``ValueError`` a compiled cell they cannot hold.

Precision threads through here from :class:`KernelPolicy.dtype_policy`:
``compute_dtype``/``accum_fp32`` select fp32 accumulation over
low-precision factor storage.  With the default fp32 policy no cast is
inserted anywhere — those paths stay bitwise-identical to the historical
kernels (DESIGN.md §13).
"""
from __future__ import annotations

from typing import Optional, Union

import jax

from . import ref
from .nomad_sgd import (nomad_sgd_block, nomad_sgd_waves_block,
                        nomad_sgd_waves_grid)
from .policy import KernelPolicy


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def on_accelerator() -> bool:
    """True on any accelerator backend (TPU or GPU)."""
    return jax.default_backend() in ("tpu", "gpu", "cuda", "rocm")


def _run_wave(W, H, rows, cols, vals, mask, lr, lam, policy):
    return ref.block_sgd_waves(W, H, rows, cols, vals, mask, lr, lam,
                               compute_dtype=policy.compute_dtype)


def _run_wave_pallas(W, H, rows, cols, vals, mask, lr, lam, policy):
    return nomad_sgd_waves_block(W, H, rows, cols, vals, mask, lr, lam,
                                 wave_chunk=policy.wave_chunk,
                                 interpret=not on_tpu(),
                                 accum_fp32=policy.mixed)


def _run_xla(W, H, rows, cols, vals, mask, lr, lam, policy):
    return ref.block_sgd_ref(W, H, rows, cols, vals, mask, lr, lam,
                             compute_dtype=policy.compute_dtype)


def _run_pallas(W, H, rows, cols, vals, mask, lr, lam, policy):
    return nomad_sgd_block(W, H, rows, cols, vals, mask, lr, lam,
                           chunk=policy.chunk, interpret=not on_tpu(),
                           accum_fp32=policy.mixed)


_DISPATCH = {
    "wave": _run_wave,
    "wave_pallas": _run_wave_pallas,
    "xla": _run_xla,
    "pallas": _run_pallas,
}


def _resolve(policy, impl, chunk, wave_chunk):
    if policy is None:
        policy = KernelPolicy(impl=impl, chunk=chunk, wave_chunk=wave_chunk)
    elif isinstance(policy, str):
        policy = KernelPolicy(impl=policy, chunk=chunk,
                              wave_chunk=wave_chunk)
    name = "xla" if policy.impl == "auto" else policy.impl
    return policy, name


def block_sgd(W, H, rows, cols, vals, mask, lr, lam, *,
              policy: Optional[Union[KernelPolicy, str]] = None,
              impl: str = "auto", chunk: int = 1024, wave_chunk: int = 8):
    """NOMAD block SGD update, dispatched through a :class:`KernelPolicy`.

    Callers pass either ``policy=KernelPolicy(...)`` (preferred — validated
    at construction) or the legacy ``impl``/``chunk``/``wave_chunk``
    kwargs, which are coerced into a policy here.  For the sequential
    impls rows/cols/vals/mask are flat ``(nnz,)`` rating lists; for the
    wave impls they are the conflict-free ``(n_waves, wave_width)``
    layouts emitted by ``partition.pack`` (same serial ordering,
    vectorized execution — see DESIGN.md §3).
    """
    policy, name = _resolve(policy, impl, chunk, wave_chunk)
    return _DISPATCH[name](W, H, rows, cols, vals, mask, lr, lam, policy)


def block_sgd_cells(Ws, Hs, rows, cols, vals, mask, lr, lam, *,
                    policy: KernelPolicy):
    """One schedule step's batch of cell updates: ``Ws``/``Hs`` are
    ``(p, m_tile, k)``/``(p, n_tile, k)`` and the rating arrays carry a
    matching leading cell axis.  The cells of a step touch pairwise
    disjoint factor blocks (the generalized-diagonal invariant), so the
    batch axis is free parallelism.

    For ``impl='wave_pallas'`` on TPU with cells whose factor tiles fit
    VMEM (or when ``policy.block_rows`` forces it), the whole batch is one
    ``pallas_call`` with grid ``(p, n_chunks)`` —
    :func:`~.nomad_sgd.nomad_sgd_waves_grid` — so occupancy scales with
    the cell count instead of relying on ``vmap``-of-kernel.  Every
    other impl (and the CPU/interpret fallback) keeps the historical
    ``vmap`` over :func:`block_sgd`, which is bitwise-identical.
    """
    if policy.impl == "wave_pallas" and policy.wants_grid(
            int(Ws.shape[1]), int(Hs.shape[1]), int(Ws.shape[2])):
        return nomad_sgd_waves_grid(
            Ws, Hs, rows, cols, vals, mask, lr, lam,
            wave_chunk=policy.wave_chunk, interpret=not on_tpu(),
            accum_fp32=policy.mixed)
    return jax.vmap(
        lambda W, H, r, c, v, m: block_sgd(W, H, r, c, v, m, lr, lam,
                                           policy=policy)
    )(Ws, Hs, rows, cols, vals, mask)


def flash_attention(q, k, v, *, causal=True, impl: str = "auto",
                    block_q: int = 256, block_k: int = 256):
    """Blockwise causal attention.  impl in {'auto','pallas','xla','dense'}."""
    if impl == "dense":
        return ref.flash_attention_ref(q, k, v, causal=causal)
    if impl == "xla" or (impl == "auto" and not on_tpu()):
        from ..models.attention import chunked_attention
        return chunked_attention(q, k, v, causal=causal)
    from .flash_attn import flash_attention as _fa
    return _fa(q, k, v, causal=causal, block_q=block_q, block_k=block_k,
               interpret=not on_tpu())
