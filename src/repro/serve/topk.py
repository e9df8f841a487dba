"""Batched top-k scoring: ``scores = W[u_batch] @ H.T`` over the item
catalog, tiled so H streams through the scorer while a running top-k is
merged across tiles.

This is the serving hot loop (ROADMAP ``[serve]``): a recommendation
query for user ``u`` is the ``k_top`` largest entries of one row of the
reconstructed matrix.  Materializing the full ``(batch, n_items)`` score
matrix at catalog scale (100k+ items) would blow past on-chip memory, so
both implementations tile the catalog:

* ``_topk_xla``     — ``lax.scan`` over item tiles; per tile a
  ``(U, k_rank) @ (k_rank, T)`` matmul, ``lax.top_k`` tile candidates,
  and a ``lax.top_k`` merge of (running ∥ candidates).
* ``_topk_pallas``  — a Pallas kernel with the user-batch factor tile
  *resident in VMEM* across the whole grid while H tiles stream through
  (the serving twin of the training kernels' blocking scheme,
  DESIGN.md §5); the running top-k lives in the resident output block
  and is merged in-kernel by an exact iterative (score, id) selection.

Selection is **exact over the scores the scorer computes**, with
deterministic tie-breaking: ties in score resolve to the *smaller item
id*, always.  The XLA path gets this from ``lax.top_k``'s
lower-index-first tie rule plus an ordering invariant (running entries
always carry smaller ids than the current tile's candidates, and within
each part equal scores appear in id-ascending order — so position order
inside the merged array *is* id order); the Pallas path selects each
slot explicitly by (max score, then min id).

The scores themselves are f32 dot products whose summation order
depends on the matmul shape and the backend, so two scorers (or one
scorer and the dense oracle) may differ in the last bits.  The contract
against :func:`topk_dense_oracle` (host float64) is therefore: every
score within the dot product's forward error bound
``gamma_k * sum_i |w_i h_i|`` of the exact one, and the ids exact except
where the oracle scores of two items lie within that bound of each
other.  Integer-valued factors score exactly in any order, so there
ids, scores and the tie rule are bitwise (property-tested with
engineered ties in tests/test_serve.py).

Dispatch goes through :class:`repro.kernels.policy.KernelPolicy`
(``policy.serve_impl``): the Pallas train impls select the Pallas tile
kernel, everything else the XLA path, and ``"auto"`` picks the Pallas
kernel on TPU.  Like the train kernels, the Pallas path runs
``interpret=True`` off-TPU.

Rank padding note: the Pallas path pads ``k_rank`` to the 128-lane VPU
width with zero columns.  Zero summands leave every f32 partial sum
bit-identical (x + 0.0 == x), so the padded dot equals the unpadded one
exactly — the serving analogue of the SGD kernels' zero-invariant lane
padding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..kernels.policy import KernelPolicy

LANE = 128

#: the scorers' matmul precision.  f32 scores are computed at full f32
#: precision on every backend: TPU's default f32 matmul rounds its inputs
#: to bf16, whose score error (~1e-3 relative) would reorder near-tied
#: items against any f32 or f64 reference.
_PRECISION = jax.lax.Precision.HIGHEST

__all__ = ["topk_scores", "topk_scores_filtered", "topk_dense_oracle"]


def topk_dense_oracle(W_u, H, k_top: int, h_scale=None):
    """Dense reference: float64 ``W_u @ H.T`` on the host, stably
    argsorted.

    The scores are computed in float64 from the given factors, so they
    are independent of any device matmul; the ordering is
    ``np.argsort(-scores, kind="stable")``, i.e. score-descending with
    ties broken by smaller item id.  With ``h_scale`` (int8-quantized
    serving) the per-item dequantization scale multiplies the raw score
    after the dot, as in the tiled scorers.  Returns ``(scores, ids)``
    of shape ``(U, k_top)``, scores in float64.
    """
    W_u = np.asarray(jnp.asarray(W_u).astype(jnp.float32), np.float64)
    Hm = np.asarray(jnp.asarray(H).astype(jnp.float32), np.float64)
    scores = W_u @ Hm.T
    if h_scale is not None:
        scores = scores * np.asarray(h_scale, np.float64)[None, :]
    order = np.argsort(-scores, axis=1, kind="stable")[:, :k_top]
    return np.take_along_axis(scores, order, axis=1), \
        order.astype(np.int32)


def topk_scores(W_u, H, k_top: int, *,
                policy: KernelPolicy | str | None = None,
                item_tile: int = 4096, h_scale=None):
    """Top-``k_top`` items for a batch of user factor rows.

    W_u       -- (U, k_rank) gathered user factors
    H         -- (n_items, k_rank) item factors (device-resident)
    k_top     -- list length per user (1 <= k_top <= n_items)
    policy    -- KernelPolicy (or legacy impl string); ``serve_impl``
                 picks the XLA or Pallas tile scorer
    item_tile -- catalog tile width the scorer streams over
    h_scale   -- optional (n_items,) per-row dequantization scales for
                 an int8-quantized ``H`` (``FactorView.h_scale``):
                 scores become ``(W_u @ Hq.T) * h_scale``

    Returns ``(scores, ids)`` — both ``(U, k_top)``, score-descending,
    ties by smaller id; matches :func:`topk_dense_oracle` up to the f32
    score bound (module docstring).
    """
    policy = KernelPolicy.coerce(policy)
    n = int(H.shape[0])
    if not 1 <= k_top <= n:
        raise ValueError(
            f"k_top must lie in [1, n_items={n}], got {k_top}")
    if item_tile < 1:
        raise ValueError(f"item_tile must be >= 1, got {item_tile}")
    if W_u.shape[-1] != H.shape[-1]:
        raise ValueError(
            f"rank mismatch: W_u has k={W_u.shape[-1]}, H has "
            f"k={H.shape[-1]}")
    if policy.serve_impl == "pallas":
        from ..kernels.ops import on_tpu
        return _topk_pallas(W_u, H, h_scale, k_top=k_top,
                            item_tile=item_tile, interpret=not on_tpu())
    return _topk_xla(W_u, H, h_scale, k_top=k_top, item_tile=item_tile)


def topk_scores_filtered(W_u, H, k_top: int, *, exclude,
                         policy: KernelPolicy | str | None = None,
                         item_tile: int = 4096, h_scale=None):
    """:func:`topk_scores` with exact per-user candidate filtering:
    ``exclude[u]`` is an array of item rows user ``u`` must not be
    recommended (typically ``FactorView.rated_for`` — the already-rated
    items of the published version).

    Exactness by over-fetch: the scorer retrieves
    ``min(n, k_top + max_u |exclude[u]|)`` candidates — enough that
    even a user whose entire exclusion set lands in the prefix still
    has ``k_top`` admissible items below it — then drops each user's
    excluded ids on the host and keeps the first ``k_top``.  The
    surviving candidates are in exactly the total order (score desc, id
    asc) of the unfiltered scorer, so the result equals a dense oracle
    over the filtered catalog (asserted with engineered ties in
    tests/test_serve.py).  Users with fewer than ``k_top`` admissible
    items pad the tail with the sentinel id ``n`` and ``-inf`` score.
    """
    n = int(H.shape[0])
    U = int(W_u.shape[0])
    exclude = list(exclude)
    if len(exclude) > U:
        raise ValueError(
            f"exclude has {len(exclude)} entries for {U} users")
    max_ex = max((len(e) for e in exclude), default=0)
    kk = min(n, k_top + max_ex)
    s, ids = topk_scores(W_u, H, kk, policy=policy, item_tile=item_tile,
                         h_scale=h_scale)
    s = np.asarray(s)
    ids = np.asarray(ids)
    out_s = np.full((U, k_top), -np.inf, dtype=s.dtype)
    out_i = np.full((U, k_top), n, dtype=np.int32)
    for u in range(U):
        ex = (np.asarray(exclude[u], dtype=np.int64)
              if u < len(exclude) else np.zeros(0, np.int64))
        keep = ~np.isin(ids[u], ex) & (ids[u] < n)
        sel = np.flatnonzero(keep)[:k_top]
        out_s[u, : len(sel)] = s[u, sel]
        out_i[u, : len(sel)] = ids[u, sel]
    return out_s, out_i


# --------------------------------------------------------------------- #
# XLA path: scan over catalog tiles, lax.top_k merge                      #
# --------------------------------------------------------------------- #

@functools.partial(jax.jit, static_argnames=("k_top", "item_tile"))
def _topk_xla(W_u, H, h_scale, *, k_top: int, item_tile: int):
    U, _ = W_u.shape
    n = H.shape[0]
    T = min(item_tile, max(n, 1))
    n_tiles = -(-n // T)
    Hp = jnp.pad(H, ((0, n_tiles * T - n), (0, 0)))
    tiles = Hp.reshape(n_tiles, T, -1)
    bases = (jnp.arange(n_tiles, dtype=jnp.int32) * T)
    kk = min(k_top, T)
    if h_scale is not None:
        # padding scale 1.0 — padded scores are masked to -inf anyway
        hs_tiles = jnp.pad(jnp.asarray(h_scale), (0, n_tiles * T - n),
                           constant_values=1.0).reshape(n_tiles, T)
    else:
        hs_tiles = None

    def body(carry, xs):
        run_s, run_i = carry
        if hs_tiles is not None:
            tile, base, hs = xs
            scores = jnp.dot(W_u, tile.astype(W_u.dtype).T,
                             precision=_PRECISION) * hs[None, :]
        else:
            tile, base = xs
            scores = jnp.dot(W_u, tile.T, precision=_PRECISION)  # (U, T)
        ids = base + jnp.arange(T, dtype=jnp.int32)
        # catalog padding (and any genuine -inf score) parks on the
        # sentinel id n, which sorts after every real item; -0.0 becomes
        # +0.0, since top_k orders -0.0 below +0.0 and would break the
        # smaller-id rule for that tie
        scores = jnp.where((ids < n)[None, :], scores, -jnp.inf)
        scores = jnp.where(scores == 0, 0.0, scores)
        cand_s, li = jax.lax.top_k(scores, kk)
        cand_i = jnp.where(jnp.isneginf(cand_s), n, base + li)
        # merge: running ids all precede this tile's ids, and both parts
        # keep equal scores in id-ascending position order, so top_k's
        # lower-position-first tie rule == smaller-id-first
        new_s, sel = jax.lax.top_k(
            jnp.concatenate([run_s, cand_s], axis=1), k_top)
        new_i = jnp.take_along_axis(
            jnp.concatenate([run_i, cand_i], axis=1), sel, axis=1)
        return (new_s, new_i), None

    init = (jnp.full((U, k_top), -jnp.inf, W_u.dtype),
            jnp.full((U, k_top), n, jnp.int32))
    xs = (tiles, bases) if hs_tiles is None else (tiles, bases, hs_tiles)
    (out_s, out_i), _ = jax.lax.scan(body, init, xs)
    return out_s, out_i.astype(jnp.int32)


# --------------------------------------------------------------------- #
# Pallas path: resident user tile + running top-k, H tiles streamed       #
# --------------------------------------------------------------------- #

def _select_topk(cat_s, cat_i, k_top: int, sentinel):
    """Exact (score desc, id asc) selection of ``k_top`` slots out of the
    concatenated (running ∥ tile) candidates — argmax/argmin only, no
    sort primitive, so it lowers anywhere a reduction does."""
    out_s, out_i = [], []
    avail = jnp.ones(cat_s.shape, jnp.bool_)
    for _ in range(k_top):
        masked_s = jnp.where(avail, cat_s, -jnp.inf)
        best_s = jnp.max(masked_s, axis=1, keepdims=True)
        at_best = (masked_s == best_s) & avail
        masked_i = jnp.where(at_best, cat_i, sentinel)
        best_i = jnp.min(masked_i, axis=1, keepdims=True)
        # ids are unique across (running ∥ tile), so this picks one slot
        # per row — except at the all-sentinel tail, where clearing every
        # sentinel copy at once is harmless (they are interchangeable)
        avail = avail & ~(at_best & (cat_i == best_i))
        out_s.append(best_s[:, 0])
        out_i.append(best_i[:, 0])
    return jnp.stack(out_s, axis=1), jnp.stack(out_i, axis=1)


def _topk_kernel(scalars_ref, Wu_ref, Ht_ref, *rest, k_top: int,
                 tile: int, scaled: bool = False):
    if scaled:
        hs_ref, s_ref, i_ref = rest
    else:
        hs_ref = None
        s_ref, i_ref = rest
    step = pl.program_id(0)
    n = scalars_ref[0]

    @pl.when(step == 0)
    def _init():
        s_ref[...] = jnp.full_like(s_ref[...], -jnp.inf)
        i_ref[...] = jnp.full_like(i_ref[...], n)

    U = Wu_ref.shape[0]
    if scaled:
        # int8 item tile: dequantize the *score* (one multiply per
        # element, after the dot) instead of the tile (T x k multiplies)
        scores = jnp.dot(Wu_ref[...], Ht_ref[...].astype(Wu_ref.dtype).T,
                         precision=_PRECISION,
                         preferred_element_type=s_ref.dtype)
        scores = scores * hs_ref[...][None, :]
    else:
        scores = jnp.dot(Wu_ref[...], Ht_ref[...].T, precision=_PRECISION,
                         preferred_element_type=s_ref.dtype)     # (U, T)
    ids = step * tile + jax.lax.broadcasted_iota(jnp.int32, (U, tile), 1)
    scores = jnp.where(ids < n, scores, -jnp.inf)
    ids = jnp.where(ids < n, ids, n)
    new_s, new_i = _select_topk(
        jnp.concatenate([s_ref[...], scores], axis=1),
        jnp.concatenate([i_ref[...], ids], axis=1),
        k_top, sentinel=n)
    s_ref[...] = new_s
    i_ref[...] = new_i


@functools.partial(jax.jit,
                   static_argnames=("k_top", "item_tile", "interpret"))
def _topk_pallas(W_u, H, h_scale, *, k_top: int, item_tile: int,
                 interpret: bool = True):
    U, kr = W_u.shape
    n = H.shape[0]
    T = min(item_tile, max(n, 1))
    n_tiles = -(-n // T)
    k_pad = (-kr) % LANE
    Wp = jnp.pad(W_u, ((0, 0), (0, k_pad)))
    Hp = jnp.pad(H, ((0, n_tiles * T - n), (0, k_pad)))
    scalars = jnp.array([n], jnp.int32)
    kp = kr + k_pad
    scaled = h_scale is not None

    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),            # scalars
        pl.BlockSpec((U, kp), lambda s: (0, 0)),          # W_u resident
        pl.BlockSpec((T, kp), lambda s: (s, 0)),          # H streamed
    ]
    operands = [scalars, Wp, Hp]
    if scaled:
        hs_p = jnp.pad(jnp.asarray(h_scale), (0, n_tiles * T - n),
                       constant_values=1.0)
        in_specs.append(pl.BlockSpec((T,), lambda s: (s,)))  # scales
        operands.append(hs_p)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(n_tiles,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((U, k_top), lambda s: (0, 0)),       # running s
            pl.BlockSpec((U, k_top), lambda s: (0, 0)),       # running ids
        ],
    )

    out_s, out_i = pl.pallas_call(
        functools.partial(_topk_kernel, k_top=k_top, tile=T,
                          scaled=scaled),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((U, k_top), W_u.dtype),
            jax.ShapeDtypeStruct((U, k_top), jnp.int32),
        ],
        interpret=interpret,
    )(*operands)
    return out_s, out_i
