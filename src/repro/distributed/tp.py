"""Manual tensor-parallel primitives (§Perf iteration C1).

GSPMD inserts the row-parallel all-reduces on the *f32 pre-convert* dot
outputs (XLA promotes the reduction), doubling the dominant wire term of
every dense cell.  These shard_map versions pin the psum to the
activation dtype (bf16), halving per-layer collective bytes; they are
enabled by ``ModelConfig.tp_collectives='manual'`` and validated against
the GSPMD path in tests/test_distributed.py.

Owner-computes note: this is the NOMAD discipline again — the weight
shard never moves across `model`; only the (much smaller, bf16) partial
activations are combined.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .sharding import ShardingCtx


def _bspec(B: int, ctx: ShardingCtx):
    return ctx.dp if B % ctx.dp_size == 0 else None


def row_parallel_dense(x, w, ctx: ShardingCtx, bias=None):
    """y = x @ w with the contraction dim sharded over `model` and the
    psum performed in x.dtype (bf16), not f32.

    x: (B, S, f) activations sharded P(dp, None, tp);
    w: (f, d) sharded P(tp, dp) (FSDP on the output dim).
    Returns (B, S, d) sharded P(dp, None, None).
    """
    B = x.shape[0]
    bspec = _bspec(B, ctx)
    tp, dp = ctx.tp, ctx.dp

    def fn(x_loc, w_loc):
        w_full = jax.lax.all_gather(w_loc, dp, axis=1, tiled=True)
        part = x_loc @ w_full
        return jax.lax.psum(part.astype(x_loc.dtype), tp)

    y = jax.shard_map(
        fn, mesh=ctx.mesh,
        in_specs=(P(bspec, None, tp), P(tp, dp)),
        out_specs=P(bspec, None, None),
        check_vma=bspec is not None,
    )(x, w)
    if bias is not None:
        y = y + bias
    return y


def col_parallel_dense_2dtp(x, w, ctx: ShardingCtx, bias=None):
    """Decode-path column-parallel matmul that treats BOTH mesh axes as
    tensor-parallel instead of gathering FSDP weight shards per token
    (§Perf iteration C2).

    Baseline decode gathers every layer's weights over dp per step
    (~0.5 GB/layer wire for llama3-405b); here the *activations* move
    instead: all-gather x over dp (~4 MB), contract against the local
    (d/dp, out/tp) weight shard, psum_scatter the partials back over the
    batch — owner-computes for weights, nomadic activations.

    x: (B, S, d) sharded P(dp, None, None); w: (d, out) sharded P(dp, tp).
    Returns (B, S, out) sharded P(dp, None, tp).
    """
    B, S, d = x.shape
    bspec = _bspec(B, ctx)
    tp, dp = ctx.tp, ctx.dp
    dp_axes = dp if isinstance(dp, tuple) else (dp,)
    dp_size = ctx.dp_size
    d_loc = d // dp_size

    def fn(x_loc, w_loc):
        if bspec is None:
            # batch replicated over dp: every shard holds full B already
            x_full = x_loc
        else:
            x_full = jax.lax.all_gather(x_loc, dp, axis=0, tiled=True)
        idx = jax.lax.axis_index(dp_axes)
        x_me = jax.lax.dynamic_slice_in_dim(x_full, idx * d_loc, d_loc,
                                            axis=2)
        part = jnp.einsum("bsd,do->bso", x_me, w_loc)
        if bspec is None:
            return jax.lax.psum(part.astype(x_loc.dtype), dp)
        return jax.lax.psum_scatter(part.astype(x_loc.dtype), dp,
                                    scatter_dimension=0, tiled=True)

    y = jax.shard_map(
        fn, mesh=ctx.mesh,
        in_specs=(P(bspec, None, None), P(dp, tp)),
        out_specs=P(bspec, None, tp),
        check_vma=False,
    )(x, w)
    if bias is not None:
        y = y + bias
    return y


def row_parallel_dense_2dtp(x, w, ctx: ShardingCtx, bias=None):
    """Decode-path row-parallel matmul with NO weight movement (C2b).

    x: (B, S, f) sharded P(dp, None, tp); w: (f, d) sharded P(tp, dp).
    Each (dp=i, tp=j) shard contracts its f-slice against its (f_j, d_i)
    weight block for the FULL batch: all-gather x over dp (KBs), psum the
    partials over tp (bf16), then an all-to-all over dp trades the d
    blocks back for batch blocks.  Returns (B, S, d) sharded P(dp,,).
    """
    B, S, f = x.shape
    bspec = _bspec(B, ctx)
    tp, dp = ctx.tp, ctx.dp

    def fn(x_loc, w_loc):
        if bspec is not None:
            x_full = jax.lax.all_gather(x_loc, dp, axis=0, tiled=True)
        else:
            x_full = x_loc
        part = jnp.einsum("bsf,fd->bsd", x_full, w_loc)
        part = jax.lax.psum(part.astype(x_loc.dtype), tp)  # (B,S,d_loc)
        if bspec is not None:
            return jax.lax.all_to_all(part, dp, split_axis=0,
                                      concat_axis=2, tiled=True)
        return jax.lax.all_gather(part, dp, axis=2, tiled=True)

    y = jax.shard_map(
        fn, mesh=ctx.mesh,
        in_specs=(P(bspec, None, tp), P(tp, dp)),
        out_specs=P(bspec, None, None),
        check_vma=False,
    )(x, w)
    if bias is not None:
        y = y + bias
    return y


def vocab_parallel_embed(table, tokens, ctx: ShardingCtx):
    """Embedding lookup over a vocab-sharded table with a bf16 psum
    instead of GSPMD's f32-promoted gather+all-reduce.

    table: (V, d) sharded P(tp, dp); tokens: (B, S) ints sharded P(dp,).
    """
    B = tokens.shape[0]
    bspec = _bspec(B, ctx)
    tp, dp = ctx.tp, ctx.dp
    V = table.shape[0]
    tp_size = ctx.tp_size
    V_loc = V // tp_size

    def fn(tab_loc, tok):
        tab_full = jax.lax.all_gather(tab_loc, dp, axis=1, tiled=True)
        off = jax.lax.axis_index(tp) * V_loc
        local = tok - off
        valid = (local >= 0) & (local < V_loc)
        emb = jnp.take(tab_full, jnp.clip(local, 0, V_loc - 1), axis=0)
        emb = emb * valid[..., None].astype(emb.dtype)
        return jax.lax.psum(emb, tp)

    return jax.shard_map(
        fn, mesh=ctx.mesh,
        in_specs=(P(tp, dp), P(bspec, None)),
        out_specs=P(bspec, None, None),
        check_vma=bspec is not None,
    )(table, tokens)
