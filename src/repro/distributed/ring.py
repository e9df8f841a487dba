"""NOMAD-style ring collectives (DESIGN.md §3).

The paper's abstract pattern — *one operand owner-fixed, the other nomadic
around a ring, owner computes, communication overlaps compute* —
instantiated as collective matmuls:

* ``ring_ag_matmul``  — computes ``allgather(X) @ W_local`` without ever
  materializing the gathered X: the X shard circulates via ppermute while
  each owner multiplies it against its fixed weight shard.  The permute of
  step s+1 is independent of the matmul of step s, so the XLA latency-
  hiding scheduler overlaps them (collective-permute-start/done straddle
  the dot in the compiled HLO — verified in tests/benchmarks).
* ``ring_rs_matmul``  — the reduce-scatter dual: partial products stay
  owner-fixed, the *accumulator* is nomadic.

These are the beyond-paper building blocks used in the §Perf hillclimb as
drop-in replacements for GSPMD's all-gather+matmul pairs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def ring_ag_matmul(x_block, w_local, axis_name: str):
    """Per-shard view (use under shard_map).

    x_block: (m_loc, d) — this shard's rows of X (X sharded on rows over
    ``axis_name``).  w_local: (d, f_loc) — this shard's columns of W.
    Returns y: (m_loc * p, f_loc) = X_full @ w_local, row-ordered.
    """
    p = jax.lax.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % p) for i in range(p)]

    def step(x_cur, _):
        y_i = x_cur @ w_local
        x_next = jax.lax.ppermute(x_cur, axis_name, perm)
        return x_next, y_i

    _, ys = jax.lax.scan(step, x_block, None, length=p)
    # ys[i] is the product with the block that started at (me - i) mod p
    src = jnp.mod(me - jnp.arange(p), p)
    m_loc, f_loc = x_block.shape[0], w_local.shape[1]
    y = jnp.zeros((p, m_loc, f_loc), ys.dtype).at[src].set(ys)
    return y.reshape(p * m_loc, f_loc)


def ring_rs_matmul(x_local, w_local, axis_name: str):
    """Per-shard view (use under shard_map).

    x_local: (m, d_loc), w_local: (d_loc, f): partial product
    ``x_local @ w_local`` summed over shards, with the result scattered
    over rows — i.e. reduce_scatter(X @ W) where the contraction dim is
    sharded.  Returns y: (m / p, f) — this shard's row block of the sum.
    """
    p = jax.lax.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % p) for i in range(p)]
    m, f = x_local.shape[0], w_local.shape[1]
    assert m % p == 0
    m_loc = m // p

    partial = (x_local @ w_local).reshape(p, m_loc, f)

    def step(acc, i):
        # the accumulator held at hop i is destined for row block
        # (me - 1 - i) mod p: add our partial for that block and forward.
        blk = jnp.mod(me - 1 - i, p)
        acc = acc + jnp.take(partial, blk, axis=0)
        acc = jax.lax.ppermute(acc, axis_name, perm)
        return acc, ()

    acc0 = jax.lax.pcast(jnp.zeros((m_loc, f), partial.dtype), axis_name,
                         to="varying")
    acc, _ = jax.lax.scan(step, acc0, jnp.arange(p - 1))
    # after p-1 hops the accumulator in hand is destined for our own
    # block; add our local partial last.
    return acc + jnp.take(partial, me, axis=0)


def ring_ag_matmul_ref(x_block, w_local, axis_name: str):
    """Collective-free reference: explicit all_gather then matmul."""
    x_full = jax.lax.all_gather(x_block, axis_name, axis=0, tiled=True)
    return x_full @ w_local


def ring_rs_matmul_ref(x_local, w_local, axis_name: str):
    y = x_local @ w_local
    return jax.lax.psum_scatter(y, axis_name, scatter_dimension=0,
                                tiled=True)
