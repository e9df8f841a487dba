"""Mixture-of-Experts FFN with capacity-based dispatch.

NOMAD mapping (DESIGN.md §3): experts are owner-fixed on the `model` mesh
axis, tokens are the nomadic variables.  Activations are replicated over
the `model` axis at this point in the network (Megatron-style TP), so each
expert shard routes the *same* token set, dispatches only the tokens bound
for its local experts, applies them, and contributes a partial output that
a single psum combines — owner-computes, no expert weights ever move.

Rank-within-expert is computed with the sort-based method (argsort by
expert id + segment-relative iota) instead of a (T x E) one-hot cumsum —
O(Tk log Tk) instead of O(T·E) memory, which matters at E=384 (Kimi-K2).

Capacity: C = ceil(T * top_k / E * capacity_factor); overflowing tokens are
dropped (their combine weight is zero), underflowing slots are padded —
standard GShard/Switch semantics, recorded per-layer in the aux outputs.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import layers


def moe_init(key, cfg, dtype):
    d, E, ff = cfg.d_model, cfg.n_experts, cfg.d_expert
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    p = {
        "router": layers.dense_init(kr, d, E, jnp.float32),
        "gate": layers.truncated_normal(kg, (E, d, ff), dtype,
                                        1.0 / (d ** 0.5)),
        "up": layers.truncated_normal(ku, (E, d, ff), dtype,
                                      1.0 / (d ** 0.5)),
        "down": layers.truncated_normal(kd, (E, ff, d), dtype,
                                        1.0 / (ff ** 0.5)),
    }
    if cfg.n_shared_experts:
        p["shared"] = layers.swiglu_init(
            ks, d, cfg.n_shared_experts * ff, dtype)
    return p


def _ranks_by_sort(flat_e: jnp.ndarray, E: int) -> jnp.ndarray:
    """rank of each entry within its expert group (0-based), via argsort."""
    Tk = flat_e.shape[0]
    perm = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[perm]
    idx = jnp.arange(Tk, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_e[1:] != sorted_e[:-1]])
    group_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(is_start, idx, 0))
    rank_sorted = idx - group_start
    return jnp.zeros((Tk,), jnp.int32).at[perm].set(rank_sorted)


def _moe_math(x2d, router_w, wg, wu, wd, cfg, e_offset, E_local):
    """Route + dispatch + expert FFN + combine for experts
    [e_offset, e_offset + E_local).  Returns (partial_out (T, d), aux)."""
    import math
    T, d = x2d.shape
    E, k = cfg.n_experts, cfg.top_k
    C = max(1, math.ceil(T * k / E * cfg.capacity_factor))

    logits = (x2d.astype(jnp.float32) @ router_w).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                  # (T, E)
    topw, topi = jax.lax.top_k(probs, k)                     # (T, k)
    topw = topw / jnp.sum(topw, axis=-1, keepdims=True)      # renormalize

    flat_e = topi.reshape(-1)                                # (T*k,)
    rank = _ranks_by_sort(flat_e, E)                         # (T*k,)
    local = (flat_e >= e_offset) & (flat_e < e_offset + E_local)
    keep = (rank < C) & local
    e_loc = jnp.clip(flat_e - e_offset, 0, E_local - 1)
    slot = jnp.clip(rank, 0, C - 1)

    tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    xk = x2d[tok] * keep[:, None].astype(x2d.dtype)
    buf = jnp.zeros((E_local, C, d), x2d.dtype)
    buf = buf.at[e_loc, slot].add(jnp.where(keep[:, None], xk, 0))

    h = jnp.einsum("ecd,edf->ecf", buf, wg)
    u = jnp.einsum("ecd,edf->ecf", buf, wu)
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, wd)   # (E_l, C, d)

    out_k = y[e_loc, slot] * (topw.reshape(-1) * keep)[:, None].astype(y.dtype)
    partial = jax.ops.segment_sum(out_k, tok, num_segments=T)

    # Switch-style load-balance aux loss + drop fraction (diagnostics)
    frac_dispatch = jnp.zeros((E,), jnp.float32).at[flat_e].add(1.0) / T
    frac_prob = jnp.mean(probs, axis=0)
    aux_loss = E * jnp.sum(frac_dispatch * frac_prob) / k
    dropped = 1.0 - jnp.sum(keep) / jnp.maximum(jnp.sum(local), 1)
    return partial.astype(x2d.dtype), {"aux_loss": aux_loss,
                                       "dropped": dropped}


def moe_apply(p, x, cfg, ctx=None):
    """x: (B, S, d) -> (B, S, d), aux dict.

    ctx None: single-device (all experts local).  Otherwise a shard_map
    over the full mesh: tokens stay sharded over the data axes and
    replicated over `model`; each `model` shard owns E/TP experts and the
    partial outputs are psum'd over `model`.
    """
    B, S, d = x.shape

    shared_out = None
    if "shared" in p:
        shared_out = layers.swiglu(p["shared"], x)

    if ctx is None:
        out2d, aux = _moe_math(x.reshape(-1, d), p["router"]["w"],
                               p["gate"], p["up"], p["down"], cfg,
                               0, cfg.n_experts)
        out = out2d.reshape(B, S, d)
    else:
        from jax.sharding import PartitionSpec as P
        tp = ctx.tp
        tp_size = ctx.mesh.shape[tp]
        E_local = cfg.n_experts // tp_size
        dp = ctx.dp

        dp_axes = dp if isinstance(dp, tuple) else (dp,)
        dp_size = ctx.dp_size
        # small-batch decode (e.g. B=1 long-context): tokens replicated
        # over dp; each shard computes the full (tiny) routing problem.
        bspec = dp if B % dp_size == 0 else None
        tok_varies_dp = bspec is not None

        def local_fn(x_loc, router_w, wg, wu, wd):
            # x_loc: (B_loc, S, d) — replicated over `model`
            e_off = jax.lax.axis_index(tp) * E_local
            # manual FSDP gather of this shard's expert weights
            wg = jax.lax.all_gather(wg, dp, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, dp, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, dp, axis=2, tiled=True)
            out, aux = _moe_math(x_loc.reshape(-1, d), router_w,
                                 wg, wu, wd, cfg, e_off, E_local)
            out = jax.lax.psum(out, tp)
            # aux_loss varies only over dp (router is replicated over tp);
            # dropped additionally varies over tp (local-expert mask)
            aux_loss = aux["aux_loss"]
            dropped = jax.lax.pmean(aux["dropped"], tp)
            if tok_varies_dp:
                aux_loss = jax.lax.pmean(aux_loss, dp_axes)
                dropped = jax.lax.pmean(dropped, dp_axes)
            return out.reshape(x_loc.shape), aux_loss, dropped

        # check_vma=False: with replicated tokens (B < dp) the outputs are
        # replicated over dp *by construction* (same inputs, same math on
        # every dp shard after the FSDP all_gather), but the varying-type
        # inference can't prove it through the all_gather.
        out, aux_loss, dropped = jax.shard_map(
            local_fn, mesh=ctx.mesh,
            in_specs=(P(bspec, None, None), P(None, None),
                      P(tp, dp, None), P(tp, dp, None), P(tp, None, dp)),
            out_specs=(P(bspec, None, None), P(), P()),
            check_vma=tok_varies_dp,
        )(x, p["router"]["w"], p["gate"], p["up"], p["down"])
        aux = {"aux_loss": aux_loss, "dropped": dropped}

    if shared_out is not None:
        out = out + shared_out
    return out, aux
