"""Pallas TPU kernel: fused cost-adjusted profit + top-Q select + consumption.

The DD/SCD map body for the sparse GKP (one item per knapsack): for a tile
of users resident in VMEM, compute ``ap = p - lam * b``, select the top-Q
strictly-positive entries per user (ties broken by smaller item index, the
same convention as core.sparse_scd), and emit the selection mask and the
per-knapsack consumption ``v = b * x`` — all in one pass so ``ap`` never
round-trips to HBM (the paper's mapper materialises it per user; at 1e9
users that intermediate is the memory bottleneck).

TPU adaptation of quick-select: a data-dependent partition does not
vectorise on the VPU. Q is small and static, so selection runs as Q
sequential argmax passes over the (tile_n, K) block — each pass is a pair
of lane reductions (max, then min-index among maxima) and a mask update.
O(Q * tile_n * K) VPU work, no data-dependent control flow.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._util import pad_rows, resolve_interpret


def _topq_mask(ap, q):
    """(tile_n, K) -> bool mask of top-q positive entries, min-index ties."""
    n, k = ap.shape
    idx = jax.lax.broadcasted_iota(jnp.int32, (n, k), 1)
    neg_inf = jnp.asarray(-jnp.inf, ap.dtype)
    x = jnp.zeros((n, k), jnp.bool_)
    work = ap
    for _ in range(q):
        m = jnp.max(work, axis=1, keepdims=True)                  # (n,1)
        is_max = (work == m) & (m > 0)
        pick_idx = jnp.min(jnp.where(is_max, idx, k), axis=1, keepdims=True)
        pick = idx == pick_idx                                    # one-hot row
        x = x | pick
        work = jnp.where(pick, neg_inf, work)
    return x


def _kernel(p_ref, b_ref, lam_ref, x_ref, v_ref, *, q):
    p = p_ref[...]
    b = b_ref[...]
    lam = lam_ref[...]                                            # (1, K)
    ap = p - lam * b
    x = _topq_mask(ap, q)
    x_ref[...] = x
    v_ref[...] = jnp.where(x, b, jnp.zeros_like(b))


@functools.partial(jax.jit, static_argnames=("q", "tile_n", "interpret"))
def adjusted_topc(p, b, lam, q, tile_n=512, interpret=None):
    """p, b: (n, K); lam: (K,). Returns (x bool (n,K), v (n,K))."""
    n, k = p.shape
    interpret = resolve_interpret(interpret)
    tile_n = min(tile_n, n)
    # Ragged n: padded rows have ap = 0, never strictly positive, so the
    # top-q mask is all-False there; slice the outputs back.
    pad = -n % tile_n
    p = pad_rows(p, pad)
    b = pad_rows(b, pad)
    grid = ((n + pad) // tile_n,)
    lam2 = lam.reshape(1, k).astype(p.dtype)
    x, v = pl.pallas_call(
        functools.partial(_kernel, q=q),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_n, k), lambda i: (i, 0)),
            pl.BlockSpec((tile_n, k), lambda i: (i, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile_n, k), lambda i: (i, 0)),
            pl.BlockSpec((tile_n, k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n + pad, k), jnp.bool_),
            jax.ShapeDtypeStruct((n + pad, k), p.dtype),
        ],
        interpret=interpret,
    )(p, b, lam2)
    return (x[:n], v[:n]) if pad else (x, v)
