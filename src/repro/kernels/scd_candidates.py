"""Pallas TPU kernel: Algorithm 5 linear-time candidate generation.

Per user tile in VMEM: adjusted profits ``ap = max(p - lam*b, 0)``, the
Q-th / (Q+1)-th largest entries per row (the two order statistics Alg 5
needs), the per-item beat-threshold ``pbar``, and the emitted candidate
pairs ``v1 = (p - pbar)/b``, ``v2 = b`` — fused so neither ``ap`` nor the
thresholds ever leave VMEM.

Order statistics are computed with Q+1 sequential masked-max passes (see
adjusted_topc.py for why quick-select doesn't map to the VPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._util import pad_rows, resolve_interpret


def _order_stats(ap, q, axis):
    """Q-th / (Q+1)-th largest of ``ap`` along the knapsack ``axis``
    (with multiplicity), each kept as a size-1 axis."""
    k = ap.shape[axis]
    neg_inf = jnp.asarray(-jnp.inf, ap.dtype)
    work = ap
    q_th = q1_th = jnp.full(
        ap.shape[:axis] + (1,) + ap.shape[axis + 1:], jnp.inf, ap.dtype)
    idx = jax.lax.broadcasted_iota(jnp.int32, ap.shape, axis)
    for i in range(q + 1):
        m = jnp.max(work, axis=axis, keepdims=True)
        if i == q - 1:
            q_th = m
        if i == q:
            q1_th = m
        is_max = work == m
        pick_idx = jnp.min(jnp.where(is_max, idx, k), axis=axis,
                           keepdims=True)
        work = jnp.where(idx == pick_idx, neg_inf, work)
    return q_th, q1_th


def candidates_block(p, b, lam, q, axis=1):
    """Alg 5 candidate pairs (v1, v2) for one VMEM-resident block.

    ``axis`` is the knapsack axis, fixed by the calling kernel's block
    layout: p, b are (tile_n, K) with lam (1, K) for ``axis=1`` (this
    kernel), or (K, tile) with lam (K, 1) for ``axis=0`` (users on lanes,
    the fused kernel of scd_fused.py). Invalid candidates are encoded as
    v1 = -1, v2 = 0. Both kernels call this one body, so the
    tie-sensitive semantics exist once.
    """
    ap = jnp.maximum(p - lam * b, 0.0)
    k = p.shape[axis]
    if q >= k:
        pbar = jnp.zeros_like(ap)
    else:
        q_th, q1_th = _order_stats(ap, q, axis)
        in_top = ap >= q_th
        pbar = jnp.where(in_top, q1_th, q_th)
    valid = (p > pbar) & (b > 0)
    safe_b = jnp.where(b > 0, b, jnp.ones_like(b))
    v1 = jnp.where(valid, (p - pbar) / safe_b, -jnp.ones_like(p))
    v2 = jnp.where(valid, b, jnp.zeros_like(b))
    return v1, v2


def _kernel(p_ref, b_ref, lam_ref, v1_ref, v2_ref, *, q):
    v1, v2 = candidates_block(p_ref[...], b_ref[...], lam_ref[...], q)
    v1_ref[...] = v1
    v2_ref[...] = v2


@functools.partial(jax.jit, static_argnames=("q", "tile_n", "interpret"))
def scd_candidates(p, b, lam, q, tile_n=512, interpret=None):
    """p, b: (n, K); lam: (K,). Returns (v1, v2): (n, K) Alg 5 candidates."""
    n, k = p.shape
    interpret = resolve_interpret(interpret)
    tile_n = min(tile_n, n)
    # Ragged n: pad with (p=0, b=0) rows — invalid candidates (v1=-1,
    # v2=0) by construction — and slice the outputs back.
    pad = -n % tile_n
    p = pad_rows(p, pad)
    b = pad_rows(b, pad)
    grid = ((n + pad) // tile_n,)
    lam2 = lam.reshape(1, k).astype(p.dtype)
    v1, v2 = pl.pallas_call(
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
            jax.ShapeDtypeStruct((n + pad, k), p.dtype),
            jax.ShapeDtypeStruct((n + pad, k), p.dtype),
        ],
        interpret=interpret,
    )(p, b, lam2)
    return (v1[:n], v2[:n]) if pad else (v1, v2)
