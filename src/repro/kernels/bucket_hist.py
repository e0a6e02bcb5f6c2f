"""Pallas TPU kernel: Section 5.2 bucketed-reduce histogram.

Accumulates candidate mass ``v2`` into per-knapsack buckets keyed by
``searchsorted(edges[k], v1[:, k])``. The (K, E+1) accumulator lives in
VMEM across the whole user grid (all grid steps map to the same output
block; TPU grids execute sequentially, so ``out += tile`` is safe), and is
exactly the array the solver psums across the mesh — i.e. this kernel IS
the map-side of the paper's communication-compression trick.

Binning and masses run on the VPU in f32 (:func:`hist_block`): the bucket
index is a sum of compares against the edge ladder, one edge at a time,
and each bucket's mass is a masked sum over the users. That body is
axis-generic: this standalone kernel runs it on (tile_n, K) blocks, the
fused kernel (scd_fused.py) on (K, tile) blocks with users on lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._util import pad_rows, resolve_interpret


def hist_block(v1, v2, edges, axis=1):
    """Candidates -> bucket-mass block, in f32.

    ``axis`` is the knapsack axis, fixed by the calling kernel's block
    layout, and ``edges`` and the result carry K on it too: v1, v2
    (tile_n, K), edges (E, K) -> (E+1, K) for ``axis=1``; v1, v2
    (K, tile), edges (K, E) -> (K, E+1) for ``axis=0`` (users on lanes).

    idx = number of edges < v1, in [0, E]: bucket j holds
    edges[j-1] < v1 <= edges[j] — the same tie convention as
    searchsorted(side="left") so kernel and jnp reduces agree when a
    candidate lands exactly on an edge. Each edge is compared as a
    K-vector broadcast along the users, and each bucket's mass is an f32
    sum of ``v2`` over the users whose index is j: no matmul, so no MXU
    precision to pin. Shared by this kernel and the fused map+reduce
    kernel (scd_fused.py).
    """
    users = 1 - axis
    e = edges.shape[users]
    idx = jnp.zeros(v1.shape, jnp.int32)
    for j in range(e):
        edge = jax.lax.slice_in_dim(edges, j, j + 1, axis=users)
        idx = idx + (v1 > edge).astype(jnp.int32)
    mass = v2.astype(jnp.float32)
    shape = edges.shape[:users] + (e + 1,) + edges.shape[users + 1:]
    buckets = jax.lax.broadcasted_iota(jnp.int32, shape, users)
    hist = jnp.zeros(shape, jnp.float32)
    for j in range(e + 1):
        col = jnp.sum(jnp.where(idx == j, mass, 0.0), axis=users,
                      keepdims=True)
        hist = jnp.where(buckets == j, col, hist)
    return hist


def _kernel(v1_ref, v2_ref, edges_ref, out_ref):
    tile_hist = hist_block(v1_ref[...], v2_ref[...], edges_ref[...])

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += tile_hist


@functools.partial(jax.jit, static_argnames=("tile_n", "interpret"))
def bucket_hist(v1, v2, edges, tile_n=512, interpret=None):
    """v1, v2: (n, K); edges: (K, E) ascending. Returns (K, E+1) f32.

    The kernel runs on (tile_n, K) blocks, so the edge ladder and the
    accumulator enter and leave it transposed, K on lanes like the
    candidates (two (K, E)-sized transposes a call).
    """
    n, k = v1.shape
    e = edges.shape[-1]
    interpret = resolve_interpret(interpret)
    tile_n = min(tile_n, n)
    # Ragged n: padded rows carry v2 = 0, i.e. zero mass in every bucket.
    pad = -n % tile_n
    v1 = pad_rows(v1, pad, value=-1.0)
    v2 = pad_rows(v2, pad)
    grid = ((n + pad) // tile_n,)
    hist = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_n, k), lambda i: (i, 0)),
            pl.BlockSpec((tile_n, k), lambda i: (i, 0)),
            pl.BlockSpec((e, k), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((e + 1, k), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((e + 1, k), jnp.float32),
        interpret=interpret,
    )(v1, v2, edges.T.astype(v1.dtype))
    return hist.T
