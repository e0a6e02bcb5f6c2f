"""Pallas TPU kernel: Section 5.2 bucketed-reduce histogram.

Accumulates candidate mass ``v2`` into per-knapsack buckets keyed by
``searchsorted(edges[k], v1[:, k])``. The (K, E+1) accumulator lives in
VMEM across the whole user grid (all grid steps map to the same output
block; TPU grids execute sequentially, so ``out += tile`` is safe), and is
exactly the array the solver psums across the mesh — i.e. this kernel IS
the map-side of the paper's communication-compression trick.

Binning is branch-free: bucket index = #(edges < v1), computed as a sum
of compares against the edge ladder; accumulation is a (tile_n x nb)
one-hot contraction on the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._util import pad_rows, resolve_interpret


def hist_block(v1, v2, edges):
    """(tile_n, K) candidates -> (K, E+1) bucket-mass block, in f32.

    idx[n, k] = number of edges < v1, in [0, E]: bucket j holds
    edges[j-1] < v1 <= edges[j] — the same tie convention as
    searchsorted(side="left") so kernel and jnp reduces agree when a
    candidate lands exactly on an edge. Shared by this kernel and the
    fused map+reduce kernel (scd_fused.py).
    """
    tile_n, k = v1.shape
    e = edges.shape[-1]
    nb = e + 1
    gt = v1[:, :, None] > edges[None, :, :]               # (tile_n, K, E)
    idx = gt.sum(axis=-1).astype(jnp.int32)               # (tile_n, K)
    buckets = jax.lax.broadcasted_iota(jnp.int32, (tile_n, k, nb), 2)
    onehot = (buckets == idx[:, :, None]).astype(jnp.float32)
    # DEFAULT precision: chip_smoke.py phase (f) checks on the chip that
    # this K-batched contraction keeps f32 masses, unlike the unbatched
    # one-hot matmul of scd_fused's finalize, which needs HIGHEST.
    return jnp.einsum("nkb,nk->kb", onehot, v2.astype(jnp.float32))


def _kernel(v1_ref, v2_ref, edges_ref, out_ref):
    tile_hist = hist_block(v1_ref[...], v2_ref[...], edges_ref[...])

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += tile_hist


@functools.partial(jax.jit, static_argnames=("tile_n", "interpret"))
def bucket_hist(v1, v2, edges, tile_n=512, interpret=None):
    """v1, v2: (n, K); edges: (K, E) ascending. Returns (K, E+1) f32."""
    n, k = v1.shape
    e = edges.shape[-1]
    interpret = resolve_interpret(interpret)
    tile_n = min(tile_n, n)
    # Ragged n: padded rows carry v2 = 0, i.e. zero mass in every bucket.
    pad = -n % tile_n
    v1 = pad_rows(v1, pad, value=-1.0)
    v2 = pad_rows(v2, pad)
    grid = ((n + pad) // tile_n,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_n, k), lambda i: (i, 0)),
            pl.BlockSpec((tile_n, k), lambda i: (i, 0)),
            pl.BlockSpec((k, e), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((k, e + 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((k, e + 1), jnp.float32),
        interpret=interpret,
    )(v1, v2, edges.astype(v1.dtype))
