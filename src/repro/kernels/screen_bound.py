"""Pallas TPU kernel: masked max-ratio accumulation for screening.

Computes the per-chunk screening certificate of core/screening.py — the
row-max of ``p / b`` over rows with ``b > 0`` (masked accumulation:
invalid rows contribute -inf, never a NaN from the 0/0 division) — as
one grid pass over user tiles with the (1, K) running max held in VMEM,
the same sequential-grid accumulation pattern as ``bucket_hist``. The
certificate is consumed on the host between iteration epochs, so this
kernel is bandwidth-trivial; it exists so the kernel feeding path can
issue the bound computation on device memory it already holds instead
of staging chunks back to the host oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._util import pad_rows, resolve_interpret


def bound_block(p, b):
    """(tile_n, K) -> (1, K) masked max ratio block, in f32.

    The mask is applied to *both* operands before the divide (the
    select-then-divide order of ``screening.chunk_bound``): a masked
    lane divides 0-free and then selects -inf, so no spurious inf/NaN
    ever enters the VPU max tree.
    """
    valid = b > 0
    safe = jnp.where(valid, b, jnp.ones_like(b))
    ratio = jnp.where(valid, p / safe, -jnp.inf).astype(jnp.float32)
    return jnp.max(ratio, axis=0, keepdims=True)


def _kernel(p_ref, b_ref, out_ref):
    tile = bound_block(p_ref[...], b_ref[...])

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, -jnp.inf)

    out_ref[...] = jnp.maximum(out_ref[...], tile)


@functools.partial(jax.jit, static_argnames=("tile_n", "interpret"))
def screen_bound(p, b, tile_n=512, interpret=None):
    """p, b: (n, K). Returns the (K,) f32 chunk certificate.

    max is associative/commutative in IEEE f32 (no rounding), so the
    tiled accumulation is bit-identical to the single-reduction oracle
    ``screening.chunk_bound`` regardless of tiling — unlike the
    histogram kernels, no tile-order contract is needed.
    """
    n, k = p.shape
    interpret = resolve_interpret(interpret)
    tile_n = min(tile_n, n)
    # Ragged n: padded rows carry b = 0, i.e. masked to -inf.
    pad = -n % tile_n
    p = pad_rows(p, pad)
    b = pad_rows(b, pad)
    grid = ((n + pad) // tile_n,)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_n, k), lambda i: (i, 0)),
            pl.BlockSpec((tile_n, k), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, k), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, k), jnp.float32),
        interpret=interpret,
    )(p, b)
    return out[0]
