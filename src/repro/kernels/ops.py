"""Jitted public wrappers for the kernel layer.

On a TPU these call the Pallas kernels compiled natively; on the CPU they
run the same kernel bodies under ``interpret=True``, which traces the
kernel through XLA so correctness (incl. the grid accumulation pattern) is
exercised end to end. Any other backend raises
(``_util.resolve_interpret``). ``use_pallas=False`` falls back to the
pure-jnp oracle — the solver uses that switch to A/B the kernel path.
"""
from __future__ import annotations

import jax

from . import ref
from .adjusted_topc import adjusted_topc as _adjusted_topc
from .bucket_hist import bucket_hist as _bucket_hist
from .scd_candidates import scd_candidates as _scd_candidates
from .scd_fused import scd_finalize_hist as _scd_finalize_hist
from .scd_fused import scd_fused_hist as _scd_fused_hist
from .screen_bound import screen_bound as _screen_bound

_TILE_LADDER = (512, 256, 128)


def pick_tile(n, max_tile=512):
    """User-axis tile for a shard of n rows.

    Prefers the largest ladder tile that divides n (no padding, full
    sublane occupancy). Otherwise the shard runs as a single tile
    (n <= max_tile) or as max_tile-sized tiles with the ragged tail
    padded inside the kernel wrappers. The ladder stops at 128: a
    smaller dividing tile would serialise the grid (n=100000 -> 3125
    steps at tile 32 vs 196 padded steps at tile 512), which costs far
    more than <= tile-1 inert padded rows.
    """
    for t in _TILE_LADDER:
        if t <= max_tile and n % t == 0:
            return t
    return min(max_tile, max(n, 1))


def adjusted_topc(p, b, lam, q, use_pallas=True, **kw):
    """Fused DD map: (x mask, consumption v) for the sparse GKP."""
    if not use_pallas:
        return ref.adjusted_topc_ref(p, b, lam, q)
    return _adjusted_topc(p, b, lam, q, **kw)


def scd_candidates(p, b, lam, q, use_pallas=True, **kw):
    """Alg 5 map: candidate (v1, v2) pairs."""
    if not use_pallas:
        return ref.scd_candidates_ref(p, b, lam, q)
    return _scd_candidates(p, b, lam, q, **kw)


def bucket_hist(v1, v2, edges, use_pallas=True, **kw):
    """§5.2 reduce-side histogram (K, E+1)."""
    if not use_pallas:
        return ref.bucket_hist_ref(v1, v2, edges)
    return _bucket_hist(v1, v2, edges, **kw)


def scd_fused_hist(p, b, lam, edges, q, use_pallas=True, **kw):
    """Fused Alg-5 map + §5.2 histogram: (hist (K, E+1), top (K,)).

    The candidate (v1, v2) intermediates never leave VMEM — this is the
    solver's bucketed-reduce hot path when ``cfg.use_kernels``. Pass
    ``hist_init``/``top_init`` to seed the accumulators when scanning
    user chunks (the chunked solve's bit-identity contract; the ref
    oracle combines seeds at allclose level only).
    """
    if not use_pallas:
        return ref.scd_fused_hist_ref(
            p, b, lam, edges, q,
            hist_init=kw.get("hist_init"), top_init=kw.get("top_init"))
    return _scd_fused_hist(p, b, lam, edges, q, **kw)


def screen_bound(p, b, use_pallas=True, **kw):
    """Masked max-ratio accumulation: the (K,) per-chunk screening
    certificate of core/screening.py (row-max of p/b over b > 0 rows;
    masked rows bound to -inf). Bit-identical across the kernel and
    oracle paths — f32 max carries no rounding."""
    if not use_pallas:
        from ..core.screening import chunk_bound
        return chunk_bound(p, b)
    return _screen_bound(p, b, **kw)


def scd_finalize_hist(p, b, lam, pedges, q, use_pallas=True, **kw):
    """Fused streaming-finalize pass (DESIGN.md §5c): the post-solve
    metrics partials (r, primal, dual_sum, group-profit lo/hi) and the
    §5.4 removable consumption/profit histograms, accumulated in one
    VMEM grid pass. Seed the ``*_init`` accumulators when scanning user
    chunks (carry-seeded, like :func:`scd_fused_hist`; the ref oracle
    combines seeds at allclose level only). Returns (cons_hist,
    gain_hist, r, primal, dual_sum, lo, hi)."""
    if not use_pallas:
        kw.pop("tile_n", None)
        return ref.scd_finalize_ref(p, b, lam, pedges, q, **kw)
    return _scd_finalize_hist(p, b, lam, pedges, q, **kw)
