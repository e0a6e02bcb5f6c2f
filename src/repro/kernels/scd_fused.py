"""Pallas TPU kernel: fused SCD map + §5.2 bucketed reduce.

One grid pass per user tile does the whole per-iteration SCD hot path:
adjusted profits ``ap = max(p - lam*b, 0)``, the two Alg-5 order
statistics (Q-th / (Q+1)-th largest per user), the candidate pairs
``v1 = (p - pbar)/b``, ``v2 = b``, the §5.2 binning of ``v1`` against the
per-knapsack edge ladder, and the running per-knapsack max of ``v1`` —
accumulating straight into the (K, E+1) histogram and (K, 1) top blocks
that live in VMEM across the whole grid.

This is the paper's communication-compression argument applied one level
down the memory hierarchy: across machines only the constant-size
histogram is shuffled (§5.2); within a device only the constant-size
histogram leaves the core. The unfused pair (scd_candidates ->
bucket_hist) writes and re-reads the full (n, K) ``v1``/``v2`` arrays
through HBM every iteration — 4 O(n*K) transfers this kernel deletes.

Users on lanes: :func:`scd_fused_hist` takes ``(n, K)`` arguments and
hands the kernel their logical transpose, in ``(K, tile)`` blocks with
``tile`` users along the 128-wide lane axis and the K knapsacks on
sublanes. (With K = 10 a ``(tile_n, K)`` block would fill 10 of 128
lanes.) XLA keeps an ``(n, K)`` array with small K users-minor on the
TPU, so on a resident array the transpose is a bitcast, not a copy.
The kernel runs the same axis-generic bodies as the two standalone
kernels — :func:`candidates_block` and :func:`hist_block` with the
knapsack axis 0: the order statistics reduce over sublanes, each edge of
the ladder is a (K, 1) column broadcast along the lanes, and each
bucket's mass is an f32 sum over the lanes on the VPU. The standalone
kernels stay ``(tile_n, K)`` and remain the parity oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._util import pad_rows, resolve_interpret
from .adjusted_topc import _topq_mask
from .bucket_hist import hist_block
from .scd_candidates import candidates_block

# Users per grid step of scd_fused_hist, from a sweep of 1024-16384 on a
# TPU v5e at n = 1e7, K = 10 (PERF.md §6: 2048 and 4096 tie, 8192 and
# 16384 are ~9% slower); a power of two, so it divides the host-fed
# chunk of 65536 users.
LANE_TILE = 4096


def _kernel(p_ref, b_ref, lam_ref, edges_ref, hist0_ref, top0_ref,
            hist_ref, top_ref, *, q, n):
    # Alg 5 map, then the §5.2 binning — the same shared blocks the two
    # standalone kernels run, here on (K, tile) blocks (knapsack axis 0),
    # with v1/v2 kept in VMEM between them.
    v1, v2 = candidates_block(p_ref[...], b_ref[...], lam_ref[...], q,
                              axis=0)
    tile = v1.shape[1]
    if n % tile:
        # The last block overhangs the n users; what it reads there is
        # undefined. Those lanes become invalid candidates (v1 = -1,
        # v2 = 0): zero mass, and they never raise the top.
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
        real = pl.program_id(0) * tile + lane < n
        v1 = jnp.where(real, v1, -1.0)
        v2 = jnp.where(real, v2, 0.0)
    tile_hist = hist_block(v1, v2, edges_ref[...], axis=0)   # (K, E+1)
    tile_top = jnp.max(v1, axis=1, keepdims=True)             # (K, 1)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        hist_ref[...] = hist0_ref[...]
        top_ref[...] = top0_ref[...]

    hist_ref[...] += tile_hist
    top_ref[...] = jnp.maximum(top_ref[...], tile_top)


@functools.partial(jax.jit, static_argnames=("q", "tile_n", "interpret"))
def scd_fused_hist(p, b, lam, edges, q, tile_n=None, interpret=None,
                   hist_init=None, top_init=None):
    """Fused Alg-5 map + §5.2 histogram. No (n, K) intermediate in HBM.

    p, b: (n, K); lam: (K,); edges: (K, E) ascending. Returns
    (hist (K, E+1) f32, top (K,) p.dtype) — exactly
    ``bucket_hist(*scd_candidates(p, b, lam, q), edges)`` and
    ``max(v1, axis=0)``, with v1/v2 never materialised off-chip.

    The kernel sees users on lanes: ``tile_n`` users per grid step in
    (K, tile_n) blocks of ``p.T`` and ``b.T``; ``None`` takes
    ``LANE_TILE``, or the whole shard when it is smaller. No tile has to
    divide n. On the TPU a tile below n must be a multiple of 128.

    ``hist_init`` (K, E+1) / ``top_init`` (K,) seed the VMEM accumulators
    (defaults: zeros / -inf, the unseeded behaviour). The out-of-core
    chunked solve scans user chunks through this kernel with the running
    (hist, top) carried between calls; because the accumulators are
    *seeded* rather than summed afterwards, the f32 addition chain over
    tiles is the same one the single unchunked call performs — chunked
    and unchunked results are bit-identical whenever the tile
    decomposition of the user axis is the same (chunk_size a multiple of
    tile_n; see core/solver.py). The seed inputs are aliased to the
    outputs so the carried accumulator is updated in place on TPU.

    Ragged n is masked inside the kernel from the true n: the lanes of
    the last block past n are invalid candidates (v1 = -1, v2 = 0),
    contributing zero mass and never raising the top (real v1 is -1 or
    positive), exactly as the inert (p = 0, b = 0) rows a chunked caller
    pads with. Nothing is padded or copied per call.
    """
    n, k = p.shape
    e = edges.shape[-1]
    interpret = resolve_interpret(interpret)
    tile_n = min(tile_n or LANE_TILE, n)
    grid = (pl.cdiv(n, tile_n),)
    if hist_init is None:
        hist_init = jnp.zeros((k, e + 1), jnp.float32)
    if top_init is None:
        top_init = jnp.full((k,), -jnp.inf, p.dtype)
    hist, top = pl.pallas_call(
        functools.partial(_kernel, q=q, n=n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, tile_n), lambda i: (0, i)),
            pl.BlockSpec((k, tile_n), lambda i: (0, i)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
            pl.BlockSpec((k, e), lambda i: (0, 0)),
            pl.BlockSpec((k, e + 1), lambda i: (0, 0)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((k, e + 1), lambda i: (0, 0)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, e + 1), jnp.float32),
            jax.ShapeDtypeStruct((k, 1), p.dtype),
        ],
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
    )(p.T, b.T, lam.reshape(k, 1).astype(p.dtype), edges.astype(p.dtype),
      hist_init.astype(jnp.float32), top_init.reshape(k, 1).astype(p.dtype))
    return hist, top[:, 0]


def finalize_block(p, b, lam, q):
    """Primal map for one VMEM-resident block of the streaming finalize.

    p, b: (tile_n, K); lam: (1, K). Returns (x bool, cons, gain (tile, 1),
    pt (tile, 1)): the Alg-1 greedy selection at lam, its consumption,
    and per-user raw/cost-adjusted selected profit. ``pt`` is the sum of
    selected adjusted profits — the sparse group profit of §5.4 — in the
    per-row reduction form shared with the jnp streaming body
    (core/chunked.py), so kernel and jnp paths bin it into identical
    buckets (a half-ulp difference would shift whole mass units between
    adjacent buckets).
    """
    ap = p - lam * b
    x = _topq_mask(ap, q)
    cons = jnp.where(x, b, jnp.zeros_like(b))
    gain = jnp.sum(jnp.where(x, p, jnp.zeros_like(p)), axis=1, keepdims=True)
    pt = jnp.sum(jnp.where(x, ap, jnp.zeros_like(ap)), axis=1, keepdims=True)
    return x, cons, gain, pt


def _finalize_kernel(p_ref, b_ref, lam_ref, *refs, q, with_hist):
    """One kernel body for both finalize variants (metrics ± histograms).

    The bit-exactness-critical metrics accumulation exists once; the
    ``with_hist`` closure only decides whether the §5.4 histogram refs
    are present and binned into. Ref order matches the pallas_call specs
    built in :func:`scd_finalize_hist`.
    """
    if with_hist:
        (pedges_ref, ch0_ref, gh0_ref, r0_ref, s0_ref, m0_ref,
         ch_ref, gh_ref, r_ref, s_ref, m_ref) = refs
    else:
        r0_ref, s0_ref, m0_ref, r_ref, s_ref, m_ref = refs
    x, cons, gain, pt = finalize_block(p_ref[...], b_ref[...], lam_ref[...], q)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        if with_hist:
            ch_ref[...] = ch0_ref[...]
            gh_ref[...] = gh0_ref[...]
        r_ref[...] = r0_ref[...]
        s_ref[...] = s0_ref[...]
        m_ref[...] = m0_ref[...]

    r_ref[...] += jnp.sum(cons, axis=0, keepdims=True).astype(jnp.float32)
    primal = jnp.sum(jnp.where(x, p_ref[...], 0.0), keepdims=True)
    dual = jnp.sum(jnp.where(x, p_ref[...] - lam_ref[...] * b_ref[...], 0.0),
                   keepdims=True)
    s_ref[...] += jnp.concatenate(
        [primal.reshape(1, 1), dual.reshape(1, 1)], axis=1).astype(jnp.float32)
    # Group-profit range over users with any selection; inert/empty rows
    # are excluded (their pt = 0 carries no removable mass anyway). lo is
    # tracked negated so one maximum-combine covers both ends.
    sel = jnp.any(x, axis=1, keepdims=True)
    inf = jnp.asarray(jnp.inf, pt.dtype)
    hi = jnp.max(jnp.where(sel, pt, -inf), keepdims=True).reshape(1, 1)
    nlo = jnp.max(jnp.where(sel, -pt, -inf), keepdims=True).reshape(1, 1)
    m_ref[...] = jnp.maximum(m_ref[...], jnp.concatenate([hi, nlo], axis=1))
    if not with_hist:
        return
    # §5.4 removable histograms: searchsorted-left edge-ladder binning of
    # pt (same convention as hist_block), mass = consumption / raw profit.
    tile_n = pt.shape[0]
    e = pedges_ref.shape[-1]
    idx = jnp.sum(pt > pedges_ref[...], axis=1).astype(jnp.int32)  # (tile,)
    buckets = jax.lax.broadcasted_iota(jnp.int32, (tile_n, e + 1), 1)
    onehot = (buckets == idx[:, None]).astype(jnp.float32)
    # HIGHEST: at DEFAULT, Mosaic feeds f32 operands to the MXU as bf16,
    # rounding every consumption to ~3 significant digits (v5e).
    ch_ref[...] += jnp.einsum("nb,nk->kb", onehot, cons.astype(jnp.float32),
                              precision=jax.lax.Precision.HIGHEST)
    gh_ref[...] += jnp.sum(onehot * gain.astype(jnp.float32), axis=0,
                           keepdims=True)


@functools.partial(jax.jit,
                   static_argnames=("q", "tile_n", "interpret", "with_hist"))
def scd_finalize_hist(p, b, lam, pedges, q, tile_n=512, interpret=None,
                      with_hist=True, cons_hist_init=None,
                      gain_hist_init=None, r_init=None, sums_init=None,
                      maxs_init=None):
    """Fused streaming-finalize pass: metrics partials + §5.4 histograms.

    One grid pass over the user tiles computes everything the streaming
    solve needs after convergence — the greedy primal selection at
    ``lam``, its consumption ``r``, the primal / dual-sum scalars, the
    group-profit range, and (``with_hist``) the removable consumption and
    raw-profit histograms binned against the fixed ladder ``pedges``
    (E,) — accumulating all of it in VMEM across the grid, exactly like
    :func:`scd_fused_hist` does for the per-iteration reduce. This is
    the kernel behind the iters+1 pass accounting of DESIGN.md §5c: the
    legacy finalize runs three separate passes for the same outputs.

    Returns ``(cons_hist (K, E+1), gain_hist (E+1,), r (K,), primal (),
    dual_sum (), lo (), hi ())`` — all f32 except lo/hi in p.dtype; the
    first two are None when ``with_hist=False`` (metrics-only variant,
    used by the sampled-history path). The ``*_init`` seeds continue a
    carried accumulation chunk by chunk (input/output aliased, in-place
    on TPU): because the seeds initialise the running VMEM accumulators,
    the f32 chain over tiles is the one a single whole-shard call
    performs, so chunked and unchunked finalizes are bit-identical under
    the same tile decomposition — the same contract as
    :func:`scd_fused_hist`. Ragged n pads with inert (p = b = 0) rows:
    nothing is selected there, so they contribute zero mass everywhere
    and never touch the lo/hi range.
    """
    n, k = p.shape
    interpret = resolve_interpret(interpret)
    tile_n = min(tile_n, n)
    pad = -n % tile_n
    p = pad_rows(p, pad)
    b = pad_rows(b, pad)
    grid = ((n + pad) // tile_n,)
    lam2 = lam.reshape(1, k).astype(p.dtype)
    if r_init is None:
        r_init = jnp.zeros((k,), jnp.float32)
    if sums_init is None:
        sums_init = jnp.zeros((2,), jnp.float32)
    if maxs_init is None:
        maxs_init = jnp.full((2,), -jnp.inf, p.dtype)
    r_init = r_init.reshape(1, k).astype(jnp.float32)
    sums_init = sums_init.reshape(1, 2).astype(jnp.float32)
    maxs_init = maxs_init.reshape(1, 2).astype(p.dtype)
    scalar_specs = [
        pl.BlockSpec((1, k), lambda i: (0, 0)),
        pl.BlockSpec((1, 2), lambda i: (0, 0)),
        pl.BlockSpec((1, 2), lambda i: (0, 0)),
    ]
    scalar_shapes = [
        jax.ShapeDtypeStruct((1, k), jnp.float32),
        jax.ShapeDtypeStruct((1, 2), jnp.float32),
        jax.ShapeDtypeStruct((1, 2), p.dtype),
    ]
    row_specs = [
        pl.BlockSpec((tile_n, k), lambda i: (i, 0)),
        pl.BlockSpec((tile_n, k), lambda i: (i, 0)),
        pl.BlockSpec((1, k), lambda i: (0, 0)),
    ]
    if not with_hist:
        r, s, m = pl.pallas_call(
            functools.partial(_finalize_kernel, q=q, with_hist=False),
            grid=grid,
            in_specs=row_specs + scalar_specs,
            out_specs=scalar_specs,
            out_shape=scalar_shapes,
            input_output_aliases={3: 0, 4: 1, 5: 2},
            interpret=interpret,
        )(p, b, lam2, r_init, sums_init, maxs_init)
        return (None, None, r[0], s[0, 0], s[0, 1], -m[0, 1], m[0, 0])
    e = pedges.shape[-1]
    if cons_hist_init is None:
        cons_hist_init = jnp.zeros((k, e + 1), jnp.float32)
    if gain_hist_init is None:
        gain_hist_init = jnp.zeros((e + 1,), jnp.float32)
    hist_specs = [
        pl.BlockSpec((k, e + 1), lambda i: (0, 0)),
        pl.BlockSpec((1, e + 1), lambda i: (0, 0)),
    ]
    hist_shapes = [
        jax.ShapeDtypeStruct((k, e + 1), jnp.float32),
        jax.ShapeDtypeStruct((1, e + 1), jnp.float32),
    ]
    ch, gh, r, s, m = pl.pallas_call(
        functools.partial(_finalize_kernel, q=q, with_hist=True),
        grid=grid,
        in_specs=row_specs + [pl.BlockSpec((1, e), lambda i: (0, 0))]
        + hist_specs + scalar_specs,
        out_specs=hist_specs + scalar_specs,
        out_shape=hist_shapes + scalar_shapes,
        input_output_aliases={4: 0, 5: 1, 6: 2, 7: 3, 8: 4},
        interpret=interpret,
    )(p, b, lam2, pedges.reshape(1, e).astype(p.dtype),
      cons_hist_init.astype(jnp.float32),
      gain_hist_init.reshape(1, e + 1).astype(jnp.float32),
      r_init, sums_init, maxs_init)
    return (ch, gh[0], r[0], s[0, 0], s[0, 1], -m[0, 1], m[0, 0])
