"""Out-of-core streaming solve: n bounded by the chunk generator, not HBM.

The paper's headline (1e9 decision variables in under an hour) rests on
the map stage *streaming* items while the reduce stays constant-size
(§5.2); Murthy et al. (arXiv:1702.00787) make the same point for
distributed MKP — per-processor state must be O(items processed at a
time), not O(local items). ``cfg.chunk_size`` in core/solver.py bounds
the per-iteration intermediates but still holds the (n, K) instance in
device memory. This module removes that last O(n) term: the instance is
a :class:`ChunkSource` — a function producing chunk ``i`` on demand —
and the whole solve touches only O(chunk·K + K·E) device state.

Two traceable source families (host-fed numpy sources live in
:mod:`repro.core.prefetch`):

* **Generated** (``data.synth.sparse_chunk_source``): chunk ``i`` is a
  pure function of ``(seed, i)``, synthesized *inside* the scan body.
  Nothing O(n) ever exists anywhere — this is how the benchmark solves
  instances far beyond the unchunked HBM ceiling at flat device memory,
  and it doubles as the repo's restart-deterministic data-pipeline story
  (any chunk can be regenerated byte-identically after a failure).
* **Resident-backed** (:func:`array_source`): chunks sliced from arrays
  already in memory; the parity oracle for tests.

Every iteration is a ``lax.scan`` over the chunk index driving the same
accumulators as the resident chunked solve (``scd_chunk_accumulate``):
the carried (K, E+1) histogram seeds each chunk's reduction, so the
streaming multiplier trajectory is **bit-identical** to ``solve`` /
``solve_sharded`` on the same rows (SCD bucketed path; kernel path under
the same tile, see core/solver.py).

Pass accounting (DESIGN.md §5c): recomputing the map is the classic
out-of-core bargain of compute for memory, so the number of passes over
the source IS the cost model. A converged solve needs ``iters`` passes
for the multiplier iteration — that is the floor plus whatever the
post-solve work costs. The default fused finalize
(``cfg.stream_finalize="fused"``) folds the final metrics, the §5.4
removable histograms and the projection arithmetic into ONE extra pass
(``iters + 1`` total): the projection-apply needs no pass at all because
the removable *profit* histogram makes the post-projection (r, primal)
exact prefix subtractions (postprocess.threshold_and_removed). The
legacy three-pass finalize (metrics, histogram, apply; ``iters + 3``)
is kept under ``stream_finalize="legacy"`` as the oracle and benchmark
baseline. Scalar sums group by chunk either way, so ``r`` / ``primal`` /
``dual`` match the resident solve to f32 reduce-order.

The primal decision matrix x is never materialised — consumers stream it
out per chunk with :func:`decisions_chunk` using the returned
``(lam, tau)``, which reproduces the §5.4-projected solution exactly
(this is where the fused path's projection is "applied").
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from .bucketing import make_edges, threshold_from_hist
from .postprocess import (
    profit_edges,
    profit_edges_fixed,
    removable_hist,
    threshold_and_removed,
    threshold_from_removable_hist,
)
from .solver import (
    _chunk_xs,
    _flat_axis_index,
    _kernel_tile,
    _psum,
    _solve_local,
    _straggler_mask,
    iterate_multipliers,
    scd_chunk_accumulate,
)
from .sparse_scd import select_sparse
from .types import SolverConfig, SparseKP

__all__ = ["ChunkSource", "StreamResult", "array_source", "solve_streaming",
           "stream_solve_fn", "decisions_chunk", "decisions_rows",
           "finalize_chunk_accumulate", "ordered_fold"]


def ordered_fold(x, axis=0):
    """Sum ``x`` over ``axis`` in strict index order (f32-deterministic).

    ``jnp.sum`` lets XLA reassociate the reduction (SIMD lanes regroup
    the f32 additions), and a ``psum``'s grouping is whatever the
    backend's all-reduce does. This fold pins the exact addition chain
    ``x[0] + x[1] + ... + x[S-1]`` — a ``fori_loop`` (never unrolled)
    with each partial sum behind an optimization barrier — so combining
    per-shard partials through it yields the same bits no matter how the
    shards are laid out over physical devices. The host-fed sharded
    driver (core/prefetch.py) folds its virtual-slot accumulators with
    this, which is what makes a checkpoint resumed on a *smaller* mesh
    bitwise-identical to the uninterrupted run: the slot partials are
    mesh-independent, and so is this combine. On the CPU backend the
    chain also coincides with ``psum``'s in-rank-order all-reduce, which
    is why the slots==devices case stays field-for-field bitwise with
    the traced ``shard_map`` driver (pinned by tests).
    """
    def body(i, acc):
        row = jax.lax.dynamic_index_in_dim(x, i, axis, keepdims=False)
        return jax.lax.optimization_barrier(acc + row)

    init = jax.lax.index_in_dim(x, 0, axis, keepdims=False)
    return jax.lax.fori_loop(1, x.shape[axis], body, init)


class ChunkSource(NamedTuple):
    """A sparse GKP instance delivered as on-demand user chunks.

    ``fn(i)`` maps a (traced) int32 chunk index to ``(p, b)`` arrays of
    shape (chunk, K) holding rows [i*chunk, (i+1)*chunk) of the virtual
    (n, K) instance. Rows at global index >= n — the ragged tail and any
    index past the last chunk — MUST come back as p = b = 0: such rows
    are inert in every pass (invalid candidates, never selected, zero
    consumption/profit; the finalize passes additionally exclude
    zero-selection rows from the group-profit min/max). ``fn`` must be
    jit-traceable; it runs inside ``lax.scan`` on device. For chunks
    that exist on the host (numpy / memory-mapped files) use
    :mod:`repro.core.prefetch` instead.
    """

    n: int                 # virtual user count
    k: int                 # knapsacks (== items, sparse form)
    chunk: int             # rows per chunk
    budgets: jnp.ndarray   # (K,) global budgets
    fn: Callable           # i -> (p (chunk, K), b (chunk, K))


class StreamResult(NamedTuple):
    """Streaming solve output: SolveResult minus the O(n) fields.

    No ``x`` (stream it with :func:`decisions_chunk`). ``tau`` is the
    §5.4 feasibility threshold the final primal was projected with
    (-inf when nothing was removed; +inf when the fused ladder's
    overflow fallback removed everything). ``history`` is None unless
    ``cfg.record_history`` with ``cfg.metrics_every`` sampling is on."""

    lam: jnp.ndarray       # (K,) final multipliers
    iters: jnp.ndarray     # () int32
    r: jnp.ndarray         # (K,) post-projection consumption
    primal: jnp.ndarray    # () post-projection primal objective
    dual: jnp.ndarray      # () dual objective at lam
    tau: jnp.ndarray       # () group-profit removal threshold (§5.4)
    history: Optional[dict] = None  # sampled per-iteration records
    # Fused-finalize removable (cons_hist (K, E+1), gain_hist (E+1,)):
    # attached by the host-fed driver (core/prefetch.py) so kill/resume
    # tests can compare the accumulated §5.4 histograms; the traced
    # driver leaves it None (it never leaves the jitted program).
    fin_hist: Optional[tuple] = None
    # Active-set screening telemetry (cfg.screening, core/screening.py):
    # the traced driver reports {"active_chunks": (max_iters,) i32 global
    # streamed-chunk count per iteration (-1 past convergence),
    # "resets": () i32 floor escapes}; the host-fed driver attaches its
    # HostScreen.stats() dict (survivor mask, bounds, floor, per-epoch
    # streamed counts). None when screening is off.
    screen: Optional[dict] = None


def _num_chunks(n, chunk):
    return -(-n // chunk)


def array_source(kp: SparseKP, chunk: int) -> ChunkSource:
    """Wrap a resident SparseKP as a ChunkSource (parity/testing path).

    The arrays are padded to a chunk multiple with inert rows once;
    ``fn`` dynamically slices chunk i and zeroes any chunk index past the
    end (mesh padding can ask for a few). A real out-of-core deployment
    replaces this with a generated source (data/synth.py) or host-fed
    chunks (core/prefetch.py).
    """
    p3, b3 = _chunk_xs(kp, chunk)
    c = p3.shape[0]

    def fn(i):
        # Select-based masking, NOT `p3[j] * (i < c)`: a multiplicative
        # mask hands XLA an algebraic identity it will fuse into the
        # downstream candidate arithmetic (FMA / reassociation), shifting
        # v1 by an ulp and flipping bucket assignments near the 1e-4-wide
        # inner buckets — which breaks the bitwise streaming==resident
        # contract. jnp.where passes the original values through exactly.
        j = jnp.minimum(i, c - 1)
        live = i < c
        return (jnp.where(live, p3[j], 0.0), jnp.where(live, b3[j], 0.0))

    return ChunkSource(n=kp.p.shape[0], k=kp.p.shape[1], chunk=chunk,
                       budgets=kp.budgets, fn=fn)


def _scan_chunks(body, init, i0, length):
    """Scan ``body`` over chunk indices [i0, i0 + length) carrying state."""
    def wrapped(carry, _):
        state, i = carry
        return (body(state, i), i + 1), None
    (state, _), _ = jax.lax.scan(wrapped, (init, i0), None, length=length)
    return state


def _scd_pass_local(source, lam, edges, q, cfg, i0, cpl, keep, scale):
    """This shard's SCD map at lam: local (hist (K, E+1), top (K,)).

    The scan body of :func:`_scd_pass_stream`, factored out so the
    screened update's fallback pass can rebuild the exact unscreened
    local partials (same closure, same scan) before the collectives.
    """
    hist0 = jnp.zeros((source.k, edges.shape[-1] + 1), jnp.float32)
    top0 = jnp.full((source.k,), -jnp.inf, lam.dtype)

    def body(carry, i):
        hist, top = carry
        p_c, b_c = source.fn(i)
        if cfg.use_kernels:
            return scd_chunk_accumulate(p_c, b_c, lam, edges, q, cfg,
                                        hist, top)
        return scd_chunk_accumulate(p_c, b_c, lam, edges, q, cfg,
                                    hist, top, keep, scale)

    return _scan_chunks(body, (hist0, top0), i0, cpl)


def _scd_pass_stream(source, lam, q, cfg, axis, i0, cpl, keep, scale):
    """One full SCD map+reduce at lam, streamed -> proposed lam (K,).

    Straggler scaling follows the resident conventions exactly (the
    histogram is linear in v2, so both are the same estimator, but f32
    multiplication does not distribute over the sum): the jnp path
    scales v2 per row before the scatter, the fused kernel path scales
    the accumulated histogram once — bit-for-bit what
    ``solver._scd_pass_chunked`` does, even with partial_fraction < 1.
    """
    edges = make_edges(lam, cfg.bucket_delta, cfg.bucket_growth, cfg.bucket_half)
    hist, top = _scd_pass_local(source, lam, edges, q, cfg, i0, cpl,
                                keep, scale)
    if cfg.use_kernels:
        hist = hist * (keep * scale)
    hist = _psum(hist, axis)
    top = jax.lax.pmax(top, axis) if axis is not None else top
    return threshold_from_hist(hist, edges, source.budgets, top)


def _update_stream(source, lam, q, cfg, axis, i0, cpl):
    """One SCD/DD iteration with the map streamed over this shard's chunks.

    Mirrors ``solver._scd_update`` / ``solver._dd_update`` exactly
    (sync and cyclic CD, straggler scaling on the accumulated
    histogram/consumption) so the streaming trajectory keeps the
    bit-identity contract with the resident solve.
    """
    keep, scale = _straggler_mask(cfg, axis)
    if cfg.algo == "dd":
        def body(r, i):
            p_c, b_c = source.fn(i)
            x = select_sparse(p_c, b_c, lam, q)
            return r + jnp.sum(b_c * x.astype(b_c.dtype), axis=0)
        r = _scan_chunks(body, jnp.zeros_like(lam), i0, cpl)
        r = _psum(r * keep, axis) * scale
        return jnp.maximum(lam + cfg.dd_lr * (r - source.budgets), 0.0)
    if cfg.cd_mode == "cyclic":
        for kk in range(source.k):
            lam_k = _scd_pass_stream(source, lam, q, cfg, axis, i0, cpl,
                                     keep, scale)[kk]
            lam = lam.at[kk].set(lam_k)
        return lam
    return _scd_pass_stream(source, lam, q, cfg, axis, i0, cpl, keep, scale)


def _screened_update_stream(source, lam, aux, q, cfg, axis, i0, cpl):
    """One screened sync-SCD iteration: skip provably-bucket-0 chunks.

    ``aux`` carries the shard-local active mask (cpl,), the per-chunk
    λ-independent bounds (cpl, K), the global λ floor (K,), the
    per-iteration global streamed-chunk counts, the floor-escape count
    and the iteration cursor (core/screening.py has the safety
    argument). The screened histogram is trusted — bit-identical buckets
    >= 1 and a crossing above bucket 0 for every knapsack — or the
    iteration falls back to one full unscreened pass. shard_map
    discipline: every collective runs unconditionally (the fallback
    ``lax.cond`` computes only *local* partials, zeros when trusted —
    same structure as ``_history_metrics_fn``), and both the trust
    predicate and the floor state are replicated, so all shards take the
    same branch.
    """
    from .screening import chunk_bound, crossing_trusted

    keep, scale = _straggler_mask(cfg, axis)
    active, bmax, lam_lo, counts, resets, it = aux

    # Floor protocol: λ below the certified interval reactivates every
    # chunk for this iteration (full pass => trivially trusted) and
    # re-anchors the floor; otherwise the floor only ever rises, so the
    # e0(lam_lo) certificates stay valid and the active set shrinks
    # monotonically between escapes.
    floor = (lam * cfg.screening_floor).astype(lam_lo.dtype)
    ok = jnp.all(lam >= lam_lo)
    active_eff = active | ~ok
    resets = resets + jnp.where(ok, 0, 1).astype(jnp.int32)
    lam_lo = jnp.where(ok, jnp.maximum(lam_lo, floor), floor)

    edges = make_edges(lam, cfg.bucket_delta, cfg.bucket_growth,
                       cfg.bucket_half)
    hist0 = jnp.zeros((source.k, edges.shape[-1] + 1), jnp.float32)
    top0 = jnp.full((source.k,), -jnp.inf, lam.dtype)

    def body(carry, i):
        il = i - i0

        def run(c):
            hist, top, bm = c
            p_c, b_c = source.fn(i)
            if cfg.use_kernels:
                hist, top = scd_chunk_accumulate(p_c, b_c, lam, edges, q,
                                                 cfg, hist, top)
            else:
                hist, top = scd_chunk_accumulate(p_c, b_c, lam, edges, q,
                                                 cfg, hist, top, keep, scale)
            bm = jax.lax.dynamic_update_index_in_dim(
                bm, chunk_bound(p_c, b_c), il, 0)
            return hist, top, bm

        return jax.lax.cond(active_eff[il], run, lambda c: c, carry)

    hist, top, bmax = _scan_chunks(body, (hist0, top0, bmax), i0, cpl)
    if cfg.use_kernels:
        hist = hist * (keep * scale)
    hist = _psum(hist, axis)
    top = jax.lax.pmax(top, axis) if axis is not None else top

    n_active = _psum(jnp.sum(active_eff.astype(jnp.int32)), axis)
    n_retired = _psum(jnp.sum((~active_eff).astype(jnp.int32)), axis)
    trusted = (n_retired == 0) | crossing_trusted(hist, source.budgets)
    prop_scr = threshold_from_hist(hist, edges, source.budgets, top)

    # Fallback: the full unscreened pass, local partials only inside the
    # cond (zeros when trusted), collectives unconditional.
    def fb(_):
        return _scd_pass_local(source, lam, edges, q, cfg, i0, cpl,
                               keep, scale)

    hist_fb, top_fb = jax.lax.cond(
        trusted, lambda _: (hist0, top0), fb, None)
    if cfg.use_kernels:
        hist_fb = hist_fb * (keep * scale)
    hist_fb = _psum(hist_fb, axis)
    top_fb = jax.lax.pmax(top_fb, axis) if axis is not None else top_fb
    prop_fb = threshold_from_hist(hist_fb, edges, source.budgets, top_fb)
    prop = jnp.where(trusted, prop_scr, prop_fb)

    # Retire after the accepted proposal: a chunk whose bound clears the
    # floor's lowest edge for every knapsack can never contribute mass
    # above bucket 0 at any λ >= lam_lo. Inert padded chunks bound to
    # -inf and retire on their first pass.
    e0 = make_edges(lam_lo, cfg.bucket_delta, cfg.bucket_growth,
                    cfg.bucket_half)[:, 0]
    can = jnp.all(bmax <= e0[None, :], axis=-1)
    active_new = active_eff & ~can
    counts = counts.at[jnp.minimum(it, counts.shape[0] - 1)].set(n_active)
    return prop, (active_new, bmax, lam_lo, counts, resets, it + 1)


def adjusted_profit_chunk(p_c, b_c, lam):
    """``p - lam*b`` with the product pinned behind an optimization
    barrier. Without it XLA is free to contract the multiply-subtract
    into an FMA — or not — depending on the surrounding program, and the
    host-fed driver (core/prefetch.py) runs this same arithmetic under
    its own small jits: a context-dependent half-ulp in ``ap`` would
    break the host==traced bitwise contract (it flips the dual sum's
    last bit and, near a ladder edge, whole histogram mass units). The
    barrier makes the rounding the same in every compilation context.
    """
    return p_c - jax.lax.optimization_barrier(lam[None, :] * b_c)


def _chunk_primal(source, lam, q, i):
    """Map pass for chunk i at lam: (p, b, x, cons, ptilde, valid mask).

    ``ptilde`` is the per-row sum of *selected adjusted profits* — the
    sparse group profit of §5.4 in the per-row reduction form the fused
    finalize kernel computes (kernels/scd_fused.finalize_block), so tau
    comparisons agree bitwise across the jnp body, the kernel and
    :func:`decisions_chunk` (a half-ulp difference would flip rows that
    sit exactly on the removal threshold).
    """
    p_c, b_c = source.fn(i)
    x = select_sparse(p_c, b_c, lam, q)
    cons = b_c * x.astype(b_c.dtype)
    ap = adjusted_profit_chunk(p_c, b_c, lam)
    # Same pinned row reduction as finalize_chunk_accumulate, so tau
    # comparisons agree bitwise with the pass that produced tau.
    pt = jax.lax.optimization_barrier(
        jnp.sum(jnp.where(x, ap, 0.0), axis=-1))
    rows = i * source.chunk + jnp.arange(source.chunk)
    return p_c, b_c, x, cons, pt, rows < source.n


def finalize_chunk_accumulate(p_c, b_c, lam, q, cfg, carry, pedges=None):
    """Fold one chunk into the running streaming-finalize accumulators.

    ``carry`` is ``(r (K,), primal (), dual_sum (), lo (), hi ())``,
    extended with the removable ``(cons_hist (K, E+1), gain_hist
    (E+1,))`` when ``pedges`` (the fixed §5.4 ladder) is given. The
    histograms are carry-seeded (scatter-add onto the carry / Pallas
    accumulator init), so accumulating chunk by chunk is bit-identical
    to one pass over all rows — the same contract as
    ``solver.scd_chunk_accumulate``; the scalar sums group by chunk
    (reduce-order only). Shared by the device scan bodies below and the
    host-fed epoch driver (core/prefetch.py), which is what keeps the
    two paths bit-identical to each other.
    """
    if cfg.use_kernels:
        from ..kernels import ops as kops
        tile = _kernel_tile(cfg, p_c.shape[0])
        if pedges is None:
            r, primal, dual_sum, lo, hi = carry
            _, _, r, primal, dual_sum, lo, hi = kops.scd_finalize_hist(
                p_c, b_c, lam, jnp.zeros((1,), lam.dtype), q, tile_n=tile,
                with_hist=False, r_init=r,
                sums_init=jnp.stack([primal, dual_sum]),
                maxs_init=jnp.stack([hi, -lo]))
            return r, primal, dual_sum, lo, hi
        r, primal, dual_sum, lo, hi, ch, gh = carry
        ch, gh, r, primal, dual_sum, lo, hi = kops.scd_finalize_hist(
            p_c, b_c, lam, pedges, q, tile_n=tile,
            cons_hist_init=ch, gain_hist_init=gh, r_init=r,
            sums_init=jnp.stack([primal, dual_sum]),
            maxs_init=jnp.stack([hi, -lo]))
        return r, primal, dual_sum, lo, hi, ch, gh
    x = select_sparse(p_c, b_c, lam, q)
    ap = adjusted_profit_chunk(p_c, b_c, lam)
    # The optimization barriers pin the per-row reductions: XLA would
    # otherwise be free to compute e.g. the full dual sum directly from
    # the where-array in one program and as a sum of these row sums in
    # another (it does, once the histogram ops share the body), and the
    # regrouped f32 additions break the host==traced bitwise contract —
    # see adjusted_profit_chunk. The scalar sums below are *defined* as
    # sums of the pinned row sums, so every compilation context performs
    # the same additions in the same order.
    barrier = jax.lax.optimization_barrier
    cons = barrier(b_c * x.astype(b_c.dtype))
    pt = barrier(jnp.sum(jnp.where(x, ap, 0.0), axis=-1))
    gain = barrier(jnp.sum(jnp.where(x, p_c, 0.0), axis=-1))
    inf = jnp.asarray(jnp.inf, lam.dtype)
    if pedges is None:
        r, primal, dual_sum, lo, hi = carry
    else:
        r, primal, dual_sum, lo, hi, ch, gh = carry
    r = r + jnp.sum(cons, axis=0)
    primal = primal + jnp.sum(gain)
    dual_sum = dual_sum + jnp.sum(pt)
    sel = jnp.any(x, axis=-1)
    lo = jnp.minimum(lo, jnp.min(jnp.where(sel, pt, inf)))
    hi = jnp.maximum(hi, jnp.max(jnp.where(sel, pt, -inf)))
    if pedges is None:
        return r, primal, dual_sum, lo, hi
    ch = removable_hist(pt, cons, pedges, init=ch)
    gh = removable_hist(pt, gain[:, None], pedges, init=gh[None, :])[0]
    return r, primal, dual_sum, lo, hi, ch, gh


def _metrics_init(k, dtype):
    inf = jnp.asarray(jnp.inf, dtype)
    return (jnp.zeros((k,), dtype), jnp.zeros((), dtype),
            jnp.zeros((), dtype), inf, -inf)


def _pinned_dot(a, b):
    """lam·budgets as a pinned multiply-then-reduce. ``jnp.dot`` of two
    (K,) vectors is FMA-chained at XLA's discretion, which varies with
    the surrounding program; the streamed dual objective must assemble
    identically in the traced driver and the host-fed driver's small
    jits (see adjusted_profit_chunk)."""
    return jnp.sum(jax.lax.optimization_barrier(a * b))


def _metrics_stream(source, lam, q, cfg, axis, i0, cpl):
    """r / primal / dual plus the global group-profit range, streamed.

    Legacy finalize pass 1 of 3 (also the sampled-history pass): one
    full source scan for the metrics partials alone.
    """
    def body(carry, i):
        p_c, b_c = source.fn(i)
        return finalize_chunk_accumulate(p_c, b_c, lam, q, cfg, carry)

    r, primal, dual_sum, lo, hi = _scan_chunks(
        body, _metrics_init(source.k, lam.dtype), i0, cpl)
    r = _psum(r, axis)
    primal = _psum(primal, axis)
    dual = _psum(dual_sum, axis) + _pinned_dot(lam, source.budgets)
    if axis is not None:
        lo = jax.lax.pmin(lo, axis)
        hi = jax.lax.pmax(hi, axis)
    return r, primal, dual, lo, hi


def _postprocess_stream(source, lam, q, cfg, axis, i0, cpl, r, lo, hi):
    """§5.4 projection, streamed: histogram pass -> tau -> removal pass.

    Legacy finalize passes 2 and 3 of 3: the histogram against the
    data-dependent (lo, hi) ladder, then a dedicated projection-apply
    pass re-summing the kept rows. The fused finalize replaces both with
    histogram prefix subtractions — see ``_finalize_stream``.
    """
    edges = profit_edges(lo, hi, cfg.profit_buckets)

    def hist_body(hist, i):
        _, _, _, cons, pt, _ = _chunk_primal(source, lam, q, i)
        return removable_hist(pt, cons, edges, init=hist)

    hist = _scan_chunks(
        hist_body, jnp.zeros((source.k, edges.shape[0] + 1), lam.dtype),
        i0, cpl)
    hist = _psum(hist, axis)
    tau = threshold_from_removable_hist(hist, edges, r, source.budgets)

    def apply_body(carry, i):
        r2, primal2 = carry
        p_c, _, x, cons, pt, _ = _chunk_primal(source, lam, q, i)
        keep_row = pt > tau
        x = x & keep_row[:, None]
        cons = cons * keep_row[:, None].astype(cons.dtype)
        return (r2 + jnp.sum(cons, axis=0),
                primal2 + jnp.sum(jnp.where(x, p_c, 0.0)))

    r2, primal2 = _scan_chunks(
        apply_body, (jnp.zeros_like(r), jnp.zeros((), lam.dtype)), i0, cpl)
    return _psum(r2, axis), _psum(primal2, axis), tau


def _finalize_stream(source, lam, q, cfg, axis, i0, cpl):
    """The fused finalize: ONE pass for metrics + §5.4 (DESIGN.md §5c).

    A single scan accumulates the metrics partials and — when
    ``cfg.postprocess`` — the removable consumption/profit histograms
    against the *fixed* geometric ladder (the data-dependent (lo, hi)
    ladder of the legacy path is only known once this same pass ends).
    The projection then needs no further pass: removing every group at
    or below an edge removes exactly the histogram prefix sums, so the
    post-projection (r, primal) are constant-size subtractions and the
    actual removal happens lazily in :func:`decisions_chunk` via tau.
    Returns (r, primal, dual, tau).
    """
    init = _metrics_init(source.k, lam.dtype)
    pedges = None
    if cfg.postprocess:
        pedges = profit_edges_fixed(cfg.profit_buckets, cfg.profit_ladder_lo,
                                    cfg.profit_ladder_hi, lam.dtype)
        init = init + (jnp.zeros((source.k, pedges.shape[0] + 1), lam.dtype),
                       jnp.zeros((pedges.shape[0] + 1,), lam.dtype))

    def body(carry, i):
        p_c, b_c = source.fn(i)
        return finalize_chunk_accumulate(p_c, b_c, lam, q, cfg, carry, pedges)

    out = _scan_chunks(body, init, i0, cpl)
    r = _psum(out[0], axis)
    primal = _psum(out[1], axis)
    dual = _psum(out[2], axis) + _pinned_dot(lam, source.budgets)
    if not cfg.postprocess:
        return r, primal, dual, jnp.asarray(-jnp.inf, lam.dtype)
    ch = _psum(out[5], axis)
    gh = _psum(out[6], axis)
    tau, removed_cons, removed_gain = threshold_and_removed(
        ch, gh, pedges, r, source.budgets)
    return r - removed_cons, primal - removed_gain, dual, tau


def _history_metrics_fn(source, q, cfg, axis, i0, cpl):
    """Sampled per-iteration history for the scan driver (streaming).

    Every ``cfg.metrics_every``-th iteration pays one extra metrics pass
    over the source (lax.cond around the scan; the collectives run
    unconditionally on the local partials so shard_map stays uniform);
    unsampled iterations record NaN scalars. ``lam`` is recorded every
    row. Iterations frozen at convergence keep re-recording the
    converged sample — correct, just not free; analysis mode only.
    """
    every = max(cfg.metrics_every, 1)
    k = source.k

    def metrics_fn(lam, it):
        nan = jnp.asarray(jnp.nan, lam.dtype)

        def live(_):
            def body(carry, i):
                p_c, b_c = source.fn(i)
                return finalize_chunk_accumulate(p_c, b_c, lam, q, cfg, carry)
            r, primal, dual_sum, _, _ = _scan_chunks(
                body, _metrics_init(k, lam.dtype), i0, cpl)
            return r, primal, dual_sum

        def skip(_):
            return jnp.full((k,), nan), nan, nan

        sampled = ((it - 1) % every) == 0
        r, primal, dual_sum = jax.lax.cond(sampled, live, skip, None)
        r = _psum(r, axis)
        primal = _psum(primal, axis)
        dual = _psum(dual_sum, axis) + _pinned_dot(lam, source.budgets)
        viol = jnp.max(jnp.maximum(r - source.budgets, 0.0) / source.budgets)
        return {
            "lam": lam,
            "primal": primal,
            "dual": dual,
            "gap": dual - primal,
            "max_violation": viol,
        }

    return metrics_fn


def _presolve_stream(source, lam0, q, cfg, axis, i0, cpl, shards):
    """§5.3 warm start from the shard's leading chunks (materialised).

    As many consecutive chunks as the requested sample size needs are
    materialised (they are small by construction — this is the one place
    the streaming solve holds more than one chunk), so
    ``presolve_samples > chunk`` is honoured exactly like the resident
    ``_presolve`` instead of being silently capped at one chunk."""
    if cfg.presolve_samples <= 0:
        return lam0
    s = min(cfg.presolve_samples, cpl * source.chunk, source.n)
    m = -(-s // source.chunk)
    parts = [source.fn(i0 + j) for j in range(m)]
    p = jnp.concatenate([pp for pp, _ in parts])[:s]
    b = jnp.concatenate([bb for _, bb in parts])[:s]
    frac = s * shards / source.n
    small = SparseKP(p=p, b=b, budgets=source.budgets * frac)
    sub_cfg = cfg.replace(presolve_samples=0, record_history=False,
                          postprocess=False, chunk_size=None)
    return _solve_local(small, lam0, q, sub_cfg, axis).lam


def _stream_entry(budgets, lam0, *, source, q, cfg, axis, cpl, shards):
    i0 = _flat_axis_index(axis) * cpl if axis is not None else jnp.int32(0)
    source = source._replace(budgets=budgets)
    lam0 = _presolve_stream(source, lam0, q, cfg, axis, i0, cpl, shards)
    metrics_fn = (_history_metrics_fn(source, q, cfg, axis, i0, cpl)
                  if cfg.record_history else None)
    screen = None
    if cfg.screening:
        aux0 = (
            jnp.ones((cpl,), jnp.bool_),
            jnp.full((cpl, source.k), jnp.inf, jnp.float32),
            (lam0 * cfg.screening_floor).astype(jnp.float32),
            jnp.full((cfg.max_iters,), -1, jnp.int32),
            jnp.int32(0),
            jnp.int32(0),
        )
        lam, iters, hist, aux = iterate_multipliers(
            lambda lam, aux: _screened_update_stream(
                source, lam, aux, q, cfg, axis, i0, cpl),
            lam0, cfg, metrics_fn, aux0=aux0)
        screen = {"active_chunks": aux[3], "resets": aux[4]}
    else:
        lam, iters, hist = iterate_multipliers(
            lambda lam: _update_stream(source, lam, q, cfg, axis, i0, cpl),
            lam0, cfg, metrics_fn)
    if cfg.stream_finalize == "legacy":
        r, primal, dual, lo, hi = _metrics_stream(
            source, lam, q, cfg, axis, i0, cpl)
        if cfg.postprocess:
            r, primal, tau = _postprocess_stream(
                source, lam, q, cfg, axis, i0, cpl, r, lo, hi)
        else:
            tau = jnp.asarray(-jnp.inf, lam.dtype)
    else:
        r, primal, dual, tau = _finalize_stream(
            source, lam, q, cfg, axis, i0, cpl)
    return StreamResult(lam, iters, r, primal, dual, tau, hist,
                        screen=screen)


def _validate_stream_cfg(cfg):
    """Shared by the traced driver here and the host-fed one (prefetch)."""
    if cfg.algo == "scd" and cfg.reduce != "bucketed":
        raise ValueError("solve_streaming requires reduce='bucketed' "
                         "(the exact reduce must sort all candidates)")
    if cfg.stream_finalize not in ("fused", "legacy"):
        raise ValueError(
            f"stream_finalize must be 'fused' or 'legacy', "
            f"got {cfg.stream_finalize!r}")
    if cfg.record_history and cfg.metrics_every < 1:
        raise ValueError(
            "record_history=True would re-scan the whole chunk source on "
            "every iteration when streaming. Either set cfg.metrics_every=m "
            "to sample the streamed metrics every m-th iteration (one extra "
            "source pass per sample), or solve resident "
            "(repro.core.solver.solve / solve_sharded), where per-iteration "
            "history is free.")
    if cfg.screening and (cfg.algo != "scd" or cfg.cd_mode != "sync"
                          or cfg.reduce != "bucketed"):
        raise ValueError(
            "cfg.screening requires the synchronous-SCD bucketed streaming "
            "path (algo='scd', cd_mode='sync', reduce='bucketed'): the "
            "safe-elimination certificates are statements about the bucket "
            "ladder (core/screening.py), and DD/cyclic have no bucketed "
            "skip contract.")


def stream_solve_fn(source: ChunkSource, cfg: SolverConfig = SolverConfig(),
                    q: int = 1, mesh=None, axes: Optional[tuple] = None):
    """Build the jitted streaming entry: (budgets, lam0) -> StreamResult.

    The single place the chunk-index partitioning and (optional)
    shard_map wiring live — ``solve_streaming`` calls the returned
    function, and AOT consumers (launch/dryrun.py, bench_chunked) call
    ``.lower()`` on it so the program they analyse is exactly the one
    users run. Validates cfg like :func:`solve_streaming`.
    """
    _validate_stream_cfg(cfg)
    c = _num_chunks(source.n, source.chunk)
    if mesh is None:
        return jax.jit(functools.partial(
            _stream_entry, source=source, q=q, cfg=cfg, axis=None, cpl=c,
            shards=1))
    axes = tuple(mesh.axis_names) if axes is None else axes
    shards = 1
    for name, size in zip(mesh.axis_names, mesh.devices.shape):
        if name in axes:
            shards *= size
    cpl = _num_chunks(c, shards)
    hist_specs = None
    if cfg.record_history:
        hist_specs = {"lam": P(), "primal": P(), "dual": P(), "gap": P(),
                      "max_violation": P()}
    screen_specs = None
    if cfg.screening:
        # Both telemetry entries are replicated: the count is psum'd and
        # the reset counter is driven by the replicated floor check.
        screen_specs = {"active_chunks": P(), "resets": P()}
    fn = shard_map(
        functools.partial(_stream_entry, source=source, q=q, cfg=cfg,
                          axis=axes, cpl=cpl, shards=shards),
        mesh=mesh,
        in_specs=(P(), P()),
        out_specs=StreamResult(lam=P(), iters=P(), r=P(), primal=P(),
                               dual=P(), tau=P(), history=hist_specs,
                               screen=screen_specs),
        check_vma=False,
    )
    return jax.jit(fn)


def solve_streaming(source: ChunkSource, cfg: SolverConfig = SolverConfig(),
                    q: int = 1, lam0=None, mesh=None,
                    axes: Optional[tuple] = None) -> StreamResult:
    """Solve a ChunkSource-backed sparse GKP with O(chunk) device state.

    One jitted program runs the whole solve: the early-exit
    ``while_loop`` driver of core/solver.py, with every map pass a
    ``lax.scan`` over on-demand chunks. Per-device memory is
    O(chunk·K + K·E) — independent of ``source.n`` — which is what lets
    the chunked benchmark solve instances ~10x past the unchunked HBM
    ceiling at flat peak memory (BENCH_chunked.json). A converged solve
    touches the source ``iters + 1`` times (fused finalize, the
    default) or ``iters + 3`` times (``cfg.stream_finalize="legacy"``);
    see DESIGN.md §5c for the pass accounting.

    With ``mesh`` the chunk index range is split across every mesh axis
    (``axes`` to restrict): shard s scans chunks [s*cpl, (s+1)*cpl), the
    histogram/consumption reduce is the same constant-size psum as
    ``solve_sharded``. Chunk indices past the last real chunk produce
    all-inert rows by the ChunkSource contract.

    Restrictions (each raises ValueError): SCD requires the bucketed
    reduce (the exact reduce cannot stream), and ``record_history``
    needs ``cfg.metrics_every`` sampling (an unsampled history would
    re-scan the source every iteration). ``cfg.chunk_size`` is ignored —
    the source's chunk is the chunk.

    The multiplier trajectory (lam, iters) is bit-identical to
    ``solve``/``solve_sharded`` on the same rows for the SCD bucketed
    path; r/primal/dual group their sums by chunk and so agree to f32
    reduce-order. The §5.4 projection is bucketed (conservative) like
    the sharded resident solve's; the fused finalize bins against the
    fixed geometric ladder (postprocess.profit_edges_fixed), the legacy
    finalize against the data-dependent (lo, hi) ladder.
    """
    fn = stream_solve_fn(source, cfg, q, mesh=mesh, axes=axes)
    if lam0 is None:
        lam0 = jnp.ones((source.k,), cfg.dtype)
    return fn(source.budgets, lam0)


def decisions_rows(p_c, b_c, lam, q: int, valid, tau=None):
    """Primal decision rows for one chunk's arrays at a solved ``(lam, tau)``.

    The body of :func:`decisions_chunk` on explicit ``(chunk, K)`` arrays
    — the single place the lookup arithmetic lives, shared by the full
    chunk export below and the on-demand decision service
    (:mod:`repro.serve.decisions`), which feeds it host chunks under its
    own jit. The selected-adjusted-profit row sum compared against
    ``tau`` is the same pinned reduction as :func:`_chunk_primal` /
    ``finalize_chunk_accumulate`` (optimization barriers around the
    ``lam*b`` product and the row sum), so a row sitting exactly on the
    removal threshold resolves identically in every compilation context
    — this is what makes a cached single-chunk lookup bitwise-equal to
    materialising the whole solution. ``valid`` masks rows past the
    virtual ``n`` (the inert ragged tail).
    """
    x = select_sparse(p_c, b_c, lam, q)
    if tau is not None:
        ap = adjusted_profit_chunk(p_c, b_c, lam)
        pt = jax.lax.optimization_barrier(
            jnp.sum(jnp.where(x, ap, 0.0), axis=-1))
        x = x & (pt > tau)[:, None]
    return x & valid[:, None]


def decisions_chunk(source: ChunkSource, lam, q: int, i, tau=None):
    """Materialise the primal decisions for chunk ``i`` of a solved source.

    Returns (x (chunk, K) bool, valid (chunk,) bool): the greedy
    selection at ``lam`` with the §5.4 projection applied when ``tau``
    (from :class:`StreamResult`) is given — exactly the rows the resident
    solve's ``x`` would hold for this chunk. This is where the fused
    finalize's projection lands on actual rows: the solve itself never
    runs a removal pass. Stream over ``range(ceil(n/chunk))`` to export
    a full solution without ever holding it whole; for point lookups
    ("what is x for user i?") use :class:`repro.serve.decisions.
    DecisionService`, which regenerates only the owning chunk through
    :func:`decisions_rows`.
    """
    lam = jnp.asarray(lam)
    p_c, b_c = source.fn(jnp.int32(i))
    rows = jnp.int32(i) * source.chunk + jnp.arange(source.chunk)
    valid = rows < source.n
    return decisions_rows(p_c, b_c, lam, q, valid, tau), valid
