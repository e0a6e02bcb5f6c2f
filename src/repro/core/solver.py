"""Distributed GKP solver driver: DD (Alg 2) and SCD (Alg 4).

One jitted program runs the whole iterative solve: the per-iteration
map (candidate generation / greedy solve) happens on the local user shard,
the reduce is a constant-size ``psum`` (bucketed histogram or consumption
vector), and the multiplier update is replicated. Distribution is explicit
``shard_map`` over the mesh with the user dimension sharded across *all*
mesh axes; ``mesh=None`` runs the identical code path on one device.

Deviations from the paper's Spark driver are listed in DESIGN.md §6:
notably the T-iteration loop runs inside the program (no per-iteration
job scheduling) — a ``lax.while_loop`` that exits at convergence, or,
when per-iteration history is recorded, a fixed-length ``lax.scan`` with
converged iterations frozen so the recorded iteration count matches
Alg 2/4 semantics. With ``cfg.use_kernels`` the sparse bucketed path runs
map + reduce as one fused Pallas kernel (kernels/scd_fused.py): only the
(K, E+1) histogram leaves the chip, never the (n, K) candidates.

Chunked map (``cfg.chunk_size``)
--------------------------------
With ``chunk_size=c`` the per-iteration map becomes a ``lax.scan`` over
fixed-size user chunks: each chunk is driven through the same map
(fused Pallas kernel or jnp candidates), accumulating into the running
(K, E+1) histogram / (K,) top (SCD) or (K,) consumption (DD). The
device-resident *working set* of an iteration is then O(c·K + K·E)
instead of O(n·K) — the shard's input arrays remain resident, so this
mode bounds intermediates, not inputs. For instances whose inputs do not
fit device memory, use :mod:`repro.core.chunked` (``solve_streaming``),
which generates or uploads chunks on the fly and keeps *nothing* O(n) on
device.

Chunked-vs-unchunked contract: with ``reduce="bucketed"`` the chunked
solve is **bit-identical** to the unchunked one — the histogram is
accumulated by seeding each chunk's scatter-add (jnp path) or Pallas
accumulator (kernel path) with the carried value, so the f32 addition
chain over rows is exactly the one the unchunked reduce performs. On the
kernel path this additionally requires the same user-tile decomposition
on both sides (``cfg.kernel_tile`` pins it; the default tile is derived
from the chunk size). The exact reduce cannot be chunked (it must sort
all candidates) and raises ``ValueError``. DD's consumption reduce is a
plain sum whose grouping follows the chunking, so chunked DD matches
unchunked DD only to f32 reduce-order (~1 ulp), not bitwise.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from .bucketing import (
    bucket_histogram,
    exact_threshold,
    make_edges,
    threshold_from_hist,
)
from .greedy import adjusted_profit, consumption, greedy_solve
from .postprocess import (
    feasibility_threshold_bucketed,
    feasibility_threshold_exact,
    group_profit,
)
from .scd import candidates_general
from .sparse_scd import candidates_sparse, select_sparse
from .types import DenseKP, SolverConfig, SparseKP

__all__ = ["SolveResult", "solve", "solve_fn", "solve_sharded",
           "dual_objective"]


class SolveResult(NamedTuple):
    """Everything a solve returns. Scalars/lam are replicated across the
    mesh; ``x`` is user-sharded like the inputs. ``x``/``history`` are
    ``None`` when the solve mode does not produce them (streaming solves
    never materialise x; history only exists with record_history)."""

    lam: jnp.ndarray        # (K,) final multipliers
    x: jnp.ndarray          # (n, K) or (n, M) bool primal solution (post-processed)
    iters: jnp.ndarray      # () int32, iterations until convergence
    r: jnp.ndarray          # (K,) final consumption (post-processed)
    primal: jnp.ndarray     # () primal objective (post-processed)
    dual: jnp.ndarray       # () dual objective at lam
    history: Optional[dict]  # per-iteration records when cfg asks


# --------------------------------------------------------------------------
# Per-iteration lambda updates (map + reduce fused).
# --------------------------------------------------------------------------

def _psum(x, axis):
    return jax.lax.psum(x, axis) if axis is not None else x


def _flat_axis_index(axis):
    """Flattened linear index across one or many mesh axes."""
    names = (axis,) if isinstance(axis, str) else tuple(axis)
    idx = jnp.int32(0)
    for name in names:
        idx = idx * jax.lax.psum(1, name) + jax.lax.axis_index(name)
    return idx


def _straggler_mask(cfg, axis):
    """Simulated straggler mitigation: proceed with a fraction of shards.

    Map results from slow shards are dropped and the histogram is unbiased
    by 1/fraction (same estimator as §5.3 pre-solving). With
    partial_fraction == 1.0 this is the identity.
    """
    if axis is None or cfg.partial_fraction >= 1.0:
        return 1.0, 1.0
    idx = _flat_axis_index(axis)
    size = jax.lax.psum(1, axis)
    keep = (idx.astype(jnp.float32) + 1.0) <= cfg.partial_fraction * size
    frac = jnp.maximum(cfg.partial_fraction, 1.0 / size)
    return keep.astype(jnp.float32), 1.0 / frac


def _kernel_tile(cfg, n):
    """User-axis tile for the Pallas kernels: cfg override or the ladder."""
    from ..kernels import ops as kops
    return cfg.kernel_tile if cfg.kernel_tile else kops.pick_tile(n)


def _scd_candidates(kp, lam, q, cfg=None):
    """Alg 5 (sparse) or Alg 3 (dense) map. Returns v1, v2: (Z, K)."""
    if isinstance(kp, SparseKP):
        if cfg is not None and cfg.use_kernels:
            from ..kernels import ops as kops
            n = kp.p.shape[0]
            return kops.scd_candidates(kp.p, kp.b, lam, q,
                                       tile_n=_kernel_tile(cfg, n))
        return candidates_sparse(kp.p, kp.b, lam, q)       # (n, K)
    v1, v2 = candidates_general(kp.p, kp.b, lam, kp.sets, kp.caps)
    n, k, pp = v1.shape
    v1 = v1.transpose(0, 2, 1).reshape(n * pp, k)
    v2 = v2.transpose(0, 2, 1).reshape(n * pp, k)
    return v1, v2


def _scd_reduce(v1, v2, lam, budgets, cfg, axis):
    """Alg 4 reduce over all K coordinates: exact or §5.2 bucketed."""
    if cfg.reduce == "exact":
        if axis is not None:
            v1 = jax.lax.all_gather(v1, axis, axis=0, tiled=True)
            v2 = jax.lax.all_gather(v2, axis, axis=0, tiled=True)
        return jax.vmap(exact_threshold, in_axes=(1, 1, 0))(v1, v2, budgets)
    edges = make_edges(lam, cfg.bucket_delta, cfg.bucket_growth, cfg.bucket_half)
    if cfg.use_kernels:
        from ..kernels import ops as kops
        hist = kops.bucket_hist(v1, v2, edges,
                                tile_n=_kernel_tile(cfg, v1.shape[0]))
    else:
        hist = bucket_histogram(v1, v2, edges)
    top = jnp.max(v1, axis=0)
    hist = _psum(hist, axis)
    top = jax.lax.pmax(top, axis) if axis is not None else top
    return threshold_from_hist(hist, edges, budgets, top)


def _scd_step_fused(kp, lam, q, keep, scale, cfg, axis):
    """Map + bucketed reduce in ONE Pallas kernel (sparse GKP hot path).

    The (n, K) candidate arrays stay in VMEM; only the (K, E+1) histogram
    and the (K,) running max reach HBM / the mesh collective. The
    straggler mask multiplies the histogram instead of v2 — the histogram
    is linear in v2, so the estimator is unchanged.
    """
    from ..kernels import ops as kops
    edges = make_edges(lam, cfg.bucket_delta, cfg.bucket_growth, cfg.bucket_half)
    hist, top = kops.scd_fused_hist(kp.p, kp.b, lam, edges, q,
                                    tile_n=cfg.kernel_tile)
    hist = _psum(hist * (keep * scale), axis)
    top = jax.lax.pmax(top, axis) if axis is not None else top
    return threshold_from_hist(hist, edges, kp.budgets, top)


# --------------------------------------------------------------------------
# Chunked map: lax.scan over fixed-size user chunks.
# --------------------------------------------------------------------------

def _chunk_xs(kp, chunk):
    """Pad the user axis to a chunk multiple and reshape for lax.scan.

    Returns (p, b) reshaped to (C, chunk, ...). Padded rows are
    ``p = b = 0`` — inert everywhere: invalid SCD candidates (v1 = -1,
    v2 = 0, zero histogram mass, never raise the running max), never
    selected by the greedy primal (adjusted profit 0), zero consumption.
    Scatter-adding their zero mass onto the histogram is bit-invisible
    (x + 0.0 == x for the non-negative masses involved), which is what
    keeps the ragged-final-chunk case bit-identical to unchunked.
    """
    n = kp.p.shape[0]
    c = -(-n // chunk)
    pad = c * chunk - n

    def rs(a):
        if pad:
            a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape((c, chunk) + a.shape[1:])

    return rs(kp.p), rs(kp.b)


def scd_chunk_accumulate(p_c, b_c, lam, edges, q, cfg, hist, top,
                         keep=None, scale=None):
    """Fold one user chunk into the running SCD (hist, top) accumulators.

    p_c, b_c: (c, K) sparse chunk; hist: (K, E+1) f32; top: (K,). The
    carried accumulators *seed* the chunk's reduction (Pallas accumulator
    init / scatter-add operand) rather than being summed with a
    per-chunk sub-histogram afterwards — that seeding is the bitwise
    chunked==unchunked guarantee (see the module docstring). ``keep`` /
    ``scale`` (straggler mask) are applied per-row on the jnp path,
    matching the unfused unchunked convention; the fused kernel path
    scales the final histogram instead (both are exact: the histogram is
    linear in v2). Shared by the in-memory chunked solve below and the
    streaming driver in core/chunked.py.
    """
    if cfg.use_kernels:
        from ..kernels import ops as kops
        return kops.scd_fused_hist(p_c, b_c, lam, edges, q,
                                   tile_n=cfg.kernel_tile,
                                   hist_init=hist, top_init=top)
    v1, v2 = candidates_sparse(p_c, b_c, lam, q)
    if keep is not None:
        v2 = v2 * keep * scale
    hist = bucket_histogram(v1, v2, edges, init=hist)
    top = jnp.maximum(top, jnp.max(v1, axis=0))
    return hist, top


def _scd_pass_chunked(kp, lam, q, keep, scale, cfg, axis, fused):
    """One SCD map+reduce with the user axis streamed in chunks."""
    edges = make_edges(lam, cfg.bucket_delta, cfg.bucket_growth, cfg.bucket_half)
    k = kp.budgets.shape[0]
    hist0 = jnp.zeros((k, edges.shape[-1] + 1), jnp.float32)
    top0 = jnp.full((k,), -jnp.inf, kp.p.dtype)
    xs = _chunk_xs(kp, cfg.chunk_size)
    dense = isinstance(kp, DenseKP)

    def body(carry, xs_c):
        hist, top = carry
        p_c, b_c = xs_c
        if dense:
            v1, v2 = candidates_general(p_c, b_c, lam, kp.sets, kp.caps)
            c, kk, pp = v1.shape
            v1 = v1.transpose(0, 2, 1).reshape(c * pp, kk)
            v2 = v2.transpose(0, 2, 1).reshape(c * pp, kk) * keep * scale
            hist = bucket_histogram(v1, v2, edges, init=hist)
            top = jnp.maximum(top, jnp.max(v1, axis=0))
        elif fused:
            hist, top = scd_chunk_accumulate(p_c, b_c, lam, edges, q, cfg,
                                             hist, top)
        else:
            hist, top = scd_chunk_accumulate(p_c, b_c, lam, edges, q, cfg,
                                             hist, top, keep, scale)
        return (hist, top), None

    (hist, top), _ = jax.lax.scan(body, (hist0, top0), xs)
    if fused:
        hist = hist * (keep * scale)
    hist = _psum(hist, axis)
    top = jax.lax.pmax(top, axis) if axis is not None else top
    return threshold_from_hist(hist, edges, kp.budgets, top)


def _scd_pass(kp, lam, q, keep, scale, cfg, axis):
    """One full SCD map+reduce at ``lam`` -> proposed multipliers (K,)."""
    fused = (isinstance(kp, SparseKP) and cfg.use_kernels
             and cfg.reduce == "bucketed")
    if cfg.chunk_size is not None:
        return _scd_pass_chunked(kp, lam, q, keep, scale, cfg, axis, fused)
    if fused:
        return _scd_step_fused(kp, lam, q, keep, scale, cfg, axis)
    v1, v2 = _scd_candidates(kp, lam, q, cfg)
    return _scd_reduce(v1, v2 * keep * scale, lam, kp.budgets, cfg, axis)


def _scd_update(kp, lam, q, cfg, axis):
    """One SCD iteration: candidates -> reduce -> new lam.

    cd_mode "sync": all K coordinates updated from one map pass (Alg 4).
    cd_mode "cyclic": K passes, coordinate k re-mapped at the already
    updated multipliers (classic Gauss-Seidel CD; §4.3.2's other mode).
    """
    keep, scale = _straggler_mask(cfg, axis)
    if cfg.cd_mode == "cyclic":
        for kk in range(kp.budgets.shape[0]):
            lam_k = _scd_pass(kp, lam, q, keep, scale, cfg, axis)[kk]
            lam = lam.at[kk].set(lam_k)
        return lam
    return _scd_pass(kp, lam, q, keep, scale, cfg, axis)


def _solve_primal(kp, lam, q):
    """Greedy primal solution and its consumption at multipliers lam."""
    if isinstance(kp, SparseKP):
        x = select_sparse(kp.p, kp.b, lam, q)
        cons = kp.b * x.astype(kp.b.dtype)                 # (n, K) per-user
    else:
        x = greedy_solve(adjusted_profit(kp.p, kp.b, lam), kp.sets, kp.caps)
        cons = consumption(kp.b, x)                        # (n, K)
    return x, cons


def _dd_update(kp, lam, q, cfg, axis):
    """Alg 2: projected sub-gradient step on the dual.

    With ``cfg.chunk_size`` the shard consumption is accumulated chunk by
    chunk (running (K,) carry); the grouping of that sum follows the
    chunking, so chunked DD tracks unchunked DD to reduce-order (~1 ulp),
    not bitwise — see the module docstring.
    """
    keep, scale = _straggler_mask(cfg, axis)
    if cfg.chunk_size is None:
        _, cons = _solve_primal(kp, lam, q)
        r = jnp.sum(cons, axis=0)
    else:
        def body(r, xs_c):
            ck = kp._replace(p=xs_c[0], b=xs_c[1])
            _, cons = _solve_primal(ck, lam, q)
            return r + jnp.sum(cons, axis=0), None
        r, _ = jax.lax.scan(body, jnp.zeros_like(lam),
                            _chunk_xs(kp, cfg.chunk_size))
    r = _psum(r * keep, axis) * scale                      # (K,)
    return jnp.maximum(lam + cfg.dd_lr * (r - kp.budgets), 0.0)


def dual_objective(kp, lam, q, axis=None, primal=None):
    """g(lam) = sum_i max_x [ p~ . x_i ] + lam . B  (upper bounds the IP).

    ``primal`` optionally passes a precomputed ``_solve_primal`` result so
    callers that already ran the map pass at lam don't run it twice.
    """
    x, _ = _solve_primal(kp, lam, q) if primal is None else primal
    if isinstance(kp, SparseKP):
        ap = kp.p - lam[None, :] * kp.b
        per_user = jnp.sum(jnp.where(x, ap, 0.0), axis=-1)
    else:
        ap = adjusted_profit(kp.p, kp.b, lam)
        per_user = jnp.sum(jnp.where(x, ap, 0.0), axis=-1)
    tot = _psum(jnp.sum(per_user), axis)
    return tot + jnp.dot(lam, kp.budgets)


# --------------------------------------------------------------------------
# Driver.
# --------------------------------------------------------------------------

def damped_multiplier_step(lam, dprev, prop, cfg):
    """One damped fixed-point step: proposed lam -> (lam_new, delta, moved).

    The single definition of the reversal-damping and convergence
    arithmetic (see :func:`iterate_multipliers` for the rationale),
    shared by the traced drivers here and the host-fed epoch driver
    (core/prefetch.py) — a second copy would silently break their
    bit-identical-trajectory contract the first time one was edited.
    """
    delta = prop - lam
    if cfg.cd_damping < 1.0 and cfg.algo == "scd":
        delta = delta * jnp.where(delta * dprev < 0.0, cfg.cd_damping, 1.0)
    lam_new = lam + delta
    moved = jnp.max(jnp.abs(lam_new - lam)) > cfg.tol * (1.0 + jnp.max(lam))
    return lam_new, delta, moved


def iterate_multipliers(update, lam0, cfg, metrics_fn=None, aux0=None):
    """Run the damped multiplier fixed-point iteration to convergence.

    ``update``: lam -> proposed lam (one Alg 2/4 iteration at lam).
    ``metrics_fn``: (lam, it) -> history record dict, called per
    iteration when ``cfg.record_history`` (fixed-length ``lax.scan``,
    converged iterations frozen; ``it`` is the just-finished iteration
    number, frozen too, so samplers like the streaming
    ``cfg.metrics_every`` path can key off it); otherwise a
    ``lax.while_loop`` exits at convergence. Both drivers share one step
    function, so lam / iters trajectories are bit-identical between
    them.

    Damping (``cfg.cd_damping``, SCD only): a coordinate whose step
    reverses sign relative to the previous iteration
    (delta_t * delta_{t-1} < 0) has its step scaled by the damping
    factor. This breaks the sync-CD period-2 limit cycle
    (bucket-interpolation wobble + Jacobi coupling keeps |delta|
    plateaued just above tol on small tight instances): each reversal
    halves the oscillation, so movement drops below tol geometrically.
    Monotone coordinates never see a reversal and are untouched. DD is
    exempt — its projected sub-gradient step (Alg 2) must be allowed to
    land exactly on the lam = 0 boundary, which a half-step would
    overshoot into the interior. Shared by the in-memory and streaming
    solve drivers, so their trajectories agree bit-for-bit given
    bit-identical updates.

    ``aux0``: optional pytree of auxiliary loop state the update owns
    (active-set screening carries its survivor masks / bounds through
    the loop this way). When given, ``update`` is called as
    ``update(lam, aux) -> (prop, aux_new)`` and the aux is frozen — like
    lam — once the solve converges (fixed-length scan mode keeps
    stepping the frozen carry). The no-aux path below is byte-for-byte
    the historical step function; the aux path is a separate closure so
    existing traced programs keep their exact jaxpr.

    Returns (lam, iters, history) — or (lam, iters, history, aux) when
    ``aux0`` is given.
    """
    def step(carry, _):
        lam, dprev, it, done = carry
        prop = update(lam)
        lam_new, delta, moved = damped_multiplier_step(lam, dprev, prop, cfg)
        lam_next = jnp.where(done, lam, lam_new)
        d_next = jnp.where(done, dprev, delta)
        it_next = it + jnp.where(done, 0, 1).astype(jnp.int32)
        done_next = done | ~moved
        rec = metrics_fn(lam_next, it_next) if cfg.record_history else None
        return (lam_next, d_next, it_next, done_next), rec

    def step_aux(carry, _):
        lam, dprev, it, done, aux = carry
        prop, aux_new = update(lam, aux)
        lam_new, delta, moved = damped_multiplier_step(lam, dprev, prop, cfg)
        lam_next = jnp.where(done, lam, lam_new)
        d_next = jnp.where(done, dprev, delta)
        it_next = it + jnp.where(done, 0, 1).astype(jnp.int32)
        done_next = done | ~moved
        aux_next = jax.tree.map(
            lambda a, b: jnp.where(done, a, b), aux, aux_new)
        rec = metrics_fn(lam_next, it_next) if cfg.record_history else None
        return (lam_next, d_next, it_next, done_next, aux_next), rec

    init = (lam0, jnp.zeros_like(lam0), jnp.int32(0), jnp.asarray(False))
    body = step if aux0 is None else step_aux
    if aux0 is not None:
        init = init + (aux0,)
    if cfg.record_history:
        out, hist = jax.lax.scan(body, init, None, length=cfg.max_iters)
    else:
        out = jax.lax.while_loop(
            lambda c: (c[2] < cfg.max_iters) & ~c[3],
            lambda c: body(c, None)[0],
            init,
        )
        hist = None
    lam, iters = out[0], out[2]
    if aux0 is None:
        return lam, iters, hist
    return lam, iters, hist, out[4]


def _metrics(kp, lam, q, axis):
    x, cons = _solve_primal(kp, lam, q)
    r = _psum(jnp.sum(cons, axis=0), axis)
    primal = _psum(jnp.sum(jnp.where(x, kp.p, 0.0)), axis)
    dual = dual_objective(kp, lam, q, axis, primal=(x, cons))
    viol = jnp.max(jnp.maximum(r - kp.budgets, 0.0) / kp.budgets)
    return x, cons, r, primal, dual, viol


def _solve_local(kp, lam0, q, cfg, axis=None):
    """The full solve on one shard (axis=None) or inside shard_map.

    The iteration loop is ``iterate_multipliers`` (while_loop fast path /
    scan history path). The final primal, metrics and §5.4 projection run
    over the whole resident shard even when ``cfg.chunk_size`` chunks the
    iteration map — the inputs are resident in this mode anyway, and it
    makes every SolveResult field bit-identical to the unchunked solve
    once lam is (the streaming driver in core/chunked.py is the one that
    must also stream these passes).
    """
    update_fn = _scd_update if cfg.algo == "scd" else _dd_update
    update = functools.partial(update_fn, kp, q=q, cfg=cfg, axis=axis)

    def metrics_fn(lam, _it):
        _, _, r, primal, dual, viol = _metrics(kp, lam, q, axis)
        return {
            "lam": lam,
            "primal": primal,
            "dual": dual,
            "gap": dual - primal,
            "max_violation": viol,
        }

    # Named scopes mark each phase's ops in a profile (op metadata only).
    with jax.named_scope("iterate_multipliers"):
        lam, iters, hist = iterate_multipliers(
            lambda lam: update(lam), lam0, cfg, metrics_fn
        )

    # Final primal + §5.4 feasibility projection.
    with jax.named_scope("final_primal"):
        x, cons, r, primal, dual, _ = _metrics(kp, lam, q, axis)
    if cfg.postprocess:
        with jax.named_scope("postprocess"):
            pt = group_profit(kp.p, cons, lam, x)
            if axis is None:
                tau = feasibility_threshold_exact(pt, cons, kp.budgets)
            else:
                tau = feasibility_threshold_bucketed(pt, cons, r,
                                                     kp.budgets, axis)
            drop = pt <= tau
            x = x & ~drop[:, None]
            cons = cons * (~drop[:, None]).astype(cons.dtype)
            r = _psum(jnp.sum(cons, axis=0), axis)
            primal = _psum(jnp.sum(jnp.where(x, kp.p, 0.0)), axis)
    return SolveResult(lam, x, iters, r, primal, dual, hist)


def _presolve(kp, lam0, q, cfg, axis):
    """§5.3: warm-start lam by solving a sampled shard with scaled budgets."""
    s = cfg.presolve_samples
    if s <= 0:
        return lam0
    n = kp.p.shape[0]
    s = min(s, n)
    # Sampled users per shard / users per shard == global sample fraction.
    frac = s / n
    small = kp._replace(
        p=kp.p[:s],
        b=kp.b[:s],
        budgets=kp.budgets * frac,
    )
    sub_cfg = cfg.replace(
        presolve_samples=0, record_history=False, postprocess=False
    )
    res = _solve_local(small, lam0, q, sub_cfg, axis)
    return res.lam


def _solve_entry(kp, lam0, q, cfg, axis):
    with jax.named_scope("presolve"):
        lam0 = _presolve(kp, lam0, q, cfg, axis)
    return _solve_local(kp, lam0, q, cfg, axis)


def _validate_cfg(cfg):
    if cfg.chunk_size is not None:
        if cfg.chunk_size < 1:
            raise ValueError(f"chunk_size must be >= 1, got {cfg.chunk_size}")
        if cfg.algo == "scd" and cfg.reduce != "bucketed":
            raise ValueError(
                "chunk_size requires reduce='bucketed': the exact reduce "
                "sorts all candidates and cannot stream the item dimension"
            )


# --------------------------------------------------------------------------
# Public API.
# --------------------------------------------------------------------------

def solve(kp, cfg: SolverConfig = SolverConfig(), q: int = 1, lam0=None):
    """Single-device solve (the N-user shard fits on one device).

    kp: ``SparseKP`` (p, b: (n, K)) or ``DenseKP`` (p: (n, M),
    b: (n, M, K)); q: the sparse at-most-Q local cap (static; ignored for
    dense). lam0: (K,) warm start, default all-ones. Returns a
    ``SolveResult`` with x: (n, K)/(n, M) bool.

    Chunked-vs-unchunked contract: ``cfg.chunk_size=c`` streams the
    per-iteration map over ceil(n/c) user chunks. For the SCD bucketed
    reduce the result is bit-identical to ``chunk_size=None`` for every
    field of the SolveResult (any c >= 1, ragged tail included; on the
    kernel path both sides must run the same tile, see
    ``cfg.kernel_tile``). Chunked DD agrees to f32 reduce-order instead.
    The instance itself stays device-resident — for out-of-core n see
    ``repro.core.chunked.solve_streaming``.
    """
    k = kp.budgets.shape[0]
    if lam0 is None:
        lam0 = jnp.ones((k,), cfg.dtype)
    return solve_fn(cfg, q)(kp, lam0)


def solve_fn(cfg: SolverConfig = SolverConfig(), q: int = 1):
    """Build the jitted single-device entry: (kp, lam0) -> SolveResult.

    :func:`solve` calls the returned function; AOT consumers call
    ``.lower()`` on it, so the program they inspect (memory analysis,
    whether the Pallas kernels compiled natively) is exactly the one
    users run. Validates cfg like :func:`solve`.
    """
    _validate_cfg(cfg)
    return jax.jit(functools.partial(_solve_entry, q=q, cfg=cfg, axis=None))


def solve_sharded(kp, mesh, cfg: SolverConfig = SolverConfig(), q: int = 1,
                  lam0=None, axes: Optional[tuple] = None):
    """Multi-device solve: users sharded over every axis of ``mesh``.

    ``kp`` holds *global* arrays (or ShapeDtypeStructs for AOT lowering);
    the user dimension must divide the mesh size. Returns globally
    replicated lam/scalars and a user-sharded x (spec ``P(axes)`` on the
    user axis). Every mesh axis participates by default; pass ``axes`` to
    shard users over a subset.

    The per-iteration reduce moves O(K·E) bytes per device regardless of
    n (§5.2's communication-compression claim). ``cfg.chunk_size``
    applies per shard — each device scans its local n/|mesh| rows in
    chunks — and the bit-identity contract of :func:`solve` holds
    shard-locally, so chunked and unchunked sharded solves also agree
    bit-for-bit on the SCD bucketed path.
    """
    _validate_cfg(cfg)
    axes = tuple(mesh.axis_names) if axes is None else axes
    k = kp.budgets.shape[0]
    if lam0 is None:
        lam0 = jnp.ones((k,), cfg.dtype)
    user_spec = P(axes)
    if isinstance(kp, SparseKP):
        in_kp_specs = SparseKP(p=user_spec, b=user_spec, budgets=P())
        x_spec = P(axes, None)
    else:
        in_kp_specs = DenseKP(
            p=user_spec, b=user_spec, budgets=P(), sets=P(), caps=P()
        )
        x_spec = P(axes, None)
    out_specs = SolveResult(
        lam=P(), x=x_spec, iters=P(), r=P(), primal=P(), dual=P(),
        history=None if not getattr(cfg, "record_history", False) else {
            "lam": P(), "primal": P(), "dual": P(), "gap": P(),
            "max_violation": P(),
        },
    )
    fn = shard_map(
        functools.partial(_solve_entry, q=q, cfg=cfg, axis=axes),
        mesh=mesh,
        in_specs=(in_kp_specs, P()),
        out_specs=out_specs,
        # lam/scalars are replicated by construction (psum / tiled gather);
        # VMA inference cannot see that through the gather, so opt out.
        check_vma=False,
    )
    return jax.jit(fn)(kp, lam0)
