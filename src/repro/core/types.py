"""Problem containers for the generalized knapsack problem (GKP).

Paper: "Solving Billion-Scale Knapsack Problems" (WWW'20), eqs. (1)-(4).

Two instance families are first-class:

* ``DenseKP`` — the general form: N users x M items, K global knapsacks with
  dense cost tensor ``b[i, j, k]`` and laminar (hierarchical) local
  constraints described by boolean index-set masks.
* ``SparseKP`` — the Section 5.1 sparse form: M == K, one item per knapsack
  (``b[i, j, k] = 0`` for j != k, stored as the diagonal ``b[i, k]``) and a
  single cardinality local constraint (choose at most Q items per user).

Both are NamedTuples of arrays, hence JAX pytrees: they can be sharded,
donated and passed through jit/shard_map directly. Static structure
(number of local constraints, Q) travels separately as Python ints.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax.numpy as jnp


class LaminarSets(NamedTuple):
    """Hierarchical local constraints (Definition 2.1).

    ``sets`` is an (L, M) boolean mask matrix; row l is the index set S_l.
    Rows MUST be in topological (leaf -> root) order: if S_a is a strict
    subset of S_b then a < b. ``caps`` is the (L,) int32 vector of C_l.
    """

    sets: jnp.ndarray  # (L, M) bool
    caps: jnp.ndarray  # (L,) int32


class DenseKP(NamedTuple):
    """General GKP shard: ``p`` (n, M) profits, ``b`` (n, M, K) costs,
    ``budgets`` (K,), plus laminar local constraints."""

    p: jnp.ndarray        # (n, M) f32
    b: jnp.ndarray        # (n, M, K) f32, non-negative
    budgets: jnp.ndarray  # (K,) f32, strictly positive
    sets: jnp.ndarray     # (L, M) bool
    caps: jnp.ndarray     # (L,) int32


class SparseKP(NamedTuple):
    """Section 5.1 sparse GKP shard: item j consumes only knapsack j.

    ``p`` (n, K) profits, ``b`` (n, K) diagonal costs b[i, k, k],
    ``budgets`` (K,). The single local constraint (at most Q items per
    user) is static and passed alongside.
    """

    p: jnp.ndarray        # (n, K) f32
    b: jnp.ndarray        # (n, K) f32, non-negative
    budgets: jnp.ndarray  # (K,) f32, strictly positive


@dataclasses.dataclass(frozen=True)
class SolverConfig:
    """Static solver configuration (hashable; safe as a jit static arg).

    algo: "scd" (Alg 4) or "dd" (Alg 2).
    reduce: "bucketed" (Section 5.2 production path) or "exact"
        (bit-faithful Alg 4 reduce; gathers candidates, test scale only).
    chunk_size: None runs the per-iteration map over the whole local shard
        at once; an int streams the user axis through the map in fixed-size
        chunks via ``lax.scan`` (see core/solver.py "Chunked map" and the
        chunked-vs-unchunked contract in the ``solve`` docstring). Requires
        ``reduce="bucketed"`` (the exact reduce must see all candidates).
    """

    algo: str = "scd"
    # §4.3.2: synchronous CD updates every lam_k at once (production mode);
    # cyclic CD sweeps coordinates one at a time (K reduces per iteration,
    # converges monotonically on small/strongly-coupled instances).
    cd_mode: str = "sync"
    reduce: str = "bucketed"
    max_iters: int = 32
    tol: float = 1e-3
    # Per-coordinate damping applied to SCD when a multiplier's step
    # reverses direction (delta_t * delta_{t-1} < 0): the step is scaled
    # by this factor. Breaks the sync-CD period-2 limit cycle near the
    # fixed point (bucket-interpolation wobble + Jacobi coupling) by
    # geometrically shrinking oscillations below tol; monotone
    # trajectories are untouched (no reversal, no damping), and DD is
    # exempt (Alg 2's projected step must reach the lam = 0 boundary
    # exactly). 1.0 disables.
    cd_damping: float = 0.5
    # Stream the per-iteration map over user chunks of this size (None =
    # whole shard at once). See core/solver.py.
    chunk_size: Optional[int] = None
    # Override the kernel user-axis tile (None = kernels.ops.pick_tile).
    # Chunked and unchunked kernel paths are bit-identical only when both
    # run the same tile decomposition; tests pin this to compare them.
    kernel_tile: Optional[int] = None
    # DD (Alg 2) learning rate.
    dd_lr: float = 1e-3
    # Section 5.2 bucketing: edges at lam_t +/- delta * growth**i,
    # i in [0, half). n_buckets = 2 * half + 2.
    bucket_half: int = 24
    bucket_delta: float = 1e-4
    bucket_growth: float = 1.6
    # Section 5.3 pre-solving.
    presolve_samples: int = 0  # 0 disables
    # Fraction of map shards the reduce is allowed to proceed with
    # (straggler mitigation; 1.0 = wait for all).
    partial_fraction: float = 1.0
    # Record per-iteration (lam, primal, dual, gap, violation) traces.
    record_history: bool = False
    # Streaming solves only: with record_history, compute the streamed
    # metrics every this-many iterations (each sample is one extra pass
    # over the chunk source; unsampled rows record NaN scalars). 0
    # disables sampling, which makes record_history=True an error when
    # streaming — see core/chunked.stream_solve_fn.
    metrics_every: int = 0
    # Host-fed streaming solves only (core/prefetch.py): write a
    # constant-size StreamCheckpointState through checkpoint/ckpt.py
    # every this-many iterations (and, during the fused finalize pass,
    # every this-many chunk columns), so a preempted solve resumes
    # bitwise from `solve_streaming_host(resume_from=...)`. 0 disables.
    # Requires a checkpoint_dir at the call site; see DESIGN.md §7.
    checkpoint_every: int = 0
    # Streaming checkpoint retention: how many resume states ckpt.prune
    # keeps in the checkpoint directory (must be >= 1 — pruning every
    # step would leave nothing to resume from). Excluded from the
    # resume-state fingerprint like checkpoint_every: changing the
    # retention across a restart is legitimate.
    checkpoint_keep: int = 3
    # Host-fed streaming fault tolerance (core/faults.py): with
    # fetch_retries > 0 every source.fn chunk read — epochs, sharded
    # sub-sources, the presolve head, the fingerprint's chunk-0 probe —
    # runs through a retrying fetcher with capped exponential backoff
    # and deterministic (chunk, attempt)-keyed jitter. Retries re-run
    # only the pure fetch, never the accumulate, so a solve that
    # survives transient faults is bitwise the fault-free solve.
    # 0 disables the wrapper entirely (fail-fast, the historical path).
    # All fetch_* knobs and verify_refetch are excluded from the resume
    # fingerprint: changing the fault policy across a restart is
    # legitimate, like checkpoint_every.
    fetch_retries: int = 0
    fetch_backoff: float = 0.05
    fetch_backoff_growth: float = 2.0
    fetch_backoff_cap: float = 2.0
    fetch_jitter: float = 0.25
    # Per-fetch wall-clock bound in seconds, enforced by a worker
    # thread; overruns are retryable timeouts. 0 disables.
    fetch_timeout: float = 0.0
    # Paranoid fetch-is-pure check: read every chunk twice and require
    # byte-equality, turning silent payload corruption into a detected,
    # retryable fault. Doubles source reads; off by default.
    verify_refetch: bool = False
    # Streaming finalize strategy (core/chunked.py): "fused" folds the
    # final metrics, the §5.4 removable histograms and the projection
    # into ONE pass over the chunk source (iters + 1 total); "legacy"
    # keeps the PR-2 three-pass finalize (metrics, histogram, apply;
    # iters + 3) as the oracle/benchmark baseline. See DESIGN.md §5c.
    stream_finalize: str = "fused"
    # §5.4 group-profit ladder: bucket count (both finalize paths) and
    # the fixed geometric range of the fused single-pass ladder.
    profit_buckets: int = 512
    profit_ladder_lo: float = 1e-6
    profit_ladder_hi: float = 1e6
    # Safe λ-interval active-set screening (core/screening.py): retire
    # chunks whose items provably bin below the bucket ladder for every
    # remaining multiplier value, and skip them in subsequent iteration
    # passes. The screened solve is bitwise-identical to the unscreened
    # oracle (DESIGN.md §11); requires the sync-SCD bucketed streaming
    # path. Excluded from the resume fingerprint like checkpoint_every:
    # screening never steers the trajectory, so toggling it across a
    # restart is legitimate.
    screening: bool = False
    # Floor protocol: each iteration certifies multipliers down to
    # lam * screening_floor; a multiplier escaping below its floor
    # reactivates every chunk for one full pass and re-anchors. Smaller
    # values retire chunks earlier but survive larger downward swings.
    screening_floor: float = 0.5
    # Use the Pallas kernels for the sparse map + histogram: compiled on a
    # TPU, interpreted on the CPU (slow; integration tests only), refused
    # on any other backend (kernels/_util.resolve_interpret).
    use_kernels: bool = False
    # Apply the §5.4 feasibility projection to the returned primal.
    postprocess: bool = True
    dtype: jnp.dtype = jnp.float32

    def replace(self, **kw) -> "SolverConfig":
        """Functional update: a copy with the given fields replaced."""
        return dataclasses.replace(self, **kw)


def disjoint_partition_sets(group_sizes, caps, m=None):
    """Build a LaminarSets for disjoint groups of consecutive items."""
    total = int(sum(group_sizes))
    m = total if m is None else m
    rows, start = [], 0
    for g in group_sizes:
        row = jnp.zeros((m,), bool).at[start:start + g].set(True)
        rows.append(row)
        start += g
    return LaminarSets(jnp.stack(rows), jnp.asarray(caps, jnp.int32))


def cardinality_set(m, cap):
    """Single local constraint: choose at most ``cap`` of the m items."""
    return LaminarSets(jnp.ones((1, m), bool), jnp.asarray([cap], jnp.int32))


def hierarchy_from_lists(index_lists, caps, m):
    """LaminarSets from explicit index lists (validated laminar, topo-sorted).

    Raises ValueError if the family is not laminar (Definition 2.1).
    """
    sets = [frozenset(s) for s in index_lists]
    for a in sets:
        for b in sets:
            inter = a & b
            if inter and not (a <= b or b <= a):
                raise ValueError("local constraint family is not laminar")
    order = sorted(range(len(sets)), key=lambda i: len(sets[i]))
    rows = []
    out_caps = []
    for i in order:
        row = jnp.zeros((m,), bool).at[jnp.asarray(sorted(sets[i]), jnp.int32)].set(True)
        rows.append(row)
        out_caps.append(caps[i])
    return LaminarSets(jnp.stack(rows), jnp.asarray(out_caps, jnp.int32))
