"""Fused SCD map+reduce kernel (scd_fused_hist) vs the unfused paths.

The fused kernel puts users on lanes ((K, tile) blocks); the unfused
kernels it is checked against keep (tile_n, K) blocks. It must be
bit-compatible (up to float accumulation order) with the composition it
replaces — ``bucket_histogram(candidates_sparse)`` on the jnp side and
``bucket_hist(scd_candidates(...))`` on the kernel side — including tie
cases exactly on bucket edges, all-invalid tiles, K that fills sublanes
partly or spills past 16, and a ragged last block masked inside the
kernel. Chunked calls carrying the seeded accumulators must equal one
call bit for bit at a pinned lane tile. The solve driver's while_loop
fast path must reproduce the scan path's trajectory exactly.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SolverConfig, solve
from repro.core.bucketing import bucket_histogram, make_edges
from repro.core.instances import shard_key, sparse_instance
from repro.core.sparse_scd import candidates_sparse
from repro.kernels import ops, ref
from repro.kernels.scd_fused import LANE_TILE

jax.config.update("jax_platform_name", "cpu")

# K = 3, 10 and 17 fill the sublanes of a (K, tile) block partly, to
# 10 of 16, and past 16.
SHAPES = [(128, 8), (512, 16), (384, 10), (383, 8), (1021, 8), (7, 4),
          (300, 3), (257, 17)]


def _inst(n, k, dtype=jnp.float32, seed=0):
    kp, kb, kl = jax.random.split(jax.random.PRNGKey(seed), 3)
    p = jax.random.uniform(kp, (n, k), jnp.float32)
    b = jax.random.uniform(kb, (n, k), jnp.float32, 0.05, 1.0)
    lam = jax.random.uniform(kl, (k,), jnp.float32, 0.0, 1.5)
    return p.astype(dtype), b.astype(dtype), lam.astype(dtype)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("q", [1, 2, 4])
def test_fused_matches_unfused_jnp_composition(shape, q):
    """Parity vs bucket_histogram(candidates_sparse(...)), incl. ragged n."""
    n, k = shape
    p, b, lam = _inst(n, k, seed=n + q)
    edges = make_edges(lam, 1e-4, 1.6, 24)
    h_f, top_f = ops.scd_fused_hist(p, b, lam, edges, q, tile_n=128,
                                    interpret=True)
    v1, v2 = candidates_sparse(p, b, lam, q)
    h_u = bucket_histogram(v1, v2, edges)
    np.testing.assert_allclose(np.asarray(h_f), np.asarray(h_u),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(top_f),
                               np.asarray(jnp.max(v1, axis=0)), rtol=1e-6)
    # mass conservation: every unit of v2 lands in exactly one bucket
    np.testing.assert_allclose(float(h_f.sum()), float(v2.sum()), rtol=1e-5)


@pytest.mark.parametrize("tile", [128, 256, None])
@pytest.mark.parametrize("shape", [(256, 8), (383, 16), (1000, 17)])
def test_fused_matches_unfused_kernel_composition(shape, tile):
    """Parity vs the two-kernel path it replaces in the solver, at lane
    tiles of 128, 256 and the default."""
    n, k = shape
    q = 2
    p, b, lam = _inst(n, k, seed=5)
    edges = make_edges(lam, 1e-4, 1.6, 24)
    h_f, top_f = ops.scd_fused_hist(p, b, lam, edges, q, tile_n=tile,
                                    interpret=True)
    v1, v2 = ops.scd_candidates(p, b, lam, q, tile_n=128, interpret=True)
    h_u = ops.bucket_hist(v1, v2, edges, tile_n=128, interpret=True)
    np.testing.assert_allclose(np.asarray(h_f), np.asarray(h_u),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(top_f),
                               np.asarray(jnp.max(v1, axis=0)), rtol=1e-6)


@pytest.mark.parametrize("k,reps,tile", [(4, 1, 4), (10, 50, 128)])
def test_fused_ties_exactly_on_bucket_edges(k, reps, tile):
    """Candidates landing exactly on an edge bin identically in all paths.

    q >= K makes pbar = 0 so v1 = p/b = p (b = 1): rows are placed
    exactly on the edge ladder. searchsorted-left convention: a candidate
    at edges[j] belongs to bucket j, not j+1. The second case spreads
    the rows over three (K, 128) lane blocks, the last one ragged.
    """
    edges = jnp.tile(jnp.array([[0.5, 1.0, 1.5]]), (k, 1))
    vals = jnp.tile(jnp.array([0.5, 1.0, 1.5, 0.25, 1.75, 1.0]), reps)
    p = jnp.tile(vals[:, None], (1, k))
    b = jnp.ones_like(p)
    lam = jnp.zeros((k,))
    q = k  # local constraint never binds -> v1 = p
    h_f, top_f = ops.scd_fused_hist(p, b, lam, edges, q, tile_n=tile,
                                    interpret=True)
    v1, v2 = candidates_sparse(p, b, lam, q)
    h_u = bucket_histogram(v1, v2, edges)
    h_r, top_r = ref.scd_fused_hist_ref(p, b, lam, edges, q)
    np.testing.assert_array_equal(np.asarray(h_f), np.asarray(h_u))
    np.testing.assert_array_equal(np.asarray(h_f), np.asarray(h_r))
    # explicit tie placement: bucket j = (edges[j-1], edges[j]]
    np.testing.assert_array_equal(np.asarray(h_f[0]),
                                  reps * np.array([2.0, 2.0, 1.0, 1.0]))
    np.testing.assert_allclose(np.asarray(top_f), np.full(k, 1.75), rtol=0)


def test_fused_all_invalid_tiles():
    """p = 0 emits no candidates anywhere: zero mass, top = -1 sentinel."""
    n, k, q = 256, 8, 2
    p = jnp.zeros((n, k))
    b = jnp.ones((n, k))
    lam = jnp.full((k,), 0.7)
    edges = make_edges(lam, 1e-4, 1.6, 24)
    h_f, top_f = ops.scd_fused_hist(p, b, lam, edges, q, tile_n=64,
                                    interpret=True)
    assert float(jnp.abs(h_f).sum()) == 0.0
    np.testing.assert_array_equal(np.asarray(top_f), np.full(k, -1.0))


def test_fused_ragged_padding_is_invisible():
    """A ragged tail must change nothing: fused(n) == fused on exact tiles
    of the same rows, and padded rows contribute no mass."""
    n, k, q = 301, 8, 2  # 301 = 7 * 43: no ladder tile divides it
    p, b, lam = _inst(n, k, seed=9)
    edges = make_edges(lam, 1e-4, 1.6, 24)
    h_rag, top_rag = ops.scd_fused_hist(p, b, lam, edges, q, tile_n=128,
                                        interpret=True)
    h_one, top_one = ops.scd_fused_hist(p, b, lam, edges, q, tile_n=301,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(h_rag), np.asarray(h_one),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(top_rag), np.asarray(top_one),
                               rtol=1e-6)
    v1, v2 = candidates_sparse(p, b, lam, q)
    np.testing.assert_allclose(float(h_rag.sum()), float(v2.sum()), rtol=1e-5)


@pytest.mark.parametrize("tile", [128, None])
def test_fused_ragged_mask_equals_exact_tiles(tile, monkeypatch):
    """n = 10037, which no lane tile divides: the kernel's in-kernel mask
    of the last block gives bit for bit what the same rows padded with
    inert (p = 0, b = 0) users to a tile multiple give, and every unit
    of candidate mass lands in a bucket.

    On the TPU the lanes of the last block past n hold whatever its VMEM
    buffer held before. The interpreter fills them with NaN, which would
    be inert by itself, so the test plants valid-looking users there.
    """
    from jax._src.pallas import primitives as pallas_primitives
    nan_fill = pallas_primitives.uninitialized_value

    def stale_fill(shape, dtype):
        if jnp.issubdtype(dtype, jnp.floating):
            return jnp.full(shape, 0.75, dtype)
        return nan_fill(shape, dtype)

    monkeypatch.setattr(pallas_primitives, "uninitialized_value", stale_fill)
    n, k, q = 10037, 10, 1
    p, b, lam = _inst(n, k, seed=11)
    edges = make_edges(lam, 1e-4, 1.6, 24)
    t = tile or min(LANE_TILE, n)
    pad = -n % t
    h_rag, top_rag = ops.scd_fused_hist(p, b, lam, edges, q, tile_n=tile,
                                        interpret=True)
    h_pad, top_pad = ops.scd_fused_hist(
        jnp.pad(p, ((0, pad), (0, 0))), jnp.pad(b, ((0, pad), (0, 0))),
        lam, edges, q, tile_n=t, interpret=True)
    np.testing.assert_array_equal(np.asarray(h_rag), np.asarray(h_pad))
    np.testing.assert_array_equal(np.asarray(top_rag), np.asarray(top_pad))
    v1, v2 = candidates_sparse(p, b, lam, q)
    np.testing.assert_allclose(float(h_rag.sum()), float(v2.sum()), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(top_rag),
                               np.asarray(jnp.max(v1, axis=0)), rtol=0)


@pytest.mark.parametrize("tile", [128, 256])
def test_fused_chunked_bitwise_at_pinned_lane_tile(tile):
    """Chunks of 2 * tile users carried through the seeded accumulators
    give bit for bit the one call over all users at the same lane tile,
    with a ragged final chunk padded by inert users as the chunked
    solvers pad it."""
    n, k, q = 1021, 10, 2
    p, b, lam = _inst(n, k, seed=13)
    edges = make_edges(lam, 1e-4, 1.6, 24)
    h_one, top_one = ops.scd_fused_hist(p, b, lam, edges, q, tile_n=tile,
                                        interpret=True)
    chunk = 2 * tile
    pad = -n % chunk
    pc = jnp.pad(p, ((0, pad), (0, 0))).reshape(-1, chunk, k)
    bc = jnp.pad(b, ((0, pad), (0, 0))).reshape(-1, chunk, k)
    hist = jnp.zeros_like(h_one)
    top = jnp.full((k,), -jnp.inf)
    for i in range(pc.shape[0]):
        hist, top = ops.scd_fused_hist(pc[i], bc[i], lam, edges, q,
                                       tile_n=tile, interpret=True,
                                       hist_init=hist, top_init=top)
    np.testing.assert_array_equal(np.asarray(hist), np.asarray(h_one))
    np.testing.assert_array_equal(np.asarray(top), np.asarray(top_one))


@pytest.mark.parametrize("use_kernels", [False, True])
def test_while_loop_driver_matches_scan(use_kernels):
    """record_history toggles scan <-> while_loop; lam and iters must be
    identical (the early exit only skips frozen iterations)."""
    kp, q = sparse_instance(shard_key(17), n=512, k=8, q=2, tightness=0.4)
    cfg = SolverConfig(reduce="bucketed", max_iters=30,
                       use_kernels=use_kernels)
    scan = solve(kp, cfg.replace(record_history=True), q=q)
    wl = solve(kp, cfg.replace(record_history=False), q=q)
    assert int(scan.iters) < cfg.max_iters, "instance must converge early"
    assert int(scan.iters) == int(wl.iters)
    np.testing.assert_array_equal(np.asarray(scan.lam), np.asarray(wl.lam))
    np.testing.assert_allclose(float(scan.primal), float(wl.primal), rtol=0)


def test_solver_fused_path_matches_jnp_path_ragged():
    """End-to-end kernel path on a prime-ish n (exercises pad+mask)."""
    kp, q = sparse_instance(shard_key(7), n=509, k=8, q=1, tightness=0.4)
    cfg = SolverConfig(reduce="bucketed", max_iters=8)
    a = solve(kp, cfg, q=q)
    b = solve(kp, cfg.replace(use_kernels=True), q=q)
    np.testing.assert_allclose(np.asarray(a.lam), np.asarray(b.lam),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(a.primal), float(b.primal), rtol=1e-5)


try:  # jax.core.Jaxpr moved to jax.extend.core in newer jax
    from jax.extend.core import ClosedJaxpr as _ClosedJaxpr, Jaxpr as _Jaxpr
except (ImportError, AttributeError):
    _Jaxpr, _ClosedJaxpr = jax.core.Jaxpr, jax.core.ClosedJaxpr


def _sub_jaxprs(params):
    for v in params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for x in vs:
            if isinstance(x, _Jaxpr):
                yield x
            elif isinstance(x, _ClosedJaxpr):
                yield x.jaxpr


def _walk_eqns(jaxpr):
    """All eqns, recursing into subjaxprs EXCEPT pallas_call kernel bodies
    (whose intermediates live in VMEM, which is exactly the point)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in _sub_jaxprs(eqn.params):
            yield from _walk_eqns(sub)


def test_fused_reduce_is_single_pallas_call_no_candidate_intermediates():
    """The jaxpr of the fused solver reduce contains exactly one
    pallas_call and no (n, K) intermediate — v1/v2 never reach HBM."""
    from repro.core import solver as S
    from repro.core.types import SparseKP

    n, k, q = 512, 8, 2
    p, b, lam = _inst(n, k, seed=3)
    kp = SparseKP(p=p, b=b, budgets=jnp.full((k,), 10.0))
    cfg = SolverConfig(reduce="bucketed", use_kernels=True)

    def fused_reduce(kp, lam):
        return S._scd_step_fused(kp, lam, q, 1.0, 1.0, cfg, None)

    jaxpr = jax.make_jaxpr(fused_reduce)(kp, lam).jaxpr
    eqns = list(_walk_eqns(jaxpr))
    n_pallas = sum(e.primitive.name == "pallas_call" for e in eqns)
    assert n_pallas == 1, f"expected 1 pallas_call, got {n_pallas}"
    big = [
        v.aval.shape
        for e in eqns
        if e.primitive.name != "pallas_call"
        for v in e.outvars
        if getattr(v.aval, "shape", ()) and v.aval.shape[:1] == (n,)
    ]
    assert not big, f"(n, K) intermediates escaped the kernel: {big}"
