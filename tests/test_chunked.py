"""Chunked / out-of-core solve == unchunked oracle, bit for bit.

The contract under test (core/solver.py module docstring): with the SCD
bucketed reduce, chunking the per-iteration map — any chunk size,
including 1, ragged final chunks and chunk >= n — produces a SolveResult
bit-identical to the unchunked solve, because the histogram accumulation
is carry-seeded (same f32 additions in the same order). The kernel path
additionally requires the same tile decomposition on both sides
(cfg.kernel_tile pins it). The streaming driver (core/chunked.py) must
match the same oracle on lam/iters and reconstruct the identical primal
via decisions_chunk. DD chunked is reduce-order-level, not bitwise.

Pass accounting (DESIGN.md §5c): a converged streaming solve touches the
source exactly ``iters + 1`` times with the fused finalize and
``iters + 3`` with the legacy one — counted at runtime by a traced
source-call counter (io_callback) — and the host-fed driver
(core/prefetch.py) must be bit-identical to the traced one, double
buffered or not.
"""
import hashlib
import math
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import io_callback

from repro.core import SolverConfig, solve
from repro.core.bucketing import bucket_histogram, make_edges
from repro.core.chunked import array_source, decisions_chunk, solve_streaming
from repro.core.instances import shard_key, sparse_instance, dense_instance
from repro.core.postprocess import profit_edges_fixed
from repro.core.prefetch import (
    host_array_source,
    memmap_source,
    solve_streaming_host,
)
from repro.core.sparse_scd import candidates_sparse
from repro.data.synth import sparse_chunk_source

jax.config.update("jax_platform_name", "cpu")

REPO = pathlib.Path(__file__).resolve().parent.parent


class CountingSource:
    """Wrap a ChunkSource with a *runtime* source-call counter.

    ``fn`` is traced once, but an (unordered) io_callback fires on every
    execution — including inside lax.scan and lax.while_loop — so
    ``calls`` counts actual chunk fetches, and ``passes`` converts that
    to full sweeps over the source. ``jax.effects_barrier()`` flushes
    in-flight callbacks before reading.
    """

    def __init__(self, src):
        self.calls = 0
        inner = src.fn

        def _bump(_):
            self.calls += 1
            return np.int32(0)

        def fn(i):
            io_callback(_bump, jax.ShapeDtypeStruct((), np.int32), i,
                        ordered=False)
            return inner(i)

        self.source = src._replace(fn=fn)

    def passes(self, n_chunks):
        jax.effects_barrier()
        assert self.calls % n_chunks == 0, (self.calls, n_chunks)
        return self.calls // n_chunks


def _assert_same_result(a, b):
    np.testing.assert_array_equal(np.asarray(a.lam), np.asarray(b.lam))
    assert int(a.iters) == int(b.iters)
    np.testing.assert_array_equal(np.asarray(a.x), np.asarray(b.x))
    np.testing.assert_array_equal(np.asarray(a.r), np.asarray(b.r))
    assert float(a.primal) == float(b.primal)
    assert float(a.dual) == float(b.dual)


# ---------------------------------------------------------------------------
# bucket_histogram: the carry-seeded scatter is the bitwise foundation.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [1, 7, 64, 500, 1021, 4096])
def test_seeded_histogram_chunking_invariant(chunk):
    """Chunked scatter-add onto the carry == one scatter over all rows."""
    kp, q = sparse_instance(shard_key(3), n=1021, k=8, q=2, tightness=0.4)
    lam = jnp.full((8,), 0.7)
    edges = make_edges(lam, 1e-4, 1.6, 24)
    v1, v2 = candidates_sparse(kp.p, kp.b, lam, q)
    whole = bucket_histogram(v1, v2, edges)
    acc = jnp.zeros_like(whole)
    for i in range(0, 1021, chunk):
        acc = bucket_histogram(v1[i:i + chunk], v2[i:i + chunk], edges,
                               init=acc)
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(whole))


# ---------------------------------------------------------------------------
# cfg.chunk_size: in-memory chunked solve vs the unchunked oracle.
# ---------------------------------------------------------------------------

# 1021 is prime: every chunk size below exercises a ragged final chunk.
@pytest.mark.parametrize("chunk", [1, 7, 256, 1021, 4096])
def test_chunked_solve_bit_identical_sparse(chunk):
    """chunk = 1, ragged tails, chunk == n and chunk >= n, all bitwise."""
    kp, q = sparse_instance(shard_key(4), n=1021, k=10, q=2, tightness=0.4)
    cfg = SolverConfig(reduce="bucketed", max_iters=20)
    _assert_same_result(solve(kp, cfg.replace(chunk_size=chunk), q=q),
                        solve(kp, cfg, q=q))


def test_chunked_solve_bit_identical_kernels():
    """Kernel path: same tile on both sides -> bitwise, incl. ragged."""
    kp, q = sparse_instance(shard_key(7), n=509, k=8, q=1, tightness=0.4)
    cfg = SolverConfig(reduce="bucketed", max_iters=10, use_kernels=True,
                       kernel_tile=128)
    for chunk in [128, 256, 1024]:   # multiples of the pinned tile
        _assert_same_result(solve(kp, cfg.replace(chunk_size=chunk), q=q),
                            solve(kp, cfg, q=q))


def test_chunked_solve_bit_identical_kernels_chunk1():
    """chunk = 1 on the kernel path: tile 1 on both sides."""
    kp, q = sparse_instance(shard_key(5), n=48, k=6, q=1, tightness=0.4)
    cfg = SolverConfig(reduce="bucketed", max_iters=6, use_kernels=True,
                       kernel_tile=1)
    _assert_same_result(solve(kp, cfg.replace(chunk_size=1), q=q),
                        solve(kp, cfg, q=q))


def test_chunked_solve_bit_identical_dense():
    """Dense (Alg 3 map) chunking is bitwise too."""
    kp = dense_instance(shard_key(6), n=130, m=6, k=4, local="C223",
                        tightness=0.25)
    cfg = SolverConfig(reduce="bucketed", max_iters=10)
    _assert_same_result(solve(kp, cfg.replace(chunk_size=32), q=0),
                        solve(kp, cfg, q=0))


def test_chunked_dd_matches_to_reduce_order():
    """DD's consumption sum groups by chunk: allclose, documented non-bitwise."""
    kp, q = sparse_instance(shard_key(4), n=1021, k=10, q=2, tightness=0.4)
    cfg = SolverConfig(algo="dd", max_iters=10, dd_lr=2e-3)
    a = solve(kp, cfg, q=q)
    b = solve(kp, cfg.replace(chunk_size=100), q=q)
    np.testing.assert_allclose(np.asarray(a.lam), np.asarray(b.lam),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(a.primal), float(b.primal), rtol=1e-5)


def test_chunked_exact_reduce_rejected():
    """The exact reduce must see every candidate: chunking raises."""
    kp, q = sparse_instance(shard_key(4), n=64, k=4, q=1, tightness=0.4)
    with pytest.raises(ValueError, match="bucketed"):
        solve(kp, SolverConfig(reduce="exact", chunk_size=16), q=q)


# ---------------------------------------------------------------------------
# Streaming driver: nothing O(n) on device.
# ---------------------------------------------------------------------------

def test_streaming_matches_resident_bitwise():
    """array_source streaming == resident solve on lam/iters, any chunking."""
    kp, q = sparse_instance(shard_key(4), n=1021, k=10, q=2, tightness=0.4)
    cfg = SolverConfig(reduce="bucketed", max_iters=20)
    base = solve(kp, cfg, q=q)
    for chunk in [100, 256, 2048]:   # ragged tail / mid / single chunk
        sr = solve_streaming(array_source(kp, chunk), cfg, q=q)
        np.testing.assert_array_equal(np.asarray(sr.lam), np.asarray(base.lam))
        assert int(sr.iters) == int(base.iters)
        np.testing.assert_allclose(float(sr.dual), float(base.dual),
                                   rtol=1e-6)
        # §5.4 differs by design: bucketed (conservative) vs exact sort.
        assert np.all(np.asarray(sr.r) <= np.asarray(kp.budgets) * (1 + 1e-4))
        np.testing.assert_allclose(float(sr.primal), float(base.primal),
                                   rtol=2e-2)


def test_streaming_kernels_matches_resident_chunked():
    """Fused-kernel streaming == resident chunked kernels, pinned tile."""
    kp, q = sparse_instance(shard_key(4), n=1021, k=10, q=2, tightness=0.4)
    cfg = SolverConfig(reduce="bucketed", max_iters=10, use_kernels=True,
                       kernel_tile=128)
    res = solve(kp, cfg.replace(chunk_size=256), q=q)
    sr = solve_streaming(array_source(kp, 256), cfg, q=q)
    np.testing.assert_array_equal(np.asarray(sr.lam), np.asarray(res.lam))
    assert int(sr.iters) == int(res.iters)


def test_streaming_decisions_reconstruct_primal():
    """decisions_chunk streams out exactly the solution the solve scored."""
    kp, q = sparse_instance(shard_key(4), n=1021, k=10, q=2, tightness=0.4)
    cfg = SolverConfig(reduce="bucketed", max_iters=20)
    src = array_source(kp, 256)
    sr = solve_streaming(src, cfg, q=q)
    primal, r = 0.0, jnp.zeros((10,))
    for i in range(math.ceil(1021 / 256)):
        x, valid = decisions_chunk(src, sr.lam, q, i, tau=sr.tau)
        p_c, b_c = src.fn(jnp.int32(i))
        primal += float(jnp.sum(jnp.where(x, p_c, 0.0)))
        r = r + jnp.sum(b_c * x.astype(b_c.dtype), axis=0)
    np.testing.assert_allclose(primal, float(sr.primal), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(r), np.asarray(sr.r), rtol=1e-5)


def test_streaming_synth_source_never_materialises():
    """Generated source solves at quality on n far beyond the chunk size."""
    src = sparse_chunk_source(0, n=100_000, k=8, chunk=4096, q=1,
                              tightness=0.4)
    cfg = SolverConfig(reduce="bucketed", max_iters=15)
    res = solve_streaming(src, cfg, q=1)
    assert int(res.iters) < 15
    assert np.all(np.asarray(res.r) <= np.asarray(src.budgets) * (1 + 1e-4))
    gap = float((res.dual - res.primal) / res.primal)
    assert 0 <= gap < 0.01


def test_streaming_rejects_exact_and_history():
    kp, q = sparse_instance(shard_key(4), n=64, k=4, q=1, tightness=0.4)
    src = array_source(kp, 16)
    with pytest.raises(ValueError, match="bucketed"):
        solve_streaming(src, SolverConfig(reduce="exact"), q=q)
    with pytest.raises(ValueError, match="record_history"):
        solve_streaming(src, SolverConfig(record_history=True), q=q)


# ---------------------------------------------------------------------------
# Pass accounting: iters + 1 fused vs iters + 3 legacy (DESIGN.md §5c).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("finalize,extra", [("fused", 1), ("legacy", 3)])
def test_streaming_pass_counts(finalize, extra):
    """A converged solve touches the source iters + 1 (fused) times."""
    kp, q = sparse_instance(shard_key(4), n=1021, k=8, q=2, tightness=0.4)
    cfg = SolverConfig(reduce="bucketed", max_iters=20,
                       stream_finalize=finalize)
    cs = CountingSource(array_source(kp, 256))
    res = solve_streaming(cs.source, cfg, q=q)
    iters = int(res.iters)
    assert 0 < iters < 20          # converged: the while_loop exited early
    assert cs.passes(math.ceil(1021 / 256)) == iters + extra


@pytest.mark.parametrize("finalize,extra", [("fused", 1), ("legacy", 3)])
def test_host_streaming_pass_counts(finalize, extra):
    """The host-fed epoch driver performs the same pass counts."""
    kp, q = sparse_instance(shard_key(4), n=1021, k=8, q=2, tightness=0.4)
    cfg = SolverConfig(reduce="bucketed", max_iters=20,
                       stream_finalize=finalize)
    src = host_array_source(np.asarray(kp.p), np.asarray(kp.b),
                            np.asarray(kp.budgets), 256)
    calls = {"n": 0}
    inner = src.fn

    def fn(i):
        calls["n"] += 1
        return inner(i)

    res = solve_streaming_host(src._replace(fn=fn), cfg, q=q)
    iters = int(res.iters)
    assert 0 < iters < 20
    assert calls["n"] == (iters + extra) * math.ceil(1021 / 256)


# ---------------------------------------------------------------------------
# Fused finalize: parity with the legacy three-pass path and the kernel.
# ---------------------------------------------------------------------------

def test_fused_finalize_metrics_bitwise_vs_legacy():
    """Without §5.4 both finalizes are one metrics reduction: bitwise."""
    kp, q = sparse_instance(shard_key(4), n=1021, k=10, q=2, tightness=0.4)
    cfg = SolverConfig(reduce="bucketed", max_iters=20, postprocess=False)
    fused = solve_streaming(array_source(kp, 256), cfg, q=q)
    legacy = solve_streaming(array_source(kp, 256),
                             cfg.replace(stream_finalize="legacy"), q=q)
    for f, l in zip(fused[:6], legacy[:6]):
        np.testing.assert_array_equal(np.asarray(f), np.asarray(l))


def test_fused_finalize_postprocess_close_to_legacy():
    """With §5.4 the ladders differ (fixed geometric vs data-dependent):
    lam/iters/dual stay bitwise, the projected primal/r agree closely,
    and both projections are feasible."""
    kp, q = sparse_instance(shard_key(4), n=1021, k=10, q=2, tightness=0.4)
    cfg = SolverConfig(reduce="bucketed", max_iters=20)
    fused = solve_streaming(array_source(kp, 256), cfg, q=q)
    legacy = solve_streaming(array_source(kp, 256),
                             cfg.replace(stream_finalize="legacy"), q=q)
    np.testing.assert_array_equal(np.asarray(fused.lam),
                                  np.asarray(legacy.lam))
    assert int(fused.iters) == int(legacy.iters)
    assert float(fused.dual) == float(legacy.dual)
    for res in (fused, legacy):
        assert np.all(np.asarray(res.r) <= np.asarray(kp.budgets) * (1 + 1e-4))
    np.testing.assert_allclose(float(fused.primal), float(legacy.primal),
                               rtol=1e-2)


@pytest.mark.parametrize("chunk", [100, 256, 2048])
def test_fused_finalize_bitwise_across_chunkings(chunk):
    """The fused tau / projected (r, primal) are histogram-prefix derived
    — carry-seeded scatters — so they are bitwise invariant to the
    chunking, unlike the legacy apply-pass re-sums."""
    kp, q = sparse_instance(shard_key(4), n=1021, k=10, q=2, tightness=0.4)
    cfg = SolverConfig(reduce="bucketed", max_iters=20)
    base = solve_streaming(array_source(kp, 256), cfg, q=q)
    other = solve_streaming(array_source(kp, chunk), cfg, q=q)
    np.testing.assert_array_equal(np.asarray(base.lam), np.asarray(other.lam))
    assert float(base.tau) == float(other.tau)


def test_finalize_kernel_matches_ref_ragged():
    """scd_finalize_hist == its jnp oracle on a prime-n (ragged) shard."""
    from repro.kernels import ops as kops
    from repro.kernels import ref

    rng = np.random.default_rng(7)
    n, k, q = 509, 8, 2
    p = jnp.asarray(rng.uniform(size=(n, k)), jnp.float32)
    b = jnp.asarray(rng.uniform(size=(n, k)), jnp.float32)
    lam = jnp.asarray(rng.uniform(0.2, 1.0, size=(k,)), jnp.float32)
    pedges = profit_edges_fixed(64)
    out_k = kops.scd_finalize_hist(p, b, lam, pedges, q, tile_n=128)
    out_r = ref.scd_finalize_ref(p, b, lam, pedges, q)
    for name, a, c in zip(["ch", "gh", "r", "primal", "dual", "lo", "hi"],
                          out_k, out_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=2e-6,
                                   atol=1e-6, err_msg=name)
    # metrics-only variant
    mk = kops.scd_finalize_hist(p, b, lam, pedges, q, tile_n=128,
                                with_hist=False)
    mr = ref.scd_finalize_ref(p, b, lam, pedges, q, with_hist=False)
    assert mk[0] is None and mk[1] is None
    for name, a, c in zip(["r", "primal", "dual", "lo", "hi"], mk[2:], mr[2:]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=2e-6,
                                   err_msg=name)


def test_finalize_kernel_seeded_chunking_bitwise():
    """Seeded finalize accumulation over chunks == one whole-shard call,
    bit for bit (same tile) — the kernel-path §5c contract."""
    from repro.kernels import ops as kops

    rng = np.random.default_rng(3)
    n, k, q = 512, 6, 1
    p = jnp.asarray(rng.uniform(size=(n, k)), jnp.float32)
    b = jnp.asarray(rng.uniform(size=(n, k)), jnp.float32)
    lam = jnp.asarray(rng.uniform(0.2, 1.0, size=(k,)), jnp.float32)
    pedges = profit_edges_fixed(64)
    nb = pedges.shape[0] + 1
    acc = (jnp.zeros((k, nb), jnp.float32), jnp.zeros((nb,), jnp.float32),
           jnp.zeros((k,), jnp.float32), jnp.zeros((), jnp.float32),
           jnp.zeros((), jnp.float32), jnp.asarray(jnp.inf),
           jnp.asarray(-jnp.inf))
    ch, gh, r, pr, du, lo, hi = acc
    for i in range(0, n, 128):
        ch, gh, r, pr, du, lo, hi = kops.scd_finalize_hist(
            p[i:i + 128], b[i:i + 128], lam, pedges, q, tile_n=128,
            cons_hist_init=ch, gain_hist_init=gh, r_init=r,
            sums_init=jnp.stack([pr, du]), maxs_init=jnp.stack([hi, -lo]))
    whole = kops.scd_finalize_hist(p, b, lam, pedges, q, tile_n=128)
    for name, a, c in zip(["ch", "gh", "r", "primal", "dual", "lo", "hi"],
                          (ch, gh, r, pr, du, lo, hi), whole):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c),
                                      err_msg=name)


def test_fused_finalize_kernel_path_streaming():
    """use_kernels streaming: lam bitwise vs resident chunked (pinned
    tile), finalize outputs allclose to the jnp streaming path."""
    kp, q = sparse_instance(shard_key(4), n=1021, k=10, q=2, tightness=0.4)
    cfg = SolverConfig(reduce="bucketed", max_iters=10, use_kernels=True,
                       kernel_tile=128)
    res = solve(kp, cfg.replace(chunk_size=256), q=q)
    sk = solve_streaming(array_source(kp, 256), cfg, q=q)
    np.testing.assert_array_equal(np.asarray(sk.lam), np.asarray(res.lam))
    assert int(sk.iters) == int(res.iters)
    sj = solve_streaming(array_source(kp, 256),
                         cfg.replace(use_kernels=False), q=q)
    np.testing.assert_allclose(np.asarray(sk.r), np.asarray(sj.r), rtol=1e-5)
    np.testing.assert_allclose(float(sk.primal), float(sj.primal), rtol=1e-5)
    assert np.all(np.asarray(sk.r) <= np.asarray(kp.budgets) * (1 + 1e-4))


# ---------------------------------------------------------------------------
# record_history when streaming: actionable error / metrics_every sampling.
# ---------------------------------------------------------------------------

def test_streaming_history_error_names_workarounds():
    kp, q = sparse_instance(shard_key(4), n=64, k=4, q=1, tightness=0.4)
    src = array_source(kp, 16)
    with pytest.raises(ValueError) as exc:
        solve_streaming(src, SolverConfig(record_history=True), q=q)
    msg = str(exc.value)
    assert "metrics_every" in msg          # the sampling workaround
    assert "resident" in msg               # ... or solve resident


def test_streaming_metrics_every_samples_history():
    kp, q = sparse_instance(shard_key(4), n=1021, k=10, q=2, tightness=0.4)
    cfg = SolverConfig(reduce="bucketed", max_iters=20)
    base = solve_streaming(array_source(kp, 256), cfg, q=q)
    rh = solve_streaming(
        array_source(kp, 256),
        cfg.replace(record_history=True, metrics_every=3), q=q)
    # scan and while drivers share the step fn: trajectories bitwise.
    np.testing.assert_array_equal(np.asarray(rh.lam), np.asarray(base.lam))
    assert int(rh.iters) == int(base.iters)
    h = rh.history
    assert sorted(h) == ["dual", "gap", "lam", "max_violation", "primal"]
    prim = np.asarray(h["primal"])
    assert prim.shape == (20,)
    finite = np.isfinite(prim)
    assert finite[0] and finite[3] and not finite[1]   # every 3rd sampled
    assert np.all(np.isfinite(np.asarray(h["lam"])))   # lam on every row
    # a converged sample evaluates the final metrics
    last = np.flatnonzero(finite)[-1]
    assert np.isfinite(np.asarray(h["dual"])[last])


# ---------------------------------------------------------------------------
# Host-fed sources (core/prefetch.py): bitwise vs the traced driver.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("double_buffer", [True, False])
def test_host_streaming_bitwise_vs_device(double_buffer):
    """Double-buffered or synchronous, the host-fed solve reproduces the
    traced array_source solve bit for bit, field for field."""
    kp, q = sparse_instance(shard_key(4), n=1021, k=10, q=2, tightness=0.4)
    cfg = SolverConfig(reduce="bucketed", max_iters=20)
    dev = solve_streaming(array_source(kp, 256), cfg, q=q)
    host = solve_streaming_host(
        host_array_source(np.asarray(kp.p), np.asarray(kp.b),
                          np.asarray(kp.budgets), 256),
        cfg, q=q, double_buffer=double_buffer)
    for f in ["lam", "iters", "r", "primal", "dual", "tau"]:
        np.testing.assert_array_equal(np.asarray(getattr(host, f)),
                                      np.asarray(getattr(dev, f)), err_msg=f)


def test_host_streaming_dd_and_legacy_bitwise():
    kp, q = sparse_instance(shard_key(4), n=1021, k=10, q=2, tightness=0.4)
    hsrc = host_array_source(np.asarray(kp.p), np.asarray(kp.b),
                             np.asarray(kp.budgets), 256)
    for cfg in [SolverConfig(algo="dd", max_iters=10, dd_lr=2e-3),
                SolverConfig(reduce="bucketed", max_iters=20,
                             stream_finalize="legacy")]:
        dev = solve_streaming(array_source(kp, 256), cfg, q=q)
        host = solve_streaming_host(hsrc, cfg, q=q)
        for f in ["lam", "iters", "r", "primal", "dual", "tau"]:
            np.testing.assert_array_equal(np.asarray(getattr(host, f)),
                                          np.asarray(getattr(dev, f)),
                                          err_msg=f)


def test_memmap_source_streams_from_disk(tmp_path):
    """Raw on-disk files, memory-mapped: same solve as in-memory host."""
    kp, q = sparse_instance(shard_key(4), n=777, k=6, q=1, tightness=0.4)
    p = np.asarray(kp.p, np.float32)
    b = np.asarray(kp.b, np.float32)
    p_path, b_path = tmp_path / "p.bin", tmp_path / "b.bin"
    p.tofile(p_path)
    b.tofile(b_path)
    src = memmap_source(p_path, b_path, 777, 6, np.asarray(kp.budgets), 128)
    cfg = SolverConfig(reduce="bucketed", max_iters=15)
    res = solve_streaming_host(src, cfg, q=q)
    ref = solve_streaming_host(
        host_array_source(p, b, np.asarray(kp.budgets), 128), cfg, q=q)
    for f in ["lam", "iters", "r", "primal", "dual", "tau"]:
        np.testing.assert_array_equal(np.asarray(getattr(res, f)),
                                      np.asarray(getattr(ref, f)), err_msg=f)


def test_host_streaming_rejects_cyclic_and_unsampled_history():
    kp, q = sparse_instance(shard_key(4), n=64, k=4, q=1, tightness=0.4)
    src = host_array_source(np.asarray(kp.p), np.asarray(kp.b),
                            np.asarray(kp.budgets), 16)
    with pytest.raises(ValueError, match="cyclic"):
        solve_streaming_host(src, SolverConfig(cd_mode="cyclic"), q=q)
    # Unsampled history would re-scan the source every iteration: same
    # rejection as the traced driver. Sampled history works (below).
    with pytest.raises(ValueError, match="record_history"):
        solve_streaming_host(src, SolverConfig(record_history=True), q=q)


def test_host_streaming_metrics_every_matches_traced_bitwise():
    """Host-fed sampled history == the traced solve_streaming history at
    the same cfg.metrics_every, bitwise: live sampled rows, NaN rows and
    the frozen converged tail (ROADMAP leftover, ported in PR 4)."""
    kp, q = sparse_instance(shard_key(4), n=1021, k=10, q=2, tightness=0.4)
    cfg = SolverConfig(reduce="bucketed", max_iters=20,
                       record_history=True, metrics_every=3)
    dev = solve_streaming(array_source(kp, 256), cfg, q=q)
    host = solve_streaming_host(
        host_array_source(np.asarray(kp.p), np.asarray(kp.b),
                          np.asarray(kp.budgets), 256), cfg, q=q)
    for f in ["lam", "iters", "r", "primal", "dual", "tau"]:
        np.testing.assert_array_equal(np.asarray(getattr(host, f)),
                                      np.asarray(getattr(dev, f)), err_msg=f)
    assert sorted(host.history) == sorted(dev.history)
    for key in dev.history:
        a, b = np.asarray(host.history[key]), np.asarray(dev.history[key])
        assert a.shape == b.shape, key
        np.testing.assert_array_equal(a, b, err_msg=key)


# ---------------------------------------------------------------------------
# Fused finalize under shard_map (8 virtual devices, subprocess).
# ---------------------------------------------------------------------------

_SHARDED_FINALIZE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core import solve_sharded
from repro.core.chunked import array_source, solve_streaming
from repro.core.instances import sparse_instance, shard_key
from repro.core.types import SolverConfig

kp, q = sparse_instance(shard_key(4), n=1024, k=10, q=1, tightness=0.4)
mesh = jax.make_mesh((4, 2), ("data", "model"))
cfg = SolverConfig(reduce="bucketed", max_iters=20)

fused = solve_streaming(array_source(kp, 64), cfg, q=q, mesh=mesh)
legacy = solve_streaming(array_source(kp, 64),
                         cfg.replace(stream_finalize="legacy"), q=q, mesh=mesh)
np.testing.assert_array_equal(np.asarray(fused.lam), np.asarray(legacy.lam))
assert int(fused.iters) == int(legacy.iters)
assert float(fused.dual) == float(legacy.dual), "dual not bitwise"
assert np.all(np.asarray(fused.r) <= np.asarray(kp.budgets) * (1 + 1e-4))
np.testing.assert_allclose(float(fused.primal), float(legacy.primal),
                           rtol=1e-2)

# postprocess off: the two finalizes are the same reduction — bitwise.
f0 = solve_streaming(array_source(kp, 64), cfg.replace(postprocess=False),
                     q=q, mesh=mesh)
l0 = solve_streaming(array_source(kp, 64),
                     cfg.replace(postprocess=False, stream_finalize="legacy"),
                     q=q, mesh=mesh)
for a, b in zip(f0[:6], l0[:6]):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

# multiplier trajectory still bitwise vs the resident sharded solve.
base = solve_sharded(kp, mesh, cfg, q=q)
np.testing.assert_array_equal(np.asarray(fused.lam), np.asarray(base.lam))
assert int(fused.iters) == int(base.iters)
print("FINALIZE-OK")
"""


@pytest.mark.slow
def test_fused_finalize_sharded_subprocess():
    """Fused vs legacy finalize under shard_map on 8 virtual devices."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    out = subprocess.run(
        [sys.executable, "-c", _SHARDED_FINALIZE_SCRIPT], env=env,
        capture_output=True, text=True, timeout=900, cwd=str(REPO))
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "FINALIZE-OK" in out.stdout


# ---------------------------------------------------------------------------
# Screening-inertness regression: the exact bytes, by digest.
# ---------------------------------------------------------------------------

# sha256 over the result fields below. The traced streaming driver's
# unscreened digest is the reference; it is not a recorded constant,
# because both the jax.random instance bytes and the solve's f32
# arithmetic move with the JAX/XLA build. The host-fed driver must
# reproduce those bytes, and so must both drivers with
# cfg.screening=True on this uniform fixture (whose chunk ratio maxima
# never clear the bucket ladder) — the feature is provably inert here.
_GOLDEN_FIELDS = ("lam", "iters", "r", "primal", "dual", "tau")


def _result_digest(res):
    h = hashlib.sha256()
    for f in _GOLDEN_FIELDS:
        h.update(np.asarray(getattr(res, f)).tobytes())
    return h.hexdigest()


def test_streaming_golden_digest_unchanged():
    kp, q = sparse_instance(shard_key(4), 1021, 10, 2, tightness=0.4)
    cfg = SolverConfig(reduce="bucketed", max_iters=20)
    src_np = (np.asarray(kp.p), np.asarray(kp.b), np.asarray(kp.budgets))

    traced = solve_streaming(array_source(kp, 256), cfg, q=q)
    golden = _result_digest(traced)
    assert int(traced.iters) <= 20
    assert bool(jnp.all(traced.r <= kp.budgets))
    host = solve_streaming_host(host_array_source(*src_np, 256), cfg, q=q)
    assert _result_digest(host) == golden

    # Screening on: retires nothing here, must still not move a bit.
    scfg = cfg.replace(screening=True)
    t_scr = solve_streaming(array_source(kp, 256), scfg, q=q)
    assert _result_digest(t_scr) == golden
    assert t_scr.screen is not None
    h_scr = solve_streaming_host(host_array_source(*src_np, 256), scfg, q=q)
    assert _result_digest(h_scr) == golden
    assert bool(h_scr.screen["active"].all())
