"""Host-fed streaming solves: chunks that live on disk, not in a trace.

``core.chunked`` streams instances whose chunks are *traceable* — a
generated function of the chunk index, or slices of device-resident
arrays. Real datasets are neither: they sit in files on the host. This
module adds the third source family — a :class:`HostChunkSource`
producing NumPy chunks (memory-mapped files, in-memory arrays, or any
callable) — and a Python-level epoch driver, :func:`solve_streaming_host`,
that feeds them through the *same* accumulation kernels as the traced
driver with the next chunk's host-to-device transfer overlapped against
the current chunk's compute:

* **Double buffering.** Each per-chunk step is dispatched
  asynchronously; while the device works, the host produces chunk i+1
  (memmap page-in, decompression, whatever ``fn`` does) and issues its
  ``jax.device_put``, so H2D rides under the kernel. The synchronous
  mode (``double_buffer=False``) blocks on every transfer and every
  step — the naive feeding loop — and exists as the benchmark baseline
  (BENCH_stream_passes.json measures the gap).
* **Donated carries.** The running (histogram, top) / finalize
  accumulators are donated back to each step, so the constant-size
  carry state is updated in place rather than reallocated per chunk.
* **Sharding.** With ``mesh`` the chunk range is split into ``slots``
  *virtual shards* (:func:`sharded_source`), each an independent
  carry-seeded accumulator; every column step uploads one chunk per
  slot with per-device shardings and runs the accumulation under
  ``shard_map`` (one dispatch, all devices in parallel), and the
  constant-size slot partials are combined with
  :func:`repro.core.chunked.ordered_fold` — a fixed in-slot-order f32
  addition chain. With ``slots == devices`` this reproduces the traced
  ``stream_solve_fn`` sharded driver field-for-field (the CPU psum
  all-reduces in rank order — pinned by tests); because the slot
  partials and the fold never depend on which physical device ran a
  slot, the same solve is *bitwise invariant to the mesh size*, which
  is what makes elastic resume possible.
* **Fault tolerance.** With ``cfg.fetch_retries > 0`` (or a
  ``fetch_timeout`` / ``verify_refetch``) the source is wrapped in
  :func:`repro.core.faults.resilient_source` at solve entry, so *every*
  fetch site — the epoch loops, the sharded per-slot sub-sources, the
  presolve head read, the fingerprint's chunk-0 probe — retries
  transient failures under a capped, deterministically jittered backoff
  and an optional per-fetch timeout. Retries re-run only the pure
  fetch, never the accumulate, so a solve that survives injected
  transient faults is **bitwise identical** to the fault-free solve
  (chaos-parity tests pin this); exhausted retries raise a
  ``ChunkFetchError`` naming the chunk index and the attempt history.
* **Preemption safety.** ``cfg.checkpoint_every`` writes a
  constant-size resume state (lam, the damping carry, the
  fused-finalize slot partials, an epoch/chunk cursor and a source
  fingerprint) through the atomic checkpoint layer
  (:mod:`repro.checkpoint.ckpt`) every N iterations — and every N
  columns inside the fused finalize pass. ``resume_from=`` restores the
  latest checkpoint (torn ``.tmp`` writes are ignored by construction),
  re-places the slot partials onto the *current* mesh via the elastic
  re-sharding path, and continues to a result bitwise-identical to the
  uninterrupted run — on the same mesh or a degraded one (8 -> 4 -> 1
  devices), as long as the device count divides ``slots``. Resume
  requires the source to be restart-deterministic (memmap files and the
  ``data/synth`` generators are; the fingerprint hashes chunk 0 to
  catch feeding a different instance).

Bit-identity: every per-chunk step runs ``solver.scd_chunk_accumulate``
and ``chunked.finalize_chunk_accumulate`` — the exact functions the
traced scan bodies run — and the multiplier update replays the
``iterate_multipliers`` step arithmetic, so a host-fed solve over the
same rows and chunking is bit-identical to ``solve_streaming`` over an
``array_source``, fields for fields, single-device and sharded alike
(tests pin both). Deviation: the sharded host presolve (§5.3) samples
the *global* stream head like the single-device driver, not each
shard's head like the traced sharded presolve — pass ``lam0`` for exact
warm-start parity, or leave ``presolve_samples=0`` (the default).
"""
from __future__ import annotations

import functools
import hashlib
import time
from typing import Callable, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..checkpoint import ckpt
from ..obs import NULL_TRACER
from .bucketing import make_edges, threshold_from_hist
from .faults import policy_from_cfg, resilient_source
from .chunked import (
    StreamResult,
    _metrics_init,
    _num_chunks,
    _pinned_dot,
    _validate_stream_cfg,
    adjusted_profit_chunk,
    finalize_chunk_accumulate,
    ordered_fold,
)
from .postprocess import (
    profit_edges,
    profit_edges_fixed,
    removable_hist,
    threshold_and_removed,
    threshold_from_removable_hist,
)
from .screening import HostScreen, crossing_trusted
from .solver import damped_multiplier_step, scd_chunk_accumulate, solve
from .sparse_scd import select_sparse
from .types import SolverConfig, SparseKP

__all__ = ["HostChunkSource", "host_array_source", "memmap_source",
           "callable_source", "sharded_source", "chunk_hashes",
           "solve_streaming_host", "source_fingerprint"]

# Resume-state phases (the "epoch cursor" of the checkpoint): the solve
# is either still iterating multipliers or inside the finalize pass.
_PHASE_ITER = 0
_PHASE_FIN = 1


class HostChunkSource(NamedTuple):
    """A sparse GKP instance delivered as on-demand *NumPy* chunks.

    The host-side mirror of ``chunked.ChunkSource``: ``fn(i)`` is a
    plain Python callable mapping the int chunk index to ``(p, b)``
    NumPy arrays of shape exactly (chunk, K) — rows at global index
    >= n (the ragged tail) MUST come back as p = b = 0, the same
    inert-row contract as the traced sources. ``fn`` runs on the host
    thread between device dispatches, so anything goes: memmap slices,
    file decoding, RPC fetches. Checkpoint/resume additionally requires
    ``fn`` to be restart-deterministic (same bytes for the same index
    across process restarts).
    """

    n: int                 # virtual user count
    k: int                 # knapsacks (== items, sparse form)
    chunk: int             # rows per chunk
    budgets: np.ndarray    # (K,) global budgets
    fn: Callable           # i -> (p (chunk, K), b (chunk, K)) numpy


def _pad_chunk(a, chunk, dtype):
    a = np.asarray(a, dtype=dtype)
    if a.shape[0] < chunk:
        a = np.concatenate(
            [a, np.zeros((chunk - a.shape[0],) + a.shape[1:], dtype)])
    return a


def host_array_source(p, b, budgets, chunk: int) -> HostChunkSource:
    """Wrap host-resident (n, K) arrays — incl. ``np.memmap`` — as chunks.

    Slicing a memmap only touches the pages of the requested chunk, so
    this is the out-of-core path for instances that exist as files: the
    (n, K) arrays are never resident in process memory, only the
    O(chunk·K) working slice (plus page cache at the OS's discretion).
    The ragged tail is zero-padded per the inert-row contract.
    """
    p = np.asarray(p) if not isinstance(p, np.memmap) else p
    b = np.asarray(b) if not isinstance(b, np.memmap) else b
    n, k = p.shape
    dtype = np.float32

    def fn(i):
        lo = i * chunk
        hi = min(lo + chunk, n)
        return (_pad_chunk(p[lo:hi], chunk, dtype),
                _pad_chunk(b[lo:hi], chunk, dtype))

    return HostChunkSource(n=n, k=k, chunk=chunk,
                           budgets=np.asarray(budgets, dtype), fn=fn)


def memmap_source(p_path, b_path, n: int, k: int, budgets,
                  chunk: int, dtype=np.float32) -> HostChunkSource:
    """Memory-mapped on-disk instance: raw row-major (n, K) p/b files.

    Opens both files with ``np.memmap(mode="r")`` and serves them
    through :func:`host_array_source`; nothing O(n) is ever read into
    memory — the epoch loop faults in exactly the chunks it streams,
    overlapped with device compute when double buffering is on.
    """
    p = np.memmap(p_path, dtype=dtype, mode="r", shape=(n, k))
    b = np.memmap(b_path, dtype=dtype, mode="r", shape=(n, k))
    return host_array_source(p, b, budgets, chunk)


def chunk_hashes(source: HostChunkSource, chunks=None) -> np.ndarray:
    """Per-chunk sha256 content digests of a host source, as (c, 32) uint8.

    Hashes the exact float32 payload bytes (``p`` then ``b``) each chunk
    index serves — the same bytes the solver consumes and the
    fingerprint's chunk-0 probe hashes — so two sources whose digests
    match for a chunk are byte-identical there. This is the identity a
    *real* (file-backed, non-synthetic) source brings to delta refresh:
    :func:`repro.serve.engine.content_chunk_diff` compares the previous
    generation's digests to the new ones and re-streams only chunks
    whose content actually changed (DESIGN.md §11). ``chunks`` restricts
    the scan to specific indices (returned in that order); the default
    hashes all of them — one sequential O(n·K) read, the price of not
    having a generator's closed-form diff.
    """
    if chunks is None:
        chunks = range(-(-source.n // source.chunk))
    out = np.zeros((len(chunks), 32), np.uint8)
    for j, i in enumerate(chunks):
        p, b = source.fn(int(i))
        h = hashlib.sha256(np.asarray(p, np.float32).tobytes())
        h.update(np.asarray(b, np.float32).tobytes())
        out[j] = np.frombuffer(h.digest(), np.uint8)
    return out


def callable_source(fn, n: int, k: int, budgets, chunk: int) -> HostChunkSource:
    """HostChunkSource from any chunk-producing callable.

    ``fn(i)`` must honour the inert-row contract (rows past n come back
    zero); the produced arrays are converted/padded defensively.
    """
    def wrapped(i):
        p, b = fn(i)
        return (_pad_chunk(p, chunk, np.float32),
                _pad_chunk(b, chunk, np.float32))

    return HostChunkSource(n=n, k=k, chunk=chunk,
                           budgets=np.asarray(budgets, np.float32),
                           fn=wrapped)


def sharded_source(source: HostChunkSource, slots: int):
    """Split a host source into ``slots`` disjoint chunk-range sub-sources.

    Slot ``s`` owns global chunks [s*cps, (s+1)*cps), cps = ceil(c/slots)
    — the same contiguous chunk partition the traced sharded driver
    hands shard ``s`` (``stream_solve_fn``'s ``i0 = shard * cpl``), so a
    slot's carry-seeded accumulation reproduces that shard's partial
    bit-for-bit. Sub-source ``fn(j)`` serves the global chunk
    ``s*cps + j``, or an all-zero (inert) chunk for indices past the
    last real chunk — mirroring the traced sources' padded-index
    contract, which matters bitwise: the traced scan *does* run those
    inert chunks (e.g. their invalid candidates still raise the running
    top from -inf), so the host slots must too. Works over every source
    family — memmap, callable, in-memory arrays, and the ``data/synth``
    generators.
    """
    if slots < 1:
        raise ValueError(f"slots must be >= 1, got {slots}")
    c = _num_chunks(source.n, source.chunk)
    cps = -(-c // slots)
    subs = []
    for s in range(slots):
        def fn(j, _s=s):
            i = _s * cps + j
            if i >= c:
                z = np.zeros((source.chunk, source.k), np.float32)
                return z, z.copy()
            return source.fn(i)

        lo = min(s * cps * source.chunk, source.n)
        hi = min((s + 1) * cps * source.chunk, source.n)
        subs.append(HostChunkSource(n=hi - lo, k=source.k,
                                    chunk=source.chunk,
                                    budgets=source.budgets, fn=fn))
    return subs


# --------------------------------------------------------------------------
# The double-buffered epoch driver.
# --------------------------------------------------------------------------

def _put_chunk(source, i, dtype, acc=None):
    # ``acc`` is the per-epoch ingest accumulator ([fetch_s, h2d_s,
    # chunks]): timings are bare perf_counter pairs on the host and are
    # emitted as ONE ingest.fetch + ONE ingest.h2d record per epoch —
    # per-chunk span objects on the streaming critical path would
    # dominate the cost they measure. Nothing here reads a clock inside
    # traced code, so the produced bytes are identical either way.
    if acc is not None:
        t0 = time.perf_counter()
        p, b = source.fn(i)
        t1 = time.perf_counter()
        out = (jax.device_put(np.asarray(p, dtype)),
               jax.device_put(np.asarray(b, dtype)))
        acc[0] += t1 - t0
        acc[1] += time.perf_counter() - t1
        acc[2] += 1
        return out
    p, b = source.fn(i)
    return (jax.device_put(np.asarray(p, dtype)),
            jax.device_put(np.asarray(b, dtype)))


def _epoch(source, step, state, extra, dtype, double_buffer,
           start=0, on_step=None, indices=None, tracer=NULL_TRACER):
    """One pass over chunks [start, c): ``state = step(state, p, b, *extra)``.

    Double-buffered mode dispatches the step (async) and only then
    produces + uploads the next chunk, so host work and H2D overlap the
    device compute; the carry pytree is donated by ``step`` so the
    constant-size state is updated in place. Synchronous mode blocks on
    the transfer and on the step — one chunk fully in flight at a time —
    and is kept as the benchmark baseline. ``on_step(i, state)``, when
    given, observes the post-chunk-i state (the checkpoint hook; reading
    it synchronizes, which is the measured checkpoint overhead).
    ``indices`` overrides the index range with an explicit ascending
    chunk list — the active-set screening pass (core/screening.py)
    streams only surviving chunks through exactly this loop.
    """
    c = _num_chunks(source.n, source.chunk)
    idxs = list(range(start, c)) if indices is None else list(indices)
    acc = [0.0, 0.0, 0] if tracer.enabled else None
    t_epoch = time.time() if tracer.enabled else 0.0
    if not double_buffer:
        for i in idxs:
            cur = _put_chunk(source, i, dtype, acc)
            jax.block_until_ready(cur)
            state = step(state, *cur, *extra)
            jax.block_until_ready(state)
            if on_step is not None:
                on_step(i, state)
        _emit_ingest(tracer, t_epoch, acc)
        return state
    if not idxs:
        return state
    nxt = _put_chunk(source, idxs[0], dtype, acc)
    for t, i in enumerate(idxs):
        cur, nxt = nxt, None
        state = step(state, *cur, *extra)
        if t + 1 < len(idxs):
            nxt = _put_chunk(source, idxs[t + 1], dtype, acc)
        if on_step is not None:
            on_step(i, state)
    _emit_ingest(tracer, t_epoch, acc)
    return state


def _emit_ingest(tracer, t_epoch, acc):
    """One ingest.fetch + one ingest.h2d record for a finished epoch."""
    if acc is not None and acc[2]:
        tracer.record("ingest.fetch", t_epoch, acc[0], chunks=acc[2])
        tracer.record("ingest.h2d", t_epoch, acc[1], chunks=acc[2])


def _observing_source(source, scr, base=0):
    """Wrap a source so every fetched chunk also records its screening
    certificate (:meth:`HostScreen.note_bound`). The bound is computed
    from exactly the bytes the accumulate consumes — after the fault
    layer's retries — so a certificate always describes the data that
    actually entered the histogram."""
    def fn(i):
        p, b = source.fn(i)
        scr.note_bound(base + i, p, b)
        return p, b
    return source._replace(fn=fn)


def _presolve_host(source, lam0, q, cfg):
    """§5.3 warm start: materialise the leading chunks, solve scaled."""
    if cfg.presolve_samples <= 0:
        return lam0
    s = min(cfg.presolve_samples, source.n)
    m = -(-s // source.chunk)
    parts = [source.fn(i) for i in range(m)]
    p = np.concatenate([pp for pp, _ in parts])[:s]
    b = np.concatenate([bb for _, bb in parts])[:s]
    frac = s / source.n
    small = SparseKP(p=jnp.asarray(p), b=jnp.asarray(b),
                     budgets=jnp.asarray(source.budgets) * frac)
    sub_cfg = cfg.replace(presolve_samples=0, record_history=False,
                          postprocess=False, chunk_size=None)
    return solve(small, sub_cfg, q=q, lam0=lam0).lam


def _legacy_finalize_host(source, lam, q, cfg, budgets, st, dtype,
                          double_buffer):
    """The three-pass legacy finalize, host-fed (benchmark baseline)."""
    metrics_step, hist_step, apply_step = (
        st["metrics_step"], st["hist_step"], st["apply_step"])
    r, primal, dual_sum, lo, hi = _epoch(
        source, metrics_step, _metrics_init(source.k, lam.dtype),
        (lam,), dtype, double_buffer)
    dual = dual_sum + _pinned_dot(lam, budgets)
    if not cfg.postprocess:
        return StreamResult(lam, None, r, primal, dual,
                            jnp.asarray(-jnp.inf, lam.dtype))
    edges = profit_edges(lo, hi, cfg.profit_buckets)
    hist = _epoch(
        source, hist_step,
        jnp.zeros((source.k, cfg.profit_buckets + 1), lam.dtype),
        (lam, edges), dtype, double_buffer)
    tau = threshold_from_removable_hist(hist, edges, r, budgets)
    r2, primal2 = _epoch(
        source, apply_step,
        (jnp.zeros_like(r), jnp.zeros((), lam.dtype)),
        (lam, tau), dtype, double_buffer)
    return StreamResult(lam, None, r2, primal2, dual, tau)


# --------------------------------------------------------------------------
# Checkpoint state (constant size): save / restore / fingerprint.
# --------------------------------------------------------------------------

_FIN_KEYS = ["fin_r", "fin_primal", "fin_dual", "fin_lo", "fin_hi",
             "fin_ch", "fin_gh"]


# The SolverConfig fields whose values steer the multiplier trajectory
# or the finalize arithmetic: they are hashed (in this order — the byte
# layout is load-bearing for existing checkpoints) into the resume-state
# fingerprint. ``dtype`` is hashed too, as ``str(cfg.dtype)``.
_FINGERPRINT_CFG_FIELDS = (
    "algo", "cd_mode", "reduce", "tol", "cd_damping", "dd_lr",
    "bucket_half", "bucket_delta", "bucket_growth", "presolve_samples",
    "partial_fraction", "stream_finalize", "profit_buckets",
    "profit_ladder_lo", "profit_ladder_hi", "use_kernels", "kernel_tile",
    "postprocess",
)

# Fields deliberately EXCLUDED from the fingerprint: changing any of
# them across a restart is legitimate because none of them alters the
# accepted multiplier trajectory or the finalize results — iteration
# budget / save cadence / retention, analysis sampling, the fault-retry
# policy, the resident-solver chunking (ignored when streaming), and
# active-set screening (trajectory-neutral by construction — a resumed
# solve rebuilds its screening state from all-active; DESIGN.md §11).
# Every SolverConfig field must appear in exactly one of these two sets
# (tests/test_fingerprint_fields.py enumerates the dataclass and fails
# on a field that is neither fingerprinted nor explicitly exempted).
FINGERPRINT_EXEMPT_FIELDS = frozenset({
    "max_iters", "metrics_every", "record_history",
    "checkpoint_every", "checkpoint_keep",
    "fetch_retries", "fetch_backoff", "fetch_backoff_growth",
    "fetch_backoff_cap", "fetch_jitter", "fetch_timeout",
    "verify_refetch",
    "chunk_size",
    "screening", "screening_floor",
})


def _fingerprint(source, cfg, q, lam_init):
    """Identity hash of (instance, solver arithmetic): workload shape,
    budgets bytes, the warm-start multipliers, the bytes of chunk 0,
    and every cfg field that steers the trajectory
    (``_FINGERPRINT_CFG_FIELDS``). Saved in the resume state; a mismatch
    on resume means the checkpoint belongs to a different solve and is
    refused. ``FINGERPRINT_EXEMPT_FIELDS`` are deliberately excluded —
    extending the iteration budget, changing the save cadence or fault
    policy, or toggling screening across a restart is legitimate.
    """
    h = hashlib.sha256()
    h.update(repr(
        (source.n, source.k, source.chunk, int(q))
        + tuple(getattr(cfg, f) for f in _FINGERPRINT_CFG_FIELDS)
        + (str(cfg.dtype),)).encode())
    h.update(np.asarray(source.budgets, np.float32).tobytes())
    h.update(np.asarray(lam_init, np.float32).tobytes())
    p0, b0 = source.fn(0)
    h.update(np.asarray(p0, np.float32).tobytes())
    h.update(np.asarray(b0, np.float32).tobytes())
    # Stored as raw bytes: an int64 scalar would be silently truncated
    # to int32 by dtype canonicalization on the restore device_put.
    return np.frombuffer(h.digest()[:8], np.uint8).copy()


def source_fingerprint(source: HostChunkSource, cfg: SolverConfig, q: int,
                       lam0=None) -> np.ndarray:
    """Public identity hash of one (source, cfg, q, lam0) solve — (8,) uint8.

    Exactly the fingerprint ``solve_streaming_host`` stores in its resume
    state and refuses to resume across, exposed so higher layers can
    stamp *published* artifacts with the same identity: the serving
    refresh engine (:mod:`repro.serve.engine`) records it in every
    generation, which lets a decision service verify it is answering
    lookups against the workload the generation was actually solved on.
    ``lam0`` defaults to the all-ones cold start like the solver.
    """
    lam0 = (np.ones((source.k,), np.float32) if lam0 is None
            else np.asarray(lam0, np.float32))
    # The chunk-0 probe fetches like any other read: under the cfg's
    # fault policy, so a transient fault during stamping retries instead
    # of failing a refresh whose solve already survived it.
    policy = policy_from_cfg(cfg)
    if policy is not None:
        source = resilient_source(source, policy, verify=cfg.verify_refetch)
    return _fingerprint(source, cfg, q, lam0)


def _save_state(directory, step, phase, iters, cursor, slots, fp, lam,
                dprev, fin, keep=3, tracer=NULL_TRACER):
    """Write one StreamCheckpointState atomically; prune old steps.

    ``fin()`` returns the per-slot fused-finalize partial tuple (leading
    axis = slots; 5 or 7 leaves) — zeros while still iterating. Everything
    is host-gathered NumPy, constant size in n. ``keep`` is the retention
    passed through to ``ckpt.prune`` (``cfg.checkpoint_keep``).

    Spans: ``ckpt.save`` around the whole save, holding ``ckpt.gather``
    (the host reads of the state; reading a device carry waits for the
    device, which is why ``fin`` is called inside it) and ``ckpt.write``
    (the files, their fsyncs and the prune).
    """
    with tracer.span("ckpt.save", step=step):
        with tracer.span("ckpt.gather"):
            state = {
                "phase": np.int32(phase),
                "iters": np.int32(iters),
                "cursor": np.int32(cursor),
                "slots": np.int32(slots),
                "fingerprint": np.asarray(fp, np.uint8),
                "lam": np.asarray(lam),
                "dprev": np.asarray(dprev),
            }
            for name, arr in zip(_FIN_KEYS, fin()):
                state[name] = np.asarray(arr)
        with tracer.span("ckpt.write"):
            ckpt.save(directory, step, state)
            ckpt.prune(directory, keep=keep)


def _load_state(resume_from, mesh, axes):
    """Latest resume state, or None when the directory has none (fresh
    start). With a mesh, the per-slot ``fin_*`` leaves are placed
    straight onto it through the elastic re-sharding path
    (``ckpt.restore_auto`` + ``sharding_tree``) and stay device-resident
    for the finalize to continue from; scalars and the replicated
    multiplier state come back as host NumPy for the driver."""
    step = ckpt.latest_step(resume_from)
    if step is None:
        return None
    sharding_tree = None
    if mesh is not None:
        slot_sh = NamedSharding(mesh, P(axes))
        sharding_tree = {name: slot_sh for name in _FIN_KEYS}
    try:
        state = ckpt.restore_auto(resume_from, step,
                                  sharding_tree=sharding_tree)
    except ValueError as e:
        # Chain the original error: this also catches e.g. a corrupt
        # manifest, not just a re-placement failure.
        raise ValueError(
            f"could not restore checkpoint {resume_from!r} step {step}: "
            f"{e} (if the mesh changed, note the checkpoint's slot count "
            "must be a multiple of the device count)") from e
    return {k: (v if k in _FIN_KEYS else np.asarray(v))
            for k, v in state.items()}


def _fin_zeros_np(slots, k, nb, postprocess, dtype=np.float32):
    """ITER-phase placeholder for the finalize partials (constant shape)."""
    dtype = np.dtype(dtype)
    fin = (np.zeros((slots, k), dtype), np.zeros((slots,), dtype),
           np.zeros((slots,), dtype),
           np.full((slots,), np.inf, dtype),
           np.full((slots,), -np.inf, dtype))
    if postprocess:
        fin = fin + (np.zeros((slots, k, nb), dtype),
                     np.zeros((slots, nb), dtype))
    return fin


# --------------------------------------------------------------------------
# Jitted per-chunk steps: single-device family.
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def _jit_steps(cfg, q):
    """Jitted per-chunk steps and update tails for one (cfg, q).

    Cached on the (hashable) config so repeated host-fed solves — and
    the benchmark's warm-up solve — reuse the compiled programs instead
    of re-jitting per call. Every step donates its carry (argument 0):
    the constant-size accumulators are updated in place chunk by chunk.
    """
    @functools.partial(jax.jit, donate_argnums=(0,))
    def dd_step(r, p_c, b_c, lam):
        x = select_sparse(p_c, b_c, lam, q)
        return r + jnp.sum(b_c * x.astype(b_c.dtype), axis=0)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def scd_step(carry, p_c, b_c, lam, edges):
        # No straggler keep/scale: the single-device driver has one
        # shard, so the traced path's mask is identically 1.0 there —
        # and f32 multiplication by 1.0 is exact, so omitting it is
        # bitwise equivalent (the parity tests pin this).
        hist, top = carry
        return scd_chunk_accumulate(p_c, b_c, lam, edges, q, cfg, hist, top)

    @jax.jit
    def scd_tail(hist, top, lam, dprev, budgets, edges):
        prop = threshold_from_hist(hist, edges, budgets, top)
        return damped_multiplier_step(lam, dprev, prop, cfg)

    @jax.jit
    def scd_tail_scr(hist, top, lam, dprev, budgets, edges):
        # The screened-iteration tail: same threshold + damped step,
        # plus the crossing guard — computed in the SAME compiled
        # program, so the guard's in_bucket tensor is (CSE) the one the
        # threshold recovery selects from: trusted here *means* the
        # accepted step never read bucket 0 (core/screening.py §3).
        prop = threshold_from_hist(hist, edges, budgets, top)
        out = damped_multiplier_step(lam, dprev, prop, cfg)
        return out + (crossing_trusted(hist, budgets),)

    @jax.jit
    def dd_tail(r, lam, dprev, budgets):
        prop = jnp.maximum(lam + cfg.dd_lr * (r - budgets), 0.0)
        return damped_multiplier_step(lam, dprev, prop, cfg)

    pedges = profit_edges_fixed(cfg.profit_buckets, cfg.profit_ladder_lo,
                                cfg.profit_ladder_hi, cfg.dtype)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def fused_step(carry, p_c, b_c, lam):
        return finalize_chunk_accumulate(
            p_c, b_c, lam, q, cfg, carry,
            pedges if cfg.postprocess else None)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def metrics_step(carry, p_c, b_c, lam):
        return finalize_chunk_accumulate(p_c, b_c, lam, q, cfg, carry)

    @jax.jit
    def metrics_tail(r, primal, dual_sum, lam, budgets):
        # The same lines _history_metrics_fn runs on the psum'd partials
        # (axis=None here), so sampled host history rows are bitwise the
        # traced ones.
        dual = dual_sum + _pinned_dot(lam, budgets)
        viol = jnp.max(jnp.maximum(r - budgets, 0.0) / budgets)
        return {"lam": lam, "primal": primal, "dual": dual,
                "gap": dual - primal, "max_violation": viol}

    def _pt(p_c, b_c, lam, x):
        # The pinned row reduction of chunked._chunk_primal.
        return jax.lax.optimization_barrier(jnp.sum(
            jnp.where(x, adjusted_profit_chunk(p_c, b_c, lam), 0.0),
            axis=-1))

    @functools.partial(jax.jit, donate_argnums=(0,))
    def hist_step(hist, p_c, b_c, lam, edges):
        x = select_sparse(p_c, b_c, lam, q)
        cons = b_c * x.astype(b_c.dtype)
        return removable_hist(_pt(p_c, b_c, lam, x), cons, edges, init=hist)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def apply_step(carry, p_c, b_c, lam, tau):
        r2, primal2 = carry
        x = select_sparse(p_c, b_c, lam, q)
        cons = b_c * x.astype(b_c.dtype)
        keep_row = _pt(p_c, b_c, lam, x) > tau
        x = x & keep_row[:, None]
        cons = cons * keep_row[:, None].astype(cons.dtype)
        return (r2 + jnp.sum(cons, axis=0),
                primal2 + jnp.sum(jnp.where(x, p_c, 0.0)))

    return {"dd_step": dd_step, "scd_step": scd_step, "scd_tail": scd_tail,
            "scd_tail_scr": scd_tail_scr,
            "dd_tail": dd_tail, "fused_step": fused_step,
            "metrics_step": metrics_step, "metrics_tail": metrics_tail,
            "hist_step": hist_step, "apply_step": apply_step,
            "pedges": pedges}


# --------------------------------------------------------------------------
# Jitted per-column steps: sharded (virtual-slot) family.
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def _jit_steps_sharded(cfg, q, mesh, spd):
    """Per-column shard_map steps + ordered-fold combines for one
    (cfg, q, mesh, slots-per-device).

    Every step carries per-slot accumulators (leading axis S = spd *
    devices, sharded over all mesh axes) and one chunk per slot
    ((S, chunk, K) batches); inside shard_map each device loops its
    ``spd`` local slots, running the *same* accumulate bodies as the
    traced scan. No collectives in the steps — the combines host-gather
    the S constant-size partials and fold them in slot order
    (``ordered_fold``), which coincides with the traced driver's psum on
    CPU (slots == devices) and never depends on the physical device
    count (elastic resume).
    """
    axes = tuple(mesh.axis_names)
    spec0 = P(axes)
    slots = spd * mesh.devices.size
    pedges = profit_edges_fixed(cfg.profit_buckets, cfg.profit_ladder_lo,
                                cfg.profit_ladder_hi, cfg.dtype)

    # Straggler mask per *slot*, mirroring solver._straggler_mask with
    # size = slots (the flat shard index of the traced driver): keyed on
    # the virtual shard, not the physical device, so degraded meshes
    # drop the same slots.
    if cfg.partial_fraction < 1.0:
        idx = np.arange(slots, dtype=np.float32)
        keep_np = ((idx + 1.0) <= np.float32(cfg.partial_fraction)
                   * np.float32(slots)).astype(np.float32)
        frac = np.maximum(np.float32(cfg.partial_fraction),
                          np.float32(1.0) / np.float32(slots))
        scale_np = np.float32(1.0) / frac
    else:
        keep_np, scale_np = np.ones((slots,), np.float32), np.float32(1.0)

    def _rows(carry, t):
        return tuple(a[t] for a in carry)

    def _stack(rows):
        return tuple(jnp.stack(parts) for parts in zip(*rows))

    def scd_body(hist, top, pb, bb, lam, edges, keep):
        rows = []
        for t in range(spd):
            if cfg.use_kernels or cfg.partial_fraction >= 1.0:
                rows.append(scd_chunk_accumulate(
                    pb[t], bb[t], lam, edges, q, cfg, hist[t], top[t]))
            else:
                rows.append(scd_chunk_accumulate(
                    pb[t], bb[t], lam, edges, q, cfg, hist[t], top[t],
                    keep[t], jnp.float32(scale_np)))
        return _stack(rows)

    # keep is per-slot and must arrive sharded like the carries, so each
    # device indexes its *local* slots' mask values.
    scd_step = jax.jit(shard_map(
        scd_body, mesh=mesh,
        in_specs=(spec0, spec0, spec0, spec0, P(), P(), spec0),
        out_specs=(spec0, spec0), check_vma=False),
        donate_argnums=(0, 1))

    def dd_body(r, pb, bb, lam):
        rows = []
        for t in range(spd):
            x = select_sparse(pb[t], bb[t], lam, q)
            rows.append(r[t] + jnp.sum(bb[t] * x.astype(bb[t].dtype),
                                       axis=0))
        return jnp.stack(rows)

    dd_step = jax.jit(shard_map(
        dd_body, mesh=mesh,
        in_specs=(spec0, spec0, spec0, P()),
        out_specs=spec0, check_vma=False),
        donate_argnums=(0,))

    def fin_body(pedges_or_none, carry, pb, bb, lam):
        rows = []
        for t in range(spd):
            rows.append(finalize_chunk_accumulate(
                pb[t], bb[t], lam, q, cfg, _rows(carry, t), pedges_or_none))
        return _stack(rows)

    n_fin = 7 if cfg.postprocess else 5
    fin_step = jax.jit(shard_map(
        lambda *a: fin_body(pedges if cfg.postprocess else None,
                            a[:n_fin], a[n_fin], a[n_fin + 1], a[n_fin + 2]),
        mesh=mesh,
        in_specs=(spec0,) * n_fin + (spec0, spec0, P()),
        out_specs=(spec0,) * n_fin, check_vma=False),
        donate_argnums=tuple(range(n_fin)))

    metrics_step = jax.jit(shard_map(
        lambda *a: fin_body(None, a[:5], a[5], a[6], a[7]),
        mesh=mesh,
        in_specs=(spec0,) * 5 + (spec0, spec0, P()),
        out_specs=(spec0,) * 5, check_vma=False),
        donate_argnums=(0, 1, 2, 3, 4))

    # Combines: host-gathered slot partials in, replicated results out.
    # ordered_fold = the psum-in-rank-order addition chain, pinned.
    @jax.jit
    def scd_combine(hist, top, lam, dprev, budgets, edges):
        if cfg.use_kernels and cfg.partial_fraction < 1.0:
            # Traced kernel path scales each shard's accumulated
            # histogram once (linear in v2), before the reduce.
            hist = hist * (jnp.asarray(keep_np)[:, None, None]
                           * jnp.float32(scale_np))
        h = ordered_fold(hist)
        t = jnp.max(top, axis=0)               # pmax: order-invariant
        prop = threshold_from_hist(h, edges, budgets, t)
        return damped_multiplier_step(lam, dprev, prop, cfg)

    @jax.jit
    def scd_combine_scr(hist, top, lam, dprev, budgets, edges):
        # Screened-iteration combine: scd_combine's fold + threshold +
        # damped step with the bucket->=1 crossing guard in the same
        # program (see _jit_steps.scd_tail_scr).
        if cfg.use_kernels and cfg.partial_fraction < 1.0:
            hist = hist * (jnp.asarray(keep_np)[:, None, None]
                           * jnp.float32(scale_np))
        h = ordered_fold(hist)
        t = jnp.max(top, axis=0)
        prop = threshold_from_hist(h, edges, budgets, t)
        out = damped_multiplier_step(lam, dprev, prop, cfg)
        return out + (crossing_trusted(h, budgets),)

    @jax.jit
    def dd_combine(r, lam, dprev, budgets):
        rk = ordered_fold(r * jnp.asarray(keep_np)[:, None])
        rk = rk * jnp.float32(scale_np)
        prop = jnp.maximum(lam + cfg.dd_lr * (rk - budgets), 0.0)
        return damped_multiplier_step(lam, dprev, prop, cfg)

    @jax.jit
    def fin_combine(carry, lam, budgets):
        r = ordered_fold(carry[0])
        primal = ordered_fold(carry[1])
        dual = ordered_fold(carry[2]) + _pinned_dot(lam, budgets)
        if not cfg.postprocess:
            return (r, primal, dual, jnp.asarray(-jnp.inf, lam.dtype),
                    None, None)
        ch = ordered_fold(carry[5])
        gh = ordered_fold(carry[6])
        tau, removed_cons, removed_gain = threshold_and_removed(
            ch, gh, pedges, r, budgets)
        return r - removed_cons, primal - removed_gain, dual, tau, ch, gh

    @jax.jit
    def metrics_combine(carry, lam, budgets):
        r = ordered_fold(carry[0])
        primal = ordered_fold(carry[1])
        dual = ordered_fold(carry[2]) + _pinned_dot(lam, budgets)
        viol = jnp.max(jnp.maximum(r - budgets, 0.0) / budgets)
        return {"lam": lam, "primal": primal, "dual": dual,
                "gap": dual - primal, "max_violation": viol}

    return {"scd_step": scd_step, "dd_step": dd_step, "fin_step": fin_step,
            "metrics_step": metrics_step, "scd_combine": scd_combine,
            "scd_combine_scr": scd_combine_scr,
            "dd_combine": dd_combine, "fin_combine": fin_combine,
            "metrics_combine": metrics_combine, "pedges": pedges,
            "keep_np": keep_np}


# --------------------------------------------------------------------------
# Runtimes: the epoch/finalize machinery behind the phase driver.
# --------------------------------------------------------------------------

class _SingleRuntime:
    """Mesh-less host feeding (slots == 1): the original per-chunk jits.

    Kept as its own code path (rather than a 1-device shard_map) so the
    compiled programs — and therefore the f32 rounding contexts the
    PR-3 bitwise host==traced contract was pinned against — are exactly
    the ones the parity tests already cover.
    """

    def __init__(self, source, cfg, q, double_buffer):
        self.source, self.cfg, self.q = source, cfg, q
        self.double_buffer = double_buffer
        self.dtype = cfg.dtype
        self.budgets = jnp.asarray(source.budgets, cfg.dtype)
        self.st = _jit_steps(cfg, q)
        self.fin_cols = _num_chunks(source.n, source.chunk)
        self.real_c = self.fin_cols
        self.slots = 1
        self.scr = None   # HostScreen, installed by the driver
        self.tracer = NULL_TRACER   # phase-span tracer, installed likewise

    def iter_epoch(self, lam, dprev):
        st, cfg, src = self.st, self.cfg, self.source
        if cfg.algo == "dd":
            r = _epoch(src, st["dd_step"], jnp.zeros_like(lam), (lam,),
                       self.dtype, self.double_buffer, tracer=self.tracer)
            return st["dd_tail"](r, lam, dprev, self.budgets)
        edges = make_edges(lam, cfg.bucket_delta, cfg.bucket_growth,
                           cfg.bucket_half)
        if self.scr is not None:
            return self._iter_epoch_screened(lam, dprev, edges)
        hist0 = jnp.zeros((src.k, edges.shape[-1] + 1), jnp.float32)
        top0 = jnp.full((src.k,), -jnp.inf, lam.dtype)
        hist, top = _epoch(src, st["scd_step"], (hist0, top0),
                           (lam, edges), self.dtype, self.double_buffer,
                           tracer=self.tracer)
        return st["scd_tail"](hist, top, lam, dprev, self.budgets, edges)

    def _iter_epoch_screened(self, lam, dprev, edges):
        """One SCD epoch over the active chunk set only; the crossing
        guard (core/screening.py §3) either certifies the screened
        histogram or triggers one full unscreened pass."""
        st, src, scr = self.st, self.source, self.scr
        scr.begin_iter(np.asarray(lam))
        idx = scr.active_indices()
        obs = _observing_source(src, scr)

        def run(over, indices=None):
            hist0 = jnp.zeros((src.k, edges.shape[-1] + 1), jnp.float32)
            top0 = jnp.full((src.k,), -jnp.inf, lam.dtype)
            hist, top = _epoch(over, st["scd_step"], (hist0, top0),
                               (lam, edges), self.dtype,
                               self.double_buffer, indices=indices,
                               tracer=self.tracer)
            return st["scd_tail_scr"](hist, top, lam, dprev, self.budgets,
                                      edges)

        lam_n, d_n, moved, trusted = run(obs, indices=idx)
        scr.record_streamed(len(idx))
        self.tracer.event("screen.skip", streamed=len(idx),
                          skipped=self.real_c - len(idx))
        if scr.any_retired() and not bool(trusted):
            lam_n, d_n, moved, _ = run(src)
            scr.record_streamed(self.real_c, fallback=True)
        scr.retire()
        return lam_n, d_n, moved

    def metrics_record(self, lam):
        out = _epoch(self.source, self.st["metrics_step"],
                     _metrics_init(self.source.k, lam.dtype), (lam,),
                     self.dtype, self.double_buffer, tracer=self.tracer)
        return self.st["metrics_tail"](out[0], out[1], out[2], lam,
                                       self.budgets)

    def fin_init(self):
        init = _metrics_init(self.source.k, self.cfg.dtype)
        if self.cfg.postprocess:
            nb = self.st["pedges"].shape[0] + 1
            init = init + (jnp.zeros((self.source.k, nb), self.cfg.dtype),
                           jnp.zeros((nb,), self.cfg.dtype))
        return init

    def fin_run(self, carry, lam, start, on_col):
        return _epoch(self.source, self.st["fused_step"], carry, (lam,),
                      self.dtype, self.double_buffer, start=start,
                      on_step=on_col, tracer=self.tracer)

    def fin_result(self, out, lam, iters):
        r, primal, dual_sum = out[0], out[1], out[2]
        dual = dual_sum + _pinned_dot(lam, self.budgets)
        fin_hist = None
        if self.cfg.postprocess:
            tau, removed_cons, removed_gain = threshold_and_removed(
                out[5], out[6], self.st["pedges"], r, self.budgets)
            r = r - removed_cons
            primal = primal - removed_gain
            fin_hist = (out[5], out[6])
        else:
            tau = jnp.asarray(-jnp.inf, lam.dtype)
        return StreamResult(lam, jnp.int32(iters), r, primal, dual, tau,
                            None, fin_hist)

    def fin_to_np(self, carry):
        return tuple(np.asarray(a)[None] for a in carry)

    def fin_from_np(self, fin):
        return tuple(jnp.asarray(a[0]) for a in fin)

    def legacy_result(self, lam, iters):
        res = _legacy_finalize_host(self.source, lam, self.q, self.cfg,
                                    self.budgets, self.st, self.dtype,
                                    self.double_buffer)
        return res._replace(iters=jnp.int32(iters))


def _chip_piece(chunks, dt):
    """One chip's (spd, chunk, K) host piece of a column: a view of its
    one slot's chunk, or a stack of its slots' chunks."""
    if len(chunks) == 1:
        return np.asarray(chunks[0], dt)[None]
    return np.stack(chunks, dtype=dt, casting="unsafe")


def _stack_bytes(chunks, spd, dt):
    """Host bytes :func:`_chip_piece` copies for a column's ``chunks``:
    all of them when a chip holds several slots, else the chunks that
    ``np.asarray`` has to cast to ``dt``."""
    return sum(np.size(c) * dt.itemsize for c in chunks
               if spd > 1 or not (isinstance(c, np.ndarray)
                                  and c.dtype == dt))


class _ShardedRuntime:
    """Virtual-slot shard_map feeding: S slots over D devices (S % D == 0).

    Each column step uploads one chunk per slot ((S, chunk, K), sharded
    over the mesh) and advances every slot's carry under shard_map; the
    constant-size slot partials are host-gathered once per epoch and
    combined in fixed slot order. Nothing downstream of the per-slot
    accumulation depends on D, which is what makes a checkpoint written
    on one mesh resume bitwise on another.
    """

    def __init__(self, source, cfg, q, mesh, slots, double_buffer):
        self.source, self.cfg, self.q = source, cfg, q
        self.double_buffer = double_buffer
        self.slots = slots
        self.subs = sharded_source(source, slots)
        c = _num_chunks(source.n, source.chunk)
        self.real_c = c
        self.cps = -(-c // slots)
        self.fin_cols = self.cps
        self.scr = None   # HostScreen over slots*cps padded chunk slots
        self.tracer = NULL_TRACER   # phase-span tracer, driver-installed
        self.spd = spd = slots // mesh.devices.size
        self.st = _jit_steps_sharded(cfg, q, mesh, spd)
        self.slot_sh = NamedSharding(mesh, P(tuple(mesh.axis_names)))
        # Which slots each addressable chip holds of an (S, chunk, K)
        # column, from the sharding's own device -> index map: a chip's
        # piece is uploaded straight from its slots' chunks.
        self.col_shape = (slots, source.chunk, source.k)
        idx = self.slot_sh.addressable_devices_indices_map(self.col_shape)
        self.chips = list(idx)
        self.chip_slots = [range(*idx[d][0].indices(slots))
                           for d in self.chips]
        self.budgets = jnp.asarray(source.budgets, cfg.dtype)
        self.keep = jax.device_put(self.st["keep_np"], self.slot_sh)

    def _fetch_cols(self, j, screen, dt):
        if not screen:
            ps, bs = zip(*(sub.fn(j) for sub in self.subs))
            return ps, bs
        # Screened column: fetch only slots whose chunk (global slot
        # index s*cps + j) is still active; retired slots are fed
        # zeros — bitwise-neutral by the inert-row contract (their
        # scatter-adds contribute +0.0 and their candidate values
        # sit below ``max(top, edges[:, -1])``, screening.py §4).
        scr, cps = self.scr, self.cps
        zero = np.zeros((self.source.chunk, self.source.k), dt)
        ps, bs = [], []
        for s, sub in enumerate(self.subs):
            g = s * cps + j
            if scr.active[g]:
                p, b = sub.fn(j)
                scr.note_bound(g, p, b)
            else:
                p = b = zero
            ps.append(p)
            bs.append(b)
        return ps, bs

    def _produce(self, j, screen=False):
        # Same cfg.dtype cast as the single-device _put_chunk, so a
        # source producing wider arrays feeds both runtimes identically.
        dt = np.dtype(self.cfg.dtype)
        with self.tracer.span("ingest.fetch", col=int(j)):
            ps, bs = self._fetch_cols(j, screen, dt)
        with self.tracer.span("ingest.h2d", col=int(j)):
            # The host assembly of each chip's piece: a view of its one
            # slot's chunk, or a stack of its slots' chunks; the rest of
            # ``ingest.h2d`` is the per-chip upload alone.
            with self.tracer.span("ingest.stack", col=int(j),
                                  bytes=_stack_bytes((*ps, *bs), self.spd,
                                                     dt)):
                pieces = [_chip_piece([cs[s] for s in sl], dt)
                          for cs in (ps, bs) for sl in self.chip_slots]
            arrs = jax.device_put(pieces, self.chips * 2)
            n = len(self.chips)
            return tuple(jax.make_array_from_single_device_arrays(
                self.col_shape, self.slot_sh, arrs[i:i + n])
                for i in (0, n))

    def _epoch_cols(self, step, state, extra, start=0, on_col=None,
                    indices=None, screen=False):
        """One pass over columns [start, cps): every slot advances one
        chunk per column. Same double-buffering contract as ``_epoch``.
        ``indices`` restricts the pass to an explicit ascending column
        list (the screening path: columns whose slots are all retired
        are skipped outright)."""
        cols = self.cps
        idxs = list(range(start, cols)) if indices is None else list(indices)

        def call(state, cur):
            out = step(*state, *cur, *extra)
            return out if isinstance(out, tuple) else (out,)

        if not self.double_buffer:
            for j in idxs:
                cur = self._produce(j, screen)
                jax.block_until_ready(cur)
                state = call(state, cur)
                jax.block_until_ready(state)
                if on_col is not None:
                    on_col(j, state)
            return state
        if not idxs:
            return state
        nxt = self._produce(idxs[0], screen)
        for t, j in enumerate(idxs):
            cur, nxt = nxt, None
            state = call(state, cur)
            if t + 1 < len(idxs):
                nxt = self._produce(idxs[t + 1], screen)
            if on_col is not None:
                on_col(j, state)
        return state

    def iter_epoch(self, lam, dprev):
        cfg, st, S, k = self.cfg, self.st, self.slots, self.source.k
        dt = np.dtype(cfg.dtype)
        if cfg.algo == "dd":
            r0 = jax.device_put(np.zeros((S, k), dt), self.slot_sh)
            (r,) = self._epoch_cols(st["dd_step"], (r0,), (lam,))
            with self.tracer.span("slots.combine"):
                return st["dd_combine"](np.asarray(r), lam, dprev,
                                        self.budgets)
        edges = make_edges(lam, cfg.bucket_delta, cfg.bucket_growth,
                           cfg.bucket_half)
        if self.scr is not None:
            return self._iter_epoch_screened(lam, dprev, edges)
        # The histogram is f32 by design (traced convention); top carries
        # the multiplier dtype.
        hist0 = jax.device_put(
            np.zeros((S, k, edges.shape[-1] + 1), np.float32), self.slot_sh)
        top0 = jax.device_put(np.full((S, k), -np.inf, dt), self.slot_sh)
        hist, top = self._epoch_cols(st["scd_step"], (hist0, top0),
                                     (lam, edges, self.keep))
        with self.tracer.span("slots.combine"):
            return st["scd_combine"](np.asarray(hist), np.asarray(top), lam,
                                     dprev, self.budgets, edges)

    def _iter_epoch_screened(self, lam, dprev, edges):
        """Screened SCD epoch: retired slots feed zeros, columns with no
        active slot are skipped; the crossing guard runs on the folded
        histogram inside the same program as the multiplier step."""
        cfg, st, S, k = self.cfg, self.st, self.slots, self.source.k
        dt = np.dtype(cfg.dtype)
        scr, cps = self.scr, self.cps
        scr.begin_iter(np.asarray(lam))
        act = scr.active.reshape(S, cps)
        cols = [int(j) for j in np.flatnonzero(act.any(axis=0))]
        streamed = int(np.count_nonzero(scr.active[:self.real_c]))

        def run(indices=None, screen=False):
            hist0 = jax.device_put(
                np.zeros((S, k, edges.shape[-1] + 1), np.float32),
                self.slot_sh)
            top0 = jax.device_put(np.full((S, k), -np.inf, dt),
                                  self.slot_sh)
            hist, top = self._epoch_cols(st["scd_step"], (hist0, top0),
                                         (lam, edges, self.keep),
                                         indices=indices, screen=screen)
            with self.tracer.span("slots.combine"):
                return st["scd_combine_scr"](np.asarray(hist),
                                             np.asarray(top), lam, dprev,
                                             self.budgets, edges)

        lam_n, d_n, moved, trusted = run(indices=cols, screen=True)
        scr.record_streamed(streamed)
        self.tracer.event("screen.skip", streamed=streamed,
                          skipped=self.real_c - streamed)
        if scr.any_retired() and not bool(trusted):
            lam_n, d_n, moved, _ = run()
            scr.record_streamed(self.real_c, fallback=True)
        scr.retire()
        return lam_n, d_n, moved

    def metrics_record(self, lam):
        init = _fin_zeros_np(self.slots, self.source.k, 0, False,
                             self.cfg.dtype)
        carry = tuple(jax.device_put(a, self.slot_sh) for a in init)
        out = self._epoch_cols(self.st["metrics_step"], carry, (lam,))
        with self.tracer.span("slots.combine"):
            return self.st["metrics_combine"](
                tuple(np.asarray(a) for a in out[:3]), lam, self.budgets)

    def fin_init(self):
        fin = _fin_zeros_np(self.slots, self.source.k,
                            self.st["pedges"].shape[0] + 1,
                            self.cfg.postprocess, self.cfg.dtype)
        return tuple(jax.device_put(a, self.slot_sh) for a in fin)

    def fin_run(self, carry, lam, start, on_col):
        return self._epoch_cols(self.st["fin_step"], carry, (lam,),
                                start=start, on_col=on_col)

    def fin_result(self, carry, lam, iters):
        with self.tracer.span("slots.combine"):
            vals = tuple(np.asarray(a) for a in carry)
            r, primal, dual, tau, ch, gh = self.st["fin_combine"](
                vals, lam, self.budgets)
        fin_hist = (ch, gh) if self.cfg.postprocess else None
        return StreamResult(lam, jnp.int32(iters), r, primal, dual, tau,
                            None, fin_hist)

    def fin_to_np(self, carry):
        return tuple(np.asarray(a) for a in carry)

    def fin_from_np(self, fin):
        return tuple(jax.device_put(a, self.slot_sh) for a in fin)


# --------------------------------------------------------------------------
# The driver: presolve -> iterate -> finalize, with checkpoint/resume.
# --------------------------------------------------------------------------

def solve_streaming_host(source: HostChunkSource,
                         cfg: SolverConfig = SolverConfig(), q: int = 1,
                         lam0=None, double_buffer: bool = True, mesh=None,
                         slots: Optional[int] = None, checkpoint_dir=None,
                         resume_from=None,
                         screen_init: Optional[dict] = None,
                         tracer=None) -> StreamResult:
    """Solve a host-fed sparse GKP, chunks uploaded as they are consumed.

    The host-side twin of ``chunked.solve_streaming``: the iteration
    loop runs in Python (one *epoch* over the chunks per SCD/DD
    iteration, early exit at convergence), every per-chunk device step
    is the same accumulation the traced scan performs — carry-seeded
    histogram, donated buffers — and the finalize follows
    ``cfg.stream_finalize`` ("fused": one epoch; "legacy": three,
    single-device only). With ``double_buffer`` (default) the next
    chunk's production and H2D transfer overlap the current chunk's
    compute.

    Results are bit-identical to ``solve_streaming`` over an
    ``array_source`` holding the same rows and chunking (same
    accumulation functions, same update arithmetic, same finalize), so
    the traced driver remains this one's oracle — single-device and,
    with ``mesh``, under ``shard_map`` field-for-field (tests pin both).

    Sharding: ``mesh`` splits the chunk range into ``slots`` virtual
    shards (default: one per device) fed with per-device shardings; see
    the module docstring. ``slots`` may exceed the device count (each
    device then works several slots per column), which is what lets a
    checkpoint resume on a *smaller* mesh bitwise.

    Preemption safety: with ``cfg.checkpoint_every = N`` and a
    ``checkpoint_dir``, a constant-size resume state is written
    atomically every N iterations, and every N chunk columns inside the
    fused finalize pass. ``resume_from=<dir>`` restores the latest state
    (fingerprint-checked against this source/cfg; torn writes ignored)
    and continues; an interrupted-and-resumed solve returns bitwise the
    uninterrupted ``lam/iters/r/primal/dual/tau`` — and the same
    ``fin_hist`` — on the same mesh or any mesh whose device count
    divides the checkpoint's slot count. An empty/missing ``resume_from``
    directory starts fresh (the standard relaunch loop: always pass
    ``--resume``).

    Restrictions (each raises ValueError): sparse SCD (sync) and DD only
    — ``cd_mode="cyclic"`` would re-feed the source K times per
    iteration; the sharded runtime requires the fused finalize;
    ``record_history`` needs ``cfg.metrics_every`` sampling (one extra
    metrics epoch per sample, bitwise the traced sampled history) and
    cannot be combined with checkpoint/resume.

    Observability: ``tracer`` (a :class:`repro.obs.Tracer`; default the
    shared no-op) emits host-side phase spans to its JSONL journal. At
    the top level they tile the solve: ``solve.fingerprint`` (resume
    state and identity hash), one ``solve.iterate`` per iteration
    (epoch, convergence wait, resume-state save) and ``solve.finalize``
    (finalize-entry save, the finalize pass and its result). Inside
    them: ``ckpt.save`` with ``ckpt.gather`` and ``ckpt.write`` per
    resume-state save, ``ingest.fetch`` / ``ingest.h2d`` and
    ``screen.skip``; on the sharded runtime also ``ingest.stack`` (the
    host assembly of each chip's piece of a column: a view of its one
    slot's chunk, or a stack of its slots' chunks, with the ``bytes``
    it copied; inside the column's ``ingest.h2d``) and
    ``slots.combine`` (each epoch's read-back and fixed-order fold of
    the slot partials). Tracing is *not* a ``SolverConfig`` field:
    it never enters the resume fingerprint, and because spans bracket
    only host Python (never a value inside a jitted program), a traced
    solve is bitwise identical to an untraced one (``tests/test_obs.py``
    and ``benchmarks/bench_obs.py`` gate this).
    """
    _validate_stream_cfg(cfg)
    if cfg.algo == "scd" and cfg.cd_mode != "sync":
        raise ValueError(
            "solve_streaming_host supports cd_mode='sync' (cyclic CD "
            "re-feeds the whole source K times per iteration)")
    # Fault layer: wrap the source once, here, so every downstream fetch
    # site (epochs, sharded sub-sources, presolve, fingerprint) retries
    # transient failures under cfg's policy. Retries re-run only the
    # pure fetch — the accumulate consumes exactly the bytes a clean
    # fetch returns, which is what keeps a fault-surviving solve bitwise
    # equal to the fault-free one.
    fault_policy = policy_from_cfg(cfg)
    if fault_policy is not None:
        source = resilient_source(source, fault_policy,
                                  verify=cfg.verify_refetch)
    # cfg.checkpoint_every is the cadence; the directory is the enable
    # switch. A cadence with no directory runs unprotected (so reference
    # runs can share the exact cfg of a checkpointed job); the launcher
    # rejects that combination for production jobs.
    ckpt_every = cfg.checkpoint_every
    if checkpoint_dir is None:
        checkpoint_dir = resume_from
    checkpointing = ckpt_every > 0 and checkpoint_dir is not None
    if checkpointing and cfg.checkpoint_keep < 1:
        raise ValueError(
            f"checkpoint_keep must be >= 1 (got {cfg.checkpoint_keep}): "
            "retaining zero resume states would leave nothing to resume "
            "from")
    if (checkpointing or resume_from is not None) and cfg.record_history:
        raise ValueError(
            "record_history is an analysis mode and cannot be combined "
            "with checkpoint/resume (the sampled rows are not part of "
            "the constant-size resume state)")

    tracer = NULL_TRACER if tracer is None else tracer
    # The resume state and the identity hash: host reads of the
    # checkpoint directory and of chunk 0, before any solve work.
    with tracer.span("solve.fingerprint"):
        restored = (_load_state(resume_from,
                                mesh, tuple(mesh.axis_names) if mesh else None)
                    if resume_from is not None else None)
        if restored is not None:
            S = int(restored["slots"])
            if slots is not None and slots != S:
                raise ValueError(
                    f"checkpoint was written with slots={S}; asked for "
                    f"slots={slots} (the slot count is fixed at first launch)")
        else:
            S = slots if slots is not None else (
                mesh.devices.size if mesh is not None else 1)
        if mesh is None and S > 1:
            # Degraded all the way down to one process-default device: run
            # the same slot structure on an internal single-device mesh.
            mesh = jax.make_mesh((1,), ("slots",))
        if mesh is not None:
            d = mesh.devices.size
            if S < d or S % d != 0:
                raise ValueError(
                    f"slots={S} must be a positive multiple of the mesh "
                    f"device count {d} (elastic resume divides slots over "
                    f"devices)")
        sharded = mesh is not None
        if sharded and cfg.stream_finalize == "legacy":
            raise ValueError(
                "sharded host feeding supports stream_finalize='fused' only "
                "(the legacy three-pass finalize remains on the single-device "
                "path as the oracle/benchmark baseline)")

        dtype = cfg.dtype
        lam = (jnp.ones((source.k,), dtype) if lam0 is None
               else jnp.asarray(lam0, dtype))
        fp = (_fingerprint(source, cfg, q, np.asarray(lam))
              if (checkpointing or restored is not None) else None)
        if restored is not None and not np.array_equal(
                np.asarray(restored["fingerprint"], np.uint8), fp):
            raise ValueError(
                "resume state fingerprint mismatch: the checkpoint in "
                f"{resume_from!r} was written for a different "
                "(source, cfg, q, lam0) — refusing to resume")

    rt = (_ShardedRuntime(source, cfg, q, mesh, S, double_buffer) if sharded
          else _SingleRuntime(source, cfg, q, double_buffer))
    rt.tracer = tracer
    dprev = jnp.zeros_like(lam)
    iters, phase, cursor, fin_carry = 0, _PHASE_ITER, 0, None
    if restored is not None:
        lam = jnp.asarray(restored["lam"], dtype)
        dprev = jnp.asarray(restored["dprev"], dtype)
        iters = int(restored["iters"])
        phase = int(restored["phase"])
        cursor = int(restored["cursor"])
        if phase == _PHASE_FIN and cursor > 0:
            fin_carry = rt.fin_from_np(tuple(
                restored[k] for k in _FIN_KEYS if k in restored))
    else:
        lam = _presolve_host(source, lam, q, cfg)

    scr = None
    if cfg.screening:   # _validate_stream_cfg pinned algo/cd_mode/reduce
        # Screening state is rebuilt fresh on every (re)start — it is
        # not part of the checkpoint (see HostScreen: it never steers
        # the trajectory). ``screen_init`` seeds it from a previous
        # solve's stats for the serving layer's delta refresh.
        scr = HostScreen(rt.slots * rt.fin_cols, source.k, cfg,
                         np.asarray(lam), seed=screen_init)
        rt.scr = scr

    rows = [] if cfg.record_history else None
    every = max(cfg.metrics_every, 1)
    fin_zeros = functools.partial(_fin_zeros_np, S, source.k,
                                  cfg.profit_buckets + 1, cfg.postprocess,
                                  cfg.dtype)

    save = functools.partial(_save_state, checkpoint_dir, slots=S, fp=fp,
                             keep=cfg.checkpoint_keep, tracer=tracer)
    fresh_finalize = phase == _PHASE_ITER
    if phase == _PHASE_ITER:
        while iters < cfg.max_iters:
            # The whole iteration: the epoch, the wait on ``moved`` and
            # the iteration's resume-state save.
            with tracer.span("solve.iterate", iter=iters):
                lam, dprev, moved = rt.iter_epoch(lam, dprev)
                iters += 1
                if rows is not None:
                    if (iters - 1) % every == 0:
                        rows.append(rt.metrics_record(lam))
                    else:
                        nan = jnp.asarray(jnp.nan, lam.dtype)
                        rows.append({"lam": lam, "primal": nan, "dual": nan,
                                     "gap": nan, "max_violation": nan})
                if not bool(moved):
                    break
                if (checkpointing and iters % ckpt_every == 0
                        and iters < cfg.max_iters):
                    save(iters, _PHASE_ITER, iters, 0, lam=lam, dprev=dprev,
                         fin=fin_zeros)

    with tracer.span("solve.finalize", mode=cfg.stream_finalize,
                     iters=iters):
        if checkpointing and fresh_finalize:
            # Finalize-entry state: without it, a kill during the
            # finalize would force replaying multiplier iterations.
            save(cfg.max_iters + 1, _PHASE_FIN, iters, 0, lam=lam,
                 dprev=dprev, fin=fin_zeros)

        history = None
        if rows is not None:
            # The traced scan solve freezes converged iterations: every
            # row past convergence re-records the final iteration's
            # sample — which is exactly a copy of the last live row (the
            # sampling predicate is keyed on the frozen iteration number).
            while len(rows) < cfg.max_iters:
                rows.append(rows[-1])
            history = {k: jnp.stack([r[k] for r in rows]) for k in rows[0]}

        scr_stats = scr.stats() if scr is not None else None
        if cfg.stream_finalize == "legacy":
            res = rt.legacy_result(lam, iters)
            return res._replace(history=history, screen=scr_stats)

        on_col = None
        if checkpointing:
            def on_col(j, state):
                done = j + 1
                if done % ckpt_every == 0 and done < rt.fin_cols:
                    save(cfg.max_iters + 1 + done, _PHASE_FIN, iters, done,
                         lam=lam, dprev=dprev,
                         fin=functools.partial(rt.fin_to_np, state))

        carry = rt.fin_init() if fin_carry is None else fin_carry
        carry = rt.fin_run(carry, lam, cursor, on_col)
        res = rt.fin_result(carry, lam, iters)
    return res._replace(history=history, screen=scr_stats)
