"""Solver launcher: the paper's production job.

    python -m repro.launch.solve --workload table1 --scale 1e-4
    python -m repro.launch.solve --n 1000000 --k 10 --q 1
    python -m repro.launch.solve --n 4000000 --k 10 --streaming --chunk-size 65536

Runs the distributed SCD solver over however many devices exist (all mesh
axes carry the user shard), reports iterations / primal / duality gap /
violations — i.e., the paper's Table 1 row for the requested size. The
full-size workloads only fit a cluster; ``--scale`` shrinks N while
keeping the structure (budgets scale with N, §6).

``--chunk-size C`` streams the per-iteration map over C-user chunks
(identical results on the SCD bucketed path — see core/solver.py for the
chunked-vs-unchunked contract). ``--streaming`` additionally stops
materialising the instance at all: chunks are synthesized on demand
inside the solve (core/chunked.py), so N is bounded by patience, not
device memory — this is the out-of-core mode the chunked benchmark uses
to run far past the unchunked ceiling. A converged streaming solve
touches the source iters + 1 times (``--stream-finalize legacy`` keeps
the three-pass finalize, iters + 3 — see DESIGN.md §5c). ``--host-feed``
swaps in the host-fed pipeline (core/prefetch.py): chunks are produced
as NumPy arrays on the host and uploaded with double-buffered
``device_put`` (``--no-double-buffer`` for the synchronous baseline) —
the mode a real on-disk dataset runs in. Host-fed solves shard over
the mesh (virtual slots, ``--slots`` to pin more than one per device)
and survive preemption: ``--checkpoint-dir D --checkpoint-every N``
writes the atomic resume state, and a relaunch with ``--resume`` picks
the solve back up bitwise (DESIGN.md §7), e.g.

    python -m repro.launch.solve --n 16000000 --host-feed \
        --chunk-size 65536 --checkpoint-dir ckpt/ --checkpoint-every 8 \
        --resume
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.paper_kp import WORKLOADS, KPWorkload
from repro.core import SolverConfig, solve, solve_sharded
from repro.core.chunked import solve_streaming
from repro.core.instances import shard_key, sparse_instance
from repro.core.prefetch import solve_streaming_host
from repro.data.synth import sparse_chunk_source, sparse_host_chunk_source
from repro.launch.env import enable_compile_cache


def _mesh():
    if jax.device_count() > 1:
        return jax.make_mesh((jax.device_count(),), ("users",))
    return None


def run(workload: KPWorkload, cfg: SolverConfig, seed=0, mesh=None):
    """Solve one §6 sparse workload; returns the Table-1-style row dict.

    The instance is materialised on device and solved with
    ``solve``/``solve_sharded`` (``cfg.chunk_size`` chunks the iteration
    map if set). ``mesh=None`` auto-shards over all visible devices.
    """
    kp, q = sparse_instance(
        shard_key(seed), workload.n_users, workload.k, workload.q,
        tightness=workload.tightness,
    )
    t0 = time.time()
    if mesh is None:
        mesh = _mesh()
    if mesh is not None:
        res = solve_sharded(kp, mesh, cfg, q=q)
    else:
        res = solve(kp, cfg, q=q)
    # Dispatch is asynchronous: without the wait, wall_s times the enqueue.
    res = jax.block_until_ready(res)
    dt = time.time() - t0
    viol = float(jnp.max((res.r - kp.budgets) / kp.budgets))
    return {
        "n_users": workload.n_users,
        "k": workload.k,
        "iterations": int(res.iters),
        "primal": float(res.primal),
        "dual": float(res.dual),
        "duality_gap": float(res.dual - res.primal),
        "max_violation": viol,
        "wall_s": round(dt, 2),
    }


def run_streaming(workload: KPWorkload, cfg: SolverConfig, chunk: int,
                  seed=0, mesh=None, host_feed=False, double_buffer=True,
                  checkpoint_dir=None, resume=False, slots=None):
    """Out-of-core solve of a §6 workload: chunks generated on demand.

    Nothing O(N) is ever materialised (device state is O(chunk·K + K·E));
    the decision matrix is not returned — stream it per chunk with
    ``core.chunked.decisions_chunk`` using the reported (lam, tau).
    ``host_feed`` produces the chunks as NumPy arrays on the host and
    runs the prefetch pipeline (core/prefetch.py) instead of the traced
    in-program generator — the path a real on-disk dataset takes. In
    host-feed mode the solve shards over the mesh (one virtual slot per
    device by default; ``slots`` to pin more for elastic resume) and,
    with ``cfg.checkpoint_every`` and a ``checkpoint_dir``, survives
    preemption: relaunch with ``resume=True`` and the same directory.
    """
    t0 = time.time()
    if host_feed:
        src = sparse_host_chunk_source(
            seed, workload.n_users, workload.k, chunk, q=workload.q,
            tightness=workload.tightness)
        if mesh is None and cfg.stream_finalize != "legacy":
            # The legacy three-pass finalize lives on the single-device
            # driver only (its benchmark-baseline role); every other
            # host-fed solve shards over the visible devices.
            mesh = _mesh()
        res = solve_streaming_host(
            src, cfg, q=workload.q, double_buffer=double_buffer, mesh=mesh,
            slots=slots, checkpoint_dir=checkpoint_dir,
            resume_from=checkpoint_dir if resume else None)
    else:
        src = sparse_chunk_source(seed, workload.n_users, workload.k, chunk,
                                  q=workload.q, tightness=workload.tightness)
        if mesh is None:
            mesh = _mesh()
        res = solve_streaming(src, cfg, q=workload.q, mesh=mesh)
    res = jax.block_until_ready(res)
    dt = time.time() - t0
    viol = float(jnp.max((res.r - src.budgets) / src.budgets))
    out = {
        "n_users": workload.n_users,
        "k": workload.k,
        "chunk_size": chunk,
        "iterations": int(res.iters),
        "primal": float(res.primal),
        "dual": float(res.dual),
        "duality_gap": float(res.dual - res.primal),
        "max_violation": viol,
        "wall_s": round(dt, 2),
    }
    if getattr(res, "screen", None) is not None:
        # Host driver: per-epoch streamed-chunk counts. Traced driver:
        # per-iteration active-chunk counts (-1 rows = never reached).
        if "streamed_chunks" in res.screen:
            counts = np.asarray(res.screen["streamed_chunks"])
        else:
            ac = np.asarray(res.screen["active_chunks"])
            counts = ac[ac >= 0]
        out["screen_chunks_per_iter"] = counts.tolist()
        out["screen_resets"] = int(np.asarray(res.screen["resets"]))
    return out


def main():
    """CLI entry point; prints one ``key: value`` line per metric."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", choices=list(WORKLOADS), default="table1")
    ap.add_argument("--scale", type=float, default=1e-4,
                    help="shrink N by this factor (1.0 = full size)")
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--k", type=int, default=None)
    ap.add_argument("--q", type=int, default=None)
    ap.add_argument("--algo", choices=["scd", "dd"], default="scd")
    ap.add_argument("--reduce", choices=["bucketed", "exact"], default="bucketed")
    ap.add_argument("--presolve", type=int, default=0)
    ap.add_argument("--max-iters", type=int, default=40)
    ap.add_argument("--use-kernels", action="store_true",
                    help="Pallas kernel path (fused map+reduce for the "
                         "sparse bucketed solve; compiled on a TPU, "
                         "interpreted on the CPU)")
    ap.add_argument("--chunk-size", type=int, default=None,
                    help="stream the per-iteration map over user chunks "
                         "of this size (bit-identical on the SCD bucketed "
                         "path; see core/solver.py)")
    ap.add_argument("--streaming", action="store_true",
                    help="out-of-core mode: synthesize chunks on demand, "
                         "never materialise the (N, K) instance "
                         "(requires --chunk-size)")
    ap.add_argument("--stream-finalize", choices=["fused", "legacy"],
                    default="fused",
                    help="streaming finalize: one fused pass (iters + 1 "
                         "source passes) or the legacy three-pass oracle "
                         "(iters + 3); DESIGN.md §5c")
    ap.add_argument("--host-feed", action="store_true",
                    help="streaming mode with host-produced NumPy chunks "
                         "through the double-buffered prefetch pipeline "
                         "(core/prefetch.py)")
    ap.add_argument("--no-double-buffer", action="store_true",
                    help="host-feed only: synchronous device_put (the "
                         "naive baseline the bench compares against)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="host-feed only: directory for the atomic "
                         "preemption-safe resume state (DESIGN.md §7)")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="write the resume state every N iterations "
                         "(and every N chunk columns inside the fused "
                         "finalize pass); 0 disables")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest checkpoint from "
                         "--checkpoint-dir before solving (fresh start "
                         "when the directory has none, so a relaunch "
                         "loop can always pass --resume)")
    ap.add_argument("--slots", type=int, default=None,
                    help="host-feed only: virtual shard count (default: "
                         "one per device); fixed at first launch so a "
                         "checkpoint can resume on any mesh whose device "
                         "count divides it")
    ap.add_argument("--screening", action="store_true",
                    help="safe λ-interval active-set screening: retire "
                         "chunks that provably bin below the bucket "
                         "ladder and skip them in iteration passes "
                         "(bitwise-identical results; streaming SCD "
                         "bucketed path only, DESIGN.md §11)")
    ap.add_argument("--screening-floor", type=float, default=0.5,
                    help="certify multipliers down to lam * this factor; "
                         "an escape below the floor reactivates every "
                         "chunk for one full pass")
    args = ap.parse_args()
    enable_compile_cache()

    wl = WORKLOADS[args.workload]
    n = args.n or max(int(wl.n_users * args.scale), 1024)
    wl = KPWorkload(wl.name, n, args.k or wl.k, args.q or wl.q, wl.tightness)
    cfg = SolverConfig(algo=args.algo, reduce=args.reduce,
                       max_iters=args.max_iters,
                       presolve_samples=args.presolve,
                       use_kernels=args.use_kernels,
                       stream_finalize=args.stream_finalize,
                       checkpoint_every=args.checkpoint_every,
                       chunk_size=None if args.streaming else args.chunk_size,
                       screening=args.screening,
                       screening_floor=args.screening_floor)
    if args.screening and not (args.streaming or args.host_feed):
        raise SystemExit("--screening requires --streaming or --host-feed "
                         "(only the chunk-streamed drivers carry an active "
                         "chunk set)")
    if ((args.checkpoint_every or args.checkpoint_dir or args.resume
         or args.slots) and not args.host_feed):
        raise SystemExit("--checkpoint-every/--checkpoint-dir/--resume/"
                         "--slots require --host-feed (only the host-fed "
                         "epoch driver is preemption-safe and slot-sharded)")
    if args.checkpoint_every and not args.checkpoint_dir:
        raise SystemExit("--checkpoint-every requires --checkpoint-dir")
    if args.resume and not args.checkpoint_dir:
        raise SystemExit("--resume requires --checkpoint-dir")
    if args.streaming or args.host_feed:
        if not args.chunk_size:
            raise SystemExit("--streaming/--host-feed require --chunk-size")
        out = run_streaming(wl, cfg, args.chunk_size,
                            host_feed=args.host_feed,
                            double_buffer=not args.no_double_buffer,
                            checkpoint_dir=args.checkpoint_dir,
                            resume=args.resume, slots=args.slots)
    else:
        out = run(wl, cfg)
    for k, v in out.items():
        print(f"{k}: {v}")


if __name__ == "__main__":
    main()
