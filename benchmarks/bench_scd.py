"""Per-iteration SCD hot path: fused vs unfused map+reduce wall-time.

``PYTHONPATH=src python -m benchmarks.bench_scd [--smoke] [--out PATH]``

Times one SCD iteration's map+reduce — candidates + bucketed histogram +
per-knapsack top — through the two-kernel path (scd_candidates ->
bucket_hist, (n, K) v1/v2 round-tripping through HBM) and the fused
single-kernel path (kernels/scd_fused.py, candidates never leave VMEM)
across an (n, K) grid, and writes ``BENCH_scd.json`` so later PRs can
diff the perf trajectory. On CPU both run the Pallas interpreter: the
measured win there is the deleted second grid pass; the HBM-traffic win
on top of it only shows on real TPU.
"""
from __future__ import annotations

import argparse
import functools
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.common import timeit  # noqa: E402
from repro.core.bucketing import make_edges  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels.scd_fused import LANE_TILE  # noqa: E402

# Per-device user shards at production scale (a billion users over a pod
# is ~1e4-1e5 per core). Below ~4k rows the interpret-mode dispatch
# overhead drowns the fusion win on CPU, so CI measures from 8k up.
GRID = [(8192, 8), (8192, 32), (32768, 8), (32768, 32)]
# Smoke gates CI: one point with the widest fused-vs-unfused margin
# (~1.5x on CPU interpret), so host noise can't flip the comparison.
SMOKE_GRID = [(32768, 8)]


@functools.partial(jax.jit, static_argnames=("q", "tile"))
def _unfused(p, b, lam, edges, q, tile):
    v1, v2 = ops.scd_candidates(p, b, lam, q, tile_n=tile)
    hist = ops.bucket_hist(v1, v2, edges, tile_n=tile)
    return hist, jnp.max(v1, axis=0)


@functools.partial(jax.jit, static_argnames=("q",))
def _fused(p, b, lam, edges, q):
    return ops.scd_fused_hist(p, b, lam, edges, q)


def bench_point(n, k, q=2, half=24, seed=0, samples=16):
    kp, kb, kl = jax.random.split(jax.random.PRNGKey(seed), 3)
    p = jax.random.uniform(kp, (n, k), jnp.float32)
    b = jax.random.uniform(kb, (n, k), jnp.float32, 0.05, 1.0)
    lam = jax.random.uniform(kl, (k,), jnp.float32, 0.0, 1.5)
    edges = make_edges(lam, 1e-4, 1.6, half)
    tile = ops.pick_tile(n)
    # Compile both variants up front, then take the min over many short
    # interleaved samples: best-case time is the standard noise-robust
    # estimator, and interleaving keeps scheduler/load drift on a shared
    # host from biasing whichever variant runs second.
    jax.block_until_ready(_unfused(p, b, lam, edges, q, tile))
    jax.block_until_ready(_fused(p, b, lam, edges, q))
    ts_u, ts_f = [], []
    for _ in range(samples):
        ts_u.append(timeit(_unfused, p, b, lam, edges, q, tile,
                           warmup=0, iters=1))
        ts_f.append(timeit(_fused, p, b, lam, edges, q,
                           warmup=0, iters=1))
    t_unfused = min(ts_u)
    t_fused = min(ts_f)
    return {
        "n": n,
        "k": k,
        "q": q,
        "tile": tile,
        "lane_tile": min(LANE_TILE, n),
        "unfused_s": t_unfused,
        "fused_s": t_fused,
        "speedup": t_unfused / t_fused,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="single small point (CI-friendly)")
    ap.add_argument("--out", default="BENCH_scd.json")
    args = ap.parse_args()
    # Fail on an unwritable destination BEFORE the minutes-long measurement.
    out_path = pathlib.Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)

    points = []
    print("n,k,unfused_us,fused_us,speedup")
    for n, k in (SMOKE_GRID if args.smoke else GRID):
        r = bench_point(n, k)
        points.append(r)
        print(f"{n},{k},{r['unfused_s'] * 1e6:.1f},"
              f"{r['fused_s'] * 1e6:.1f},{r['speedup']:.2f}x")

    report = {"backend": jax.default_backend(), "points": points}
    out_path.write_text(json.dumps(report, indent=2) + "\n")
    print(f"wrote {args.out}")

    slow = [r for r in points if r["fused_s"] > r["unfused_s"]]
    if slow:
        print(f"REGRESSION: fused slower on {len(slow)} point(s)")
        sys.exit(1)


if __name__ == "__main__":
    main()
