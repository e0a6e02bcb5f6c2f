#!/usr/bin/env python3
"""Chip smoke: the GKP solve and refresh path, end to end, on a TPU.

    python chip_smoke.py             # one chip: phases (a)-(f)
    python chip_smoke.py --chips 4   # four chips: the sharded paths only

Every instance has the shape of the paper's ``table1`` deployment
(configs/paper_kp.py: K = 10 knapsacks, at most Q = 1 item per user,
tightness 0.5, seed 0; profits and costs uniform on [0, 1)).

One chip:

(a) device check: the default backend is a TPU, or the script exits 2;
(b) resident solve at n = 1e7 through ``repro.launch.solve.run``, once on
    the Pallas kernel path and once on the jnp path; the kernel
    program's HLO must hold ``tpu_custom_call`` (compiled, not
    interpreted) and no relayout copy of p or b, and the two runs agree;
(c) out-of-core streaming solve of ``table1`` at its full n = 1e8 through
    ``repro.launch.solve.run_streaming`` (chunks generated on device);
(d) two refresh generations at n = 4e6 through
    ``repro.launch.refresh.run_scenario`` with 256 lookups: lookups are
    bitwise the materialised decisions, and the warm refresh beats cold;
(e) plain reference: (b)'s kernel-path decisions re-scored in float64
    NumPy, and a 1e6-user instance solved on the CPU backend against the
    chip;
(f) precision: the kernels' histogram sums (the VPU bucket sums of
    ``bucket_hist`` and ``scd_fused_hist``, the finalize kernel's one-hot
    contraction) on operands that bf16 rounding moves by 2^-10, against
    float64.

Four chips (``--chips 4``): the host-fed sharded streaming solve with 4
slots on a 4-device mesh against the same solve on one device (bitwise),
and ``solve_sharded`` of (b)'s instance on 4 devices against the
one-device solve.

Each phase prints its numbers next to their limits, wall time with the
compile time apart, and peak device memory. Any failed check raises; the
last line, printed only when every phase passed, is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import pathlib
import sys
import tempfile
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

from repro.launch.env import enable_compile_cache  # noqa: E402

K, Q, TIGHTNESS, SEED = 10, 1, 0.5, 0
N_RESIDENT = 10_000_000
N_STREAM = 100_000_000
N_REFRESH = 4_000_000
N_CPU = 1_000_000
N_SHARDED_FEED = 4_194_304          # 64 chunks: 16 per slot
CHUNK = 65536
MAX_ITERS = 20
REFRESH_MAX_ITERS = 60
LOOKUPS = 256
# Agreement between two solves of one instance that differ only in how
# f32 sums are grouped (kernel vs jnp path, chip vs CPU, 4 vs 1 device):
# the same iteration count, and primal/dual within this relative bound.
RTOL_SOLVE = 1e-5
# Chip-reported primal and consumption vs their float64 recompute.
RTOL_REF = 1e-5
# Budget feasibility: r <= budgets * (1 + FEAS).
FEAS = 1e-4
# Phase (f): operands of 1 + 2^-10 are exact in f32 and 1.0 in bf16; over
# N_PRECISION rows every f32 partial sum of them is exact, so a histogram
# whose contraction rounded its operands to bf16 reads 2^-10 / (1 + 2^-10)
# = 9.756e-04 low, and one kept in f32 is exact.
PRECISION_V = 1 + 2**-10
N_PRECISION = 4096
RTOL_PRECISION = 1e-6


# Seconds spent in XLA compiles (persistent-cache reads included), summed
# by a listener on JAX's compile event; timed() reports a call's share.
_COMPILE_S = [0.0]


def _count_compile(event, duration_secs, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[0] += duration_secs


def timed(fn, *args, **kwargs):
    """(fn's result, wall seconds, seconds of that spent compiling).

    The wall ends when the result's arrays are ready on the device.
    """
    import jax

    c0, t0 = _COMPILE_S[0], time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kwargs))
    return out, time.perf_counter() - t0, _COMPILE_S[0] - c0


class SmokeFailure(AssertionError):
    """A check of the smoke failed."""


def log(msg):
    print(msg, flush=True)


def check(ok, what):
    if not ok:
        raise SmokeFailure(what)
    log(f"    PASS {what}")


def rel(a, b):
    return abs(float(a) - float(b)) / max(abs(float(b)), 1e-30)


def peak_gib(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use", float("nan")) / 2**30


def assert_compiled_kernels(hlo, what):
    """A kernel path's compiled HLO holds its Pallas calls natively."""
    n = hlo.count("tpu_custom_call")
    check(n > 0, f"{what}: {n} tpu_custom_call in the compiled HLO (> 0)")


def relayout_report(hlo, n):
    """Where the kernel path lays p and b out again for a Pallas call.

    The entry arguments keep users on lanes, and so do the blocks of
    the fused kernel, so the solve should hold no such copy. Reports the
    computations that copy p or b into a K-on-lanes (n, K) layout.
    """
    comp, where = None, []
    for line in hlo.splitlines():
        if line and not line.startswith(" ") and line.rstrip().endswith("{"):
            comp = line.split()[0]
            if comp == "ENTRY":
                comp = "ENTRY " + line.split()[1]
        if (f"f32[{n},{K}]{{1,0" in line and " copy(" in line
                and "=" in line):
            where.append(comp)
    return where


def phase_resident(dev, n=N_RESIDENT, max_iters=MAX_ITERS):
    import jax.numpy as jnp

    from repro.configs.paper_kp import KPWorkload
    from repro.core import SolverConfig
    from repro.core.instances import shard_key, sparse_instance
    from repro.core.solver import solve_fn
    from repro.launch.solve import run

    log(f"[b] resident solve, table1 shape at n={n:,}, max_iters={max_iters}")
    wl = KPWorkload("table1", n, K, Q, TIGHTNESS)
    out = {}
    for tag, kernels in (("kernel", True), ("jnp", False)):
        cfg = SolverConfig(max_iters=max_iters, use_kernels=kernels)
        kp, _ = sparse_instance(shard_key(SEED), n, K, Q, tightness=TIGHTNESS)
        t0 = time.perf_counter()
        # The program run() executes, compiled ahead of time to inspect.
        compiled = solve_fn(cfg, Q).lower(
            kp, jnp.ones((K,), jnp.float32)).compile()
        compile_s = time.perf_counter() - t0
        mem = compiled.memory_analysis()
        hlo = compiled.as_text()
        del kp
        log(f"    {tag}: compile {compile_s:.1f}s; argument "
            f"{mem.argument_size_in_bytes / 2**30:.3f} GiB, temp "
            f"{mem.temp_size_in_bytes / 2**30:.3f} GiB")
        if kernels:
            assert_compiled_kernels(hlo, "resident kernel solve")
            where = relayout_report(hlo, n)
            check(not where, f"kernel relayout copies of p, b: "
                  f"{len(where)} in {sorted(set(where))} (none)")
        else:
            check("tpu_custom_call" not in hlo,
                  "resident jnp solve: no Pallas call in its HLO")
        res, wall, comp = timed(run, wl, cfg, seed=SEED)
        log(f"    {tag}: {json.dumps(res)}; run wall {wall:.2f}s, of which "
            f"compile {comp:.2f}s; peak {peak_gib(dev):.3f} GiB")
        out[tag] = res
    a, b = out["kernel"], out["jnp"]
    check(a["iterations"] == b["iterations"],
          f"kernel iterations {a['iterations']} == jnp {b['iterations']}")
    for f in ("primal", "dual"):
        r = rel(a[f], b[f])
        check(r <= RTOL_SOLVE, f"kernel vs jnp {f}: rel diff {r:.3e} "
              f"<= {RTOL_SOLVE:g} (bitwise equal: {a[f] == b[f]})")
    for tag in ("kernel", "jnp"):
        v = out[tag]["max_violation"]
        check(v <= FEAS, f"{tag} max_violation {v:.3e} <= {FEAS:g}")
    return out


def phase_streaming(dev, n=N_STREAM, chunk=CHUNK, max_iters=MAX_ITERS):
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.paper_kp import WORKLOADS, KPWorkload
    from repro.core import SolverConfig
    from repro.core.chunked import stream_solve_fn
    from repro.data.synth import sparse_chunk_source
    from repro.launch.solve import run_streaming

    wl = WORKLOADS["table1"]
    wl = KPWorkload(wl.name, n, wl.k, wl.q, wl.tightness)
    log(f"[c] streaming solve of table1 at n={n:,}, chunk {chunk}, "
        f"kernels, max_iters={max_iters}")
    cfg = SolverConfig(max_iters=max_iters, use_kernels=True)
    src = sparse_chunk_source(SEED, n, wl.k, chunk, q=wl.q,
                              tightness=wl.tightness)
    t0 = time.perf_counter()
    compiled = stream_solve_fn(src, cfg, wl.q).lower(
        src.budgets, jnp.ones((wl.k,), jnp.float32)).compile()
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    log(f"    compile {compile_s:.1f}s; argument "
        f"{mem.argument_size_in_bytes / 2**20:.3f} MiB, temp "
        f"{mem.temp_size_in_bytes / 2**20:.3f} MiB")
    assert_compiled_kernels(compiled.as_text(), "streaming kernel solve")
    res, wall, comp = timed(run_streaming, wl, cfg, chunk, seed=SEED)
    log(f"    {json.dumps(res)}; run wall {wall:.2f}s, of which compile "
        f"{comp:.2f}s; peak {peak_gib(dev):.3f} GiB")
    check(all(np.isfinite([res["primal"], res["dual"]])),
          "primal and dual are finite")
    check(1 <= res["iterations"] <= max_iters,
          f"iterations {res['iterations']} in [1, {max_iters}]")
    check(res["max_violation"] <= FEAS,
          f"max_violation {res['max_violation']:.3e} <= {FEAS:g}")
    check(res["primal"] <= res["dual"],
          f"primal {res['primal']:.6g} <= dual {res['dual']:.6g}")
    return res


def phase_refresh(dev, n=N_REFRESH, chunk=CHUNK, lookups=LOOKUPS):
    from repro.core import SolverConfig
    from repro.launch.refresh import run_scenario
    from repro.serve import WorkloadSpec

    log(f"[d] refresh: 2 generations at n={n:,}, chunk {chunk}, "
        f"{lookups} lookups, kernels")
    spec = WorkloadSpec(seed=SEED, n=n, k=K, chunk=chunk, q=Q,
                        tightness=TIGHTNESS)
    cfg = SolverConfig(reduce="bucketed", max_iters=REFRESH_MAX_ITERS,
                       checkpoint_every=4, use_kernels=True)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_refresh_") as root:
        out, wall, comp = timed(run_scenario, spec, 2, root, cfg,
                                lookups=lookups)
    log(f"    {json.dumps(out)}; wall {wall:.2f}s, of which compile "
        f"{comp:.2f}s; peak {peak_gib(dev):.3f} GiB")
    check(out["lookups_bitwise"] is True,
          "lookups bitwise equal to materialised decisions")
    check(out["warm_refreshes"] == 1
          and out["warm_iters_total"] < out["cold_iters_total"],
          f"warm iterations {out['warm_iters_total']} < cold "
          f"{out['cold_iters_total']}")
    return out


def phase_reference(dev, n=N_RESIDENT, n_cpu=N_CPU, max_iters=MAX_ITERS):
    import jax
    import numpy as np

    from repro.core import SolverConfig
    from repro.core.instances import shard_key, sparse_instance
    from repro.core.solver import solve

    log(f"[e] plain reference: float64 re-score of the n={n:,} kernel solve")
    cfg = SolverConfig(max_iters=max_iters, use_kernels=True)
    kp, _ = sparse_instance(shard_key(SEED), n, K, Q, tightness=TIGHTNESS)
    res, wall, comp = timed(solve, kp, cfg, q=Q)
    x = np.asarray(res.x)
    p = np.asarray(kp.p).astype(np.float64)
    b = np.asarray(kp.b).astype(np.float64)
    budgets = np.asarray(kp.budgets).astype(np.float64)
    del kp
    primal64 = float(np.where(x, p, 0.0).sum())
    r64 = np.where(x, b, 0.0).sum(axis=0)
    del p, b
    log(f"    solve {wall:.2f}s, of which compile {comp:.2f}s, iterations "
        f"{int(res.iters)}; chip primal "
        f"{float(res.primal)!r} vs float64 {primal64!r}; "
        f"{int(x.sum())} items selected")
    check(rel(res.primal, primal64) <= RTOL_REF,
          f"primal rel diff {rel(res.primal, primal64):.3e} <= {RTOL_REF:g}")
    r_chip = np.asarray(res.r).astype(np.float64)
    r_rel = float(np.max(np.abs(r_chip - r64) / r64))
    check(r_rel <= RTOL_REF, f"consumption r max rel diff {r_rel:.3e} "
          f"<= {RTOL_REF:g}")
    slack = float(np.max(r64 / budgets - 1.0))
    check(slack <= FEAS, f"float64 max(r / budgets - 1) = {slack:.3e} "
          f"<= {FEAS:g} (feasible)")

    log(f"[e] CPU backend vs chip on n={n_cpu:,}")
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        kp_cpu, _ = sparse_instance(shard_key(SEED), n_cpu, K, Q,
                                    tightness=TIGHTNESS)
        ref, cpu_s, cpu_comp = timed(
            solve, kp_cpu, cfg.replace(use_kernels=False), q=Q)
        ref_primal, ref_iters = float(ref.primal), int(ref.iters)
    kp_dev = jax.device_put(kp_cpu, dev)
    log(f"    cpu jnp: iterations {ref_iters}, primal {ref_primal!r} "
        f"({cpu_s:.2f}s, of which compile {cpu_comp:.2f}s)")
    for tag, kernels in (("kernel", True), ("jnp", False)):
        got = solve(kp_dev, cfg.replace(use_kernels=kernels), q=Q)
        log(f"    chip {tag}: iterations {int(got.iters)}, primal "
            f"{float(got.primal)!r}")
        check(int(got.iters) == ref_iters,
              f"chip {tag} iterations {int(got.iters)} == cpu {ref_iters}")
        d = rel(got.primal, ref_primal)
        check(d <= RTOL_SOLVE, f"chip {tag} vs cpu primal rel diff "
              f"{d:.3e} <= {RTOL_SOLVE:g}")
    log(f"    peak {peak_gib(dev):.3f} GiB")


def _hist_rel(got, want):
    """Largest bucket error relative to the largest bucket."""
    import numpy as np

    got = np.asarray(got, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def phase_precision(dev, n=N_PRECISION):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl

    from repro.kernels._util import resolve_interpret
    from repro.kernels.bucket_hist import bucket_hist
    from repro.kernels.scd_fused import scd_finalize_hist, scd_fused_hist

    log(f"[f] precision of the kernels' histogram contractions: {n} rows "
        f"of {PRECISION_V!r}, float64 reference")
    v = np.float32(PRECISION_V)
    edges1 = np.linspace(-1.0, 1.0, 49, dtype=np.float32)
    edges = np.tile(edges1, (K, 1))
    rows = np.arange(n)

    def ref_hist(v1, mass, e):
        """(K, E+1) float64 bucket sums, searchsorted-left binning."""
        out = np.zeros((K, e.shape[-1] + 1))
        for k in range(K):
            idx = np.sum(v1[:, k, None] > e[k][None, :], axis=1)
            np.add.at(out[k], idx, mass[:, k])
        return out

    # bucket_hist (hist_block's VPU bucket sums, shared with
    # scd_fused_hist): v1 spread over the ladder so many buckets fill.
    v1 = (-0.99 + 1.98 * ((rows[:, None] * 7 + np.arange(K)) % 97) / 97
          ).astype(np.float32)
    v2 = np.full((n, K), v, np.float32)
    got = jax.device_put(bucket_hist(jnp.asarray(v1), jnp.asarray(v2),
                                     jnp.asarray(edges)), dev)
    d = _hist_rel(got, ref_hist(v1.astype(np.float64),
                                v2.astype(np.float64), edges))
    check(d <= RTOL_PRECISION,
          f"bucket_hist vs float64: rel {d:.3e} <= {RTOL_PRECISION:g}")

    # One candidate per user at lam = 0, Q = 1: column i % K, where p is
    # 0.9 against 0.1 + 0.01 k elsewhere; its cost b = v is the mass.
    p = np.tile((0.1 + 0.01 * np.arange(K)).astype(np.float32), (n, 1))
    p[rows, rows % K] = np.float32(0.9)
    b = np.full((n, K), v, np.float32)
    sel = np.zeros((n, K), bool)
    sel[rows, rows % K] = True
    p64 = p.astype(np.float64)
    second = np.sort(p64, axis=1)[:, -2:-1]
    cand_v1 = np.where(sel, (p64 - second) / float(v), -1.0)
    mass = np.where(sel, float(v), 0.0)
    lam = jnp.zeros((K,), jnp.float32)
    hist, _ = scd_fused_hist(jnp.asarray(p), jnp.asarray(b), lam,
                             jnp.asarray(edges), Q)
    d = _hist_rel(hist, ref_hist(cand_v1, mass, edges))
    check(d <= RTOL_PRECISION,
          f"scd_fused_hist vs float64: rel {d:.3e} <= {RTOL_PRECISION:g}")

    # The finalize kernel's consumption histogram, binned by the selected
    # adjusted profit (0.9 for every user).
    ch, _, r, *_ = scd_finalize_hist(jnp.asarray(p), jnp.asarray(b), lam,
                                     jnp.asarray(edges1), Q, with_hist=True)
    pt = np.full((n, K), 0.9)
    d = _hist_rel(ch, ref_hist(pt, mass, np.tile(edges1.astype(np.float64),
                                                 (K, 1))))
    check(d <= RTOL_PRECISION, f"scd_finalize_hist consumption histogram "
          f"vs float64: rel {d:.3e} <= {RTOL_PRECISION:g}")
    d = float(np.max(np.abs(np.asarray(r, np.float64) - mass.sum(0))
                     / mass.sum(0)))
    check(d <= RTOL_PRECISION,
          f"scd_finalize_hist r vs float64: rel {d:.3e} <= {RTOL_PRECISION:g}")

    # Control, not a check: the finalize histogram's one-hot contraction
    # at DEFAULT precision, which the kernel pins to HIGHEST. Its reading
    # shows whether the bound above separates bf16 from f32 operands.
    nb = edges1.shape[0] + 1

    def control(oh_ref, c_ref, o_ref):
        @pl.when(pl.program_id(0) == 0)
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)

        o_ref[...] += jnp.einsum("nb,nk->kb", oh_ref[...], c_ref[...])

    oh = np.zeros((n, nb), np.float32)
    oh[:, 3] = 1.0
    ctl = pl.pallas_call(
        control, grid=(n // 512,),
        in_specs=[pl.BlockSpec((512, nb), lambda i: (i, 0)),
                  pl.BlockSpec((512, K), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((K, nb), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((K, nb), jnp.float32),
        interpret=resolve_interpret(None))(jnp.asarray(oh), jnp.asarray(b))
    want = np.zeros((K, nb))
    want[:, 3] = n * float(v)
    log(f"    control: the same contraction at DEFAULT precision reads rel "
        f"{_hist_rel(ctl, want):.3e} (bf16 operands: 9.756e-04; limit "
        f"{RTOL_PRECISION:g})")


def _digest(res):
    import numpy as np

    h = hashlib.sha256()
    for f in ("lam", "iters", "r", "primal", "dual", "tau"):
        h.update(np.asarray(getattr(res, f)).tobytes())
    return h.hexdigest()


def phase_sharded(devs, n_feed=N_SHARDED_FEED, n=N_RESIDENT,
                  chunk=CHUNK, max_iters=MAX_ITERS):
    import jax

    from repro.configs.paper_kp import KPWorkload
    from repro.core import SolverConfig
    from repro.core.instances import shard_key, sparse_instance
    from repro.core.prefetch import solve_streaming_host
    from repro.core.solver import solve
    from repro.data.synth import sparse_host_chunk_source
    from repro.launch.solve import run

    cfg = SolverConfig(max_iters=max_iters, use_kernels=True)
    log(f"[4a] host-fed sharded streaming, slots=4, n={n_feed:,}, "
        f"chunk {chunk}: 4 devices vs 1")
    src = sparse_host_chunk_source(SEED, n_feed, K, chunk, q=Q,
                                   tightness=TIGHTNESS)
    placed = set()
    real_put = jax.device_put

    def spy_put(x, *a, **kw):
        out = real_put(x, *a, **kw)
        if getattr(out, "ndim", 0) == 3:     # one (slots, chunk, K) column
            placed.update(s.device for s in out.addressable_shards)
        return out

    results = {}
    for tag, d in (("4 devices", devs[:4]), ("1 device", devs[:1])):
        mesh = jax.make_mesh((len(d),), ("slots",), devices=d)
        placed.clear()
        jax.device_put = spy_put
        try:
            res, wall, comp = timed(solve_streaming_host, src, cfg, q=Q,
                                    mesh=mesh, slots=4)
        finally:
            jax.device_put = real_put
        results[tag] = res
        log(f"    {tag}: iterations {int(res.iters)}, primal "
            f"{float(res.primal)!r}, dual {float(res.dual)!r}, "
            f"{wall:.2f}s, of which compile {comp:.2f}s; chunk columns on "
            f"{sorted(str(x) for x in placed)}")
        check(placed == set(d), f"{tag}: per-slot chunks land on "
              f"{len(placed)} device(s), the mesh's {len(d)}")
    a, b = results["4 devices"], results["1 device"]
    check(_digest(a) == _digest(b),
          "4-device result bitwise the 1-device result "
          f"(sha256 {_digest(a)[:16]})")

    log(f"[4b] solve_sharded of the n={n:,} resident instance on 4 "
        "devices vs 1")
    wl = KPWorkload("table1", n, K, Q, TIGHTNESS)
    mesh4 = jax.make_mesh((4,), ("users",), devices=devs[:4])
    sh, wall, comp = timed(run, wl, cfg, seed=SEED, mesh=mesh4)
    log(f"    4 devices: {json.dumps(sh)}; wall {wall:.2f}s, of which "
        f"compile {comp:.2f}s")
    kp, _ = sparse_instance(shard_key(SEED), n, K, Q, tightness=TIGHTNESS)
    one = solve(kp, cfg, q=Q)
    log(f"    1 device: iterations {int(one.iters)}, primal "
        f"{float(one.primal)!r}, dual {float(one.dual)!r}")
    check(sh["iterations"] == int(one.iters),
          f"iterations {sh['iterations']} == {int(one.iters)}")
    for f in ("primal", "dual"):
        d = rel(sh[f], getattr(one, f))
        check(d <= RTOL_SOLVE, f"4 vs 1 device {f} rel diff {d:.3e} "
              f"<= {RTOL_SOLVE:g}")
    for i, dev in enumerate(devs[:4]):
        log(f"    device {i} peak {peak_gib(dev):.3f} GiB")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases (a)-(f); 4: the sharded paths only")
    args = ap.parse_args()
    cache = enable_compile_cache()

    import jax

    jax.monitoring.register_event_duration_secs_listener(_count_compile)

    devs = jax.devices()
    dev = devs[0]
    log(f"[a] backend {dev.platform}, device_kind {dev.device_kind!r}, "
        f"{len(devs)} device(s); compile cache {cache}")
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (default backend {dev.platform!r})",
              file=sys.stderr)
        sys.exit(2)
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devs)}", file=sys.stderr)
        sys.exit(2)

    t_all = time.perf_counter()
    if args.chips == 4:
        phase_sharded(devs)
    else:
        for phase in (phase_resident, phase_streaming, phase_refresh,
                      phase_reference, phase_precision):
            t0 = time.perf_counter()
            phase(dev)
            log(f"    phase wall {time.perf_counter() - t0:.2f}s")
    log(f"[done] all phases passed in {time.perf_counter() - t_all:.1f}s")
    # The count is the chips the phases ran on; [a] gives the host's.
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}), flush=True)


if __name__ == "__main__":
    main()
