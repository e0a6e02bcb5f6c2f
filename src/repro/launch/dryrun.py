import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS_EXTRA", "")
)
# ^ MUST run before any other import: jax locks the device count on first
# initialisation. 512 fake host devices back both production meshes.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this AOT-compiles the real step function (train_step /
prefill / decode) against ShapeDtypeStruct inputs on the production mesh —
no arrays are ever allocated — then records:

  * memory_analysis()      -> per-device bytes (does it fit 16 GB v5e HBM?)
  * cost_analysis()        -> HLO FLOPs / bytes for the roofline
  * collective bytes       -> parsed from the optimized HLO text
  * (scan correction)      -> a single-block probe program is compiled and
                              its body cost is multiplied by the remaining
                              scan trips, because XLA's cost model counts a
                              while-loop body exactly once.

Also dry-runs the paper's own workload: one SCD iteration of the
billion-user sparse GKP sharded over all 512 devices.

Usage:
    python -m repro.launch.dryrun --arch yi-34b --shape train_4k --multi-pod
    python -m repro.launch.dryrun --all --out reports/dryrun.json
    python -m repro.launch.dryrun --paper-kp billion
"""
import argparse
import dataclasses
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs import registry
from repro.configs.paper_kp import WORKLOADS
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.models import sharding
from repro.optim import OptConfig, OptState
from repro.optim.adamw import init_opt_state


# ---------------------------------------------------------------------------
# collective-bytes parser
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum per-op-kind operand bytes of communication ops in optimized HLO.

    Only the output-shape declaration on the LHS of each collective line is
    counted (per-device payload)."""
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        # e.g.:  %ag = bf16[4,1024]{1,0} all-gather(%x), ...
        m = re.match(r"%?[\w.\-]+\s*=\s*(\([^)]*\)|[^ ]+)\s+([\w\-]+)", s)
        if not m:
            continue
        op = m.group(2)
        for kind in _COLLECTIVES:
            if op.startswith(kind):
                out[kind] += _shape_bytes(m.group(1))
                counts[kind] += 1
    return {"bytes": out, "counts": counts,
            "total_bytes": int(sum(out.values()))}


def _cost_dict(compiled) -> dict:
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    return {
        "flops": float(ca.get("flops", -1.0)),
        "bytes_accessed": float(ca.get("bytes accessed", -1.0)),
    }


def _mem_dict(compiled, n_devices=1) -> dict:
    """Calibrated on this backend (see EXPERIMENTS §Dry-run): argument/
    output sizes are PER-DEVICE; temp is the GLOBAL buffer total, so the
    per-device estimate divides by the mesh size."""
    try:
        ma = compiled.memory_analysis()
        arg = int(getattr(ma, "argument_size_in_bytes", -1))
        out = int(getattr(ma, "output_size_in_bytes", -1))
        temp = int(getattr(ma, "temp_size_in_bytes", -1))
        return {
            "argument_bytes": arg,
            "output_bytes": out,
            "temp_bytes": temp,
            "per_device_bytes_est": int(arg + temp / max(n_devices, 1)),
            "fits_16gb_hbm": bool(arg + temp / max(n_devices, 1) < 16e9),
        }
    except Exception as e:  # CPU backend may not implement it fully
        return {"error": str(e)}


# ---------------------------------------------------------------------------
# cell lowering
# ---------------------------------------------------------------------------

def _abstract(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
        if not isinstance(x, jax.ShapeDtypeStruct) else x, tree)


def lower_cell(arch: str, shape: str, multi_pod: bool, probe: bool = True,
               scan_layers: bool = True, router: str = None,
               fsdp_mode: str = None, batch_override: int = None):
    """Lower+compile one cell. Returns a result dict (see dryrun report)."""
    cfg = registry.get(arch)
    if not scan_layers:
        cfg = cfg.replace(scan_layers=False)
    if router:
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, router=router))
    if fsdp_mode:
        cfg = cfg.replace(fsdp_mode=fsdp_mode)
    cell = M.SHAPES[shape]
    if batch_override:
        cell = dataclasses.replace(cell, global_batch=batch_override)
    skip = M.cell_applicable(cfg, cell)
    if skip:
        return {"arch": arch, "shape": shape,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "skipped", "reason": skip}

    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = M.production_rules(multi_pod, cfg.fsdp_mode)
    t0 = time.time()
    result = {"arch": arch, "shape": shape,
              "mesh": "2x16x16" if multi_pod else "16x16", "status": "ok",
              "fsdp_mode": cfg.fsdp_mode, "router": cfg.moe.router or None,
              "global_batch": cell.global_batch}
    with jax.sharding.set_mesh(mesh):
        sharding.set_rules(rules)
        try:
            pshape = jax.eval_shape(
                lambda k: M.init(cfg, k), jax.ShapeDtypeStruct((2,), jnp.uint32))
            pspecs, ospecs, bspecs = M.shardings(cfg, cell, multi_pod)
            inputs = _abstract(M.input_specs(cfg, cell))

            if cell.kind == "train":
                opt_cfg = OptConfig()
                oshape = jax.eval_shape(lambda p: init_opt_state(p, opt_cfg), pshape)
                fn = M.make_train_step(cfg, opt_cfg)
                lowered = jax.jit(
                    fn,
                    in_shardings=(pspecs, ospecs, bspecs),
                    out_shardings=(pspecs, ospecs, None),
                    donate_argnums=(0, 1),
                ).lower(pshape, oshape, inputs)
            elif cell.kind == "prefill":
                fn = M.make_prefill_step(cfg)
                lowered = jax.jit(
                    fn, in_shardings=(pspecs, bspecs), out_shardings=None,
                ).lower(pshape, inputs)
            else:
                fn = M.make_decode_step(cfg)
                cspecs = bspecs["caches"]
                lowered = jax.jit(
                    fn,
                    in_shardings=(pspecs, cspecs, bspecs["token"], bspecs["pos"]),
                    out_shardings=(None, cspecs),
                    donate_argnums=(1,),
                ).lower(pshape, inputs["caches"], inputs["token"], inputs["pos"])

            compiled = lowered.compile()
            result["compile_s"] = round(time.time() - t0, 1)
            result["cost"] = _cost_dict(compiled)
            result["memory"] = _mem_dict(compiled, mesh.size)
            hlo = compiled.as_text()
            result["collectives"] = collective_bytes(hlo)
            import math
            result["n_params"] = int(sum(
                math.prod(l.shape) for l in jax.tree.leaves(pshape)))

            # scan-body probe: cost_analysis counts while bodies once.
            if probe and cfg.scan_layers:
                result["scan_probe"] = _probe_block(cfg, cell, mesh, multi_pod)
        except Exception as e:
            result["status"] = "error"
            result["error"] = f"{type(e).__name__}: {e}"
            result["traceback"] = traceback.format_exc()[-2000:]
        finally:
            sharding.set_rules(None)
    return result


def _probe_block(cfg, cell, mesh, multi_pod):
    """Compile ONE scan period as its own program to correct cost_analysis
    (XLA counts a while body once; the full model runs n_periods trips)."""
    from repro.models import blocks as B

    b = cell.global_batch
    if cell.kind in ("train", "prefill"):
        s = M._text_len(cfg, cell.seq_len)
        x_sds = jax.ShapeDtypeStruct((b, s, cfg.d_model), cfg.dtype)

        def one_period(slot_params, x):
            positions = jnp.arange(x.shape[1])
            for i, (slot, ffn) in enumerate(zip(cfg.pattern, cfg.ffn_pattern)):
                x = B.block_apply(slot_params[i], cfg, x, positions, slot, ffn)
            return x

        if cell.kind == "train":
            def probe_fn(slot_params, x):
                def loss(sp, xx):
                    return jnp.sum(one_period(sp, xx).astype(jnp.float32) ** 2)
                g = jax.grad(loss)(slot_params, x)
                return g
        else:
            probe_fn = one_period

        pshape = jax.eval_shape(
            lambda k: [jax.vmap(lambda kk: B.init_block(kk, cfg, slot, ffn))(
                jax.random.split(k, 1))
                for slot, ffn in zip(cfg.pattern, cfg.ffn_pattern)],
            jax.ShapeDtypeStruct((2,), jnp.uint32))
        pshape = jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype), pshape)
        pspecs = M.param_specs(cfg, {"slots": pshape})["slots"]
        rules = M.production_rules(multi_pod, cfg.fsdp_mode)
        x_spec = M.sanitize(
            P(rules["batch"], rules["seq"], None), x_sds.shape)
        lowered = jax.jit(
            probe_fn,
            in_shardings=(pspecs, x_spec),
        ).lower(pshape, x_sds)
    else:
        # decode probe: one period of block_decode
        def probe_fn(slot_params, slot_caches, x, pos):
            new = []
            for i, (slot, ffn) in enumerate(zip(cfg.pattern, cfg.ffn_pattern)):
                x, nc = B.block_decode(slot_params[i], cfg, x, slot_caches[i],
                                       pos, slot, ffn)
                new.append(nc)
            return x, new

        pshape = jax.eval_shape(
            lambda k: [B.init_block(k, cfg, slot, ffn)
                       for slot, ffn in zip(cfg.pattern, cfg.ffn_pattern)],
            jax.ShapeDtypeStruct((2,), jnp.uint32))
        cshape = jax.eval_shape(
            lambda: [B.init_block_cache(cfg, slot, b, cell.seq_len, cfg.dtype)
                     for slot in cfg.pattern])
        pspecs = M.param_specs(cfg, {"slots": jax.tree.map(
            lambda l: jax.ShapeDtypeStruct((1, *l.shape), l.dtype), pshape)})["slots"]
        pspecs = jax.tree.map(lambda s: P(*s[1:]), pspecs,
                              is_leaf=lambda s: isinstance(s, P))
        cspecs_full = M.cache_specs(cfg, cell, {"slots": jax.tree.map(
            lambda l: jax.ShapeDtypeStruct((1, *l.shape), l.dtype), cshape)},
            multi_pod)["slots"]
        cspecs = jax.tree.map(lambda s: P(*s[1:]), cspecs_full,
                              is_leaf=lambda s: isinstance(s, P))
        x_sds = jax.ShapeDtypeStruct((b, 1, cfg.d_model), cfg.dtype)
        rules = M.production_rules(multi_pod, cfg.fsdp_mode)
        x_spec = M.sanitize(P(rules["batch"], None, None), x_sds.shape)
        lowered = jax.jit(
            probe_fn,
            in_shardings=(pspecs, cspecs, x_spec, P()),
        ).lower(pshape, cshape, x_sds, jax.ShapeDtypeStruct((), jnp.int32))

    compiled = lowered.compile()
    out = _cost_dict(compiled)
    out["collectives"] = collective_bytes(compiled.as_text())
    out["n_periods"] = cfg.n_periods
    return out


# ---------------------------------------------------------------------------
# paper workload dry-run
# ---------------------------------------------------------------------------

def lower_paper_kp(workload: str, multi_pod: bool = True,
                   reduce: str = "bucketed", algo: str = "scd",
                   max_iters: int = 2, chunk_size: int = None,
                   streaming: bool = False, stream_finalize: str = "fused"):
    """One jitted solve of the paper-scale sparse GKP sharded over every
    device of the production mesh. ``reduce``/``algo`` select the §Perf
    A/B variants (exact gather vs §5.2 bucketed psum; DD vs SCD).

    ``chunk_size`` chunks the per-iteration map (core/solver.py);
    ``streaming`` lowers the out-of-core driver (core/chunked.py) whose
    chunks are synthesized inside the program — its memory_analysis shows
    argument + temp bytes independent of N, the headline of the chunked
    solve path (compare against the resident lowering, whose argument
    bytes are 8·N·K). ``stream_finalize`` picks the single-pass fused
    finalize or the legacy three-pass one (DESIGN.md §5c), so the two
    lowered programs' cost/collective profiles can be diffed."""
    from repro.core import SolverConfig, SparseKP
    from repro.core.solver import _solve_entry
    import functools

    wl = WORKLOADS[workload]
    mesh = make_production_mesh(multi_pod=multi_pod)
    axes = tuple(mesh.axis_names)
    # round to a mesh multiple (shard_map needs exact divisibility)
    n = (wl.n_users // mesh.size) * mesh.size
    k = wl.k
    cfg = SolverConfig(algo=algo, reduce=reduce, max_iters=max_iters,
                       postprocess=True, chunk_size=chunk_size,
                       stream_finalize=stream_finalize)
    t0 = time.time()
    if streaming:
        if reduce != "bucketed":
            raise ValueError("--streaming lowers the bucketed-reduce "
                             "driver only (solve_streaming cannot stream "
                             "the exact reduce)")
        from repro.core.chunked import stream_solve_fn
        from repro.data.synth import sparse_chunk_source
        chunk = chunk_size = chunk_size or 65536
        src = sparse_chunk_source(0, n, k, chunk, q=wl.q,
                                  tightness=wl.tightness)
        cfg = cfg.replace(chunk_size=None)
        # The exact program users run: the shared streaming entry builder.
        fn = stream_solve_fn(src, cfg, wl.q, mesh=mesh)
        lowered = fn.lower(
            jax.ShapeDtypeStruct((k,), jnp.float32),
            jax.ShapeDtypeStruct((k,), jnp.float32))
    else:
        kp = SparseKP(
            p=jax.ShapeDtypeStruct((n, k), jnp.float32),
            b=jax.ShapeDtypeStruct((n, k), jnp.float32),
            budgets=jax.ShapeDtypeStruct((k,), jnp.float32),
        )
        user = P(axes)
        # out_specs: lam/iters/r/primal/dual replicated; x user-sharded
        from repro.core.solver import SolveResult
        fn = shard_map(
            functools.partial(_solve_entry, q=wl.q, cfg=cfg, axis=axes),
            mesh=mesh,
            in_specs=(SparseKP(p=user, b=user, budgets=P()), P()),
            out_specs=SolveResult(lam=P(), x=P(axes, None), iters=P(), r=P(),
                                  primal=P(), dual=P(), history=None),
            check_vma=False,
        )
        lowered = jax.jit(fn).lower(kp, jax.ShapeDtypeStruct((k,), jnp.float32))
    compiled = lowered.compile()
    res = {
        "workload": workload, "n_users": n, "k": k,
        "algo": algo, "reduce": reduce, "iters": max_iters,
        "chunk_size": chunk_size, "streaming": streaming,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "status": "ok",
        "compile_s": round(time.time() - t0, 1),
        "cost": _cost_dict(compiled),
        "memory": _mem_dict(compiled, mesh.size),
        "collectives": collective_bytes(compiled.as_text()),
    }
    return res


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(M.SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--paper-kp", choices=list(WORKLOADS))
    ap.add_argument("--reduce", choices=["bucketed", "exact"], default="bucketed")
    ap.add_argument("--algo", choices=["scd", "dd"], default="scd")
    ap.add_argument("--chunk-size", type=int, default=None,
                    help="paper-kp: chunk the per-iteration map "
                         "(core/solver.py chunked mode)")
    ap.add_argument("--streaming", action="store_true",
                    help="paper-kp: lower the out-of-core driver "
                         "(core/chunked.py) — argument/temp bytes flat in N")
    ap.add_argument("--stream-finalize", choices=["fused", "legacy"],
                    default="fused",
                    help="paper-kp --streaming: fused single-pass finalize "
                         "vs the legacy three-pass one (DESIGN.md §5c)")
    ap.add_argument("--no-probe", action="store_true")
    ap.add_argument("--unrolled", action="store_true",
                    help="disable scan-over-layers (exact HLO flops)")
    ap.add_argument("--router", choices=["topk", "scd"])
    ap.add_argument("--fsdp", choices=["full", "zero1", "none", "fsdp_only", "dp_full"], default=None)
    ap.add_argument("--batch", type=int, default=None,
                    help="override the cell's global batch")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    results = []
    if args.paper_kp:
        r = lower_paper_kp(args.paper_kp, multi_pod=True,
                           reduce=args.reduce, algo=args.algo,
                           chunk_size=args.chunk_size,
                           streaming=args.streaming,
                           stream_finalize=args.stream_finalize)
        print(json.dumps(r, indent=2))
        results.append(r)
    elif args.all:
        for arch in registry.names():
            for shape in M.SHAPES:
                for mp in ([False, True] if args.both_meshes else [args.multi_pod]):
                    r = lower_cell(arch, shape, mp, probe=not args.no_probe,
                                   scan_layers=not args.unrolled,
                                   router=args.router)
                    print(json.dumps({k: v for k, v in r.items()
                                      if k != "traceback"}))
                    results.append(r)
    else:
        meshes = [False, True] if args.both_meshes else [args.multi_pod]
        for mp in meshes:
            r = lower_cell(args.arch, args.shape, mp,
                           probe=not args.no_probe,
                           scan_layers=not args.unrolled,
                           router=args.router, fsdp_mode=args.fsdp,
                           batch_override=args.batch)
            print(json.dumps({k: v for k, v in r.items() if k != "traceback"},
                             indent=2))
            results.append(r)

    if args.out:
        import pathlib
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    ok = all(r["status"] in ("ok", "skipped") for r in results)
    raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
