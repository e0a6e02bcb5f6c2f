"""The harness: runs one cell named in BENCHMARK.json, by data alone.

A cell names a configuration and a traffic mix. The harness finds every
piece by name: ``configs/<config>.json`` (whose ``system`` names
``systems/<system>.py``, the adapter to the system under test),
``traffic/<traffic>.json`` (whose ``pattern`` names
``patterns/<pattern>.py``, read by ``chipbench.traffic``),
``limits/<cell>.json`` (the limits of the comparison that decides
``correct``) and ``metrics/<metric>.py`` (one reader per metric, end to
end and per layer). Adding a cell adds files; no file here changes.

A run: set-up (data made on the device from the seed, programs compiled
or read from the persistent cache, the mix's set-up operations), then
the window of ``--seconds`` (whole operations, each timed by its adapter
to the completion of its result; the last one may end past the window),
then the peak memory reading, the release of the program's state and
the comparison with the plain reference. With ``--trace 1`` the first
operations of the window run under the profiler and the per-layer
metrics are read from that trace and from the program's host spans.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time
import types

import numpy as np

from . import devtrace, traffic
from .spans import Collector

HERE = pathlib.Path(__file__).resolve().parents[1]        # benchmarks/chip
ROOT = HERE.parents[1]                                    # the checkout
STATE = ROOT / ".bench_state"   # what the system under test writes
TRACE_UNITS = 2          # operations of the window run under the profiler
UNIT_ANNOTATION = "bench.unit"


class SetupError(RuntimeError):
    """The run cannot start: no chip, too few chips, or no program."""


def _load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: pathlib.Path):
    return json.loads(path.read_text())


def cell(bench: dict, name: str) -> dict:
    """Everything the run needs about one cell, found by name."""
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise SetupError(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    config = _json(ROOT / cfg_entry["file"])
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in e2e_names)]
    return {
        "workload": wl, "config": config,
        "traffic": traffic.load(HERE / "traffic" / f"{wl['traffic']}.json"),
        "limits": _json(HERE / "limits" / f"{name}.json"),
        "system": HERE / "systems" / f"{config['system']}.py",
        "end_to_end": e2e, "per_layer": layer,
    }


def _check_program():
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise SetupError(f"the program is not in this checkout ({src})")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def _compile_cache():
    """JAX's persistent cache at a fixed directory inside the checkout."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from repro.launch.env import enable_compile_cache

    return enable_compile_cache()


def devices_for(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SetupError(f"no TPU: JAX's default backend is "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise SetupError(f"the cell asks for {chips} chips, JAX finds "
                         f"{len(devs)}")
    return devs[:chips]


class _CompileCount:
    def __init__(self):
        self.n = 0

    def __call__(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def _profiler_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def _log(msg):
    print(msg, file=sys.stderr, flush=True)


def open_cell(name: str, seed: int, *, require_tpu: bool = True,
              overrides=None):
    """(cell, devices, system, collector, compile counter) of one run."""
    c = cell(_json(ROOT / "BENCHMARK.json"), name)
    if overrides:
        c["config"] = {**c["config"], **overrides}
    _check_program()
    cache = _compile_cache()
    import jax

    chips = c["workload"]["chips"]
    devices = devices_for(chips, require_tpu)
    compiles = _CompileCount()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    _log(f"[bench] {name} seed {seed}: {devices[0].device_kind} x{chips}, "
         f"compile cache {cache}")
    collector = Collector()
    STATE.mkdir(exist_ok=True)
    ctx = types.SimpleNamespace(
        config=c["config"], traffic=c["traffic"], limits=c["limits"],
        seed=seed, devices=devices, collector=collector, log=_log,
        rng=np.random.default_rng([seed, 1]), state_dir=STATE)
    adapter = _load_module(c["system"], f"chip_system_{c['config']['system']}")
    return c, devices, adapter.build(ctx), collector, compiles


def run(name: str, seed: int, seconds: float, trace: bool, *,
        t0: float, require_tpu: bool = True, overrides=None) -> dict:
    """One run of one cell; returns the result object."""
    c, devices, system, collector, compiles = open_cell(
        name, seed, require_tpu=require_tpu, overrides=overrides)
    import jax

    chips = len(devices)
    try:
        system.setup()
        setup_ops, window_ops = traffic.plan(c["traffic"])
        for op in setup_ops:
            system.serve(op)
        setup_s = time.perf_counter() - t0
        _log(f"[bench] set-up {setup_s:.3f} s")

        records, trace_dir, planes = [], None, None
        system.begin_window()
        n0 = compiles.n
        tracing = trace
        if tracing:
            trace_dir = tempfile.mkdtemp(prefix="chip_trace_")
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=_profiler_options())
            collector.annotate = True
        tw = time.perf_counter()
        for i, op in enumerate(window_ops):
            mark = collector.mark()
            if tracing:
                with jax.profiler.TraceAnnotation(UNIT_ANNOTATION):
                    rec = system.serve(op)
            else:
                rec = system.serve(op)
            rec["spans"] = (mark, collector.mark())
            rec["traced"] = tracing
            records.append(rec)
            if tracing and i + 1 >= TRACE_UNITS:
                jax.profiler.stop_trace()
                collector.annotate = tracing = False
            if time.perf_counter() - tw >= seconds:
                break
        if tracing:
            jax.profiler.stop_trace()
            collector.annotate = False
        window_s = time.perf_counter() - tw
        window_compiles = compiles.n - n0
        if trace:
            planes = devtrace.load(trace_dir)
            shutil.rmtree(trace_dir, ignore_errors=True)

        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
        checks, failed = system.check(records)
    finally:
        system.close()

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": chips, "memory_peak_bytes": int(peak)}
    result = {"correct": all(ch["ok"] for ch in checks.values()),
              "attempted": len(records), "failed": int(failed)}
    if trace:
        traced = [r for r in records if r["traced"]]
        red = reduce_trace(planes, chips)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        mctx = types.SimpleNamespace(
            units=traced, spans=collector, trace=red, config=c["config"],
            peak=peak_of(devices[0].device_kind), devtrace=devtrace)
        result["metrics"] = read_metrics(c["per_layer"], mctx)
        result["device"] = device
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    else:
        mctx = types.SimpleNamespace(units=records, window_s=window_s,
                                     setup_s=setup_s, config=c["config"])
        result["metrics"] = read_metrics(c["end_to_end"], mctx)
        result["device"] = device
    result["window"] = {"seconds": window_s, "compiles": window_compiles}
    result["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                        for k, v in checks.items()}
    _log(f"[bench] window {window_s:.3f} s, {len(records)} operations, "
         f"{window_compiles} compiles inside it")
    for k, v in checks.items():
        _log(f"check {k}: {v['value']!r} <= {v['limit']!r} "
             f"{'ok' if v['ok'] else 'FAIL'}")
    return result


def reduce_trace(planes, chips):
    line = devtrace.host_line(planes, UNIT_ANNOTATION)
    if line is None:
        raise RuntimeError("the trace holds no traced operation")
    spans = [(s, e) for n, s, e in devtrace.host_events(planes, line)
             if n == UNIT_ANNOTATION]
    window = (min(s for s, _ in spans), max(e for _, e in spans))
    return devtrace.reduce(planes, window, chips, line)


def peak_of(kind: str) -> dict:
    table = _json(HERE / "chipbench" / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peak figures for device kind {kind!r}")
    return table[kind]


def read_metrics(entries, mctx) -> dict:
    """Each metric's value from its reader ``metrics/<name>.py``; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in entries:
        mod = _load_module(HERE / "metrics" / f"{m['name']}.py",
                           "chip_metric_" + m["name"].replace(".", "_"))
        value = mod.read(mctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None, t0=None) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(description="Run one chip benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace), t0=t0)
    except SetupError as e:
        _log(f"[bench] cannot run: {e}")
        return 3
    print(json.dumps(result), flush=True)
    return 0
