#!/usr/bin/env python3
"""Readings that set a cell's limits: sound runs and the lower-precision control.

    python3 benchmarks/chip/control.py --workload table1-share.cold \
        --seeds 11,12,13 --ops 1 --out readings.jsonl

For each seed, in one process: the cell's set-up and ``--ops``
operations of its traffic (no timed window), then the comparison the
benchmark's runs make (the sound reading), then the same comparison with
the plain reference computed in bfloat16 put in the program's place (the
control: the nearest precision below the float32 the configuration
states), and the ``fixed_point`` reading of a multiplier step that
leaves its state unchanged (the control computes no multipliers of its
own). One JSON line per seed. The benchmark's own runs never run these.
"""
import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from chipbench import harness, traffic  # noqa: E402


def readings(name, seed, ops, require_tpu=True, overrides=None):
    c, devices, system, _, _ = harness.open_cell(
        name, seed, require_tpu=require_tpu, overrides=overrides)
    import ml_dtypes

    t0 = time.perf_counter()
    try:
        system.setup()
        setup_ops, window_ops = traffic.plan(c["traffic"])
        for op in setup_ops:
            system.serve(op)
        system.begin_window()
        records = [system.serve(op) for op, _ in zip(window_ops, range(ops))]
        t1 = time.perf_counter()
        checks, failed = system.check(records)
        t2 = time.perf_counter()
        ctl = system.control(ml_dtypes.bfloat16)
        fault = system.fault_unchanged()
        t3 = time.perf_counter()
    finally:
        system.close()
    limits = c["limits"]
    return {
        "workload": name, "seed": seed,
        "iters": [r["iters"] for r in records],
        "seconds": [r["seconds"] for r in records],
        "sound": {k: v["value"] for k, v in checks.items()},
        "sound_correct": all(v["ok"] for v in checks.values()),
        "control": ctl,
        "control_fails": sorted(k for k, v in ctl.items()
                                if k in limits and v > limits[k]),
        "fault_unchanged_state": {"fixed_point": fault},
        "timing_s": {"setup_and_ops": t1 - t0, "check": t2 - t1,
                     "control": t3 - t2},
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--ops", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    for s in args.seeds.split(","):
        rec = readings(args.workload, int(s), args.ops)
        line = json.dumps(rec)
        print(line, flush=True)
        with out.open("a") as fh:
            fh.write(line + "\n")


if __name__ == "__main__":
    main()
