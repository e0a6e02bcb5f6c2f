#!/usr/bin/env python3
"""Chip benchmark entry point: one cell of BENCHMARK.json, one run.

    python3 benchmarks/chip/run.py --workload table1-share.cold \
        --seed 12345 --seconds 30 --trace 0

Prints the cell's end-to-end metrics (``--trace 0``) or its per-layer
metrics read from a device trace (``--trace 1``) as the last line of
standard output, one JSON object, after checking the timed path's
answers against the plain reference. Exits non-zero, with no result,
where JAX finds no TPU or fewer chips than the cell asks for.
"""
import pathlib
import sys
import time

T0 = time.perf_counter()
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t0=T0))
