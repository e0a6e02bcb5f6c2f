"""The benchmark's own checks, on the CPU at small sizes.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q

The repository's own suite does not collect them (its ``testpaths`` is
``tests``).
"""
import os
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[2] / "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# Four host devices, so the four-chip cell's mesh exists on the CPU.
os.environ["XLA_FLAGS"] = " ".join(
    [f for f in os.environ.get("XLA_FLAGS", "").split()
     if not f.startswith("--xla_force_host_platform_device_count")]
    + ["--xla_force_host_platform_device_count=4"])
