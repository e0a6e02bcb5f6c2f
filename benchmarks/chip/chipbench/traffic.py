"""The one traffic generator: a mix's data file in, a stream of operations out.

A mix is ``traffic/<name>.json``, parameters only. Its ``pattern`` names
``patterns/<pattern>.py``, found by name, whose ``plan(mix)`` turns the
parameters into the run's operations: a list run in set-up and an
endless iterator the window draws from. A new mix with a known pattern
adds one data file; a new arrival shape adds one pattern file.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib

PATTERNS = pathlib.Path(__file__).resolve().parents[1] / "patterns"


def load(path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def plan(mix: dict):
    """(set-up operations, endless iterator of the window's operations)."""
    path = PATTERNS / f"{mix['pattern']}.py"
    if not path.is_file():
        raise ValueError(f"unknown traffic pattern {mix['pattern']!r}")
    spec = importlib.util.spec_from_file_location(
        "chip_pattern_" + mix["pattern"], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.plan(mix)
