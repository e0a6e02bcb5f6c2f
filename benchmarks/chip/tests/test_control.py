"""The lower-precision control fails the limits that sound runs meet.

The control puts the plain reference, computed in bfloat16 (the
precision below the configurations' float32), in the program's place at
the program's multipliers. Here at a small size on the CPU; the readings
that set the limits come from the chip at the cells' own sizes.
"""
import ml_dtypes
import pytest

import control
from chipbench import harness


@pytest.fixture(autouse=True)
def no_cache(monkeypatch):
    monkeypatch.setattr(harness, "_compile_cache", lambda: None)


@pytest.mark.parametrize("name, overrides, ops", [
    ("table1-share.cold", {"n_users": 65536}, 1),
    ("table1-feed.refresh", {"n_users": 63000, "chunk": 4096}, 2)])
def test_control_fails_where_sound_runs_pass(name, overrides, ops):
    rec = control.readings(name, 2**31 + 5, ops, require_tpu=False,
                           overrides=overrides)
    assert rec["sound_correct"], rec["sound"]
    assert {"x_mismatch", "r_gap", "primal_gap"} <= set(rec["control_fails"])
    assert ml_dtypes.bfloat16  # the control's precision is importable
