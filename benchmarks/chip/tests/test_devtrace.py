"""The trace reduction on a hand-made trace and on a recorded TPU trace."""
import json
import pathlib

import pytest

from chipbench import devtrace, harness

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _plane(name, line, events):
    return {"name": name, "lines": [{"name": line, "events": events}]}


def _handmade():
    # Device: a while (0-100) enclosing two kernel calls (10-40, 50-80) and
    # a fusion (85-95); a copy (120-130); idle 100-120 and 130-200.
    dev = _plane("/device:TPU:0", "XLA Ops", [
        ["%while.2 = (f32[10]) while(...)", 0, 100],
        ["%scd_fused_hist.6 = (...) custom-call(...)", 10, 30],
        ["%scd_fused_hist.6 = (...) custom-call(...)", 50, 30],
        ["%fusion.5 = f32[8] fusion(...)", 85, 10],
        ["%copy.3 = f32[8] copy(...)", 120, 10],
    ])
    host = _plane("/host:CPU", "python", [
        ["bench.unit", 0, 200],
        ["ingest.h2d", 100, 20],
        ["refresh.publish", 140, 50],
    ])
    return [dev, host]


def test_handmade_busy_idle_and_self_time():
    red = devtrace.reduce(_handmade(), (0, 200), 1, "python")
    assert red["window_s"] == pytest.approx(200e-9)
    assert red["busy_s"] == pytest.approx(110e-9)
    ops = dict(red["device_ops"])
    assert ops["while.2"] == pytest.approx(30e-9)       # 100 - 60 - 10
    assert ops["scd_fused_hist.6"] == pytest.approx(60e-9)
    assert ops["fusion.5"] == pytest.approx(10e-9)
    assert ops["copy.3"] == pytest.approx(10e-9)
    assert sum(ops.values()) == pytest.approx(red["busy_s"])
    gaps = dict(red["idle_gaps"])
    assert gaps == pytest.approx({"ingest.h2d": 20e-9, "refresh.publish": 70e-9})
    t, calls = devtrace.kernel_ns(red["ops"][0], "scd_fused_hist")
    assert (t, calls) == (60, 2)


def test_window_clips_intervals():
    red = devtrace.reduce(_handmade(), (50, 125), 1, "python")
    assert red["busy_s"] == pytest.approx(55e-9)        # 50-100, 120-125
    assert dict(red["idle_gaps"]) == pytest.approx({"ingest.h2d": 20e-9})


def test_too_few_device_planes_is_an_error():
    with pytest.raises(ValueError):
        devtrace.reduce(_handmade(), (0, 200), 4, "python")


@pytest.mark.parametrize("name, base", [
    ("%scd_fused_hist.6 = (f32[10,50]) custom-call(f32[65536,10])",
     "scd_fused_hist"),
    ("%fusion.5 = f32[1048576] fusion(...)", "fusion"),
    ("copy-start.3", "copy-start"),
    ("scd_finalize_hist.1", "scd_finalize_hist"),
])
def test_op_names(name, base):
    assert devtrace.base_name(name) == base


def _renamed_host_line(planes, name):
    """The planes with the host thread line renamed, as the profiler names
    the main thread after the process that runs it."""
    out = []
    for p in planes:
        if p["name"] == devtrace.HOST_PLANE:
            p = {**p, "lines": [{**line, "name": name} for line in p["lines"]]}
        out.append(p)
    return out


@pytest.mark.parametrize("thread", ["python", "python3", "python3.12"])
def test_recorded_tpu_trace(thread):
    rec = json.loads((DATA / "recorded_trace.json").read_text())
    planes = _renamed_host_line(rec["planes"], thread)
    assert devtrace.host_line(planes, harness.UNIT_ANNOTATION) == thread
    red = harness.reduce_trace(planes, 1)
    assert 0 < red["busy_s"] <= red["window_s"]
    t, calls = devtrace.kernel_ns(red["ops"][0], "scd_fused_hist")
    assert calls == sum(rec["iters"])
    ops = dict(red["device_ops"])
    assert max(ops, key=ops.get).startswith("scd_fused_hist")
    assert t * 1e-9 <= red["busy_s"]
    assert red["idle_gaps"], "a trace of two solves has gaps between them"


def test_trace_without_traced_operation_is_an_error():
    planes = [p for p in _handmade() if p["name"] != devtrace.HOST_PLANE]
    assert devtrace.host_line(planes, harness.UNIT_ANNOTATION) is None
    with pytest.raises(RuntimeError):
        harness.reduce_trace(planes, 1)
