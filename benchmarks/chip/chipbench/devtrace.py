"""Reduction of a profiler trace to device busy time, op time and gaps.

A trace is read into plain data: a list of planes, each
``{"name": str, "lines": [{"name": str, "events": [[name, start_ns,
dur_ns], ...]}]}`` (``load`` does that from the profiler's
``.xplane.pb``; tests build the same shape by hand or from a recorded
file). Device planes are ``/device:TPU:<i>``; on each, the ``XLA Ops``
line holds the operations that ran, nested where control flow (a
``while``) encloses its body's operations. Host annotations sit on the
host plane's thread lines, on the same clock. A thread's line is named
after the thread, and the main thread after the process (``python``,
``python3``, ...), so the thread that drives the program is found by the
annotation it opens around each operation, never by its name.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
_OP_NAME = re.compile(r"^%?([^\s=]+)")


def load(log_dir: str) -> list:
    """Planes of the newest ``.xplane.pb`` under a profiler log dir."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    planes = []
    for plane in pd.planes:
        lines = [{"name": line.name,
                  "events": [[e.name, float(e.start_ns), float(e.duration_ns)]
                             for e in line.events]}
                 for line in plane.lines]
        planes.append({"name": plane.name, "lines": lines})
    return planes


def op_name(event_name: str) -> str:
    """``%scd_fused_hist.6 = (...) custom-call(...)`` -> ``scd_fused_hist.6``."""
    m = _OP_NAME.match(event_name)
    return m.group(1) if m else event_name


def base_name(name: str) -> str:
    """An op name without its numeric suffix: ``fusion.5`` -> ``fusion``."""
    return re.sub(r"(\.\d+)+$", "", op_name(name))


def device_planes(planes: list) -> list:
    return sorted((p for p in planes if p["name"].startswith(DEVICE_PREFIX)),
                  key=lambda p: p["name"])


def ops(plane: dict) -> list:
    """The plane's XLA operations as (name, start_ns, end_ns), by start."""
    for line in plane["lines"]:
        if line["name"] == OPS_LINE:
            return sorted((op_name(n), s, s + d) for n, s, d in line["events"])
    return []


def clip(intervals, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in intervals
            if e > lo and s < hi]


def union(intervals) -> list:
    """Merged [start, end) spans of (name, start, end) intervals."""
    out = []
    for _, s, e in sorted(intervals, key=lambda t: t[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(intervals) -> dict:
    """Per-op time with enclosed operations' time taken out (nesting)."""
    out = {}
    stack = []          # [name, start, end, enclosed time]

    def close(entry):
        name, s, e, child = entry
        out[name] = out.get(name, 0.0) + (e - s) - child
        if stack:
            stack[-1][3] += e - s

    for name, s, e in sorted(intervals, key=lambda t: (t[1], -t[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        stack.append([name, s, e, 0.0])
    while stack:
        close(stack.pop())
    return out


def kernel_ns(intervals, kernel: str) -> tuple[float, int]:
    """(total time, calls) of the ops whose base name is ``kernel``."""
    hits = [(s, e) for n, s, e in intervals if base_name(n) == kernel]
    return sum(e - s for s, e in hits), len(hits)


def host_line(planes: list, marker: str):
    """Name of the host thread line holding ``marker`` events, or None."""
    for p in planes:
        if p["name"] != HOST_PLANE:
            continue
        for line in p["lines"]:
            if any(ev[0] == marker for ev in line["events"]):
                return line["name"]
    return None


def host_events(planes: list, line_name: str) -> list:
    """Events of one host thread's line as (name, start_ns, end_ns)."""
    return [(n, s, s + d) for p in planes if p["name"] == HOST_PLANE
            for line in p["lines"] if line["name"] == line_name
            for n, s, d in line["events"]]


def gaps(busy: list, lo: float, hi: float) -> list:
    """Idle [start, end) spans of [lo, hi) between merged busy spans."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def label(gap, spans) -> str:
    """The innermost host span open at the gap's midpoint, or ``idle``."""
    mid = (gap[0] + gap[1]) / 2
    best = None
    for n, s, e in spans:
        if s <= mid < e and (best is None or e - s < best[2] - best[1]):
            best = (n, s, e)
    return best[0] if best else "idle"


def reduce(planes: list, window: tuple, n_devices: int, line_name: str,
           top: int = 10) -> dict:
    """Busy seconds per device, per-op self time and labelled idle gaps.

    ``window`` is [start_ns, end_ns) on the trace's clock; only the first
    ``n_devices`` device planes (the chips the cell uses) count. Idle gaps
    are labelled by the spans of the host thread line ``line_name``.
    """
    lo, hi = window
    planes_d = device_planes(planes)[:n_devices]
    if len(planes_d) < n_devices:
        raise ValueError(f"trace has {len(planes_d)} device planes, "
                         f"{n_devices} expected")
    host = host_events(planes, line_name)
    busy, op_self, kernels, gap_lbl = [], {}, [], {}
    for plane in planes_d:
        iv = clip(ops(plane), lo, hi)
        kernels.append(iv)
        merged = union(iv)
        busy.append(sum(e - s for s, e in merged))
        for n, t in self_times(iv).items():
            op_self[n] = op_self.get(n, 0.0) + t
        for g in gaps(merged, lo, hi):
            key = label(g, host)
            gap_lbl[key] = gap_lbl.get(key, 0.0) + (g[1] - g[0])
    nd = len(planes_d)
    ops_top = sorted(op_self.items(), key=lambda kv: -kv[1])[:top]
    gaps_top = sorted(gap_lbl.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / nd * 1e-9,
        "device_ops": [[n, t / nd * 1e-9] for n, t in ops_top],
        "idle_gaps": [[n, t / nd * 1e-9] for n, t in gaps_top],
        "ops": kernels,
    }
