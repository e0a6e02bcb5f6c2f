"""The comparison that decides ``correct``: program answers vs the reference.

Each compared number has a limit in ``limits/<cell>.json``; a number
over its limit makes the run not correct. The numbers:

* ``x_mismatch``: share of users whose decision row differs from the
  reference's at the program's multipliers (every user of the compared
  solve or published generation);
* ``lookup_mismatch``: share of looked-up users whose row, as the
  decision service answers it, differs from the published export's;
* ``r_gap``: largest |r - r_ref| / budget over the knapsacks;
* ``primal_gap``, ``dual_gap``: relative gaps of the objective values;
* ``tau_gap``: ladder buckets between the published removal threshold
  and the reference's (host-fed cells);
* ``fixed_point``: max_k |T(lam)_k - lam_k| / (1 + max lam), T the exact
  SCD map (Algorithms 4 and 5) at the published multipliers: how far
  the returned multipliers are from a converged solve's;
* ``overuse``: max_k r_k / budget_k - 1 of the decisions the program
  published, in float64: the feasibility guarantee;
* ``live_lag``: generations between the last refresh and what a reader
  finds through the live pointer.
* ``unsynced``: refreshes of the window that returned without fsyncing
  their record, ``LIVE.json`` or the directories of either: the
  durability guarantee.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

from . import reference


class Data(NamedTuple):
    p: np.ndarray          # (n, K) float32, as the program got it
    b: np.ndarray
    budgets: np.ndarray    # (K,) float32
    q: int


def _rel(a, b):
    return abs(float(a) - float(b)) / max(abs(float(b)), 1e-30)


def _rows(ans: reference.Answer, k: int) -> np.ndarray:
    """Reference decisions of every user as (n, K) bool."""
    out = np.zeros((len(ans.choice), k), bool)
    for j in range(ans.choice.shape[1]):
        c = ans.choice[:, j].astype(np.int64)
        m = (c >= 0) & ans.kept
        out[np.nonzero(m)[0], c[m]] = True
    return out


def _x_mismatch(x, ans) -> float:
    want = _rows(ans, x.shape[1])
    return float(np.mean(np.any(x != want, axis=1)))


def consumption(b, x) -> np.ndarray:
    """(K,) float64 consumption of decisions x, in blocks."""
    r = np.zeros(b.shape[1])
    for lo in range(0, b.shape[0], reference.BLOCK):
        hi = lo + reference.BLOCK
        r += np.where(x[lo:hi], b[lo:hi].astype(np.float64), 0.0).sum(axis=0)
    return r


def totals(prog: dict, ans: reference.Answer, budgets) -> dict:
    """The gaps of a program answer's totals against the reference's."""
    budgets = np.asarray(budgets, np.float64)
    return {"r_gap": float(np.max(np.abs(np.asarray(prog["r"], np.float64)
                                         - ans.r) / budgets)),
            "primal_gap": _rel(prog["primal"], ans.primal),
            "dual_gap": _rel(prog["dual"], ans.dual)}


def judge(per_unit: list, limits: dict):
    """Fold per-unit numbers into checks and a count of failed units."""
    checks, failed = {}, 0
    for nums in per_unit:
        bad = False
        for k, v in nums.items():
            ok = v <= limits[k]
            bad |= not ok
            c = checks.setdefault(k, {"value": v, "limit": limits[k]})
            c["value"] = max(c["value"], v)
        failed += bad
    for c in checks.values():
        c["ok"] = bool(c["value"] <= c["limit"])
    return checks, failed


def resident(data: Data, window: list, idx: int, x: np.ndarray,
             limits: dict, log=print):
    """Cold resident solves: every solve's totals and multipliers, and the
    sampled solve's full decisions, against the exact-removal reference."""
    groups = {}
    for i, rec in enumerate(window):
        groups.setdefault(rec["lam"].tobytes(), []).append(i)
    keys = sorted(groups, key=lambda kk: idx not in groups[kk])[:3]
    per_unit = []
    for key in keys:
        lam = window[groups[key][0]]["lam"]
        ans = reference.answer(data.p, data.b, data.budgets, lam, data.q,
                               "exact")
        fp = reference.fixed_point(data.p, data.b, data.budgets, lam, data.q)
        log(f"[check] lam {np.array2string(lam, precision=6)}: "
            f"{len(groups[key])} solve(s); reference primal {ans.primal!r}")
        for i in groups[key]:
            nums = totals(window[i], ans, data.budgets)
            nums["fixed_point"] = fp
            if i == idx:
                nums["x_mismatch"] = _x_mismatch(x, ans)
                nums["overuse"] = float(np.max(
                    consumption(data.b, x) / data.budgets - 1.0))
            per_unit.append(nums)
    return judge(per_unit, limits)


def resident_control(data: Data, lam, dtype) -> dict:
    """The numbers of the reference computed in ``dtype``, put in the
    program's place at the program's multipliers."""
    ans = reference.answer(data.p, data.b, data.budgets, lam, data.q,
                           "exact")
    ctl = reference.answer(data.p, data.b, data.budgets, lam, data.q,
                           "exact", dtype=dtype)
    x = _rows(ctl, data.p.shape[1])
    nums = totals({"r": ctl.r, "primal": ctl.primal, "dual": ctl.dual},
                  ans, data.budgets)
    nums["x_mismatch"] = _x_mismatch(x, ans)
    nums["overuse"] = float(np.max(consumption(data.b, x)
                                   / data.budgets - 1.0))
    return nums


def ladder_bucket(tau, edges) -> int:
    """Index of tau on the ladder: -1 below it (nothing removed), E above."""
    if tau == -np.inf:
        return -1
    return int(np.searchsorted(edges, tau, side="left"))


def lookup_mismatch(x, users, rows) -> float:
    """Share of looked-up ``users`` whose ``rows`` differ from ``x``."""
    return float(np.mean(np.any(x[users] != rows, axis=1)))


def published(data: Data, budgets, gen: dict, x, ladder,
              dtype=np.float64) -> dict:
    """Numbers of one published generation (or the control in its place).

    ``gen`` holds the published lam, tau, r, primal, dual; ``x`` every
    user's published decision. With ``dtype`` narrower than float64, the
    reference computed in ``dtype`` at the published lam takes the
    program's place: its totals, threshold and decisions.
    """
    ans = reference.answer(data.p, data.b, budgets, gen["lam"], data.q,
                           "ladder", ladder=ladder)
    edges = reference.ladder_edges(*ladder)
    if np.dtype(dtype) != np.float64:
        ctl = reference.answer(data.p, data.b, budgets, gen["lam"], data.q,
                               "ladder", ladder=ladder, dtype=dtype)
        gen = {"r": ctl.r, "primal": ctl.primal, "dual": ctl.dual,
               "tau": ctl.tau}
        x = _rows(ctl, data.p.shape[1])
    nums = totals(gen, ans, budgets)
    nums["tau_gap"] = float(abs(ladder_bucket(float(gen["tau"]), edges)
                                - ladder_bucket(ans.tau, edges)))
    nums["x_mismatch"] = _x_mismatch(x, ans)
    nums["overuse"] = float(np.max(consumption(data.b, x)
                                   / np.asarray(budgets, np.float64) - 1.0))
    return nums
