"""Plain reference for the sparse GKP: the answer at given multipliers.

A straightforward NumPy implementation of the paper's semantics, in
float64 by default, written from the paper and importing nothing of the
program:

* selection (Algorithm 1, sparse form): each user takes its top-Q items
  by adjusted profit ``p - lam * b``, among those with a positive one;
  ties go to the lower item index;
* post-processing (Section 5.4): users are ranked by their group profit
  ``pt`` (the sum of their selected adjusted profits) and dropped from
  the bottom until every budget holds: either the minimal exact prefix
  (``projection="exact"``) or the minimal prefix that ends on an edge of
  a fixed group-profit ladder (``projection="ladder"``);
* the SCD map (Algorithm 5) and exact reduce (Algorithm 4) at ``lam``,
  for the fixed-point residual of the multipliers.

``dtype`` sets the precision of the per-item arithmetic; sums are
accumulated in float64, or in float32 when the items are of a narrower
type than float32 (the lower-precision control). Rows are processed in
blocks on a pool of threads (NumPy releases the interpreter lock), so
the reference of a 1e7-user instance takes seconds.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple, Optional

import numpy as np

BLOCK = 1 << 18
THREADS = min(os.cpu_count() or 1, 12)


class Answer(NamedTuple):
    """The decisions and totals at one set of multipliers."""

    tau: float             # removal threshold (-inf: nothing removed)
    r: np.ndarray          # (K,) consumption after removal
    primal: float          # selected profit after removal
    dual: float            # sum of pre-removal pt + lam . budgets
    kept: np.ndarray       # (n,) bool: user survives the removal
    choice: np.ndarray     # (n, Q) int16 selected items, -1 for none
    pt: np.ndarray         # (n,) group profit of the selection


def _blocks(n):
    return [(lo, min(lo + BLOCK, n)) for lo in range(0, n, BLOCK)]


def _map(fn, items):
    with ThreadPoolExecutor(THREADS) as pool:
        return list(pool.map(fn, items))


def _acc(dtype):
    return np.float64 if np.dtype(dtype).itemsize >= 4 else np.float32


def _select(p, b, lam, q):
    """(ap, x) for a block: adjusted profits and the top-Q selection."""
    ap = p - lam * b
    n, k = ap.shape
    if q >= k:
        return ap, ap > 0
    if q == 1:
        j = np.argmax(ap, axis=1)
        x = np.zeros(ap.shape, bool)
        rows = np.arange(n)
        x[rows, j] = ap[rows, j] > 0
        return ap, x
    order = np.argsort(-ap.astype(np.float64), axis=1, kind="stable")
    rank = np.empty_like(order)
    np.put_along_axis(rank, order, np.arange(k)[None, :], axis=1)
    return ap, (ap > 0) & (rank < q)


def _choice(x, q):
    """(n, Q) int16 indices of the selected items in index order, -1 pad."""
    if q == 1:
        j = np.argmax(x, axis=1)
        hit = x[np.arange(x.shape[0]), j]
        return np.where(hit, j, -1).astype(np.int16)[:, None]
    idx = np.argsort(~x, axis=1, kind="stable")[:, :q]
    hit = np.take_along_axis(x, idx, axis=1)
    return np.where(hit, idx, -1).astype(np.int16)


def ladder_edges(n_edges, lo, hi):
    """The fixed geometric group-profit ladder, as float32 values."""
    return np.logspace(np.log10(lo), np.log10(hi), n_edges).astype(
        np.float32).astype(np.float64)


def answer(p, b, budgets, lam, q, projection, dtype=np.float64,
           ladder=(512, 1e-6, 1e6), tau: Optional[float] = None) -> Answer:
    """Decisions and totals at ``lam``; ``tau`` given skips its search."""
    dt = np.dtype(dtype)
    acc = _acc(dt)
    n, k = p.shape
    lam_d = np.asarray(lam, np.float64).astype(dt)
    budgets64 = np.asarray(budgets, np.float64)
    pt = np.empty(n, np.float64)
    choice = np.empty((n, q), np.int16)
    zero = np.zeros((), dt)

    def score(span):
        lo, hi = span
        pb = p[lo:hi].astype(dt)
        bb = b[lo:hi].astype(dt)
        ap, x = _select(pb, bb, lam_d, q)
        pt[lo:hi] = np.where(x, ap, zero).astype(acc).sum(axis=1)
        choice[lo:hi] = _choice(x, q)
        return np.where(x, bb, zero).astype(acc).sum(axis=0)

    r0 = np.sum(_map(score, _blocks(n)), axis=0, dtype=np.float64)
    dual = float(pt.sum()) + float(np.dot(np.asarray(lam, np.float64),
                                          budgets64))
    excess = np.maximum(r0 - budgets64, 0.0)
    if tau is None:
        if not np.any(excess > 0):
            tau = -np.inf
        elif projection == "exact":
            tau = _exact_tau(b, choice, pt, excess, dt, acc)
        elif projection == "ladder":
            tau = _ladder_tau(b, choice, pt, excess, ladder_edges(*ladder),
                              dt, acc)
        else:
            raise ValueError(f"unknown projection {projection!r}")
    r, primal, kept = at_tau(p, b, choice, pt, tau, dt)
    return Answer(float(tau), r, primal, dual, kept, choice, pt)


def at_tau(p, b, choice, pt, tau, dtype=np.float64):
    """(r, primal, kept) of the selection ``choice`` after removing every
    user whose group profit ``pt`` is at most ``tau``."""
    dt = np.dtype(dtype)
    acc = _acc(dt)
    n, k = p.shape
    kept = pt > tau

    def block(span):
        lo, hi = span
        r = np.zeros(k, np.float64)
        primal = 0.0
        for j in range(choice.shape[1]):
            c = choice[lo:hi, j]
            rows = lo + np.nonzero(kept[lo:hi] & (c >= 0))[0]
            cols = choice[rows, j].astype(np.int64)
            r += np.bincount(cols, weights=b[rows, cols].astype(dt)
                             .astype(acc), minlength=k)
            primal += float(p[rows, cols].astype(dt).astype(acc).sum())
        return r, primal

    parts = _map(block, _blocks(n))
    return (np.sum([r for r, _ in parts], axis=0),
            float(sum(s for _, s in parts)), kept)


def _cons_rows(b, choice, rows, dt, acc):
    """(len(rows), K) consumption of the given users' selections."""
    k = b.shape[1]
    out = np.zeros((len(rows), k), acc)
    for j in range(choice.shape[1]):
        c = choice[rows, j].astype(np.int64)
        m = c >= 0
        out[np.nonzero(m)[0], c[m]] += b[rows[m], c[m]].astype(dt).astype(acc)
    return out


def _exact_tau(b, choice, pt, excess, dt, acc):
    """Least pt whose prefix (ascending pt) removal covers every excess.

    Only the lowest users can be in the prefix: take the m smallest pt,
    sort those, and grow m until their removal covers the excess.
    """
    n = len(pt)
    m = min(n, 1 << 16)
    while True:
        low = np.argpartition(pt, m - 1)[:m] if m < n else np.arange(n)
        rows = low[np.lexsort((low, pt[low]))]
        run = np.cumsum(_cons_rows(b, choice, rows, dt, acc), axis=0)
        ok = np.all(run >= excess[None, :], axis=1)
        if ok.any():
            return float(pt[rows[int(np.argmax(ok))]])
        if m == n:
            return float(pt[rows[-1]])
        m = min(n, 4 * m)


def _ladder_tau(b, choice, pt, excess, edges, dt, acc):
    """Least ladder edge whose prefix removal covers excess (+inf: all)."""
    n_e = edges.shape[0]
    k = b.shape[1]

    def block(span):
        rows = np.arange(*span)
        idx = np.searchsorted(edges, pt[rows], side="left")
        cons = _cons_rows(b, choice, rows, dt, acc)
        return np.stack([np.bincount(idx, weights=cons[:, kk],
                                     minlength=n_e + 1) for kk in range(k)])

    hist = np.sum(_map(block, _blocks(len(pt))), axis=0)
    cum = np.cumsum(hist, axis=1)[:, :n_e]
    feas = np.all(cum >= excess[:, None], axis=0)
    return float(edges[int(np.argmax(feas))]) if feas.any() else np.inf


def scd_threshold(p, b, budgets, lam, q):
    """Algorithm 5's candidates at ``lam`` reduced exactly by Algorithm 4.

    Returns the (K,) proposed multipliers: per knapsack, the least
    candidate value v with the summed cost of candidates at or above v
    within the budget (0 when everything fits).
    """
    n, k = p.shape
    lam = np.asarray(lam, np.float64)
    budgets = np.asarray(budgets, np.float64)
    v1 = np.empty((k, n), np.float64)
    v2 = np.empty((k, n), np.float64)

    def block(span):
        lo, hi = span
        pb = p[lo:hi].astype(np.float64)
        bb = b[lo:hi].astype(np.float64)
        ap = np.maximum(pb - lam * bb, 0.0)
        if q >= k:
            pbar = np.zeros_like(ap)
        elif q == 1:
            j = np.argmax(ap, axis=1)
            rows = np.arange(hi - lo)
            top1 = ap[rows, j]
            second = ap.copy()
            second[rows, j] = -np.inf
            top2 = second.max(axis=1)
            pbar = np.where(ap >= top1[:, None], top2[:, None],
                            top1[:, None])
        else:
            top = -np.sort(-ap, axis=1)[:, :q + 1]
            q_th = top[:, q - 1:q]
            pbar = np.where(ap >= q_th, top[:, q:q + 1], q_th)
        valid = (pb > pbar) & (bb > 0)
        v1[:, lo:hi] = np.where(valid, (pb - pbar) / np.where(bb > 0, bb, 1),
                                -1.0).T
        v2[:, lo:hi] = np.where(valid, bb, 0.0).T

    _map(block, _blocks(n))
    return np.array(_map(lambda kk: _threshold(v1[kk], v2[kk], budgets[kk],
                                               lam[kk]), range(k)))


def _threshold(v1, v2, budget, guess):
    """Least candidate value v with mass(v1 >= v) <= budget (Algorithm 4)."""
    valid = v1 >= 0
    v1, v2 = v1[valid], v2[valid]
    if v1.size == 0 or v2.sum() <= budget:
        return 0.0
    vmax = float(v1.max())
    if v2[v1 == vmax].sum() > budget:
        return vmax * (1 + 1e-6) + 1e-6     # nothing fits
    w = 1e-3 * (1.0 + abs(guess))
    while True:
        lo, hi = max(guess - w, 0.0), guess + w
        above = float(v2[v1 > hi].sum())
        sel = (v1 >= lo) & (v1 <= hi)
        if above <= budget < above + float(v2[sel].sum()) or (
                lo == 0.0 and hi >= vmax):
            break
        w *= 4
    vals, mass = v1[sel], v2[sel]
    order = np.argsort(-vals, kind="stable")
    vals, mass = vals[order], mass[order]
    uniq, start = np.unique(-vals, return_index=True)
    tot = above + np.cumsum(np.add.reduceat(mass, start))
    fits = np.nonzero(tot <= budget)[0]
    if fits.size == 0:          # the least value that fits lies above hi
        return float(v1[v1 > hi].min())
    return max(float(-uniq[fits[-1]]), 0.0)


def fixed_point(p, b, budgets, lam, q) -> float:
    """max_k |T(lam)_k - lam_k| / (1 + max lam), T the exact SCD map."""
    lam = np.asarray(lam, np.float64)
    t = scd_threshold(p, b, budgets, lam, q)
    return float(np.max(np.abs(t - lam)) / (1.0 + np.max(lam)))
