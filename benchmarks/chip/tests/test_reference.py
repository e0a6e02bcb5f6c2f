"""The plain reference against brute force on tiny instances."""
import numpy as np
import pytest

from chipbench import reference as R


def _instance(n=300, k=4, seed=0):
    rng = np.random.default_rng(seed)
    p = rng.random((n, k)).astype(np.float32)
    b = rng.random((n, k)).astype(np.float32)
    budgets = np.full(k, 0.5 * n * 0.5 / k, np.float32)
    return p, b, budgets


def _brute_select(p, b, lam, q):
    ap = p.astype(np.float64) - lam * b.astype(np.float64)
    x = np.zeros(p.shape, bool)
    for i in range(p.shape[0]):
        order = sorted(range(p.shape[1]), key=lambda j: (-ap[i, j], j))
        for j in order[:q]:
            x[i, j] = ap[i, j] > 0
    return ap, x


@pytest.mark.parametrize("q", [1, 2])
def test_answer_exact_matches_brute_force(q):
    p, b, budgets = _instance()
    lam = np.full(p.shape[1], 0.2)          # low prices: budgets overrun
    ans = R.answer(p, b, budgets * q, lam, q, "exact")
    ap, x = _brute_select(p, b, lam, q)
    pt = np.where(x, ap, 0).sum(1)
    cons = np.where(x, b.astype(np.float64), 0)
    excess = np.maximum(cons.sum(0) - budgets * q, 0)
    assert excess.max() > 0
    order = sorted(range(len(pt)), key=lambda i: (pt[i], i))
    run = np.cumsum(cons[order], axis=0)
    first = next(t for t in range(len(order)) if np.all(run[t] >= excess))
    tau = pt[order[first]]
    assert ans.tau == tau
    kept = pt > tau
    assert np.array_equal(ans.kept, kept)
    assert np.allclose(ans.r, (cons * kept[:, None]).sum(0))
    assert ans.primal == pytest.approx(
        np.where(x & kept[:, None], p.astype(np.float64), 0).sum())
    assert np.all(ans.r <= budgets * q)


def test_answer_ladder_removes_a_whole_bucket_prefix():
    p, b, budgets = _instance(seed=1)
    lam = np.full(p.shape[1], 0.2)
    ladder = (16, 1e-3, 1.0)
    ans = R.answer(p, b, budgets, lam, 1, "ladder", ladder=ladder)
    edges = R.ladder_edges(*ladder)
    assert ans.tau in edges
    e = int(np.searchsorted(edges, ans.tau))
    assert np.all(ans.r <= budgets)
    if e > 0:   # the edge below would not have covered the excess
        r_below, _, _ = R.at_tau(p, b, ans.choice, ans.pt, edges[e - 1])
        assert np.any(r_below > budgets)


def _brute_threshold(v1, v2, budget):
    vals = sorted(set(v1[v1 >= 0]), reverse=True)
    best = 0.0
    if v2[v1 >= 0].sum() <= budget:
        return 0.0
    for v in vals:
        if v2[v1 >= v].sum() <= budget:
            best = v
        else:
            break
    return best


@pytest.mark.parametrize("q", [1, 2])
def test_scd_threshold_matches_brute_force(q):
    p, b, budgets = _instance(n=200, k=3, seed=2)
    lam = np.array([0.3, 0.5, 0.1])
    got = R.scd_threshold(p, b, budgets * q, lam, q)
    ap = np.maximum(p.astype(np.float64) - lam * b, 0)
    for kk in range(3):
        v1 = np.full(200, -1.0)
        v2 = np.zeros(200)
        for i in range(200):
            others = sorted(ap[i], reverse=True)
            qth, q1th = others[q - 1], others[q]
            pbar = q1th if ap[i, kk] >= qth else qth
            if p[i, kk] > pbar and b[i, kk] > 0:
                v1[i] = (p[i, kk] - pbar) / float(b[i, kk])
                v2[i] = b[i, kk]
        assert got[kk] == pytest.approx(_brute_threshold(v1, v2,
                                                         budgets[kk] * q))


def test_fixed_point_of_the_initial_multipliers_is_far():
    p, b, budgets = _instance(n=4000, k=10, seed=3)
    assert R.fixed_point(p, b, budgets, np.ones(10), 1) > 0.02
