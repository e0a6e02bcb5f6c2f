"""Pure-jnp oracles for the Pallas kernels.

These are the semantics contracts: every kernel result is
assert_allclose'd against these across shape/dtype sweeps. They share the
tie-break convention (stable by item index) with core/greedy and
core/sparse_scd, and are themselves cross-checked against those modules in
the kernel tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# The one-hot histogram contractions are MXU matmuls on a TPU, where
# DEFAULT precision rounds f32 operands to bf16; an oracle must not.
_EXACT = jax.lax.Precision.HIGHEST


def adjusted_topc_ref(p, b, lam, q):
    """Fused DD/SCD map body, sparse GKP (one item per knapsack).

    p, b: (n, K); lam: (K,). Returns (x (n,K) bool, v (n,K) f32) where x is
    the top-q positive adjusted profits (ties -> smaller index) and
    v = b * x is the per-user consumption.
    """
    ap = p - lam[None, :] * b
    order = jnp.argsort(-ap, axis=-1, stable=True)
    ranks = jnp.argsort(order, axis=-1, stable=True)
    x = (ap > 0) & (ranks < q)
    return x, jnp.where(x, b, 0.0).astype(p.dtype)


def scd_candidates_ref(p, b, lam, q):
    """Algorithm 5 map: candidate (v1, v2) per (user, knapsack).

    Matches core.sparse_scd.candidates_sparse (invalid -> v1=-1, v2=0).
    """
    n, k = p.shape
    ap = jnp.maximum(p - lam[None, :] * b, 0.0)
    if q >= k:
        pbar = jnp.zeros_like(ap)
    else:
        top, _ = jax.lax.top_k(ap, q + 1)
        q_th = top[:, q - 1] if q >= 1 else jnp.full((n,), jnp.inf, ap.dtype)
        q1_th = top[:, q]
        in_top = ap >= q_th[:, None]
        pbar = jnp.where(in_top, q1_th[:, None], q_th[:, None])
    valid = (p > pbar) & (b > 0)
    v1 = jnp.where(valid, (p - pbar) / jnp.where(b > 0, b, 1.0), -1.0)
    v2 = jnp.where(valid, b, 0.0)
    return v1.astype(p.dtype), v2.astype(p.dtype)


def scd_fused_hist_ref(p, b, lam, edges, q, hist_init=None, top_init=None):
    """Fused SCD map+reduce oracle: the unfused two-stage composition.

    Returns (hist (K, E+1), top (K,)) where hist is
    ``bucket_hist_ref(*scd_candidates_ref(p, b, lam, q), edges)`` and top
    is the per-knapsack max candidate value max(v1, axis=0). Optional
    ``hist_init``/``top_init`` accumulator seeds are combined with
    ``+``/``maximum`` (an allclose-level oracle for the kernel's seeded
    accumulation, not a bit-exact one — the kernel folds the seed into
    its tile chain instead of adding it afterwards).
    """
    v1, v2 = scd_candidates_ref(p, b, lam, q)
    hist = bucket_hist_ref(v1, v2, edges)
    top = jnp.max(v1, axis=0)
    if hist_init is not None:
        hist = hist + hist_init
    if top_init is not None:
        top = jnp.maximum(top, top_init)
    return hist, top


def scd_finalize_ref(p, b, lam, pedges, q, with_hist=True,
                     cons_hist_init=None, gain_hist_init=None, r_init=None,
                     sums_init=None, maxs_init=None):
    """Streaming-finalize oracle: metrics partials + §5.4 histograms.

    Matches ``kernels.scd_fused.scd_finalize_hist`` at allclose level
    (per the repo's kernel-oracle convention the seed combination and the
    tile-grouped sums differ in the last ulp; bucket *indices* are
    bit-identical because pt is the same per-row reduction on both
    sides). Returns the same 7-tuple: (cons_hist (K, E+1), gain_hist
    (E+1,), r (K,), primal (), dual_sum (), lo (), hi ()); the
    histograms are None when ``with_hist`` is False.
    """
    x, cons = adjusted_topc_ref(p, b, lam, q)
    ap = p - lam[None, :] * b
    gain = jnp.sum(jnp.where(x, p, 0.0), axis=-1)            # (n,)
    pt = jnp.sum(jnp.where(x, ap, 0.0), axis=-1)             # (n,)
    r = jnp.sum(cons, axis=0).astype(jnp.float32)
    primal = jnp.sum(jnp.where(x, p, 0.0)).astype(jnp.float32)
    dual_sum = jnp.sum(jnp.where(x, ap, 0.0)).astype(jnp.float32)
    sel = jnp.any(x, axis=-1)
    inf = jnp.asarray(jnp.inf, p.dtype)
    lo = jnp.min(jnp.where(sel, pt, inf))
    hi = jnp.max(jnp.where(sel, pt, -inf))
    if r_init is not None:
        r = r + r_init
    if sums_init is not None:
        primal = primal + sums_init[0]
        dual_sum = dual_sum + sums_init[1]
    if maxs_init is not None:
        hi = jnp.maximum(hi, maxs_init[0])
        lo = jnp.minimum(lo, -maxs_init[1])
    if not with_hist:
        return None, None, r, primal, dual_sum, lo, hi
    e = pedges.shape[-1]
    idx = jnp.searchsorted(pedges, pt, side="left")          # (n,)
    onehot = jax.nn.one_hot(idx, e + 1, dtype=jnp.float32)   # (n, E+1)
    ch = jnp.einsum("nb,nk->kb", onehot, cons.astype(jnp.float32),
                    precision=_EXACT)
    gh = jnp.einsum("nb,n->b", onehot, gain.astype(jnp.float32),
                    precision=_EXACT)
    if cons_hist_init is not None:
        ch = ch + cons_hist_init
    if gain_hist_init is not None:
        gh = gh + gain_hist_init
    return ch, gh, r, primal, dual_sum, lo, hi


def bucket_hist_ref(v1, v2, edges):
    """Section 5.2 histogram: mass of v2 per (knapsack, bucket).

    v1, v2: (n, K); edges: (K, E) ascending. Bucket j of row k holds
    candidates with edges[k, j-1] < v1 <= edges[k, j]; returns (K, E+1).
    """
    n, k = v1.shape
    e = edges.shape[-1]
    idx = jax.vmap(jnp.searchsorted, in_axes=(0, 1))(edges, v1)   # (K, n)
    onehot = jax.nn.one_hot(idx, e + 1, dtype=v2.dtype)           # (K, n, E+1)
    return jnp.einsum("kne,nk->ke", onehot, v2, precision=_EXACT)
