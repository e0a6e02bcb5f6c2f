"""The benchmark's own instance generator: sparse GKP data from a seed.

Every seed gets the same multiset of users, in its own order: one base
instance is drawn from a fixed key, and the seed permutes its rows. The
solver's work (its iteration count, its passes) therefore does not
change with the seed, while the bytes every run feeds it do. Profits and
costs are uniform on [0, 1) and budgets are set at ``tightness`` of the
unconstrained expected use, as in the paper's Section 6 instances.
"""
from __future__ import annotations

import functools

import numpy as np

BASE_KEY = 20200420          # fixed: the base instance every seed permutes


def seed_key(seed: int):
    """A JAX key for any non-negative seed (more than 32 bits welcome)."""
    import jax

    if seed < 0 or seed >= 1 << 63:
        raise ValueError(f"seed must be in [0, 2**63), got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def budgets(n: int, k: int, q: int, tightness: float) -> np.ndarray:
    """(K,) float32 budgets: tightness * n * q * E[b] / K, E[b] = 1/2."""
    return np.full((k,), tightness * n * q * 0.5 / k, np.float32)


@functools.lru_cache(maxsize=4)
def _generator(n: int, k: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gen(key):
        kp, kb = jax.random.split(jax.random.PRNGKey(BASE_KEY))
        p = jax.random.uniform(kp, (n, k), jnp.float32)
        b = jax.random.uniform(kb, (n, k), jnp.float32)
        perm = jax.random.permutation(key, n)
        return p[perm], b[perm]

    return gen


def device_instance(seed: int, n: int, k: int, device=None):
    """(p, b): (n, K) float32 arrays on ``device``, made in one jitted call."""
    import jax

    gen = _generator(n, k)
    key = seed_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return gen(key)
