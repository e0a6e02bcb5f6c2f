"""A run with the timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip, runs a cell at a small
size on the CPU with one fault planted in the program, and checks that
``correct`` is false. A run with nothing planted comes out correct.
"""
import time

import numpy as np
import pytest

from chipbench import harness

SEED = 2**31 + 11
COLD = {"n_users": 16384}
FEED = {"n_users": 63000, "chunk": 4096}     # a ragged last chunk


@pytest.fixture(autouse=True)
def no_cache(monkeypatch):
    """Keep the persistent compile cache out of the checkout, and start
    every test from freshly built step programs."""
    from repro.core import prefetch

    monkeypatch.setattr(harness, "_compile_cache", lambda: None)
    prefetch._jit_steps.cache_clear()
    prefetch._jit_steps_sharded.cache_clear()
    yield
    prefetch._jit_steps.cache_clear()
    prefetch._jit_steps_sharded.cache_clear()


def run(name, overrides):
    return harness.run(name, SEED, 0.5, False, t0=time.perf_counter(),
                       require_tpu=False, overrides=overrides)


def _failed(res):
    return sorted(k for k, v in res["checks"].items()
                  if v["value"] > v["limit"])


def _unchanged_step(lam, dprev, prop, cfg):
    """A multiplier step that returns its state unchanged."""
    import jax.numpy as jnp

    return lam, jnp.zeros_like(lam), jnp.asarray(False)


@pytest.mark.parametrize("name, overrides", [
    ("table1-share.cold", COLD), ("table1-feed.refresh", FEED)])
def test_sound_run_is_correct(name, overrides):
    res = run(name, overrides)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0


@pytest.mark.parametrize("name, overrides, module", [
    ("table1-share.cold", COLD, "repro.core.solver"),
    ("table1-feed.refresh", FEED, "repro.core.prefetch")])
def test_state_left_unchanged(monkeypatch, name, overrides, module):
    monkeypatch.setattr(module + ".damped_multiplier_step", _unchanged_step)
    res = run(name, overrides)
    assert not res["correct"]
    assert "fixed_point" in _failed(res)


def test_cold_half_the_batch(monkeypatch):
    from repro.core import solver

    real = solver.solve_fn

    def half(cfg, q):
        fn = real(cfg, q)

        def call(kp, lam0):
            n = kp.p.shape[0]
            live = (np.arange(n) < n // 2)[:, None]
            return fn(kp._replace(p=kp.p * live, b=kp.b * live,
                                  budgets=kp.budgets / 2), lam0)
        return call

    monkeypatch.setattr(solver, "solve_fn", half)
    res = run("table1-share.cold", COLD)
    assert not res["correct"]
    assert {"x_mismatch", "r_gap"} <= set(_failed(res))


def test_cold_answer_altered(monkeypatch):
    from repro.core import solver

    real = solver.solve_fn

    def altered(cfg, q):
        fn = real(cfg, q)

        def call(kp, lam0):
            res = fn(kp, lam0)
            n = res.x.shape[0]
            return res._replace(x=res.x.at[: n // 100].set(False))
        return call

    monkeypatch.setattr(solver, "solve_fn", altered)
    res = run("table1-share.cold", COLD)
    assert not res["correct"]
    assert "x_mismatch" in _failed(res)


def _wrap_streaming(monkeypatch, wrap):
    from repro.serve import engine

    real = engine.solve_streaming_host
    monkeypatch.setattr(engine, "solve_streaming_host",
                        lambda source, *a, **kw: wrap(real, source, *a, **kw))


def test_feed_half_the_batch(monkeypatch):
    def half(real, source, *a, **kw):
        c = source.n // source.chunk

        def fn(i):
            p, b = source.fn(i)
            return (p, b) if i < c // 2 else (p * 0, b * 0)
        return real(source._replace(fn=fn, budgets=source.budgets / 2),
                    *a, **kw)

    _wrap_streaming(monkeypatch, half)
    res = run("table1-feed.refresh", FEED)
    assert not res["correct"]
    assert {"x_mismatch", "r_gap"} <= set(_failed(res))


def test_feed_answer_altered(monkeypatch):
    def altered(real, source, *a, **kw):
        res = real(source, *a, **kw)
        return res._replace(primal=res.primal * 1.001)

    _wrap_streaming(monkeypatch, altered)
    res = run("table1-feed.refresh", FEED)
    assert not res["correct"]
    assert "primal_gap" in _failed(res)


def test_feed_publication_not_durable(monkeypatch):
    from repro.checkpoint import ckpt

    monkeypatch.setattr(ckpt, "fsync_dir", lambda path: None)
    res = run("table1-feed.refresh", FEED)
    assert not res["correct"]
    assert _failed(res) == ["unsynced"]


def test_feed_lookup_altered(monkeypatch):
    from repro.serve import decisions

    real = decisions.DecisionService.decide_batch
    monkeypatch.setattr(decisions.DecisionService, "decide_batch",
                        lambda self, users: ~real(self, users))
    res = run("table1-feed.refresh", FEED)
    assert not res["correct"]
    assert _failed(res) == ["lookup_mismatch"]
