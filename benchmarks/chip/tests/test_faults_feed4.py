"""The four-chip feed cell, ``table1-feed4.refresh``, under the feed
faults of ``test_faults.py``: a sound run comes out correct, and each
fault planted in the program comes out not correct.

The cell runs its sharded runtime (four slots) on the four host devices
that ``conftest.py`` forces, at the one-chip feed tests' small size.
"""
from test_faults import (FEED, _failed, _unchanged_step, _wrap_streaming,
                         no_cache, run)  # noqa: F401 (no_cache is autouse)

CELL = "table1-feed4.refresh"


def test_sound_run_is_correct():
    res = run(CELL, FEED)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0


def test_state_left_unchanged(monkeypatch):
    monkeypatch.setattr("repro.core.prefetch.damped_multiplier_step",
                        _unchanged_step)
    res = run(CELL, FEED)
    assert not res["correct"]
    assert "fixed_point" in _failed(res)


def test_half_the_batch(monkeypatch):
    def half(real, source, *a, **kw):
        c = source.n // source.chunk

        def fn(i):
            p, b = source.fn(i)
            return (p, b) if i < c // 2 else (p * 0, b * 0)
        return real(source._replace(fn=fn, budgets=source.budgets / 2),
                    *a, **kw)

    _wrap_streaming(monkeypatch, half)
    res = run(CELL, FEED)
    assert not res["correct"]
    assert {"x_mismatch", "r_gap"} <= set(_failed(res))


def test_answer_altered(monkeypatch):
    def altered(real, source, *a, **kw):
        res = real(source, *a, **kw)
        return res._replace(primal=res.primal * 1.001)

    _wrap_streaming(monkeypatch, altered)
    res = run(CELL, FEED)
    assert not res["correct"]
    assert "primal_gap" in _failed(res)


def test_publication_not_durable(monkeypatch):
    from repro.checkpoint import ckpt

    monkeypatch.setattr(ckpt, "fsync_dir", lambda path: None)
    res = run(CELL, FEED)
    assert not res["correct"]
    assert _failed(res) == ["unsynced"]


def test_lookup_altered(monkeypatch):
    from repro.serve import decisions

    real = decisions.DecisionService.decide_batch
    monkeypatch.setattr(decisions.DecisionService, "decide_batch",
                        lambda self, users: ~real(self, users))
    res = run(CELL, FEED)
    assert not res["correct"]
    assert _failed(res) == ["lookup_mismatch"]


def test_cell_runs_the_sharded_runtime(monkeypatch):
    """The cell's refreshes go through ``_ShardedRuntime``, four slots
    over the four devices, not the one-slot runtime."""
    from repro.core import prefetch

    made = []
    real = prefetch._ShardedRuntime.__init__

    def spy(self, source, cfg, q, mesh, slots, *a, **kw):
        made.append((slots, mesh.devices.size))
        real(self, source, cfg, q, mesh, slots, *a, **kw)

    monkeypatch.setattr(prefetch._ShardedRuntime, "__init__", spy)
    res = run(CELL, FEED)
    assert res["correct"], res["checks"]
    assert made and set(made) == {(4, 4)}
