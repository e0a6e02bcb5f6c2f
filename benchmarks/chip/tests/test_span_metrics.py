"""The readers of the refresh's resume-state and top-level spans, on
hand-built runs: two traced refreshes whose spans are known."""
import types

import pytest

from chipbench import harness
from chipbench.spans import Collector

NEW = ("ckpt_save_s", "ckpt_saves", "refresh_unspanned_s")


def _entries():
    bench = harness._json(harness.ROOT / "BENCHMARK.json")
    return [m for m in bench["per_layer"] if m["name"] in NEW]


def _refresh(col, rest, iters, saves, save_s=0.025):
    """Spans of one refresh as the program reports them, innermost
    first; returns the unit record. Its top-level spans leave ``rest``
    seconds of the refresh uncovered."""
    a = col.mark()
    col.record("refresh.prepare", 0, 0.01)
    col.record("solve.fingerprint", 0, 0.02)
    for _ in range(iters):
        col.record("ingest.h2d", 0, 0.1)
        col.record("solve.iterate", 0, 0.5)
    for _ in range(saves):
        col.record("ckpt.gather", 0, save_s / 5)
        col.record("ckpt.write", 0, save_s * 4 / 5)
        col.record("ckpt.save", 0, save_s)
    col.record("solve.finalize", 0, 1.0)
    col.record("refresh.stamp", 0, 0.03)
    col.record("refresh.publish", 0, 0.01)
    col.record("refresh.publish", 0, 0.005)
    col.record("refresh.readback", 0, 0.015)
    top = 0.01 + 0.02 + 0.5 * iters + 1.0 + 0.03 + 0.01 + 0.005 + 0.015
    return {"seconds": top + rest, "spans": (a, col.mark())}


def _ctx(col, units):
    return types.SimpleNamespace(units=units, spans=col)


def test_entries_are_the_feed_cells():
    entries = _entries()
    assert [m["name"] for m in entries] == list(NEW)
    for m in entries:
        assert m["workloads"] == ["table1-feed.refresh"]
        assert m["moves"] == "refresh_s"


def test_ckpt_readers_per_refresh():
    col = Collector()
    units = [_refresh(col, 0.05, 5, 40), _refresh(col, 0.05, 6, 39)]
    got = harness.read_metrics(_entries(), _ctx(col, units))
    assert got["ckpt_saves"]["value"] == pytest.approx(39.5)
    assert got["ckpt_saves"]["unit"] == "saves"
    assert got["ckpt_save_s"]["value"] == pytest.approx(39.5 * 0.025)


def test_unspanned_remainder_is_what_no_top_span_covers():
    col = Collector()
    units = [_refresh(col, 0.05, 5, 40), _refresh(col, 0.11, 6, 39)]
    got = harness.read_metrics(_entries(), _ctx(col, units))
    assert got["refresh_unspanned_s"]["value"] == pytest.approx(0.08)
    assert got["refresh_unspanned_s"]["unit"] == "s"


def test_silent_on_a_program_without_the_spans():
    """The parent program opens only solve.iterate/finalize and the
    publish spans: every new reader is silent, none raises."""
    col = Collector()
    a = col.mark()
    col.record("solve.iterate", 0, 0.5)
    col.record("solve.finalize", 0, 1.0)
    col.record("refresh.publish", 0, 0.01)
    col.record("refresh.publish", 0, 0.005)
    units = [{"seconds": 2.0, "spans": (a, col.mark())}]
    assert harness.read_metrics(_entries(), _ctx(col, units)) == {}
    assert harness.read_metrics(_entries(), _ctx(col, [])) == {}
