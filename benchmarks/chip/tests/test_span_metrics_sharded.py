"""The readers of the sharded host-fed runtime's own spans,
``ingest.stack`` and ``slots.combine``, on hand-built runs: two traced
refreshes whose spans are known, and runs of programs that open neither
span (the one-slot runtime, and a program from before the spans)."""
import pytest

from chipbench import harness
from chipbench.spans import Collector
from test_span_metrics import NEW, _ctx
from test_span_metrics import _refresh as _one_slot_refresh

SHARDED = ("ingest_stack_s", "slot_combine_s")
CELL = "table1-feed4.refresh"


def _entries(names=SHARDED):
    bench = harness._json(harness.ROOT / "BENCHMARK.json")
    return [m for m in bench["per_layer"] if m["name"] in names]


def _refresh(col, iters, cols, stack_s=0.002, combine_s=0.004,
             fin_combine_s=0.006):
    """Spans of one sharded refresh, innermost first: each of ``iters``
    epochs uploads ``cols`` columns, each stacked inside its
    ``ingest.h2d``, and combines its slot partials once inside
    ``solve.iterate``; the finalize combines once more."""
    a = col.mark()
    col.record("refresh.prepare", 0, 0.01)
    for _ in range(iters):
        for _ in range(cols):
            col.record("ingest.stack", 0, stack_s)
            col.record("ingest.h2d", 0, stack_s + 0.001)
        col.record("slots.combine", 0, combine_s)
        col.record("solve.iterate", 0, 0.5)
    col.record("slots.combine", 0, fin_combine_s)
    col.record("solve.finalize", 0, 1.0)
    col.record("refresh.publish", 0, 0.01)
    return {"seconds": 0.02 + 0.5 * iters + 1.0, "spans": (a, col.mark())}


def test_entries_are_the_four_chip_cell():
    entries = _entries()
    assert [m["name"] for m in entries] == list(SHARDED)
    for m in entries:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "refresh_s"
        assert m["source"] == "program_span" and m["unit"] == "s"


def test_four_chip_cell_reads_its_readers():
    """The harness hands the cell both new readers, and no reader of
    the one-chip feed cell that the cell was not appended to."""
    bench = harness._json(harness.ROOT / "BENCHMARK.json")
    names = {m["name"] for m in harness.cell(bench, CELL)["per_layer"]}
    assert set(SHARDED) <= names
    assert {"refresh_iters", "ingest_fetch_s", "ingest_h2d_s", "publish_s",
            "device_idle.refresh"} <= names
    assert not names & set(NEW)


def test_readers_per_refresh():
    col = Collector()
    units = [_refresh(col, 5, 39), _refresh(col, 6, 39)]
    got = harness.read_metrics(_entries(), _ctx(col, units))
    # 5.5 epochs of 39 columns a refresh; one combine an epoch and one
    # for the finalize.
    assert got["ingest_stack_s"]["value"] == pytest.approx(5.5 * 39 * 0.002)
    assert got["slot_combine_s"]["value"] == pytest.approx(
        5.5 * 0.004 + 0.006)
    assert {v["unit"] for v in got.values()} == {"s"}


def test_silent_on_a_program_without_the_spans():
    """A program from before the spans opens only solve.iterate/finalize,
    the ingest and the publish spans: both readers are silent, neither
    raises, also on a run with no traced refresh."""
    col = Collector()
    a = col.mark()
    col.record("ingest.h2d", 0, 0.1)
    col.record("solve.iterate", 0, 0.5)
    col.record("solve.finalize", 0, 1.0)
    col.record("refresh.publish", 0, 0.01)
    units = [{"seconds": 2.0, "spans": (a, col.mark())}]
    assert harness.read_metrics(_entries(), _ctx(col, units)) == {}
    assert harness.read_metrics(_entries(), _ctx(col, [])) == {}


def test_silent_on_the_one_slot_runtime():
    """A one-slot refresh, with every span but the sharded runtime's own:
    the sharded readers are silent while the one-chip readers read."""
    col = Collector()
    units = [_one_slot_refresh(col, 0.05, 5, 40)]
    got = harness.read_metrics(_entries(NEW + SHARDED), _ctx(col, units))
    assert set(got) == set(NEW)
