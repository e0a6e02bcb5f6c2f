"""An in-memory collector for the program's host spans.

It has the interface of the program's tracer (``enabled``, ``span``,
``event``, ``record``, ``flush``, ``close``), so the system under test
reports its spans into it without writing a journal. While the profiler
runs, each ``span`` also opens a ``jax.profiler.TraceAnnotation`` of the
same name, which puts it on the device trace's clock for labelling idle
gaps.
"""
from __future__ import annotations

import time


class _Span:
    __slots__ = ("_col", "_phase", "_t0", "_ann")

    def __init__(self, col, phase):
        self._col, self._phase = col, phase

    def __enter__(self):
        self._ann = None
        if self._col.annotate:
            import jax

            self._ann = jax.profiler.TraceAnnotation(self._phase)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._col.spans.append((self._phase, dur))
        return False


class Collector:
    """Keeps (phase, seconds) of every span, event and record it is given."""

    enabled = True

    def __init__(self):
        self.spans = []
        self.annotate = False

    def span(self, phase, **attrs):
        return _Span(self, phase)

    def event(self, phase, **attrs):
        self.spans.append((phase, 0.0))

    def record(self, phase, t0, dur_s, **attrs):
        self.spans.append((phase, float(dur_s)))

    def flush(self):
        pass

    def close(self):
        pass

    def mark(self) -> int:
        return len(self.spans)

    def seconds(self, phase, since=0, until=None) -> float:
        """Summed seconds of ``phase`` over spans[since:until]."""
        return sum(d for p, d in self.spans[since:until] if p == phase)
