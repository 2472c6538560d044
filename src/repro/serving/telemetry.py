"""Host spans of one serving engine, kept in memory.

A :class:`Telemetry` records named spans on ``time.perf_counter``: each has
a start, an end, the index of its parent span (``-1`` for a root), the round
id of the ``MonitorEngine.step`` it belongs to, and one count whose meaning
is fixed per span name (samples, bytes, rows, slots or windows; see
``MonitorEngine``).  It is off by default, and a call site pays one
attribute test while it is off::

    tel = self.telemetry
    if tel.on:
        i = tel.open("engine.gather")
    ...
    if tel.on:
        tel.close(i, nbytes)

With ``annotate`` set, every span also opens a
``jax.profiler.TraceAnnotation`` of the same name (a round root opens a
``StepTraceAnnotation`` numbered by its round), so the spans land on a
profiler trace's clock beside the device's operations.
"""
from __future__ import annotations

import time

import jax
import numpy as np

clock = time.perf_counter

# fields of a span record [name, start, end, parent, round, count]
_END, _ROUND, _COUNT = 2, 4, 5


class Telemetry:
    """In-memory span recorder; see the module docstring."""

    def __init__(self):
        self.on = False
        self.annotate = False
        self.spans: list[list] = []  # [name, start, end, parent, round, count]
        # the open spans, innermost last: (index, its TraceAnnotation or None)
        self._stack: list[tuple] = []

    def open(self, name: str, round: int | None = None, step: bool = False) -> int:
        """Open a span; returns its index.

        Without ``round`` the span is a child of the innermost open span and
        shares its round id (``-1`` where none is open).  With ``round`` it
        is a root: whatever an exception left open is closed first (ended
        now, count 0), so a failed round cannot adopt what follows it.
        ``step=True`` marks a round root for the profiler."""
        stack = self._stack
        if round is None:
            parent = stack[-1][0] if stack else -1
            round = self.spans[parent][_ROUND] if stack else -1
        else:
            if stack:
                self.close(stack[0][0])
            parent = -1
        ann = None
        if self.annotate:
            ann = (jax.profiler.StepTraceAnnotation(name, step_num=round) if step
                   else jax.profiler.TraceAnnotation(name))
            ann.__enter__()
        i = len(self.spans)
        self.spans.append([name, clock(), 0.0, parent, round, 0])
        stack.append((i, ann))
        return i

    def close(self, i: int, count: int = 0) -> None:
        """Close span ``i`` (and any child still open inside it)."""
        t = clock()
        stack = self._stack
        while stack:
            j, ann = stack.pop()
            rec = self.spans[j]
            rec[_END] = t
            if ann is not None:
                ann.__exit__(None, None, None)
            if j == i:
                rec[_COUNT] = count
                return

    # -- reading ---------------------------------------------------------------

    def _select(self, name: str, lo: float, hi: float):
        """(start, end, parent, count) columns of every span, and the mask of
        the spans called ``name`` that start in ``[lo, hi)``."""
        cols = list(zip(*self.spans)) or [()] * 6
        names = np.asarray(cols[0], str)
        start, end = np.asarray(cols[1], float), np.asarray(cols[2], float)
        parent, count = np.asarray(cols[3], np.int64), np.asarray(cols[5], np.int64)
        return (start, end, parent, count), (names == name) & (start >= lo) & (start < hi)

    def arrays(self, name: str, lo: float = -np.inf, hi: float = np.inf):
        """(start, end, count) arrays of the spans called ``name`` that start
        in ``[lo, hi)``."""
        (start, end, _, count), sel = self._select(name, lo, hi)
        return start[sel], end[sel], count[sel]

    def self_times(self, name: str, lo: float = -np.inf, hi: float = np.inf) -> np.ndarray:
        """Durations of the spans called ``name`` that start in ``[lo, hi)``,
        each less the time covered by its children."""
        (start, end, parent, _), sel = self._select(name, lo, hi)
        dur = end - start
        child = parent >= 0
        covered = np.bincount(parent[child], weights=dur[child], minlength=len(dur))
        return (dur - covered)[sel]
