"""What every load loop shares: the host clock, spans and scores.

A traffic mix is a JSON file of parameters under ``chipbench/traffic/``; its
``loop`` names the module ``chipbench/loops/<loop>.py`` that drives the
engine with it (found by ``catalog.loop``).  Each such module defines a
class ``Loop(engine, mix, rng, spans, scores, *, seconds, capacity_windows)``
with ``pool`` (the ``ScenePool`` its audio comes from) and:

* ``warm()`` -- the traffic's own set-up; measuring starts when it returns;
* ``run(t_end)`` -- drive the engine until the host clock reads ``t_end``;
* ``finish(t0, t_end, t_untraced)`` -- after the window: drain the engine and
  return ``attempted`` (windows the window attempted), ``pushed`` (samples
  pushed per stream), ``latency_ms`` and ``lag_ms`` (arrays, or None where
  the loop has no schedule to be late against).

Loops time every call into the engine on the host clock (``Spans``), record
every score the engine returns (``Scores``), and wrap each phase in a
``jax.profiler.TraceAnnotation`` while a trace is being taken.
"""
from __future__ import annotations

import contextlib
import time

import jax
import numpy as np

clock = time.perf_counter
sleep = time.sleep


class Spans:
    """Host spans ``(name, start, end, windows)`` kept in memory."""

    def __init__(self):
        self.name: list[str] = []
        self.start: list[float] = []
        self.end: list[float] = []
        self.windows: list[int] = []
        self.annotate = False  # wrap phases in TraceAnnotation (traced segment)

    @contextlib.contextmanager
    def span(self, name: str):
        ann = jax.profiler.TraceAnnotation(name) if self.annotate else contextlib.nullcontext()
        t0 = clock()
        box = [0]
        with ann:
            yield box
        self.name.append(name)
        self.start.append(t0)
        self.end.append(clock())
        self.windows.append(box[0])

    def arrays(self, name: str, lo: float = -np.inf, hi: float = np.inf):
        """(start, end, windows) arrays of the spans called ``name`` that start
        in ``[lo, hi)``."""
        names = np.asarray(self.name)
        start = np.asarray(self.start)
        sel = (names == name) & (start >= lo) & (start < hi)
        return start[sel], np.asarray(self.end)[sel], np.asarray(self.windows, np.int64)[sel]


class Scores:
    """Every score the engine returned: stream, window index, probability,
    smoothed score, active flag and the host time of the ``step()`` return."""

    def __init__(self):
        self.cols: list[tuple] = []

    def take(self, out, t: float) -> int:
        self.cols.extend((w.stream, w.window_idx, w.p_uav, w.smoothed, w.active, t) for w in out)
        return len(out)

    def arrays(self) -> dict:
        if not self.cols:
            z = np.zeros(0)
            return dict(stream=z.astype(np.int64), idx=z.astype(np.int64), p=z, smoothed=z,
                        active=z.astype(bool), t=z)
        s, i, p, sm, a, t = zip(*self.cols)
        return dict(stream=np.asarray(s, np.int64), idx=np.asarray(i, np.int64),
                    p=np.asarray(p, np.float64), smoothed=np.asarray(sm, np.float64),
                    active=np.asarray(a, bool), t=np.asarray(t, np.float64))

