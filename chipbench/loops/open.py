"""Open loop: live microphones on a fixed schedule.

Every stream is a 16 kHz microphone.  Chunks of seeded lengths fall due on a
fixed schedule, with stream phases spread uniformly over one window; one
thread pushes every due chunk, then calls ``step()`` while a window is
ready.  A window's latency runs from the due time of the chunk that
completed it to the return of its score, so a stall counts against every
window it delays.

Traffic keys: ``streams``, ``chunk_seconds`` ([lo, hi]), ``ramp_seconds``
(set-up that staggers the streams and fills their rings), ``clips`` and
``clip_windows``.
"""
from __future__ import annotations

import numpy as np

from chipbench import load
from chipbench.scenes import SR, WINDOW, ScenePool


class Loop:
    def __init__(self, engine, mix: dict, rng: np.random.Generator, spans, scores, *,
                 seconds: float, capacity_windows: int):
        self.engine, self.spans, self.scores = engine, spans, scores
        self.ramp = mix["ramp_seconds"]
        n_streams = mix["streams"]
        horizon_s = self.ramp + seconds + 5.0
        lo, hi = mix["chunk_seconds"]
        n_chunks = int(np.ceil(horizon_s / lo)) + 2
        sizes = (rng.uniform(lo, hi, (n_streams, n_chunks)) * SR).astype(np.int64)
        self.ends = np.cumsum(sizes, axis=1)  # sample count after each chunk
        self.phase = rng.uniform(0.0, WINDOW / SR, n_streams)
        due = self.phase[:, None] + self.ends / SR
        keep = due < horizon_s
        order = np.argsort(due[keep], kind="stable")
        streams = np.broadcast_to(np.arange(n_streams)[:, None], due.shape)[keep]
        starts = (self.ends - sizes)[keep]
        self.due = due[keep][order]
        self.stream = streams[order]
        self.start = starts[order]
        self.size = sizes[keep][order]
        self.pushed_at = np.full(len(self.due), np.nan)
        self.pool = ScenePool(n_streams, mix["clips"], mix["clip_windows"], int(sizes.max()), rng)
        self.next = 0
        self.origin = 0.0

    def window_due(self, stream: np.ndarray, idx: np.ndarray) -> np.ndarray:
        """Due time (seconds after the origin) of the chunk completing each window."""
        need = (idx + 1) * WINDOW
        j = np.array([np.searchsorted(self.ends[s], n) for s, n in zip(stream, need)], np.int64)
        return self.phase[stream] + self.ends[stream, j] / SR

    def windows_due(self, lo: float, hi: float) -> int:
        """Windows whose completing chunk falls due in [lo, hi) (seconds after the origin)."""
        due = self.phase[:, None] + self.ends / SR
        before_hi = np.where(due < hi, self.ends, 0).max(axis=1) // WINDOW
        before_lo = np.where(due < lo, self.ends, 0).max(axis=1) // WINDOW
        return int(np.sum(before_hi - before_lo))

    def push_due(self, upto: float) -> int:
        """Push every chunk due before ``upto`` (seconds after the origin)."""
        j = int(np.searchsorted(self.due, upto, side="right"))
        if j <= self.next:
            return 0
        with self.spans.span("push") as box:
            eng, pool = self.engine, self.pool
            for e in range(self.next, j):
                s = int(self.stream[e])
                eng.push(s, pool.chunk(s, int(self.start[e]), int(self.size[e])))
                self.pushed_at[e] = load.clock() - self.origin
            box[0] = j - self.next
        self.next = j
        return box[0]

    def serve_ready(self) -> int:
        n = 0
        while self.engine.ready_windows().any():
            with self.spans.span("step") as box:
                out = self.engine.step()
                box[0] = self.scores.take(out, load.clock())
            n += box[0]
        return n

    def warm(self) -> None:
        """The schedule's first ``ramp_seconds``: streams staggered, rings filled."""
        self.origin = load.clock()
        self.run(self.origin + self.ramp)

    def run(self, t_end: float) -> None:
        """Follow the schedule until ``t_end`` (host clock)."""
        while True:
            now = load.clock()
            if now >= t_end:
                return
            self.push_due(now - self.origin)
            if self.serve_ready():
                continue
            if self.next >= len(self.due):
                return
            nxt = float(self.due[self.next])
            wait = min(self.origin + nxt, t_end) - load.clock()
            if wait > 0:
                with self.spans.span("wait_due"):
                    load.sleep(wait)
            if self.origin + nxt < t_end:
                self.push_due(nxt)  # slept until it fell due

    def finish(self, t0: float, t_end: float, t_untraced: float) -> dict:
        """Push what fell due before the window closed and score everything
        buffered; every window due in the window is attempted, and one never
        scored counts with the run's length."""
        lo, hi = t0 - self.origin, t_end - self.origin
        self.push_due(hi)
        self.serve_ready()
        sc = self.scores.arrays()
        due = self.window_due(sc["stream"], sc["idx"])
        in_win = (due >= lo) & (due < hi)
        n_due = self.windows_due(lo, hi)
        lat = (sc["t"][in_win] - self.origin - due[in_win]) * 1e3
        missing = max(n_due - int(in_win.sum()), 0)
        latency_ms = np.concatenate([lat, np.full(missing, (load.clock() - t0) * 1e3)])
        ev_in = (self.due >= lo) & (self.due < t_untraced - self.origin)
        lag_ms = (self.pushed_at[ev_in] - self.due[ev_in]) * 1e3
        pushed = np.zeros(len(self.phase), np.int64)
        done = ~np.isnan(self.pushed_at)
        np.add.at(pushed, self.stream[done], self.size[done])
        return dict(attempted=n_due, pushed=pushed, latency_ms=latency_ms, lag_ms=lag_ms)
