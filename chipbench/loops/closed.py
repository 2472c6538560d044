"""Closed loop: catch-up replay, one round per ``step()``.

Every stream replays recorded audio as fast as the engine takes it.  Before
each ``step()`` every ring is topped up with seeded chunks wherever it has
room for the next one, so no sample is dropped and every round carries one
window per stream.

Traffic keys: ``streams``, ``chunk_windows`` ([lo, hi] chunk length in
windows), ``chunk_cycle`` (seeded lengths per stream, reused cyclically),
``clips``, ``clip_windows`` and ``warm_rounds``.
"""
from __future__ import annotations

import numpy as np

from chipbench import load
from chipbench.scenes import WINDOW, ScenePool


class Loop:
    def __init__(self, engine, mix: dict, rng: np.random.Generator, spans, scores, *,
                 seconds: float, capacity_windows: int):
        self.engine, self.spans, self.scores = engine, spans, scores
        self.warm_rounds = mix["warm_rounds"]
        n_streams = mix["streams"]
        lo, hi = mix["chunk_windows"]
        self.sizes = (rng.uniform(lo, hi, (n_streams, mix["chunk_cycle"])) * WINDOW).astype(np.int64)
        self.pool = ScenePool(n_streams, mix["clips"], mix["clip_windows"],
                              int(self.sizes.max()), rng)
        self.rows = np.arange(n_streams)
        self.k = np.zeros(n_streams, np.int64)
        self.pushed = np.zeros(n_streams, np.int64)
        self.capacity = capacity_windows * WINDOW
        self.completed0 = 0

    def completed(self) -> int:
        """Windows completed by every push so far."""
        return int((self.pushed // WINDOW).sum())

    def top_up(self) -> None:
        with self.spans.span("top_up"):
            eng, pool, pushed = self.engine, self.pool, self.pushed
            while True:
                room = self.capacity - (pushed - eng.served_windows * WINDOW)
                nxt = self.sizes[self.rows, self.k % self.sizes.shape[1]]
                todo = np.flatnonzero(nxt <= room)
                if not todo.size:
                    return
                for s in todo.tolist():
                    eng.push(s, pool.chunk(s, pushed[s], nxt[s]))
                pushed[todo] += nxt[todo]
                self.k[todo] += 1

    def round(self) -> int:
        self.top_up()
        with self.spans.span("step") as box:
            out = self.engine.step()
            box[0] = self.scores.take(out, load.clock())
        return box[0]

    def warm(self) -> None:
        """``warm_rounds`` rounds; windows completed after them are attempted."""
        for _ in range(self.warm_rounds):
            self.round()
        self.completed0 = self.completed()

    def run(self, t_end: float) -> None:
        """Rounds until ``t_end`` (host clock)."""
        while load.clock() < t_end:
            self.round()

    def finish(self, t0: float, t_end: float, t_untraced: float) -> dict:
        """Score everything buffered; what the window attempted."""
        attempted = self.completed() - self.completed0
        while True:
            with self.spans.span("drain") as box:
                box[0] = self.scores.take(self.engine.step(), load.clock())
            if not box[0]:
                break
        return dict(attempted=attempted, pushed=self.pushed, latency_ms=None, lag_ms=None)
