"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The trace (``*.xplane.pb``) is read with ``jax.profiler.ProfileData``.  A
device is a plane named ``/device:TPU:<n>``; its operations are the events
of the line ``XLA Ops``.  The harness's own host spans are events of the
host plane (``/host:CPU``), on the same clock.  The measured segment is the
host event ``chipbench.window``.

* busy time of a chip: the union of its operation intervals inside the
  segment; idle share: 1 - busy / segment, averaged over chips;
* device operations: total time per operation name, averaged over chips;
* idle gaps: every stretch of the segment in which no operation runs on
  chip 0, labelled by the harness host span that covers its midpoint, and
  summed per label.
"""
from __future__ import annotations

import glob
import os
import re
import shutil

import numpy as np

WINDOW_EVENT = "chipbench.window"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_LABELS = ("top_up", "step", "push", "wait_due", "drain")


def load(path: str, copy_to: str | None = None) -> dict:
    """``{"devices": {n: [(name, start_ns, end_ns)]}, "host": [(name, start_ns, end_ns)]}``
    from a trace file, or from the newest one under a profile directory
    (copied to ``copy_to`` first, where given)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True),
                       key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    if copy_to:
        shutil.copyfile(path, copy_to)
    data = ProfileData.from_file(path)
    devices: dict[int, list] = {}
    host: list = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ev = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ev.extend((e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events)
            devices[int(m.group(1))] = ev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events)
    return {"devices": devices, "host": host}


def segment(trace: dict) -> tuple[float, float]:
    """(start_ns, end_ns) of the measured segment."""
    for name, t0, t1 in trace["host"]:
        if name == WINDOW_EVENT:
            return t0, t1
    raise ValueError(f"trace holds no {WINDOW_EVENT!r} host event")


def _clipped(events, lo: float, hi: float) -> np.ndarray:
    iv = np.array([(max(a, lo), min(b, hi)) for _, a, b in events if b > lo and a < hi],
                  np.float64).reshape(-1, 2)
    return iv[np.argsort(iv[:, 0], kind="stable")] if len(iv) else iv


def union(intervals: np.ndarray) -> np.ndarray:
    """Sorted, disjoint cover of ``(start, end)`` intervals sorted by start."""
    out: list[list[float]] = []
    for a, b in intervals:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return np.array(out, np.float64).reshape(-1, 2)


def busy_s(trace: dict) -> dict[int, float]:
    """Seconds in which an operation ran, per chip, inside the segment."""
    lo, hi = segment(trace)
    return {d: float(np.sum(np.diff(union(_clipped(ev, lo, hi)), axis=1))) / 1e9
            for d, ev in trace["devices"].items()}


def window_s(trace: dict) -> float:
    lo, hi = segment(trace)
    return (hi - lo) / 1e9


def device_ops(trace: dict, top: int = 10) -> list[list]:
    """The ``top`` operation names by device time inside the segment,
    ``[[name, seconds averaged over chips], ...]``."""
    lo, hi = segment(trace)
    tot: dict[str, float] = {}
    n = max(1, len(trace["devices"]))
    for ev in trace["devices"].values():
        for name, a, b in ev:
            if b > lo and a < hi:
                tot[name] = tot.get(name, 0.0) + (min(b, hi) - max(a, lo)) / 1e9 / n
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:top]]


def idle_gaps(trace: dict, top: int = 10) -> list[list]:
    """Idle time of chip 0 inside the segment, summed by the harness host span
    covering each gap's midpoint (``idle`` where none does)."""
    lo, hi = segment(trace)
    devs = sorted(trace["devices"])
    if not devs:
        return []
    busy = union(_clipped(trace["devices"][devs[0]], lo, hi))
    edges = np.concatenate([[lo], busy.ravel(), [hi]]).reshape(-1, 2)
    gaps = edges[edges[:, 1] > edges[:, 0]]
    spans = [(n, a, b) for n, a, b in trace["host"] if n in HOST_LABELS]
    starts = np.array([a for _, a, _ in spans], np.float64)
    order = np.argsort(starts)
    starts = starts[order]
    spans = [spans[i] for i in order]
    tot: dict[str, float] = {}
    for a, b in gaps:
        mid = (a + b) / 2
        i = int(np.searchsorted(starts, mid, side="right")) - 1
        label = spans[i][0] if i >= 0 and spans[i][2] >= mid else "idle"
        tot[label] = tot.get(label, 0.0) + (b - a) / 1e9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:top]]

