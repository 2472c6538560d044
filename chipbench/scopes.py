"""Device time of the forward's layers in a traced run.

The program runs each layer of its forward under a ``jax.named_scope``
(``frontend``, ``conv<i>``, ``flatten``, ``dense<i>``, ``softmax``), so every
compiled operation names its layer in its ``op_name``.  A device operation
of the trace is named by its HLO instruction (``%fusion.166 = f32[...]
...``); ``MonitorEngine.op_scopes()`` maps instruction names to scopes for
the forward as the cell's engine compiles it.  The harness takes that map
from the engine the run measured, once the traced window has closed
(:func:`of_engine`), and the reader here sums, per scope, the union of its
operations' intervals inside the traced segment, over chips.  A program
without ``op_scopes`` yields nothing.
"""
from __future__ import annotations

import re

import numpy as np

from chipbench import trace as tracemod

_INSTRUCTION = re.compile(r"^%?([\w.\-]+)")


def instruction(event_name: str) -> str:
    """The HLO instruction name a device event is named by."""
    m = _INSTRUCTION.match(event_name)
    return m.group(1) if m else event_name


def of_engine(engine) -> dict[str, str] | None:
    """``{instruction: scope}`` of the engine's forward, or None where the
    program has no ``op_scopes``."""
    return engine.op_scopes() if hasattr(engine, "op_scopes") else None


def scope_seconds(trace: dict, scopes: dict[str, str]) -> dict[str, float]:
    """Seconds in which an operation of each scope ran inside the segment,
    summed over chips; operations of no scope fall under ``""``."""
    lo, hi = tracemod.segment(trace)
    out: dict[str, float] = {}
    for events in trace["devices"].values():
        by: dict[str, list] = {}
        for e in events:
            by.setdefault(scopes.get(instruction(e[0]), ""), []).append(e)
        for scope, evs in by.items():
            iv = tracemod.union(tracemod._clipped(evs, lo, hi))
            out[scope] = out.get(scope, 0.0) + float(np.sum(np.diff(iv, axis=1))) / 1e9
    return out


def layer_seconds(r) -> dict[str, float] | None:
    """:func:`scope_seconds` of a traced run, computed once per reading."""
    if r.trace is None or not r.trace["devices"]:
        return None
    if not hasattr(r, "_layer_seconds"):
        r._layer_seconds = None if r.op_scopes is None else scope_seconds(r.trace, r.op_scopes)
    return r._layer_seconds


def us_per_window(r, pattern: str) -> float | None:
    """Device microseconds per window scored in the segment of the scopes
    that match ``pattern``."""
    secs = layer_seconds(r)
    if secs is None:
        return None
    _, n, _ = r.span_totals("step", *r.segment)
    if not n:
        return None
    return sum(s for k, s in secs.items() if re.fullmatch(pattern, k)) / n * 1e6
