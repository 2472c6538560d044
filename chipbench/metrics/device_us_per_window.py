"""Chip microseconds per window: the union of operation intervals on each
chip inside the traced segment, summed over chips, over the windows scored
by rounds that started in the segment."""
from chipbench import trace


def read(r):
    if r.trace is None:
        return None
    _, n, _ = r.span_totals("step", *r.segment)
    busy = trace.busy_s(r.trace)
    return sum(busy.values()) / n * 1e6 if n and busy else None
