"""Median decision latency (open loop): from the due time of the chunk that
completed a window to the return of its score from ``step()``, over every
window that fell due in the measured window; a window never scored counts
with the whole run's length."""
from chipbench.harness import percentile


def read(r):
    if r.latency_ms is None or not len(r.latency_ms):
        return None
    return percentile(r.latency_ms, 50)
