"""95th percentile of the decision latency over every window that fell due
in the measured window (see decision_p50_ms)."""
from chipbench.harness import percentile


def read(r):
    if r.latency_ms is None or not len(r.latency_ms):
        return None
    return percentile(r.latency_ms, 95)
