"""95th percentile of how late the generator pushed each chunk against its
due time (open loop), over chunks due in the untraced part of the window."""
from chipbench.harness import percentile


def read(r):
    if r.lag_ms is None or not len(r.lag_ms):
        return None
    return percentile(r.lag_ms, 95)
