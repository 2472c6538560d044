"""95th percentile of the duration of ``MonitorEngine.step`` calls (open
loop), over the untraced part of the window."""
from chipbench.harness import percentile


def read(r):
    _, _, d = r.span_totals("step", r.t0, r.t_untraced)
    return percentile(d * 1e3, 95) if len(d) else None
