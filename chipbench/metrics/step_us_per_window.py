"""Host microseconds inside ``MonitorEngine.step`` (batching, pack,
``device_put``, the forward, harvest, tracker) per window scored, over the
untraced rounds of the window."""


def read(r):
    t, n, _ = r.span_totals("step", r.t0, r.t_untraced)
    return t / n * 1e6 if n else None
