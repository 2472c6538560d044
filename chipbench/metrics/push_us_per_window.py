"""Host microseconds spent topping the rings up (the harness's loop of
``MonitorEngine.push`` calls) per window scored, over the untraced rounds of
the window."""


def read(r):
    t, _, _ = r.span_totals("top_up", r.t0, r.t_untraced)
    _, n, _ = r.span_totals("step", r.t0, r.t_untraced)
    return t / n * 1e6 if n else None
