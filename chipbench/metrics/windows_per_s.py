"""Windows scored per second over the whole measured window (closed loop):
every window scored by a round that started inside the window, over the time
from the window's start to the end of its last round."""


def read(r):
    _, n, _ = r.span_totals("step", r.t0, r.t_end)
    if not n:
        return None
    return n / (r.t_last - r.t0)
