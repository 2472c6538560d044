"""Share of the traced segment in which no operation runs on a chip, averaged
over chips."""
from chipbench import trace


def read(r):
    if r.trace is None:
        return None
    busy = trace.busy_s(r.trace)
    if not busy:
        return None
    return 100.0 * (1.0 - sum(busy.values()) / len(busy) / trace.window_s(r.trace))
