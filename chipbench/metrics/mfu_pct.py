"""The whole served step's share of the chip's peak: windows per second over
the untraced rounds, times the least chip time one window's layers need at
the published peak of each layer's stated precision (fp32 at the bf16
peak; the DSP front-end not counted), over the chips used."""


def read(r):
    _, n, _ = r.span_totals("step", r.t0, r.t_untraced)
    start, end, _ = r.spans.arrays("step", r.t0, r.t_untraced)
    if not n:
        return None
    rate = n / (end.max() - r.t0)
    return 100.0 * rate * r.peak_s_per_window / r.chips
