"""One run of one cell: set up, measure, check, report.

``run(cell, seed, seconds, trace)`` builds the program under test of the
cell's configuration from seeded float weights, both through the
configuration's family module (``chipbench/families/<family>.py``), drives
it with the cell's traffic for ``seconds`` after a warm-up, checks what the
timed path scored against the configuration's plain reference, and returns
the result line.
"""
from __future__ import annotations

import gc
import statistics
import sys
import tempfile

import jax
import numpy as np

from chipbench import catalog, check, flops, load, scopes
from chipbench import trace as tracemod

#: longest stretch of a run the profiler records (seconds)
TRACE_SECONDS = 3.0


class Readings:
    """Everything a metric's ``read(r)`` may look at; see ``chipbench/metrics/``."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def span_totals(self, name: str, lo: float, hi: float) -> tuple[float, int, np.ndarray]:
        """(seconds inside spans ``name`` starting in [lo, hi), windows they
        scored, their durations)."""
        start, end, n = self.spans.arrays(name, lo, hi)
        return float(np.sum(end - start)), int(np.sum(n)), end - start


def percentile(values, q: float) -> float:
    """``q``-th percentile (q in 1..99) as Python's ``statistics.quantiles`` cuts it."""
    values = list(values)
    if len(values) < 2:
        return float(values[0]) if values else float("nan")
    return float(statistics.quantiles(values, n=100)[int(q) - 1])


def memory_peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        devices=None, root=catalog.ROOT, control: dict | None = None,
        keep_trace: str | None = None) -> dict:
    """One measured run of ``cell`` (a name, or a ``catalog.Cell``); ``t_start``
    is the host clock at process start.
    ``control`` (``{name: a precision per layer}``, see ``check.compare``)
    adds ``control_checks``: under each name, the same numbers with the
    reference computed so in the program's place (``chipbench/control.py``).
    ``keep_trace`` is a path to copy a traced run's ``.xplane.pb`` to."""
    if isinstance(cell, str):
        cell = catalog.load_cell(cell, root)
    devices = devices if devices is not None else jax.devices()[: cell.chips]
    rng = np.random.default_rng([seed, 1])
    params = cell.family.weights(cell.config["model"], seed)
    engine = cell.family.engine(cell, params)
    engine.precompile()
    spans, scores = load.Spans(), load.Scores()
    trace_len = min(TRACE_SECONDS, 0.3 * seconds) if trace else 0.0
    clock = load.clock

    gen = catalog.loop(cell.traffic["loop"], root).Loop(
        engine, cell.traffic, rng, spans, scores, seconds=seconds,
        capacity_windows=cell.config["engine"]["capacity_windows"])
    gen.warm()
    t0 = clock()
    setup_s = t0 - t_start
    t_end = t0 + seconds
    t_untraced = t_end - trace_len - 0.5 if trace else t_end

    profile_dir = None
    seg = (t_end, t_end)
    if trace:
        gen.run(t_untraced)
        profile_dir = tempfile.TemporaryDirectory(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # no Python function events: they slow the host
        opts.host_tracer_level = 1  # the harness's annotations, little else
        jax.profiler.start_trace(profile_dir.name, profiler_options=opts)
        spans.annotate = True
        seg_lo = clock()
        with jax.profiler.TraceAnnotation(tracemod.WINDOW_EVENT):
            gen.run(t_end)
        seg = (seg_lo, clock())
        spans.annotate = False
        jax.profiler.stop_trace()
    else:
        gen.run(t_end)
    t_last = clock()

    res = gen.finish(t0, t_end, t_untraced)
    attempted, pushed = res["attempted"], res["pushed"]
    mem_peak = memory_peak_bytes(devices)

    sc = scores.arrays()
    tr = op_scopes = None
    if trace:
        tr = tracemod.load(profile_dir.name, keep_trace)
        profile_dir.cleanup()
        op_scopes = scopes.of_engine(engine)

    device = jax.devices()[0]
    r = Readings(
        cell=cell, seed=seed, seconds=seconds, chips=cell.chips,
        t0=t0, t_end=t_end, t_last=t_last, t_untraced=t_untraced,
        setup_s=setup_s, spans=spans, scores=sc, latency_ms=res["latency_ms"],
        lag_ms=res["lag_ms"], trace=tr, segment=seg, op_scopes=op_scopes,
        peak_s_per_window=flops.peak_seconds_per_window(
            cell.family.layers(cell.config), catalog.peaks(device.device_kind, root)),
    )

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = catalog.reader(m["name"], root)(r)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    del engine, gen.engine  # the reference runs with the program's state freed
    gc.collect()

    checks, n_compared = check.compare(cell, params, gen.pool, sc, pushed, seed)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    dev = {"platform": device.platform, "kind": device.device_kind, "count": len(devices),
           "memory_peak_bytes": mem_peak}
    failed = int(min(checks["unscored"]["value"], attempted))
    out = {"correct": bool(correct), "attempted": int(attempted), "failed": failed,
           "metrics": metrics, "device": dev}
    if tr is not None:
        busy = tracemod.busy_s(tr)
        dev["busy_s"] = float(np.mean(list(busy.values()))) if busy else 0.0
        dev["window_s"] = tracemod.window_s(tr)
        out["breakdown"] = {"device_ops": tracemod.device_ops(tr),
                            "idle_gaps": tracemod.idle_gaps(tr)}
    out["windows_compared"] = n_compared
    if control:
        out["control_checks"] = {
            name: check.compare(cell, params, gen.pool, sc, pushed, seed, control=modes)[0]
            for name, modes in control.items()}
    out["checks"] = checks
    return out


def print_result(out: dict) -> None:
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    import json

    print(json.dumps(out), flush=True)
