"""CPU tests of the per-layer device time (``chipbench/scopes.py`` and the
``*_device_us_per_window`` readers) on synthetic traces, and of the trace
format they rely on against a trace recorded on a TPU v5 lite."""
from __future__ import annotations

import collections
from pathlib import Path

import pytest

from chipbench import catalog, harness, load, scopes, trace

DATA = Path(__file__).resolve().parent / "data"

WIN = ("chipbench.window", 1_000, 11_000)


def test_instruction_names_of_device_events():
    assert scopes.instruction("%fusion.166 = f32[1,51,64]{2,1,0} fusion(f32[1,51,513]{2,1,0} %p)") \
        == "fusion.166"
    assert scopes.instruction("%conv1d_fused_q.3 = f32[64,1536,128]{2,1,0} custom-call()") \
        == "conv1d_fused_q.3"
    assert scopes.instruction("copy-start.2") == "copy-start.2"


def test_scope_seconds_are_unions_per_scope_summed_over_chips():
    sc = {"while.3": "frontend", "fusion.166": "frontend", "conv1d_fused_q.3": "conv1"}
    d0 = [("%while.3 = (s32[]) while()", 500, 4_000),
          ("%fusion.166 = f32[1] fusion()", 2_000, 3_000),  # inside the while: counted once
          ("%conv1d_fused_q.3 = f32[1] custom-call()", 5_000, 6_000),
          ("%copy.124 = f32[1] copy()", 6_000, 6_500),  # compiler-made: no scope
          ("%conv1d_fused_q.3 = f32[1] custom-call()", 10_500, 12_000)]  # clipped at 11,000
    d1 = [("%fusion.166 = f32[1] fusion()", 3_000, 5_000)]
    got = scopes.scope_seconds({"devices": {0: d0, 1: d1}, "host": [WIN]}, sc)
    assert got["frontend"] == pytest.approx((3_000 + 2_000) * 1e-9)
    assert got["conv1"] == pytest.approx(1_500e-9)
    assert got[""] == pytest.approx(500e-9)


def _readings(cell, trace, windows, op_scopes=None):
    spans = load.Spans()
    spans.name += ["step", "step"]
    spans.start += [2e-6, 20e-6]  # the second round starts after the segment
    spans.end += [3e-6, 21e-6]
    spans.windows += [windows, 99]
    return harness.Readings(cell=cell, seed=2**31 + 5, trace=trace, spans=spans,
                            segment=(1e-6, 11e-6), op_scopes=op_scopes)


def _engine(cell):
    return cell.family.engine(cell, cell.family.weights(cell.config["model"], 2**31 + 5))


def test_device_scope_readers_on_a_tiny_cell(tiny_root):
    """The readers take the op-to-layer map from the cell's own engine."""
    cell = catalog.load_cell("tiny.catchup", tiny_root)
    op_scopes = scopes.of_engine(_engine(cell))
    by_scope: dict[str, str] = {}
    for inst, scope in op_scopes.items():
        by_scope.setdefault(scope, inst)
    assert set(by_scope) == {"frontend", "conv0", "conv1", "dense0", "dense1", "softmax"}
    length = {"frontend": 4_000, "conv0": 1_000, "conv1": 1_000, "dense0": 800,
              "dense1": 200, "softmax": 100}
    t, dev = 1_000, []
    for scope, ns in length.items():
        dev.append((f"%{by_scope[scope]} = f32[8] op()", t, t + ns))
        t += ns
    r = _readings(cell, {"devices": {0: dev}, "host": [WIN]}, 4, op_scopes)
    per_window = {name: catalog.reader(name, tiny_root)(r)
                  for name in ("frontend_device_us_per_window", "conv_device_us_per_window",
                               "dense_device_us_per_window")}
    assert per_window == pytest.approx({"frontend_device_us_per_window": 4_000e-3 / 4,
                                        "conv_device_us_per_window": 2_000e-3 / 4,
                                        "dense_device_us_per_window": 1_000e-3 / 4})


def test_device_scope_readers_find_nothing_to_read(tiny_root, monkeypatch):
    """No trace, a trace without a chip, or a program without op scopes (an
    older program under this benchmark): each reader yields nothing."""
    from repro.serving.engine import MonitorEngine

    cell = catalog.load_cell("tiny.catchup", tiny_root)
    read = catalog.reader("conv_device_us_per_window", tiny_root)
    assert read(_readings(cell, None, 4)) is None
    assert read(_readings(cell, {"devices": {}, "host": [WIN]}, 4)) is None
    monkeypatch.delattr(MonitorEngine, "op_scopes")
    op_scopes = scopes.of_engine(_engine(cell))
    assert op_scopes is None
    dev = {0: [("%fusion.1 = f32[1] fusion()", 2_000, 3_000)]}
    assert read(_readings(cell, {"devices": dev, "host": [WIN]}, 4, op_scopes)) is None


def test_recorded_v5e_trace_names_ops_by_instruction_and_layer():
    """``record_trace.py --workload int8.catchup --seconds 1`` on a TPU v5
    lite: its ``.xplane.pb`` is 2.7 MB, so its listings are kept, the
    operations with a fifth column, the scope ``MonitorEngine.op_scopes()``
    gave each on the chip.  The planes ``trace.py`` reads are there, every
    device operation is named by its HLO instruction, and each layer of the
    forward holds operations."""
    planes = dict(line.split(": ", 1) for line in
                  (DATA / "int8_catchup.planes.txt").read_text().splitlines())
    devices = [p for p in planes if trace.DEVICE_PLANE.match(p)]
    assert devices == ["/device:TPU:0"]
    assert f"('{trace.OPS_LINE}', " in planes[devices[0]]
    assert any(p.startswith("/host:") for p in planes)
    seconds = collections.Counter()
    for line in (DATA / "int8_catchup.ops.txt").read_text().splitlines():
        chip, name, count, secs, scope = line.split("\t")
        assert chip == "0" and int(count) > 0
        assert name.startswith(f"%{scopes.instruction(name)} = ")
        seconds[scope] += float(secs)
    assert {"frontend", "conv0", "conv1", "conv2", "dense0", "dense1", "softmax"} \
        == set(seconds) - {""}
    # what the compiler added with no op_name (layout copies) is the rest
    assert sum(v for k, v in seconds.items() if k) / sum(seconds.values()) > 0.9
