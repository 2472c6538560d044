"""Host spans of ``MonitorEngine`` (``serving/telemetry.py``): off by default
and invisible to the numbers, nested per round, and self times that add up to
the time spent in ``push`` and ``step``; and the named scopes that tie each
compiled operation of the forward to its layer."""
import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.precision_policy import PrecisionPolicy
from repro.core.pruning import plan_prune
from repro.data import features
from repro.models import cnn1d
from repro.serving import telemetry as telemetry_mod
from repro.serving.accelerator import SCOPES, _forward_quantized, hlo_scopes
from repro.serving.engine import MonitorEngine
from repro.serving.quantized_params import quantize_params
from repro.serving.telemetry import Telemetry

STEP_CHILDREN = ("engine.gather", "engine.pack", "engine.put", "engine.launch",
                 "engine.wait", "engine.tracker", "engine.commit")
NAMES = ("engine.push", "engine.step") + STEP_CHILDREN
N_STREAMS, SLOTS = 3, 2


@pytest.fixture(scope="module")
def detector():
    cfg = cnn1d.CNNConfig(
        input_len=features.FEATURE_DIMS["zcr"], channels=(4, 8), hidden=8
    )
    return cfg, cnn1d.init_params(jax.random.PRNGKey(0), cfg)


def _serve(detector, on=False, annotate=False, rounds=4, fault_round=None):
    """A tiny engine fed seeded uneven chunks; returns it and every score."""
    cfg, params = detector
    eng = MonitorEngine(params, cfg, n_streams=N_STREAMS, feature_kind="zcr",
                        batch_slots=SLOTS)
    eng.telemetry.on, eng.telemetry.annotate = on, annotate
    rng = np.random.default_rng(3)
    out = []
    for r in range(rounds):
        for s in range(N_STREAMS):
            n = int(rng.uniform(0.5, 1.6) * features.N_SAMPLES)
            eng.push(s, rng.standard_normal(n).astype(np.float32))
        if r == fault_round:
            def boom(items):
                raise RuntimeError("injected")
            eng.fault_hook = boom
            with pytest.raises(RuntimeError):
                eng.step()
            eng.fault_hook = None
        out += eng.step()
    out += eng.drain()
    return eng, [(w.stream, w.window_idx, w.p_uav, w.smoothed, w.active) for w in out]


def test_telemetry_off_records_nothing(detector):
    eng, out = _serve(detector)
    assert out and not eng.telemetry.on
    assert eng.telemetry.spans == []
    assert "telemetry" not in eng.snapshot()


@pytest.mark.parametrize("annotate", [False, True])
def test_scores_bitwise_equal_with_telemetry_on_and_off(detector, annotate):
    _, off = _serve(detector)
    eng, on = _serve(detector, on=True, annotate=annotate)
    assert on == off  # floats compared exactly
    assert {s[0] for s in eng.telemetry.spans} == set(NAMES)


def test_spans_nest_per_round_with_fixed_counts(detector):
    eng, out = _serve(detector, on=True)
    tel = eng.telemetry
    spans = tel.spans
    width = features.N_SAMPLES
    steps = {}
    for i, (name, start, end, parent, rnd, count) in enumerate(spans):
        assert end >= start
        if name == "engine.push":
            assert parent == -1 and count > 0
        elif name == "engine.step":
            assert parent == -1
            steps[i] = rnd
        else:
            assert spans[parent][0] == "engine.step"
            assert rnd == spans[parent][4]  # a round's spans share its id
            p0, p1 = spans[parent][1], spans[parent][2]
            assert p0 <= start and end <= p1
    # one root per round, numbered by the engine's committed rounds
    scored = [i for i in steps if spans[i][5] > 0]
    assert [steps[i] for i in scored] == list(range(eng.rounds))
    assert sum(spans[i][5] for i in steps) == len(out) == eng.windows_scored
    by = collections.defaultdict(list)
    for name, *_, count in spans:
        by[name].append(count)
    assert sum(by["engine.tracker"]) == sum(by["engine.commit"]) == len(out)
    assert sum(by["engine.gather"]) == len(out) * width * 4  # float32 windows
    assert sum(by["engine.pack"]) == len(out)  # live rows
    assert len(by["engine.pack"]) == eng.forward_calls  # one block fill each
    assert by["engine.launch"] == by["engine.wait"] == [SLOTS] * eng.forward_calls
    assert by["engine.put"] == [SLOTS * width * 4] * eng.forward_calls


def test_self_times_sum_to_push_and_step(detector):
    eng, _ = _serve(detector, on=True)
    tel = eng.telemetry
    total = 0.0
    for name in ("engine.push", "engine.step"):
        start, end, _ = tel.arrays(name)
        total += float(np.sum(end - start))
    parts = sum(float(np.sum(tel.self_times(n))) for n in NAMES)
    assert parts == pytest.approx(total, rel=1e-9)
    assert all((tel.self_times(n) >= -1e-12).all() for n in NAMES)


def test_failed_round_spans_are_closed_by_the_next(detector):
    """A raising forward leaves spans open; the next round's root closes
    them, so nothing of the failed round is adopted by the next one."""
    eng, out = _serve(detector, on=True, fault_round=1)
    _, clean = _serve(detector)
    assert out == clean
    spans = eng.telemetry.spans
    assert not eng.telemetry._stack
    for name, start, end, parent, rnd, _ in spans:
        assert end >= start > 0
        if parent >= 0:
            assert spans[parent][1] <= start and end <= spans[parent][2]
            assert rnd == spans[parent][4]


def test_self_times_on_a_fake_clock(monkeypatch):
    """step [0, 10) holds gather [1, 4) and wait [5, 9) which holds a pack
    [6, 7); a push [12, 13) outside: self times 3, 3, 3, 1, 1."""
    ticks = iter([0.0, 1.0, 4.0, 5.0, 6.0, 7.0, 9.0, 10.0, 12.0, 13.0])
    monkeypatch.setattr(telemetry_mod, "clock", lambda: next(ticks))
    tel = Telemetry()
    root = tel.open("engine.step", 7, step=True)
    g = tel.open("engine.gather")
    tel.close(g, 100)
    w = tel.open("engine.wait")
    p = tel.open("engine.pack")
    tel.close(p, 2)
    tel.close(w, 8)
    tel.close(root, 5)
    push = tel.open("engine.push", 8)
    tel.close(push, 64)
    want = {"engine.step": 3.0, "engine.gather": 3.0, "engine.wait": 3.0,
            "engine.pack": 1.0, "engine.push": 1.0}
    for name, t in want.items():
        np.testing.assert_array_equal(tel.self_times(name), [t])
    assert [(n, rnd) for n, _, _, _, rnd, _ in tel.spans] == [
        ("engine.step", 7), ("engine.gather", 7), ("engine.wait", 7), ("engine.pack", 7),
        ("engine.push", 8)]
    start, end, count = tel.arrays("engine.wait")
    assert (list(start), list(end), list(count)) == ([5.0], [9.0], [8])
    # the [lo, hi) window selects by start
    assert len(tel.arrays("engine.gather", 1.0, 1.5)[0]) == 1
    assert len(tel.arrays("engine.gather", 1.5, 20.0)[0]) == 0
    assert tel.self_times("engine.step", 0.5).size == 0
    assert Telemetry().arrays("engine.step")[0].size == 0


# ---------------------------------------------------------------------------
# Named scopes of the forward
# ---------------------------------------------------------------------------

COMPUTE = re.compile(
    r"^\s+(?:ROOT )?%(\S+) = .*? (fusion|convolution|dot|custom-call|while|reduce-window)\("
)


def _tiny_artifact(name):
    cfg = cnn1d.CNNConfig(input_len=1096, channels=(4, 8), hidden=8)
    params = cnn1d.init_params(jax.random.PRNGKey(0), cfg)
    if name == "int8":
        return cfg, quantize_params(params, cfg, mode="int8", feature_kind="mfcc20")
    spec = plan_prune(np.asarray(params["conv1"]["w"]), cfg.n_frames, keep=4, trim_frames=1)
    policy = PrecisionPolicy.parse("conv0/w=bf16,dense1/w=fp32", default="int8")
    return cfg, quantize_params(params, cfg, mode="int8", prune=spec, policy=policy,
                                feature_kind="mfcc20")


@pytest.mark.parametrize("name", ["int8", "pruned_mixed"])
@pytest.mark.parametrize("rows", [3, 8])
def test_compiled_forward_ops_carry_one_scope(name, rows):
    """Every compute instruction the forward emits names exactly one layer
    in its op_name, and every top-level one maps to a layer, except what the
    compiler made with no op_name of the program at all."""
    cfg, qp = _tiny_artifact(name)
    text = _forward_quantized.lower(
        qp, jax.ShapeDtypeStruct((rows, features.N_SAMPLES), jnp.float32),
        interpret=True, per_sample_acts=True, raw_windows=True,
    ).compile().as_text()
    for line in text.splitlines():
        on = re.search(r'op_name="(jit\([^"]*)"', line)
        if on and COMPUTE.match(line):
            assert len([p for p in on.group(1).split("/") if SCOPES.match(p)]) == 1, line
    scopes = hlo_scopes(text)
    entry = text[text.index("\nENTRY"):]
    top = [m.group(1) for m in map(COMPUTE.match, entry.splitlines()) if m]
    unmapped = [n for n in top if n not in scopes]
    for n in unmapped:
        line = next(ln for ln in entry.splitlines() if re.match(rf"^\s+(ROOT )?%{re.escape(n)} = ", ln))
        assert "op_name" not in line and "calls=" in line, line
    assert len(unmapped) <= 2
    want = {"frontend", "conv0", "conv1", "dense0", "dense1", "softmax"}
    assert {scopes[n] for n in top if n in scopes} == want


def test_hlo_scopes_resolves_fusions_through_their_computations():
    text = "\n".join([
        "%fused_a (p: f32[4]) -> f32[4] {",
        '  ROOT %m.1 = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(f)/conv1/mul"}',
        "}",
        "%fused_b (p: f32[4]) -> f32[4] {",
        '  %m.2 = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(f)/conv1/mul"}',
        '  ROOT %m.3 = f32[4]{0} add(%m.2, %p), metadata={op_name="jit(f)/dense0/add"}',
        "}",
        "ENTRY %main (x: f32[4]) -> f32[4] {",
        '  %x = f32[4]{0} parameter(0), metadata={op_name="x"}',
        "  %fusion.1 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_a",
        "  %fusion.2 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_b",
        '  %fusion.3 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_b, metadata={op_name="jit(f)/softmax/exp"}',
        "  ROOT %copy.4 = f32[4]{0} copy(%fusion.3)",
        "}",
    ])
    got = hlo_scopes(text)
    assert got["fusion.1"] == "conv1"  # through its computation
    assert "fusion.2" not in got  # two layers inside: no one scope
    assert got["fusion.3"] == "softmax"  # its own op_name wins
    assert "copy.4" not in got and "x" not in got


def test_engine_op_scopes_cover_every_layer(detector):
    cfg, params = detector
    eng = MonitorEngine(params, cfg, n_streams=2, feature_kind="zcr",
                        batch_slots=SLOTS)
    scopes = eng.op_scopes()
    assert set(scopes.values()) == {"frontend", "conv0", "conv1", "dense0", "dense1", "softmax"}
