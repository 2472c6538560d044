"""CPU runs of whole tiny cells through the harness, the Pallas kernels
interpreted: the check passes on a sound program, fails on a broken one, and
new configurations, mixes and metrics are found by name."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from chipbench import catalog, flops, harness, scopes
from chipbench.tests.conftest import ROOT

SECONDS = 2.0


def _run(root, cell, trace=False, seed=2**31 + 3):
    return harness.run(cell, seed, SECONDS, trace, time.perf_counter(), root=root)


@pytest.mark.parametrize("cell,e2e", [
    ("tiny.catchup", {"windows_per_s", "setup_s"}),
    ("tiny_pm.catchup", {"windows_per_s", "setup_s"}),
    ("tiny.realtime", {"decision_p50_ms", "decision_p95_ms", "setup_s"}),
])
def test_sound_program_is_correct(tiny_root, cell, e2e):
    out = _run(tiny_root, cell)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == e2e
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["windows_compared"] > 0
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == 1


def test_traced_run_reports_per_layer_and_new_metric(tiny_root, tmp_path):
    """A metric added as one file plus one entry is found and reported."""
    root = tmp_path / "co"
    shutil.copytree(tiny_root, root)
    (root / "chipbench" / "metrics" / "rounds_seen.py").write_text(
        "def read(r):\n    return float(len(r.spans.arrays('step')[0]))\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({"name": "rounds_seen", "unit": "rounds", "better": "higher",
                               "source": "host_clock", "layer": "engine round",
                               "moves": "windows_per_s", "workloads": ["tiny.catchup"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    out = _run(root, "tiny.catchup", trace=True)
    assert out["correct"], out["checks"]
    got = out["metrics"]
    assert {"push_us_per_window", "step_us_per_window", "mfu_pct", "rounds_seen"} <= set(got)
    assert "windows_per_s" not in got and got["rounds_seen"]["value"] > 0
    assert out["device"]["window_s"] > 0 and "busy_s" in out["device"]
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


LOCKSTEP_LOOP = '''
import numpy as np
from chipbench import load
from chipbench.scenes import WINDOW, ScenePool


class Loop:
    """Every stream pushes exactly one window, then one step() scores them."""

    def __init__(self, engine, mix, rng, spans, scores, *, seconds, capacity_windows):
        self.engine, self.spans, self.scores = engine, spans, scores
        self.pool = ScenePool(mix["streams"], mix["clips"], mix["clip_windows"], WINDOW, rng)
        self.pushed = np.zeros(mix["streams"], np.int64)
        self.first = 0

    def round(self):
        for s in range(len(self.pushed)):
            self.engine.push(s, self.pool.chunk(s, int(self.pushed[s]), WINDOW))
        self.pushed += WINDOW
        with self.spans.span("step") as box:
            box[0] = self.scores.take(self.engine.step(), load.clock())

    def warm(self):
        self.round()
        self.first = int(self.pushed.sum() // WINDOW)

    def run(self, t_end):
        while load.clock() < t_end:
            self.round()

    def finish(self, t0, t_end, t_untraced):
        return dict(attempted=int(self.pushed.sum() // WINDOW) - self.first, pushed=self.pushed,
                    latency_ms=None, lag_ms=None)
'''


def test_new_loop_and_mix_are_found_by_name(tiny_root, tmp_path):
    """A load loop and a traffic mix added as files plus one cell entry run
    through the harness unchanged."""
    root = tmp_path / "co"
    shutil.copytree(tiny_root, root)
    (root / "chipbench" / "loops" / "lockstep.py").write_text(LOCKSTEP_LOOP)
    (root / "chipbench" / "traffic" / "tiny_lockstep.json").write_text(json.dumps(
        {"loop": "lockstep", "streams": 8, "clips": 2, "clip_windows": 3, "check_streams": 4,
         "check_windows": 16}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny.lockstep", "config": "tiny",
                               "traffic": "tiny_lockstep", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if "int8.catchup" in m.get("workloads", []):
            m["workloads"].append("tiny.lockstep")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    out = _run(root, "tiny.lockstep")
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"windows_per_s", "setup_s"}
    assert out["failed"] == 0 and out["attempted"] > 0 and out["attempted"] % 8 == 0


ONE_CONV_FAMILY = '''
"""A one-conv detector with uniform weights: a family the harness has never seen."""
import jax
import jax.numpy as jnp

from chipbench.weights import key


def weights(model, seed):
    width, hidden = model["width"], model["hidden"]
    flatten = model["input_len"] // 2 * width
    shapes = {"conv0": (3, 1, width), "dense0": (flatten, hidden), "dense1": (hidden, 2)}

    @jax.jit
    def init(k):
        ks = iter(jax.random.split(k, 2 * len(shapes)))
        out = {}
        for name, shape in shapes.items():
            lim = (6.0 / (shape[0] * shape[1] if len(shape) == 3 else shape[0])) ** 0.5
            out[name] = {"w": jax.random.uniform(next(ks), shape, jnp.float32, -lim, lim),
                         "b": jax.random.uniform(next(ks), shape[-1:], jnp.float32, -0.1, 0.1)}
        return out

    return init(key(seed))


def engine(cell, params):
    from repro.models.cnn1d import CNNConfig
    from repro.serving.engine import MonitorEngine

    m, eng = cell.config["model"], cell.config["engine"]
    cfg = CNNConfig(input_len=m["input_len"], channels=(m["width"],), kernel=3,
                    hidden=m["hidden"], n_classes=2)
    return MonitorEngine(params, cfg, n_streams=cell.traffic["streams"], feature_kind="mfcc20",
                         on_device_features=True, batch_slots=eng["batch_slots_per_chip"],
                         precision="int8", capacity_windows=eng["capacity_windows"],
                         **eng["tracker"])


def layers(config):
    m = config["model"]
    n, w, h = m["input_len"], m["width"], m["hidden"]
    return [("conv0", 2 * n * 3 * w, "int8"), ("dense0", 2 * (n // 2) * w * h, "int8"),
            ("dense1", 2 * h * 2, "int8")]
'''

ONE_CONV_REFERENCE = '''
"""Reference of the one-conv detector: the 1D-CNN reference's layers, no prune."""
from pathlib import Path

from chipbench import catalog

_cnn = catalog._module(Path(__file__).with_name("shield8_cnn_reference.py"))
control_modes, track = _cnn.control_modes, _cnn.track


def p_uav(params, windows, config, modes):
    feats = _cnn.features(windows, modes.get("front_end", "fp32"))
    return _cnn.forward(params, feats, None, modes)[:, 1]
'''


def test_new_family_is_found_by_name(tiny_root, tmp_path):
    """A model family, its reference, a configuration and a cell added as
    new files plus entries in ``BENCHMARK.json`` run through the harness
    unchanged: weights, engine and operation counts come from the family."""
    root = tmp_path / "co"
    shutil.copytree(tiny_root, root)
    here = root / "chipbench"
    (here / "families" / "one_conv.py").write_text(ONE_CONV_FAMILY)
    (here / "configs" / "one_conv_reference.py").write_text(ONE_CONV_REFERENCE)
    tiny = json.loads((here / "configs" / "tiny.json").read_text())
    stated = {"front_end": "fp32", "conv0": "int8", "dense0": "int8", "dense1": "int8"}
    (here / "configs" / "one_conv.json").write_text(json.dumps(dict(
        tiny, name="one_conv", family="one_conv", reference="one_conv_reference",
        model={"input_len": 1096, "width": 4, "hidden": 8}, stated_precision=stated)))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "one_conv", "source": "test",
                             "file": "chipbench/configs/one_conv.json", "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "one_conv.catchup", "config": "one_conv",
                               "traffic": "tiny_catchup", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "int8.catchup" in m.get("workloads", []):
            m["workloads"].append("one_conv.catchup")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = catalog.load_cell("one_conv.catchup", root)
    assert flops.ops_per_window(cell.family.layers(cell.config)) == 2 * 1096 * 3 * 4 + 2 * 548 * 4 * 8 + 32
    assert set(cell.family.weights(cell.config["model"], 3)) == {"conv0", "dense0", "dense1"}
    out = _run(root, "one_conv.catchup")
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"windows_per_s", "setup_s"}
    assert out["failed"] == 0 and out["attempted"] > 0 and out["windows_compared"] > 0
    out = _run(root, "one_conv.catchup", trace=True)
    assert out["correct"], out["checks"]
    assert {"push_us_per_window", "step_us_per_window", "mfu_pct"} <= set(out["metrics"])


def test_traced_run_builds_one_engine(tiny_root, monkeypatch):
    """The op-to-layer map of a traced run comes from the engine the run
    measured: no second engine, and no second copy of the weights."""
    from repro.serving import engine

    built = []
    orig = engine.MonitorEngine.__init__

    def init(self, *a, **kw):
        built.append(self)
        orig(self, *a, **kw)

    monkeypatch.setattr(engine.MonitorEngine, "__init__", init)
    seen = []
    monkeypatch.setattr(scopes, "of_engine", lambda e: seen.append(e) or {})
    out = _run(tiny_root, "tiny.catchup", trace=True)
    assert out["correct"], out["checks"]
    assert len(built) == 1 and seen == built


def _alter_answer(monkeypatch):
    """An answer altered where it is produced: the first slot of every block
    comes back as the opposite, confident class."""
    from repro.serving import engine

    orig = engine.MonitorEngine._submit

    def submit(self, block):
        out = orig(self, block)
        flip = (out[0, 1] < 0.5).astype(out.dtype)
        return out.at[0].set(np.array([0.01, 0.99], np.float32) * flip
                             + np.array([0.99, 0.01], np.float32) * (1 - flip))

    monkeypatch.setattr(engine.MonitorEngine, "_submit", submit)


def _alter_tracker(monkeypatch):
    """The tracker's state altered where it is produced."""
    from repro.serving import tracker

    orig = tracker.VectorTemporalTracker.update

    def update(self, p, mask=None):
        state = orig(self, p, mask)
        state["smoothed"] = state["smoothed"] + 1e-6
        return state

    monkeypatch.setattr(tracker.VectorTemporalTracker, "update", update)


def _drop_answers(monkeypatch):
    """Windows taken from the rings and never answered: every round returns
    only the first half of its scores (none of a one-window round)."""
    from repro.serving import engine

    orig = engine.MonitorEngine.step

    def step(self):
        out = orig(self)
        return out[: len(out) // 2]

    monkeypatch.setattr(engine.MonitorEngine, "step", step)


@pytest.mark.parametrize("fault", [_alter_answer, _alter_tracker, _drop_answers])
@pytest.mark.parametrize("cell", ["tiny.catchup", "tiny.realtime"])
def test_broken_timed_path_is_not_correct(tiny_root, monkeypatch, fault, cell):
    fault(monkeypatch)
    out = _run(tiny_root, cell)
    assert not out["correct"], out["checks"]


def test_run_py_exits_nonzero_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for root in (ROOT, tmp_path):
        if root == tmp_path:  # a checkout of only BENCHMARK.json and the benchmark's files
            shutil.copy(ROOT / "BENCHMARK.json", root)
            shutil.copytree(ROOT / "chipbench", root / "chipbench",
                            ignore=shutil.ignore_patterns("__pycache__"))
        p = subprocess.run([sys.executable, "chipbench/run.py", "--workload", "int8.catchup",
                            "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
                           cwd=root, env=env, capture_output=True, text=True, timeout=300)
        assert p.returncode != 0
        assert p.stdout.strip() == ""
