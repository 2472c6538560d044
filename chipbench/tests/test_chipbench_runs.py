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

from chipbench import harness
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
