"""CPU fixtures: a copy of the benchmark in a temporary checkout, holding a
tiny detector cell (channels 4/8) that the Pallas interpreter runs quickly."""
from __future__ import annotations

import copy
import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TINY_MODEL = {"input_len": 1096, "channels": [4, 8], "kernel": 3, "hidden": 8, "n_classes": 2}


def _tiny_config(base: str, name: str) -> dict:
    cfg = json.loads((ROOT / "chipbench" / "configs" / f"{base}.json").read_text())
    cfg["name"] = name
    cfg["model"] = dict(TINY_MODEL)
    cfg["engine"]["batch_slots_per_chip"] = 8
    if cfg["bake"].get("prune"):
        cfg["bake"]["prune"] = {"keep": 4, "trim_frames": 1}
    return cfg


def _tiny_mix(base: str, streams: int) -> dict:
    mix = json.loads((ROOT / "chipbench" / "traffic" / f"{base}.json").read_text())
    mix.update(streams=streams, clips=3, clip_windows=4, check_streams=4)
    if "warm_rounds" in mix:
        mix["warm_rounds"] = 1
    if "ramp_seconds" in mix:
        mix["ramp_seconds"] = 1.0
    return mix


def make_root(tmp: Path) -> Path:
    """A checkout holding the benchmark and tiny cells ``tiny.catchup``,
    ``tiny_pm.catchup`` and ``tiny.realtime`` (with the open loop's latency
    metrics); the peaks table gains the CPU."""
    root = tmp / "checkout"
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "chipbench" / "configs" / "tiny.json").write_text(
        json.dumps(_tiny_config("shield8_int8", "tiny")))
    (root / "chipbench" / "configs" / "tiny_pm.json").write_text(
        json.dumps(_tiny_config("shield8_pruned_mixed", "tiny_pm")))
    (root / "chipbench" / "traffic" / "tiny_catchup.json").write_text(
        json.dumps(_tiny_mix("catchup", 16)))
    (root / "chipbench" / "traffic" / "tiny_realtime.json").write_text(
        json.dumps(_tiny_mix("realtime", 8)))
    bench["configs"] += [
        {"name": "tiny", "source": "test", "file": "chipbench/configs/tiny.json",
         "reduced": [], "why": "test"},
        {"name": "tiny_pm", "source": "test", "file": "chipbench/configs/tiny_pm.json",
         "reduced": [], "why": "test"},
    ]
    cells = [("tiny.catchup", "tiny", "tiny_catchup"), ("tiny_pm.catchup", "tiny_pm", "tiny_catchup"),
             ("tiny.realtime", "tiny", "tiny_realtime")]
    bench["workloads"] += [{"name": n, "config": c, "traffic": t, "chips": 1, "why": "test"}
                           for n, c, t in cells]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "int8.catchup" in m.get("workloads", []):
            m["workloads"] = m["workloads"] + ["tiny.catchup", "tiny_pm.catchup"]
    # the open loop's metrics, which no cell of BENCHMARK.json reports yet
    rt = {"workloads": ["tiny.realtime"], "unit": "ms", "better": "lower", "source": "host_clock"}
    bench["end_to_end"] += [dict(rt, name="decision_p50_ms", bound=0.25),
                            dict(rt, name="decision_p95_ms", bound=0.25)]
    bench["per_layer"] += [dict(rt, name=n, layer=layer, moves="decision_p95_ms")
                           for n, layer in (("step_ms_p95", "engine round"),
                                            ("generator_lag_p95_ms", "load generator"))]
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    peaks = json.loads((ROOT / "chipbench" / "peaks.json").read_text())
    peaks["devices"]["cpu"] = copy.deepcopy(peaks["devices"]["TPU v5 lite"])
    (root / "chipbench" / "peaks.json").write_text(json.dumps(peaks))
    return root


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> Path:
    return make_root(tmp_path_factory.mktemp("chipbench"))
