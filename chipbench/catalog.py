"""Finds every piece of a cell by the names ``BENCHMARK.json`` gives.

* a configuration: ``chipbench/configs/<config>.json``; the family module
  that file names (``chipbench/families/<family>.py``), which defines
  ``weights(model, seed)`` (the float weights, from ``weights.key(seed)``),
  ``engine(cell, params)`` (the program under test, through its public
  constructor) and ``layers(config)`` (``[(layer, operations per window,
  stated precision)]``); and the plain reference module that file names
  (``chipbench/configs/<reference>.py``), whose one entry point for the
  check is ``p_uav(params, windows, config, modes)`` (the probability of
  "UAV" per window), beside ``control_modes`` and ``track``;
* a traffic mix: ``chipbench/traffic/<traffic>.json``, and the load loop
  its ``loop`` names (``chipbench/loops/<loop>.py``, see ``load.py``);
* a metric: ``chipbench/metrics/<metric>.py``, which defines ``read(r)``;
* a kernel's operations and bytes: ``chipbench/kernels/<kernel>.py``;
* the peaks of a device: ``chipbench/peaks.json``, keyed by ``device_kind``.

New cells, families, mixes, loops, metrics and kernels are new files plus new
entries in ``BENCHMARK.json``; nothing here names one of them.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def _module(path: Path):
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark module: {path}")
    spec = importlib.util.spec_from_file_location(f"chipbench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    family: object  # the configuration's family module
    reference: object  # the configuration's reference module
    end_to_end: list  # metric entries of BENCHMARK.json this cell reports
    per_layer: list


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") in e2e_names if "moves" in metric else True


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``BENCHMARK.json`` names ``name``, with the metrics it reports."""
    bench = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    e2e = [m for m in bench["end_to_end"] if _reports(m, name, set())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return make_cell(name, w["config"], w["traffic"], int(w["chips"]), e2e, per_layer, root)


def make_cell(name: str, config: str, traffic: str, chips: int, end_to_end: list,
              per_layer: list, root: Path = ROOT) -> Cell:
    """A cell of a configuration named in ``BENCHMARK.json`` and a traffic
    mix, reporting the metric entries given."""
    configs = {c["name"]: c for c in _json(root / "BENCHMARK.json")["configs"]}
    cfg = _json(root / configs[config]["file"])
    here = root / "chipbench"
    return Cell(
        name=name,
        chips=chips,
        config=cfg,
        traffic=_json(here / "traffic" / f"{traffic}.json"),
        family=family(cfg["family"], root),
        reference=_module(here / "configs" / f"{cfg['reference']}.py"),
        end_to_end=end_to_end,
        per_layer=per_layer,
    )


def family(name: str, root: Path = ROOT):
    """The module of a model family, which defines ``weights``, ``engine`` and ``layers``."""
    return _module(root / "chipbench" / "families" / f"{name}.py")


def reader(metric: str, root: Path = ROOT):
    """The ``read(r)`` function of a metric."""
    return _module(root / "chipbench" / "metrics" / f"{metric}.py").read


def loop(name: str, root: Path = ROOT):
    """The module of a load loop, which defines ``Loop``."""
    return _module(root / "chipbench" / "loops" / f"{name}.py")


def kernel(name: str, root: Path = ROOT):
    return _module(root / "chipbench" / "kernels" / f"{name}.py")


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is an error."""
    table = _json(root / "chipbench" / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no published peaks for device kind {device_kind!r} in peaks.json")
    return table["devices"][device_kind]
