"""Readings that set the limits of ``correct``, for one cell, in one process.

    python3 chipbench/control.py --workload int8.catchup --seconds 10 \\
        --seeds 11,12,13,14,15,16,17,18,19,20,21,22 --control-seeds 3 \\
        --lower all,front_end

Each seed is one run of the cell as the benchmark makes it.  Every run
prints the program's compared numbers (the lower readings) and, for the
first ``--control-seeds`` seeds, the same numbers with the configuration's
reference in the program's place, computed with the layers of each
``--lower`` group one precision step below the statement (``all``: every
layer and the front-end, the control whose smallest readings are the upper
ones; ``front_end``: the front-end alone; or ``+``-joined layer names).  One
JSON line per seed, then a summary line with the largest program reading and
the smallest reading of each control.  It needs the cell's chips, as
``run.py`` does.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import run as runmod  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--lower", default="all", help="comma-separated control groups")
    args = ap.parse_args(argv)
    runmod._paths()
    from chipbench import catalog, harness

    cell = catalog.load_cell(args.workload, runmod.ROOT)
    runmod.check_devices(cell.chips)
    runmod.enable_compile_cache()
    stated = cell.config["stated_precision"]
    groups = {g: cell.reference.control_modes(stated, None if g == "all" else g.split("+"))
              for g in args.lower.split(",")}
    lower: dict[str, float] = {}
    upper: dict[str, dict[str, float]] = {g: {} for g in groups}
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = harness.run(cell, seed, args.seconds, False, t, root=runmod.ROOT,
                          control=groups if i < args.control_seeds else None)
        line = {"seed": seed, "correct": out["correct"], "windows_compared": out["windows_compared"],
                "program": {k: v["value"] for k, v in out["checks"].items()},
                "metrics": {k: v["value"] for k, v in out["metrics"].items()}, "control": {}}
        for k, v in line["program"].items():
            lower[k] = max(lower.get(k, v), v)
        for g, got in out.get("control_checks", {}).items():
            line["control"][g] = {k: v["value"] for k, v in got.items()}
            for k, v in line["control"][g].items():
                upper[g][k] = min(upper[g].get(k, v), v)
        print(json.dumps(line), flush=True)
    print(json.dumps({"workload": args.workload, "lower": lower, "upper": upper}), flush=True)


if __name__ == "__main__":
    main()
