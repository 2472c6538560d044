"""Knee sweep of an open-loop traffic mix at several stream counts.

    python3 chipbench/sweep.py --config shield8_int8 --traffic realtime \\
        --seconds 10 --streams 1024,2048,2560,3072,3584,4096 --seed 5

One process on one chip; each stream count is one run of the configuration
under the mix as the benchmark makes it, with the traffic file's
``streams`` replaced.  One JSON line per run: streams, offered windows/s,
the median and 95th percentile of the decision latency, correct, attempted
and failed.  The knee is the highest count whose ``decision_p95_ms`` stays
within the paper's 116 ms end-to-end budget with nothing failed; a cell
runs at four fifths of it, rounded down to a multiple of 64.
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
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--streams", required=True, help="comma-separated stream counts")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    runmod._paths()
    from chipbench import catalog, harness
    from chipbench.scenes import SR, WINDOW

    def ms(name):
        return {"name": name, "unit": "ms"}

    cell = catalog.make_cell(f"{args.config}.{args.traffic}", args.config, args.traffic, 1,
                             [ms("decision_p50_ms"), ms("decision_p95_ms")], [], runmod.ROOT)
    runmod.check_devices(cell.chips)
    runmod.enable_compile_cache()
    for n in (int(s) for s in args.streams.split(",")):
        cell.traffic["streams"] = n
        out = harness.run(cell, args.seed, args.seconds, False, time.perf_counter(),
                          root=runmod.ROOT)
        print(json.dumps({"streams": n, "offered_windows_per_s": n * SR / WINDOW,
                          **{k: v["value"] for k, v in out["metrics"].items()},
                          "correct": out["correct"], "attempted": out["attempted"],
                          "failed": out["failed"]}), flush=True)


if __name__ == "__main__":
    main()
