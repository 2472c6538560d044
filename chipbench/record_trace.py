"""Record one traced run of a cell and keep its trace file.

    python3 chipbench/record_trace.py --workload int8.catchup --seed 5 \\
        --seconds 3 --out chipbench/tests/data/int8_catchup.xplane.pb

Runs the cell as ``run.py --trace 1`` does (its traced stretch is the last
three tenths of ``--seconds``, at most three seconds), copies the profiler's
``.xplane.pb`` to ``--out``, prints the result line, and writes beside the
trace the names and line counts of its planes (``.planes.txt``) and every
device operation inside the segment with its count and seconds
(``.ops.txt``), for reading by hand.  It needs the cell's chips.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import run as runmod  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    runmod._paths()
    from jax.profiler import ProfileData

    from chipbench import catalog, harness
    from chipbench import trace as tracemod

    cell = catalog.load_cell(args.workload, runmod.ROOT)
    runmod.check_devices(cell.chips)
    runmod.enable_compile_cache()
    out = harness.run(cell, args.seed, args.seconds, True, T_START, root=runmod.ROOT,
                      keep_trace=args.out)
    tr = tracemod.load(args.out)
    lo, hi = tracemod.segment(tr)
    tot: dict = {}
    for d, ev in tr["devices"].items():
        for name, a, b in ev:
            if b > lo and a < hi:
                n, s = tot.get((d, name), (0, 0.0))
                tot[(d, name)] = (n + 1, s + (b - a) / 1e9)
    with open(args.out + ".ops.txt", "w") as f:
        for (d, name), (n, s) in sorted(tot.items(), key=lambda kv: -kv[1][1]):
            f.write(f"{d}\t{name}\t{n}\t{s!r}\n")
    with open(args.out + ".planes.txt", "w") as f:
        for plane in ProfileData.from_file(args.out).planes:
            f.write(f"{plane.name}: {[(ln.name, len(list(ln.events))) for ln in plane.lines]}\n")
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
