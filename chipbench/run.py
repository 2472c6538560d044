"""Run one benchmark cell once and print its result line.

    python3 chipbench/run.py --workload int8.catchup --seed 7 --seconds 10 --trace 0

The cell, its configuration, traffic mix and metrics are found by name from
``BENCHMARK.json`` (see ``chipbench/catalog.py``).  The run needs a TPU with
at least the cell's chips: with none, it exits non-zero and prints no
result.  The last line of standard output is one JSON object; the numbers
compared for ``correct`` are also the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def _paths() -> None:
    for p in (ROOT / "src", ROOT):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed directory inside the
    checkout, or where ``JAX_COMPILATION_CACHE_DIR`` says; every program is
    cached, however fast it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def check_devices(chips: int) -> None:
    """Exit non-zero unless JAX sees a TPU with at least ``chips`` chips."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        sys.exit(f"chipbench: JAX found no accelerator: {e}")
    if devs[0].platform != "tpu":
        sys.exit(f"chipbench: JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        sys.exit(f"chipbench: the cell needs {chips} chip(s), JAX sees {len(devs)}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".tpu_logs"))
    _paths()
    from chipbench import catalog

    cell = catalog.load_cell(args.workload, ROOT)
    check_devices(cell.chips)
    if not (ROOT / "src" / "repro").is_dir():
        sys.exit("chipbench: the program under test (src/repro) is not in this checkout")
    enable_compile_cache()
    from chipbench import harness

    out = harness.run(args.workload, args.seed, args.seconds, bool(args.trace), T_START, root=ROOT)
    harness.print_result(out)


if __name__ == "__main__":
    main()
