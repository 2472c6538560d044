"""Benchmark driver: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Set FAST=1 to restrict the
accuracy tables to the headline feature set.
"""
from __future__ import annotations

import os
import traceback


def main() -> None:
    from benchmarks import (
        bench_fig45,
        bench_kernels,
        bench_table1,
        bench_table2,
        bench_table34,
        bench_table5,
        roofline,
    )

    print("name,us_per_call,derived")
    sections = [
        ("table1", bench_table1.main),
        ("table2", lambda: bench_table2.main(fast=bool(os.environ.get("FAST")))),
        ("fig45", bench_fig45.main),
        ("table34", bench_table34.main),
        ("table5", bench_table5.main),
        ("kernels", bench_kernels.main),
        ("roofline", roofline.main),
    ]
    failures = []
    for name, fn in sections:
        try:
            fn()
        except Exception as e:  # noqa: BLE001
            failures.append((name, e))
            print(f"{name}/ERROR,,{type(e).__name__}: {e}")
            traceback.print_exc()
    # BENCH_kernels.json (fused vs im2col conv rows included) is written by
    # bench_kernels.main itself — the single write site — so a failed section
    # here never clobbers the committed perf trajectory with partial data.
    if failures:
        raise SystemExit(f"{len(failures)} benchmark sections failed")


if __name__ == "__main__":
    main()
