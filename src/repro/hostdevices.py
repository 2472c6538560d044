"""Simulated-device bootstrap (single home for the XLA_FLAGS dance).

Sharded-batch dispatch on CPU needs N simulated XLA devices, and
``--xla_force_host_platform_device_count`` only takes effect if it is in
``XLA_FLAGS`` *before* the first jax import.  This module is deliberately
jax-import-free so drivers and benches can call it at module-load time;
what needs the override (``launch/monitor``) routes through here instead
of hand-rolling the env append.
"""
from __future__ import annotations

import os
import sys


def force_host_device_count(n: int) -> bool:
    """Request ``n`` simulated host devices; returns whether the flag landed.

    No-ops (returns False) when jax is already imported — too late for the
    flag to matter — or when a device-count override is already present
    (e.g. an outer harness set its own; never fight it).
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if n <= 1 or "jax" in sys.modules or "xla_force_host_platform_device_count" in flags:
        return False
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n}".strip()
    )
    return True


def shards_from_argv(argv: list[str] | None = None) -> int | None:
    """Extract a ``--shards`` value from raw argv before argparse exists.

    Understands both ``--shards N`` and ``--shards=N``; returns None when
    absent or malformed (argparse will produce the real error later).
    """
    args = sys.argv[1:] if argv is None else list(argv)
    for i, a in enumerate(args):
        try:
            if a == "--shards" and i + 1 < len(args):
                return int(args[i + 1])
            if a.startswith("--shards="):
                return int(a.split("=", 1)[1])
        except ValueError:
            return None
    return None
