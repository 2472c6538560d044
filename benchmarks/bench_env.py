"""Pinned benchmark environment, applied *before the first jax import*.

Perf rows are only comparable across PRs if the process environment that
produced them is pinned — the olmax run.sh idiom (SNIPPETS.md): force the
host platform device count so XLA's thread pools are carved identically on
every run, silence the TF log spam that skews short timings, and record
whether tcmalloc is preloaded (the single biggest allocator effect on
numpy-heavy benches) and whether the host carries TPU chips.

Usage, at the very top of a bench module (before anything imports jax)::

    from benchmarks import bench_env
    bench_env.apply()

``fingerprint()`` (callable any time after jax is importable) returns the
environment dict; ``fingerprint_id()`` is its short stable hash, attached to
every bench row via ``benchmarks.common.set_env_fingerprint`` so a JSON row
always names the environment that produced it.
"""
from __future__ import annotations

import glob
import hashlib
import json
import os
import platform
import sys

#: How a TPU host shows its chips before JAX is imported.  A v5e host has no
#: /dev/accel* nodes: its chips are PCI functions (Google's vendor id 0x1ae0,
#: device 0x0063) bound to /dev/vfio/<n>, measured on a v5litepod-4 host.
#: Older TPU VMs expose /dev/accel*.  Module-level so tests can point the
#: globs at a tmp path and exercise the TPU leg without hardware.
ACCEL_DEVICE_GLOB = "/dev/accel*"
PCI_DEVICE_GLOB = "/sys/bus/pci/devices/*"
TPU_PCI_IDS = {("0x1ae0", "0x0063")}  # (vendor, device): TPU v5e

_state: dict = {
    "applied": False,
    "late": False,
    "host_devices": None,
    "tpu_host": False,
}


def _pci_id(dev: str) -> tuple[str, str] | None:
    try:
        with open(os.path.join(dev, "vendor")) as v, open(os.path.join(dev, "device")) as d:
            return v.read().strip(), d.read().strip()
    except OSError:
        return None


def _tpu_hardware_present() -> bool:
    """True on a host that carries TPU chips (libtpu merely being
    pip-installed, as on a CPU-only machine, does not count)."""
    if glob.glob(ACCEL_DEVICE_GLOB):
        return True
    return any(_pci_id(d) in TPU_PCI_IDS for d in glob.glob(PCI_DEVICE_GLOB))


def apply(host_devices: int = 1) -> dict:
    """Pin the bench environment.  Must run before the first jax import —
    a late call is recorded in the fingerprint (the rows will say so)
    rather than silently measuring an unpinned process."""
    _state["late"] = "jax" in sys.modules
    _state["host_devices"] = host_devices
    # No TPU-only flag is pinned: the runtime on a v5e aborts on an XLA flag
    # it does not know (--xla_step_marker_location, in XLA_FLAGS and in
    # LIBTPU_INIT_ARGS alike), so the TPU leg only records the hardware.
    flags = [f"--xla_force_host_platform_device_count={host_devices}"]
    _state["tpu_host"] = _tpu_hardware_present()
    existing = os.environ.get("XLA_FLAGS", "")
    merged = existing.split() if existing else []
    for f in flags:
        key = f.split("=")[0]
        if not any(m.startswith(key) for m in merged):
            merged.append(f)
    os.environ["XLA_FLAGS"] = " ".join(merged)
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "4")  # no dataset warnings
    _state["applied"] = True
    return dict(_state)


def tcmalloc_loaded() -> bool:
    """The olmax runs LD_PRELOAD libtcmalloc; detect either the preload
    request or the library actually mapped into this process."""
    if "tcmalloc" in os.environ.get("LD_PRELOAD", ""):
        return True
    try:
        with open("/proc/self/maps") as f:
            return "tcmalloc" in f.read()
    except OSError:  # non-Linux host
        return False


def fingerprint() -> dict:
    """The machine-readable bench environment.  Imports jax (fine by now:
    ``apply()`` already ran, or ``late`` records that it did not)."""
    import jax

    return {
        "applied": _state["applied"],
        "late": _state["late"],
        "host_devices": _state["host_devices"],
        "tpu_host": _state["tpu_host"],
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
        "tcmalloc": tcmalloc_loaded(),
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "jax": jax.__version__,
        "python": platform.python_version(),
        "machine": platform.machine(),
    }


def fingerprint_id() -> str:
    """Short stable digest of :func:`fingerprint` — the per-row field."""
    blob = json.dumps(fingerprint(), sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:10]
