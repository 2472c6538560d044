"""Unit tests for the shared benchmark row-builder helpers.

Round-latency percentile columns come from
:func:`benchmarks.common.percentile_fields`, which must degrade to null
fields on zero recorded rounds (a run that scores everything in warmup or
drain) instead of letting ``np.percentile`` raise on an empty list.
"""
import csv
import io
import json

import pytest

from benchmarks import common
from benchmarks.common import (
    PERCENTILE_KEYS,
    format_percentiles,
    median_us,
    percentile_fields,
    row,
    write_json,
)


def test_percentile_fields_empty_rounds_degrade_to_null():
    fields = percentile_fields([])
    assert fields == {k: None for k in PERCENTILE_KEYS}
    assert format_percentiles(fields) == "round latency n/a (0 rounds)"


def test_percentile_fields_scale_and_ordering():
    fields = percentile_fields([0.001, 0.002, 0.010, 0.004])
    assert set(fields) == set(PERCENTILE_KEYS)
    p50, p95, p99 = (fields[k] for k in PERCENTILE_KEYS)
    assert p50 <= p95 <= p99  # percentiles are monotone in q
    assert p50 == pytest.approx(3.0)  # seconds -> milliseconds
    assert p99 <= 10.0
    text = format_percentiles(fields)
    assert text.startswith("round latency p50/p95/p99 ")
    assert text.endswith(" ms")


def test_percentile_fields_single_round_collapses():
    fields = percentile_fields([0.005])
    assert all(fields[k] == 5.0 for k in PERCENTILE_KEYS)


def test_format_percentiles_null_safe_on_partial_fields():
    fields = percentile_fields([0.001])
    fields["round_p99_ms"] = None
    assert format_percentiles(fields) == "round latency n/a (0 rounds)"


def test_row_records_non_numeric_median_as_null(capsys):
    before = len(common._RECORDS)
    row("kernels/unit_test_na", "n/a", "derived text", extra_key=7)
    rec = common._RECORDS[-1]
    try:
        assert rec["median_us"] is None
        assert rec["extra_key"] == 7
        assert capsys.readouterr().out.strip() == (
            "kernels/unit_test_na,n/a,derived text"
        )
    finally:
        del common._RECORDS[before:]  # keep the module-global sink clean


def test_row_csv_quotes_commas_and_parses_back(capsys):
    """``derived`` strings routinely contain commas ("drop 0.0%, reject
    0.0%") — the emitted CSV must round-trip through ``csv.reader`` as
    exactly three fields, not shear into five."""
    before = len(common._RECORDS)
    derived = "drop 0.0%, reject 0.0%, p50 1.2 ms"
    row("kernels/unit_test_csv", 42.0, derived)
    try:
        out = capsys.readouterr().out
        parsed = list(csv.reader(io.StringIO(out)))
        assert len(parsed) == 1
        assert parsed[0] == ["kernels/unit_test_csv", "42.0", derived]
    finally:
        del common._RECORDS[before:]


def test_median_us_true_median_for_even_iters():
    # 4 samples: true median is the mean of the middle two (2.5s -> 2.5e6us);
    # the old sorted-index pick returned the upper-mid element (3.0s).
    assert median_us([4.0, 1.0, 3.0, 2.0]) == pytest.approx(2.5e6)
    assert median_us([5.0, 1.0, 3.0]) == pytest.approx(3.0e6)


def test_row_attaches_env_fingerprint_when_registered():
    before = len(common._RECORDS)
    try:
        common.set_env_fingerprint("deadbeef00")
        row("kernels/unit_test_env", 1.0, "a")
        assert common._RECORDS[-1]["env_fingerprint"] == "deadbeef00"
        common.set_env_fingerprint(None)
        row("kernels/unit_test_noenv", 1.0, "b")
        assert "env_fingerprint" not in common._RECORDS[-1]
    finally:
        common.set_env_fingerprint(None)
        del common._RECORDS[before:]


def test_write_json_merge_preserves_unmeasured_rows(tmp_path):
    path = tmp_path / "BENCH_unit.json"
    path.write_text(json.dumps({"kernels/old_row": {"median_us": 1.0, "derived": "x"}}))
    before = len(common._RECORDS)
    row("kernels/new_row", 2.0, "y")
    try:
        write_json(str(path), prefix="kernels/", merge=True)
        data = json.loads(path.read_text())
        assert "kernels/old_row" in data  # survived the merge
        assert data["kernels/new_row"]["median_us"] == 2.0
        write_json(str(path), prefix="kernels/", merge=False)
        assert "kernels/old_row" not in json.loads(path.read_text())
    finally:
        del common._RECORDS[before:]


def test_write_json_filters_by_prefix(tmp_path):
    before = len(common._RECORDS)
    row("serving/unit_a", 12.3456, "a")
    row("kernels/unit_b", 1.0, "b")
    try:
        path = tmp_path / "BENCH_unit.json"
        write_json(str(path), prefix="serving/")
        data = json.loads(path.read_text())
        assert "serving/unit_a" in data
        assert "kernels/unit_b" not in data
        assert data["serving/unit_a"]["median_us"] == 12.346  # rounded
        assert data["serving/unit_a"]["derived"] == "a"
    finally:
        del common._RECORDS[before:]


_BENCH_ENV_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_cpu_enable_fast_math=false"
from benchmarks import bench_env

state = bench_env.apply(host_devices=2)
assert state["late"] is False  # ran before the first jax import
flags = os.environ["XLA_FLAGS"].split()
assert "--xla_cpu_enable_fast_math=false" in flags  # caller flag survives
assert "--xla_force_host_platform_device_count=2" in flags
bench_env.apply(host_devices=4)  # key already present: no duplicate/override
assert os.environ["XLA_FLAGS"].split().count(
    "--xla_force_host_platform_device_count=2"
) == 1

fp = bench_env.fingerprint()
assert fp["applied"] and not fp["late"]
assert fp["device_count"] == 2  # the pinned count actually took effect
assert isinstance(fp["tcmalloc"], bool)
fid = bench_env.fingerprint_id()
assert len(fid) == 10 and fid == bench_env.fingerprint_id()  # stable
print("BENCH_ENV_OK")
"""


def test_bench_env_pins_before_jax_import_subprocess():
    """``apply()`` merges the pinned flags into caller-set XLA_FLAGS without
    clobbering them, never duplicates a key, and the forced host device
    count actually takes effect — in a subprocess, because the whole point
    is mutating the pre-jax-import environment."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = f"{root}:{root / 'src'}"
    proc = subprocess.run(
        [sys.executable, "-c", _BENCH_ENV_SCRIPT],
        cwd=root, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    assert "BENCH_ENV_OK" in proc.stdout


def test_bench_env_step_marker_leg_with_mocked_accel(tmp_path, monkeypatch):
    """The TPU leg of ``bench_env.apply()`` — exercised without hardware by
    pointing the device globs at a tmp path: a host is recognised by an
    /dev/accel* node or by a v5e PCI function, the fingerprint records it,
    and no step-marker flag is pinned (the v5e runtime aborts on it)."""
    import os

    from benchmarks import bench_env

    accel = tmp_path / "dev"
    pci = tmp_path / "pci"
    accel.mkdir()
    for name, vendor, device in [("nic", "0x1ae0", "0x0042"), ("tpu", "0x1ae0", "0x0063")]:
        (pci / name).mkdir(parents=True)
        (pci / name / "vendor").write_text(vendor + "\n")
        (pci / name / "device").write_text(device + "\n")
    monkeypatch.setattr(bench_env, "ACCEL_DEVICE_GLOB", str(accel / "accel*"))
    monkeypatch.setattr(bench_env, "PCI_DEVICE_GLOB", str(pci / "*"))
    monkeypatch.setenv("XLA_FLAGS", "")
    saved = dict(bench_env._state)
    try:
        state = bench_env.apply(host_devices=1)
        assert state["tpu_host"] is True  # the v5e PCI function
        assert os.environ["XLA_FLAGS"].split() == [
            "--xla_force_host_platform_device_count=1"
        ]
        assert "step_marker" not in os.environ["XLA_FLAGS"]

        # another Google PCI function alone (a NIC) is not a TPU host ...
        monkeypatch.setattr(bench_env, "PCI_DEVICE_GLOB", str(pci / "nic"))
        assert bench_env.apply(host_devices=1)["tpu_host"] is False
        # ... but an accel node is
        (accel / "accel0").touch()
        assert bench_env.apply(host_devices=1)["tpu_host"] is True
    finally:
        bench_env._state.clear()
        bench_env._state.update(saved)
