"""Durable crash-safe fleet state: checkpoints, the write-ahead chunk
journal, the exact state codec, and disk-fault injection.

The acceptance contract mirrors ``test_fault_tolerance.py`` but for process
*death* instead of worker faults, and it is *bitwise*, not approximate:

* kill a durable supervisor mid-round (pushes delivered, ``step()`` never
  ran) under a seeded fault plan, restore a brand-new supervisor from the
  ``--state-dir`` artifacts alone — the union of pre-crash and post-restore
  window scores, and the final ``TrackEvent`` lists, equal the uninterrupted
  run exactly;
* corrupt or tear the WAL tail (the routine end state of a crash
  mid-append) — replay truncates and counts, it never raises;
* inject disk faults (ENOSPC, torn writes, bit flips, slow fsyncs) through
  the filesystem seam — durability degrades and is counted
  (``wal_errors``/``ckpt_errors``), serving output stays bitwise identical.

The state-codec property tests run under real ``hypothesis`` when
installed, else the deterministic fallback shim
(tests/_hypothesis_fallback.py).
"""
import errno
import functools
import os

import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # bare container: deterministic-example fallback shim
    from _hypothesis_fallback import given, settings, st

from repro.data import features
from repro.models import cnn1d
from repro.serving.durability import (
    FRAME_HEADER,
    WAL_DROPPED,
    WAL_FAULTED,
    CheckpointStore,
    ChunkWAL,
    CorruptRecord,
    LocalFilesystem,
    dumps_state,
    frame,
    loads_state,
    read_frames,
    write_atomic,
)
from repro.serving.engine import MonitorEngine, SanitizePolicy, StreamRing
from repro.serving.faults import (
    DISK_KINDS,
    KINDS,
    Fault,
    FaultClock,
    FaultPlan,
    FaultyFilesystem,
    InjectedFault,
)
from repro.serving.faults import main as faults_main
from repro.serving.quantized_params import quantize_params
from repro.serving.supervisor import FleetSupervisor
from repro.serving.tracker import TrackEvent, VectorTemporalTracker

TRACK_KW = dict(ema_alpha=0.7, enter_threshold=0.02, exit_threshold=0.01,
                min_duration=1)
SUP_KW = dict(feature_kind="zcr", batch_slots=2,
              sanitize=SanitizePolicy(nonfinite="reject"), **TRACK_KW)


@functools.lru_cache(maxsize=1)
def _detector():
    """Bake one frozen artifact per module (cached, not a fixture, so the
    property tests can reach it from inside ``@given`` bodies too)."""
    cfg = cnn1d.CNNConfig(
        input_len=features.FEATURE_DIMS["zcr"], channels=(4, 8), hidden=8
    )
    params = cnn1d.init_params(jax.random.PRNGKey(0), cfg)
    qp = quantize_params(params, cfg, mode="int8", feature_kind="zcr")
    return cfg, qp


@pytest.fixture(scope="module")
def detector():
    return _detector()


def _assert_state_equal(a, b, path="$"):
    """Recursive *exact* equality: dtypes, shapes, scalar types and values
    all match — the codec contract is lossless, not approximately so."""
    if isinstance(a, np.ndarray):
        assert isinstance(b, np.ndarray), f"{path}: {type(b)} is not ndarray"
        assert a.dtype == b.dtype, f"{path}: dtype {a.dtype} != {b.dtype}"
        assert a.shape == b.shape, f"{path}: shape {a.shape} != {b.shape}"
        np.testing.assert_array_equal(a, b, err_msg=path)
    elif isinstance(a, dict):
        assert isinstance(b, dict) and set(a) == set(b), f"{path}: keys differ"
        for k in a:
            _assert_state_equal(a[k], b[k], f"{path}.{k}")
    elif isinstance(a, (list, tuple)) and not isinstance(a, TrackEvent):
        assert type(a) is type(b) and len(a) == len(b), f"{path}: {a} != {b}"
        for i, (x, y) in enumerate(zip(a, b)):
            _assert_state_equal(x, y, f"{path}[{i}]")
    elif isinstance(a, np.generic):
        assert type(a) is type(b), f"{path}: {type(a)} != {type(b)}"
        assert a == b, f"{path}: {a!r} != {b!r}"
    else:
        assert type(a) is type(b), f"{path}: {type(a)} != {type(b)}"
        assert a == b, f"{path}: {a!r} != {b!r}"


# ---------------------------------------------------------------------------
# CRC framing and the exact state codec
# ---------------------------------------------------------------------------


def test_frame_roundtrip_and_damage_detection():
    payloads = [b"alpha", b"bravo-bravo", b"charlie" * 9]
    blob = b"".join(frame(p) for p in payloads)
    out, clean = read_frames(blob)
    assert out == payloads and clean == len(blob)

    # torn tail: the final frame promises more bytes than exist
    out, clean = read_frames(blob[:-3])
    assert out == payloads[:2]
    assert clean == len(frame(payloads[0])) + len(frame(payloads[1]))

    # bit rot mid-stream: parsing stops at the damaged frame's offset
    rot = bytearray(blob)
    rot[len(frame(payloads[0])) + FRAME_HEADER.size + 2] ^= 0x10
    out, clean = read_frames(bytes(rot))
    assert out == payloads[:1] and clean == len(frame(payloads[0]))

    # empty payloads frame fine (WAL DROPPED markers have no chunk bytes)
    out, clean = read_frames(frame(b""))
    assert out == [b""] and clean == FRAME_HEADER.size


def test_state_codec_exact_roundtrip():
    payload = {
        "f32": np.linspace(-1.0, 1.0, 7, dtype=np.float32),
        "f64": np.array([1e-300, np.pi, -0.0]),
        "i64": np.arange(-3, 4, dtype=np.int64),
        "bools": np.array([True, False, True]),
        "mat": np.arange(6, dtype=np.float32).reshape(2, 3),
        "scalar_i": np.int64(-7),
        "scalar_f": np.float32(0.1),
        3: "int keys survive",
        "tuple": (1, 2.5, "x", None),
        "set": {4, 1, 2},
        "events": [TrackEvent(onset_idx=1, offset_idx=5, peak_score=0.9,
                              mean_score=0.5)],
        "nested": {"d": {0: np.float64(2.0)}, "l": [[1], [2, 3]]},
    }
    out = loads_state(dumps_state(payload))
    _assert_state_equal(payload, out)
    # the bytes themselves are a fixpoint of the round-trip
    assert dumps_state(out) == dumps_state(payload)
    # numpy bools deliberately collapse to python bool (json-native)
    assert loads_state(dumps_state(np.bool_(True))) is True
    with pytest.raises(TypeError):
        dumps_state(object())
    with pytest.raises(CorruptRecord):
        loads_state(b"\x01\x02\x03")


def chunk_sizes(max_chunk=96, max_chunks=24):
    return st.lists(
        st.floats(0.0, float(max_chunk)).map(int), min_size=1, max_size=max_chunks
    )


@given(chunk_sizes())
@settings(max_examples=25, deadline=None)
def test_streamring_state_survives_bytes_roundtrip(sizes):
    rng = np.random.default_rng(sum(sizes) + len(sizes))
    ring = StreamRing(window=64, hop=32, capacity_windows=3)
    for i, n in enumerate(sizes):
        ring.push(rng.standard_normal(n).astype(np.float32))
        if i % 2 and ring.ready:  # pop sometimes: exercise both heads
            ring.pop_window()
    sd = ring.state_dict()
    sd2 = loads_state(dumps_state(sd))
    _assert_state_equal(sd, sd2)
    ring2 = StreamRing(window=64, hop=32, capacity_windows=3)
    ring2.load_state_dict(sd2)
    assert dumps_state(ring2.state_dict()) == dumps_state(sd)


@given(st.lists(st.floats(0.0, 1.0), min_size=1, max_size=40))
@settings(max_examples=25, deadline=None)
def test_tracker_state_survives_bytes_roundtrip(probs):
    rng = np.random.default_rng(len(probs))
    tr = VectorTemporalTracker(3, ema_alpha=0.5, enter_threshold=0.6,
                               exit_threshold=0.4, min_duration=2)
    for p in probs:
        tr.update(np.full(3, p, np.float64), rng.random(3) < 0.8)
    sd = tr.state_dict()
    sd2 = loads_state(dumps_state(sd))
    _assert_state_equal(sd, sd2)
    tr2 = VectorTemporalTracker(3, ema_alpha=0.5, enter_threshold=0.6,
                                exit_threshold=0.4, min_duration=2)
    tr2.load_state_dict(sd2)
    assert dumps_state(tr2.state_dict()) == dumps_state(sd)


@given(st.lists(st.floats(0.1, 1.8), min_size=1, max_size=10))
@settings(max_examples=10, deadline=None)
def test_engine_snapshot_survives_bytes_roundtrip(sizes):
    """Push-only engine states (ingest mutates rings and counters but never
    calls the forward) round-trip through ``snapshot_bytes`` exactly."""
    cfg, qp = _detector()
    rng = np.random.default_rng(int(sum(sizes) * 100))

    def fresh():
        return MonitorEngine(qp, cfg, n_streams=2, feature_kind="zcr",
                             batch_slots=2, **TRACK_KW)

    eng = fresh()
    for i, f in enumerate(sizes):
        n = int(f * features.N_SAMPLES)
        eng.push(i % 2, rng.standard_normal(n).astype(np.float32))
    blob = eng.snapshot_bytes()
    _assert_state_equal(eng.snapshot(), loads_state(blob))
    eng2 = fresh()
    eng2.restore_bytes(blob)
    assert eng2.snapshot_bytes() == blob


# ---------------------------------------------------------------------------
# CheckpointStore: retention, corruption fallback, version pinning
# ---------------------------------------------------------------------------


def test_checkpoint_store_retention_and_corrupt_fallback(tmp_path):
    store = CheckpointStore(str(tmp_path / "ck"), retain=2)
    for v in range(1, 6):
        store.save(v, {"v": v, "arr": np.full(3, v, np.int64)})
    assert store.versions() == [4, 5]  # compacted down to `retain`

    # at_or_before pins the search below a known version: a newer orphan
    # (written pre-crash, never referenced by any meta) is not resurrected
    v, payload = store.load_latest(at_or_before=4)
    assert v == 4 and payload["v"] == 4

    # bit-rot the newest version: load() raises, load_latest() falls back
    blob = bytearray(store.fs.read_bytes(store._path(5)))
    blob[-1] ^= 0xFF
    with open(store._path(5), "wb") as fh:
        fh.write(bytes(blob))
    with pytest.raises(CorruptRecord):
        store.load(5)
    v, payload = store.load_latest()
    assert v == 4 and payload["v"] == 4 and store.corrupt_skipped == 1

    assert CheckpointStore(str(tmp_path / "empty")).load_latest() is None
    with pytest.raises(ValueError):
        CheckpointStore(str(tmp_path / "bad"), retain=0)


def test_write_atomic_publishes_all_or_nothing(tmp_path):
    plan = FaultPlan([Fault("torn_write", 0, magnitude=0.5)])
    fs = FaultyFilesystem(LocalFilesystem(), plan)
    target = str(tmp_path / "pub.bin")
    with pytest.raises(InjectedFault):
        write_atomic(fs, target, b"hello world")
    # the faulted write leaves neither the file nor its temp behind
    assert not os.path.exists(target) and not os.path.exists(target + ".tmp")
    write_atomic(fs, target, b"hello world")  # op 1: clean
    assert fs.read_bytes(target) == b"hello world"


# ---------------------------------------------------------------------------
# ChunkWAL: append/replay, torn-tail truncation, fsync policies
# ---------------------------------------------------------------------------


def test_chunk_wal_replay_and_tail_truncation(tmp_path):
    path = str(tmp_path / "wal.log")
    wal = ChunkWAL(path, fsync="always")
    c0 = np.arange(4, dtype=np.float32)
    wal.append(stream=0, seq=0, round_=1, chunk=c0)
    wal.append(stream=1, seq=0, round_=1, chunk=c0 * 2.0, flags=WAL_FAULTED)
    wal.append(stream=0, seq=1, round_=2, flags=WAL_FAULTED | WAL_DROPPED)

    recs = wal.replay()
    assert [(r.stream, r.seq, r.round, r.flags) for r in recs] == [
        (0, 0, 1, 0), (1, 0, 1, WAL_FAULTED),
        (0, 1, 2, WAL_FAULTED | WAL_DROPPED),
    ]
    np.testing.assert_array_equal(recs[0].chunk, c0)
    assert recs[0].chunk.dtype == np.float32
    assert recs[2].chunk.size == 0  # DROPPED marker carries no payload
    assert wal.truncations == 0

    # tear the tail mid-frame (crash mid-append) — replay drops exactly the
    # torn record, truncates the file back to its last clean frame, counts
    blob = wal.fs.read_bytes(path)
    wal.fs.truncate(path, len(blob) - 3)
    recs2 = wal.replay()
    assert [(r.stream, r.seq) for r in recs2] == [(0, 0), (1, 0)]
    assert wal.truncations == 1
    assert len(wal.fs.read_bytes(path)) < len(blob) - 3
    # a second replay of the now-clean file is stable: no further damage
    assert len(wal.replay()) == 2 and wal.truncations == 1

    # appended garbage (bit rot past the end) is likewise truncated away
    with open(path, "ab") as fh:
        fh.write(b"\x00garbage-not-a-frame")
    assert len(wal.replay()) == 2 and wal.truncations == 2

    wal.reset()
    assert wal.replay() == [] and not wal.fs.exists(path)
    wal.close()

    with pytest.raises(ValueError):
        ChunkWAL(str(tmp_path / "w2.log"), fsync="sometimes")
    with pytest.raises(ValueError):
        ChunkWAL(str(tmp_path / "w3.log"), fsync_interval=0)


def test_chunk_wal_fsync_policies_count_flushes(tmp_path):
    class CountingFS(LocalFilesystem):
        synced = 0

        def fsync(self, fh):
            type(self).synced += 1
            super().fsync(fh)

    for policy, interval, expect in (("always", 1, 6), ("interval", 3, 2),
                                     ("never", 1, 0)):
        fs = CountingFS()
        CountingFS.synced = 0
        wal = ChunkWAL(str(tmp_path / f"{policy}.log"), fs=fs, fsync=policy,
                       fsync_interval=interval)
        for i in range(6):
            wal.append(stream=0, seq=i, round_=0,
                       chunk=np.zeros(2, np.float32))
        assert CountingFS.synced == expect, policy
        assert len(wal.replay()) == 6


# ---------------------------------------------------------------------------
# FaultyFilesystem: deterministic disk faults on the seam
# ---------------------------------------------------------------------------


def test_faulty_filesystem_injects_deterministically(tmp_path):
    plan = FaultPlan([
        Fault("enospc", 0),
        Fault("torn_write", 1, magnitude=0.25),
        Fault("bit_flip", 2, magnitude=3.0),
    ])
    fs = FaultyFilesystem(LocalFilesystem(), plan)
    path = str(tmp_path / "f.bin")
    fh = fs.open_write(path)
    with pytest.raises(OSError) as ei:  # op 0: disk full, nothing written
        fs.write(fh, b"doomed")
    assert ei.value.errno == errno.ENOSPC
    with pytest.raises(InjectedFault):  # op 1: only a prefix reaches disk
        fs.write(fh, b"xxxxxxxx")
    fs.write(fh, b"ABCD")  # op 2: silent single-bit corruption
    fs.close(fh)
    data = fs.read_bytes(path)
    assert data[:2] == b"xx" and len(data) == 6
    flipped = [bin(a ^ b).count("1") for a, b in zip(data[2:], b"ABCD")]
    assert sum(flipped) == 1  # exactly one bit differs
    assert fs.injected == [("enospc", 0), ("torn_write", 1), ("bit_flip", 2)]

    # the CRC framing is what catches the silent flip on read-back
    fs2 = FaultyFilesystem(LocalFilesystem(),
                           FaultPlan([Fault("bit_flip", 0, magnitude=40.0)]))
    p2 = str(tmp_path / "framed.bin")
    fh = fs2.open_write(p2)
    fs2.write(fh, frame(b"precious payload"))
    fs2.close(fh)
    payloads, clean = read_frames(fs2.read_bytes(p2))
    assert payloads == [] and clean == 0


def test_fault_plan_disk_kinds_generate_and_cli(tmp_path, capsys):
    gen_kw = dict(n_streams=4, n_workers=2, n_rounds=10, n_faults=12,
                  kinds=KINDS)
    p1 = FaultPlan.generate(9, **gen_kw)
    assert p1.faults == FaultPlan.generate(9, **gen_kw).faults  # seeded
    assert any(f.kind in DISK_KINDS for f in p1.faults)
    assert p1.has_disk_faults
    p2 = FaultPlan.from_json(p1.to_json())
    assert p2.faults == p1.faults and p2.seed == 9
    # the default mix still excludes disk kinds (existing seeded plans in
    # the chaos sweep must not change under them)
    default = FaultPlan.generate(9, n_streams=4, n_workers=2, n_rounds=10)
    assert not default.has_disk_faults
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultPlan.generate(0, n_streams=2, n_workers=1, n_rounds=4,
                           kinds=("nope",))

    out = tmp_path / "plan.json"
    faults_main(["--seed", "3", "--rounds", "6", "--faults", "8",
                 "--kinds", "torn_write,enospc,drop_chunk",
                 "--out", str(out)])
    plan = FaultPlan.from_json(out.read_text())
    assert len(plan.faults) == 8
    assert {f.kind for f in plan.faults} <= {"torn_write", "enospc",
                                             "drop_chunk"}
    assert FaultPlan.from_json(plan.to_json()).faults == plan.faults
    capsys.readouterr()
    with pytest.raises(SystemExit):
        faults_main(["--kinds", "bogus", "--out", str(out)])
    assert "unknown fault kind" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# Cold-restart conformance: the acceptance-criteria tests
# ---------------------------------------------------------------------------

N_STREAMS, N_WORKERS, N_ROUNDS = 6, 2, 16


@pytest.fixture(scope="module")
def fleet_scene():
    """Precomputed delivery schedule + seeded fault plan, shared by every
    cold-restart test so interrupted and uninterrupted runs replay the
    identical scene."""
    rng = np.random.default_rng(7)
    schedule = [
        [(s, rng.normal(size=int(rng.uniform(0.4, 1.6) * features.N_SAMPLES)
                        ).astype(np.float32)) for s in range(N_STREAMS)]
        for _ in range(N_ROUNDS)
    ]
    plan = FaultPlan.generate(42, n_streams=N_STREAMS, n_workers=N_WORKERS,
                              n_rounds=N_ROUNDS, n_faults=6)
    return schedule, plan


def _fleet(detector, plan=None, **kw):
    cfg, qp = detector
    if plan is not None:
        # fresh copy per supervisor: worker-fault bookkeeping is stateful
        kw.update(faults=FaultPlan(list(plan.faults), seed=plan.seed),
                  clock=FaultClock(), dispatch_deadline_s=1.0)
    return FleetSupervisor(qp, cfg, n_streams=N_STREAMS, n_workers=N_WORKERS,
                           **SUP_KW, **kw)


def _drive(sup, schedule, *, start=0, cursor=None, upto=None):
    """Deliver the schedule, skipping pushes the restored cursor says were
    already delivered and steps the restored round says were committed.
    ``upto=k`` crashes mid-round k: its pushes are delivered, its ``step()``
    never runs."""
    out = []
    cursor = np.zeros(N_STREAMS, np.int64) if cursor is None else cursor
    ordinals = np.zeros(N_STREAMS, np.int64)
    for r, pushes in enumerate(schedule):
        for s, chunk in pushes:
            if ordinals[s] >= cursor[s]:
                sup.push(s, chunk)
            ordinals[s] += 1
        if r < start:
            continue
        if upto is not None and r >= upto:
            return out
        out.extend(sup.step())
    return out


def _score_map(scored):
    return {(w.stream, w.window_idx): (w.p_uav, w.smoothed, w.active)
            for w in scored}


@pytest.fixture(scope="module")
def fault_reference(detector, fleet_scene):
    schedule, plan = fleet_scene
    ref = _fleet(detector, plan)
    scores = _score_map(_drive(ref, schedule))
    events = ref.finalize()
    assert len(scores) > 0 and sum(len(e) for e in events) > 0
    return scores, events, ref.faulted_chunks.copy()


def test_cold_restart_bitwise_equal_clean_crash(detector, fleet_scene,
                                                tmp_path):
    """SIGKILL between rounds (no close, WAL empty at the crash instant):
    the restored fleet resumes at the checkpointed round and the combined
    run is bitwise identical to one that was never interrupted."""
    schedule, _ = fleet_scene
    ref = _fleet(detector)
    refd = _score_map(_drive(ref, schedule))
    ref_events = ref.finalize()

    d = str(tmp_path / "state")
    sup1 = _fleet(detector, state_dir=d)
    merged = _score_map(_drive(sup1, schedule[:7]))
    del sup1  # the crash: no close(), nothing flushed beyond the last step

    cfg, qp = detector
    sup2 = FleetSupervisor.restore_from_dir(qp, cfg, state_dir=d, **SUP_KW)
    assert sup2 is not None and sup2.round == 7
    assert sup2.replayed_chunks == 0  # between rounds: the WAL was empty
    s2 = _drive(sup2, schedule, start=sup2.round,
                cursor=sup2.pushed_chunks.copy())
    for k, v in _score_map(s2).items():
        assert merged.get(k, v) == v, f"overlap mismatch at {k}"
        merged[k] = v
    assert merged == refd
    assert sup2.finalize() == ref_events


def test_cold_restart_bitwise_equal_midround_crash_with_faults(
        detector, fleet_scene, fault_reference, tmp_path):
    """The acceptance-criteria test: crash *mid-round* (round-6 chunks
    pushed, step never ran) under a seeded fault plan.  The WAL replays the
    uncommitted pushes (``replayed_chunks > 0``), and scores, events and
    fault counters all match the uninterrupted faulted run bitwise."""
    schedule, plan = fleet_scene
    refd, ref_events, ref_faulted = fault_reference

    d = str(tmp_path / "state")
    sup1 = _fleet(detector, plan, state_dir=d)
    merged = _score_map(_drive(sup1, schedule, upto=6))
    del sup1

    cfg, qp = detector
    sup2 = FleetSupervisor.restore_from_dir(
        qp, cfg, state_dir=d,
        faults=FaultPlan(list(plan.faults), seed=plan.seed),
        clock=FaultClock(), dispatch_deadline_s=1.0, **SUP_KW)
    assert sup2 is not None
    assert sup2.replayed_chunks > 0  # the WAL actually did work
    s2 = _drive(sup2, schedule, start=sup2.round,
                cursor=sup2.pushed_chunks.copy())
    for k, v in _score_map(s2).items():
        assert merged.get(k, v) == v, f"overlap mismatch at {k}"
        merged[k] = v
    assert merged == refd
    assert sup2.finalize() == ref_events
    assert sup2.faulted_chunks.tolist() == ref_faulted.tolist()


def test_cold_restart_with_execution_lanes(detector, fleet_scene,
                                           fault_reference, tmp_path):
    """Same contract under threaded lanes: chunks queued but not yet
    drained at the crash never advanced the delivery cursor, so the driver
    re-delivers them after restore."""
    schedule, plan = fleet_scene
    refd, ref_events, _ = fault_reference

    d = str(tmp_path / "state")
    sup1 = _fleet(detector, plan, state_dir=d, lanes="threads")
    merged = _score_map(_drive(sup1, schedule, upto=9))
    del sup1

    cfg, qp = detector
    sup2 = FleetSupervisor.restore_from_dir(
        qp, cfg, state_dir=d, lanes="threads",
        faults=FaultPlan(list(plan.faults), seed=plan.seed),
        clock=FaultClock(), dispatch_deadline_s=1.0, **SUP_KW)
    assert sup2 is not None
    s2 = _drive(sup2, schedule, start=sup2.round,
                cursor=sup2.pushed_chunks.copy())
    sup2.close()
    merged.update(_score_map(s2))
    assert merged == refd
    assert sup2.finalize() == ref_events


def test_restore_from_empty_dir_returns_none(detector, tmp_path):
    cfg, qp = detector
    assert FleetSupervisor.restore_from_dir(
        qp, cfg, state_dir=str(tmp_path / "nothing"), **SUP_KW) is None


# ---------------------------------------------------------------------------
# Supervisor-level damage and disk faults
# ---------------------------------------------------------------------------


def test_supervisor_truncates_torn_wal_tail(detector, tmp_path):
    """A corrupted WAL tail — the routine end state of a crash mid-append —
    is truncated and counted on restore, never an unhandled exception."""
    cfg, qp = detector
    d = str(tmp_path / "state")
    rng = np.random.default_rng(3)
    chunks = [[rng.standard_normal(features.N_SAMPLES).astype(np.float32)
               for _ in range(2)] for _ in range(3)]

    sup = FleetSupervisor(qp, cfg, n_streams=2, n_workers=1, state_dir=d,
                          **SUP_KW)
    for r in range(2):
        for s in range(2):
            sup.push(s, chunks[r][s])
        sup.step()
    for s in range(2):  # crash mid-round 2: pushes WAL-logged, no step
        sup.push(s, chunks[2][s])
    del sup

    wal_path = os.path.join(d, "worker-000", "wal.log")
    assert os.path.exists(wal_path)
    with open(wal_path, "ab") as fh:
        fh.write(b"\x00half-written-frame")

    sup2 = FleetSupervisor.restore_from_dir(qp, cfg, state_dir=d,
                                            n_streams=2, n_workers=1,
                                            **SUP_KW)
    assert sup2 is not None
    assert sup2.wal_truncations == 1  # damage detected, cut, counted
    assert sup2.replayed_chunks == 2  # the clean prefix fully replayed
    assert sup2.round == 2
    assert len(sup2.step()) > 0  # and the fleet keeps serving


def test_disk_faults_degrade_durability_not_serving(detector, tmp_path):
    """ENOSPC / torn writes / bit flips / slow fsyncs on the durability
    seam are counted (``wal_errors``/``ckpt_errors``) while the serving
    output stays bitwise identical to a fault-free, non-durable run."""
    cfg, qp = detector
    plan = FaultPlan([
        Fault("slow_fsync", 1, magnitude=2.0),
        Fault("enospc", 2),
        Fault("torn_write", 5, magnitude=0.5),
        Fault("bit_flip", 7, magnitude=9.0),
    ])
    sup = FleetSupervisor(qp, cfg, n_streams=2, n_workers=1,
                          state_dir=str(tmp_path / "state"), faults=plan,
                          clock=FaultClock(), dispatch_deadline_s=30.0,
                          fsync="always", **SUP_KW)
    ref = FleetSupervisor(qp, cfg, n_streams=2, n_workers=1, **SUP_KW)

    rng = np.random.default_rng(5)
    scored, ref_scored = [], []
    for _ in range(4):
        for s in range(2):
            chunk = rng.standard_normal(features.N_SAMPLES).astype(np.float32)
            sup.push(s, chunk)
            ref.push(s, chunk)
        scored.extend(sup.step())
        ref_scored.extend(ref.step())
    sup.close()

    assert isinstance(sup._fs, FaultyFilesystem)  # auto-wrapped on the seam
    assert sup._fs.injected  # the plan actually fired
    assert sup.wal_errors + sup.ckpt_errors >= 1  # degradation was counted
    assert _score_map(scored) == _score_map(ref_scored)  # output untouched
    assert sup.finalize() == ref.finalize()
