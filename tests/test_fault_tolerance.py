"""Fault-tolerance conformance: crash-recoverable engine state, ingest
hardening, and (from the supervisor sections onward) the chaos suite over
the fleet supervisor's deterministic fault-injection harness.

The central contract everywhere in this file is *bitwise*, not approximate:

* kill an engine mid-scene, restore its snapshot into a fresh engine built
  from the same baked artifact, finish the scene — the window scores and
  ``TrackEvent`` lists equal the uninterrupted run exactly;
* inject faults into some streams/workers — every stream not directly hit
  by a data-destroying fault produces event lists identical to the
  fault-free run (per-sample activation scales make rows co-batch
  independent; snapshot/restore and transactional rounds make worker death
  lossless).
"""
import jax
import numpy as np
import pytest

from repro.data import features
from repro.models import cnn1d
from repro.serving.engine import MonitorEngine, SanitizePolicy, StreamRing
from repro.serving.faults import Fault, FaultClock, FaultPlan
from repro.serving.quantized_params import quantize_params
from repro.serving.supervisor import FleetSupervisor

TRACK_KW = dict(ema_alpha=0.7, enter_threshold=0.02, exit_threshold=0.01,
                min_duration=1)


@pytest.fixture(scope="module")
def detector():
    cfg = cnn1d.CNNConfig(
        input_len=features.FEATURE_DIMS["zcr"], channels=(4, 8), hidden=8
    )
    params = cnn1d.init_params(jax.random.PRNGKey(0), cfg)
    # Bake once: every engine/worker in this module serves the same frozen
    # artifact, exactly like a supervisor rebuilding dead workers.
    qp = quantize_params(params, cfg, mode="int8", feature_kind="zcr")
    return cfg, qp


def _scene_audio(rng, n_streams, n_win):
    return rng.standard_normal(
        (n_streams, n_win * features.N_SAMPLES)
    ).astype(np.float32)


def _delivery_schedule(rng, audio):
    """Uneven real-world-ish chunk boundaries, precomputed so interrupted
    and uninterrupted runs replay the identical delivery."""
    n_streams, total = audio.shape
    schedule = []  # list of rounds; each round: [(stream, lo, hi), ...]
    cursors = [0] * n_streams
    while any(c < total for c in cursors):
        rnd = []
        for s in range(n_streams):
            if cursors[s] >= total:
                continue
            n = int(rng.uniform(0.3, 1.7) * features.N_SAMPLES)
            rnd.append((s, cursors[s], min(total, cursors[s] + n)))
            cursors[s] += n
        schedule.append(rnd)
    return schedule


def _drive(engine, audio, schedule, *, start_round=0, scores=None):
    """Deliver schedule[start_round:], stepping once per delivery round and
    draining at the end; collects per-stream p_uav lists."""
    scores = {s: [] for s in range(audio.shape[0])} if scores is None else scores
    for rnd in schedule[start_round:]:
        for s, lo, hi in rnd:
            engine.push(s, audio[s, lo:hi])
        for ws in engine.step():
            scores[ws.stream].append(ws.p_uav)
    while True:
        scored = engine.step()
        if not scored:
            break
        for ws in scored:
            scores[ws.stream].append(ws.p_uav)
    return scores


# ---------------------------------------------------------------------------
# Engine snapshot / restore (crash-recoverable state)
# ---------------------------------------------------------------------------


def test_engine_kill_restore_bitwise_equal_to_uninterrupted(detector):
    """The acceptance-criteria conformance test: kill an engine mid-scene,
    restore its snapshot into a fresh engine built from the same baked
    artifact, finish the scene — scores and events bitwise equal the run
    that was never interrupted."""
    cfg, qp = detector
    rng = np.random.default_rng(7)
    n_streams, n_win = 3, 6
    audio = _scene_audio(rng, n_streams, n_win)
    schedule = _delivery_schedule(rng, audio)
    kill_at = len(schedule) // 2
    assert 0 < kill_at < len(schedule)

    def fresh():
        return MonitorEngine(
            qp, cfg, n_streams=n_streams, feature_kind="zcr",
            batch_slots=2, **TRACK_KW,
        )

    ref_engine = fresh()
    ref_scores = _drive(ref_engine, audio, schedule)
    ref_events = ref_engine.finalize()
    assert sum(len(v) for v in ref_scores.values()) == n_streams * n_win
    assert sum(len(e) for e in ref_events) > 0

    # interrupted leg: run the first half, snapshot, "kill" the engine, and
    # revive the snapshot in a brand-new engine
    first = fresh()
    scores = _drive(first, audio, schedule[:kill_at])
    snap = first.snapshot()
    del first  # the crash

    revived = fresh()
    revived.restore(snap)
    scores = _drive(revived, audio, schedule, start_round=kill_at, scores=scores)
    events = revived.finalize()

    for s in range(n_streams):
        np.testing.assert_array_equal(
            np.asarray(scores[s], np.float64), np.asarray(ref_scores[s], np.float64)
        )
        assert events[s] == ref_events[s]
    # counters survive the crash too
    assert revived.windows_scored == ref_engine.windows_scored
    assert revived.dropped_samples == ref_engine.dropped_samples


def test_engine_snapshot_is_isolated_from_live_engine(detector):
    """Snapshot then keep running: later rounds must not mutate the snapshot
    (a supervisor holds last-good snapshots across many rounds)."""
    cfg, qp = detector
    rng = np.random.default_rng(8)
    engine = MonitorEngine(qp, cfg, n_streams=2, feature_kind="zcr", **TRACK_KW)
    audio = _scene_audio(rng, 2, 3)
    for s in range(2):
        engine.push(s, audio[s, : features.N_SAMPLES])
    engine.step()
    snap = engine.snapshot()
    ring_r = [sd["r"] for sd in snap["rings"]]
    ema = snap["tracker"]["_ema"].copy()
    for s in range(2):
        engine.push(s, audio[s, features.N_SAMPLES:])
    engine.drain()
    assert [sd["r"] for sd in snap["rings"]] == ring_r
    np.testing.assert_array_equal(snap["tracker"]["_ema"], ema)


def test_engine_restore_validates_stream_count(detector):
    cfg, qp = detector
    e3 = MonitorEngine(qp, cfg, n_streams=3, feature_kind="zcr")
    e2 = MonitorEngine(qp, cfg, n_streams=2, feature_kind="zcr")
    with pytest.raises(ValueError, match="3 stream"):
        e2.restore(e3.snapshot())


def _assert_snapshots_equal(a: dict, b: dict):
    """Deep bitwise equality over EVERY snapshot field — rings, tracker
    arrays and events, all counters, pending evictions."""
    assert a.keys() == b.keys()
    assert a["pending_evictions"] == b["pending_evictions"]
    assert len(a["rings"]) == len(b["rings"])
    for ra, rb in zip(a["rings"], b["rings"]):
        assert ra.keys() == rb.keys()
        for k in ra:
            np.testing.assert_array_equal(ra[k], rb[k], err_msg=f"rings.{k}")
    for part in ("tracker", "counters"):
        assert a[part].keys() == b[part].keys()
        for k in a[part]:
            if k == "events":
                assert a[part][k] == b[part][k]
            else:
                np.testing.assert_array_equal(
                    a[part][k], b[part][k], err_msg=f"{part}.{k}"
                )


def test_engine_snapshot_restore_roundtrips_every_field(detector):
    """Regression (the pending-evictions snapshot bug): ``snapshot()``
    omitted ``_pending_evictions`` and ``restore()`` reset it to ``[]``, so
    a revive from a snapshot taken between a stream's de-admission and the
    supervisor's ``take_evictions()`` left the stream de-admitted but never
    evicted.  The conformance is now total: a restored engine's snapshot
    deep-equals the original over every field, including a live pending
    eviction, which the revived engine still hands to its supervisor."""
    from repro.serving.batching import AdmissionPolicy

    cfg, qp = detector
    rng = np.random.default_rng(41)
    W = features.N_SAMPLES
    kw = dict(
        feature_kind="zcr", batch_slots=2, capacity_windows=1,
        sanitize=SanitizePolicy(nonfinite="reject"),
        admission=AdmissionPolicy(evict_overflow_rounds=1), **TRACK_KW,
    )
    engine = MonitorEngine(qp, cfg, n_streams=2, **kw)
    engine.push(0, rng.standard_normal(2 * W).astype(np.float32))  # overflow
    engine.push(1, rng.standard_normal(W).astype(np.float32))
    engine.step()  # stream 0 de-admitted, eviction pending, NOT collected

    snap = engine.snapshot()
    assert snap["pending_evictions"] == [0]  # the field the bug dropped

    revived = MonitorEngine(qp, cfg, n_streams=2, **kw)
    revived.restore(snap)
    _assert_snapshots_equal(revived.snapshot(), snap)
    # the revived engine still surfaces the eviction to its supervisor
    assert revived.take_evictions() == [0]
    assert engine.take_evictions() == [0]

    # drained state round-trips too (pending_evictions now empty)
    snap2 = engine.snapshot()
    assert snap2["pending_evictions"] == []
    again = MonitorEngine(qp, cfg, n_streams=2, **kw)
    again.restore(snap2)
    _assert_snapshots_equal(again.snapshot(), snap2)


def test_ring_state_dict_validates_geometry():
    sd = StreamRing(window=10, hop=5, capacity_windows=2).state_dict()
    with pytest.raises(ValueError, match="hop"):
        StreamRing(window=10, hop=10, capacity_windows=2).load_state_dict(sd)


# ---------------------------------------------------------------------------
# Ingest hardening (SanitizePolicy)
# ---------------------------------------------------------------------------


def test_sanitize_reject_blocks_nan_poison_and_isolates_streams(detector):
    """A NaN-emitting microphone on stream 0: with the reject policy its
    chunks are refused (counted per stream), its tracker EMA stays finite,
    and stream 1 — fed the identical audio as a clean reference run — stays
    bitwise identical."""
    cfg, qp = detector
    rng = np.random.default_rng(9)
    n_win = 4
    clean = _scene_audio(rng, 2, n_win)

    ref = MonitorEngine(qp, cfg, n_streams=2, feature_kind="zcr", **TRACK_KW)
    for s in range(2):
        ref.push(s, clean[s])
    ref_scores = {s: [] for s in range(2)}
    for ws in ref.drain():
        ref_scores[ws.stream].append(ws.p_uav)
    ref_events = ref.finalize()

    engine = MonitorEngine(
        qp, cfg, n_streams=2, feature_kind="zcr",
        sanitize=SanitizePolicy(nonfinite="reject"), **TRACK_KW,
    )
    poisoned = clean[0].copy()
    poisoned[::50] = np.nan
    engine.push(0, poisoned)  # rejected whole
    engine.push(0, np.full(100, np.inf, np.float32))  # rejected too
    engine.push(1, clean[1])
    scores = {s: [] for s in range(2)}
    for ws in engine.drain():
        scores[ws.stream].append(ws.p_uav)
    events = engine.finalize()

    assert engine.rejected_chunks.tolist() == [2, 0]
    assert scores[0] == []  # nothing reached stream 0's ring
    assert np.isfinite(engine.tracker.smoothed).all()
    np.testing.assert_array_equal(
        np.asarray(scores[1], np.float64), np.asarray(ref_scores[1], np.float64)
    )
    assert events[1] == ref_events[1]


def test_sanitize_zero_mode_keeps_alignment(detector):
    """zero mode: poisoned samples are zeroed in place, chunk length (and so
    window alignment) is preserved, and the per-stream zeroed counter says
    exactly how many samples were scrubbed."""
    cfg, qp = detector
    engine = MonitorEngine(
        qp, cfg, n_streams=1, feature_kind="zcr",
        sanitize=SanitizePolicy(nonfinite="zero"),
    )
    chunk = np.ones(features.N_SAMPLES, np.float32)
    chunk[:7] = np.nan
    chunk[10:13] = -np.inf
    engine.push(0, chunk)
    assert engine.zeroed_samples.tolist() == [10]
    assert engine.rejected_chunks.tolist() == [0]
    ring = engine._rings[0]
    assert ring.buffered == features.N_SAMPLES  # full chunk kept
    w = ring.peek_window()
    assert np.isfinite(w).all()
    np.testing.assert_array_equal(w[:7], np.zeros(7))
    np.testing.assert_array_equal(w[13:], np.ones(features.N_SAMPLES - 13))


def test_sanitize_clip_detection_counts_and_rejects():
    policy_count = SanitizePolicy(clip_level=1.0, max_clip_fraction=0.1)
    loud = np.ones(100, np.float32)  # 100% at full scale
    soft = np.full(100, 0.5, np.float32)
    kept, rep = policy_count.apply(loud)
    assert rep.clipped and not rep.rejected and kept is not None
    kept, rep = policy_count.apply(soft)
    assert not rep.clipped and kept is not None

    policy_reject = SanitizePolicy(
        clip_level=1.0, max_clip_fraction=0.1, clipped_action="reject"
    )
    kept, rep = policy_reject.apply(loud)
    assert rep.rejected and rep.reason == "clipped" and kept is None


def test_sanitize_policy_validates_knobs():
    for bad in (
        dict(nonfinite="drop"),
        dict(clipped_action="zero"),
        dict(clip_level=0.0),
        dict(max_clip_fraction=1.5),
    ):
        with pytest.raises(ValueError):
            SanitizePolicy(**bad)


def test_without_sanitize_nan_poisons_only_its_own_stream():
    """The hazard the policy exists for, and the blast-radius guarantee that
    bounds it: with no sanitize policy a NaN chunk does poison its stream's
    EMA forever — but per-sample activation scales keep every co-batched
    clean stream bitwise intact.  (psd features: a NaN sample propagates
    through the FFT into the whole feature row — unlike zcr, whose
    sign-comparison math silently launders NaN into zeros.)"""
    cfg = cnn1d.CNNConfig(
        input_len=features.FEATURE_DIMS["psd"], channels=(4, 8), hidden=8
    )
    params = cnn1d.init_params(jax.random.PRNGKey(3), cfg)
    qp = quantize_params(params, cfg, mode="int8", feature_kind="psd")
    rng = np.random.default_rng(10)
    clean = _scene_audio(rng, 2, 2)

    ref = MonitorEngine(qp, cfg, n_streams=2, feature_kind="psd", **TRACK_KW)
    for s in range(2):
        ref.push(s, clean[s])
    ref_scores = {s: [] for s in range(2)}
    for ws in ref.drain():
        ref_scores[ws.stream].append(ws.p_uav)

    engine = MonitorEngine(qp, cfg, n_streams=2, feature_kind="psd", **TRACK_KW)
    poisoned = clean[0].copy()
    poisoned[1000] = np.nan
    engine.push(0, poisoned)
    engine.push(1, clean[1])
    scores = {s: [] for s in range(2)}
    for ws in engine.drain():
        scores[ws.stream].append(ws.p_uav)

    assert np.isnan(engine.tracker.smoothed[0])  # the un-hardened hazard
    np.testing.assert_array_equal(  # the blast radius: one row, one stream
        np.asarray(scores[1], np.float64), np.asarray(ref_scores[1], np.float64)
    )


# ---------------------------------------------------------------------------
# Fleet supervisor + deterministic fault injection (the chaos suite)
# ---------------------------------------------------------------------------

SUP_KW = dict(feature_kind="zcr", batch_slots=2,
              sanitize=SanitizePolicy(nonfinite="reject"), **TRACK_KW)


def _fleet(detector, n_streams, n_workers, **kw):
    cfg, qp = detector
    return FleetSupervisor(
        qp, cfg, n_streams=n_streams, n_workers=n_workers,
        clock=FaultClock(), dispatch_deadline_s=1.0, **SUP_KW, **kw,
    )


@pytest.fixture(scope="module")
def fleet_scene(detector):
    """One shared scene + fault-free supervisor baseline for the whole chaos
    section: 4 streams, uneven delivery, scores and events to compare every
    faulted run against."""
    rng = np.random.default_rng(21)
    audio = _scene_audio(rng, 4, 5)
    schedule = _delivery_schedule(rng, audio)
    sup = _fleet(detector, 4, 2)
    scores = _drive(sup, audio, schedule)
    events = sup.finalize()
    assert sum(len(e) for e in events) > 0
    return audio, schedule, scores, events


def _assert_streams_bitwise(scores, events, ref_scores, ref_events, streams):
    for s in streams:
        np.testing.assert_array_equal(
            np.asarray(scores[s], np.float64),
            np.asarray(ref_scores[s], np.float64),
            err_msg=f"stream {s} scores diverged",
        )
        assert events[s] == ref_events[s], f"stream {s} events diverged"


def test_fleet_without_faults_matches_single_engine(detector, fleet_scene):
    """Conformance: partitioning streams over a worker pool is numerically
    invisible — the fleet's per-stream scores and events equal one monolithic
    engine serving all streams, bitwise, for every pool size."""
    cfg, qp = detector
    audio, schedule, sup_scores, sup_events = fleet_scene
    mono = MonitorEngine(qp, cfg, n_streams=4, **SUP_KW)
    ref_scores = _drive(mono, audio, schedule)
    ref_events = mono.finalize()
    _assert_streams_bitwise(sup_scores, sup_events, ref_scores, ref_events,
                            range(4))
    sup4 = _fleet(detector, 4, 4)  # one worker per stream
    scores4 = _drive(sup4, audio, schedule)
    _assert_streams_bitwise(scores4, sup4.finalize(), ref_scores, ref_events,
                            range(4))


def test_lossy_chunk_faults_isolate_target_streams(detector, fleet_scene):
    """Dropped and corrupted chunks hurt exactly their target stream: every
    other stream — including the target's co-batched neighbour on the same
    worker — stays bitwise identical to the fault-free run."""
    audio, schedule, ref_scores, ref_events = fleet_scene
    plan = FaultPlan([
        Fault("drop_chunk", round=1, stream=0),
        Fault("corrupt_chunk", round=2, stream=3),
    ])
    assert plan.affected_streams == {0, 3}
    sup = _fleet(detector, 4, 2, faults=plan)
    scores = _drive(sup, audio, schedule)
    events = sup.finalize()
    _assert_streams_bitwise(scores, events, ref_scores, ref_events, {1, 2})
    assert sup.faulted_chunks.tolist() == [1, 0, 0, 1]
    # the corrupt chunk was NaN-poisoned and the reject policy refused it
    assert sup.workers[sup._route[3][0]].engine.rejected_chunks[
        sup._route[3][1]] == 1
    # the damage is real: the target streams scored fewer windows
    assert len(scores[0]) < len(ref_scores[0])


def test_jitter_resegmentation_is_bitwise_invisible(detector, fleet_scene):
    """Jitter re-segments a chunk into two pushes with identical content —
    ALL streams, including the jittered one, must match the fault-free run
    bitwise (the ring's hop alignment doesn't care about chunk boundaries)."""
    audio, schedule, ref_scores, ref_events = fleet_scene
    plan = FaultPlan([
        Fault("jitter_chunk", round=0, stream=1, magnitude=0.4),
        Fault("jitter_chunk", round=3, stream=2, magnitude=0.7),
    ])
    assert plan.affected_streams == set()
    sup = _fleet(detector, 4, 2, faults=plan)
    scores = _drive(sup, audio, schedule)
    _assert_streams_bitwise(scores, sup.finalize(), ref_scores, ref_events,
                            range(4))
    assert sup.faulted_chunks.sum() == 2


def test_worker_crash_stall_kill_are_lossless(detector, fleet_scene):
    """The tentpole chaos contract: a crashing forward, a stalled forward
    (detected via the dispatch deadline on the injected clock) and a killed
    worker all recover losslessly — every stream of every worker bitwise
    matches the fault-free run, and the incident log classifies each fault
    correctly."""
    audio, schedule, ref_scores, ref_events = fleet_scene
    plan = FaultPlan([
        Fault("raise_forward", round=1, worker=0),
        Fault("stall_forward", round=2, worker=1, magnitude=5.0),
        Fault("kill_worker", round=3, worker=0),
    ])
    sup = _fleet(detector, 4, 2, faults=plan)
    scores = _drive(sup, audio, schedule)
    events = sup.finalize()
    _assert_streams_bitwise(scores, events, ref_scores, ref_events, range(4))
    assert [i["kind"] for i in sup.incidents] == ["crash", "stall", "kill"]
    assert [i["worker"] for i in sup.incidents] == [0, 1, 0]
    assert sup.workers[0].rebuilds == 2 and sup.workers[1].rebuilds == 1
    assert all(w.alive for w in sup.workers)


def test_back_to_back_worker_failures_never_escape_step(detector, fleet_scene):
    """Regression (the post-revive retry bug): a transient fault whose
    magnitude makes the recovery re-run fail *again* — back-to-back
    failures inside one round — must be absorbed by the same revive path,
    not escape ``step()``.  Before the fix the retry ran outside the
    try/except, so the second consecutive raise crashed the supervisor."""
    audio, schedule, ref_scores, ref_events = fleet_scene
    plan = FaultPlan([
        # first attempt AND the recovery re-run both raise; third succeeds
        Fault("raise_forward", round=1, worker=0, magnitude=2),
    ])
    sup = _fleet(detector, 4, 2, faults=plan)
    scores = _drive(sup, audio, schedule)  # the bug made this raise
    events = sup.finalize()
    _assert_streams_bitwise(scores, events, ref_scores, ref_events, range(4))
    assert [i["kind"] for i in sup.incidents] == ["crash", "crash"]
    assert [i["round"] for i in sup.incidents] == [1, 1]
    assert sup.workers[0].rebuilds == 2
    assert all(w.alive for w in sup.workers)


def test_transient_fault_outliving_rebuild_budget_retires_losslessly(
        detector, fleet_scene):
    """The bounded end of the retry loop: a fault that outlives
    ``max_rebuilds`` consecutive re-runs tips the worker into retirement —
    its streams migrate to the survivor mid-scene with zero loss and the
    fault still never escapes ``step()``."""
    audio, schedule, ref_scores, ref_events = fleet_scene
    plan = FaultPlan([
        Fault("raise_forward", round=1, worker=0, magnitude=5),
    ])
    sup = _fleet(detector, 4, 2, max_rebuilds=1, faults=plan)
    scores = _drive(sup, audio, schedule)
    events = sup.finalize()
    _assert_streams_bitwise(scores, events, ref_scores, ref_events, range(4))
    assert [i["kind"] for i in sup.incidents] == ["crash", "crash", "reassign"]
    assert not sup.workers[0].alive
    assert sup.workers[1].streams == [2, 3, 0, 1]


def test_reassignment_after_repeated_kills_is_lossless(detector, fleet_scene):
    """A worker that dies more than max_rebuilds times is retired and its
    streams migrate — with their full state — to the survivor.  The merged
    worker's output stays bitwise identical for ALL streams, routing follows
    the streams, and health reports the retirement."""
    audio, schedule, ref_scores, ref_events = fleet_scene
    plan = FaultPlan([
        Fault("kill_worker", round=1, worker=0),
        Fault("kill_worker", round=2, worker=0),
    ])
    sup = _fleet(detector, 4, 2, max_rebuilds=1, faults=plan)
    scores = _drive(sup, audio, schedule)
    events = sup.finalize()
    _assert_streams_bitwise(scores, events, ref_scores, ref_events, range(4))
    assert not sup.workers[0].alive
    assert sup.workers[1].streams == [2, 3, 0, 1]
    assert sup._route[0] == (1, 2) and sup._route[1] == (1, 3)
    kinds = [i["kind"] for i in sup.incidents]
    assert kinds == ["kill", "kill", "reassign"]
    health = sup.health()
    assert health[0]["alive"] is False and health[0]["streams"] == []
    assert health[1]["streams"] == [2, 3, 0, 1]


def test_generated_plans_complete_and_isolate(detector, fleet_scene):
    """Seeded random plans (the chaos sweep): whatever the mix of faults,
    the supervisor finishes the scene without raising and every stream not
    hit by a lossy fault is bitwise identical to the fault-free run."""
    audio, schedule, ref_scores, ref_events = fleet_scene
    for seed in (0, 1, 2):
        plan = FaultPlan.generate(
            seed, n_streams=4, n_workers=2, n_rounds=len(schedule), n_faults=5
        )
        sup = _fleet(detector, 4, 2, faults=plan)
        scores = _drive(sup, audio, schedule)
        events = sup.finalize()
        clean = set(range(4)) - plan.affected_streams
        _assert_streams_bitwise(scores, events, ref_scores, ref_events, clean)
        assert len(sup.health()) == 2


def test_fault_plan_determinism_and_json_roundtrip(tmp_path):
    p1 = FaultPlan.generate(42, n_streams=8, n_workers=2, n_rounds=30)
    p2 = FaultPlan.generate(42, n_streams=8, n_workers=2, n_rounds=30)
    assert p1.faults == p2.faults
    p3 = FaultPlan.from_json(p1.to_json())
    assert p3.faults == p1.faults and p3.seed == 42
    # the CLI writes a plan the supervisor can load
    from repro.serving import faults as faults_mod
    out = tmp_path / "plan.json"
    faults_mod.main(["--seed", "7", "--streams", "4", "--workers", "2",
                     "--rounds", "10", "--out", str(out)])
    plan = FaultPlan.from_json(out.read_text())
    assert plan.seed == 7 and len(plan.faults) > 0


def test_fault_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        Fault("explode", 0, stream=1)
    with pytest.raises(ValueError, match="target stream"):
        Fault("drop_chunk", 0)
    with pytest.raises(ValueError, match="target worker"):
        Fault("kill_worker", 0)


def test_supervisor_health_heartbeat_and_validation(detector):
    cfg, qp = detector
    clock = FaultClock(tick=0.25)
    sup = FleetSupervisor(
        qp, cfg, n_streams=2, n_workers=2, clock=clock, **SUP_KW
    )
    assert all(h["heartbeat_age_s"] is None for h in sup.health())
    sup.push(0, np.zeros(features.N_SAMPLES, np.float32))
    sup.step()
    h = sup.health()
    assert h[0]["rounds"] == 1 and h[1]["rounds"] == 0  # only stream 0 scored
    assert all(hh["heartbeat_age_s"] is not None and hh["heartbeat_age_s"] >= 0
               for hh in h)
    with pytest.raises(ValueError, match="out of range"):
        sup.push(5, np.zeros(4, np.float32))

    params = cnn1d.init_params(jax.random.PRNGKey(1), cfg)
    with pytest.raises(ValueError, match="pre-baked"):
        FleetSupervisor(params, cfg, n_streams=2, **SUP_KW)
    with pytest.raises(ValueError, match="n_workers"):
        FleetSupervisor(qp, cfg, n_streams=2, n_workers=3, **SUP_KW)
    with pytest.raises(ValueError, match="dispatch_deadline_s"):
        FleetSupervisor(qp, cfg, n_streams=2, dispatch_deadline_s=0, **SUP_KW)


# ---------------------------------------------------------------------------
# Stream admission / overflow eviction through the supervisor
# ---------------------------------------------------------------------------


def test_supervisor_evicts_persistently_overflowing_stream(detector):
    """A stream that overflows its ring in evict_overflow_rounds consecutive
    rounds is evicted through the supervisor: its worker is rebuilt without
    it (the reassignment machinery in reverse), further pushes are refused,
    its closed events survive finalize(), and every surviving stream stays
    bitwise identical to a monolithic engine that never evicted anyone."""
    from repro.serving.batching import AdmissionPolicy

    cfg, qp = detector
    rng = np.random.default_rng(33)
    n_win = 6
    audio = _scene_audio(rng, 4, n_win)
    W = features.N_SAMPLES

    def deliver(engine, r):
        # stream 0 firehoses 2 windows/round into a 1-window ring (overflows
        # every round); streams 1-3 are well-behaved
        engine.push(0, audio[0, : 2 * W])
        for s in (1, 2, 3):
            engine.push(s, audio[s, r * W : (r + 1) * W])

    def run(engine):
        scores = {s: [] for s in range(4)}
        for r in range(n_win):
            deliver(engine, r)
            for ws in engine.step():
                scores[ws.stream].append(ws.p_uav)
        return scores

    sup = _fleet(
        detector, 4, 2, capacity_windows=1,
        admission=AdmissionPolicy(evict_overflow_rounds=2),
    )
    scores = run(sup)
    events = sup.finalize()

    assert [i["kind"] for i in sup.incidents] == ["evict"]
    assert "[0]" in sup.incidents[0]["detail"]
    assert sup.evicted == {0}
    assert sup.workers[0].streams == [1]  # rebuilt without the firehose
    assert sup._route[1] == (0, 0) and 0 not in sup._route
    # pushes after eviction were refused, not raised, and counted
    assert sup.refused_chunks[0] == n_win - 2
    assert len(scores[0]) == 2  # only the pre-eviction rounds scored

    mono = MonitorEngine(qp, cfg, n_streams=4, capacity_windows=1, **SUP_KW)
    ref_scores = run(mono)
    ref_events = mono.finalize()
    _assert_streams_bitwise(scores, events, ref_scores, ref_events, (1, 2, 3))


def test_supervisor_eviction_can_retire_whole_worker(detector):
    """Evicting every stream of a worker retires the worker cleanly."""
    from repro.serving.batching import AdmissionPolicy

    cfg, qp = detector
    rng = np.random.default_rng(35)
    W = features.N_SAMPLES
    sup = _fleet(
        detector, 2, 2, capacity_windows=1,
        admission=AdmissionPolicy(evict_overflow_rounds=1),
    )
    for _ in range(2):
        sup.push(0, rng.standard_normal(2 * W).astype(np.float32))
        sup.push(1, rng.standard_normal(W).astype(np.float32))
        sup.step()
    assert sup.evicted == {0}
    assert not sup.workers[0].alive and sup.workers[0].streams == []
    # the surviving worker keeps serving
    sup.push(1, rng.standard_normal(W).astype(np.float32))
    assert [ws.stream for ws in sup.step()] == [1]


def test_evicted_streams_keep_final_counter_totals(detector):
    """Regression (the per-stream gather bug): ``served_windows`` /
    ``deferred_windows`` promised that evicted streams keep their final
    totals, but the gather only read live workers' current streams — an
    evicted stream silently reported 0.  The totals are now stashed at
    eviction (like the event lists) and folded into the gather, matching a
    monolithic engine that de-admitted the same stream."""
    from repro.serving.batching import AdmissionPolicy

    cfg, qp = detector
    rng = np.random.default_rng(34)
    n_win = 6
    audio = _scene_audio(rng, 4, n_win)
    W = features.N_SAMPLES

    def run(engine):
        for r in range(n_win):
            engine.push(0, audio[0, : 2 * W])  # overflows every round
            for s in (1, 2, 3):
                engine.push(s, audio[s, r * W : (r + 1) * W])
            engine.step()

    kw = dict(capacity_windows=1,
              admission=AdmissionPolicy(evict_overflow_rounds=2))
    sup = _fleet(detector, 4, 2, **kw)
    run(sup)
    assert sup.evicted == {0}
    mono = MonitorEngine(qp, cfg, n_streams=4, **kw, **SUP_KW)
    run(mono)
    # the evicted stream's pre-eviction totals survive (the bug zeroed them)
    assert sup.served_windows[0] == mono.served_windows[0] > 0
    np.testing.assert_array_equal(sup.served_windows, mono.served_windows)
    np.testing.assert_array_equal(sup.deferred_windows, mono.deferred_windows)


def test_whole_worker_retirement_keeps_stream_totals(detector):
    """The same gather contract across whole-worker death: evicting a
    worker's last stream retires the worker, and the dead worker's streams
    still report their final served totals."""
    from repro.serving.batching import AdmissionPolicy

    rng = np.random.default_rng(36)
    W = features.N_SAMPLES
    sup = _fleet(
        detector, 2, 2, capacity_windows=1,
        admission=AdmissionPolicy(evict_overflow_rounds=1),
    )
    for _ in range(2):
        sup.push(0, rng.standard_normal(2 * W).astype(np.float32))
        sup.push(1, rng.standard_normal(W).astype(np.float32))
        sup.step()
    assert not sup.workers[0].alive  # stream 0 was its only stream
    assert sup.served_windows[0] == 1  # the pre-eviction round still counts
    assert sup.served_windows[1] == 2


def test_fleet_admission_cap_refuses_late_streams(detector):
    """max_streams is a fleet-level first-come cap: late streams' chunks are
    refused and counted at the supervisor, never delivered to a worker."""
    from repro.serving.batching import AdmissionPolicy

    rng = np.random.default_rng(37)
    W = features.N_SAMPLES
    sup = _fleet(detector, 4, 2, admission=AdmissionPolicy(max_streams=2))
    win = lambda: rng.standard_normal(W).astype(np.float32)
    assert sup.push(0, win()) == 0 and sup.push(3, win()) == 0  # admitted
    assert sup.push(1, win()) == 0 and sup.push(2, win()) == 0  # refused
    assert sorted(ws.stream for ws in sup.step()) == [0, 3]
    np.testing.assert_array_equal(sup.refused_chunks, [0, 1, 1, 0])
    # refusal is sticky; an unknown stream still raises
    sup.push(1, win())
    assert sup.refused_chunks[1] == 2
    with pytest.raises(ValueError, match="out of range"):
        sup.push(7, win())
