"""Concurrent-fleet conformance and the SLO autoscaler loop.

The headline contract of the execution-lane work: a fleet whose workers
run in named lane threads produces per-stream scores and events **bitwise
equal** to the sequential fleet and to one monolithic engine — with and
without a seeded fault plan.  Per-sample activation scales make every
window's score independent of its co-batch, worker engines are isolated
per stream group, and the supervisor defers all fleet-level mutations to
the join point, so thread interleaving has nowhere to leak into the
numbers.

The second half exercises the elasticity actuators the
:class:`~repro.serving.controller.FleetController` drives — spawn, retire,
retune — and the closed SLO loop itself under a bursty arrival schedule:
the controller must scale the fleet up against deferral pressure and back
down when the backlog drains, all bitwise losslessly.
"""
import dataclasses
import threading

import jax
import numpy as np
import pytest

from repro.data import features
from repro.models import cnn1d
from repro.serving.batching import AdmissionPolicy, IngestQueue
from repro.serving.controller import FleetController, SLOTarget
from repro.serving.engine import MonitorEngine, SanitizePolicy
from repro.serving.faults import Fault, FaultClock, FaultPlan
from repro.serving.quantized_params import quantize_params
from repro.serving.supervisor import FleetSupervisor

TRACK_KW = dict(ema_alpha=0.7, enter_threshold=0.02, exit_threshold=0.01,
                min_duration=1)
SUP_KW = dict(feature_kind="zcr", batch_slots=2,
              sanitize=SanitizePolicy(nonfinite="reject"), **TRACK_KW)


@pytest.fixture(scope="module")
def detector():
    cfg = cnn1d.CNNConfig(
        input_len=features.FEATURE_DIMS["zcr"], channels=(4, 8), hidden=8
    )
    params = cnn1d.init_params(jax.random.PRNGKey(0), cfg)
    qp = quantize_params(params, cfg, mode="int8", feature_kind="zcr")
    return cfg, qp


def _fleet(detector, n_streams, n_workers, **kw):
    cfg, qp = detector
    return FleetSupervisor(
        qp, cfg, n_streams=n_streams, n_workers=n_workers,
        clock=FaultClock(), dispatch_deadline_s=1.0, **SUP_KW, **kw,
    )


def _scene(rng, n_streams, n_win):
    audio = rng.standard_normal(
        (n_streams, n_win * features.N_SAMPLES)
    ).astype(np.float32)
    schedule = []
    cursors = [0] * n_streams
    total = audio.shape[1]
    while any(c < total for c in cursors):
        rnd = []
        for s in range(n_streams):
            if cursors[s] >= total:
                continue
            n = int(rng.uniform(0.3, 1.7) * features.N_SAMPLES)
            rnd.append((s, cursors[s], min(total, cursors[s] + n)))
            cursors[s] += n
        schedule.append(rnd)
    return audio, schedule


def _drive(engine, audio, schedule):
    scores = {s: [] for s in range(audio.shape[0])}
    for rnd in schedule:
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


def _assert_streams_bitwise(scores, events, ref_scores, ref_events, streams):
    for s in streams:
        np.testing.assert_array_equal(
            np.asarray(scores[s], np.float64),
            np.asarray(ref_scores[s], np.float64),
            err_msg=f"stream {s} scores diverged",
        )
        assert events[s] == ref_events[s], f"stream {s} events diverged"


@pytest.fixture(scope="module")
def lane_scene(detector):
    """Shared 6-stream scene + monolithic-engine baseline."""
    cfg, qp = detector
    rng = np.random.default_rng(51)
    audio, schedule = _scene(rng, 6, 5)
    mono = MonitorEngine(qp, cfg, n_streams=6, **SUP_KW)
    ref_scores = _drive(mono, audio, schedule)
    ref_events = mono.finalize()
    assert sum(len(e) for e in ref_events) > 0
    return audio, schedule, ref_scores, ref_events


# ---------------------------------------------------------------------------
# Headline conformance: lanes == sequential == monolithic, bitwise
# ---------------------------------------------------------------------------


def test_lane_fleet_bitwise_equals_sequential_and_monolithic(
        detector, lane_scene):
    audio, schedule, ref_scores, ref_events = lane_scene
    for n_workers in (2, 3, 6):
        seq = _fleet(detector, 6, n_workers)
        seq_scores = _drive(seq, audio, schedule)
        seq_events = seq.finalize()
        lanes = _fleet(detector, 6, n_workers, lanes="threads")
        lane_scores = _drive(lanes, audio, schedule)
        lane_events = lanes.finalize()
        _assert_streams_bitwise(
            seq_scores, seq_events, ref_scores, ref_events, range(6)
        )
        _assert_streams_bitwise(
            lane_scores, lane_events, ref_scores, ref_events, range(6)
        )
        # fleet counters agree too — lane mode is observationally identical
        np.testing.assert_array_equal(
            lanes.served_windows, seq.served_windows
        )
        np.testing.assert_array_equal(
            lanes.deferred_windows, seq.deferred_windows
        )
        assert lanes.windows_scored == seq.windows_scored
        assert lanes.round == seq.round
        lanes.close()


def test_lane_fleet_bitwise_equals_sequential_under_fault_plans(
        detector, lane_scene):
    """The chaos half of the headline: the same seeded fault plan replayed
    against the sequential and the lane-parallel fleet produces identical
    per-stream output, identical per-worker incident sequences, and (for
    streams untouched by lossy faults) identical output to the fault-free
    monolithic baseline."""
    audio, schedule, ref_scores, ref_events = lane_scene
    handcrafted = FaultPlan([
        Fault("raise_forward", round=1, worker=0, magnitude=2),
        Fault("stall_forward", round=2, worker=1, magnitude=5.0),
        Fault("kill_worker", round=3, worker=2),
        Fault("drop_chunk", round=1, stream=4),
        Fault("jitter_chunk", round=2, stream=0, magnitude=0.4),
    ])
    plans = [handcrafted] + [
        FaultPlan.generate(seed, n_streams=6, n_workers=3,
                           n_rounds=len(schedule), n_faults=5)
        for seed in (0, 1)
    ]
    for plan in plans:
        seq = _fleet(detector, 6, 3, faults=plan)
        seq_scores = _drive(seq, audio, schedule)
        seq_events = seq.finalize()
        lanes = _fleet(detector, 6, 3, faults=plan, lanes="threads")
        lane_scores = _drive(lanes, audio, schedule)
        lane_events = lanes.finalize()
        # lanes == sequential for EVERY stream, faulted ones included
        _assert_streams_bitwise(
            lane_scores, lane_events, seq_scores, seq_events, range(6)
        )
        # both == fault-free monolithic for streams no lossy fault touched
        clean = set(range(6)) - plan.affected_streams
        _assert_streams_bitwise(
            lane_scores, lane_events, ref_scores, ref_events, clean
        )
        # incidents agree per worker (lanes may interleave across workers)
        def per_worker(sup):
            out = {}
            for i in sup.incidents:
                out.setdefault(i["worker"], []).append((i["round"], i["kind"]))
            return out
        assert per_worker(lanes) == per_worker(seq)
        np.testing.assert_array_equal(
            lanes.faulted_chunks, seq.faulted_chunks
        )
        lanes.close()


def test_lane_push_defers_delivery_to_step(detector):
    """Lane-mode push is a non-blocking enqueue: delivery (journal, chunk
    faults, admission) happens at the top of the next step, and close()
    flushes anything still queued instead of dropping it."""
    sup = _fleet(detector, 2, 2, lanes="threads")
    win = np.zeros(features.N_SAMPLES, np.float32)
    assert sup.push(0, win) == 0
    assert len(sup._ingest) == 1
    assert all(len(w.journal) == 0 for w in sup.workers)  # not delivered yet
    with pytest.raises(ValueError, match="out of range"):
        sup.push(9, win)  # range errors still surface at push time
    scored = sup.step()
    assert [ws.stream for ws in scored] == [0]
    assert len(sup._ingest) == 0
    # queued ingest survives close() (delivered, not dropped)
    sup.push(1, win)
    sup.close()
    assert sup._ingest is None
    assert [ws.stream for ws in sup.step()] == [1]


def test_lanes_are_named_threads(detector):
    """Each worker's beat runs on its own named lane thread (the name ties
    faulthandler dumps and fault plans to the worker), not the caller."""
    sup = _fleet(detector, 2, 2, lanes="threads")
    seen = {}
    orig = sup._step_worker

    def spy(w):
        seen[w.idx] = threading.current_thread().name
        return orig(w)

    sup._step_worker = spy
    for s in range(2):
        sup.push(s, np.zeros(features.N_SAMPLES, np.float32))
    sup.step()
    assert seen == {0: "lane-0", 1: "lane-1"}
    health = sup.health()
    assert [h["lane"] for h in health] == ["lane-0", "lane-1"]
    sup.close()


def test_ingest_queue_is_thread_safe():
    q = IngestQueue()
    n_threads, per = 8, 200

    def feed(t):
        for i in range(per):
            q.append((t, i))

    threads = [threading.Thread(target=feed, args=(t,)) for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    items = q.drain()
    assert len(items) == n_threads * per
    assert len(q) == 0 and q.drain() == []
    # FIFO per producer
    for t in range(n_threads):
        assert [i for tt, i in items if tt == t] == list(range(per))


# ---------------------------------------------------------------------------
# Elasticity actuators: spawn / retire / retune
# ---------------------------------------------------------------------------


def test_spawn_and_retire_mid_scene_are_lossless(detector, lane_scene):
    """Scale up then back down mid-scene: the resized fleet's per-stream
    output stays bitwise equal to the monolithic engine, routing follows
    the streams, and fleet scalar totals are conserved across the split."""
    audio, schedule, ref_scores, ref_events = lane_scene
    sup = _fleet(detector, 6, 1)
    third = len(schedule) // 3
    scores = {s: [] for s in range(6)}

    def play(rounds):
        for rnd in rounds:
            for s, lo, hi in rnd:
                sup.push(s, audio[s, lo:hi])
            for ws in sup.step():
                scores[ws.stream].append(ws.p_uav)

    play(schedule[:third])
    idx = sup.spawn_worker()  # 6 streams on one worker -> split 3/3
    assert idx == 1 and sup.n_live_workers == 2
    assert sup.workers[0].streams == [0, 1, 2]
    assert sup.workers[1].streams == [3, 4, 5]
    assert sup._route[4] == (1, 1)
    assert [i["kind"] for i in sup.incidents] == ["spawn"]

    play(schedule[third : 2 * third])
    assert sup.retire_worker(1)  # fold it back
    assert sup.n_live_workers == 1
    assert sup.workers[0].streams == [0, 1, 2, 3, 4, 5]
    assert [i["kind"] for i in sup.incidents] == ["spawn", "retire"]

    play(schedule[2 * third :])
    while True:
        scored = sup.step()
        if not scored:
            break
        for ws in scored:
            scores[ws.stream].append(ws.p_uav)
    events = sup.finalize()
    _assert_streams_bitwise(scores, events, ref_scores, ref_events, range(6))
    # scalar totals conserved: the spun-off worker started zeroed
    assert sup.windows_scored == 6 * 5


def test_spawn_retire_edge_cases(detector):
    sup = _fleet(detector, 2, 2)
    # retiring below one live worker is refused
    assert sup.retire_worker() is True
    assert sup.retire_worker() is False
    assert sup.n_live_workers == 1
    # the survivor holds everything; a single-stream-per-worker fleet built
    # from 1-stream groups cannot spawn once each worker is down to 1 stream
    solo = _fleet(detector, 2, 2)
    assert solo.workers[0].streams == [0]
    assert solo.spawn_worker() is None  # no donor with >= 2 streams
    # spawning respects lanes: a lane-parallel fleet keeps working after it
    lanes = _fleet(detector, 4, 1, lanes="threads")
    idx = lanes.spawn_worker()
    assert idx == 1
    for s in range(4):
        lanes.push(s, np.zeros(features.N_SAMPLES, np.float32))
    assert sorted(ws.stream for ws in lanes.step()) == [0, 1, 2, 3]
    assert lanes.health()[idx]["lane"] == f"lane-{idx}"
    lanes.close()


def test_retune_admission_updates_every_live_worker(detector):
    sup = _fleet(
        detector, 4, 2,
        admission=AdmissionPolicy(max_per_stream_per_round=1, round_budget=2),
    )
    assert sup.admission.round_budget == 2
    new = dataclasses.replace(sup.admission, round_budget=8)
    sup.retune_admission(new)
    assert sup.admission.round_budget == 8
    for w in sup.workers:
        assert w.engine.admission.round_budget == 8
        assert w.engine.admission.max_streams is None  # fleet-level cap only
    # rebuilds inherit the retuned policy
    sup._revive(sup.workers[0])
    assert sup.workers[0].engine.admission.round_budget == 8


# ---------------------------------------------------------------------------
# The SLO loop: FleetController
# ---------------------------------------------------------------------------


def test_slo_target_validation():
    with pytest.raises(ValueError, match="min_workers"):
        SLOTarget(min_workers=0)
    with pytest.raises(ValueError, match="max_workers"):
        SLOTarget(min_workers=4, max_workers=2)
    with pytest.raises(ValueError, match="round_p95_ms"):
        SLOTarget(round_p95_ms=0.0)
    with pytest.raises(ValueError, match="max_defer_rate"):
        SLOTarget(max_defer_rate=-0.1)


def test_controller_latency_breach_spawns_and_headroom_retires(detector):
    """Unit-level decision ladder with injected latencies: a p95 breach over
    a full window spawns; sustained sub-margin latency retires."""
    sup = _fleet(detector, 4, 1)
    ctrl = FleetController(
        sup, SLOTarget(round_p95_ms=10.0, min_workers=1, max_workers=2),
        window=4, cooldown_rounds=0,
    )
    for _ in range(3):
        assert ctrl.step(50.0) is None  # window not full yet: no evidence
    action = ctrl.step(50.0)
    assert action is not None and action["kind"] == "spawn"
    assert sup.n_live_workers == 2
    for _ in range(4):
        last = ctrl.step(1.0)  # far under margin (0.5 * 10 ms)
    assert last is not None and last["kind"] == "retire"
    assert sup.n_live_workers == 1
    assert [a["kind"] for a in ctrl.actions] == ["spawn", "retire"]


def test_controller_retunes_budget_at_size_cap(detector):
    """At max_workers a defer-rate breach widens the admission budget
    instead of spawning."""
    sup = _fleet(
        detector, 4, 2,
        admission=AdmissionPolicy(round_budget=2),
    )
    ctrl = FleetController(
        sup, SLOTarget(max_defer_rate=0.2, min_workers=1, max_workers=2),
        window=2, cooldown_rounds=0,
    )
    W = features.N_SAMPLES
    rng = np.random.default_rng(61)
    # every stream dumps 3 windows; budget 2/worker defers the rest
    for s in range(4):
        sup.push(s, rng.standard_normal(3 * W).astype(np.float32))
    sup.step()
    action = ctrl.step(1.0)
    assert action is not None and action["kind"] == "retune"
    assert sup.admission.round_budget == 4
    for w in sup.workers:
        assert w.engine.admission.round_budget == 4


def test_controller_retires_stale_heartbeat_worker(detector):
    sup = _fleet(detector, 4, 2)
    ctrl = FleetController(
        sup, SLOTarget(max_heartbeat_age_s=30.0, min_workers=1, max_workers=4),
        window=2, cooldown_rounds=0,
    )
    for s in range(4):
        sup.push(s, np.zeros(features.N_SAMPLES, np.float32))
    sup.step()
    sup.workers[1].last_heartbeat -= 1000.0  # presumed hung
    action = ctrl.step(1.0)
    assert action is not None and action["kind"] == "retire_stale"
    assert action["worker"] == 1
    assert not sup.workers[1].alive
    assert sup.workers[0].streams == [0, 1, 2, 3]


def test_slo_loop_resizes_fleet_losslessly_under_bursty_arrivals(detector):
    """The acceptance-criteria SLO-loop test: under a bursty arrival
    schedule the controller scales the fleet up (spawn) against deferral
    pressure and back down (retire) when the backlog drains — and the
    resized fleet's per-stream output stays bitwise equal to a monolithic
    engine fed the identical schedule.  Autoscaling changes when windows
    are scored, never what they score."""
    cfg, qp = detector
    n_streams, burst_windows = 8, 3
    W = features.N_SAMPLES
    kw = dict(
        capacity_windows=burst_windows + 1,
        admission=AdmissionPolicy(max_per_stream_per_round=1),
    )
    rng = np.random.default_rng(71)
    audio = rng.standard_normal(
        (n_streams, burst_windows * W)
    ).astype(np.float32)

    def run(engine, ctrl=None):
        scores = {s: [] for s in range(n_streams)}
        # two bursty waves: every stream dumps a whole multi-window burst
        # at once, then the fleet drains it over quiet rounds
        for wave in range(2):
            for s in range(n_streams):
                lo = wave * burst_windows * W // 2
                hi = lo + burst_windows * W // 2
                engine.push(s, audio[s, lo:hi])
            for _ in range(6):  # drain rounds (quiet: no new arrivals)
                for ws in engine.step():
                    scores[ws.stream].append(ws.p_uav)
                if ctrl is not None:
                    ctrl.step(1.0)
        while True:
            scored = engine.step()
            if not scored:
                break
            for ws in scored:
                scores[ws.stream].append(ws.p_uav)
        return scores

    mono = MonitorEngine(qp, cfg, n_streams=n_streams, **kw, **SUP_KW)
    ref_scores = run(mono)
    ref_events = mono.finalize()

    sup = _fleet(detector, n_streams, 1, **kw)
    ctrl = FleetController(
        sup,
        SLOTarget(max_defer_rate=0.3, min_workers=1, max_workers=4),
        window=3, cooldown_rounds=1, scale_down_margin=0.5,
    )
    scores = run(sup, ctrl)
    events = sup.finalize()

    kinds = [a["kind"] for a in ctrl.actions]
    assert "spawn" in kinds, f"no scale-up under burst pressure: {kinds}"
    assert "retire" in kinds, f"no scale-down after drain: {kinds}"
    assert max(a["metrics"]["n_live"] for a in ctrl.actions) >= 2
    # losslessness: every window of every stream, bitwise
    assert sum(len(v) for v in scores.values()) == n_streams * burst_windows
    _assert_streams_bitwise(scores, events, ref_scores, ref_events,
                            range(n_streams))
