"""Streaming monitor engine: ring buffers, micro-batching, and the central
parity guarantee — windows streamed one at a time through the engine produce
bitwise-identical probabilities and identical track events to one batched
raw-window ``accelerator_forward`` of the engine's artifact + scalar tracker
over the same windows.

That guarantee rests on per-sample activation scales (each row quantises
independently of its co-batch), so this file is also the regression surface
for the per-tensor-scale bug.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import features
from repro.models import cnn1d
from repro.serving.accelerator import accelerator_forward
from repro.serving.batching import AdmissionPolicy
from repro.serving.engine import MonitorEngine, StreamRing
from repro.serving.quantized_params import quantize_params
from repro.serving.tracker import track_stream

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_fallback import given, settings, st

TRACK_KW = dict(ema_alpha=0.7, enter_threshold=0.02, exit_threshold=0.01, min_duration=1)


def _small_detector(kind="zcr"):
    cfg = cnn1d.CNNConfig(
        input_len=features.FEATURE_DIMS[kind], channels=(4, 8), hidden=8
    )
    params = cnn1d.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _raw_forward(engine, windows):
    """The engine's artifact over raw windows in one batched forward: the
    reference every engine score must equal bitwise."""
    return np.asarray(
        accelerator_forward(
            engine._qp, jnp.asarray(windows), engine.cfg, raw_windows=True
        )
    )[:, 1].astype(np.float64)


# ---------------------------------------------------------------------------
# StreamRing
# ---------------------------------------------------------------------------


def test_ring_hop_aligned_windows():
    r = StreamRing(window=10, hop=10, capacity_windows=3)
    assert r.push(np.arange(7)) == 0
    assert r.ready == 0 and r.pop_window() is None
    r.push(np.arange(7, 25))
    assert r.ready == 2
    np.testing.assert_array_equal(r.pop_window(), np.arange(10))
    np.testing.assert_array_equal(r.pop_window(), np.arange(10, 20))
    assert r.pop_window() is None
    r.push(np.arange(25, 30))
    np.testing.assert_array_equal(r.pop_window(), np.arange(20, 30))


def test_ring_overlapping_hop():
    r = StreamRing(window=10, hop=5, capacity_windows=4)
    r.push(np.arange(20))
    assert r.ready == 3
    np.testing.assert_array_equal(r.pop_window(), np.arange(10))
    np.testing.assert_array_equal(r.pop_window(), np.arange(5, 15))
    np.testing.assert_array_equal(r.pop_window(), np.arange(10, 20))


def test_ring_wraparound_many_times():
    r = StreamRing(window=8, hop=8, capacity_windows=2)
    expect = 0
    for chunk in range(40):
        r.push(np.arange(expect + 0, expect + 0 + 8) % 1000)
        w = r.pop_window()
        np.testing.assert_array_equal(w, np.arange(expect, expect + 8) % 1000)
        expect += 8
    assert r.dropped == 0


def test_ring_overflow_drops_oldest_hops():
    r = StreamRing(window=10, hop=10, capacity_windows=2)
    r.push(np.zeros(20))
    assert r.push(np.ones(10)) == 10  # oldest window dropped, hop-aligned
    assert r.dropped == 10 and r.ready == 2
    np.testing.assert_array_equal(r.pop_window(), np.zeros(10))
    np.testing.assert_array_equal(r.pop_window(), np.ones(10))


def test_ring_giant_push_keeps_tail():
    r = StreamRing(window=10, hop=10, capacity_windows=2)
    dropped = r.push(np.arange(55))
    assert dropped == 40  # hop-aligned tail survives
    np.testing.assert_array_equal(r.pop_window(), np.arange(40, 50))


def _index_gather(ring, k, first=0):
    """The ring's windows by a modulo fancy index over its buffer: the
    oracle the contiguous-slice copy must match bitwise."""
    idx = (
        ring._r
        + (first + np.arange(k))[:, None] * ring.hop
        + np.arange(ring.window)[None, :]
    ) % ring.capacity
    return ring._buf[idx]


# (window, hop, capacity_windows, pushes, advances, pushes after)
RING_CASES = {
    "hop_eq_window": (8, 8, 4, [20], 0, []),
    "overlapping_hop": (8, 3, 4, [17], 0, []),
    "wraps_ring_end": (8, 5, 3, [18], 2, [10]),
    "k_gt_1_full_ring": (8, 8, 8, [64], 0, []),
    "head_after_overflow": (8, 4, 3, [11, 29], 0, [3]),
}


@pytest.mark.parametrize("case", sorted(RING_CASES))
def test_ring_copy_windows_equals_index_gather(case):
    window, hop, cap_w, pushes, advances, later = RING_CASES[case]
    r = StreamRing(window=window, hop=hop, capacity_windows=cap_w)
    n = 0
    for size in pushes:
        r.push(np.arange(n, n + size, dtype=np.float32))
        n += size
    for _ in range(advances):
        r.advance()
    for size in later:
        r.push(np.arange(n, n + size, dtype=np.float32))
        n += size
    ready, heads = r.ready, (r._r, r._w)
    assert ready >= 2
    starts = [(r._r + d * hop) % r.capacity for d in range(ready)]
    if case == "wraps_ring_end":
        assert any(s + window > r.capacity for s in starts)
    if case == "head_after_overflow":
        assert r.dropped > 0 and r._r % r.capacity != 0
    for first in range(ready):
        for k in range(1, ready - first + 1):
            out = np.full((k, window), np.nan, np.float32)
            assert r.copy_windows(out, first) is out
            np.testing.assert_array_equal(out, _index_gather(r, k, first))
    for k in range(1, ready + 1):
        np.testing.assert_array_equal(r.peek_windows(k), _index_gather(r, k))
    np.testing.assert_array_equal(r.peek_window(), _index_gather(r, 1)[0])
    with pytest.raises(ValueError, match="ready"):
        r.copy_windows(np.empty((2, window), np.float32), ready - 1)
    with pytest.raises(ValueError, match="ready"):
        r.peek_windows(ready + 1)
    assert (r._r, r._w) == heads and r.ready == ready  # nothing consumed


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


def test_engine_rejects_mismatched_feature_dim():
    cfg, params = _small_detector()
    with pytest.raises(ValueError, match="feature dim"):
        MonitorEngine(params, cfg, n_streams=1, feature_kind="mfcc20")


def test_ring_and_engine_validate_with_real_exceptions():
    """Constructor validation raises ValueError, not assert — asserts vanish
    under ``python -O`` and the always-on monitor must keep its guardrails."""
    for bad in (dict(window=0, hop=1), dict(window=4, hop=0),
                dict(window=4, hop=2, capacity_windows=0)):
        with pytest.raises(ValueError):
            StreamRing(**bad)
    cfg, params = _small_detector()
    with pytest.raises(ValueError, match="n_streams"):
        MonitorEngine(params, cfg, n_streams=0, feature_kind="zcr")
    with pytest.raises(ValueError, match="batch_slots"):
        MonitorEngine(params, cfg, n_streams=1, feature_kind="zcr", batch_slots=0)


def test_engine_push_rejects_bad_stream_index():
    cfg, params = _small_detector()
    engine = MonitorEngine(params, cfg, n_streams=2, feature_kind="zcr")
    for bad in (-1, 2, 7):
        with pytest.raises(ValueError, match="out of range"):
            engine.push(bad, np.zeros(4, np.float32))


def test_ring_peek_then_advance_equals_pop():
    r = StreamRing(window=10, hop=5, capacity_windows=4)
    r.push(np.arange(20))
    np.testing.assert_array_equal(r.peek_window(), np.arange(10))
    np.testing.assert_array_equal(r.peek_window(), np.arange(10))  # no consume
    r.advance()
    np.testing.assert_array_equal(r.pop_window(), np.arange(5, 15))
    np.testing.assert_array_equal(r.peek_window(), np.arange(10, 20))
    r.advance()
    assert r.peek_window() is None
    with pytest.raises(ValueError, match="advance"):
        r.advance()


def test_step_requeues_on_forward_error():
    """The window-loss/desync regression: a forward that raises mid-round
    must leave rings and tracker untouched, and a retry must produce events
    bitwise identical to a never-faulted run."""
    cfg, params = _small_detector()
    rng = np.random.default_rng(21)
    n_streams, n_win = 3, 5
    audio = rng.standard_normal(
        (n_streams, n_win * features.N_SAMPLES)
    ).astype(np.float32)

    def run(fail_rounds):
        engine = MonitorEngine(
            params, cfg, n_streams=n_streams, feature_kind="zcr",
            batch_slots=2, **TRACK_KW,
        )
        real_forward = engine._forward
        calls = {"n": 0}

        def flaky(rows):
            calls["n"] += 1
            if calls["n"] in fail_rounds:
                raise RuntimeError("injected forward crash")
            return real_forward(rows)

        engine._forward = flaky
        for s in range(n_streams):
            engine.push(s, audio[s])
        scores: dict[int, list[float]] = {s: [] for s in range(n_streams)}
        done = 0
        while done < n_streams * n_win:
            heads = [r._r for r in engine._rings]
            ema = engine.tracker._ema.copy()
            idx = engine.tracker._idx.copy()
            try:
                scored = engine.step()
            except RuntimeError:
                # nothing consumed: ring read heads and tracker state unmoved
                assert [r._r for r in engine._rings] == heads
                np.testing.assert_array_equal(engine.tracker._ema, ema)
                np.testing.assert_array_equal(engine.tracker._idx, idx)
                continue
            for ws in scored:
                scores[ws.stream].append(ws.p_uav)
            done += len(scored)
        return scores, engine.finalize()

    clean_scores, clean_events = run(fail_rounds=())
    faulty_scores, faulty_events = run(fail_rounds={1, 3, 4})
    assert faulty_scores == clean_scores
    assert faulty_events == clean_events
    # per-stream window indices never desynced: n_win windows each
    assert all(len(v) == n_win for v in faulty_scores.values())


def test_streaming_parity_bitwise_probs_and_events():
    """The acceptance-criteria test: uneven chunked delivery through the
    engine == one batched forward + scalar tracker, bitwise/exactly."""
    cfg, params = _small_detector()
    rng = np.random.default_rng(5)
    n_streams, n_win = 3, 5
    audio = rng.standard_normal(
        (n_streams, n_win * features.N_SAMPLES)
    ).astype(np.float32)

    engine = MonitorEngine(
        params, cfg, n_streams=n_streams, feature_kind="zcr",
        batch_slots=2, **TRACK_KW,
    )
    cursors = [0] * n_streams
    scores: dict[int, list[float]] = {s: [] for s in range(n_streams)}
    while any(c < audio.shape[1] for c in cursors):
        for s in range(n_streams):
            n = int(rng.uniform(0.2, 1.9) * features.N_SAMPLES)
            engine.push(s, audio[s, cursors[s] : cursors[s] + n])
            cursors[s] += n
        for ws in engine.step():
            scores[ws.stream].append(ws.p_uav)
    for ws in engine.drain():
        scores[ws.stream].append(ws.p_uav)
    events = engine.finalize()
    assert engine.dropped_samples == 0

    total_events = 0
    for s in range(n_streams):
        # One batched forward over the whole stream at a different batch
        # size: per-sample activation scales make each row's result
        # independent of its co-batch.
        probs = _raw_forward(engine, audio[s].reshape(n_win, features.N_SAMPLES))
        got = np.asarray(scores[s], np.float64)
        assert len(got) == n_win
        np.testing.assert_array_equal(got, probs)
        ref_events = track_stream(probs, **TRACK_KW)
        assert events[s] == ref_events
        total_events += len(ref_events)
    assert total_events > 0  # thresholds chosen so events actually occur


def test_engine_micro_batching_pads_dead_slots():
    cfg, params = _small_detector()
    engine = MonitorEngine(
        params, cfg, n_streams=5, feature_kind="zcr", batch_slots=4
    )
    rng = np.random.default_rng(0)
    for s in range(5):
        engine.push(s, rng.standard_normal(features.N_SAMPLES).astype(np.float32))
    scored = engine.step()
    assert len(scored) == 5
    # 5 ready windows / 4 slots -> two forward calls, 3 padded slots
    assert engine.forward_calls == 2
    assert engine.padded_slots == 3
    assert engine.step() == []  # nothing left buffered


def test_engine_on_device_features_streaming_parity():
    """Fused front-end leg of the parity guarantee: raw windows streamed
    through the engine in uneven chunks == one batched raw-window forward,
    bitwise — the feature bits are per-row inside the jitted program."""
    cfg, params = _small_detector()
    rng = np.random.default_rng(9)
    n_streams, n_win = 3, 4
    audio = rng.standard_normal(
        (n_streams, n_win * features.N_SAMPLES)
    ).astype(np.float32)
    audio *= (10.0 ** rng.uniform(-2, 2, size=(n_streams, 1))).astype(np.float32)

    engine = MonitorEngine(
        params, cfg, n_streams=n_streams, feature_kind="zcr",
        batch_slots=2, **TRACK_KW,
    )
    cursors = [0] * n_streams
    scores: dict[int, list[float]] = {s: [] for s in range(n_streams)}
    while any(c < audio.shape[1] for c in cursors):
        for s in range(n_streams):
            n = int(rng.uniform(0.2, 1.9) * features.N_SAMPLES)
            engine.push(s, audio[s, cursors[s] : cursors[s] + n])
            cursors[s] += n
        for ws in engine.step():
            scores[ws.stream].append(ws.p_uav)
    for ws in engine.drain():
        scores[ws.stream].append(ws.p_uav)

    qp = engine._qp
    assert qp.feature_kind == "zcr"
    for s in range(n_streams):
        wins = jnp.asarray(audio[s].reshape(n_win, features.N_SAMPLES))
        probs = np.asarray(
            accelerator_forward(qp, wins, cfg, raw_windows=True)
        )[:, 1]
        np.testing.assert_array_equal(
            np.asarray(scores[s], np.float64), probs.astype(np.float64)
        )


def test_engine_on_device_equals_manual_two_stage():
    """Fusion correctness: the in-graph front-end feeding the datapath is
    bitwise the same as extracting JAX features first and forwarding them."""
    from repro.data import features_jax

    cfg, params = _small_detector()
    rng = np.random.default_rng(2)
    wins = rng.standard_normal((4, features.N_SAMPLES)).astype(np.float32)
    engine = MonitorEngine(
        params, cfg, n_streams=4, feature_kind="zcr", batch_slots=4,
    )
    for s in range(4):
        engine.push(s, wins[s])
    scored = engine.step()
    feats = features_jax.batch_features_jax(wins, "zcr")
    two_stage = np.asarray(accelerator_forward(engine._qp, feats, cfg))[:, 1]
    got = np.asarray([ws.p_uav for ws in sorted(scored, key=lambda w: w.stream)])
    np.testing.assert_array_equal(got, two_stage.astype(np.float64))


@pytest.mark.parametrize("per_round", [1, 3])
def test_on_device_rounds_equal_forward_of_peeked_windows(per_round):
    """With the front-end on the device each ring copies its windows
    straight into the dispatch blocks: over several rounds the scores stay
    bitwise equal to one raw-window forward of the rings' peeked windows,
    stacked stream-major in depth order — with more than one block a round,
    a partial last block with dead slots, and several windows per stream."""
    cfg, params = _small_detector()
    rng = np.random.default_rng(17)
    n_streams = 5
    engine = MonitorEngine(
        params, cfg, n_streams=n_streams, feature_kind="zcr",
        batch_slots=2, capacity_windows=4,
        admission=AdmissionPolicy(max_per_stream_per_round=per_round), **TRACK_KW,
    )
    multi_block = dead_slots = deep = 0
    for _ in range(4):
        for s in range(n_streams):
            n = int(rng.uniform(0.6, 2.2) * features.N_SAMPLES)
            engine.push(s, rng.standard_normal(n).astype(np.float32))
        take = np.minimum(engine.ready_windows(), per_round)
        stacked = np.concatenate(
            [engine._rings[s].peek_windows(int(k)) for s, k in enumerate(take) if k]
        )
        want = np.asarray(
            accelerator_forward(engine._qp, jnp.asarray(stacked), cfg, raw_windows=True)
        )[:, 1]
        calls, padded = engine.forward_calls, engine.padded_slots
        out = engine.step()
        got = [w.p_uav for w in sorted(out, key=lambda w: (w.stream, w.window_idx))]
        np.testing.assert_array_equal(np.asarray(got), want.astype(np.float64))
        multi_block += engine.forward_calls - calls > 1
        dead_slots += engine.padded_slots > padded
        deep += int(take.max()) > 1
    assert multi_block and dead_slots
    assert bool(deep) == (per_round > 1)


def _round_state(engine):
    return (
        [(r._r, r._w) for r in engine._rings],
        engine.ready_windows(),
        engine.tracker.state_dict(),
        engine.windows_scored,
        engine.rounds,
    )


def _assert_same_state(a, b):
    assert a[0] == b[0] and a[3:] == b[3:]
    np.testing.assert_array_equal(a[1], b[1])
    assert a[2].keys() == b[2].keys()
    for key in a[2]:
        if key == "events":
            assert a[2][key] == b[2][key]
        else:
            np.testing.assert_array_equal(a[2][key], b[2][key])


@pytest.mark.parametrize(
    "fault", ["fault_hook", "second_block_submit", "last_block_submit"]
)
def test_on_device_failed_round_is_untouched_and_retries_identically(fault):
    """A round that raises — in the fault seam before anything is copied,
    in the submit of its second block after the first block's windows were
    copied and sent, or in the submit of its last block with every earlier
    block in flight — leaves rings, ready counts and tracker as they were,
    waits for the blocks it had sent, and the retried round scores exactly
    what a clean engine does."""
    cfg, params = _small_detector()
    n_streams = 3

    def build():
        engine = MonitorEngine(
            params, cfg, n_streams=n_streams, feature_kind="zcr",
            batch_slots=2,
            admission=AdmissionPolicy(max_per_stream_per_round=2), **TRACK_KW,
        )
        rng = np.random.default_rng(23)
        for s in range(n_streams):
            n = int((2.4 + 0.5 * s) * features.N_SAMPLES)
            engine.push(s, rng.standard_normal(n).astype(np.float32))
        return engine

    def scores(out):
        return [(w.stream, w.window_idx, w.p_uav, w.smoothed, w.active) for w in out]

    clean = build()
    want = [scores(clean.step())]
    n_blocks = clean.forward_calls  # blocks in the first round
    want.append(scores(clean.step()))
    assert n_blocks == 3
    engine = build()
    engine.telemetry.on = True
    before = _round_state(engine)
    fail_at = {"second_block_submit": 2, "last_block_submit": n_blocks}.get(fault)
    if fault == "fault_hook":
        def boom(items):
            raise RuntimeError("injected")
        engine.fault_hook = boom
    else:
        real_submit, calls = engine._submit, []

        def submit(block):
            calls.append(block.shape[0])
            if len(calls) == fail_at:
                raise RuntimeError("injected")
            return real_submit(block)
        engine._submit = submit
    with pytest.raises(RuntimeError, match="injected"):
        engine.step()
    _assert_same_state(_round_state(engine), before)
    # every block sent before the failure was waited on before it propagated
    waited = len(engine.telemetry.arrays("engine.wait")[0])
    assert waited == (0 if fail_at is None else fail_at - 1)
    assert engine.served_windows.sum() == 0
    engine.fault_hook = None
    engine.__dict__.pop("_submit", None)
    assert [scores(engine.step()), scores(engine.step())] == want


def test_on_device_round_copies_each_window_once_into_its_block(monkeypatch):
    """The fused-front-end round stacks nothing (no ``peek_windows``): each
    scored window is copied once, by its ring, into a row of the dispatch
    block that is sent."""
    cfg, params = _small_detector()
    engine = MonitorEngine(
        params, cfg, n_streams=5, feature_kind="zcr", batch_slots=2,
    )
    rng = np.random.default_rng(8)
    for s in range(5):
        engine.push(s, rng.standard_normal(features.N_SAMPLES).astype(np.float32))
    copies, sent = [], []
    real_copy = StreamRing.copy_windows

    def copy_windows(ring, out, first=0):
        copies.append((ring, len(out), out.base))
        return real_copy(ring, out, first)

    def no_stack(*a, **k):
        raise AssertionError("the round stacked its windows")

    monkeypatch.setattr(StreamRing, "copy_windows", copy_windows)
    monkeypatch.setattr(StreamRing, "peek_windows", no_stack)
    real_submit = engine._submit
    engine._submit = lambda block: sent.append(block) or real_submit(block)
    out = engine.step()
    monkeypatch.undo()
    assert len(out) == 5 and engine.padded_slots == 1
    assert [c[0] for c in copies] == engine._rings  # stream-major, once each
    assert all(n == 1 for _, n, _ in copies)
    assert [id(c[2]) for c in copies] == [id(sent[j // 2]) for j in range(5)]


@pytest.mark.parametrize("host_front_end", [False, True])
def test_engine_rejects_artifact_without_feature_kind(host_front_end):
    """The engine serves raw windows through the artifact's front-end, so
    it needs that front-end baked in: a plain artifact must be rejected, not
    silently served on raw samples; and the removed host front-end cannot
    be asked for."""
    cfg, params = _small_detector()
    if host_front_end:
        with pytest.raises(ValueError, match="host front-end was removed"):
            MonitorEngine(
                params, cfg, n_streams=1, feature_kind="zcr",
                on_device_features=False,
            )
        return
    qp = cnn1d.export_quantized(params, cfg, mode="int8")
    assert qp.feature_kind is None
    with pytest.raises(ValueError, match="baked for"):
        MonitorEngine(qp, cfg, n_streams=1, feature_kind="zcr")


@pytest.mark.parametrize("kind", ["mfcc20", "mel128", "psd", "zcr"])
def test_engine_serves_every_feature_kind(kind):
    """Every detector feature kind is served by the fused front-end: the
    engine's scores over uneven chunks equal one raw-window forward of its
    artifact, bitwise, and the artifact carries the kind it was baked for."""
    cfg, params = _small_detector(kind)
    rng = np.random.default_rng(31)
    n_streams, n_win = 3, 2
    audio = rng.standard_normal(
        (n_streams, n_win * features.N_SAMPLES)
    ).astype(np.float32)
    engine = MonitorEngine(
        params, cfg, n_streams=n_streams, feature_kind=kind, batch_slots=2,
    )
    assert engine._qp.feature_kind == kind
    for s in range(n_streams):
        half = int(0.7 * features.N_SAMPLES)
        engine.push(s, audio[s, :half])
        engine.push(s, audio[s, half:])
    scores: dict[int, list[float]] = {s: [] for s in range(n_streams)}
    for ws in engine.drain():
        scores[ws.stream].append(ws.p_uav)
    for s in range(n_streams):
        want = _raw_forward(engine, audio[s].reshape(n_win, features.N_SAMPLES))
        np.testing.assert_array_equal(np.asarray(scores[s], np.float64), want)


@pytest.mark.parametrize("per_round", [1, 4])
def test_engine_block_buffer_reuse_is_invisible(per_round):
    """The preallocated dispatch buffers must behave exactly like the old
    fresh-np.zeros-per-chunk blocks: many rounds with varying ready counts
    (full blocks, partial tails after full blocks), each round's blocks all
    queued before the first harvest, stay bitwise equal to a per-stream
    batched reference.  ``per_round=4`` drains the whole backlog in one
    round of 10 blocks in flight at once."""
    cfg, params = _small_detector()
    rng = np.random.default_rng(4)
    n_streams, n_win = 5, 4
    audio = rng.standard_normal(
        (n_streams, n_win * features.N_SAMPLES)
    ).astype(np.float32)
    engine = MonitorEngine(
        params, cfg, n_streams=n_streams, feature_kind="zcr", batch_slots=2,
        admission=AdmissionPolicy(max_per_stream_per_round=per_round),
    )
    ref = {
        s: _raw_forward(engine, audio[s].reshape(n_win, features.N_SAMPLES))
        for s in range(n_streams)
    }
    # round 1 fills both slots of the last block (5 ready -> 2+2+1: the
    # stale-tail case), later rounds rewrite previously-padded buffers
    scores: dict[int, list[float]] = {s: [] for s in range(n_streams)}
    for w in range(n_win):
        for s in range(n_streams):
            engine.push(s, audio[s, w * features.N_SAMPLES : (w + 1) * features.N_SAMPLES])
    for ws in engine.drain():
        scores[ws.stream].append(ws.p_uav)
    for s in range(n_streams):
        np.testing.assert_array_equal(np.asarray(scores[s], np.float64), ref[s])
    # every round queued all its blocks: 3 a round (2+2+1), or all 10 at once
    assert engine.depth_histogram == ({3: n_win} if per_round == 1 else {10: 1})


def test_engine_depth_histogram_counts_rounds_of_full_blocks():
    """``depth_histogram`` reads ``{k: rounds}`` for rounds of k full
    blocks: 8 streams over 2 slots queue 4 blocks a round."""
    cfg, params = _small_detector()
    engine = MonitorEngine(
        params, cfg, n_streams=8, feature_kind="zcr", batch_slots=2,
    )
    assert engine.depth_histogram == {}
    rng = np.random.default_rng(2)
    for s in range(8):
        engine.push(s, rng.standard_normal(3 * features.N_SAMPLES).astype(np.float32))
    assert len(engine.drain()) == 24
    assert engine.depth_histogram == {4: 3}
    assert engine.slot_histogram == {2: 12} and engine.padded_slots == 0
    # each round rewinds the pool: it holds one round's 4 buffers, not 12
    assert len(engine._pool._pools[2]) == 4


def test_engine_dropped_samples_incremental_counter():
    """dropped_samples is maintained incrementally by push() and agrees with
    the per-ring ground truth."""
    cfg, params = _small_detector()
    engine = MonitorEngine(
        params, cfg, n_streams=2, feature_kind="zcr", capacity_windows=2
    )
    rng = np.random.default_rng(0)
    assert engine.dropped_samples == 0
    # overflow stream 0: capacity is 2 windows; push 4 windows' worth
    d = engine.push(0, rng.standard_normal(4 * features.N_SAMPLES).astype(np.float32))
    assert d > 0
    assert engine.dropped_samples == d == sum(r.dropped for r in engine._rings)
    d2 = engine.push(1, rng.standard_normal(3 * features.N_SAMPLES).astype(np.float32))
    assert engine.dropped_samples == d + d2 == sum(r.dropped for r in engine._rings)


def test_engine_serves_from_quantized_artifact():
    """Engine construction from a pre-quantised artifact does zero extra
    weight-quantisation work at serve time."""
    from repro.serving import quantized_params as qpm

    cfg, params = _small_detector()
    qp = quantize_params(params, cfg, mode="int8", feature_kind="zcr")
    engine = MonitorEngine(qp, cfg, n_streams=2, feature_kind="zcr")
    before = qpm.quantize_calls
    rng = np.random.default_rng(1)
    for s in range(2):
        engine.push(s, rng.standard_normal(2 * features.N_SAMPLES).astype(np.float32))
    assert len(engine.drain()) == 4
    assert qpm.quantize_calls == before  # weights untouched while serving


# ---------------------------------------------------------------------------
# Adaptive slot sizing + admission control (the shared dispatch core)
# ---------------------------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(
    st.integers(min_value=0, max_value=10_000),
    st.sampled_from((2, 4)),
    st.integers(min_value=1, max_value=3),
)
def test_adaptive_slots_bitwise_equal_fixed_any_schedule(
    seed, batch_slots, n_streams
):
    """The elastic-batching property: whatever grow/shrink schedule the
    adaptive slot policy follows over a random push sequence, every
    stream's probability sequence and event list are bitwise identical to
    the fixed-slot engine — per-sample activation scales make each row
    independent of its co-batch, so block shape is unobservable."""
    cfg, params = _small_detector()
    rng = np.random.default_rng(seed)
    n_win = int(rng.integers(2, 5))
    audio = rng.standard_normal(
        (n_streams, n_win * features.N_SAMPLES)
    ).astype(np.float32)
    engines = [
        MonitorEngine(
            params, cfg, n_streams=n_streams, feature_kind="zcr",
            batch_slots=batch_slots, adaptive_slots=adaptive,
            capacity_windows=n_win + 1, **TRACK_KW,
        )
        for adaptive in (False, True)
    ]
    scores = [{s: [] for s in range(n_streams)} for _ in engines]
    total = audio.shape[1]
    cursors = [0] * n_streams
    while any(c < total for c in cursors):
        for s in range(n_streams):
            # identical uneven delivery to both engines
            chunk = int(rng.uniform(0.2, 2.3) * features.N_SAMPLES)
            lo, hi = cursors[s], min(total, cursors[s] + chunk)
            if lo < hi:
                for e in engines:
                    e.push(s, audio[s, lo:hi])
            cursors[s] = hi
        for e, sc in zip(engines, scores):
            for ws in e.step():
                sc[ws.stream].append(ws.p_uav)
    for e, sc in zip(engines, scores):
        for ws in e.drain():
            sc[ws.stream].append(ws.p_uav)
    for s in range(n_streams):
        np.testing.assert_array_equal(
            np.asarray(scores[0][s], np.float64),
            np.asarray(scores[1][s], np.float64),
        )
    assert engines[0].finalize() == engines[1].finalize()
    # and the adaptive engine never pads more than the fixed one
    assert engines[1].padded_slots <= engines[0].padded_slots


def test_adaptive_slots_dispatch_smaller_blocks():
    """1 live stream on an 8-slot engine: fixed pads 7/8 slots per round,
    adaptive dispatches 1-slot blocks (the headline waste the bench rows
    show at 1 stream)."""
    cfg, params = _small_detector()
    rng = np.random.default_rng(3)
    audio = rng.standard_normal(3 * features.N_SAMPLES).astype(np.float32)
    fixed = MonitorEngine(
        params, cfg, n_streams=1, feature_kind="zcr", batch_slots=8, **TRACK_KW
    )
    adaptive = MonitorEngine(
        params, cfg, n_streams=1, feature_kind="zcr", batch_slots=8,
        adaptive_slots=True, **TRACK_KW,
    )
    assert adaptive.slot_policy.ladder == (1, 2, 4, 8)
    assert adaptive.precompile() == (1, 2, 4, 8)
    for e in (fixed, adaptive):
        e.push(0, audio)
        e.drain()
    assert fixed.padded_slots == 3 * 7
    assert adaptive.padded_slots == 0
    assert adaptive.slot_histogram == {1: 3}


def test_multi_window_rounds_bitwise_equal_classic_beat():
    """max_per_stream_per_round > 1 drains a backlog in fewer rounds but
    must feed each stream's windows to the tracker in the same order —
    scores and events stay bitwise identical to the one-window beat."""
    cfg, params = _small_detector()
    rng = np.random.default_rng(11)
    n_streams, n_win = 3, 6
    audio = rng.standard_normal(
        (n_streams, n_win * features.N_SAMPLES)
    ).astype(np.float32)
    runs = []
    for adm in (None, AdmissionPolicy(max_per_stream_per_round=4)):
        engine = MonitorEngine(
            params, cfg, n_streams=n_streams, feature_kind="zcr",
            batch_slots=4, capacity_windows=n_win, admission=adm, **TRACK_KW,
        )
        for s in range(n_streams):
            engine.push(s, audio[s])
        scores = {s: [] for s in range(n_streams)}
        for ws in engine.drain():
            scores[ws.stream].append(ws.p_uav)
        runs.append((scores, engine.finalize(), engine.rounds))
    (sc_one, ev_one, rounds_one), (sc_multi, ev_multi, rounds_multi) = runs
    for s in range(n_streams):
        np.testing.assert_array_equal(
            np.asarray(sc_one[s], np.float64), np.asarray(sc_multi[s], np.float64)
        )
    assert ev_one == ev_multi
    assert rounds_multi < rounds_one  # the backlog drained in fewer rounds


def test_firehose_cannot_starve_trickle_stream():
    """Depth-fair round budget: a stream with a deep backlog never displaces
    another stream's first window of the round, so the trickle stream's
    window is always scored in the round it becomes ready."""
    cfg, params = _small_detector()
    rng = np.random.default_rng(7)
    adm = AdmissionPolicy(max_per_stream_per_round=4, round_budget=4)
    engine = MonitorEngine(
        params, cfg, n_streams=2, feature_kind="zcr", batch_slots=4,
        capacity_windows=12, admission=adm, **TRACK_KW,
    )
    # firehose: 8 windows buffered up front; trickle: one window per round
    engine.push(0, rng.standard_normal(8 * features.N_SAMPLES).astype(np.float32))
    for _ in range(2):
        engine.push(1, rng.standard_normal(features.N_SAMPLES).astype(np.float32))
        served = {0: 0, 1: 0}
        for ws in engine.step():
            served[ws.stream] += 1
        assert served[1] == 1  # trickle served the round it arrived
        assert served[0] == 3  # firehose fills the rest of the budget
    assert engine.deferred_windows[0] > 0
    assert engine.deferred_windows[1] == 0
    np.testing.assert_array_equal(engine.served_windows, [6, 2])


def test_max_streams_admission_first_come():
    cfg, params = _small_detector()
    rng = np.random.default_rng(5)
    engine = MonitorEngine(
        params, cfg, n_streams=3, feature_kind="zcr", batch_slots=2,
        admission=AdmissionPolicy(max_streams=2), **TRACK_KW,
    )
    win = lambda: rng.standard_normal(features.N_SAMPLES).astype(np.float32)
    engine.push(0, win())
    engine.push(1, win())
    assert engine.push(2, win()) == 0  # over the cap: refused, not scored
    assert engine.refused_chunks[2] == 1
    np.testing.assert_array_equal(engine.admitted, [True, True, False])
    assert sorted(ws.stream for ws in engine.step()) == [0, 1]
    # refusal is sticky, and an unknown stream id still raises
    assert engine.push(2, win()) == 0
    assert engine.refused_chunks[2] == 2
    with pytest.raises(ValueError, match="out of range"):
        engine.push(3, win())


def test_engine_evicts_persistently_overflowing_stream():
    """A stream whose ring overflows in evict_overflow_rounds consecutive
    committed rounds is de-admitted; a stream that overflows once and
    recovers is not."""
    cfg, params = _small_detector()
    rng = np.random.default_rng(9)
    engine = MonitorEngine(
        params, cfg, n_streams=2, feature_kind="zcr", batch_slots=2,
        capacity_windows=1,  # capacity == one window: easy to overflow
        admission=AdmissionPolicy(evict_overflow_rounds=2), **TRACK_KW,
    )
    win = lambda k: rng.standard_normal(k * features.N_SAMPLES).astype(np.float32)
    # round 1: stream 0 overflows (2 windows into capacity 1), stream 1 fine
    engine.push(0, win(2))
    engine.push(1, win(1))
    engine.step()
    assert engine.take_evictions() == []  # one bad round is not persistent
    # round 2: stream 0 overflows again -> evicted; stream 1 keeps serving
    engine.push(0, win(2))
    engine.push(1, win(1))
    engine.step()
    assert engine.take_evictions() == [0]
    np.testing.assert_array_equal(engine.admitted, [False, True])
    assert engine.push(0, win(1)) == 0 and engine.refused_chunks[0] == 1
    engine.push(1, win(1))
    assert [ws.stream for ws in engine.step()] == [1]


def test_ready_windows_incremental_matches_ring_scan():
    """The incremental ready-count must agree with a full ring scan at
    every point of an uneven push/step/overflow/restore sequence."""
    cfg, params = _small_detector()
    rng = np.random.default_rng(13)
    engine = MonitorEngine(
        params, cfg, n_streams=3, feature_kind="zcr", batch_slots=2,
        capacity_windows=2, **TRACK_KW,
    )

    def check():
        np.testing.assert_array_equal(
            engine.ready_windows(),
            np.array([r.ready for r in engine._rings], np.int64),
        )

    check()
    for _ in range(6):
        for s in range(3):
            n = int(rng.uniform(0.2, 2.6) * features.N_SAMPLES)
            engine.push(s, rng.standard_normal(n).astype(np.float32))
            check()
        engine.step()
        check()
    snap = engine.snapshot()
    fresh = MonitorEngine(
        params, cfg, n_streams=3, feature_kind="zcr", batch_slots=2,
        capacity_windows=2, **TRACK_KW,
    )
    fresh.restore(snap)
    np.testing.assert_array_equal(fresh.ready_windows(), engine.ready_windows())
