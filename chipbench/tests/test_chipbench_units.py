"""CPU tests of the benchmark's pieces that need no engine run."""
from __future__ import annotations

import hashlib
import json
import types

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import catalog, check, flops, load, trace
from chipbench.scenes import SR, WINDOW, ScenePool
from chipbench.tests.conftest import ROOT


def _config(name):
    return json.loads((ROOT / "chipbench" / "configs" / f"{name}.json").read_text())


def _weights(cfg, seed):
    return catalog.family(cfg["family"]).weights(cfg["model"], seed)


def _layers(cfg):
    return catalog.family(cfg["family"]).layers(cfg)


@pytest.mark.parametrize("name,ops", [("shield8_int8", 85_716_224),
                                      ("shield8_pruned_mixed", 41_938_176)])
def test_ops_per_window(name, ops):
    assert flops.ops_per_window(_layers(_config(name))) == ops


def test_peak_seconds_use_each_layers_precision():
    peaks = catalog.peaks("TPU v5 lite")
    cfg = _config("shield8_pruned_mixed")
    want = sum(n / (197e12 if mode in ("bf16", "fp32") else 393e12)
               for _, n, mode in _layers(cfg))
    assert flops.peak_seconds_per_window(_layers(cfg), peaks) == pytest.approx(want)
    assert peaks["int8_ops_per_s"] == 393e12 and peaks["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        catalog.peaks("TPU v9 imaginary")


def test_every_benchmark_entry_has_its_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = catalog.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer, w["name"]
        reported = {m["name"] for m in cell.end_to_end}
        for m in cell.per_layer:
            assert m["moves"] in reported
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(catalog.reader(m["name"]))


def _loop(traffic, seed, seconds, **mix_keys):
    mix = json.loads((ROOT / "chipbench" / "traffic" / f"{traffic}.json").read_text())
    mix.update(clips=2, clip_windows=2, **mix_keys)
    return catalog.loop(mix["loop"]).Loop(None, mix, np.random.default_rng([seed, 1]),
                                          load.Spans(), load.Scores(), seconds=seconds,
                                          capacity_windows=8)


def _closed(seed, n=6):
    return _loop("catchup", seed, 1.0, streams=n)


def _open(seed, n=6, horizon=5.0):
    return _loop("realtime", seed, horizon - 5.0, streams=n, ramp_seconds=0.0)


def test_traffic_is_a_function_of_the_seed():
    big = 2**31 + 12345
    a, b, c = _closed(big), _closed(big), _closed(big + 1)
    assert np.array_equal(a.sizes, b.sizes) and not np.array_equal(a.sizes, c.sizes)
    assert all(np.array_equal(x, y) for x, y in zip(a.pool.clips, b.pool.clips))
    assert np.array_equal(a.pool.offset, b.pool.offset)
    o1, o2, o3 = _open(big), _open(big), _open(big + 1)
    for f in ("due", "stream", "start", "size"):
        assert np.array_equal(getattr(o1, f), getattr(o2, f))
    assert not np.array_equal(o1.due, o3.due)


def test_scene_pool_chunks_match_windows():
    pool = ScenePool(4, 2, 3, 30_000, np.random.default_rng(5))
    for s in range(4):
        stream = np.concatenate([pool.chunk(s, i, 7_000) for i in range(0, 5 * 7_000, 7_000)])
        assert np.array_equal(stream[: 2 * WINDOW].reshape(2, WINDOW), pool.windows(s, 0, 2))


class _FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += max(dt, 0.0)


class _FakeEngine:
    """Ring counters only; every ``step()`` takes ``step_s`` of fake time."""

    def __init__(self, n, clock, step_s):
        self.buf = np.zeros(n, np.int64)
        self.idx = np.zeros(n, np.int64)
        self.clock, self.step_s = clock, step_s

    def push(self, s, x):
        self.buf[s] += len(x)

    def ready_windows(self):
        return self.buf // WINDOW

    def step(self):
        self.clock.t += self.step_s
        out = []
        for s in np.flatnonzero(self.buf >= WINDOW):
            out.append(types.SimpleNamespace(stream=int(s), window_idx=int(self.idx[s]), p_uav=0.5,
                                             smoothed=0.5, active=False))
            self.buf[s] -= WINDOW
            self.idx[s] += 1
        return out


def test_open_loop_latency_runs_from_due_time(monkeypatch):
    clock = _FakeClock()
    monkeypatch.setattr(load, "clock", clock)
    monkeypatch.setattr(load, "sleep", clock.sleep)
    drv = _open(7, n=5, horizon=6.0)
    drv.engine = _FakeEngine(5, clock, step_s=0.003)
    drv.origin = clock()
    drv.run(drv.origin + 5.0)
    sc = drv.scores.arrays()
    assert len(sc["t"]) >= 5 * 4
    due = drv.window_due(sc["stream"], sc["idx"]) + drv.origin
    # pushed exactly when due, scored by the next step: latency = one step
    np.testing.assert_allclose(sc["t"] - due, 0.003, atol=1e-9)
    pushed = ~np.isnan(drv.pushed_at)
    np.testing.assert_allclose(drv.pushed_at[pushed], drv.due[pushed], atol=1e-9)
    # a stall delays every window behind it: latency keeps counting from due time
    drv.engine.step_s = 0.5
    drv.run(drv.origin + 6.0)
    sc = drv.scores.arrays()
    late = sc["t"] - (drv.window_due(sc["stream"], sc["idx"]) + drv.origin)
    assert late.max() >= 0.5


def test_open_loop_schedule_is_realtime():
    drv = _open(3, n=8, horizon=20.0)
    samples = np.zeros(8)
    np.add.at(samples, drv.stream, drv.size)
    # every microphone delivers 16 kHz: within one chunk of its share of the horizon
    assert np.all(np.abs(samples / SR - (20.0 - drv.phase)) < 0.4 + 1e-9)
    assert np.all(np.diff(drv.due) >= 0)


def _tr(devs, host):
    return {"devices": devs, "host": host}


def test_trace_reduction_on_synthetic_events():
    win = ("chipbench.window", 1_000, 11_000)
    host = [win, ("top_up", 1_000, 4_000), ("step", 4_000, 11_000)]
    d0 = [("fusion.1", 500, 2_000), ("conv", 5_000, 7_000), ("conv", 6_000, 8_000)]
    d1 = [("fusion.1", 4_000, 6_000)]
    t = _tr({0: d0, 1: d1}, host)
    assert trace.window_s(t) == pytest.approx(1e-5)
    busy = trace.busy_s(t)
    assert busy[0] == pytest.approx(4_000e-9) and busy[1] == pytest.approx(2_000e-9)
    ops = dict(trace.device_ops(t))
    assert ops["conv"] == pytest.approx(4_000e-9 / 2) and ops["fusion.1"] == pytest.approx(1.5e-6)
    gaps = dict(trace.idle_gaps(t))
    assert gaps["top_up"] == pytest.approx(3_000e-9)  # 2,000 .. 5,000 ns
    assert gaps["step"] == pytest.approx(3_000e-9)  # 8,000 .. 11,000 ns


def test_logit_gap_is_scale_free_and_clipped():
    p_ref = np.array([0.1, 0.5, 0.9, 1 - 1e-9])
    assert check.logit_gap(p_ref, p_ref) == 0.0
    lo = check.log_odds(p_ref)
    assert lo[-1] == check.LOGODDS_CLIP
    p = 1 / (1 + np.exp(-(lo + 0.5)))
    assert check.logit_gap(p, p_ref) == pytest.approx(0.5 / (1 + np.std(lo)), rel=1e-6)


@pytest.mark.parametrize("answers,want", [
    ([(0, 0), (0, 1), (2, 0)], 0),  # every completed window answered once, in order
    ([(0, 0), (2, 0)], 1),  # stream 0's second window never came back
    ([(0, 0), (0, 1), (0, 1), (2, 0)], 1),  # one answered twice
    ([(0, 1), (0, 0), (2, 0)], 0),  # returned out of order, still each once
    ([(0, 0), (0, 2), (2, 0)], 2),  # a gap: window 1 missing, window 2 misplaced
    ([(0, 0), (0, 1)], 1),  # stream 2 never answered at all
])
def test_unscored_counts_every_stream(answers, want):
    s, i = (np.array(c, np.int64) for c in zip(*answers))
    pushed = np.array([2, 0, 1]) * WINDOW + 5
    assert check.unscored({"stream": s, "idx": i}, pushed) == want


@pytest.mark.parametrize("name", ["shield8_int8", "shield8_pruned_mixed"])
def test_control_fails_the_limit_at_published_width(name):
    """The reference one precision step lower (the control) reads above the
    configuration's limit on 96 windows at the published widths."""
    cfg = _config(name)
    cell = types.SimpleNamespace(config=cfg, reference=catalog._module(
        ROOT / "chipbench" / "configs" / f"{cfg['reference']}.py"))
    params = _weights(cfg, 2**31 + 99)
    pool = ScenePool(48, 6, 4, 30_000, np.random.default_rng(99))
    x = np.concatenate([pool.windows(s, 0, 2) for s in range(48)])
    stated = cfg["stated_precision"]
    p_ref = check.reference_p(cell, params, x, {k: "fp32" for k in stated})
    p_ctl = check.reference_p(cell, params, x, cell.reference.control_modes(stated))
    assert check.logit_gap(p_ctl, p_ref) > cfg["limits"]["logit_gap"]


def test_control_groups_lower_what_they_name():
    ref = catalog._module(ROOT / "chipbench" / "configs" / "shield8_cnn_reference.py")
    stated = _config("shield8_pruned_mixed")["stated_precision"]
    assert ref.control_modes(stated) == {"front_end": "bf16", "conv0": "int8", "conv1": "int4",
                                         "conv2": "int4", "dense0": "int4", "dense1": "bf16"}
    fe = ref.control_modes(stated, ["front_end"])
    assert fe == {k: "bf16" if k == "front_end" else "fp32" for k in stated}
    x = jnp.asarray(ScenePool(4, 2, 2, WINDOW, np.random.default_rng(4)).windows(1, 0, 2))
    gap = float(jnp.max(jnp.abs(ref.features(x, "bf16") - ref.features(x))))
    assert 0.0 < gap < 0.5  # bfloat16 projections move the features, a little


def test_reference_tracker_float32_control_departs():
    ref = catalog._module(ROOT / "chipbench" / "configs" / "shield8_cnn_reference.py")
    p = np.random.default_rng(0).uniform(0, 1, 200)
    kw = dict(ema_alpha=0.4, enter=0.65, exit=0.35)
    s64, a64 = ref.track(p, **kw)
    s32, _ = ref.track(p, dtype=np.float32, **kw)
    assert np.max(np.abs(s32 - s64)) > _config("shield8_int8")["limits"]["tracker_gap"]
    ema = p[0]
    for i, v in enumerate(p):  # the plain recurrence, by hand
        ema = v if i == 0 else 0.4 * v + 0.6 * ema
        assert s64[i] == ema


def test_reference_prune_matches_table_one():
    cfg = _config("shield8_pruned_mixed")
    ref = catalog._module(ROOT / "chipbench" / "configs" / "shield8_cnn_reference.py")
    params = _weights(cfg, 5)
    pruned, kf = ref.prune(params, cfg["model"], cfg["bake"]["prune"])
    assert kf == 136 and pruned["dense0"]["w"].shape == (8_704, 64)
    imp = jnp.abs(params["conv2"]["w"]).sum(axis=(0, 1))
    kept = np.sort(np.argsort(np.asarray(imp))[-64:])
    assert np.array_equal(np.asarray(pruned["conv2"]["w"]), np.asarray(params["conv2"]["w"])[:, :, kept])


# -- the family seam: the 1D-CNN's weights, reference and checks as before it --

PIN_SEED = 2**31 + 77
#: sha256 of the float weights of both configurations' model at PIN_SEED, as
#: the harness made them before the family seam (``weights.make``)
PIN_WEIGHTS = "7656e18674232370ff04287dd0201464f1af83aa7386b93458014cc946b162cf"
#: the reference's probability of "UAV" on ``_pin_windows()`` at PIN_SEED, as
#: ``check.reference_p`` composed it before the seam (prune, features, forward,
#: column 1); the last bits move with the CPU's thread count
PIN_P = {
    "shield8_int8": [0.860665500164032, 0.7515239715576172, 0.8030366897583008, 0.631642758846283,
                     0.7870256900787354, 0.4299183487892151, 0.46452897787094116, 0.7688567042350769,
                     0.5157069563865662, 0.8295890092849731, 0.6944171190261841, 0.373270720243454,
                     0.4311385452747345, 0.6338753700256348, 0.5469149351119995, 0.8514454960823059],
    "shield8_pruned_mixed": [0.12751910090446472, 0.13443829119205475, 0.09411362558603287,
                             0.07955709844827652, 0.10896006971597672, 0.2374526709318161,
                             0.14062075316905975, 0.07738539576530457, 0.13482354581356049,
                             0.06303030997514725, 0.11038424074649811, 0.21716490387916565,
                             0.10404393821954727, 0.07027792930603027, 0.1300031840801239,
                             0.09292779862880707],
}
#: ``check.compare`` on ``_pin_scores`` at PIN_SEED, before the seam
PIN_CHECKS = {
    "shield8_int8": {"logit_gap": 1.691331284424362, "tracker_gap": 1.3999999992631018e-08,
                     "unscored": 1.0},
    "shield8_pruned_mixed": {"logit_gap": 2.774721914099131, "tracker_gap": 1.3999999992631018e-08,
                             "unscored": 1.0},
}
PIN_OPS = {"shield8_int8": 85_716_224, "shield8_pruned_mixed": 41_938_176}


def _digest(params) -> str:
    h = hashlib.sha256()
    for layer in sorted(params):
        for k in sorted(params[layer]):
            a = np.asarray(params[layer][k])
            h.update(f"{layer}/{k}{a.shape}{a.dtype}".encode())
            h.update(a.tobytes())
    return h.hexdigest()


def _pin_windows():
    pool = ScenePool(8, 2, 2, WINDOW, np.random.default_rng(7))
    return np.concatenate([pool.windows(s, 0, 2) for s in range(8)])


def _reference_cell(cfg, **kw):
    return types.SimpleNamespace(config=cfg, reference=catalog._module(
        ROOT / "chipbench" / "configs" / f"{cfg['reference']}.py"), **kw)


@pytest.mark.parametrize("name", sorted(PIN_P))
def test_shield8_cnn_weights_are_pinned(name):
    cfg = _config(name)
    assert cfg["family"] == "shield8_cnn"
    assert _digest(_weights(cfg, PIN_SEED)) == PIN_WEIGHTS


@pytest.mark.parametrize("name", sorted(PIN_P))
def test_p_uav_is_the_composition_it_replaced(name):
    """``p_uav`` gives, bit for bit, what prune -> features -> forward ->
    column 1 gives, for the reference and for its control, and the pinned
    reference probabilities."""
    import jax

    cfg = _config(name)
    ref = _reference_cell(cfg).reference
    params = _weights(cfg, PIN_SEED)
    x = jnp.asarray(_pin_windows())
    pruned, keep_frames = ref.prune(params, cfg["model"], cfg["bake"].get("prune"))
    stated = cfg["stated_precision"]
    for modes in ({k: "fp32" for k in stated}, ref.control_modes(stated)):
        @jax.jit
        def composed(params, windows, modes=modes):
            feats = ref.features(windows, modes.get("front_end", "fp32"))
            return ref.forward(params, feats, keep_frames, modes)[:, 1]

        with jax.default_matmul_precision("highest"):
            got = np.asarray(ref.p_uav(params, x, cfg, modes))
            want = np.asarray(composed(pruned, x))
        assert np.array_equal(got, want)
    cell = _reference_cell(cfg)
    p = check.reference_p(cell, params, _pin_windows(), {k: "fp32" for k in stated})
    np.testing.assert_allclose(p, PIN_P[name], rtol=1e-5)


def _pin_scores(ref):
    """Six streams answered three windows each, in order, but for stream 5's
    last; the smoothed scores a little off the reference tracker's."""
    stream = np.repeat(np.arange(6), 3)[:-1]
    idx = np.tile(np.arange(3), 6)[:-1]
    p = np.random.default_rng(12).uniform(0.05, 0.95, len(stream))
    sm, act = np.empty_like(p), np.empty(len(p), bool)
    for s in range(6):
        sel = stream == s
        sm[sel], act[sel] = ref.track(p[sel], ema_alpha=0.4, enter=0.65, exit=0.35)
    sm += 1e-9 * np.arange(len(sm))
    return {"stream": stream, "idx": idx, "p": p, "smoothed": sm, "active": act}


@pytest.mark.parametrize("name", sorted(PIN_CHECKS))
def test_checks_and_ops_are_pinned(name):
    """The numbers that decide ``correct``, and the operation count, read
    what they read before the family seam."""
    cfg = _config(name)
    cell = _reference_cell(cfg, traffic={"check_streams": 3, "check_windows": 8})
    pool = ScenePool(6, 2, 3, WINDOW, np.random.default_rng(11))
    checks, n = check.compare(cell, _weights(cfg, PIN_SEED), pool, _pin_scores(cell.reference),
                              np.full(6, 3 * WINDOW + 5), PIN_SEED)
    assert n == 17
    got = {k: v["value"] for k, v in checks.items()}
    assert got == pytest.approx(PIN_CHECKS[name], rel=1e-5)
    assert got["tracker_gap"] == PIN_CHECKS[name]["tracker_gap"]
    assert flops.ops_per_window(_layers(cfg)) == PIN_OPS[name]
