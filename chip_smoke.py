"""Chip smoke run: the detector's served path once on a TPU, at published width.

    python chip_smoke.py              # one chip: device, kernels, served, golden
    python chip_smoke.py --chips 4    # four chips: the sharded phase only

Phases, in order, each printing its own line(s):

* ``device``  — platform, device kind and count; exits non-zero unless JAX
  sees a TPU and the Pallas kernels resolve to compiled (not interpreted).
* ``kernels`` — ``conv1d_fused_q`` at the three canonical conv layers and
  ``quant_matmul`` at 35,072x64 and 8,704x64: the int32 accumulators must
  equal the im2col oracle (``ops.conv1d_q``'s patches through the matmul
  kernel) and a numpy int64 product, bitwise.
* ``served``  — ``CNNConfig()`` with seeded random weights, baked into two
  artifacts (uniform int8, and pruned 8,704-flatten with the mixed policy),
  each served to 64 synthetic scene streams in uneven chunks through
  ``MonitorEngine(on_device_features=True, batch_slots=64)`` and again
  through a 2-worker ``FleetSupervisor``.  Every delivered window must be
  scored; the engine's and the fleet's scores, and a lone window's, must
  equal one batched ``accelerator_forward`` bitwise; probabilities must be
  finite and sum to 1; the deviation from the float32 reference (numpy
  features, then ``cnn1d.forward``) must stay within ``DEV_BOUND``.
* ``golden``  — the committed ``artifacts/golden/`` artifacts, compiled for
  the chip, against their CPU interpret-mode expectations within
  ``GOLDEN_TOL`` (not bitwise: the float layers and the front-end round
  differently on the TPU).

``--chips 4`` runs only the sharded path: ``MonitorEngine(shards=4)`` over
four chips, bitwise against the one-chip ``accelerator_forward``.

Timings printed along the way come from one smoke run, not a benchmark.  The
last line of stdout is ``{"ok": true, "device": {...}}``; a failed check
raises, and the script exits non-zero without printing it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SEED = 0
STREAMS = 64
SLOTS = 64
SCENE_SECONDS = 4.0  # 5 windows of 0.8 s per stream
PRUNE_KEEP = 64  # 137 frames x 256 ch -> 136 x 64 = 8,704 (paper Table I)
MIXED_POLICY = "conv0/w=bf16,dense1/w=fp32"
CONV_LAYERS = ((1096, 1, 64), (548, 64, 128), (274, 128, 256))  # (L, Cin, Cout)
CONV_BATCH = 8
MATMUL_K = (35_072, 8_704)
SUM_TOL = 1e-5  # |row sum - 1| of the CORDIC softmax, float32

#: max |p - p_fp32| per artifact: the value the same check measured in
#: interpret mode on the CPU at SEED (int8 3.7844e-3, pruned-mixed 3.6284e-2)
#: plus a margin of 0.01 for the TPU's own float32 rounding in the front-end
#: and float layers, which can move an int8 activation by one step.
DEV_BOUND = {"int8": 0.0138, "pruned_mixed": 0.0463}
#: max |p - expected| of a golden artifact compiled for the chip.
GOLDEN_TOL = 1e-2


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: check failed: {what}")


# -- phases -------------------------------------------------------------------


def device_phase(need: int) -> dict:
    import jax

    from repro.kernels.backend import resolve_interpret

    devs = jax.devices()
    info = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    if info["platform"] != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {info['platform']!r})")
    if resolve_interpret(None):
        sys.exit("chip_smoke: Pallas kernels would run in interpret mode")
    if info["count"] < need:
        sys.exit(f"chip_smoke: need {need} chip(s), JAX sees {info['count']}")
    return info


def _np_im2col(x, k: int):
    """(B, L, C) -> (B*L, k*C) 'same' patches, numpy (the int64 reference)."""
    import numpy as np

    b, l, c = x.shape
    pad = (k - 1) // 2
    xp = np.pad(x, ((0, 0), (pad, k - 1 - pad), (0, 0)))
    return np.stack([xp[:, t : t + l] for t in range(k)], axis=2).reshape(b * l, k * c)


def kernels_phase(seed: int) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.kernels.conv1d_fused import conv1d_fused_q
    from repro.kernels.quant_matmul import quant_matmul

    rng = np.random.default_rng(seed)
    k = 3
    for l, cin, cout in CONV_LAYERS:
        xq = rng.integers(-128, 128, (CONV_BATCH, l, cin), dtype=np.int8)
        wq = rng.integers(-128, 128, (k, cin, cout), dtype=np.int8)
        one, ones = jnp.float32(1.0), jnp.ones((cout,), jnp.float32)
        acc = np.asarray(conv1d_fused_q(xq, wq, one, ones, return_acc=True))
        patches = ops._im2col(jnp.asarray(xq), k)
        oracle = np.asarray(quant_matmul(
            patches, jnp.asarray(wq.reshape(k * cin, cout)),
            jnp.ones((1, 1)), jnp.ones((1, cout)), return_acc=True,
        )).reshape(acc.shape)
        ref = (_np_im2col(xq.astype(np.int64), k)
               @ wq.reshape(k * cin, cout).astype(np.int64)).reshape(acc.shape)
        check(acc.dtype == np.int32, f"conv acc dtype {acc.dtype}")
        check(np.array_equal(acc, oracle), f"conv L={l} Cin={cin}: fused != im2col oracle")
        check(np.array_equal(acc, ref), f"conv L={l} Cin={cin}: fused != numpy int64")
        print(f"kernels: conv1d_fused_q B={CONV_BATCH} L={l} Cin={cin} "
              f"Cout={cout}: int32 acc == im2col oracle == numpy int64, bitwise",
              flush=True)
    for kk in MATMUL_K:
        x = rng.integers(-128, 128, (SLOTS, kk), dtype=np.int8)
        w = rng.integers(-128, 128, (kk, 64), dtype=np.int8)
        acc = np.asarray(quant_matmul(
            x, w, jnp.ones((SLOTS, 1)), jnp.ones((1, 64)), return_acc=True
        ))
        ref = x.astype(np.int64) @ w.astype(np.int64)
        check(np.array_equal(acc, ref), f"quant_matmul K={kk}: != numpy int64")
        print(f"kernels: quant_matmul {kk}x64 M={SLOTS}: int32 acc == numpy "
              f"int64, bitwise", flush=True)


def build_artifacts(seed: int) -> dict:
    """Seeded published-width detector baked two ways; each entry holds the
    artifact and its float32 reference (features -> class probabilities)."""
    import jax

    from repro.core.precision_policy import PrecisionPolicy
    from repro.models import cnn1d
    from repro.serving.quantized_params import quantize_params

    cfg = cnn1d.CNNConfig()
    params = cnn1d.init_params(jax.random.PRNGKey(seed), cfg)
    pparams, pcfg, spec = cnn1d.prune_model(
        params, cfg, keep=PRUNE_KEEP, trim_frames=1
    )
    check(spec.flatten_after == 8_704, f"pruned flatten {spec.flatten_after}")
    policy = PrecisionPolicy.parse(MIXED_POLICY, default="int8")

    def softmax_of(logits_fn):
        def ref(feats):
            with jax.default_matmul_precision("highest"):
                return jax.nn.softmax(logits_fn(feats), axis=-1)
        return ref

    return {
        "int8": dict(
            qp=quantize_params(params, cfg, mode="int8", feature_kind="mfcc20"),
            ref=softmax_of(lambda f: cnn1d.forward(params, f, cfg)),
        ),
        "pruned_mixed": dict(
            qp=quantize_params(
                params, cfg, mode="int8", prune=spec, policy=policy,
                feature_kind="mfcc20",
            ),
            ref=softmax_of(lambda f: cnn1d.forward_pruned(pparams, f, pcfg, spec)),
        ),
        "cfg": cfg,
    }


def make_traffic(seed: int):
    """64 synthetic scenes and an uneven chunk schedule (rounds of
    ``(stream, lo, hi)`` pushes, 0.3-1.7 windows each, never aligned)."""
    import numpy as np

    from repro.data import features
    from repro.launch.monitor import synth_scene

    rng = np.random.default_rng(seed + 1)
    scenes = [synth_scene(SCENE_SECONDS, rng)[0] for _ in range(STREAMS)]
    schedule, cursors = [], [0] * STREAMS
    while any(c < len(s) for c, s in zip(cursors, scenes)):
        pushes = []
        for s in range(STREAMS):
            chunk = int(rng.uniform(0.3, 1.7) * features.N_SAMPLES)
            if cursors[s] < len(scenes[s]):
                pushes.append((s, cursors[s], cursors[s] + chunk))
                cursors[s] += chunk
        schedule.append(pushes)
    n = features.N_SAMPLES
    windows = np.stack(
        [sc[i * n : (i + 1) * n] for sc in scenes for i in range(len(sc) // n)]
    )
    per_stream = [len(sc) // n for sc in scenes]
    return scenes, schedule, windows, per_stream


def serve(engine, scenes, schedule):
    """Deliver the schedule one engine round per tick, then drain; returns
    per-stream ``p_uav`` lists in window order and the wall time."""
    scored = [[] for _ in scenes]

    def take(batch):
        for ws in batch:
            check(ws.window_idx == len(scored[ws.stream]),
                  f"stream {ws.stream} scored window {ws.window_idx} out of order")
            scored[ws.stream].append(ws.p_uav)

    t0 = time.perf_counter()
    for pushes in schedule:
        for s, lo, hi in pushes:
            engine.push(s, scenes[s][lo:hi])
        take(engine.step())
    take(engine.drain())
    return scored, time.perf_counter() - t0


def batched_forward(qp, cfg, windows):
    import jax.numpy as jnp
    import numpy as np

    from repro.serving.accelerator import accelerator_forward

    return np.asarray(
        accelerator_forward(qp, jnp.asarray(windows), cfg, raw_windows=True)
    )


def reference_deviation(ref, windows, probs) -> float:
    """max |p - p_fp32| against numpy features -> float32 ``cnn1d`` forward."""
    import jax.numpy as jnp
    import numpy as np

    from repro.data import features

    feats = features.batch_features(windows, "mfcc20").astype(np.float32)
    return float(np.max(np.abs(probs - np.asarray(ref(jnp.asarray(feats))))))


def _check_scores(label, scored, per_stream, probs):
    import numpy as np

    check([len(p) for p in scored] == per_stream,
          f"{label}: windows scored per stream {[len(p) for p in scored]} "
          f"!= delivered {per_stream}")
    got = np.concatenate([np.asarray(p, np.float64) for p in scored])
    want = probs[:, 1].astype(np.float64)
    check(np.array_equal(got, want),
          f"{label}: != batched forward ({int((got != want).sum())} differ, "
          f"max {np.abs(got - want).max():.3e})")


def served_phase(arts, traffic) -> None:
    import numpy as np

    from repro.serving.engine import MonitorEngine
    from repro.serving.supervisor import FleetSupervisor

    cfg = arts["cfg"]
    scenes, schedule, windows, per_stream = traffic
    kw = dict(feature_kind="mfcc20", on_device_features=True, batch_slots=SLOTS)
    for name in ("int8", "pruned_mixed"):
        qp, ref = arts[name]["qp"], arts[name]["ref"]
        engine = MonitorEngine(qp, cfg, n_streams=STREAMS, **kw)
        t0 = time.perf_counter()
        engine.precompile()
        compile_s = time.perf_counter() - t0
        scored, dt = serve(engine, scenes, schedule)
        check(engine.dropped_samples == 0, f"{name}: {engine.dropped_samples} samples dropped")
        check(engine.windows_scored == len(windows),
              f"{name}: {engine.windows_scored} scored of {len(windows)} delivered")
        probs = batched_forward(qp, cfg, windows)
        check(bool(np.isfinite(probs).all()), f"{name}: non-finite probabilities")
        sum_err = float(np.max(np.abs(probs.sum(axis=1) - 1.0)))
        check(sum_err <= SUM_TOL, f"{name}: row sums off by {sum_err:.3e}")
        _check_scores(f"{name} engine", scored, per_stream, probs)
        # a lone window, as the adaptive slot ladder's first rung sends it
        lone = batched_forward(qp, cfg, windows[:1])
        check(np.array_equal(lone, probs[:1]), f"{name}: B=1 forward != batched forward")
        dev = reference_deviation(ref, windows, probs)
        check(dev <= DEV_BOUND[name], f"{name}: max |p - fp32 ref| {dev:.4e} > {DEV_BOUND[name]}")

        fleet = FleetSupervisor(qp, cfg, n_streams=STREAMS, n_workers=2, **kw)
        try:
            fleet_scored, fleet_dt = serve(fleet, scenes, schedule)
        finally:
            fleet.close()
        _check_scores(f"{name} fleet", fleet_scored, per_stream, probs)
        print(f"served[{name}]: {STREAMS} streams, {len(windows)} windows all "
              f"scored, 0 dropped; engine == 2-worker fleet == batched "
              f"forward (and B=1 forward), bitwise; rows finite, max |sum-1| "
              f"{sum_err:.3e}; "
              f"max |p - fp32 ref| {dev:.4e} <= {DEV_BOUND[name]}", flush=True)
        print(f"smoke timing[{name}] (one run, not a benchmark): compile "
              f"{compile_s:.1f} s; engine {len(windows) / dt:.1f} windows/s, "
              f"fleet {len(windows) / fleet_dt:.1f} windows/s", flush=True)


def golden_phase() -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.data.features import FEATURE_DIMS
    from repro.models import cnn1d
    from repro.serving.accelerator import accelerator_forward
    from repro.serving.quantized_params import load_artifact

    golden = ROOT / "artifacts" / "golden"
    for name in ("int8", "pruned_mixed", "int8_ondevice"):
        raw = name.endswith("_ondevice")
        x = np.load(golden / ("input_windows.npy" if raw else "input.npy"))
        qp = load_artifact(golden / f"detector_{name}.npz")
        width = FEATURE_DIMS[qp.feature_kind] if raw else x.shape[1]
        cfg = cnn1d.CNNConfig(input_len=width, channels=(4, 8), hidden=8)
        got = np.asarray(accelerator_forward(qp, jnp.asarray(x), cfg, raw_windows=raw))
        want = np.load(golden / f"expected_{name}.npy")
        dev = float(np.max(np.abs(got - want)))
        check(dev <= GOLDEN_TOL, f"golden {name}: max |dp| {dev:.3e} > {GOLDEN_TOL}")
        print(f"golden[{name}]: compiled on chip, max |p - CPU expected| "
              f"{dev:.3e} <= {GOLDEN_TOL} ({int((got != want).sum())}/"
              f"{want.size} values differ)", flush=True)


def sharded_phase(arts, traffic, shards: int) -> None:
    from repro.serving.engine import MonitorEngine

    cfg = arts["cfg"]
    scenes, schedule, windows, per_stream = traffic
    for name in ("int8", "pruned_mixed"):
        qp = arts[name]["qp"]
        probs = batched_forward(qp, cfg, windows)  # one chip
        engine = MonitorEngine(
            qp, cfg, n_streams=STREAMS, feature_kind="mfcc20",
            on_device_features=True, batch_slots=SLOTS, shards=shards,
        )
        t0 = time.perf_counter()
        engine.precompile()
        compile_s = time.perf_counter() - t0
        scored, dt = serve(engine, scenes, schedule)
        _check_scores(f"{name} sharded", scored, per_stream, probs)
        print(f"sharded[{name}]: MonitorEngine shards={shards}, "
              f"{len(windows)} windows == 1-chip accelerator_forward, bitwise",
              flush=True)
        print(f"smoke timing[{name}, {shards} chips] (one run, not a "
              f"benchmark): compile {compile_s:.1f} s; "
              f"{len(windows) / dt:.1f} windows/s", flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded phase across four chips")
    ap.add_argument("--seed", type=int, default=SEED)
    args = ap.parse_args(argv)
    # libtpu logs under /tmp unless told otherwise; keep them in the checkout
    os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".tpu_logs"))

    from repro.compile_cache import enable_compile_cache

    info = device_phase(args.chips)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    if args.chips == 1:
        kernels_phase(args.seed)
        served_phase(build_artifacts(args.seed), make_traffic(args.seed))
        golden_phase()
    else:
        sharded_phase(build_artifacts(args.seed), make_traffic(args.seed), args.chips)
    print(f"smoke timing: all phases {time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": info}))


if __name__ == "__main__":
    main()
