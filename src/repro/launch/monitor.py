"""Multi-stream continuous-monitoring driver —
``python -m repro.launch.monitor --streams 4 --duration 30``.

Simulates N always-on microphones: each stream is a synthetic acoustic scene
(background clutter with one UAV pass over a random interval), delivered to
the :class:`~repro.serving.engine.MonitorEngine` in uneven real-world-ish
chunks (never aligned to window boundaries).  The engine windows each
stream, scores ready windows in micro-batches on the W8A8 kernel datapath,
and the vectorised temporal tracker emits per-stream detection events that
are printed against the known ground-truth pass.

By default a small detector is trained in-process on the synthetic corpus
(psd features, ~1 min) so the demo produces *real* detections; ``--random``
skips training for a pure plumbing smoke, and ``--feature mfcc20 --trained``
uses the full cached canonical detector artifact (slow in interpret mode).
"""
from __future__ import annotations

import argparse
import time

from repro import hostdevices

# ``--shards k`` on CPU needs k simulated XLA devices, configured *before*
# the first jax import — peek at the raw argv at module-import time.
_shards = hostdevices.shards_from_argv()
if _shards is not None:
    hostdevices.force_host_device_count(_shards)

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.data import acoustic, features
from repro.models import cnn1d
from repro.serving.engine import MonitorEngine

SMALL_CFG = dict(channels=(4, 8), hidden=8)


def synth_scene(seconds: float, rng: np.random.Generator):
    """One stream's audio: background everywhere except one UAV pass.

    Returns (samples, (t_on, t_off)) with the pass interval in seconds.
    """
    n_win = max(1, int(seconds / features.WINDOW_S))
    if n_win >= 6:
        on = int(rng.integers(1, n_win - 4))
        off = int(min(n_win - 1, on + rng.integers(3, max(4, n_win // 2))))
    else:
        on, off = 0, n_win  # short scene: all UAV
    wins = []
    for i in range(n_win):
        x = acoustic.synth_uav(rng) if on <= i < off else acoustic.synth_background(rng)
        wins.append(acoustic.add_noise_snr(x, float(rng.uniform(8, 20)), rng))
    return np.concatenate(wins), (on * features.WINDOW_S, off * features.WINDOW_S)


def quick_detector(kind: str, cfg: cnn1d.CNNConfig, *, n: int = 240, seed: int = 0):
    """Train a small in-process detector on the synthetic corpus."""
    from repro.training import loop

    ds = acoustic.make_dataset(n, seed=seed, snr_range=(0.0, 20.0))
    feats = features.batch_features(ds.audio, kind)
    n_tr = int(0.8 * n)
    res = loop.train_detector(
        feats[:n_tr], ds.labels[:n_tr], feats[n_tr:], ds.labels[n_tr:],
        cfg, epochs=12, batch=32, patience=12,
    )
    print(f"monitor: quick-trained {kind} detector, val_acc={res.best_val_acc:.2f}")
    return res.params


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--streams", type=int, default=4)
    ap.add_argument("--duration", "--seconds", type=float, default=16.0,
                    dest="duration", help="seconds per stream")
    ap.add_argument("--precision", choices=("int8", "fxp8"), default="int8")
    ap.add_argument("--prune", type=int, default=None, metavar="KEEP",
                    help="bake a structured channel prune into the served "
                         "artifact: keep this many output channels of the "
                         "last conv block (+1 boundary-frame trim, paper "
                         "SIII-C)")
    ap.add_argument("--policy", default=None, metavar="SPEC",
                    help="bake a per-layer precision policy into the served "
                         "artifact: a PrecisionPolicy JSON file/string, or "
                         "inline 'conv0/w=bf16,dense1/w=fp32' rules "
                         "(default mode = --precision)")
    ap.add_argument("--shards", type=int, default=None,
                    help="shard each micro-batch over this many devices "
                         "(sharded-batch dispatch; bitwise-identical results)")
    ap.add_argument("--feature", default=None, choices=sorted(features.FEATURE_DIMS),
                    help="feature set (default: psd, or mfcc20 with --trained)")
    ap.add_argument("--slots", type=int, default=8, help="micro-batch slot count")
    ap.add_argument("--adaptive-slots", action="store_true",
                    help="grow/shrink micro-batch blocks over a power-of-two "
                         "slot ladder to fit the ready backlog instead of "
                         "padding dead slots with silence (bitwise-identical "
                         "scores; shapes are pre-jitted)")
    ap.add_argument("--max-streams", type=int, default=None, metavar="N",
                    help="admit at most N distinct streams (first come, "
                         "first served); chunks for later streams are "
                         "refused and counted, never scored")
    ap.add_argument("--workers", type=int, default=None, metavar="N",
                    help="serve through the fault-tolerant fleet supervisor "
                         "with N health-checked workers instead of one "
                         "monolithic engine (bitwise-identical results)")
    ap.add_argument("--faults", default=None, metavar="PLAN.json",
                    help="inject a deterministic fault plan (written by "
                         "python -m repro.serving.faults) through the fleet "
                         "supervisor; implies --workers 2 unless given")
    ap.add_argument("--lanes", choices=("threads",), default=None,
                    help="give each fleet worker a named execution lane "
                         "(thread) so workers' rounds overlap — host "
                         "block packing for one worker overlaps device "
                         "scoring for another (bitwise-identical results); "
                         "implies --workers 2 unless given")
    ap.add_argument("--autoscale", action="store_true",
                    help="close the SLO loop: a FleetController watches "
                         "round latency and defer/drop rates and resizes "
                         "the fleet (spawn/retire workers, retune admission "
                         "budgets) against a default target; implies "
                         "--workers 2 unless given")
    ap.add_argument("--state-dir", default=None, metavar="DIR",
                    help="durable crash-safe fleet state: per-worker "
                         "checkpoints + write-ahead chunk journals under "
                         "DIR; rerun with the same DIR (and seed) after a "
                         "SIGKILL to resume bitwise where the fleet left "
                         "off; implies --workers 2 unless given")
    ap.add_argument("--fsync", choices=("always", "interval", "never"),
                    default="interval",
                    help="WAL fsync policy with --state-dir")
    ap.add_argument("--checkpoint-interval", type=int, default=1, metavar="R",
                    help="checkpoint every R rounds with --state-dir (R>1 "
                         "lowers overhead; 1 is the exact-restart setting)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--random", action="store_true",
                    help="random-init weights (plumbing smoke, no real detections)")
    ap.add_argument("--trained", action="store_true",
                    help="use the cached canonical detector artifact (mfcc20)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.feature is None:
        # --trained serves the cached mfcc20 artifact; an explicit other
        # feature would silently train a full canonical model on cache miss.
        args.feature = "mfcc20" if args.trained else "psd"

    if args.trained:
        from repro.training.detector_artifact import get_detector

        det = get_detector(args.feature)
        params, cfg = det["params"], det["cfg"]
    else:
        cfg = cnn1d.CNNConfig(input_len=features.FEATURE_DIMS[args.feature], **SMALL_CFG)
        if args.random:
            params = cnn1d.init_params(jax.random.PRNGKey(args.seed), cfg)
            print("monitor: --random weights; probabilities are meaningless")
        else:
            params = quick_detector(args.feature, cfg, seed=args.seed)

    # Deploy-time decisions baked into the served artifact (quantise-once).
    prune_spec = None
    if args.prune is not None:
        from repro.core.pruning import plan_prune

        last = len(cfg.channels) - 1
        prune_spec = plan_prune(
            params[f"conv{last}"]["w"], cfg.n_frames,
            keep=args.prune, trim_frames=1,
        )
        print(
            f"monitor: pruned artifact — flatten {prune_spec.flatten_before} "
            f"-> {prune_spec.flatten_after} (-{prune_spec.reduction:.0%})"
        )
    policy = None
    if args.policy is not None:
        from repro.core.precision_policy import PrecisionPolicy

        policy = PrecisionPolicy.parse(args.policy, default=args.precision)
        modes = {
            pat: prec.value for pat, prec in sorted(policy.rules.items())
        }
        print(f"monitor: mixed-precision artifact — {modes}, "
              f"default {policy.default.value}")

    admission = None
    if args.max_streams is not None:
        from repro.serving.batching import AdmissionPolicy

        admission = AdmissionPolicy(max_streams=args.max_streams)
        print(f"monitor: admission cap {args.max_streams} stream(s)")

    fleet = (
        args.workers is not None
        or args.faults is not None
        or args.lanes is not None
        or args.autoscale
        or args.state_dir is not None
    )
    if fleet:
        from repro.serving.engine import SanitizePolicy
        from repro.serving.faults import FaultClock, FaultPlan
        from repro.serving.quantized_params import quantize_params
        from repro.serving.supervisor import FleetSupervisor

        plan = None
        if args.faults is not None:
            with open(args.faults) as fh:
                plan = FaultPlan.from_json(fh.read())
            print(f"monitor: fault plan {args.faults} "
                  f"({len(plan.faults)} fault(s), seed {plan.seed})")
        # The supervisor serves an immutable baked artifact (that is what
        # makes rebuilding a dead worker exact), so bake the deploy-time
        # decisions here instead of inside the engine.
        qp = quantize_params(
            params, cfg, mode=args.precision, prune=prune_spec, policy=policy,
            feature_kind=args.feature,
        )
        n_workers = args.workers if args.workers is not None else 2
        sup_kw = dict(
            lanes=args.lanes,
            faults=plan,
            clock=FaultClock() if plan is not None else None,
            fsync=args.fsync,
            checkpoint_interval=args.checkpoint_interval,
            sanitize=SanitizePolicy(),
            feature_kind=args.feature,
            batch_slots=args.slots,
            shards=args.shards,
            adaptive_slots=args.adaptive_slots,
            admission=admission,
        )
        engine = None
        if args.state_dir is not None:
            engine = FleetSupervisor.restore_from_dir(
                qp, cfg, state_dir=args.state_dir, **sup_kw
            )
        if engine is not None:
            if engine.n_streams != args.streams:
                raise SystemExit(
                    f"monitor: --streams {args.streams} does not match the "
                    f"state dir ({engine.n_streams} stream(s)); rerun with "
                    f"the original arguments or a fresh --state-dir"
                )
            print(f"monitor: resumed from state dir at round {engine.round}, "
                  f"replayed {engine.replayed_chunks} chunk(s)")
        else:
            engine = FleetSupervisor(
                qp, cfg,
                n_streams=args.streams,
                n_workers=n_workers,
                state_dir=args.state_dir,
                **sup_kw,
            )
        lane_note = (
            "" if args.lanes is None else f", {args.lanes} execution lanes"
        )
        print(f"monitor: fleet supervisor, {engine.n_live_workers} worker(s) "
              f"over {args.streams} stream(s){lane_note}")
    else:
        engine = MonitorEngine(
            params, cfg,
            n_streams=args.streams,
            feature_kind=args.feature,
            batch_slots=args.slots,
            precision=args.precision,
            prune=prune_spec,
            policy=policy,
            shards=args.shards,
            adaptive_slots=args.adaptive_slots,
            admission=admission,
        )
    controller = None
    if args.autoscale:
        from repro.serving.controller import FleetController, SLOTarget

        controller = FleetController(
            engine,
            SLOTarget(
                max_defer_rate=0.25,
                max_drop_rate=0.05,
                min_workers=1,
                max_workers=max(2, args.streams // 2),
            ),
            window=8,
            cooldown_rounds=4,
        )
        print("monitor: SLO autoscaler on (defer<=25%, drop<=5%, "
              f"workers 1..{controller.slo.max_workers})")
    if args.adaptive_slots:
        ladder = engine.precompile()
        print(f"monitor: adaptive slots, pre-jitted ladder {list(ladder)}")
    if args.shards:
        print(f"monitor: sharded dispatch over {args.shards} device(s)")

    rng = np.random.default_rng(args.seed + 1)
    scenes, truths = zip(*(synth_scene(args.duration, rng) for _ in range(args.streams)))

    # Real-time-ish delivery: uneven chunks, one engine round per outer
    # tick.  The whole schedule is precomputed (one chunk-size draw per
    # stream per round, finished streams included — the exact rng draw
    # order of the live loop) so that a --state-dir resume can regenerate
    # the identical delivery plan and skip what the restored fleet already
    # embeds: per-stream chunks below the ``pushed_chunks`` delivery
    # cursor, and rounds below the restored round counter.
    schedule = []
    cursors = [0] * args.streams
    while any(c < len(s) for c, s in zip(cursors, scenes)):
        round_pushes = []
        for s in range(args.streams):
            chunk = int(rng.uniform(0.3, 1.7) * features.N_SAMPLES)
            if cursors[s] < len(scenes[s]):
                round_pushes.append((s, cursors[s], cursors[s] + chunk))
                cursors[s] += chunk
        schedule.append(round_pushes)
    done = np.asarray(
        getattr(engine, "pushed_chunks", np.zeros(args.streams, np.int64))
    ).copy()
    skip_rounds = int(getattr(engine, "round", 0))
    ordinals = [0] * args.streams

    t0 = time.perf_counter()
    def show(scored):
        for ws in scored:
            flag = "TRACK" if ws.active else ""
            print(
                f"  stream {ws.stream} t={ws.window_idx * features.WINDOW_S:5.1f}s "
                f"p={ws.p_uav:.2f} ema={ws.smoothed:.2f} {flag}"
            )

    for r, round_pushes in enumerate(schedule):
        for s, lo, hi in round_pushes:
            if ordinals[s] >= done[s]:
                engine.push(s, scenes[s][lo:hi])
            ordinals[s] += 1
        if r < skip_rounds:
            continue  # this round's windows were scored before the restart
        t_round = time.perf_counter()
        show(engine.step())
        if controller is not None:
            controller.step((time.perf_counter() - t_round) * 1e3)
    show(engine.drain())  # backlogged windows: delivery outpaces 1/round
    dt = time.perf_counter() - t0
    events = engine.finalize()

    print(
        f"\nmonitor: {args.streams} stream(s) x {args.duration:.1f}s "
        f"({engine.windows_scored} windows) in {dt:.2f}s "
        f"-> {engine.windows_scored / dt:.1f} windows/s, "
        f"{engine.forward_calls} forward calls, "
        f"{engine.padded_slots} padded slots, "
        f"{engine.dropped_samples} dropped samples"
    )
    if args.adaptive_slots:
        hist = ", ".join(
            f"{k}x{v}" for k, v in sorted(engine.slot_histogram.items())
        )
        print(f"monitor: slot histogram {hist or '(no blocks)'}")
    if args.max_streams is not None:
        refused = engine.refused_chunks
        n_refused = int(np.count_nonzero(refused))
        print(f"monitor: {n_refused} stream(s) refused at admission, "
              f"{int(refused.sum())} chunk(s) dropped")
    if fleet:
        for h in engine.health():
            age = ("never" if h["heartbeat_age_s"] is None
                   else f"{h['heartbeat_age_s']:.3f}s ago")
            state = "alive" if h["alive"] else "RETIRED"
            print(f"  worker {h['worker']}: {state}, streams {h['streams']}, "
                  f"{h['rebuilds']} rebuild(s), last heartbeat {age}")
        if engine.incidents:
            print(f"monitor: survived {len(engine.incidents)} incident(s):")
            for i in engine.incidents:
                print(f"    round {i['round']:3d} worker {i['worker']} "
                      f"[{i['kind']}] {i['detail']}")
        if controller is not None:
            print(f"monitor: autoscaler took {len(controller.actions)} "
                  f"action(s), fleet ended at "
                  f"{engine.n_live_workers} live worker(s)")
            for a in controller.actions:
                m = a["metrics"]
                print(f"    round {a['round']:3d} [{a['kind']}] "
                      f"defer={m['defer_rate']:.2f} drop={m['drop_rate']:.2f} "
                      f"live={m['n_live']}")
        engine.close()
    for s, (evs, (t_on, t_off)) in enumerate(zip(events, truths)):
        print(f"stream {s}: ground truth UAV at {t_on:.1f}-{t_off:.1f}s, {len(evs)} event(s)")
        for e in evs:
            print(
                f"    onset={e.onset_idx * features.WINDOW_S:.1f}s "
                f"offset={e.offset_idx * features.WINDOW_S:.1f}s "
                f"peak={e.peak_score:.2f} mean={e.mean_score:.2f}"
            )
    return events


if __name__ == "__main__":
    main()
