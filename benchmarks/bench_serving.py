"""Streaming monitor engine throughput/latency benchmark.

Drives :class:`repro.serving.engine.MonitorEngine` with synthetic raw-audio
streams at several concurrency levels and records aggregate windows/s,
per-window latency, per-round latency percentiles (p50/p95/p99 over the
step() scoring beat) and ingest drop/reject rates into
``BENCH_serving.json`` (same row machinery as the kernel bench).  The model is the small detector shape on zcr features —
interpret-mode kernel timings; the derived column notes the configuration so
rows stay comparable across PRs.

Sharded rows drive the same engine through ``shards``-way sharded-batch
dispatch (1/2/4/8 shards over simulated CPU devices — the device-count
override below must land before the first jax import, so keep this module's
import order).  Set ``SMOKE=1`` to restrict to the smallest stream count and
a single 2-shard row.
"""
from __future__ import annotations

import json
import os
import shutil
import tempfile
import time

# Simulated device pool for the sharded-dispatch rows (before jax import).
# NOTE: this changes the measurement environment of *all* rows, including
# the pre-existing unsharded ones — every row records ``host_devices`` so
# cross-PR comparisons know which environment produced it (the PR-3
# rebaseline moved the unsharded rows onto the 8-device pool).
from repro.hostdevices import force_host_device_count

N_HOST_DEVICES = 8
force_host_device_count(N_HOST_DEVICES)

import jax
import numpy as np

from benchmarks.common import (
    format_percentiles,
    percentile_fields,
    row,
    write_json,
)
from repro.core.precision_policy import Precision, PrecisionPolicy
from repro.core.pruning import plan_prune
from repro.data import features
from repro.models import cnn1d
from repro.serving.batching import AdmissionPolicy
from repro.serving.engine import MonitorEngine, SanitizePolicy

STREAM_COUNTS = (1, 8, 64)
SHARD_COUNTS = (1, 2, 4, 8)
SHARDED_STREAMS = 8
WINDOWS_PER_STREAM = 6
BATCH_SLOTS = 8
FEATURE = "zcr"

# Front-end comparison rows: the paper-canonical mfcc20 feature set, host
# numpy front-end vs the fused on-device front-end, at equal stream counts.
# All layers fp32 (pure XLA) for BOTH legs: in interpret mode the Pallas
# int8 kernels cost ~40x their compiled-TPU time, which would mask the
# front-end difference entirely — on real hardware the classifier is
# microseconds and the pipeline is front-end-bound, which is exactly the
# regime the fp32-policy CNN reproduces on CPU.
FRONTEND_FEATURE = "mfcc20"
FRONTEND_STREAMS = (1, 8, 64)

# Deployment-cell rows (pruned / mixed-precision artifacts): a dense-heavy
# detector shape where the flatten->dense interface dominates, so the
# paper's 75% flatten cut shows up as serving throughput, not just FLOPs.
DEPLOY_FEATURE = "psd"  # 512-dim input -> 128 frames x 32 ch = 4096 flatten
DEPLOY_CHANNELS = (4, 32)
DEPLOY_STREAMS = 8
DEPLOY_KEEP = 8  # 32 -> 8 channels (+1 frame trim): 4096 -> 1016 (-75%)
DEPLOY_POLICY = "conv0/w=bf16,dense1/w=fp32"

# Fleet-scale bursty-arrival rows: streams wake in seeded waves and dump a
# whole multi-window burst at once, so the per-round backlog is ragged —
# the regime the adaptive slot ladder exists for.  The ring is deliberately
# smaller than the burst (2 windows vs 4) so the drop-rate column is a real
# measurement of ingest back-pressure, not a constant zero, and a round
# budget caps how much of the backlog one scoring beat may drain so the
# round-latency percentiles reflect a bounded beat, not one giant flush.
BURSTY_STREAMS = (256, 1024)
BURSTY_WINDOWS = 4
BURSTY_CAPACITY = 2
BURSTY_WAVES = 8
BURSTY_ROUND_BUDGET = 8 * BATCH_SLOTS

# Concurrent-fleet rows: the same fleet supervisor stepped sequentially vs
# with per-worker execution lanes (threads).  Lanes overlap one worker's
# host feature extraction with another's device scoring through the
# dispatch core's queued blocks; results stay bitwise identical
# (pinned by tests/test_lane_fleet.py), so the lane row is a pure
# wall-clock measurement.  Target: >=1.3x aggregate windows/s at 4 workers
# on a multi-core host.  The ratio is physically bounded by the host's
# core count — on a single-core runner (the CI container) there is no
# second core for the overlapped beat to run on, so the honest expectation
# there is ~1.0x minus thread overhead; every row records host_cpus so the
# ratio is read against the hardware that produced it.  Interpret-mode CPU
# numbers carry a run-to-run noise band of roughly +/-10%: track the
# ratio column across PRs, not any single row's absolute windows/s.
FLEET_STREAMS = 16
FLEET_WORKERS = 4
FLEET_WINDOWS = 6

# Durability-overhead rows: the same fleet leg with a --state-dir, across
# the fsync-policy x checkpoint-interval grid.  The interesting column is
# ``durable_vs_plain`` (per-window cost relative to the in-memory fleet
# benched in the same process): WAL appends ride the push path and the
# checkpoint publish rides step(), so the ratio is the whole durability
# tax.  ``always`` pays one disk flush per chunk (the worst case);
# ``never`` is pure serialization cost.  SMOKE runs one small cell so the
# CI leg still exercises the durable path end to end.
DURABLE_GRID = (
    ("always", 1), ("always", 4),
    ("interval", 1), ("interval", 4),
    ("never", 1), ("never", 4),
)
DURABLE_SMOKE_STREAMS = 4
DURABLE_SMOKE_WORKERS = 2
DURABLE_SMOKE_WINDOWS = 2


def _smoke() -> bool:
    return bool(os.environ.get("SMOKE"))


def bench_monitor(
    n_streams: int,
    params,
    cfg,
    *,
    shards: int | None = None,
    feature: str = FEATURE,
    prune=None,
    policy=None,
    on_device_features: bool = False,
    adaptive_slots: bool = False,
) -> dict:
    rng = np.random.default_rng(n_streams)
    engine = MonitorEngine(
        params, cfg,
        n_streams=n_streams,
        feature_kind=feature,
        on_device_features=on_device_features,
        batch_slots=BATCH_SLOTS,
        adaptive_slots=adaptive_slots,
        shards=shards,
        prune=prune,
        policy=policy,
        # live ingest-hardening accounting (no-op on this clean audio, but
        # the reject-rate column measures the deployed configuration)
        sanitize=SanitizePolicy(),
    )
    audio = rng.standard_normal(
        (n_streams, WINDOWS_PER_STREAM * features.N_SAMPLES)
    ).astype(np.float32)

    # Warmup: compile the forward outside the timed region — the whole slot
    # ladder when adaptive (a lone window would only compile the 1-slot
    # shape and the timed region would pay every other trace).
    if adaptive_slots:
        engine.precompile()
    engine.push(0, audio[0, : features.N_SAMPLES])
    engine.drain()
    engine.forward_calls = 0
    engine.padded_slots = 0

    delivered = 0
    pushed_chunks = 0
    round_s: list[float] = []
    t0 = time.perf_counter()
    for s in range(n_streams):
        off = features.N_SAMPLES if s == 0 else 0  # stream 0's warmup window
        engine.push(s, audio[s, off:])
        delivered += audio.shape[1] - off
        pushed_chunks += 1
    # Per-round latency: each step() scores at most one window per stream,
    # so a round is the fleet's end-to-end scoring beat — the percentiles
    # below are what an operator's round-latency SLO would measure.
    n_win = 0
    while True:
        r0 = time.perf_counter()
        scored = engine.step()
        if not scored:
            break
        round_s.append(time.perf_counter() - r0)
        n_win += len(scored)
    dt = time.perf_counter() - t0
    engine.finalize()
    return {
        "windows": n_win,
        "windows_per_s": n_win / dt,
        "us_per_window": dt / n_win * 1e6,
        "forward_calls": engine.forward_calls,
        "padded_slots": engine.padded_slots,
        "rounds": len(round_s),
        **percentile_fields(round_s),
        "drop_rate": round(engine.dropped_samples / delivered, 6),
        "reject_rate": round(
            float(engine.rejected_chunks.sum()) / pushed_chunks, 6
        ),
    }


def bench_fleet(
    params, cfg, *, lanes: str | None,
    n_streams: int = FLEET_STREAMS,
    n_workers: int = FLEET_WORKERS,
    n_windows: int = FLEET_WINDOWS,
    state_dir: str | None = None,
    fsync: str = "interval",
    checkpoint_interval: int = 1,
) -> dict:
    """One fleet leg (sequential or lane-parallel) over the same delivery
    schedule: every stream gets a full multi-window scene up front, then
    rounds drain it one window per stream per beat.  With ``state_dir``
    the leg runs durable (checkpoints + WAL per the fsync policy), which
    is what the durability-overhead rows measure."""
    from repro.serving.quantized_params import quantize_params
    from repro.serving.supervisor import FleetSupervisor

    rng = np.random.default_rng(n_streams)
    durable_kw = (
        dict(state_dir=state_dir, fsync=fsync,
             checkpoint_interval=checkpoint_interval)
        if state_dir is not None else {}
    )
    sup = FleetSupervisor(
        quantize_params(params, cfg, mode="int8"), cfg,
        n_streams=n_streams,
        n_workers=n_workers,
        lanes=lanes,
        feature_kind=FEATURE,
        batch_slots=BATCH_SLOTS,
        sanitize=SanitizePolicy(),
        **durable_kw,
    )
    audio = rng.standard_normal(
        (n_streams, n_windows * features.N_SAMPLES)
    ).astype(np.float32)

    # Warmup: one window through every stream so each worker's jit cache is
    # hot (shapes are shared process-wide, but the first leg pays the trace).
    for s in range(n_streams):
        sup.push(s, audio[s, : features.N_SAMPLES])
    sup.drain()

    round_s: list[float] = []
    n_win = 0
    t0 = time.perf_counter()
    for s in range(n_streams):
        sup.push(s, audio[s, features.N_SAMPLES:])
    while True:
        r0 = time.perf_counter()
        scored = sup.step()
        if not scored:
            break
        round_s.append(time.perf_counter() - r0)
        n_win += len(scored)
    dt = time.perf_counter() - t0
    sup.finalize()
    sup.close()
    return {
        "windows": n_win,
        "windows_per_s": n_win / dt,
        "us_per_window": dt / n_win * 1e6,
        "rounds": len(round_s),
        **percentile_fields(round_s),
    }


def bench_bursty(n_streams: int, params, cfg) -> dict:
    """Fleet-scale bursty arrival: streams wake in seeded waves, each dumps
    a 4-window burst into a 2-window ring, and a budgeted round drains the
    backlog depth-fairly on the adaptive slot ladder."""
    rng = np.random.default_rng(n_streams)
    engine = MonitorEngine(
        params, cfg,
        n_streams=n_streams,
        feature_kind=FEATURE,
        batch_slots=BATCH_SLOTS,
        adaptive_slots=True,
        capacity_windows=BURSTY_CAPACITY,
        admission=AdmissionPolicy(
            max_per_stream_per_round=BURSTY_CAPACITY,
            round_budget=BURSTY_ROUND_BUDGET,
        ),
        sanitize=SanitizePolicy(),
    )
    engine.precompile()  # whole slot ladder, outside the timed region
    chunk = BURSTY_WINDOWS * features.N_SAMPLES
    audio = rng.standard_normal((n_streams, chunk)).astype(np.float32)
    wave = rng.integers(0, BURSTY_WAVES, n_streams)

    delivered = 0
    round_s: list[float] = []
    n_win = 0
    t0 = time.perf_counter()
    for w in range(BURSTY_WAVES):
        for s in np.flatnonzero(wave == w):
            engine.push(s, audio[s])
            delivered += chunk
        r0 = time.perf_counter()
        scored = engine.step()
        if scored:  # an arrival-free wave is not a scoring round
            round_s.append(time.perf_counter() - r0)
            n_win += len(scored)
    while True:  # drain the tail of the backlog after the last wave
        r0 = time.perf_counter()
        scored = engine.step()
        if not scored:
            break
        round_s.append(time.perf_counter() - r0)
        n_win += len(scored)
    dt = time.perf_counter() - t0
    engine.finalize()
    return {
        "windows": n_win,
        "windows_per_s": n_win / dt,
        "us_per_window": dt / n_win * 1e6,
        "forward_calls": engine.forward_calls,
        "padded_slots": engine.padded_slots,
        "slot_histogram": dict(engine.slot_histogram),
        "served": int(engine.served_windows.sum()),
        "deferred": int(engine.deferred_windows.sum()),
        "rounds": len(round_s),
        **percentile_fields(round_s),
        "drop_rate": round(engine.dropped_samples / delivered, 6),
    }


def bench_frontend_rows():
    """Host numpy features vs the fused on-device front-end on the paper-
    canonical mfcc20 set at equal stream counts (acceptance: on-device >= 3x
    host at 8 streams, both rows from this same run).

    All layers fp32 (pure XLA) for BOTH legs: in interpret mode the Pallas
    int8 kernels cost ~40x their compiled-TPU time, which would mask the
    front-end difference entirely — on real hardware the classifier is
    microseconds and the pipeline is front-end-bound, which is exactly the
    regime the fp32-policy CNN reproduces on CPU.

    Runs in this process, so one process holds the chip.  On the CPU the
    8-device pool splits XLA's thread pool, which starves the in-graph FFTs
    and understates the on-device leg; each row records ``host_devices``.
    """
    counts = FRONTEND_STREAMS[:1] if _smoke() else FRONTEND_STREAMS
    cfg = cnn1d.CNNConfig(
        input_len=features.FEATURE_DIMS[FRONTEND_FEATURE], channels=(4, 8), hidden=8
    )
    params = cnn1d.init_params(jax.random.PRNGKey(2), cfg)
    policy = PrecisionPolicy(rules={}, default=Precision.FP32)
    results = [
        dict(
            bench_monitor(n, params, cfg, feature=FRONTEND_FEATURE,
                          policy=policy, on_device_features=on_device),
            n_streams=n, on_device=on_device,
        )
        for on_device in (False, True)
        for n in counts
    ]
    host_rate = {
        r["n_streams"]: r["windows_per_s"] for r in results if not r["on_device"]
    }
    for r in results:
        leg = "devfe" if r["on_device"] else "hostfe"
        vs = (
            f"; {r['windows_per_s'] / host_rate[r['n_streams']]:.2f}x vs "
            f"host front-end"
            if r["on_device"]
            else ""
        )
        row(
            f"serving/monitor_{FRONTEND_FEATURE}_{leg}_{r['n_streams']}streams_x{WINDOWS_PER_STREAM}win",
            f"{r['us_per_window']:.0f}",
            f"{'fused on-device' if r['on_device'] else 'host numpy'} "
            f"{FRONTEND_FEATURE} front-end{vs}; fp32-policy CNN (XLA; "
            f"front-end-bound regime — interpret-mode int8 kernels would "
            f"mask the front-end); {r['windows_per_s']:.1f} windows/s "
            f"aggregate; {r['forward_calls']} forward calls "
            f"({BATCH_SLOTS} slots, {r['padded_slots']} padded)",
            windows_per_s=round(r["windows_per_s"], 2),
            n_streams=r["n_streams"],
            batch_slots=BATCH_SLOTS,
            feature=FRONTEND_FEATURE,
            on_device_features=r["on_device"],
            host_devices=jax.device_count(),
        )


def main():
    cfg = cnn1d.CNNConfig(
        input_len=features.FEATURE_DIMS[FEATURE], channels=(4, 8), hidden=8
    )
    params = cnn1d.init_params(jax.random.PRNGKey(0), cfg)
    counts = STREAM_COUNTS[:1] if _smoke() else STREAM_COUNTS
    for n in counts:
        r = bench_monitor(n, params, cfg)
        a = bench_monitor(n, params, cfg, adaptive_slots=True)
        row(
            f"serving/monitor_adaptive_{n}streams_x{WINDOWS_PER_STREAM}win",
            f"{a['us_per_window']:.0f}",
            f"interpret-mode; adaptive slot ladder (max {BATCH_SLOTS}); "
            f"{a['windows_per_s']:.1f} windows/s aggregate "
            f"({a['windows_per_s'] / r['windows_per_s']:.2f}x vs fixed-slot "
            f"this run); {format_percentiles(a)} over "
            f"{a['rounds']} rounds; {a['forward_calls']} forward calls, "
            f"{a['padded_slots']} padded slots (fixed-slot pads "
            f"{r['padded_slots']}); zcr features, small detector",
            windows_per_s=round(a["windows_per_s"], 2),
            n_streams=n,
            batch_slots=BATCH_SLOTS,
            adaptive_slots=True,
            padded_slots=a["padded_slots"],
            round_p50_ms=a["round_p50_ms"],
            round_p95_ms=a["round_p95_ms"],
            round_p99_ms=a["round_p99_ms"],
            drop_rate=a["drop_rate"],
            reject_rate=a["reject_rate"],
            host_devices=jax.device_count(),
        )
        row(
            f"serving/monitor_{n}streams_x{WINDOWS_PER_STREAM}win",
            f"{r['us_per_window']:.0f}",
            f"interpret-mode; {r['windows_per_s']:.1f} windows/s aggregate; "
            f"{format_percentiles(r)} over "
            f"{r['rounds']} rounds; drop {r['drop_rate']:.1%}, reject "
            f"{r['reject_rate']:.1%}; {r['forward_calls']} forward calls "
            f"({BATCH_SLOTS} slots, {r['padded_slots']} padded); zcr "
            f"features, small detector",
            windows_per_s=round(r["windows_per_s"], 2),
            n_streams=n,
            batch_slots=BATCH_SLOTS,
            round_p50_ms=r["round_p50_ms"],
            round_p95_ms=r["round_p95_ms"],
            round_p99_ms=r["round_p99_ms"],
            drop_rate=r["drop_rate"],
            reject_rate=r["reject_rate"],
            host_devices=jax.device_count(),
        )
    shard_counts = (2,) if _smoke() else SHARD_COUNTS
    # An outer XLA_FLAGS override wins over ours (force_host_device_count
    # never fights it) — only bench the shard counts that actually fit, and
    # say so instead of dying after the unsharded rows already ran.
    fitting = tuple(k for k in shard_counts if k <= jax.device_count())
    if fitting != shard_counts:
        print(
            f"bench_serving: only {jax.device_count()} device(s) available; "
            f"skipping shard counts {sorted(set(shard_counts) - set(fitting))}"
        )
    for k in fitting:
        r = bench_monitor(SHARDED_STREAMS, params, cfg, shards=k)
        row(
            f"serving/monitor_{SHARDED_STREAMS}streams_x{WINDOWS_PER_STREAM}win_shard{k}",
            f"{r['us_per_window']:.0f}",
            f"interpret-mode; sharded dispatch over {k} simulated CPU "
            f"device(s); {r['windows_per_s']:.1f} windows/s aggregate; "
            f"{r['forward_calls']} forward calls ({BATCH_SLOTS} slots, "
            f"{r['padded_slots']} padded); zcr features, small detector",
            windows_per_s=round(r["windows_per_s"], 2),
            n_streams=SHARDED_STREAMS,
            batch_slots=BATCH_SLOTS,
            shards=k,
            round_p50_ms=r["round_p50_ms"],
            round_p95_ms=r["round_p95_ms"],
            round_p99_ms=r["round_p99_ms"],
            drop_rate=r["drop_rate"],
            reject_rate=r["reject_rate"],
            host_devices=jax.device_count(),
        )
    # Concurrent-fleet rows (skipped under SMOKE): sequential supervisor vs
    # per-worker execution lanes, same artifact, same delivery schedule.
    if not _smoke():
        n_cpus = os.cpu_count() or 1
        seq = bench_fleet(params, cfg, lanes=None)
        lan = bench_fleet(params, cfg, lanes="threads")
        ratio = lan["windows_per_s"] / seq["windows_per_s"]
        for leg, r in (("seq", seq), ("lanes", lan)):
            vs = (
                f"; {ratio:.2f}x vs sequential fleet this run on a "
                f"{n_cpus}-cpu host (>=1.3x expected at 4 workers only with "
                f">=2 cores to overlap on; interpret-mode noise band "
                f"~+/-10%: track the ratio, not the absolute)"
                if leg == "lanes"
                else ""
            )
            row(
                f"serving/fleet_{leg}_{FLEET_WORKERS}workers_"
                f"{FLEET_STREAMS}streams_x{FLEET_WINDOWS}win",
                f"{r['us_per_window']:.0f}",
                f"interpret-mode; fleet supervisor, {FLEET_WORKERS} "
                f"worker(s), "
                f"{'thread execution lanes' if leg == 'lanes' else 'sequential step'}"
                f"; {r['windows_per_s']:.1f} windows/s aggregate{vs}; "
                f"{format_percentiles(r)} over {r['rounds']} rounds; "
                f"bitwise identical to the sequential fleet and the "
                f"monolithic engine (tests/test_lane_fleet.py); zcr "
                f"features, small detector",
                windows_per_s=round(r["windows_per_s"], 2),
                n_streams=FLEET_STREAMS,
                n_workers=FLEET_WORKERS,
                lanes=leg == "lanes",
                batch_slots=BATCH_SLOTS,
                round_p50_ms=r["round_p50_ms"],
                round_p95_ms=r["round_p95_ms"],
                round_p99_ms=r["round_p99_ms"],
                host_devices=jax.device_count(),
                host_cpus=n_cpus,
                **({"lanes_vs_seq": round(ratio, 3)} if leg == "lanes" else {}),
            )

    # Durability-overhead rows: the fleet leg re-run with state-dir
    # checkpoints + chunk WAL across the fsync x checkpoint-interval grid,
    # against an in-memory baseline benched in the same process (so the
    # ratio cancels the interpret-mode noise floor).  SMOKE runs one small
    # cell so the CI leg still exercises the durable path end to end.
    if _smoke():
        durable_grid = (("interval", 1),)  # the supervisor defaults
        durable_size = dict(
            n_streams=DURABLE_SMOKE_STREAMS,
            n_workers=DURABLE_SMOKE_WORKERS,
            n_windows=DURABLE_SMOKE_WINDOWS,
        )
    else:
        durable_grid = DURABLE_GRID
        durable_size = {}
    base = bench_fleet(params, cfg, lanes=None, **durable_size)
    for fsync, ck in durable_grid:
        state_dir = tempfile.mkdtemp(prefix="bench-durable-")
        try:
            r = bench_fleet(
                params, cfg, lanes=None, state_dir=state_dir,
                fsync=fsync, checkpoint_interval=ck, **durable_size,
            )
        finally:
            shutil.rmtree(state_dir, ignore_errors=True)
        ratio = r["us_per_window"] / base["us_per_window"]
        row(
            f"serving/fleet_durable_{fsync}_ck{ck}",
            f"{r['us_per_window']:.0f}",
            f"interpret-mode; durable fleet (state-dir checkpoints + chunk "
            f"WAL), fsync={fsync}, checkpoint every {ck} round(s); "
            f"{r['windows_per_s']:.1f} windows/s aggregate; {ratio:.2f}x "
            f"the in-memory fleet benched this run; {format_percentiles(r)} "
            f"over {r['rounds']} rounds; cold restart from these artifacts "
            f"is bitwise-conformant (tests/test_durability.py); zcr "
            f"features, small detector",
            windows_per_s=round(r["windows_per_s"], 2),
            n_streams=durable_size.get("n_streams", FLEET_STREAMS),
            n_workers=durable_size.get("n_workers", FLEET_WORKERS),
            fsync=fsync,
            checkpoint_interval=ck,
            durable_vs_plain=round(ratio, 3),
            round_p50_ms=r["round_p50_ms"],
            round_p95_ms=r["round_p95_ms"],
            round_p99_ms=r["round_p99_ms"],
            host_devices=jax.device_count(),
        )

    # Fleet-scale bursty-arrival rows (skipped under SMOKE: ~2k windows of
    # interpret-mode forward each).  Acceptance cares about the latency
    # percentiles of a budgeted scoring beat and a *live* drop-rate column
    # under genuine back-pressure.
    if not _smoke():
        for n in BURSTY_STREAMS:
            r = bench_bursty(n, params, cfg)
            hist = ", ".join(
                f"{c}x{s}" for s, c in sorted(r["slot_histogram"].items())
            )
            row(
                f"serving/monitor_bursty_{n}streams_x{BURSTY_WINDOWS}win",
                f"{r['us_per_window']:.0f}",
                f"interpret-mode; bursty arrival over {BURSTY_WAVES} waves "
                f"({BURSTY_WINDOWS}-window bursts into {BURSTY_CAPACITY}-"
                f"window rings, round budget {BURSTY_ROUND_BUDGET}); "
                f"{r['windows_per_s']:.1f} windows/s aggregate; "
                f"{format_percentiles(r)} over "
                f"{r['rounds']} rounds; drop {r['drop_rate']:.1%} (ring "
                f"overflow), {r['served']} served / {r['deferred']} "
                f"deferred window-rounds; {r['forward_calls']} forward "
                f"calls, {r['padded_slots']} padded slots, ladder use "
                f"{hist}; zcr features, small detector",
                windows_per_s=round(r["windows_per_s"], 2),
                n_streams=n,
                batch_slots=BATCH_SLOTS,
                adaptive_slots=True,
                round_budget=BURSTY_ROUND_BUDGET,
                capacity_windows=BURSTY_CAPACITY,
                round_p50_ms=r["round_p50_ms"],
                round_p95_ms=r["round_p95_ms"],
                round_p99_ms=r["round_p99_ms"],
                drop_rate=r["drop_rate"],
                host_devices=jax.device_count(),
            )

    bench_frontend_rows()

    # Deployment-cell rows: the artifact the paper actually ships — pruned
    # flatten (SIII-C) and per-layer mixed precision (SIII-B) — benched at
    # equal stream counts against the unpruned all-int8 baseline on the
    # dense-heavy shape.  Acceptance: pruned strictly above unpruned.
    deploy_cfg = cnn1d.CNNConfig(
        input_len=features.FEATURE_DIMS[DEPLOY_FEATURE],
        channels=DEPLOY_CHANNELS, hidden=8,
    )
    deploy_params = cnn1d.init_params(jax.random.PRNGKey(1), deploy_cfg)
    last = len(DEPLOY_CHANNELS) - 1
    spec = plan_prune(
        deploy_params[f"conv{last}"]["w"], deploy_cfg.n_frames,
        keep=DEPLOY_KEEP, trim_frames=1,
    )
    policy = PrecisionPolicy.parse(DEPLOY_POLICY, default="int8")
    deploy_streams = 2 if _smoke() else DEPLOY_STREAMS
    cells = [("unpruned", None, None), ("pruned", spec, None)]
    if not _smoke():
        cells += [("mixed", None, policy), ("pruned_mixed", spec, policy)]
    for name, prune, pol in cells:
        r = bench_monitor(
            deploy_streams, deploy_params, deploy_cfg,
            feature=DEPLOY_FEATURE, prune=prune, policy=pol,
        )
        flat = spec.flatten_after if prune is not None else spec.flatten_before
        row(
            f"serving/monitor_deploy_{name}_{deploy_streams}streams_x{WINDOWS_PER_STREAM}win",
            f"{r['us_per_window']:.0f}",
            f"interpret-mode; deployment cell '{name}' (flatten {flat}"
            f"{', policy ' + DEPLOY_POLICY if pol is not None else ''}); "
            f"{r['windows_per_s']:.1f} windows/s aggregate; "
            f"{r['forward_calls']} forward calls ({BATCH_SLOTS} slots, "
            f"{r['padded_slots']} padded); {DEPLOY_FEATURE} features, "
            f"channels {DEPLOY_CHANNELS}",
            windows_per_s=round(r["windows_per_s"], 2),
            n_streams=deploy_streams,
            batch_slots=BATCH_SLOTS,
            flatten=int(flat),
            pruned=prune is not None,
            mixed=pol is not None,
            host_devices=jax.device_count(),
        )

    if not _smoke():
        write_json("BENCH_serving.json", prefix="serving/")


if __name__ == "__main__":
    main()
