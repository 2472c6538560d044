#!/usr/bin/env bash
# CI entrypoint: dev deps (best effort — the container may be offline), the
# fast test tier, then a ~30s benchmark + sharded-driver smoke at the
# smallest shapes.
#
#   scripts/ci.sh         fast tier (-m "not slow"): < ~2 min
#   scripts/ci.sh --all   full tier-1 suite incl. @slow kernel-parity /
#                         multi-device / LM-architecture tests (~5-6 min)
#   scripts/ci.sh --cov   fast tier with statement coverage over the
#                         serving package (repro.serving) plus the deploy-
#                         time transform modules (repro.core.pruning,
#                         repro.core.precision_policy), fails under 85%
set -euo pipefail
cd "$(dirname "$0")/.."

# Dev extras are optional: the suite falls back to tests/_hypothesis_fallback.py.
pip install -q -r requirements-dev.txt 2>/dev/null \
  || echo "ci: pip install skipped (offline container); using test fallbacks"

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# All throwaway artifacts (bench JSON, fault plan, crash-restart state dir)
# are created up front and reaped by one EXIT trap, so no failure path —
# a red perf gate, a hung smoke, a mid-script ^C — leaks a /tmp file.
BENCH_FRESH="$(mktemp /tmp/ci_bench_fresh.XXXXXX.json)"
FAULT_PLAN="$(mktemp /tmp/ci_fault_plan.XXXXXX.json)"
STATE_DIR="$(mktemp -d /tmp/ci_state_dir.XXXXXX)"
trap 'rm -rf "$BENCH_FRESH" "$FAULT_PLAN" "$STATE_DIR"' EXIT

MARK=(-m "not slow")
COV=()
case "${1:-}" in
  --all)
    MARK=()  # full tier-1 verify (ROADMAP.md)
    ;;
  --cov)
    if python -c "import pytest_cov" 2>/dev/null; then
      COV=(--cov=repro.serving --cov=repro.serving.batching
           --cov=repro.serving.controller
           --cov=repro.core.pruning
           --cov=repro.core.precision_policy --cov=repro.data.features_jax
           --cov=repro.kernels.tiling
           --cov-report=term-missing --cov-fail-under=85)
    else
      echo "ci: pytest-cov unavailable (offline container); running without coverage" >&2
    fi
    ;;
esac
# ${ARR[@]+...}: empty-array expansion is fatal under `set -u` on bash < 4.4
python -m pytest -x -q ${MARK[@]+"${MARK[@]}"} ${COV[@]+"${COV[@]}"}

# Chaos suite under a hard wall-clock cap: a hung supervisor recovery (a
# revive loop that never converges, a stall that deadlocks a worker) is
# exactly the regression this suite exists to catch, and a hang must fail
# CI loudly, not eat the job timeout.  faulthandler dumps all thread stacks
# when `timeout` sends SIGINT so the hang site lands in the CI log.
timeout --signal=INT 300 python -X faulthandler -m pytest -x -q \
  tests/test_fault_tolerance.py tests/test_lane_fleet.py

# Benchmark smoke + perf gate: smallest shapes under the pinned bench env,
# written to a throwaway JSON, then the speedup *ratios* (fused-vs-im2col,
# jax-vs-numpy — ratios, because absolute µs swing ~±30% in the container)
# are gated against the committed BENCH_kernels.json.  --require makes the
# gate bite on a bench that silently drops a row.
SMOKE=1 BENCH_OUT="$BENCH_FRESH" python -m benchmarks.bench_kernels
python scripts/perf_gate.py --fresh "$BENCH_FRESH" \
  --require 'kernels/conv_layer_fused_*' \
  --require 'kernels/frontend_jax_*'

# Sharded-driver smoke: the --shards path boots 2 simulated devices and
# must produce windows end-to-end (random weights: plumbing only, fast).
python -m repro.launch.monitor --seconds 2 --shards 2 --random

# Pruned-serving smoke: the deployed configuration (structured prune +
# mixed per-layer precision baked into the artifact) end-to-end through the
# monitor driver (random weights: plumbing only, fast).
python -m repro.launch.monitor --seconds 2 --prune 2 \
  --policy "conv0/w=bf16,dense1/w=fp32" --random

# High-stream adaptive smoke: 256 streams through the shared dispatch core
# on the adaptive slot ladder — proves the fleet-scale admission/fairness
# path boots and drains end-to-end, capped so a ladder-retrace or ready-
# scan regression fails loudly instead of eating the job timeout.
timeout --signal=INT 300 python -m repro.launch.monitor --seconds 2 \
  --streams 256 --adaptive-slots --random

# Fault-injection demo smoke: a seeded plan (crashes, stalls, kills, chunk
# faults) through the fleet supervisor; the driver must survive every
# incident and print the incident log (random weights: plumbing only).
python -m repro.serving.faults --seed 7 --streams 3 --workers 2 \
  --rounds 12 --out "$FAULT_PLAN"
timeout --signal=INT 300 python -m repro.launch.monitor --seconds 2 \
  --workers 2 --faults "$FAULT_PLAN" --random

# Concurrent-fleet smoke: all four workers' rounds run on named execution
# lanes with the SLO autoscaler closed over them.  A lane deadlock (a lane
# waiting on a join that never comes, an ingest-queue lock held across a
# round) hangs exactly here — the hard cap plus faulthandler turns that
# into a loud failure with every lane's stack in the log.
timeout --signal=INT 300 python -X faulthandler -m repro.launch.monitor \
  --seconds 2 --workers 4 --lanes threads --autoscale --random

# Chaos-on-lanes smoke: replay the same seeded fault plan through the
# lane-parallel supervisor — crash/stall/kill recovery and stream
# reassignment must hold when every worker steps on its own thread.
timeout --signal=INT 300 python -X faulthandler -m repro.launch.monitor \
  --seconds 2 --workers 2 --lanes threads --faults "$FAULT_PLAN" --random

# Crash-restart smoke: SIGKILL a durable (--state-dir) fleet mid-run, then
# restart from the same state dir with identical arguments — the driver
# must print the resume line and replay at least one WAL chunk.  The kill
# can (rarely) land in the instant after a checkpoint reset when the WAL
# is empty; that leg is retried, the resume line itself is not.
crash_restart_smoke() {
  local attempt pid log
  for attempt in 1 2 3; do
    rm -rf "$STATE_DIR"
    # Background the BARE python command: $! must be the python pid itself.
    # A compound command here would background a subshell, and the SIGKILL
    # would hit the subshell while the real process kept running.
    python -m repro.launch.monitor --seconds 6 --workers 2 \
      --state-dir "$STATE_DIR" --random >/dev/null 2>&1 &
    pid=$!
    for _ in $(seq 1 100); do  # wait for the first published checkpoint
      compgen -G "$STATE_DIR/fleet/ckpt-*.bin" >/dev/null && break
      sleep 0.1
    done
    sleep 0.3  # let a few more rounds commit, then kill mid-scene
    kill -9 "$pid" 2>/dev/null || true
    wait "$pid" 2>/dev/null || true
    log="$(timeout --signal=INT 300 python -X faulthandler \
      -m repro.launch.monitor --seconds 6 --workers 2 \
      --state-dir "$STATE_DIR" --random)"
    echo "$log" | grep -E "monitor: resumed from state dir at round [1-9]" \
      || { echo "ci: crash-restart smoke: no resume line" >&2; return 1; }
    if echo "$log" | grep -qE "replayed [1-9][0-9]* chunk"; then
      echo "ci: crash-restart smoke OK (attempt $attempt)"
      return 0
    fi
    echo "ci: crash-restart smoke: WAL empty at the kill instant" \
      "(attempt $attempt); retrying"
  done
  echo "ci: crash-restart smoke: no WAL replay in 3 attempts" >&2
  return 1
}
crash_restart_smoke
