"""Metamorphic conformance suite: streaming == batched == sharded, bitwise.

The serving layer's scaling story rests on one invariant: a window's
probability depends only on its own row (per-sample activation scales), so
*how* the batch is executed — streamed window-at-a-time, micro-batched,
permuted across slots, or split over a device mesh — can never change the
numbers.  This file pins that invariant:

* slot-permutation metamorphism: permuting the batch rows and unpermuting
  the outputs is the identity, for random loudness mixes;
* the sharded leg runs in a subprocess with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (simulated devices
  must be configured before jax import, and must never leak into this test
  process), asserting ``streaming == batched == sharded`` bitwise for random
  stream counts/loudness mixes, plus the permutation identity *across shard
  boundaries*.

Fast tier: the subprocess uses the small zcr detector in interpret mode.
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.data import features
from repro.models import cnn1d
from repro.serving.accelerator import accelerator_forward


def _small_detector():
    cfg = cnn1d.CNNConfig(
        input_len=features.FEATURE_DIMS["zcr"], channels=(4, 8), hidden=8
    )
    params = cnn1d.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_permute_unpermute_is_identity():
    """Rows are independent of their co-batch: shuffling slot assignment and
    unshuffling the outputs reproduces the unpermuted run bitwise, even with
    a 10^4 loudness spread across the batch."""
    cfg, params = _small_detector()
    rng = np.random.default_rng(7)
    bsz = 6  # fixed so all trials share one jit trace
    for trial in range(3):
        x = rng.standard_normal((bsz, cfg.input_len)).astype(np.float32)
        x *= (10.0 ** rng.uniform(-2, 2, size=(bsz, 1))).astype(np.float32)
        ref = np.asarray(accelerator_forward(params, jnp.asarray(x), cfg))
        perm = rng.permutation(bsz)
        inv = np.argsort(perm)
        got = np.asarray(accelerator_forward(params, jnp.asarray(x[perm]), cfg))[inv]
        np.testing.assert_array_equal(ref, got)


def test_sharded_forward_single_device_in_process():
    """A 1-way "streams" mesh needs no simulated devices, so the whole
    sharded datapath (mesh helper, replicated placement, shard_map forward)
    runs in-process in the fast tier — and must still be bitwise identical
    to the unsharded forward."""
    from repro.distributed.sharding import stream_mesh
    from repro.serving.accelerator import accelerator_forward_sharded
    from repro.serving.engine import MonitorEngine

    cfg, params = _small_detector()
    rng = np.random.default_rng(3)
    x = rng.standard_normal((4, cfg.input_len)).astype(np.float32)
    mesh = stream_mesh(1)
    ref = np.asarray(accelerator_forward(params, jnp.asarray(x), cfg))
    got = np.asarray(accelerator_forward_sharded(params, jnp.asarray(x), cfg, mesh=mesh))
    np.testing.assert_array_equal(ref, got)

    # the engine's shards=1 route goes through the sharded dispatch too
    engine = MonitorEngine(
        params, cfg, n_streams=2, feature_kind="zcr", batch_slots=4, shards=1
    )
    assert engine.shards == 1
    audio = rng.standard_normal((2, 2 * features.N_SAMPLES)).astype(np.float32)
    for s in range(2):
        engine.push(s, audio[s])
    scored = engine.drain()
    assert len(scored) == 4
    for ws in scored:
        s, i = ws.stream, ws.window_idx
        wins = jnp.asarray(audio[s].reshape(2, features.N_SAMPLES))
        p = np.asarray(
            accelerator_forward(engine._qp, wins, cfg, raw_windows=True)
        )[i, 1]
        assert ws.p_uav == np.float64(p)


def test_stream_mesh_rejects_bad_shard_counts():
    import pytest

    from repro.distributed.sharding import stream_mesh

    with pytest.raises(ValueError, match="local devices"):
        stream_mesh(0)
    with pytest.raises(ValueError, match="local devices"):
        stream_mesh(len(jax.devices()) + 1)


SHARDED_SCRIPT = textwrap.dedent(
    """\
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import sys, json
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.data import features
    from repro.distributed.sharding import stream_mesh
    from repro.models import cnn1d
    from repro.serving.accelerator import accelerator_forward, accelerator_forward_sharded
    from repro.serving.engine import MonitorEngine
    from repro.serving.quantized_params import quantize_params

    cfg = cnn1d.CNNConfig(input_len=features.FEATURE_DIMS["zcr"], channels=(4, 8), hidden=8)
    params = cnn1d.init_params(jax.random.PRNGKey(0), cfg)
    qp = quantize_params(params, cfg, mode="int8", feature_kind="zcr")
    rng = np.random.default_rng(11)
    checks = 0

    n_win = 3  # fixed so the per-stream reference forwards share one trace
    for trial in range(1):
        n_streams = int(rng.integers(3, 6))
        audio = rng.standard_normal((n_streams, n_win * features.N_SAMPLES)).astype(np.float32)
        # loudness mix: each stream at its own gain over 4 orders of magnitude
        audio *= (10.0 ** rng.uniform(-2, 2, size=(n_streams, 1))).astype(np.float32)

        # (a) one batched unsharded raw-window forward per stream = the reference
        ref = []
        for s in range(n_streams):
            wins = jnp.asarray(audio[s].reshape(n_win, features.N_SAMPLES))
            ref.append(np.asarray(accelerator_forward(qp, wins, cfg, raw_windows=True))[:, 1])

        # (b) streaming through the engine, unsharded vs sharded x{2,4}
        for shards in (None, 2, 4):
            engine = MonitorEngine(
                qp, cfg, n_streams=n_streams, feature_kind="zcr",
                batch_slots=4, shards=shards,
            )
            cursors = [0] * n_streams
            scores = {s: [] for s in range(n_streams)}
            while any(c < audio.shape[1] for c in cursors):
                for s in range(n_streams):
                    n = int(rng.uniform(0.3, 1.8) * features.N_SAMPLES)
                    engine.push(s, audio[s, cursors[s] : cursors[s] + n])
                    cursors[s] += n
                for ws in engine.step():
                    scores[ws.stream].append(ws.p_uav)
            for ws in engine.drain():
                scores[ws.stream].append(ws.p_uav)
            assert engine.dropped_samples == 0
            for s in range(n_streams):
                got = np.asarray(scores[s], np.float64)
                assert got.shape == (n_win,)
                np.testing.assert_array_equal(got, ref[s].astype(np.float64))
                checks += 1

    # (c) permutation identity ACROSS shard boundaries: rows change device
    # under the permutation, outputs must still unpermute to the reference.
    mesh = stream_mesh(4)
    x = rng.standard_normal((8, cfg.input_len)).astype(np.float32)
    x *= (10.0 ** rng.uniform(-2, 2, size=(8, 1))).astype(np.float32)
    base = np.asarray(accelerator_forward(params, jnp.asarray(x), cfg))
    sharded = np.asarray(accelerator_forward_sharded(params, jnp.asarray(x), cfg, mesh=mesh))
    np.testing.assert_array_equal(base, sharded)
    perm = rng.permutation(8)  # moves rows between the 4 shards
    inv = np.argsort(perm)
    permuted = np.asarray(
        accelerator_forward_sharded(params, jnp.asarray(x[perm]), cfg, mesh=mesh)
    )[inv]
    np.testing.assert_array_equal(base, permuted)
    checks += 2

    # a batch that does not divide over the shards is rejected loudly
    try:
        accelerator_forward_sharded(params, jnp.asarray(x[:3]), cfg, mesh=mesh)
    except ValueError as e:
        assert "not divisible" in str(e)
        checks += 1
    else:
        raise AssertionError("expected ValueError for 3 rows over 4 shards")
    print("RESULT:" + json.dumps({"ok": True, "checks": checks}))
    """
)


def test_streaming_batched_sharded_bitwise_equal():
    """streaming == batched == sharded (2 and 4 shards), bitwise, for random
    stream counts and loudness mixes — on 4 simulated devices."""
    proc = subprocess.run(
        [sys.executable, "-c", SHARDED_SCRIPT],
        capture_output=True,
        text=True,
        timeout=300,
        cwd=Path(__file__).resolve().parents[1],
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")][-1]
    out = json.loads(line[len("RESULT:"):])
    # 3 dispatch modes x >= 3 streams, + the 2 permutation legs + the
    # divisibility rejection
    assert out["ok"] and out["checks"] >= 12
