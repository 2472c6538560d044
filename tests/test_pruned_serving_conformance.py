"""Conformance matrix for pruned + mixed-precision serving artifacts.

Two guarantees pin the deploy-time transforms:

* **pruned-physical == masked-unpruned, bitwise on int32 accumulators** —
  physically removing the pruned conv-out channels / dense rows from the
  artifact produces the same numbers as serving the full-size artifact with
  those channels and rows zeroed.  Because weights are quantised *after*
  pruning in both constructions (zeroed rows do not move a per-column amax),
  the int8 payloads, scales and therefore the kernel's int32 accumulators
  agree exactly — an indexing bug anywhere in the slice/flatten plumbing
  breaks this loudly.

* **streaming == batched == sharded for every artifact cell** — the
  row-independence invariant (per-sample activation scales for the 8-bit
  layer modes, per-row conv/matmul for the float modes) holds for all of
  {pruned, unpruned} x {int8, fxp8, mixed}, so window-at-a-time streaming,
  micro-batching, and 4-way sharded dispatch produce bitwise-identical
  probabilities on every cell.  The sharded leg runs in a subprocess with 4
  simulated devices (the device-count flag must land before jax import).

Fast tier: small zcr detector, interpret mode.
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.precision_policy import Precision, PrecisionPolicy
from repro.core.pruning import PruneSpec, plan_prune
from repro.core.quantization import fxp8_quantize, int8_symmetric
from repro.data import features
from repro.kernels import ops
from repro.models import cnn1d
from repro.serving.accelerator import accelerator_forward
from repro.serving.quantized_params import quantize_params


def _small_detector():
    cfg = cnn1d.CNNConfig(
        input_len=features.FEATURE_DIMS["zcr"], channels=(4, 8), hidden=8
    )
    params = cnn1d.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _mixed_policy(default: Precision = Precision.INT8) -> PrecisionPolicy:
    return PrecisionPolicy(
        rules={"conv0/w": Precision.BF16, "dense1/w": Precision.FP32},
        default=default,
    )


#: the precision axis of the matrix: (cell name, default mode, policy)
PRECISION_CELLS = [
    ("int8", "int8", None),
    ("fxp8", "fxp8", None),
    ("mixed", "int8", _mixed_policy()),
]


def _masked_setup(params, cfg, spec: PruneSpec):
    """Full-size params with pruned channels/rows zeroed, plus the frame-only
    spec that applies the same boundary trim without touching channels."""
    n_ch = cfg.channels[-1]
    last = f"conv{len(cfg.channels) - 1}"
    mask = np.zeros(n_ch, np.float32)
    mask[np.asarray(spec.keep_channels)] = 1.0
    masked = {k: dict(v) for k, v in params.items()}
    masked[last]["w"] = params[last]["w"] * mask[None, None, :]
    masked[last]["b"] = params[last]["b"] * mask
    wd = np.asarray(params["dense0"]["w"]).reshape(cfg.n_frames, n_ch, -1).copy()
    dropped = np.setdiff1d(np.arange(n_ch), np.asarray(spec.keep_channels))
    wd[:, dropped, :] = 0.0
    masked["dense0"]["w"] = jnp.asarray(wd.reshape(cfg.flatten_size, -1))
    frame_spec = PruneSpec(
        keep_channels=np.arange(n_ch),
        keep_frames=np.asarray(spec.keep_frames),
        flatten_before=cfg.flatten_size,
        flatten_after=len(spec.keep_frames) * n_ch,
    )
    return masked, frame_spec


@pytest.mark.parametrize("name,mode,policy", PRECISION_CELLS)
def test_pruned_physical_equals_masked_unpruned_bitwise(name, mode, policy):
    """The headline conformance cell: the physically-pruned artifact and the
    masked full-size artifact produce bitwise-identical probabilities on the
    whole deployed datapath, for every precision cell."""
    cfg, params = _small_detector()
    spec = plan_prune(params["conv1"]["w"], cfg.n_frames, keep=3, trim_frames=1)
    masked, frame_spec = _masked_setup(params, cfg, spec)

    qp_pruned = quantize_params(params, cfg, mode=mode, prune=spec, policy=policy)
    qp_masked = quantize_params(masked, cfg, mode=mode, prune=frame_spec, policy=policy)
    assert qp_pruned.pruned and qp_pruned.keep_frames == cfg.n_frames - 1

    rng = np.random.default_rng(3)
    x = rng.standard_normal((6, cfg.input_len)).astype(np.float32)
    x *= (10.0 ** rng.uniform(-2, 2, size=(6, 1))).astype(np.float32)
    p_pruned = np.asarray(accelerator_forward(qp_pruned, jnp.asarray(x), cfg))
    p_masked = np.asarray(accelerator_forward(qp_masked, jnp.asarray(x), cfg))
    np.testing.assert_array_equal(p_pruned, p_masked)


@pytest.mark.parametrize("name,mode,policy", PRECISION_CELLS)
def test_short_batch_rows_equal_batched_rows(name, mode, policy):
    """A batch below ``MIN_ROWS`` runs padded to it; its rows equal the same
    rows of a full batch bitwise, in every precision cell (the adaptive slot
    ladder dispatches batches of 1 and 2)."""
    cfg, params = _small_detector()
    qp = quantize_params(params, cfg, mode=mode, policy=policy)
    rng = np.random.default_rng(5)
    x = rng.standard_normal((16, cfg.input_len)).astype(np.float32)
    x *= (10.0 ** rng.uniform(-2, 2, size=(16, 1))).astype(np.float32)
    full = np.asarray(accelerator_forward(qp, jnp.asarray(x), cfg))
    for n in (1, 3):
        short = np.asarray(accelerator_forward(qp, jnp.asarray(x[:n]), cfg))
        assert short.shape == (n, cfg.n_classes)
        np.testing.assert_array_equal(short, full[:n])


@pytest.mark.parametrize("quant", [int8_symmetric, fxp8_quantize])
def test_dense_prune_int32_accumulator_parity(quant):
    """Accumulator-level form of the guarantee: slicing dense rows physically
    vs zeroing them yields identical int32 accumulators on the W8A8 kernel
    (unit scales make the fp32 output an exact image of the accumulator)."""
    rng = np.random.default_rng(0)
    flatten, keep_n, out = 96, 24, 16
    keep = np.sort(rng.choice(flatten, size=keep_n, replace=False))
    w = rng.standard_normal((flatten, out)).astype(np.float32)
    h_kept = rng.standard_normal((4, keep_n)).astype(np.float32)
    h_masked = np.zeros((4, flatten), np.float32)
    h_masked[:, keep] = h_kept

    # quantise-after-prune on both sides: per-column amax over the surviving
    # rows only (zeroed rows cannot move it), per-sample act scales.
    w_masked = np.zeros_like(w)
    w_masked[keep] = w[keep]
    wq_pruned = quant(jnp.asarray(w[keep]), axis=1)
    wq_masked = quant(jnp.asarray(w_masked), axis=1)
    np.testing.assert_array_equal(
        np.asarray(wq_pruned.scale), np.asarray(wq_masked.scale)
    )
    hq_pruned = quant(jnp.asarray(h_kept), axis=0)
    hq_masked = quant(jnp.asarray(h_masked), axis=0)
    np.testing.assert_array_equal(
        np.asarray(hq_pruned.scale), np.asarray(hq_masked.scale)
    )

    ones_m = jnp.ones((4, 1), jnp.float32)
    ones_n = jnp.ones((1, out), jnp.float32)
    acc_pruned = np.asarray(
        ops.quant_matmul(hq_pruned.q, wq_pruned.q, ones_m, ones_n)
    )
    acc_masked = np.asarray(
        ops.quant_matmul(hq_masked.q, wq_masked.q, ones_m, ones_n)
    )
    np.testing.assert_array_equal(acc_pruned, acc_masked)
    assert np.abs(acc_pruned).max() < 2.0**24  # fp32 carries the int32 exactly


def test_quantize_rejects_non_prefix_frame_subsets():
    """The accelerator serves the frame trim as a prefix slice; a spec whose
    kept frames are not a contiguous prefix would silently disagree with the
    dense rows that were actually kept — it must be rejected at bake time."""
    cfg, params = _small_detector()
    bad = PruneSpec(
        keep_channels=np.arange(cfg.channels[-1]),
        keep_frames=np.arange(1, cfg.n_frames),  # trims the FIRST frame
        flatten_before=cfg.flatten_size,
        flatten_after=(cfg.n_frames - 1) * cfg.channels[-1],
    )
    with pytest.raises(ValueError, match="contiguous prefix"):
        quantize_params(params, cfg, prune=bad)


def test_engine_rejects_prune_policy_on_prebaked_artifact():
    """prune/policy are quantise-once decisions: silently ignoring them on a
    pre-baked artifact would serve the wrong deployment cell."""
    from repro.serving.engine import MonitorEngine

    cfg, params = _small_detector()
    spec = plan_prune(params["conv1"]["w"], cfg.n_frames, keep=3, trim_frames=1)
    qp = quantize_params(params, cfg, mode="int8")
    with pytest.raises(ValueError, match="already-baked"):
        MonitorEngine(qp, cfg, n_streams=1, feature_kind="zcr", prune=spec)
    with pytest.raises(ValueError, match="already-baked"):
        MonitorEngine(
            qp, cfg, n_streams=1, feature_kind="zcr", policy=_mixed_policy()
        )


def test_mixed_artifact_tags_drive_dispatch():
    """The artifact's static tags are the dispatch surface: a mixed artifact
    stores bf16/fp32 layers as plain arrays (no QTensor payload) and 8-bit
    layers as int8 payloads + scales."""
    from repro.core.quantization import QTensor

    cfg, params = _small_detector()
    qp = quantize_params(params, cfg, mode="int8", policy=_mixed_policy())
    assert qp.layer_modes == (("bf16", "int8"), ("int8", "fp32"))
    assert qp.mixed and not qp.pruned
    assert qp.convs[0]["w"].dtype == jnp.bfloat16
    assert isinstance(qp.convs[1]["w"], QTensor)
    assert isinstance(qp.denses[0]["w"], QTensor)
    assert qp.denses[1]["w"].dtype == jnp.float32


MATRIX_SCRIPT = textwrap.dedent(
    """\
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import sys, json
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core.precision_policy import Precision, PrecisionPolicy
    from repro.core.pruning import plan_prune
    from repro.data import features
    from repro.distributed.sharding import stream_mesh
    from repro.models import cnn1d
    from repro.serving.accelerator import accelerator_forward, accelerator_forward_sharded
    from repro.serving.engine import MonitorEngine
    from repro.serving.quantized_params import quantize_params

    cfg = cnn1d.CNNConfig(input_len=features.FEATURE_DIMS["zcr"], channels=(4, 8), hidden=8)
    params = cnn1d.init_params(jax.random.PRNGKey(0), cfg)
    spec = plan_prune(params["conv1"]["w"], cfg.n_frames, keep=3, trim_frames=1)
    mixed = PrecisionPolicy(
        rules={"conv0/w": Precision.BF16, "dense1/w": Precision.FP32},
        default=Precision.INT8,
    )
    cells = [
        (prune_name, mode_name, mode, policy)
        for prune_name in ("unpruned", "pruned")
        for mode_name, mode, policy in (
            ("int8", "int8", None), ("fxp8", "fxp8", None), ("mixed", "int8", mixed),
        )
    ]
    mesh = stream_mesh(4)
    rng = np.random.default_rng(17)
    x = rng.standard_normal((4, cfg.input_len)).astype(np.float32)
    x *= (10.0 ** rng.uniform(-2, 2, size=(4, 1))).astype(np.float32)
    # raw 0.8 s windows for the on-device-features leg, same loudness spread
    wr = rng.standard_normal((4, features.N_SAMPLES)).astype(np.float32)
    wr *= (10.0 ** rng.uniform(-2, 2, size=(4, 1))).astype(np.float32)
    checks = 0

    for prune_name, mode_name, mode, policy in cells:
        prune = spec if prune_name == "pruned" else None
        qp = quantize_params(params, cfg, mode=mode, prune=prune, policy=policy)
        batched = np.asarray(accelerator_forward(qp, jnp.asarray(x), cfg))
        # sharded: 4 rows over 4 devices, bitwise
        sharded = np.asarray(
            accelerator_forward_sharded(qp, jnp.asarray(x), cfg, mesh=mesh)
        )
        np.testing.assert_array_equal(batched, sharded, err_msg=f"{prune_name}/{mode_name} sharded")
        # streamed: one row at a time, bitwise
        for i in range(x.shape[0]):
            row = np.asarray(accelerator_forward(qp, jnp.asarray(x[i : i + 1]), cfg))
            np.testing.assert_array_equal(batched[i : i + 1], row, err_msg=f"{prune_name}/{mode_name} row {i}")
        checks += 1 + x.shape[0]

        # on-device-features leg: same cell with the DSP front-end fused
        # into the jitted program — raw windows in, still bitwise across
        # streaming/batched/sharded (features recomputed shard-local).
        qp_dev = quantize_params(
            params, cfg, mode=mode, prune=prune, policy=policy, feature_kind="zcr"
        )
        b_dev = np.asarray(
            accelerator_forward(qp_dev, jnp.asarray(wr), cfg, raw_windows=True)
        )
        s_dev = np.asarray(accelerator_forward_sharded(
            qp_dev, jnp.asarray(wr), cfg, mesh=mesh, raw_windows=True
        ))
        np.testing.assert_array_equal(b_dev, s_dev, err_msg=f"{prune_name}/{mode_name} sharded raw")
        for i in range(wr.shape[0]):
            row = np.asarray(accelerator_forward(
                qp_dev, jnp.asarray(wr[i : i + 1]), cfg, raw_windows=True
            ))
            np.testing.assert_array_equal(b_dev[i : i + 1], row, err_msg=f"{prune_name}/{mode_name} raw row {i}")
        checks += 1 + wr.shape[0]

    # End-to-end engine leg on the deployed configuration (pruned + mixed):
    # uneven chunked delivery, unsharded vs 2-way sharded dispatch, host vs
    # fused front-end, must all reproduce the batched per-stream reference
    # bitwise (host features vs one host-features batched forward; on-device
    # features vs one raw-window batched forward).
    qp_deploy = quantize_params(params, cfg, mode="int8", prune=spec, policy=mixed)
    qp_deploy_dev = quantize_params(
        params, cfg, mode="int8", prune=spec, policy=mixed, feature_kind="zcr"
    )
    n_streams, n_win = 2, 2
    audio = rng.standard_normal((n_streams, n_win * features.N_SAMPLES)).astype(np.float32)
    audio *= (10.0 ** rng.uniform(-2, 2, size=(n_streams, 1))).astype(np.float32)
    ref, ref_dev = [], []
    for s in range(n_streams):
        wins = audio[s].reshape(n_win, features.N_SAMPLES)
        feats = features.batch_features(wins, "zcr")
        ref.append(np.asarray(accelerator_forward(qp_deploy, jnp.asarray(feats), cfg))[:, 1])
        ref_dev.append(np.asarray(accelerator_forward(
            qp_deploy_dev, jnp.asarray(wins), cfg, raw_windows=True
        ))[:, 1])
    for on_device in (False, True):
        for shards in (None, 2):
            engine = MonitorEngine(
                params, cfg, n_streams=n_streams, feature_kind="zcr",
                on_device_features=on_device,
                batch_slots=2, prune=spec, policy=mixed, shards=shards,
            )
            cursors = [0] * n_streams
            scores = {s: [] for s in range(n_streams)}
            while any(c < audio.shape[1] for c in cursors):
                for s in range(n_streams):
                    n = int(rng.uniform(0.4, 1.6) * features.N_SAMPLES)
                    engine.push(s, audio[s, cursors[s] : cursors[s] + n])
                    cursors[s] += n
                for ws in engine.step():
                    scores[ws.stream].append(ws.p_uav)
            for ws in engine.drain():
                scores[ws.stream].append(ws.p_uav)
            assert engine.dropped_samples == 0
            want = ref_dev if on_device else ref
            for s in range(n_streams):
                got = np.asarray(scores[s], np.float64)
                assert got.shape == (n_win,)
                np.testing.assert_array_equal(got, want[s].astype(np.float64))
                checks += 1
    print("RESULT:" + json.dumps({"ok": True, "checks": checks}))
    """
)


def test_matrix_streaming_batched_sharded_bitwise_equal():
    """streaming == batched == sharded (4 simulated devices), bitwise, for
    every {pruned, unpruned} x {int8, fxp8, mixed} artifact cell — each cell
    run twice, on host-extracted features and with the DSP front-end fused
    into the jitted program (raw windows) — plus the engine's pruned+mixed
    deployment end to end in both front-end modes."""
    proc = subprocess.run(
        [sys.executable, "-c", MATRIX_SCRIPT],
        capture_output=True,
        text=True,
        timeout=600,
        cwd=Path(__file__).resolve().parents[1],
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")][-1]
    out = json.loads(line[len("RESULT:"):])
    # 6 cells x 2 front-ends x (1 sharded + 4 streamed rows)
    # + 2 front-ends x 2 engine dispatch modes x 2 streams
    assert out["ok"] and out["checks"] == 68
