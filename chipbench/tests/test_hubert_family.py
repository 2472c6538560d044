"""CPU tests of the HuBERT X-Large verifier family: its operation and
parameter counts at published widths, its per-layer readers on synthetic
traces, and a tiny verifier cell run end to end through the harness."""
from __future__ import annotations

import json
import shutil
import time

import jax
import numpy as np
import pytest

from chipbench import catalog, flops, harness, load
from chipbench.tests.conftest import ROOT

TINY = {"conv_dim": [32] * 7, "hidden_size": 64, "num_hidden_layers": 2,
        "num_attention_heads": 4, "intermediate_size": 128, "num_conv_pos_embeddings": 16,
        "num_conv_pos_embedding_groups": 4}
NEW_METRICS = ("waveform_device_us_per_window", "encoder_device_us_per_window",
               "encoder_roofline_pct", "waveform_roofline_pct")


def _config():
    return json.loads((ROOT / "chipbench" / "configs" / "hubert_xlarge_verifier.json").read_text())


def test_layers_sum_to_the_published_work():
    """Operations per window at published widths, 2 per multiply-add:
    the conv stack, the projection, the positional conv, 48 layers of
    attention (Q/K/V/O and both products) and feed-forward, the head."""
    cfg = _config()
    layers = catalog.family("hubert_verifier").layers(cfg)
    assert layers == [("waveform", 3_918_518_272, "bf16"), ("featproj", 51_118_080, "bf16"),
                      ("posconv", 1_022_361_600, "bf16"), ("attn", 24_910_479_360, "bf16"),
                      ("ffn", 49_073_356_800, "bf16"), ("head", 25_560_064, "fp32")]
    assert flops.ops_per_window(layers) == 79_001_394_176
    peak_s = flops.peak_seconds_per_window(layers, catalog.peaks("TPU v5 lite"))
    assert peak_s == pytest.approx(79_001_394_176 / 197e12)  # >= 0.401 ms a window


def test_weights_hold_the_published_parameters():
    """``jax.eval_shape`` of the family's weights: 962,824,578 parameters,
    all float32 (3.85 GB), with no array made."""
    cfg = _config()
    shapes = jax.eval_shape(lambda: catalog.family("hubert_verifier").weights(cfg["model"], 2**33 + 7))
    leaves = jax.tree_util.tree_leaves(shapes)
    assert sum(int(np.prod(x.shape)) for x in leaves) == 962_824_578
    assert {x.dtype for x in leaves} == {np.dtype("float32")}
    assert len(shapes["layers"]) == 48 and shapes["pos_v"].shape == (128, 80, 1280)


def test_strided_conv_counts():
    conv = catalog.kernel("strided_conv")
    lengths, t = [], 12_800
    for k, s in zip((10, 3, 3, 3, 3, 2, 2), (5, 2, 2, 2, 2, 2, 2)):
        t = conv.out_len(t, k, s)
        lengths.append(t)
    assert lengths == [2559, 1279, 639, 319, 159, 79, 39]
    assert conv.out_len(39, 128, 1, 64) == 40  # the positional conv's, one frame over
    assert conv.ops(2, 39, 128, 1280, 1280, 16) == 2 * 2 * 39 * 128 * 80 * 1280
    assert conv.bytes_moved(2, 79, 39, 2, 512, 512) == 2 * 79 * 512 * 2 + 2 * 512 * 512 * 2 + 2 * 39 * 512 * 4


def test_control_lowers_each_part_one_step():
    cfg = _config()
    ref = catalog._module(ROOT / "chipbench" / "configs" / "hubert_verifier_reference.py")
    stated = cfg["stated_precision"]
    assert ref.control_modes(stated) == {"front_end": "bf16", "waveform": "fp8", "featproj": "fp8",
                                         "posconv": "fp8", "attn": "fp8", "ffn": "fp8", "head": "bf16"}
    assert ref.control_modes(stated, ["attn"]) == {k: "fp8" if k == "attn" else "fp32" for k in stated}
    v = np.array([[448.0, 1.0, 1.06], [0.5, 0.25, 0.0]], np.float32)
    np.testing.assert_array_equal(np.asarray(ref._operand(v, "fp8", 0)),
                                  [[448.0, 1.0, 1.0], [0.5, 0.25, 0.0]])  # 3 mantissa bits


def _readings(cell, per_scope_ns, windows):
    """A traced segment of one chip holding one operation per scope."""
    t, dev, op_scopes = 1_000, [], {}
    for i, (scope, ns) in enumerate(per_scope_ns.items()):
        dev.append((f"%fusion.{i} = f32[8] fusion()", t, t + ns))
        op_scopes[f"fusion.{i}"] = scope
        t += ns
    spans = load.Spans()
    spans.name += ["step"]
    spans.start += [2e-6]
    spans.end += [3e-6]
    spans.windows += [windows]
    return harness.Readings(cell=cell, trace={"devices": {0: dev},
                                              "host": [("chipbench.window", 1_000, t)]},
                            spans=spans, segment=(1e-6, t * 1e-9), op_scopes=op_scopes)


def test_readers_sum_the_verifiers_scopes(hubert_root):
    cell = catalog.load_cell("hubert_xl.catchup", hubert_root)
    assert {m["name"] for m in cell.per_layer} >= set(NEW_METRICS)
    ns = {"frontend": 1_000, "waveform": 19_000, "featproj": 500, "posconv": 2_500,
          "attn": 130_000, "ffn": 250_000, "head": 400}
    r = _readings(cell, ns, 2)
    read = {n: catalog.reader(n, hubert_root)(r) for n in NEW_METRICS}
    assert read["waveform_device_us_per_window"] == pytest.approx(20_000e-3 / 2)
    assert read["encoder_device_us_per_window"] == pytest.approx(383_000e-3 / 2)
    layers = dict((n, v) for n, v, _ in cell.family.layers(cell.config))
    peak = catalog.peaks(jax.devices()[0].device_kind, hubert_root)["bf16_flops_per_s"]
    encoder_ops = layers["featproj"] + layers["posconv"] + layers["attn"] + layers["ffn"]
    # the encoder is bound by its operations; the waveform stack by its
    # operations or its bytes, whichever needs longer
    assert read["encoder_roofline_pct"] == pytest.approx(100 * encoder_ops / peak / (383_000e-9 / 2))
    assert read["waveform_roofline_pct"] >= 100 * layers["waveform"] / peak / (20_000e-9 / 2) * 0.999
    r = _readings(cell, ns, 2)
    r.op_scopes = None  # an older program: nothing to read
    assert all(catalog.reader(n, hubert_root)(r) is None for n in NEW_METRICS)


@pytest.fixture(scope="module")
def hubert_root(tiny_root, tmp_path_factory):
    """The tiny checkout with a tiny verifier configuration and its cell
    ``tiny_hubert.catchup``, reporting what ``hubert_xl.catchup`` reports."""
    root = tmp_path_factory.mktemp("hubert") / "co"
    shutil.copytree(tiny_root, root)
    cfg = _config()
    cfg.update(name="tiny_hubert", model=dict(cfg["model"], **TINY))
    cfg["engine"]["batch_slots_per_chip"] = 8
    (root / "chipbench" / "configs" / "tiny_hubert.json").write_text(json.dumps(cfg))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny_hubert", "source": "test", "reduced": [], "why": "test",
                             "file": "chipbench/configs/tiny_hubert.json"})
    bench["workloads"].append({"name": "tiny_hubert.catchup", "config": "tiny_hubert",
                               "traffic": "tiny_catchup", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "hubert_xl.catchup" in m.get("workloads", []):
            m["workloads"].append("tiny_hubert.catchup")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_tiny_verifier_cell_runs_and_is_correct(hubert_root):
    """Weights, engine and operation counts come from the family; the
    check compares the engine's answers with the reference's; a traced run
    reports the host's per-layer metrics (a CPU trace holds no device
    operations for the four device readers)."""
    out = harness.run("tiny_hubert.catchup", 2**31 + 11, 2.0, False, time.perf_counter(),
                      root=hubert_root)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"windows_per_s", "setup_s"}
    assert out["failed"] == 0 and out["attempted"] > 0 and out["windows_compared"] > 0
    out = harness.run("tiny_hubert.catchup", 2**31 + 12, 2.0, True, time.perf_counter(),
                      root=hubert_root)
    assert out["correct"], out["checks"]
    assert {"push_us_per_window", "step_us_per_window", "mfu_pct"} <= set(out["metrics"])
    assert out["device"]["window_s"] > 0
