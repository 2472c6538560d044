"""The HuBERT X-Large verifier served through MonitorEngine, against the
benchmark's plain float32 reference, at a tiny width on the CPU.

The tiny configuration keeps the published kernels and strides, so one
12,800-sample window still gives 39 frames, with small widths (conv 32, d
64, 4 heads of 16, FFN 128, 2 layers, positional kernel 16 in 4 groups) and
the benchmark family's seeded random weights.
"""
from __future__ import annotations

import json
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import catalog, check  # noqa: E402
from repro.models.hubert import HubertConfig, HubertParams, bake  # noqa: E402
from repro.serving import accelerator  # noqa: E402
from repro.serving.engine import MonitorEngine  # noqa: E402

TINY = {"conv_dim": [32] * 7, "hidden_size": 64, "num_hidden_layers": 2,
        "num_attention_heads": 4, "intermediate_size": 128, "num_conv_pos_embeddings": 16,
        "num_conv_pos_embedding_groups": 4}
#: widest log-odds gap (``check.logit_gap``) of the bf16 program from the
#: float32 reference: bf16 operands carry 8 significant bits, so each of the
#: ~14 matmuls and convs on a window's path rounds its inputs by up to 2^-9
#: relative; the program reads 3e-4..7e-4 on these weights, and the
#: reference with float8 e4m3 operands (4 significant bits) reads 6e-3..7e-3
LOGIT_TOL = 2e-3

CONFIG = json.loads((ROOT / "chipbench" / "configs" / "hubert_xlarge_verifier.json").read_text())
MODEL = dict(CONFIG["model"], **TINY)
CFG = HubertConfig(**{k: tuple(v) if isinstance(v, list) else v for k, v in MODEL.items()})
REF = catalog._module(ROOT / "chipbench" / "configs" / "hubert_verifier_reference.py")
STATED = CONFIG["stated_precision"]


@pytest.fixture(scope="module")
def params():
    return catalog.family("hubert_verifier").weights(MODEL, 2**33 + 1)


def _windows(n: int, seed: int) -> np.ndarray:
    """Noise windows with a 10^4 loudness spread."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 12_800)) * 10.0 ** rng.uniform(-2, 2, (n, 1))
    return x.astype(np.float32)


def _reference(params, x, modes) -> np.ndarray:
    with jax.default_matmul_precision("highest"):
        return np.asarray(REF.p_uav(params, jnp.asarray(x), dict(CONFIG, model=MODEL), modes))


def _engine(params, **kw) -> MonitorEngine:
    args = dict(n_streams=4, feature_kind="waveform", batch_slots=8, precision="bf16")
    return MonitorEngine(params, CFG, **{**args, **kw})


def test_frame_count():
    assert HubertConfig().n_frames == CFG.n_frames == 39


def test_engine_and_forward_match_the_reference(params):
    """The engine's probabilities (rings -> blocks -> dispatch -> tracker)
    and the forward's log-odds agree with the float32 reference within
    LOGIT_TOL; the reference's control (each part one step lower) does not."""
    x = _windows(12, 3)
    eng = _engine(params)
    eng.precompile()
    for s in range(4):
        eng.push(s, x[3 * s : 3 * s + 3].reshape(-1))
    got = [w for _ in range(3) for w in eng.step()]
    assert eng.step() == [] and list(eng.served_windows) == [3, 3, 3, 3]
    p_engine = np.array([w.p_uav for w in sorted(got, key=lambda w: (w.stream, w.window_idx))])
    p_ref = _reference(params, x, {k: "fp32" for k in STATED})
    assert check.logit_gap(p_engine, p_ref) < LOGIT_TOL
    probs = np.asarray(accelerator.accelerator_forward(params, jnp.asarray(x), CFG, raw_windows=True))
    np.testing.assert_allclose(probs.sum(axis=1), 1.0, rtol=1e-6)
    assert check.logit_gap(probs[:, 1], p_ref) < LOGIT_TOL
    p_ctl = _reference(params, x, REF.control_modes(STATED))
    assert check.logit_gap(p_ctl, p_ref) > LOGIT_TOL


def test_forward_takes_raw_windows_only(params):
    """The verifier's input normalisation always runs in-graph: a forward
    asked to skip it is refused."""
    with pytest.raises(ValueError, match="raw_windows=True"):
        accelerator.accelerator_forward(params, jnp.asarray(_windows(1, 4)), CFG)


def test_window_alone_equals_window_in_a_full_block(params):
    """Row independence: a window's probabilities do not depend on its
    co-batch, bitwise, alone (padded to MIN_ROWS), in a full block, or
    permuted within it."""
    x = jnp.asarray(_windows(16, 5))
    art = bake(params, CFG)
    full = np.asarray(accelerator.accelerator_forward(art, x, CFG, raw_windows=True))
    perm = np.random.default_rng(6).permutation(16)
    permuted = np.asarray(accelerator.accelerator_forward(art, x[perm], CFG, raw_windows=True))
    np.testing.assert_array_equal(full[perm], permuted)
    for i in (0, 9):
        alone = np.asarray(accelerator.accelerator_forward(art, x[i : i + 1], CFG, raw_windows=True))
        np.testing.assert_array_equal(alone[0], full[i])


def test_op_scopes_map_every_matmul_conv_and_reduction(params):
    """Every dot, convolution and reduce the compiled forward runs (the
    encoder's inside the scan's while body too) maps to a named scope, and
    every part of the model holds some."""
    eng = _engine(params)
    scopes = eng.op_scopes()
    assert set(scopes.values()) == {"frontend", "waveform", "featproj", "posconv", "attn", "ffn",
                                    "head"}
    hlo = accelerator._forward_verifier.lower(
        eng._qp, jax.ShapeDtypeStruct((8, 12_800), jnp.float32)).compile().as_text()
    found = re.findall(r"^\s+(?:ROOT )?%(\S+) = \S+ (?:dot|convolution|reduce)\(", hlo, re.M)
    assert len(found) > 10
    assert [name for name in found if name not in scopes] == []


def test_bake_folds_weight_norm_and_stacks_layers(params):
    art = bake(params, CFG)
    w = art.weights
    assert isinstance(art, HubertParams) and art.cfg == CFG
    v, g = np.asarray(params["pos_v"]), np.asarray(params["pos_g"])
    folded = g[:, None, None] * v / np.sqrt((v * v).sum(axis=(1, 2), keepdims=True))
    assert w["pos_w"].dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(w["pos_w"], np.float32), folded, rtol=2**-8)
    assert w["layers"]["qkv_w"].shape == (2, 64, 192) and w["layers"]["qkv_w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(w["layers"]["qkv_w"][1, :, 64:128], np.float32),
                                  np.asarray(params["layers"][1]["k_w"].astype(jnp.bfloat16), np.float32))
    assert w["proj_w"].dtype == jnp.float32  # the head stays float32


@pytest.mark.parametrize("kw,match", [
    ({"precision": "int8"}, "bf16"),
    ({"prune": object()}, "prune"),
    ({"policy": object()}, "policy"),
    ({"shards": 2}, "one device"),
    ({"feature_kind": "mfcc20"}, "input_len"),
])
def test_engine_rejects_what_the_verifier_does_not_serve(params, kw, match):
    with pytest.raises(ValueError, match=match):
        _engine(params, **kw)
