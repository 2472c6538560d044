"""Plain reference of the HuBERT X-Large verifier: HubertForSequenceClassification.

Written from the paper (Hsu et al., arXiv:2106.07447) and the layer
equations of HF's ``HubertModel`` with ``do_stable_layer_norm=True``,
``feat_extract_norm="layer"``, ``conv_bias=True`` and exact GELU, in
straightforward ``jax.numpy`` at float32 ``Precision.HIGHEST``: no kernels,
no scan, no batching tricks; one jitted call per part (the input
normalisation, the conv stack, the feature projection, the positional conv,
each encoder layer, the head).  It imports nothing of the program under
test and takes nothing the program made: the harness hands it the float
weights it hands the program, and it folds the weight norm from ``pos_g``
and ``pos_v`` itself.

``p_uav`` is what the benchmark's check calls.  ``modes`` gives each part's
precision: ``fp32`` for the reference, and for the control one precision
step below the configuration's statement (``control_modes``): a ``bf16``
part takes float8 e4m3 operands (each activation scaled per window and each
weight per output channel so that its largest magnitude is 448, the largest
e4m3 value), a ``fp32`` part bfloat16 operands, in every matmul and conv of
the part, the accumulation kept in float32; the float32 front-end computes
its statistics and output in bfloat16.  LayerNorm, GELU, both softmaxes and
the mean over frames stay float32 in every mode.

Departures from HF, all shared with the program's deployment: random seeded
weights stand in for trained ones; the sequence-classification head is
untrained, with two labels ("UAV" second); one 12,800-sample window is one
sequence, so there is no attention mask and no padding; inference only (no
dropout, layer drop or time masking, so ``masked_spec_embed`` is not held).
"""
from __future__ import annotations

import functools
from pathlib import Path

import jax
import jax.numpy as jnp

from chipbench import catalog

HIGHEST = jax.lax.Precision.HIGHEST
#: variance floor of the feature extractor's per-window normalisation
WAVEFORM_EPS = 1e-7
#: largest finite float8 e4m3 magnitude
E4M3_MAX = 448.0
#: one precision step down, for the control
LOWER = {"fp32": "bf16", "bf16": "fp8"}

# the detector's tracker is the verifier's too (the engine runs one tracker)
track = catalog._module(Path(__file__).with_name("shield8_cnn_reference.py")).track


def _operand(v: jax.Array, mode: str, keep_axis: int) -> jax.Array:
    """A matmul or conv operand in ``mode``, as float32 values: ``fp32`` as
    is, ``bf16`` rounded to bfloat16, ``fp8`` rounded to float8 e4m3 after
    scaling each index of ``keep_axis`` so that its largest magnitude is 448."""
    if mode == "fp32":
        return v
    if mode == "bf16":
        return v.astype(jnp.bfloat16).astype(jnp.float32)
    red = tuple(i for i in range(v.ndim) if i != keep_axis % v.ndim)
    scale = jnp.maximum(jnp.max(jnp.abs(v), axis=red, keepdims=True), 1e-30) / E4M3_MAX
    return (v / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _linear(x, w, b, mode):
    """(B, ..., K) @ (K, N) + b, the activation scaled per window."""
    return jnp.matmul(_operand(x, mode, 0), _operand(w, mode, -1), precision=HIGHEST) + b


def _conv(x, w, b, mode, stride=1, pad=0, groups=1):
    """(B, L, C) conv with (k, C // groups, C') weights, plus bias."""
    y = jax.lax.conv_general_dilated(
        _operand(x, mode, 0), _operand(w, mode, -1), (stride,), [(pad, pad)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=groups,
        precision=HIGHEST)
    return y + b


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + jax.scipy.special.erf(x / jnp.sqrt(2.0)))


@functools.partial(jax.jit, static_argnames="mode")
def normalize(x, mode):
    """Zero mean, unit variance per window (the feature extractor's
    ``do_normalize``); ``bf16`` computes it in bfloat16."""
    if mode == "bf16":
        x = x.astype(jnp.bfloat16)
    x = x - jnp.mean(x, axis=1, keepdims=True)
    x = x / jnp.sqrt(jnp.mean(x * x, axis=1, keepdims=True) + WAVEFORM_EPS)
    return x.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("strides", "eps", "mode"))
def feature_encoder(conv, x, strides, eps, mode):
    """(B, 12800) -> (B, frames, 512): each conv, LayerNorm over channels, GELU."""
    h = x[:, :, None]
    for c, s in zip(conv, strides):
        h = _gelu(_layer_norm(_conv(h, c["w"], c["b"], mode, s), c["ln_g"], c["ln_b"], eps))
    return h


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def feature_projection(p, h, eps, mode):
    return _linear(_layer_norm(h, p["feat_ln_g"], p["feat_ln_b"], eps), p["feat_w"],
                   p["feat_b"], mode)


@functools.partial(jax.jit, static_argnames=("groups", "mode"))
def positional_conv(p, h, groups, mode):
    """Weight-normed grouped conv over frames (norm over all but the kernel
    axis), the last frame of an even kernel dropped, GELU, added to ``h``."""
    v = p["pos_v"]
    w = p["pos_g"][:, None, None] * v / jnp.sqrt(jnp.sum(v * v, axis=(1, 2), keepdims=True))
    k = v.shape[0]
    pos = _conv(h, w, p["pos_b"], mode, pad=k // 2, groups=groups)
    if k % 2 == 0:
        pos = pos[:, :-1]
    return h + _gelu(pos)


@functools.partial(jax.jit, static_argnames=("heads", "eps", "attn_mode", "ffn_mode"))
def encoder_layer(lw, h, heads, eps, attn_mode, ffn_mode):
    """One pre-LN layer: h + attention(LN(h)), then + feed-forward(LN(h))."""
    b, t, d = h.shape
    dh = d // heads
    x = _layer_norm(h, lw["ln1_g"], lw["ln1_b"], eps)
    q = _linear(x, lw["q_w"], lw["q_b"], attn_mode) * dh**-0.5
    k = _linear(x, lw["k_w"], lw["k_b"], attn_mode)
    v = _linear(x, lw["v_w"], lw["v_b"], attn_mode)
    q, k, v = (a.reshape(b, t, heads, dh) for a in (q, k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", _operand(q, attn_mode, 0), _operand(k, attn_mode, 0),
                        precision=HIGHEST)
    probs = jax.nn.softmax(scores, axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", _operand(probs, attn_mode, 0), _operand(v, attn_mode, 0),
                   precision=HIGHEST)
    h = h + _linear(a.reshape(b, t, d), lw["o_w"], lw["o_b"], attn_mode)
    x = _layer_norm(h, lw["ln2_g"], lw["ln2_b"], eps)
    x = _gelu(_linear(x, lw["ff1_w"], lw["ff1_b"], ffn_mode))
    return h + _linear(x, lw["ff2_w"], lw["ff2_b"], ffn_mode)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def head(p, h, eps, mode):
    """Final LayerNorm, projector on each frame, mean over frames,
    classifier, softmax."""
    h = _layer_norm(h, p["final_ln_g"], p["final_ln_b"], eps)
    pooled = jnp.mean(_linear(h, p["proj_w"], p["proj_b"], mode), axis=1)
    return jax.nn.softmax(_linear(pooled, p["cls_w"], p["cls_b"], mode), axis=-1)


def p_uav(params: dict, windows: jax.Array, config: dict, modes: dict) -> jax.Array:
    """(B, 12800) raw windows -> (B,) probability of "UAV", each part (and
    ``front_end``) in the precision ``modes`` gives it."""
    m = config["model"]
    eps = m["layer_norm_eps"]
    top = {k: v for k, v in params.items() if k not in ("conv", "layers")}
    x = normalize(jnp.asarray(windows, jnp.float32), modes["front_end"])
    h = feature_encoder(params["conv"], x, tuple(m["conv_stride"]), eps, modes["waveform"])
    h = feature_projection(top, h, eps, modes["featproj"])
    h = positional_conv(top, h, m["num_conv_pos_embedding_groups"], modes["posconv"])
    for lw in params["layers"]:
        h = encoder_layer(lw, h, m["num_attention_heads"], eps, modes["attn"], modes["ffn"])
    return head(top, h, eps, modes["head"])[:, 1]


def control_modes(stated: dict, lower=None) -> dict:
    """Each part named in ``lower`` (every part, the front-end too, when
    None) one precision step below the configuration's statement; the rest
    in float32."""
    return {name: LOWER[mode] if lower is None or name in lower else "fp32"
            for name, mode in stated.items()}
