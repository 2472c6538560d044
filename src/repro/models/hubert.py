"""HuBERT X-Large as a second-stage UAV verifier: configuration, bake, served forward.

The verifier re-scores the detector's 0.8 s, 16 kHz windows with a large
self-supervised audio encoder (Hsu et al., arXiv:2106.07447) at the widths of
the published ``facebook/hubert-xlarge-ll60k`` config, topped with the
``HubertForSequenceClassification`` head.  Layer by layer, as HF's
``HubertModel`` with ``do_stable_layer_norm=True`` computes it:

* input normalisation: zero mean, unit variance per window (the feature
  extractor's ``do_normalize``; ``features_jax`` kind ``waveform``);
* waveform conv stack: 7 biased 'valid' convs (kernels 10,3,3,3,3,2,2,
  strides 5,2,2,2,2,2,2), each followed by LayerNorm over channels and GELU
  (``feat_extract_norm="layer"``): 12,800 samples -> 39 frames of 512;
* feature projection: LayerNorm(512), Linear 512 -> 1280;
* positional conv: grouped Conv1d 1280 -> 1280 (kernel 128, padding 64, 16
  groups, weight norm over the kernel axis), the last frame dropped (even
  kernel), GELU, added to its input;
* encoder: 48 pre-LN layers (biased Q/K/V/O attention over the window's
  frames, exact GELU feed-forward), then a final LayerNorm;
* head: projector 1280 -> 256 on each frame, mean over frames, classifier
  256 -> 2, softmax.

Precision as served: bf16 weights and matmul/conv operands accumulated in
float32; LayerNorm, GELU, both softmaxes and the mean over frames in float32;
the head's two small matmuls in float32 at ``HIGHEST``.

The float checkpoint's layout (``x @ w`` linears, ``(kernel, in, out)``
convs) is the dict :func:`bake` reads: ``conv`` (a list of ``{"w", "b",
"ln_g", "ln_b"}``), ``feat_ln_g``/``feat_ln_b``/``feat_w``/``feat_b``,
``pos_v`` ``(kernel, hidden // groups, hidden)``, ``pos_g`` ``(kernel,)``,
``pos_b``, ``layers`` (a list of ``{"ln1_g", "ln1_b", "q_w", "q_b", "k_w",
"k_b", "v_w", "v_b", "o_w", "o_b", "ln2_g", "ln2_b", "ff1_w", "ff1_b",
"ff2_w", "ff2_b"}``), ``final_ln_g``/``final_ln_b``, ``proj_w``/``proj_b``
and ``cls_w``/``cls_b``.  The bake folds the weight norm once, fuses Q/K/V,
casts the matmul and conv weights to bf16 and stacks the layers, so that the
encoder runs as one ``lax.scan`` and its compile time does not grow with
depth.

Every part of :func:`forward` runs under a ``jax.named_scope`` (``frontend``,
``waveform``, ``featproj``, ``posconv``, ``attn``, ``ffn``, ``head``), so a
device trace can be summed per part (``serving.accelerator.hlo_scopes``).
Attention mixes the frames of one window only; every row of a batch is
computed on its own.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.data import features_jax

F32, BF16 = jnp.float32, jnp.bfloat16
HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class HubertConfig:
    """Widths of the published ``facebook/hubert-xlarge-ll60k`` config, under
    its names, with a two-class sequence-classification head."""

    conv_dim: tuple[int, ...] = (512,) * 7
    conv_kernel: tuple[int, ...] = (10, 3, 3, 3, 3, 2, 2)
    conv_stride: tuple[int, ...] = (5, 2, 2, 2, 2, 2, 2)
    hidden_size: int = 1280
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    intermediate_size: int = 5120
    num_conv_pos_embeddings: int = 128
    num_conv_pos_embedding_groups: int = 16
    classifier_proj_size: int = 256
    num_labels: int = 2
    layer_norm_eps: float = 1e-5
    input_len: int = features_jax.N_SAMPLES  # one 0.8 s window at 16 kHz

    @property
    def n_frames(self) -> int:
        """Frames the waveform conv stack leaves of one window."""
        n = self.input_len
        for k, s in zip(self.conv_kernel, self.conv_stride):
            n = (n - k) // s + 1
        return n


@functools.partial(jax.tree_util.register_dataclass, data_fields=["weights"],
                   meta_fields=["cfg"])
@dataclasses.dataclass(frozen=True)
class HubertParams:
    """The served artifact: baked weights, and the configuration as static
    pytree data (a jitted forward specialises on it, never on the weights)."""

    weights: dict
    cfg: HubertConfig


def bake(params: dict, cfg: HubertConfig) -> HubertParams:
    """Float checkpoint -> the bf16 serving artifact, once."""
    return HubertParams(_bake(params), cfg)


@jax.jit
def _bake(p: dict) -> dict:
    bf = lambda w: w.astype(BF16)  # noqa: E731
    v = p["pos_v"]
    pos_w = p["pos_g"][:, None, None] * v / jnp.sqrt(jnp.sum(v * v, axis=(1, 2), keepdims=True))
    layers = p["layers"]

    def stack(f):
        return jnp.stack([f(lw) for lw in layers])

    enc = {k: stack(lambda lw, k=k: lw[k])
           for k in ("ln1_g", "ln1_b", "o_b", "ln2_g", "ln2_b", "ff1_b", "ff2_b")}
    enc.update({k: stack(lambda lw, k=k: bf(lw[k])) for k in ("o_w", "ff1_w", "ff2_w")})
    enc["qkv_w"] = stack(lambda lw: bf(jnp.concatenate([lw["q_w"], lw["k_w"], lw["v_w"]], 1)))
    enc["qkv_b"] = stack(lambda lw: jnp.concatenate([lw["q_b"], lw["k_b"], lw["v_b"]]))
    out = {k: p[k] for k in ("feat_ln_g", "feat_ln_b", "feat_b", "pos_b", "final_ln_g",
                             "final_ln_b", "proj_w", "proj_b", "cls_w", "cls_b")}
    out.update(conv=[dict(c, w=bf(c["w"])) for c in p["conv"]], feat_w=bf(p["feat_w"]),
               pos_w=bf(pos_w), layers=enc)
    return out


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    return xc * jax.lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps) * g + b


def _gelu(x):
    return jax.nn.gelu(x, approximate=False)


def _dense(x, w):
    """bf16 operands, float32 accumulation."""
    return jnp.matmul(x.astype(BF16), w, preferred_element_type=F32)


def _conv(x, w, stride=1, padding=((0, 0),), groups=1):
    """(B, L, C) x (k, C // groups, C') conv: bf16 operands, float32 accumulation."""
    return jax.lax.conv_general_dilated(
        x.astype(BF16), w, window_strides=(stride,), padding=padding,
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=groups,
        preferred_element_type=F32)


def _encoder_layer(cfg: HubertConfig, h, lw):
    bsz, t, d = h.shape
    heads = cfg.num_attention_heads
    eps = cfg.layer_norm_eps
    with jax.named_scope("attn"):
        qkv = _dense(_layer_norm(h, lw["ln1_g"], lw["ln1_b"], eps), lw["qkv_w"]) + lw["qkv_b"]
        q, k, v = (a.reshape(bsz, t, heads, d // heads) for a in jnp.split(qkv, 3, axis=-1))
        q = q * (d // heads) ** -0.5
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(BF16), k.astype(BF16),
                       preferred_element_type=F32)
        a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1).astype(BF16),
                       v.astype(BF16), preferred_element_type=F32)
        h = h + _dense(a.reshape(bsz, t, d), lw["o_w"]) + lw["o_b"]
    with jax.named_scope("ffn"):
        x = _gelu(_dense(_layer_norm(h, lw["ln2_g"], lw["ln2_b"], eps), lw["ff1_w"]) + lw["ff1_b"])
        return h + _dense(x, lw["ff2_w"]) + lw["ff2_b"]


def forward(art: HubertParams, x: jax.Array) -> jax.Array:
    """(B, 12800) raw windows -> (B, num_labels) class probabilities,
    traceable; the input normalisation runs in-graph."""
    cfg, w = art.cfg, art.weights
    eps = cfg.layer_norm_eps
    with jax.named_scope("frontend"):
        x = features_jax.feature_rows(x, "waveform")
    with jax.named_scope("waveform"):
        h = x[:, :, None]
        for c, s in zip(w["conv"], cfg.conv_stride):
            h = _gelu(_layer_norm(_conv(h, c["w"], s) + c["b"], c["ln_g"], c["ln_b"], eps))
    with jax.named_scope("featproj"):
        h = _dense(_layer_norm(h, w["feat_ln_g"], w["feat_ln_b"], eps), w["feat_w"]) + w["feat_b"]
    with jax.named_scope("posconv"):
        k = cfg.num_conv_pos_embeddings
        pos = _conv(h, w["pos_w"], padding=((k // 2, k // 2),),
                    groups=cfg.num_conv_pos_embedding_groups)
        h = h + _gelu(pos[:, : h.shape[1]] + w["pos_b"])  # an even kernel leaves one frame over
    h, _ = jax.lax.scan(lambda h, lw: (_encoder_layer(cfg, h, lw), None), h, w["layers"])
    with jax.named_scope("head"):
        h = _layer_norm(h, w["final_ln_g"], w["final_ln_b"], eps)
        h = jnp.matmul(h, w["proj_w"], precision=HIGHEST) + w["proj_b"]
        pooled = features_jax._pairwise_mean(h, axis=1)
        logits = jnp.matmul(pooled, w["cls_w"], precision=HIGHEST) + w["cls_b"]
        return jax.nn.softmax(logits, axis=-1)
