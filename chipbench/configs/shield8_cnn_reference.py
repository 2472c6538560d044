"""Plain reference of the SHIELD8-UAV detector: front-end, 1D-F-CNN, tracker.

Written from the paper (arXiv:2603.01069: §III-A eq. 1 for the network,
§III-C for the channel prune, §IV-A for the MFCC-20 feature vector) in
straightforward ``jax.numpy``, float32 at ``Precision.HIGHEST``, with no
kernels, batching or caching.  It imports nothing of the program under test
and takes nothing the program made: the harness hands it the same float
weights it hands the program, and it prunes and quantises for itself.

``p_uav`` is what the benchmark's check calls: the configuration's prune,
then ``features`` and ``forward``, jitted.  They also compute its control:
the same detector with every layer one precision step below what the
configuration states (int8 -> int4, bf16 -> int8, fp32 -> bf16), as
symmetric fake quantisation with per-row activation scales and
per-output-channel weight scales; the float32 front-end's projections drop
to bfloat16 operands.

Departures from the paper, all shared with the program's deployment: random
seeded weights stand in for trained ones; the feature vector is 20 MFCCs x
51 frames + 64 pooled log-mels + 10 PSD bands + ZCR mean and std (1,096
values), as the repository's front-end defines it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

SR = 16_000
N_SAMPLES = 12_800  # one 0.8 s window
N_FFT = 1024
HOP = 256
HIGHEST = jax.lax.Precision.HIGHEST

#: bits of each symmetric integer mode used by the control
INT_BITS = {"int8": 8, "int4": 4}
#: one precision step down, for the control
LOWER = {"fp32": "bf16", "bf16": "int8", "int8": "int4"}


# -- front-end (MFCC-20 feature vector, §IV-A) --------------------------------


@functools.lru_cache(maxsize=4)
def mel_filterbank(n_mels: int) -> np.ndarray:
    """Triangular, area-normalised mel filterbank (20 Hz - 7.6 kHz), (n_mels, bins)."""

    def hz_to_mel(f):
        return 2595.0 * np.log10(1.0 + np.asarray(f) / 700.0)

    def mel_to_hz(m):
        return 700.0 * (10.0 ** (np.asarray(m) / 2595.0) - 1.0)

    pts = mel_to_hz(np.linspace(hz_to_mel(20.0), hz_to_mel(7600.0), n_mels + 2))
    bins = np.fft.rfftfreq(N_FFT, 1.0 / SR)
    fb = np.zeros((n_mels, len(bins)))
    for i in range(n_mels):
        lo, ctr, hi = pts[i], pts[i + 1], pts[i + 2]
        up = (bins - lo) / max(ctr - lo, 1e-9)
        down = (hi - bins) / max(hi - ctr, 1e-9)
        fb[i] = np.maximum(0.0, np.minimum(up, down))
        if fb[i].sum() > 0:
            fb[i] /= fb[i].sum()
    return fb


@functools.lru_cache(maxsize=4)
def dct_ii(n_out: int, n_in: int) -> np.ndarray:
    """Orthonormal DCT-II matrix, (n_out, n_in)."""
    k = np.arange(n_out)[:, None]
    n = np.arange(n_in)[None, :]
    m = np.cos(np.pi * k * (2 * n + 1) / (2 * n_in))
    m[0] *= 1.0 / np.sqrt(2)
    return m * np.sqrt(2.0 / n_in)


def _mm(a: jax.Array, b: np.ndarray, mode: str) -> jax.Array:
    """A front-end projection: float32 at ``HIGHEST``, or with ``bf16`` its
    operands rounded to bfloat16 and the sum kept in float32 (one MXU pass,
    what the TPU does at its default precision)."""
    if mode == "bf16":
        a, b = (jnp.asarray(v).astype(jnp.bfloat16) for v in (a, b))
        return jnp.matmul(a, b, preferred_element_type=jnp.float32)
    return jnp.matmul(a, b, precision=HIGHEST)


def _log_mel(x: jax.Array, n_mels: int, mode: str) -> jax.Array:
    """(B, n) -> (B, 1 + n // HOP, n_mels) log10 mel energies of a centred STFT."""
    pad = N_FFT // 2
    xp = jnp.pad(x, ((0, 0), (pad, pad)), mode="reflect")
    n_frames = 1 + x.shape[1] // HOP
    idx = np.arange(N_FFT)[None, :] + HOP * np.arange(n_frames)[:, None]
    spec = jnp.fft.rfft(xp[:, idx] * np.hanning(N_FFT).astype(np.float32), axis=-1)
    power = jnp.abs(spec) ** 2
    fb = mel_filterbank(n_mels).T.astype(np.float32)
    return jnp.log10(_mm(power, fb, mode) + 1e-10)


def features(x: jax.Array, mode: str = "fp32") -> jax.Array:
    """(B, 12800) raw windows -> (B, 1096) zero-mean, unit-RMS feature vectors;
    ``mode`` is the precision of the two projections (mel and DCT)."""
    x = x.astype(jnp.float32)
    bsz = x.shape[0]
    x = x / (jnp.max(jnp.abs(x), axis=1, keepdims=True) + 1e-9)
    logmel64 = _log_mel(x, 64, mode)
    dct = dct_ii(20, 64).T.astype(np.float32)
    mfcc = _mm(logmel64, dct, mode)[:, :51].reshape(bsz, -1)
    pooled = jnp.mean(logmel64, axis=1)
    seg = 1024
    n_seg = x.shape[1] // seg
    segs = x[:, : n_seg * seg].reshape(bsz, n_seg, seg) * np.hanning(seg).astype(np.float32)
    psd = jnp.mean(jnp.abs(jnp.fft.rfft(segs, axis=-1)) ** 2, axis=1)[:, :512]
    psd = jnp.log10(psd + 1e-10)
    p10 = jnp.mean(psd[:, :510].reshape(bsz, 10, 51), axis=2)
    hop = x.shape[1] // 128
    frames = x[:, : 128 * hop].reshape(bsz, 128, hop)
    signs = jnp.where(frames >= 0, 1.0, -1.0)  # sign with sign(0) taken as +1
    z = jnp.mean(jnp.abs(jnp.diff(signs, axis=2)) > 0, axis=2)
    aux = jnp.stack([jnp.mean(z, axis=1), jnp.std(z, axis=1)], axis=1)
    v = jnp.concatenate([mfcc, pooled, p10, aux], axis=1)
    v = v - jnp.mean(v, axis=1, keepdims=True)
    return v / (jnp.sqrt(jnp.mean(v**2, axis=1, keepdims=True)) + 1e-8)


# -- network (eq. 1) -----------------------------------------------------------


def prune_plan(w_last: np.ndarray, n_frames: int, keep: int, trim_frames: int):
    """§III-C: keep the ``keep`` output channels of the last conv with the
    largest L1 norm (in index order) and drop ``trim_frames`` trailing frames."""
    importance = np.abs(np.asarray(w_last, np.float64)).sum(axis=(0, 1))
    channels = np.sort(np.argsort(importance)[::-1][:keep])
    return channels, n_frames - trim_frames


def prune(params: dict, model: dict, prune_cfg: dict | None) -> tuple[dict, int | None]:
    """The network as served: the last conv's channels and the dense rows that
    read them sliced out.  Returns the pruned float weights and the frame count
    kept before the flatten (None when unpruned)."""
    if not prune_cfg:
        return params, None
    channels = model["channels"]
    last = f"conv{len(channels) - 1}"
    n_frames = model["input_len"] // 2 ** len(channels)
    keep_ch, keep_fr = prune_plan(
        params[last]["w"], n_frames, prune_cfg["keep"], prune_cfg["trim_frames"]
    )
    out = {k: dict(v) for k, v in params.items()}
    out[last]["w"] = params[last]["w"][:, :, keep_ch]
    out[last]["b"] = params[last]["b"][keep_ch]
    w = params["dense0"]["w"].reshape(n_frames, channels[-1], -1)
    out["dense0"]["w"] = w[:keep_fr][:, keep_ch].reshape(keep_fr * len(keep_ch), -1)
    return out, keep_fr


def _fake_quant(v: jax.Array, mode: str, keep_axis: int) -> jax.Array:
    """Symmetric fake quantisation with one scale per index of ``keep_axis``;
    ``bf16`` rounds through bfloat16 and ``fp32`` is the identity."""
    if mode == "fp32":
        return v
    if mode == "bf16":
        return v.astype(jnp.bfloat16).astype(jnp.float32)
    qmax = 2.0 ** (INT_BITS[mode] - 1) - 1
    red = tuple(i for i in range(v.ndim) if i != keep_axis % v.ndim)
    scale = jnp.maximum(jnp.max(jnp.abs(v), axis=red, keepdims=True), 1e-12) / qmax
    return jnp.clip(jnp.round(v / scale), -qmax - 1, qmax) * scale


def _maxpool2(h: jax.Array) -> jax.Array:
    b, length, c = h.shape
    return jnp.max(h[:, : length // 2 * 2].reshape(b, length // 2, 2, c), axis=2)


def forward(params: dict, feats: jax.Array, keep_frames: int | None, modes: dict) -> jax.Array:
    """(B, M) features -> (B, n_classes) probabilities.  ``modes`` maps each
    layer name to the precision it is computed in (``fp32`` for the reference);
    other keys, such as ``front_end``, are ignored here."""
    convs = sorted(k for k in params if k.startswith("conv"))
    h = feats[:, :, None].astype(jnp.float32)
    for name in convs:
        p = params[name]
        x = _fake_quant(h, modes[name], 0)
        w = _fake_quant(p["w"].astype(jnp.float32), modes[name], 2)
        h = jax.lax.conv_general_dilated(
            x, w, (1,), "SAME", dimension_numbers=("NWC", "WIO", "NWC"),
            precision=HIGHEST,
        )
        h = _maxpool2(jnp.maximum(h + p["b"], 0.0))
    if keep_frames is not None:
        h = h[:, :keep_frames]
    h = h.reshape(h.shape[0], -1)
    denses = sorted(k for k in params if k.startswith("dense"))
    for i, name in enumerate(denses):
        p = params[name]
        x = _fake_quant(h, modes[name], 0)
        w = _fake_quant(p["w"].astype(jnp.float32), modes[name], 1)
        h = jnp.matmul(x, w, precision=HIGHEST) + p["b"]
        if i < len(denses) - 1:
            h = jnp.maximum(h, 0.0)
    return jax.nn.softmax(h, axis=-1)


@functools.lru_cache(maxsize=8)
def _p_uav_fn(keep_frames: int | None, modes_items: tuple):
    modes = dict(modes_items)

    @jax.jit
    def fn(params, windows):
        feats = features(windows, modes.get("front_end", "fp32"))
        return forward(params, feats, keep_frames, modes)[:, 1]

    return fn


#: the last call's (params, model, prune settings, pruned params, kept frames):
#: the check calls ``p_uav`` once per block of rows with the same weights
_last_prune: list = []


def p_uav(params: dict, windows: jax.Array, config: dict, modes: dict) -> jax.Array:
    """(B, 12800) raw windows -> (B,) probability of "UAV" of the network as
    served: the configuration's prune, the MFCC-20 front-end and eq. 1, each
    layer (and ``front_end``) in the precision ``modes`` gives it."""
    key = (config["model"], config["bake"].get("prune"))
    if not (_last_prune and _last_prune[0] is params and _last_prune[1:3] == list(key)):
        _last_prune[:] = [params, *key, *prune(params, *key)]
    pruned, keep_frames = _last_prune[3:]
    return _p_uav_fn(keep_frames, tuple(sorted(modes.items())))(pruned, windows)


def control_modes(stated: dict, lower=None) -> dict:
    """Each layer named in ``lower`` (every layer, the front-end too, when
    None) one precision step below the configuration's statement; the rest
    in float32."""
    return {name: LOWER[mode] if lower is None or name in lower else "fp32"
            for name, mode in stated.items()}


# -- tracker (EMA, hysteresis, minimum duration) -------------------------------


def track(p: np.ndarray, *, ema_alpha: float, enter: float, exit: float, dtype=np.float64):
    """Per-window (smoothed score, active flag) of one stream's probabilities,
    in arrival order.  ``dtype`` is the state's precision (float64 as stated;
    float32 for the control)."""
    p = np.asarray(p, dtype)
    a = dtype(ema_alpha)
    one_minus_a = dtype(1) - a
    smoothed = np.empty(len(p), dtype)
    active = np.empty(len(p), bool)
    ema, on = None, False
    for i, v in enumerate(p):
        ema = v if ema is None else dtype(a * v + one_minus_a * ema)
        if not on and ema >= enter:
            on = True
        if on and ema <= exit:
            on = False
        smoothed[i], active[i] = ema, on
    return smoothed, active
