"""JAX twin of the numpy DSP front-end (:mod:`repro.data.features`).

The serving path fuses feature extraction into the jitted accelerator
program: ``accelerator_forward(..., raw_windows=True)`` takes raw
``(B, 12800)`` audio windows and the first in-graph stage is this module's
:func:`feature_rows`.  All constant operands (Hann windows, frame-gather
indices, mel filterbank, DCT-II matrix, Welch segment window) are built once
per feature kind in numpy and closed over as jit constants — tracing never
rebuilds them.

Two numerical contracts, deliberately different in strength:

* **numpy vs JAX is tolerance-bounded, NOT bitwise.**  The numpy path
  (:func:`repro.data.features.feature_vector`) is the float64 oracle; this
  path computes in float32 on-device.  ``PARITY_ATOL`` documents the
  per-kind bound the parity tests enforce.

* **within the JAX path, row i is bitwise independent of its co-batch.**
  Every op in the pipeline is either batched with strictly per-row
  arithmetic — framing/gather, windowing, FFT (each 1-D transform is an
  independent computation; no cross-transform arithmetic exists),
  elementwise math, and order-free reductions (max, counts) — or is pinned
  where the compiler would choose an association that follows the batch
  shape: the two projections (mel filterbank and DCT-II: XLA gemm blocking
  reassociates the contraction as the M dimension grows, measurably on CPU,
  and ``vmap``-ed batched gemm re-blocks the same way) run under
  ``jax.lax.map`` so each row gets the identical fixed-shape matmul, and
  every float mean is a fixed pairwise tree of slice adds
  (:func:`_pairwise_mean`; on a TPU v5e a reduce op's association followed
  the batch shape).  The streaming == batched == sharded conformance
  guarantee needs feature bits that survive re-batching and shard-local
  recomputation; tests/test_features_jax.py pins the property across batch
  sizes, permutations and silence padding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.features import (
    FEATURE_DIMS,
    HOP,
    N_FFT,
    N_SAMPLES,
    WAVEFORM_EPS,
    dct_ii,
    mel_filterbank,
)

#: per-kind max-abs-deviation bound of the float32 JAX path against the
#: float64 numpy oracle, on unit-RMS-normalised feature vectors (enforced
#: with margin by tests/test_features_jax.py).  The bound covers real audio
#: windows; a degenerate all-constant window (e.g. exact silence) normalises
#: to 0 in float64 but to an arbitrary finite constant in float32 — the
#: engine discards those (dead-slot) outputs, so only finiteness holds there.
PARITY_ATOL = {
    "mfcc20": 5e-3,
    "mel128": 5e-3,
    "psd": 5e-3,
    "zcr": 1e-4,
    "waveform": 1e-4,
}


@functools.lru_cache(maxsize=8)
def _hann32(n: int) -> np.ndarray:
    return np.hanning(n).astype(np.float32)


@functools.lru_cache(maxsize=8)
def _frame_idx(n_samples: int, n_fft: int, hop: int) -> np.ndarray:
    """Gather indices into the centre-padded signal: (frames, n_fft)."""
    n_frames = 1 + n_samples // hop
    return np.arange(n_fft)[None, :] + hop * np.arange(n_frames)[:, None]


@functools.lru_cache(maxsize=8)
def _mel32(n_mels: int) -> np.ndarray:
    """(bins, n_mels) float32 mel projection (transposed for right-matmul)."""
    return mel_filterbank(n_mels).astype(np.float32).T


@functools.lru_cache(maxsize=8)
def _dct32(n_out: int, n_in: int) -> np.ndarray:
    """(n_in, n_out) float32 DCT-II projection (transposed)."""
    return dct_ii(n_out, n_in).astype(np.float32).T


# ---------------------------------------------------------------------------
# Batched DSP with strictly per-row arithmetic (leading axis = batch)
# ---------------------------------------------------------------------------


def _pairwise_mean(v: jax.Array, axis: int = -1) -> jax.Array:
    """Mean over ``axis``, summed as a fixed tree of slice adds.

    A reduce op's association is the compiler's to choose, and on the TPU it
    follows the layout XLA picks for the whole batched array: on a v5e the
    Welch, pooled-mel, PSD-band, ZCR-statistic and normalisation means of one
    row rounded differently in batches of 1, 16 and 128.  Elementwise adds of
    fixed slices have one association on every backend and at every batch
    size, and cost about the same there.
    """
    v = jnp.moveaxis(v, axis, -1)
    n = v.shape[-1]
    while v.shape[-1] > 1:
        h = v.shape[-1] // 2
        v = jnp.concatenate([v[..., :h] + v[..., h : 2 * h], v[..., 2 * h :]], axis=-1)
    return v[..., 0] / n


def _project_rows(x: jax.Array, m: np.ndarray) -> jax.Array:
    """(B, F, K) @ (K, M) -> (B, F, M) with per-row-bitwise guarantees.

    Where the batched formulation would leak across rows: XLA lowers
    both ``reshape+matmul`` and a ``vmap``-ed matmul to gemms whose blocking
    (and therefore contraction association) changes with the batched M
    dimension.  ``lax.map`` pins each row to the identical (F, K) @ (K, M)
    gemm instead; the projections are small (<2 MFLOP/row), so the scan cost
    is noise next to the batched FFTs.  HIGHEST holds the fp32 contract on
    the TPU, whose default matmul precision is one bf16 pass.
    """
    return jax.lax.map(
        lambda q: jnp.matmul(q, m, precision=jax.lax.Precision.HIGHEST), x
    )


def _stft_power(x: jax.Array, n_fft: int = N_FFT, hop: int = HOP) -> jax.Array:
    """(B, n) -> (B, frames, n_fft//2+1) power spectrogram.

    ``re^2 + im^2`` rather than ``abs(z)^2``: same quantity without the
    hypot/sqrt round-trip (the float64 oracle keeps numpy's ``abs**2``; the
    difference is far inside PARITY_ATOL).
    """
    pad = n_fft // 2
    xp = jnp.pad(x, ((0, 0), (pad, pad)), mode="reflect")
    frames = xp[:, _frame_idx(x.shape[1], n_fft, hop)] * _hann32(n_fft)
    spec = jnp.fft.rfft(frames, axis=-1)
    return spec.real**2 + spec.imag**2


def _melspectrogram(x: jax.Array, n_mels: int) -> jax.Array:
    """(B, n) -> (B, frames, n_mels) log-mel energies."""
    return jnp.log10(_project_rows(_stft_power(x), _mel32(n_mels)) + 1e-10)


def _mfcc(x: jax.Array, n_mfcc: int = 20, n_mels: int = 64) -> jax.Array:
    return _project_rows(_melspectrogram(x, n_mels), _dct32(n_mfcc, n_mels))


def _welch_psd(x: jax.Array, n_bins: int = 512) -> jax.Array:
    seg = 2 * n_bins
    n_seg = x.shape[1] // seg
    segs = x[:, : n_seg * seg].reshape(-1, n_seg, seg) * _hann32(seg)
    spec = jnp.fft.rfft(segs, axis=-1)
    p = _pairwise_mean(spec.real**2 + spec.imag**2, axis=1)[:, :n_bins]
    return jnp.log10(p + 1e-10)


def _zcr(x: jax.Array, n_frames: int = 128) -> jax.Array:
    hop = x.shape[1] // n_frames
    frames = x[:, : n_frames * hop].reshape(-1, n_frames, hop)
    signs = jnp.sign(frames)
    signs = jnp.where(signs == 0, 1.0, signs)
    return jnp.mean(jnp.abs(jnp.diff(signs, axis=2)) > 0, axis=2)


def _normalize(v: jax.Array) -> jax.Array:
    """Zero-mean, unit-RMS (paper §IV-A), per row."""
    v = v - _pairwise_mean(v)[:, None]
    rms = jnp.sqrt(_pairwise_mean(v**2))[:, None]
    return v / (rms + 1e-8)


def _feature_batch(x: jax.Array, kind: str) -> jax.Array:
    """(B, n_samples) raw windows -> (B, FEATURE_DIMS[kind]).

    Mirrors :func:`repro.data.features.feature_vector` op for op, in float32.
    """
    bsz = x.shape[0]
    if kind == "waveform":
        x = x - _pairwise_mean(x)[:, None]
        return x / jnp.sqrt(_pairwise_mean(x**2) + WAVEFORM_EPS)[:, None]
    peak = jnp.max(jnp.abs(x), axis=1, keepdims=True) + 1e-9
    x = x / peak
    if kind == "mfcc20":
        m = _mfcc(x, 20)[:, :51].reshape(bsz, -1)
        pooled = _pairwise_mean(_melspectrogram(x, 64), axis=1)
        p = _welch_psd(x, 512)
        p10 = _pairwise_mean(p[:, :510].reshape(bsz, 10, 51))
        z = _zcr(x)
        zm = _pairwise_mean(z)
        zs = jnp.sqrt(_pairwise_mean((z - zm[:, None]) ** 2))
        aux = jnp.stack([zm, zs], axis=1)
        v = jnp.concatenate([m, pooled, p10, aux], axis=1)
    elif kind == "mel128":
        logmel = _melspectrogram(x, 128)[:, :48]
        v = _pairwise_mean(logmel.reshape(bsz, 8, 6, 128), axis=2).reshape(bsz, -1)
    elif kind == "psd":
        v = _welch_psd(x, 512)
    elif kind == "zcr":
        v = _zcr(x, 128)
    else:
        raise ValueError(f"unknown feature kind {kind!r}")
    return _normalize(v)


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


def feature_rows(windows: jax.Array, kind: str) -> jax.Array:
    """(B, n_samples) raw windows -> (B, M) features, traceable in-graph.

    This is the stage ``accelerator_forward(..., raw_windows=True)`` fuses in
    front of the quantised datapath.  Row i's bits cannot depend on the batch
    it rode in with (see module docstring).
    """
    if kind not in FEATURE_DIMS:
        raise ValueError(f"unknown feature kind {kind!r}")
    return _feature_batch(windows.astype(jnp.float32), kind)


@functools.partial(jax.jit, static_argnames=("kind",))
def batch_features_jax(windows: jax.Array, kind: str = "mfcc20") -> jax.Array:
    """Standalone jitted batched front-end (the host-callable twin of
    :func:`repro.data.features.batch_features`)."""
    return feature_rows(windows, kind)
