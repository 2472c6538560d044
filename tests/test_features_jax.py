"""On-device DSP front-end (repro.data.features_jax): parity + properties.

Two different contracts are pinned here, and they are deliberately of
different strength:

* **numpy vs JAX parity is tolerance-bounded, NOT bitwise.**  The numpy
  front-end is the float64 oracle; the JAX twin computes in float32 on the
  device.  Each feature kind gets an explicit max-abs-deviation bound
  (``features_jax.PARITY_ATOL``) on the unit-RMS-normalised vectors.  Do not
  "fix" these tests by asserting bitwise equality — it cannot and should not
  hold across the float64/float32 boundary.

* **within the JAX path, feature bits are per-row.**  Row i of the output is
  bitwise-unchanged by co-batch permutation, silence padding, and batch-size
  changes (``lax.map`` gives every projection row an identical fixed-shape
  program, and every float mean is a fixed pairwise tree).
  This is the property the serving layer's streaming == batched == sharded
  guarantee rests on once the front-end is fused into the jitted program.

The standard DSP identities (Parseval, filterbank partition of unity, DCT
orthonormality) are re-run here against the JAX path's float32 constants.
"""
import zlib

import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # bare container: deterministic-example fallback shim
    from _hypothesis_fallback import given, settings, st

from repro.data import acoustic, features, features_jax

KINDS = sorted(features.FEATURE_DIMS)


def _windows(n: int, seed: int, loudness_spread: bool = True) -> np.ndarray:
    """Mixed test corpus: noise, synthetic UAV, background — with a 10^4
    loudness spread (the micro-batching failure mode)."""
    rng = np.random.default_rng(seed)
    rows = []
    for i in range(n):
        if i % 3 == 0:
            w = acoustic.synth_uav(rng)
        elif i % 3 == 1:
            w = acoustic.synth_background(rng)
        else:
            w = rng.standard_normal(features.N_SAMPLES)
        rows.append(np.asarray(w, np.float32))
    x = np.stack(rows)
    if loudness_spread:
        x *= (10.0 ** rng.uniform(-2, 2, size=(n, 1))).astype(np.float32)
    return x


# ---------------------------------------------------------------------------
# numpy (float64 oracle) vs JAX (float32) — tolerance-bounded parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", KINDS)
def test_numpy_vs_jax_parity_tolerance(kind):
    """Per-kind tolerance bound of the float32 JAX path against the float64
    numpy oracle.  Tolerance, not bitwise — see module docstring."""
    w = _windows(6, seed=zlib.crc32(kind.encode()))  # deterministic per kind
    ref = features.batch_features(w, kind).astype(np.float64)
    got = np.asarray(features_jax.batch_features_jax(w, kind)).astype(np.float64)
    assert got.shape == ref.shape == (6, features.FEATURE_DIMS[kind])
    dev = np.abs(ref - got).max()
    assert dev < features_jax.PARITY_ATOL[kind], (
        f"{kind}: max|numpy - jax| = {dev:.3e} exceeds the documented "
        f"bound {features_jax.PARITY_ATOL[kind]:.0e}"
    )
    assert np.isfinite(got).all()


def test_silence_window_is_finite_not_parity():
    """The dead-slot padding case: an all-zero window must produce finite
    features on both paths (the in-graph front-end sees padded silence).

    Deliberately NOT a parity check: silence yields a *constant* raw feature
    vector, which zero-mean/unit-RMS normalisation maps to exactly 0 in the
    float64 oracle but — via the float32 mean's rounding residue, amplified
    by the 1/rms — to an arbitrary finite constant on the JAX path.  The
    engine discards dead-slot outputs, so finiteness is the whole contract
    here (PARITY_ATOL applies to real audio windows, which peak-normalise to
    a non-degenerate vector)."""
    z = np.zeros((1, features.N_SAMPLES), np.float32)
    for kind in KINDS:
        ref = features.batch_features(z, kind)
        got = np.asarray(features_jax.batch_features_jax(z, kind))
        assert np.isfinite(ref).all() and np.isfinite(got).all()


# ---------------------------------------------------------------------------
# DSP identities, re-run on the JAX path's constants/ops
# ---------------------------------------------------------------------------


def test_jax_mel_partition_of_unity():
    """Each float32 mel filter keeps unit area after the cast+transpose."""
    fb_t = features_jax._mel32(64)  # (bins, n_mels)
    assert fb_t.shape == (features.N_FFT // 2 + 1, 64)
    np.testing.assert_allclose(fb_t.sum(axis=0), 1.0, atol=1e-5)


def test_jax_dct_orthonormal():
    """The float32 DCT-II constant stays orthonormal to float32 precision."""
    d_t = features_jax._dct32(20, 64)  # (n_in, n_out), transposed
    np.testing.assert_allclose(d_t.T @ d_t, np.eye(20), atol=1e-5)


def test_jax_stft_parseval():
    """Parseval on the JAX STFT: per frame, the one-sided power spectrum
    (doubling the interior bins) equals N_FFT x the windowed-frame energy."""
    import jax.numpy as jnp

    rng = np.random.default_rng(1)
    x = rng.standard_normal(features.N_SAMPLES).astype(np.float32)
    p = np.asarray(features_jax._stft_power(jnp.asarray(x[None, :])))[0]
    assert p.shape[0] == 1 + features.N_SAMPLES // features.HOP
    assert (p >= 0).all()
    # reference windowed frames, same gather + window constants
    idx = features_jax._frame_idx(features.N_SAMPLES, features.N_FFT, features.HOP)
    xp = np.pad(x, (features.N_FFT // 2,) * 2, mode="reflect")
    frames = xp[idx] * features_jax._hann32(features.N_FFT)[None, :]
    energy = (frames.astype(np.float64) ** 2).sum(axis=1)
    one_sided = p[:, 0] + p[:, -1] + 2.0 * p[:, 1:-1].sum(axis=1)
    np.testing.assert_allclose(one_sided, features.N_FFT * energy, rtol=1e-4)


def test_jax_zcr_pure_tone_vs_noise():
    import jax.numpy as jnp

    t = np.arange(features.N_SAMPLES) / features.SR
    tone = np.sin(2 * np.pi * 100 * t).astype(np.float32)
    noise = np.random.default_rng(2).standard_normal(features.N_SAMPLES)
    z_tone = np.asarray(features_jax._zcr(jnp.asarray(tone[None, :])))
    z_noise = np.asarray(features_jax._zcr(jnp.asarray(noise[None, :], dtype=np.float32)))
    assert z_tone.mean() < z_noise.mean()


def test_rejects_unknown_kind():
    w = np.zeros((1, features.N_SAMPLES), np.float32)
    with pytest.raises(ValueError, match="unknown feature kind"):
        features_jax.feature_rows(w, "spectrogram2d")


# ---------------------------------------------------------------------------
# Row independence: feature bits never depend on the co-batch
# ---------------------------------------------------------------------------


def _assert_row_independent(batch: int, seed: int):
    """For every kind, row i's feature vector is bitwise-unchanged by
    (a) co-batch permutation, (b) silence padding to a larger batch, and
    (c) extraction at a different batch size."""
    w = _windows(batch, seed=seed)
    for kind in KINDS:
        base = np.asarray(features_jax.batch_features_jax(w, kind))
        rng = np.random.default_rng(seed + 1)
        perm = rng.permutation(batch)
        permuted = np.asarray(features_jax.batch_features_jax(w[perm], kind))
        np.testing.assert_array_equal(base[perm], permuted, err_msg=f"{kind} perm")
        padded_in = np.concatenate(
            [w, np.zeros((2, features.N_SAMPLES), np.float32)]
        )
        padded = np.asarray(features_jax.batch_features_jax(padded_in, kind))
        np.testing.assert_array_equal(base, padded[:batch], err_msg=f"{kind} pad")
        solo = np.asarray(features_jax.batch_features_jax(w[:1], kind))
        np.testing.assert_array_equal(base[:1], solo, err_msg=f"{kind} batch-of-1")


def test_row_independence_smoke():
    """Fast-tier leg of the row-independence guarantee: one deterministic
    batch, all kinds, all three co-batch transformations."""
    _assert_row_independent(batch=4, seed=7)


@pytest.mark.slow
@settings(deadline=None, max_examples=8)
@given(st.integers(2, 6), st.integers(0, 2**16))
def test_row_independence_property(batch, seed):
    """Property form over random batch sizes/content (each example compiles
    fresh batch shapes for every kind — full-tier only)."""
    _assert_row_independent(batch, seed)


@pytest.mark.parametrize(
    "shape,axis",
    # every (length, axis) the front-end averages over, odd lengths included
    [((3, 1), -1), ((2, 51), -1), ((2, 12, 513), 1), ((2, 8, 6, 128), 2), ((4, 1096), -1)],
)
def test_pairwise_mean_is_the_mean(shape, axis):
    x = np.random.default_rng(3).standard_normal(shape).astype(np.float32)
    got = np.asarray(features_jax._pairwise_mean(jnp.asarray(x), axis=axis))
    want = x.astype(np.float64).mean(axis=axis)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)


def test_numpy_oracle_constants_are_cached():
    """The oracle path's constants are built once, not per window
    (mirroring mel_filterbank's cache)."""
    assert features.dct_ii(20, 64) is features.dct_ii(20, 64)
    assert features._hann(features.N_FFT) is features._hann(features.N_FFT)
    assert features._hann(1024) is not features._hann(512)
