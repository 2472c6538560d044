"""Synthetic microphone audio for the load generator.

A copy of the repository's scene synthesis (quadrotor blade-pass harmonic
stacks against six background classes, Gaussian noise at 8-20 dB SNR, one
UAV pass per scene), kept with the benchmark so that a change to the
program's data module cannot move the yardstick.  Everything is drawn from
the ``numpy.random.Generator`` it is given.
"""
from __future__ import annotations

import numpy as np

SR = 16_000
WINDOW = 12_800  # 0.8 s


def _onepole(x: np.ndarray, alpha: float) -> np.ndarray:
    """One-pole lowpass as a convolution with the kernel truncated at 1e-4."""
    k = int(np.ceil(np.log(1e-4) / np.log(max(alpha, 1e-6))))
    k = max(1, min(k, 512))
    return np.convolve(x, (1.0 - alpha) * alpha ** np.arange(k))[: len(x)]


def _chirp(t, f0, f1, dur_frac, rng):
    n = len(t)
    start = rng.integers(0, max(1, int(n * (1 - dur_frac))))
    length = int(n * dur_frac)
    seg = np.zeros(n)
    f = np.linspace(f0, f1, length)
    seg[start : start + length] = np.sin(2 * np.pi * np.cumsum(f) / SR) * np.hanning(length)
    return seg


def uav(rng: np.random.Generator) -> np.ndarray:
    """One 0.8 s quadrotor window: 2-4 detuned motors, AM, FM wander, hiss."""
    t = np.arange(WINDOW) / SR
    base_rps = rng.uniform(45.0, 110.0)
    sig = np.zeros_like(t)
    for _ in range(rng.integers(2, 5)):
        bpf = 2 * base_rps * rng.uniform(0.96, 1.04)
        fm = 1.0 + 0.01 * rng.uniform(0.2, 1.0) * np.cumsum(
            rng.standard_normal(WINDOW)
        ) / np.sqrt(np.arange(1, WINDOW + 1)) / 8.0
        phase = 2 * np.pi * np.cumsum(bpf * fm) / SR
        decay = rng.uniform(0.6, 1.2)
        for k in range(1, int(min(20, (SR / 2 - 100) / bpf)) + 1):
            amp = k ** (-decay) * rng.uniform(0.7, 1.3)
            sig += amp * np.sin(k * phase + rng.uniform(0, 2 * np.pi))
    sig *= 1.0 + rng.uniform(0.05, 0.3) * np.sin(2 * np.pi * rng.uniform(1, 8) * t)
    hiss = np.diff(rng.standard_normal(WINDOW + 1))
    sig += rng.uniform(0.05, 0.25) * np.abs(sig).mean() / (np.abs(hiss).mean() + 1e-9) * hiss
    lp = _onepole(sig, rng.uniform(0.2, 0.95))
    return (lp / (np.std(lp) + 1e-9)).astype(np.float32)


def background(rng: np.random.Generator) -> np.ndarray:
    """One 0.8 s non-UAV window: wind, birds, aircraft, traffic, ambience or a
    generator whose harmonics overlap the rotor band."""
    t = np.arange(WINDOW) / SR
    kind = rng.integers(0, 6)
    if kind == 0:
        w = rng.standard_normal(WINDOW)
        sig = _onepole(w, 0.97) * 8.0 + 0.1 * w
    elif kind == 1:
        sig = 0.05 * rng.standard_normal(WINDOW)
        for _ in range(rng.integers(1, 4)):
            f0 = rng.uniform(2000, 5000)
            sig += _chirp(t, f0, f0 * rng.uniform(0.7, 1.4), rng.uniform(0.05, 0.2), rng)
    elif kind == 2:
        f0 = rng.uniform(25.0, 70.0)
        sig = np.zeros_like(t)
        for k in range(1, 12):
            sig += k ** rng.uniform(-1.6, -0.9) * np.sin(2 * np.pi * k * f0 * t + rng.uniform(0, 6.28))
        sig += _onepole(rng.standard_normal(WINDOW), 0.995) * 15.0
    elif kind == 3:
        sig = _onepole(rng.standard_normal(WINDOW), 0.99) * 10.0
        sig += 0.3 * np.sin(2 * np.pi * rng.uniform(80, 120) * t)
    elif kind == 4:
        sig = 0.3 * _onepole(rng.standard_normal(WINDOW), 0.9)
    else:
        f0 = rng.uniform(80.0, 200.0)
        fm = 1.0 + 0.005 * np.cumsum(rng.standard_normal(WINDOW)) / np.sqrt(
            np.arange(1, WINDOW + 1)
        )
        phase = 2 * np.pi * np.cumsum(f0 * fm) / SR
        sig = np.zeros_like(t)
        decay = rng.uniform(0.7, 1.3)
        for k in range(1, int(min(18, (SR / 2 - 100) / f0)) + 1):
            sig += k ** (-decay) * np.sin(k * phase + rng.uniform(0, 6.28))
        sig *= 1.0 + rng.uniform(0.05, 0.25) * np.sin(2 * np.pi * rng.uniform(1, 6) * t)
        sig += 0.1 * _onepole(rng.standard_normal(WINDOW), 0.9)
        sig = _onepole(sig, rng.uniform(0.1, 0.8))
    return (sig / (np.std(sig) + 1e-9)).astype(np.float32)


def _with_noise(x: np.ndarray, snr_db: float, rng: np.random.Generator) -> np.ndarray:
    p_noise = np.mean(x**2) / (10.0 ** (snr_db / 10.0))
    return x + rng.standard_normal(len(x)).astype(np.float32) * np.sqrt(p_noise)


def scene(n_windows: int, rng: np.random.Generator) -> np.ndarray:
    """One stream's clip: background with one UAV pass of 3 or more windows."""
    if n_windows >= 6:
        on = int(rng.integers(1, n_windows - 4))
        off = int(min(n_windows - 1, on + rng.integers(3, max(4, n_windows // 2))))
    else:
        on, off = 0, n_windows
    wins = [
        _with_noise(uav(rng) if on <= i < off else background(rng), float(rng.uniform(8, 20)), rng)
        for i in range(n_windows)
    ]
    return np.concatenate(wins).astype(np.float32)


class ScenePool:
    """``n_clips`` scenes of ``clip_windows`` windows; stream ``s`` reads clip
    ``clip[s]`` cyclically from sample ``offset[s]``.  ``samples`` returns any
    span of a stream's audio, so a window can be rebuilt after the run."""

    def __init__(self, n_streams: int, n_clips: int, clip_windows: int, max_chunk: int,
                 rng: np.random.Generator):
        self.clips = [scene(clip_windows, rng) for _ in range(n_clips)]
        self.length = clip_windows * WINDOW
        # each clip followed by its own head, so a chunk never wraps mid-copy
        self._tiled = [np.concatenate([c, c[:max_chunk]]) for c in self.clips]
        self.clip = rng.integers(0, n_clips, n_streams)
        self.offset = rng.integers(0, self.length, n_streams)

    def chunk(self, stream: int, start: int, n: int) -> np.ndarray:
        """Samples ``[start, start + n)`` of ``stream``, as a view (n <= max_chunk)."""
        o = (self.offset[stream] + start) % self.length
        return self._tiled[self.clip[stream]][o : o + n]

    def windows(self, stream: int, first: int, count: int) -> np.ndarray:
        """Windows ``first .. first + count - 1`` of ``stream``, (count, WINDOW)."""
        idx = (self.offset[stream] + first * WINDOW + np.arange(count * WINDOW)) % self.length
        return self.clips[self.clip[stream]][idx].reshape(count, WINDOW)
