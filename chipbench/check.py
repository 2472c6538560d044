"""The comparison that decides ``correct``.

After the window has closed and the engine is gone, a sample of streams and
a sample of single windows from all streams are drawn from the seed (the
second reaches every slot of every block).  Every window the timed path
scored on those streams, and each sampled window, is rebuilt from the
traffic's audio and run through the configuration's plain reference, in
blocks of rows.  Three numbers are compared, each with the
limit the configuration file states:

* ``logit_gap`` -- the widest gap between the program's and the reference's
  log-odds of "UAV" (each clipped to +-12, where float32 probabilities still
  resolve them), in units of 1 + the reference's spread (standard deviation)
  of log-odds over the compared windows.  It covers the on-device front-end,
  the int8 conv and dense kernels, the float layers and the CORDIC softmax.
* ``tracker_gap`` -- the widest gap between the smoothed score the engine
  returned and the reference tracker's, fed the same probabilities; a window
  whose active flag differs counts 1.
* ``unscored`` -- windows completed on any stream that the engine never
  answered (or answered twice, or out of order).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

LOGODDS_CLIP = 12.0
WINDOW = 12_800
BLOCK_ROWS = 256


def log_odds(p) -> np.ndarray:
    p = np.clip(np.asarray(p, np.float64), 1e-300, 1.0)
    with np.errstate(divide="ignore"):
        lo = np.log(p) - np.log1p(-p)
    return np.clip(lo, -LOGODDS_CLIP, LOGODDS_CLIP)


def logit_gap(p, p_ref) -> float:
    lo, lo_ref = log_odds(p), log_odds(p_ref)
    if not len(lo):
        return 0.0
    return float(np.max(np.abs(lo - lo_ref)) / (1.0 + np.std(lo_ref)))


def unscored(scores: dict, pushed: np.ndarray) -> int:
    """Windows completed (``pushed`` samples per stream) that did not come
    back exactly once each, in order, among the scores."""
    order = np.lexsort((scores["idx"], scores["stream"]))
    s, i = scores["stream"][order], scores["idx"][order]
    pos = np.arange(len(s)) - np.searchsorted(s, s)  # place within its stream
    good = np.bincount(s[i == pos], minlength=len(pushed))
    answers = np.bincount(s, minlength=len(pushed))
    done = pushed // WINDOW
    return int(np.sum(np.maximum(done - good, 0) + (answers - good)))


def sample_streams(n_streams: int, n: int, seed: int) -> np.ndarray:
    """Streams to check, drawn from all of them: one the engine never
    answered is as likely to be drawn as any other."""
    rng = np.random.default_rng([seed, 2])
    return np.sort(rng.choice(n_streams, size=min(n, n_streams), replace=False))


def reference_p(cell, params, windows: np.ndarray, modes: dict) -> np.ndarray:
    """Probability of "UAV" from the configuration's reference (its
    ``p_uav``), in blocks of rows at float32 ``highest``."""
    out = []
    for i in range(0, len(windows), BLOCK_ROWS):
        blk = windows[i : i + BLOCK_ROWS]
        n = len(blk)
        if n < BLOCK_ROWS:
            blk = np.concatenate([blk, np.repeat(blk[-1:], BLOCK_ROWS - n, axis=0)])
        with jax.default_matmul_precision("highest"):
            p = cell.reference.p_uav(params, jnp.asarray(blk), cell.config, modes)
            out.append(np.asarray(p)[:n])
    return np.concatenate(out).astype(np.float64) if out else np.zeros(0)


def gather(cell, pool, scores: dict, pushed: np.ndarray, seed: int):
    """The sampled streams' windows answered in order, as raw audio and
    program scores, then single windows drawn from every answer."""
    streams = sample_streams(len(pushed), cell.traffic["check_streams"], seed)
    win, p, sm, act, per = [], [], [], [], []
    for s in streams:
        sel = scores["stream"] == s
        order = np.argsort(scores["idx"][sel], kind="stable")
        idx = scores["idx"][sel][order]
        good = int(np.argmin(np.append(idx == np.arange(len(idx)), False)))  # leading run
        win.append(pool.windows(int(s), 0, good))
        p.append(scores["p"][sel][order][:good])
        sm.append(scores["smoothed"][sel][order][:good])
        act.append(scores["active"][sel][order][:good])
        per.append(good)
    cat = (lambda xs: np.concatenate(xs) if xs else np.zeros(0))
    # single windows drawn from every scored window, for the forward alone
    rng = np.random.default_rng([seed, 3])
    pick = rng.choice(len(scores["p"]), size=min(cell.traffic["check_windows"], len(scores["p"])),
                      replace=False)
    extra = [pool.windows(int(scores["stream"][i]), int(scores["idx"][i]), 1) for i in pick]
    return dict(streams=streams, windows=np.concatenate(win + extra) if win or extra
                else np.zeros((0, WINDOW), np.float32),
                p=cat(p + [scores["p"][pick]]), smoothed=cat(sm), active=cat(act), per_stream=per)


def tracker_gap(ref, tracker: dict, g: dict, p_in: np.ndarray) -> float:
    """Widest gap of the reference tracker, fed ``p_in``, from the engine's
    smoothed scores; a differing active flag counts 1."""
    worst, i = 0.0, 0
    for n in g["per_stream"]:
        sm, act = ref.track(p_in[i : i + n], ema_alpha=tracker["ema_alpha"],
                            enter=tracker["enter_threshold"], exit=tracker["exit_threshold"])
        if n:
            gap = np.abs(sm.astype(np.float64) - g["smoothed"][i : i + n])
            gap = np.where(act != g["active"][i : i + n], 1.0, gap)
            worst = max(worst, float(gap.max()))
        i += n
    return worst


def compare(cell, params, pool, scores: dict, pushed: np.ndarray, seed: int,
            control: dict | None = None) -> dict:
    """(``{number: {"value": ..., "limit": ...}}``, windows compared) for the
    program, or, given ``control`` (a precision per layer and ``front_end``),
    for the reference computed so in its place."""
    ref = cell.reference
    limits = cell.config["limits"]
    g = gather(cell, pool, scores, pushed, seed)
    p_ref = reference_p(cell, params, g["windows"],
                        {k: "fp32" for k in cell.config["stated_precision"]})
    tracker = cell.config["engine"]["tracker"]
    if control is not None:
        p_ctl = reference_p(cell, params, g["windows"], control)
        values = {"logit_gap": logit_gap(p_ctl, p_ref),
                  "tracker_gap": _control_tracker_gap(ref, tracker, g),
                  "unscored": unscored(scores, pushed)}
    else:
        values = {"logit_gap": logit_gap(g["p"], p_ref),
                  "tracker_gap": tracker_gap(ref, tracker, g, g["p"]),
                  "unscored": unscored(scores, pushed)}
    return {k: {"value": float(v), "limit": float(limits[k])} for k, v in values.items()}, len(g["p"])


def _control_tracker_gap(ref, tracker: dict, g: dict) -> float:
    """The float32 tracker fed the engine's probabilities, against the float64
    reference tracker fed the same."""
    worst, i = 0.0, 0
    for n in g["per_stream"]:
        kw = dict(ema_alpha=tracker["ema_alpha"], enter=tracker["enter_threshold"],
                  exit=tracker["exit_threshold"])
        s64, a64 = ref.track(g["p"][i : i + n], dtype=np.float64, **kw)
        s32, a32 = ref.track(g["p"][i : i + n], dtype=np.float32, **kw)
        if n:
            gap = np.where(a32 != a64, 1.0, np.abs(s32.astype(np.float64) - s64))
            worst = max(worst, float(gap.max()))
        i += n
    return worst
