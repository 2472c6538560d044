"""Per-window operations of a detector configuration, layer by layer.

The layers are those of the network as served (after the configuration's
prune); each carries the precision the configuration states for it, which
picks the peak it is judged against.  The DSP front-end is not counted.
"""
from __future__ import annotations

from chipbench import catalog

#: peak of peaks.json each stated precision runs at (fp32 rides the bf16 MXU)
PEAK_KEY = {"int8": "int8_ops_per_s", "fxp8": "int8_ops_per_s",
            "bf16": "bf16_flops_per_s", "fp32": "bf16_flops_per_s"}


def layers(config: dict) -> list[tuple[str, int, str]]:
    """``[(layer, operations per window, stated precision)]``."""
    conv = catalog.kernel("conv")
    matmul = catalog.kernel("matmul")
    model = config["model"]
    prec = config["stated_precision"]
    prune = config["bake"].get("prune")
    channels = list(model["channels"])
    if prune:
        channels[-1] = prune["keep"]
    out = []
    length, c_in = model["input_len"], 1
    for i, c_out in enumerate(channels):
        out.append((f"conv{i}", conv.ops(1, length, model["kernel"], c_in, c_out), prec[f"conv{i}"]))
        length //= 2
        c_in = c_out
    frames = length - (prune["trim_frames"] if prune else 0)
    out.append(("dense0", matmul.ops(1, frames * c_in, model["hidden"]), prec["dense0"]))
    out.append(("dense1", matmul.ops(1, model["hidden"], model["n_classes"]), prec["dense1"]))
    return out


def ops_per_window(config: dict) -> int:
    return sum(n for _, n, _ in layers(config))


def peak_seconds_per_window(config: dict, peaks: dict) -> float:
    """Least chip time one window's layers need at the published peaks."""
    return sum(n / peaks[PEAK_KEY[mode]] for _, n, mode in layers(config))
