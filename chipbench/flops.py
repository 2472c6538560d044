"""Per-window operations of a configuration, layer by layer.

The layers, with the operations each needs per window and the precision
the configuration states for it, come from the configuration's family
module (``cell.family.layers(config)``); the stated precision picks the peak
a layer is judged against.
"""
from __future__ import annotations

#: peak of peaks.json each stated precision runs at (fp32 rides the bf16 MXU)
PEAK_KEY = {"int8": "int8_ops_per_s", "fxp8": "int8_ops_per_s",
            "bf16": "bf16_flops_per_s", "fp32": "bf16_flops_per_s"}


def ops_per_window(layers: list[tuple[str, int, str]]) -> int:
    """Operations of one window over ``[(layer, operations, stated precision)]``."""
    return sum(n for _, n, _ in layers)


def peak_seconds_per_window(layers: list[tuple[str, int, str]], peaks: dict) -> float:
    """Least chip time one window's layers need at the published peaks."""
    return sum(n / peaks[PEAK_KEY[mode]] for _, n, mode in layers)
