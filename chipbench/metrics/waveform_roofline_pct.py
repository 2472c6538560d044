"""Share of its roofline that the HuBERT verifier's waveform conv stack
reaches: the least chip time of its operations per window (the family's
``waveform`` layer) and of the bytes its convs must move (inputs, weights
and float32 outputs once each, the weights once per block), the larger of
operations over the bf16 peak and bytes over HBM bandwidth, over the device
microseconds per window of the ``frontend`` and ``waveform`` scopes."""
from pathlib import Path

import jax

from chipbench import catalog, scopes

ROOT = Path(__file__).resolve().parents[2]


def read(r):
    us = scopes.us_per_window(r, r"frontend|waveform")
    if not us:
        return None
    conv = catalog.kernel("strided_conv", ROOT)
    m = r.cell.config["model"]
    batch = r.cell.config["engine"]["batch_slots_per_chip"]
    moved, length, c_in = 0, m["input_len"], 1
    for c, k, s in zip(m["conv_dim"], m["conv_kernel"], m["conv_stride"]):
        out = conv.out_len(length, k, s)
        moved += conv.bytes_moved(batch, length, out, k, c_in, c)
        length, c_in = out, c
    ops = sum(n for name, n, _ in r.cell.family.layers(r.cell.config) if name == "waveform")
    peaks = catalog.peaks(jax.devices()[0].device_kind, ROOT)
    least_s = max(ops / peaks["bf16_flops_per_s"], moved / batch / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (us * 1e-6)
