"""Share of its roofline that the HuBERT verifier's encoder reaches: the
least chip time of its operations per window (the family's ``featproj``,
``posconv``, ``attn`` and ``ffn`` layers) and of the bytes its matmuls and
convs must move (operands and float32 results once each, Q/K/V as one
matmul, the weights once per block), the larger of operations over the bf16
peak and bytes over HBM bandwidth, over the device microseconds per window
of those scopes."""
from pathlib import Path

import jax

from chipbench import catalog, scopes

ROOT = Path(__file__).resolve().parents[2]

LAYERS = ("featproj", "posconv", "attn", "ffn")


def read(r):
    us = scopes.us_per_window(r, "|".join(LAYERS))
    if not us:
        return None
    conv, mm = catalog.kernel("strided_conv", ROOT), catalog.kernel("matmul", ROOT)
    m = r.cell.config["model"]
    batch = r.cell.config["engine"]["batch_slots_per_chip"]
    t = m["input_len"]
    for k, s in zip(m["conv_kernel"], m["conv_stride"]):
        t = conv.out_len(t, k, s)
    d, f, heads = m["hidden_size"], m["intermediate_size"], m["num_attention_heads"]
    k_pos, dh, rows = m["num_conv_pos_embeddings"], d // heads, batch * t
    layer = (mm.bytes_moved(rows, d, 3 * d, 2, 2, 4) + mm.bytes_moved(rows, d, d, 2, 2, 4)
             + batch * heads * (mm.bytes_moved(t, dh, t, 2, 2, 4) + mm.bytes_moved(t, t, dh, 2, 2, 4))
             + mm.bytes_moved(rows, d, f, 2, 2, 4) + mm.bytes_moved(rows, f, d, 2, 2, 4))
    moved = (mm.bytes_moved(rows, m["conv_dim"][-1], d, 2, 2, 4)
             + conv.bytes_moved(batch, t + k_pos, t, k_pos, d, d, m["num_conv_pos_embedding_groups"])
             + m["num_hidden_layers"] * layer)
    ops = sum(n for name, n, _ in r.cell.family.layers(r.cell.config) if name in LAYERS)
    peaks = catalog.peaks(jax.devices()[0].device_kind, ROOT)
    least_s = max(ops / peaks["bf16_flops_per_s"], moved / batch / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (us * 1e-6)
