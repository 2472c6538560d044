"""hubert-xlarge (LM-stack approximation) — the encoder backbone only, as the
language-model stack can express it: 48L d1280 16H d_ff=5120 vocab=504
(cluster targets). [arXiv:2106.07447]

Not the served model.  ``input_specs()`` takes precomputed 512-wide frame
embeddings in place of HuBERT's waveform conv stack and positional conv,
and the LM stack normalises with RMSNorm where HuBERT has LayerNorm.  The
HuBERT X-Large verifier that ``MonitorEngine`` serves, with the waveform
front-end, the grouped positional conv and LayerNorm at published widths,
is :mod:`repro.models.hubert`.
"""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="hubert-xlarge",
    family="audio",
    n_layers=48,
    d_model=1280,
    n_heads=16,
    n_kv_heads=16,
    head_dim=80,
    d_ff=5120,
    vocab=504,
    pattern=("attn",),
    mlp_kind="gelu",
    causal=False,  # bidirectional encoder
    frontend="audio_frames",
    frontend_dim=512,
    source="arXiv:2106.07447",
    notes=(
        "LM-stack approximation of the HuBERT X-Large encoder backbone: "
        "precomputed 512-wide frames in place of the waveform extractor, "
        "RMSNorm in place of LayerNorm; the served model is "
        "repro.models.hubert.  Encoder-only: no decode step -> decode_32k "
        "and long_500k skipped.  prefill_32k = full encoder forward."
    ),
)
