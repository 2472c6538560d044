"""Chip microseconds per window in the HuBERT verifier's encoder: feature
projection, positional conv and the transformer layers' attention and
feed-forward (the ``featproj``, ``posconv``, ``attn`` and ``ffn`` scopes),
over the traced segment (see ``chipbench/scopes.py``)."""
from chipbench import scopes


def read(r):
    return scopes.us_per_window(r, r"featproj|posconv|attn|ffn")
