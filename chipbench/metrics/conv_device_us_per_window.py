"""Chip microseconds per window in the forward's conv layers (every
``conv<i>`` scope: activation quantiser, kernel, max-pool), over the traced
segment (see ``chipbench/scopes.py``)."""
from chipbench import scopes


def read(r):
    return scopes.us_per_window(r, r"conv\d+")
