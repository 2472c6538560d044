"""Chip microseconds per window in the forward's DSP front-end (the
``frontend`` scope), over the traced segment (see ``chipbench/scopes.py``)."""
from chipbench import scopes


def read(r):
    return scopes.us_per_window(r, r"frontend")
