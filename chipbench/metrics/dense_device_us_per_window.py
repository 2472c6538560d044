"""Chip microseconds per window in the forward's flatten and dense layers
(the ``flatten`` and every ``dense<i>`` scope), over the traced segment (see
``chipbench/scopes.py``)."""
from chipbench import scopes


def read(r):
    return scopes.us_per_window(r, r"flatten|dense\d+")
