"""Chip microseconds per window in the HuBERT verifier's input normalisation
and waveform conv stack (the ``frontend`` and ``waveform`` scopes), over the
traced segment (see ``chipbench/scopes.py``)."""
from chipbench import scopes


def read(r):
    return scopes.us_per_window(r, r"frontend|waveform")
