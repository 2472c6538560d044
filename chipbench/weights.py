"""The seed of a configuration's float weights.

Each family module (``chipbench/families/<family>.py``) makes its weights
from :func:`key` in one jitted call on the device.  The program bakes its
artifact from these float weights and the reference reads them too;
neither makes weights of its own.
"""
from __future__ import annotations

import jax
import numpy as np


def key(seed: int) -> jax.Array:
    """A threefry key from all the bits of ``seed`` (``jax.random.key`` keeps
    only the low 32 of a large one)."""
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")
