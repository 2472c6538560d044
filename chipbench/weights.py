"""Seeded float weights of a detector configuration, made on the device.

One jitted call turns the seed into every weight (He-normal kernels, small
normal biases, so that the bias epilogues carry real values).  The program
bakes its artifact from these float weights and the reference reads them
too; neither makes weights of its own.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def key(seed: int) -> jax.Array:
    """A threefry key from all the bits of ``seed`` (``jax.random.key`` keeps
    only the low 32 of a large one)."""
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def shapes(model: dict) -> dict:
    """Layer name -> (weight shape, fan-in) for a 1D-F-CNN layout."""
    out = {}
    c_in = 1
    for i, c_out in enumerate(model["channels"]):
        out[f"conv{i}"] = ((model["kernel"], c_in, c_out), model["kernel"] * c_in)
        c_in = c_out
    flatten = model["input_len"] // 2 ** len(model["channels"]) * c_in
    out["dense0"] = ((flatten, model["hidden"]), flatten)
    out["dense1"] = ((model["hidden"], model["n_classes"]), model["hidden"])
    return out


def make(model: dict, seed: int) -> dict:
    """``{layer: {"w": float32, "b": float32}}`` on the default device."""
    layout = shapes(model)

    @jax.jit
    def init(k):
        ks = jax.random.split(k, 2 * len(layout))
        params = {}
        for i, (name, (shape, fan_in)) in enumerate(layout.items()):
            w = jax.random.normal(ks[2 * i], shape, jnp.float32) * np.sqrt(2.0 / fan_in)
            b = jax.random.normal(ks[2 * i + 1], shape[-1:], jnp.float32) * 0.05
            params[name] = {"w": w, "b": b}
        return params

    return init(key(seed))
