"""The SHIELD8-UAV 1D-F-CNN family: its seeded weights, its engine, its operations.

A configuration file names its family (``"family": "shield8_cnn"``) and the
harness finds this module by that name (``catalog.family``).  A family
module defines three functions:

* ``weights(model, seed)`` -- the float weights, made on the device in one
  jitted call from ``chipbench.weights.key(seed)``;
* ``engine(cell, params)`` -- the program under test, built from those
  weights through its public constructor;
* ``layers(config)`` -- ``[(layer, operations per window, stated
  precision)]`` of the network as served.

The configuration's ``model`` holds the paper's widths (``input_len``,
``channels``, ``kernel``, ``hidden``, ``n_classes``), ``bake`` the
deployment decisions (precision, prune, per-layer policy).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import catalog
from chipbench.weights import key


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


def weights(model: dict, seed: int) -> dict:
    """``{layer: {"w": float32, "b": float32}}`` on the default device:
    He-normal kernels, small normal biases, so that the bias epilogues carry
    real values."""
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


def engine(cell, params):
    """The program under test, through its public engine constructor."""
    from repro.core.precision_policy import PrecisionPolicy
    from repro.core.pruning import plan_prune
    from repro.models.cnn1d import CNNConfig
    from repro.serving.engine import MonitorEngine

    cfgj = cell.config
    m = cfgj["model"]
    cfg = CNNConfig(input_len=m["input_len"], channels=tuple(m["channels"]), kernel=m["kernel"],
                    hidden=m["hidden"], n_classes=m["n_classes"])
    bake = cfgj["bake"]
    prune = policy = None
    if bake.get("prune"):
        last = f"conv{len(m['channels']) - 1}"
        prune = plan_prune(params[last]["w"], cfg.n_frames, keep=bake["prune"]["keep"],
                           trim_frames=bake["prune"]["trim_frames"])
    if bake.get("policy"):
        policy = PrecisionPolicy.parse(bake["policy"], default=bake["mode"])
    eng = cfgj["engine"]
    return MonitorEngine(
        params, cfg,
        n_streams=cell.traffic["streams"],
        feature_kind=cfgj["feature_kind"],
        on_device_features=True,
        batch_slots=eng["batch_slots_per_chip"] * cell.chips,
        precision=bake["mode"],
        prune=prune,
        policy=policy,
        capacity_windows=eng["capacity_windows"],
        shards=cell.chips if cell.chips > 1 else None,
        **eng["tracker"],
    )


def layers(config: dict) -> list[tuple[str, int, str]]:
    """``[(layer, operations per window, stated precision)]`` of the network
    as served (after the configuration's prune); the DSP front-end is not
    counted."""
    conv = catalog.kernel("conv")
    matmul = catalog.kernel("matmul")
    model = config["model"]
    prec = config["stated_precision"]
    prune = config["bake"].get("prune")
    channels = list(model["channels"])
    if prune:
        channels[-1] = prune["keep"]
    out = []
    length, c_in = model["input_len"], 1
    for i, c_out in enumerate(channels):
        out.append((f"conv{i}", conv.ops(1, length, model["kernel"], c_in, c_out), prec[f"conv{i}"]))
        length //= 2
        c_in = c_out
    frames = length - (prune["trim_frames"] if prune else 0)
    out.append(("dense0", matmul.ops(1, frames * c_in, model["hidden"]), prec["dense0"]))
    out.append(("dense1", matmul.ops(1, model["hidden"], model["n_classes"]), prec["dense1"]))
    return out
