"""Compile rehearsal for the TPU v5e: the served path's kernels and the whole
jitted forward, lowered at the paper's widths against a described ``v5e:2x2``
topology with ``interpret=False``.

Interpret-mode tests cannot see what the TPU compiler refuses — block shapes
off the (8, 128) tiling, VMEM over-use, unsupported ops in a kernel body — so
each test here compiles for the chip (no chip attached) and asserts the
Pallas kernel survived as a ``tpu_custom_call``.  The topology is described
inside a module fixture, never at import time: only one process may load
the TPU library, and pytest-xdist workers import every test module.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.precision_policy import PrecisionPolicy
from repro.core.pruning import plan_prune
from repro.kernels.conv1d_fused import conv1d_fused_q
from repro.kernels.cordic_act import cordic_softmax
from repro.kernels.quant_matmul import quant_matmul
from repro.models import cnn1d
from repro.serving.accelerator import SCOPES, _forward_quantized, hlo_scopes
from repro.serving.quantized_params import quantize_params

CFG = cnn1d.CNNConfig()  # published widths: M=1096, channels 64/128/256
SLOTS = 64
N_SAMPLES = 12_800
MIXED = "conv0/w=bf16,dense1/w=fp32"

# (L, Cin, Cout) of the three canonical conv layers (k=3, 'same', pool/2 after each)
CONV_LAYERS = [(1096, 1, 64), (548, 64, 128), (274, 128, 256)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("l,cin,cout", CONV_LAYERS)
def test_conv1d_fused_compiles(one_chip, l, cin, cout):
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    x, w = s((SLOTS, l, cin), jnp.int8), s((3, cin, cout), jnp.int8)
    # served form: per-sample scales, fused bias+ReLU epilogue
    served = conv1d_fused_q.lower(
        x, w, s((SLOTS, 1), jnp.float32), s((cout,), jnp.float32),
        s((cout,), jnp.float32), act="relu", interpret=False,
    ).compile()
    _assert_kernel(served)
    # sign-off form: raw int32 accumulators
    acc = conv1d_fused_q.lower(
        x, w, s((), jnp.float32), s((cout,), jnp.float32),
        interpret=False, return_acc=True,
    ).compile()
    _assert_kernel(acc)


@pytest.mark.parametrize("k", [CFG.flatten_size, 136 * 64])  # 35,072 and pruned 8,704
def test_quant_matmul_compiles(one_chip, k):
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    n = CFG.hidden
    compiled = quant_matmul.lower(
        s((SLOTS, k), jnp.int8), s((k, n), jnp.int8),
        s((SLOTS, 1), jnp.float32), s((1, n), jnp.float32),
        s((n,), jnp.float32), act="relu", interpret=False,
    ).compile()
    _assert_kernel(compiled)


def test_cordic_softmax_compiles(one_chip):
    compiled = jax.jit(lambda h: cordic_softmax(h, interpret=False)).lower(
        _spec(one_chip, (SLOTS, CFG.n_classes), jnp.float32)
    ).compile()
    _assert_kernel(compiled)


@pytest.fixture(scope="module")
def params():
    return cnn1d.init_params(jax.random.PRNGKey(0), CFG)


def _artifact(params, name):
    if name == "int8":
        return quantize_params(params, CFG, mode="int8", feature_kind="mfcc20")
    spec = plan_prune(
        np.asarray(params["conv2"]["w"]), CFG.n_frames, keep=64, trim_frames=1
    )
    assert spec.flatten_after == 8_704
    return quantize_params(
        params, CFG, mode="int8", prune=spec,
        policy=PrecisionPolicy.parse(MIXED, default="int8"),
        feature_kind="mfcc20",
    )


@pytest.fixture(scope="module")
def served_forward(one_chip, params):
    """The whole served program at 64 slots, compiled once per artifact."""
    cache = {}

    def get(name):
        if name not in cache:
            qp = _artifact(params, name)
            qp_spec = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype), qp)
            cache[name] = _forward_quantized.lower(
                qp_spec, _spec(one_chip, (SLOTS, N_SAMPLES), jnp.float32),
                interpret=False, per_sample_acts=True, raw_windows=True,
            ).compile()
        return cache[name]

    return get


@pytest.mark.parametrize("name", ["int8", "pruned_mixed"])
def test_forward_from_raw_windows_compiles(served_forward, name):
    """The whole served program at 64 slots: on-device mfcc20 front-end,
    W8A8 kernels (and the float layers of the mixed policy), CORDIC head."""
    _assert_kernel(served_forward(name))


@pytest.mark.parametrize("name", ["int8", "pruned_mixed"])
def test_forward_ops_carry_one_scope_on_tpu(served_forward, name):
    """As the chip compiles the served program, every operation that came
    from the forward names one layer, and every Pallas kernel maps to a
    layer (a device trace's operations are summed per layer through this
    map)."""
    text = served_forward(name).as_text()
    for line in text.splitlines():
        on = re.search(r'op_name="(jit\([^"]*)"', line)
        if on and re.search(r" (fusion|convolution|dot|custom-call|while|reduce-window)\(", line):
            assert len([p for p in on.group(1).split("/") if SCOPES.match(p)]) == 1, line
    scopes = hlo_scopes(text)
    kernels = re.findall(r"^\s+(?:ROOT )?%(\S+) = .*custom_call_target=\"tpu_custom_call\"",
                         text, re.M)
    layers = {"frontend", "conv0", "conv1", "conv2", "dense0", "dense1", "softmax"}
    # int8: 3 conv, 2 dense, softmax; pruned-mixed runs conv0 and dense1 in float
    assert len(kernels) == {"int8": 6, "pruned_mixed": 4}[name]
    assert {scopes[k] for k in kernels} <= layers
    # flatten is a bitcast unless the pruned artifact trims frames first
    assert layers <= set(scopes.values()) <= layers | {"flatten"}
