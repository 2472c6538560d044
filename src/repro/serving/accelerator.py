"""Deployed-datapath inference: the served forward of each model kind.

Two kinds are served, picked by the config's type: the paper's 1D-F-CNN
(:class:`~repro.models.cnn1d.CNNConfig`, below) and the HuBERT X-Large
second-stage verifier (:class:`~repro.models.hubert.HubertConfig`, baked to
a :class:`~repro.models.hubert.HubertParams` artifact and run by
:func:`_forward_verifier`).  Both take the same (B, 12800) raw-window blocks
from the engine, pad a short batch to ``MIN_ROWS`` and run each layer under
a named scope (:data:`SCOPES`).

The CNN's forward is the software twin of the POLARON accelerator's execution: every
convolution and dense layer runs on the W8A8 kernels — conv on the fused
in-kernel-im2col conv kernel, dense on quant_matmul — with bias+ReLU fused
into each layer's dequant epilogue, and the classifier head finishes with
the CORDIC softmax.  Against fp32 JAX inference this bounds the
*accelerator's* end-to-end numerical deviation — the sign-off artifact an
RTL team would diff against.

Weights come from a :class:`~repro.serving.quantized_params.QuantizedParams`
artifact (baked once at deploy time); only the per-request activations are
quantised per call.  The whole forward is one ``jax.jit`` program,
interpret-mode on CPU and compiled on TPU via the ``interpret=None``
autodetect.

The artifact's static metadata drives per-layer dispatch (the POLARON
"configuration prefetcher interprets layer metadata" idea): each layer's
``conv_modes``/``dense_modes`` tag routes it to the matching datapath —
fused W8A8 kernels for int8/fxp8, a bf16-operand/fp32-accumulate einsum for
BF16, plain fp32 otherwise — and a pruned artifact's ``keep_frames`` applies
the boundary-frame trim between the last pool and the flatten.  Every
datapath keeps each batch row's result independent of its co-batch (the
8-bit modes via per-sample activation scales, the float modes trivially), so
the streaming == batched == sharded bitwise guarantee holds for pruned and
mixed-precision artifacts unchanged (pinned by
``tests/test_pruned_serving_conformance.py``).
"""
from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.data import features_jax
from repro.distributed.sharding import STREAM_AXIS
from repro.kernels import ops
from repro.kernels.backend import resolve_interpret
from repro.models import hubert
from repro.models.cnn1d import CNNConfig, _maxpool2
from repro.models.hubert import HubertConfig, HubertParams
from repro.serving.quantized_params import QuantizedParams, quantize_params


#: fewest rows a forward computes (one f32 sublane tile); see _forward_quantized
MIN_ROWS = 8

#: the named scopes of the forwards' layers, as they appear in an op_name:
#: the CNN's (frontend, conv<i>, flatten, dense<i>, softmax) and the
#: verifier's (frontend, waveform, featproj, posconv, attn, ffn, head)
SCOPES = re.compile(
    r"^(frontend|conv\d+|flatten|dense\d+|softmax"
    r"|waveform|featproj|posconv|attn|ffn|head)$"
)


def _quantizer(layer_mode: str):
    from repro.core.quantization import fxp8_quantize, int8_symmetric

    return fxp8_quantize if layer_mode == "fxp8" else int8_symmetric


def _conv1d_float(x: jax.Array, w: jax.Array) -> jax.Array:
    """'same' 1D conv for the float layer modes; accumulates in fp32 even for
    bf16 operands (the MXU's bf16-in/fp32-accumulate discipline).  HIGHEST
    keeps fp32 operands fp32 on the TPU, whose default is one bf16 pass."""
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(1,), padding="SAME",
        dimension_numbers=("NWC", "WIO", "NWC"),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


@functools.partial(
    jax.jit, static_argnames=("interpret", "per_sample_acts", "raw_windows")
)
def _forward_quantized(
    qp: QuantizedParams,
    x: jax.Array,
    interpret: bool,
    per_sample_acts: bool,
    raw_windows: bool = False,
) -> jax.Array:
    # Every layer runs under a jax.named_scope (frontend, conv<i>, flatten,
    # dense<i>, softmax; SCOPES matches them), so each compiled operation's
    # op_name names the layer it computes and a device trace can be summed
    # per layer (hlo_scopes).
    # Fused DSP front-end: with raw_windows the program starts at the
    # microphone samples — feature extraction runs in-graph (per-row, see
    # features_jax) ahead of the quantised datapath, so host feature work
    # never serializes with device dispatch.
    if raw_windows:
        with jax.named_scope("frontend"):
            x = features_jax.feature_rows(x, qp.feature_kind)
    # Per-sample (row-wise) activation scales are the default: with one
    # per-tensor scale, a single loud sample crushes the quantisation
    # resolution of every co-batched quiet one — exactly the failure mode
    # micro-batching windows from N independent streams triggers.  Row-wise
    # scales also make every row's result independent of its co-batch, which
    # is what the streaming engine's bitwise-parity guarantee rests on.  The
    # float layer modes keep rows independent too (conv and matmul rows never
    # mix), once a short batch runs padded with copies of its last row to
    # MIN_ROWS: the TPU lowers a one-row float conv or dot unlike a batched
    # one, and on a v5e that moved a row of the mixed artifact at B=1.
    act_axis = 0 if per_sample_acts else None
    n_rows = x.shape[0]
    conv_modes, dense_modes = qp.layer_modes
    with jax.named_scope("conv0"):
        if 0 < n_rows < MIN_ROWS:
            x = jnp.pad(x, ((0, MIN_ROWS - n_rows), (0, 0)), mode="edge")
        h = x[:, :, None].astype(jnp.float32)
    bsz = x.shape[0]
    for i, (layer, lmode) in enumerate(zip(qp.convs, conv_modes)):
        with jax.named_scope(f"conv{i}"):
            h = _conv_layer(h, layer, lmode, act_axis, per_sample_acts, interpret)
    with jax.named_scope("flatten"):
        if qp.keep_frames is not None:
            h = h[:, : qp.keep_frames, :]  # pruned artifact: boundary-frame trim
        h = h.reshape(bsz, -1)
    for i, (layer, lmode) in enumerate(zip(qp.denses, dense_modes)):
        act = "relu" if i < len(qp.denses) - 1 else None
        with jax.named_scope(f"dense{i}"):
            h = _dense_layer(h, layer, lmode, act, act_axis, per_sample_acts, interpret)
    with jax.named_scope("softmax"):
        return ops.cordic_softmax(h, interpret=interpret)[:n_rows]


def _conv_layer(h, layer, lmode, act_axis, per_sample_acts, interpret):
    """One conv layer of the forward: activation quantiser, kernel, max-pool."""
    if lmode in ("int8", "fxp8"):
        hq = _quantizer(lmode)(h, axis=act_axis)  # per-request act quant
        h = ops.conv1d_fused_q(
            hq.q,
            layer["w"].q,
            hq.scale.reshape(-1, 1) if per_sample_acts else hq.scale,
            layer["w"].scale,
            layer["b"],
            act="relu",  # CORDIC ReLU == max(v, 0): fused into the epilogue
            interpret=interpret,
        )
    else:
        hin = h.astype(jnp.bfloat16) if lmode == "bf16" else h
        h = jnp.maximum(_conv1d_float(hin, layer["w"]) + layer["b"], 0.0)
    return _maxpool2(h)


def _dense_layer(h, layer, lmode, act, act_axis, per_sample_acts, interpret):
    """One dense layer of the forward; ``act`` is "relu" or None."""
    if lmode in ("int8", "fxp8"):
        hq = _quantizer(lmode)(h, axis=act_axis)
        return ops.quant_matmul(
            hq.q,
            layer["w"].q,
            hq.scale.reshape(h.shape[0] if per_sample_acts else 1, 1),
            layer["w"].scale.reshape(1, -1),
            layer["b"],
            act=act,
            interpret=interpret,
        )
    if lmode == "bf16":
        h = jnp.einsum(
            "bk,kn->bn",
            h.astype(jnp.bfloat16),
            layer["w"],
            preferred_element_type=jnp.float32,
        )
    else:
        h = jnp.einsum(
            "bk,kn->bn", h, layer["w"],
            precision=jax.lax.Precision.HIGHEST,
        )
    h = h + layer["b"]
    return jnp.maximum(h, 0.0) if act == "relu" else h


@jax.jit
def _forward_verifier(art: HubertParams, x: jax.Array) -> jax.Array:
    """The HuBERT verifier's served program (:func:`repro.models.hubert.forward`),
    with a batch below ``MIN_ROWS`` padded as the CNN's is."""
    n_rows = x.shape[0]
    if 0 < n_rows < MIN_ROWS:
        with jax.named_scope("frontend"):
            x = jnp.pad(x, ((0, MIN_ROWS - n_rows), (0, 0)), mode="edge")
    return hubert.forward(art, x)[:n_rows]


def _check_raw_windows(qp: QuantizedParams, x: jax.Array, feature_kind: str | None):
    """Validate the raw-window contract before tracing (clear errors beat
    shape mismatches inside jit)."""
    if qp.feature_kind is None:
        raise ValueError(
            "raw_windows=True needs an artifact with a baked feature kind; "
            "re-bake with quantize_params(..., feature_kind=...) or pass "
            "feature_kind= alongside the fp32 checkpoint"
        )
    if feature_kind is not None and feature_kind != qp.feature_kind:
        raise ValueError(
            f"artifact was baked for feature kind {qp.feature_kind!r}, "
            f"got feature_kind={feature_kind!r}"
        )
    if x.ndim != 2 or x.shape[1] != features_jax.N_SAMPLES:
        raise ValueError(
            f"raw_windows=True expects (B, {features_jax.N_SAMPLES}) raw "
            f"0.8 s windows, got {tuple(x.shape)}"
        )


def accelerator_forward(
    params: dict | QuantizedParams | HubertParams,
    x: jax.Array,
    cfg: CNNConfig | HubertConfig,
    *,
    fxp: bool = False,
    interpret: bool | None = None,
    per_sample_acts: bool = True,
    raw_windows: bool = False,
    feature_kind: str | None = None,
) -> jax.Array:
    """x: (B, M) features -> (B, n_classes) class probabilities, computed
    entirely on the kernel datapath.

    With a :class:`~repro.models.hubert.HubertConfig` the HuBERT verifier
    serves instead: ``params`` is its baked ``HubertParams`` (or the float
    checkpoint, baked here), ``x`` holds (B, 12800) raw windows
    (``raw_windows=True`` is required: the verifier's input normalisation
    always runs in-graph), and the quantisation options do not apply.

    Pass a :class:`QuantizedParams` artifact to serve from the weight cache
    (zero weight-quantisation work per call) — pruned and mixed-precision
    artifacts dispatch per layer off the artifact's tags.  A raw fp32
    ``params`` dict is quantised on the fly (``fxp`` selects the mode) for
    one-off sign-offs.

    ``raw_windows=True`` accepts raw (B, 12800) 0.8 s audio windows instead
    of features: the artifact's baked ``feature_kind`` front-end runs
    in-graph as the first stage of the same jitted program (windows -> probs
    end to end).  Feature bits are per-row by construction, so every parity
    guarantee (streaming == batched == sharded) carries over; note the JAX
    front-end is the float32 twin of the numpy oracle — tolerance-bounded,
    not bitwise, against host-extracted features.

    ``per_sample_acts`` (default) quantises activations with one scale per
    batch row; ``False`` restores the legacy per-tensor scale (kept as the
    A/B surface for the mixed-loudness regression tests).
    """
    if isinstance(cfg, HubertConfig):
        if not raw_windows:
            raise ValueError(
                "the verifier serves raw windows only: pass raw_windows=True"
            )
        if x.ndim != 2 or x.shape[1] != cfg.input_len:
            raise ValueError(
                f"the verifier takes (B, {cfg.input_len}) windows, got {tuple(x.shape)}"
            )
        art = params if isinstance(params, HubertParams) else hubert.bake(params, cfg)
        return _forward_verifier(art, x)
    if isinstance(params, QuantizedParams):
        qp = params
    else:
        qp = quantize_params(
            params, cfg, mode="fxp8" if fxp else "int8", feature_kind=feature_kind
        )
    if raw_windows:
        _check_raw_windows(qp, x, feature_kind)
    return _forward_quantized(
        qp, x, resolve_interpret(interpret), per_sample_acts, raw_windows
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "mesh", "axis_name", "interpret", "per_sample_acts", "raw_windows"
    ),
)
def _forward_sharded(
    qp: QuantizedParams,
    x: jax.Array,
    mesh: Mesh,
    axis_name: str,
    interpret: bool,
    per_sample_acts: bool,
    raw_windows: bool = False,
) -> jax.Array:
    # raw_windows shards the *windows*: each device runs the DSP front-end
    # shard-local on its own rows (per-row feature bits make this exactly the
    # unsharded computation), then its slice of the quantised datapath.
    fwd = functools.partial(
        _forward_quantized,
        interpret=interpret,
        per_sample_acts=per_sample_acts,
        raw_windows=raw_windows,
    )
    return jax.shard_map(
        fwd,
        mesh=mesh,
        in_specs=(P(), P(axis_name)),  # weights replicated, rows sharded
        out_specs=P(axis_name),
        check_vma=False,
    )(qp, x)


def accelerator_forward_sharded(
    params: dict | QuantizedParams,
    x: jax.Array,
    cfg: CNNConfig,
    *,
    mesh: Mesh,
    axis_name: str = STREAM_AXIS,
    fxp: bool = False,
    interpret: bool | None = None,
    raw_windows: bool = False,
    feature_kind: str | None = None,
) -> jax.Array:
    """Sharded-batch twin of :func:`accelerator_forward`: the batch dimension
    is split along ``mesh``'s ``axis_name`` axis, weights stay replicated,
    and each device runs the whole W8A8 datapath on its rows.

    Because activations are quantised with **per-sample** scales, each row's
    quantisation (and therefore its result) depends on nothing outside the
    row — the scales travel with their rows across the shard boundary, and
    the output is **bitwise identical** to the unsharded forward on the same
    batch.  That is the serving analogue of the paper's sequential scaling
    claim: partitioning the fixed batch over more hardware changes the
    schedule, never the numbers (the conformance suite pins this).

    Per-tensor activation scales are deliberately unsupported here: a shard-
    local per-tensor amax would differ from the global one, silently breaking
    the parity guarantee.  Pruned and mixed-precision artifacts shard
    unchanged — the float layer modes compute each row independently, so the
    bitwise guarantee extends to every artifact cell (conformance-pinned).

    ``raw_windows=True`` shards raw (B, 12800) windows instead of features:
    each device runs the fused DSP front-end on its own rows (shard-local,
    per-row bits) before its slice of the datapath — bitwise identical to
    the unsharded raw-window forward.

    ``x.shape[0]`` must divide evenly by the shard count.
    """
    n_shards = mesh.shape[axis_name]
    if x.shape[0] % n_shards != 0:
        raise ValueError(
            f"batch {x.shape[0]} not divisible by {n_shards} shards on "
            f"mesh axis {axis_name!r}"
        )
    if isinstance(params, QuantizedParams):
        qp = params
    else:
        qp = quantize_params(
            params, cfg, mode="fxp8" if fxp else "int8", feature_kind=feature_kind
        )
    if raw_windows:
        _check_raw_windows(qp, x, feature_kind)
    return _forward_sharded(
        qp, x, mesh, axis_name, resolve_interpret(interpret), True, raw_windows
    )


def precompile_slot_shapes(
    qp: QuantizedParams | HubertParams,
    cfg: CNNConfig | HubertConfig,
    slot_counts,
    *,
    mesh: Mesh | None = None,
    axis_name: str | None = None,
    interpret: bool | None = None,
    raw_windows: bool = False,
) -> None:
    """Trace and compile the forward once per batch (slot) shape.

    Adaptive batch-slot sizing dispatches a small ladder of block shapes
    instead of one fixed ``batch_slots``; each distinct shape costs one jit
    trace.  Serving pays that cost at whatever round first uses the shape —
    a visible latency spike — unless the shapes are compiled up front.  This
    warms the jit cache with a zeros block per ladder value (zeros = the
    engine's silence padding, so no NaN hazards) and blocks until every
    program is built.  Per-sample activation scales make the traced numbers
    irrelevant — only the shapes enter the cache key.
    """
    if not isinstance(qp, (QuantizedParams, HubertParams)):
        raise TypeError(
            f"precompile_slot_shapes needs a baked QuantizedParams or HubertParams artifact, "
            f"got {type(qp).__name__}"
        )
    row_width = features_jax.N_SAMPLES if raw_windows else cfg.input_len
    for slots in sorted(set(int(s) for s in slot_counts)):
        x = jnp.zeros((slots, row_width), jnp.float32)
        if mesh is not None:
            out = accelerator_forward_sharded(
                qp, x, cfg, mesh=mesh,
                axis_name=STREAM_AXIS if axis_name is None else axis_name,
                interpret=interpret, raw_windows=raw_windows,
            )
        else:
            out = accelerator_forward(
                qp, x, cfg, interpret=interpret, raw_windows=raw_windows
            )
        out.block_until_ready()


_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%(\S+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=%([^,\s}]+)")


def hlo_scopes(hlo_text: str) -> dict[str, str]:
    """``{instruction name: layer scope}`` of a compiled forward's HLO text.

    An instruction's scope is the one :data:`SCOPES` component of its
    ``op_name``.  One without (a fusion the compiler made for itself)
    takes the scope that the computations it calls carry, where they carry
    exactly one.  Instructions that trace to no layer (layout copies of the
    parameters, buffer allocations) are left out."""
    own: dict[str, str] = {}
    calls: dict[str, list[str]] = {}  # instruction -> computations it calls
    members: dict[str, list[str]] = {}  # computation -> its instructions
    comp = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            members[comp] = []
            continue
        m = _INSTRUCTION.match(line)
        if not m or comp is None:
            continue
        name = m.group(1)
        members[comp].append(name)
        calls[name] = _CALLS.findall(line)
        on = _OP_NAME.search(line)
        found = {p for p in on.group(1).split("/") if SCOPES.match(p)} if on else set()
        if len(found) == 1:
            own[name] = found.pop()

    @functools.cache
    def carried(c: str) -> frozenset:
        out = set()
        for name in members.get(c, ()):
            if name in own:
                out.add(own[name])
            for callee in calls[name]:
                out |= carried(callee)
        return frozenset(out)

    out = dict(own)
    for name, callees in calls.items():
        if name not in out and callees:
            found = frozenset().union(*(carried(c) for c in callees))
            if len(found) == 1:
                (out[name],) = found
    return out


def forward_scopes(
    qp: QuantizedParams | HubertParams,
    cfg: CNNConfig | HubertConfig,
    slot_counts,
    *,
    mesh: Mesh | None = None,
    axis_name: str | None = None,
    interpret: bool | None = None,
    raw_windows: bool = False,
) -> dict[str, str]:
    """:func:`hlo_scopes` of the forward compiled at each batch (slot)
    shape, as :func:`precompile_slot_shapes` builds it: the map from a
    device trace's operation names to the forward's layers (the CNN's or,
    for a ``HubertParams`` artifact, the verifier's)."""
    row_width = features_jax.N_SAMPLES if raw_windows else cfg.input_len
    interp = resolve_interpret(interpret)
    out: dict[str, str] = {}
    for slots in sorted(set(int(s) for s in slot_counts)):
        x = jax.ShapeDtypeStruct((slots, row_width), jnp.float32)
        if isinstance(qp, HubertParams):
            lowered = _forward_verifier.lower(qp, x)
        elif mesh is not None:
            axis = STREAM_AXIS if axis_name is None else axis_name
            lowered = _forward_sharded.lower(qp, x, mesh, axis, interp, True, raw_windows)
        else:
            lowered = _forward_quantized.lower(qp, x, interp, True, raw_windows)
        out.update(hlo_scopes(lowered.compile().as_text()))
    return out


def deviation_report(
    params: dict, x: jax.Array, cfg: CNNConfig, *, per_sample_acts: bool = True
) -> dict:
    """Max probability deviation + decision agreement vs fp32 inference."""
    from repro.models import cnn1d

    ref = jax.nn.softmax(cnn1d.forward(params, x, cfg), axis=-1)
    acc = accelerator_forward(params, x, cfg, per_sample_acts=per_sample_acts)
    return {
        "max_prob_dev": float(jnp.max(jnp.abs(ref - acc))),
        "decision_agreement": float(jnp.mean(jnp.argmax(ref, -1) == jnp.argmax(acc, -1))),
    }
