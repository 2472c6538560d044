"""Pallas TPU kernel: fused quantised 1D convolution (im2col-in-VMEM).

The seed datapath lowered conv onto ``quant_matmul`` by materialising an
im2col patch tensor of shape (B*L, K*Cin) in HBM — K copies of every
activation — then paying two more full HBM round-trips for the bias add and
the ReLU.  This kernel keeps the whole layer inside the compute fabric:

* **in-kernel im2col** — each grid step loads one (bl, Cin) activation block
  plus a sublane-rounded halo view (the first rows of the *next* block,
  read straight from the same padded HBM buffer through a second BlockSpec
  with a shifted index map) and forms the K shifted views with static
  slices in VMEM.  No patch tensor and no separate halo tensor ever exist
  in HBM; 1-tap convs skip the halo operand entirely.
* **weight-stationary taps** — the full (K, Cin, bn) weight block sits in
  VMEM for the whole grid step; the K tap matmuls accumulate into one int32
  register tile (the extended-precision accumulator discipline shared with
  ``quant_matmul``).
* **fused epilogue** — dequant, bias add, ReLU and the optional PACT clip
  happen on the accumulator tile, then a single fp32 store.  One HBM write
  per layer instead of three.

Block shapes default to ``kernels.tiling.select_conv_tiles`` — picked per
problem shape from the declared per-core VMEM budget, rounded to MXU/lane
granules.  Tile choice never changes the int32 accumulator bits (pinned by
``tests/test_tiling.py``).

The layout contract matches ``conv1d_q``: activations (B, L, Cin) int8 with
a per-tensor *or per-sample* ((B,)-broadcastable) scale, weights
(K, Cin, Cout) int8 with per-output-channel scales, 'same' zero padding.  ``return_acc=True`` skips the epilogue and
returns the raw int32 accumulators — the bitwise sign-off surface against
the im2col reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.quantization import QTensor, fxp8_quantize, int8_symmetric
from repro.kernels import tiling
from repro.kernels.backend import resolve_interpret


def _kernel(xm_ref, *rest, k, bl, act, has_halo, has_bias, has_clip, return_acc):
    i = 0
    if has_halo:
        xh_ref = rest[0]
        i = 1
    w_ref = rest[i]
    i += 1
    if return_acc:
        xs_ref = ws_ref = b_ref = c_ref = None
    else:
        xs_ref, ws_ref = rest[i], rest[i + 1]
        i += 2
        b_ref = rest[i] if has_bias else None
        i += has_bias
        c_ref = rest[i] if has_clip else None
        i += has_clip
    o_ref = rest[i]

    xm = xm_ref[0]  # (bl, Cin) int8 activation block
    if has_halo:
        # First k-1 rows of the next length block, read through the shifted
        # view of the same padded buffer (no HBM halo tensor exists).
        xcat = jnp.concatenate([xm, xh_ref[0, : k - 1]], axis=0)
    else:
        xcat = xm
    # im2col via shifted static slices of the VMEM-resident block: tap t of
    # output row l reads input row l + t (the 'same' pad is already baked
    # into the HBM layout), so each tap is one (bl, Cin) x (Cin, bn) matmul.
    acc = jax.lax.dot(
        xcat[0:bl], w_ref[0], preferred_element_type=jnp.int32
    )
    for t in range(1, k):
        acc += jax.lax.dot(
            xcat[t : t + bl], w_ref[t], preferred_element_type=jnp.int32
        )
    if return_acc:
        o_ref[0] = acc
        return
    y = acc.astype(jnp.float32) * xs_ref[0, 0, 0] * ws_ref[...]
    if has_bias:
        y = y + b_ref[...]
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    if has_clip:
        y = jnp.minimum(y, c_ref[0, 0])
    o_ref[0] = y


@functools.partial(
    jax.jit,
    static_argnames=("act", "bl", "bn", "lane", "interpret", "return_acc"),
)
def conv1d_fused_q(
    x_q: jax.Array,  # (B, L, Cin) int8
    w_q: jax.Array,  # (K, Cin, Cout) int8
    x_scale: jax.Array,  # scalar (per-tensor) or (B,)-broadcastable (per-sample) fp32
    w_scale: jax.Array,  # (Cout,)-broadcastable fp32 per-channel weight scale
    bias: jax.Array | None = None,  # (Cout,) fp32, fused epilogue add
    *,
    act: str | None = None,  # None or "relu"
    clip: jax.Array | None = None,  # scalar fp32 upper clip (PACT alpha)
    bl: int | None = None,  # output rows per grid step (None: VMEM-budgeted)
    bn: int | None = None,  # output channels per grid step (None: VMEM-budgeted)
    lane: int = 128,  # Cin padding granule (MXU lane width)
    interpret: bool | None = None,
    return_acc: bool = False,
) -> jax.Array:
    """Fused W8A8 'same' 1D convolution; fp32 out (int32 if ``return_acc``)."""
    assert act in (None, "relu"), act
    interpret = resolve_interpret(interpret)
    b, l, cin = x_q.shape
    k, cin2, cout = w_q.shape
    assert cin == cin2, (x_q.shape, w_q.shape)
    if bl is None or bn is None:
        picked = tiling.select_conv_tiles(
            b, l, cin, cout, k,
            lane=lane,
            has_bias=bias is not None and not return_acc,
            has_clip=clip is not None and not return_acc,
        )
        bl = picked.bl if bl is None else bl
        bn = picked.bn if bn is None else bn
    cin_p, cout_p, lout_p = _rup(cin, lane), _rup(cout, bn), _rup(l, bl)
    nblk = lout_p // bl
    pad_l = (k - 1) // 2
    has_halo = k > 1
    # HBM layout: one padded buffer ('same' zero pad baked in, so input row
    # l0 + t of tap t is a plain shifted read).  The halo is NOT a separate
    # tensor — it is a second BlockSpec view of this same buffer whose index
    # map points one length-block ahead; the trailing pad below gives the
    # last block's halo view somewhere to read.
    hr = tiling.conv_halo_rows(k) if has_halo else 0
    assert not has_halo or bl % hr == 0, (bl, hr)  # exact halo block index
    lp = lout_p + hr
    xp = jnp.pad(
        x_q, ((0, 0), (pad_l, lp - pad_l - l), (0, cin_p - cin))
    )  # (B, Lp, Cin_p) int8
    wp = jnp.pad(w_q, ((0, 0), (0, cin_p - cin), (0, cout_p - cout)))

    in_specs = [pl.BlockSpec((1, bl, cin_p), lambda bb, i, j: (bb, i, 0))]
    inputs: list = [xp]
    if has_halo:
        # Overlapping read of the padded main buffer: block index is in
        # halo-row granules, so step i's halo starts at row (i+1) * bl.
        mult = bl // hr
        in_specs.append(
            pl.BlockSpec((1, hr, cin_p), lambda bb, i, j: (bb, (i + 1) * mult, 0))
        )
        inputs.append(xp)
    in_specs.append(pl.BlockSpec((k, cin_p, bn), lambda bb, i, j: (0, 0, j)))
    inputs.append(wp)
    has_bias = bias is not None and not return_acc
    has_clip = clip is not None and not return_acc
    if not return_acc:
        ws = jnp.broadcast_to(
            w_scale.astype(jnp.float32).reshape(1, -1), (1, cout)
        )
        # Activation scale: one scalar per batch row (a per-tensor scale is
        # broadcast), so each grid step reads its own sample's dequant scale
        # — this is what lets co-batched streams quantise independently.  The
        # row axis leads a (b, 1, 1) array so the (1, 1, 1) block's last two
        # dims equal the array's, which the TPU lowering requires of a block
        # that is not (8, 128)-aligned.
        xs = jnp.broadcast_to(
            jnp.asarray(x_scale, jnp.float32).reshape(-1, 1, 1), (b, 1, 1)
        )
        inputs += [
            xs,
            jnp.pad(ws, ((0, 0), (0, cout_p - cout)), constant_values=1.0),
        ]
        in_specs += [
            pl.BlockSpec((1, 1, 1), lambda bb, i, j: (bb, 0, 0)),
            pl.BlockSpec((1, bn), lambda bb, i, j: (0, j)),
        ]
        if has_bias:
            bv = jnp.broadcast_to(bias.astype(jnp.float32).reshape(1, -1), (1, cout))
            inputs.append(jnp.pad(bv, ((0, 0), (0, cout_p - cout))))
            in_specs.append(pl.BlockSpec((1, bn), lambda bb, i, j: (0, j)))
        if has_clip:
            inputs.append(jnp.asarray(clip, jnp.float32).reshape(1, 1))
            in_specs.append(pl.BlockSpec((1, 1), lambda bb, i, j: (0, 0)))

    out_dtype = jnp.int32 if return_acc else jnp.float32
    out = pl.pallas_call(
        functools.partial(
            _kernel,
            k=k,
            bl=bl,
            act=act,
            has_halo=has_halo,
            has_bias=has_bias,
            has_clip=has_clip,
            return_acc=return_acc,
        ),
        grid=(b, nblk, cout_p // bn),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bl, bn), lambda bb, i, j: (bb, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, lout_p, cout_p), out_dtype),
        interpret=interpret,
    )(*inputs)
    return out[:, :l, :cout]


def conv1d_fused(
    x: jax.Array,  # (B, L, Cin) fp32
    w: jax.Array,  # (K, Cin, Cout) fp32
    bias: jax.Array | None = None,
    *,
    fxp: bool = False,
    act: str | None = None,
    clip: jax.Array | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Quantise fp32 operands and run the fused conv kernel.

    Uses the same quantisers and axes as ``conv1d_q`` (per-tensor
    activations, per-output-channel weights) so the two paths see bitwise
    identical int8 payloads.
    """
    quant = fxp8_quantize if fxp else int8_symmetric
    xq: QTensor = quant(x, axis=None)
    wq: QTensor = quant(w, axis=2)
    return conv1d_fused_q(
        xq.q,
        wq.q,
        xq.scale,
        wq.scale,
        bias,
        act=act,
        clip=clip,
        interpret=interpret,
    )


def _rup(x: int, b: int) -> int:
    return (x + b - 1) // b * b
