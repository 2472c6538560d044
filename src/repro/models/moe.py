"""Mixture-of-Experts FFN with capacity-based scatter dispatch (EP-friendly).

Design goals (phi3.5-moe: 16e top-2; olmoe: 64e top-8):
* FLOPs proportional to *activated* experts (capacity-bounded), never dense
  over all experts — otherwise the roofline compute term lies.
* Shardable under GSPMD with experts on the "model" mesh axis: dispatch is a
  scatter into an (E, C, d) buffer and combine a gather back, both of which
  GSPMD lowers to all-to-all-style collectives across the EP axis.
* Router stays high-precision (the sensitivity framework pins it BF16+ —
  router logits are the most quantisation-sensitive tensors in an MoE).

Token-dropping semantics: tokens beyond an expert's capacity
C = ceil(T * top_k / E * capacity_factor) are dropped for that expert
(standard Switch/GShard behaviour); the residual path carries them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.distributed.sharding import constrain
from repro.models.layers import PSpec, qeinsum, rmsnorm, rmsnorm_specs


def moe_specs(cfg: ArchConfig) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "norm": rmsnorm_specs(d),
        "router": PSpec((d, e), ("embed", None), dtype="float32"),
        "wi_gate": PSpec((e, d, f), ("experts", "embed", "mlp")),
        "wi_up": PSpec((e, d, f), ("experts", "embed", "mlp")),
        "wo": PSpec((e, f, d), ("experts", "mlp", "embed")),
    }


def capacity(n_tokens: int, cfg: ArchConfig) -> int:
    c = int(np.ceil(n_tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor))
    return max(8, -(-c // 8) * 8)  # round up to 8 for clean layouts


def moe_fwd(p, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    """x: (B, S, D) -> (B, S, D) with residual."""
    b, s, d = x.shape
    h = rmsnorm(p["norm"], x, cfg.norm_eps)
    t = b * s
    ht = h.reshape(t, d)
    e, k = cfg.n_experts, cfg.top_k
    cap = capacity(t, cfg)

    logits = jnp.einsum("td,de->te", ht.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)  # (T, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # position of each (token, k) within its expert's capacity buffer
    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.int32)  # (T, k, E)
    flatoh = onehot.reshape(t * k, e)
    pos_in_e = jnp.cumsum(flatoh, axis=0) - flatoh  # exclusive per-expert rank
    pos = (pos_in_e * flatoh).sum(-1).reshape(t, k)  # (T, k)
    eid = gate_idx  # (T, k)
    keep = pos < cap  # capacity-dropped mask

    # scatter tokens into the (E, C, D) dispatch buffer
    buf = jnp.zeros((e, cap, d), h.dtype)
    tok_idx = jnp.broadcast_to(jnp.arange(t)[:, None], (t, k))
    e_flat = jnp.where(keep, eid, e - 1).reshape(-1)
    p_flat = jnp.where(keep, pos, cap - 1).reshape(-1)
    src = jnp.where(keep.reshape(-1, 1), ht[tok_idx.reshape(-1)], 0.0)
    buf = buf.at[e_flat, p_flat].add(src)  # each (e,pos) slot has one real writer
    buf = constrain(buf, ("experts", "expert_capacity", "embed"))

    # expert computation (grouped einsum, experts sharded on "model")
    g = jax.nn.silu(qeinsum("ecd,edf->ecf", buf, p["wi_gate"]))
    u = qeinsum("ecd,edf->ecf", buf, p["wi_up"])
    eo = qeinsum("ecf,efd->ecd", g * u, p["wo"])
    eo = constrain(eo, ("experts", "expert_capacity", "embed"))

    # gather back and combine with gate weights
    out_tk = eo[e_flat, p_flat].reshape(t, k, d)
    out_tk = jnp.where(keep[..., None], out_tk, 0.0)
    out = (out_tk * gate_vals[..., None].astype(out_tk.dtype)).sum(axis=1)
    y = out.reshape(b, s, d).astype(x.dtype)
    return x + constrain(y, ("batch", "seq", "embed"))


def moe_fwd_a2a(p, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    """Expert-parallel MoE via shard_map + all_to_all token routing.

    The capacity-scatter path above keeps the (E, C, d) buffer's capacity dim
    *global* — per-device expert compute then scales with global tokens (the
    dry-run measured olmoe at ~0.5% useful FLOPs).  Here tokens are split
    over ("data","model"); each device routes its local T/256 tokens, packs
    per-expert sends of local capacity, all_to_all's them across the model
    (EP) axis, runs its resident experts, and reverses the route — expert
    FLOPs per device = global/chips, and the only collectives are the two
    all_to_alls (+ the router's own psum-free local work).

    Falls back to ``moe_fwd`` when no mesh rules are active (CPU tests).
    """
    from repro.distributed.sharding import active_rules

    rules = active_rules()
    if rules is None or "model" not in rules.mesh.axis_names:
        return moe_fwd(p, x, cfg)
    mesh = rules.mesh
    n_ep = mesh.shape["model"]
    e, k = cfg.n_experts, cfg.top_k
    if e % n_ep != 0:
        return moe_fwd(p, x, cfg)
    b, s, d = x.shape
    tok_axes = tuple(
        a for a in (*rules.mesh_axes_for("batch"), "model") if a in mesh.axis_names
    )
    n_tok_shards = 1
    for a in tok_axes:
        n_tok_shards *= mesh.shape[a]
    t = b * s
    if t % n_tok_shards != 0:
        return moe_fwd(p, x, cfg)
    t_loc = t // n_tok_shards
    cap = max(8, -(-int(t_loc * k / e * cfg.capacity_factor) // 8) * 8)
    e_loc = e // n_ep

    h = rmsnorm(p["norm"], x, cfg.norm_eps)
    ht = h.reshape(t, d)
    xres = x.reshape(t, d)

    from jax.sharding import PartitionSpec as P

    def local(ht_l, router, wi_g, wi_u, wo):
        # ht_l: (t_loc, d); experts sharded: wi_* (e_loc, d, f)
        logits = jnp.einsum("td,de->te", ht_l.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, -1)
        gv, gi = jax.lax.top_k(probs, k)  # (t_loc, k)
        gv = gv / jnp.maximum(gv.sum(-1, keepdims=True), 1e-9)
        oh = jax.nn.one_hot(gi, e, dtype=jnp.int32).reshape(t_loc * k, e)
        pos = (jnp.cumsum(oh, axis=0) - oh)
        pos = (pos * oh).sum(-1).reshape(t_loc, k)
        keep = pos < cap
        ef = jnp.where(keep, gi, e - 1).reshape(-1)
        pf = jnp.where(keep, pos, cap - 1).reshape(-1)
        send = jnp.zeros((e, cap, d), ht_l.dtype)
        src = jnp.where(
            keep.reshape(-1, 1), ht_l[jnp.arange(t_loc).repeat(k)], 0.0
        )
        send = send.at[ef, pf].add(src)
        # route: (e, cap, d) -> (n_ep, e_loc, cap, d) -> a2a over model
        send = send.reshape(n_ep, e_loc, cap, d)
        recv = jax.lax.all_to_all(send, "model", split_axis=0, concat_axis=0, tiled=False)
        # recv: (n_ep senders, e_loc, cap, d) for MY resident experts
        buf = recv.transpose(1, 0, 2, 3).reshape(e_loc, n_ep * cap, d)  # slots = (sender, cap)
        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wi_g))
        u = jnp.einsum("ecd,edf->ecf", buf, wi_u)
        eo = jnp.einsum("ecf,efd->ecd", g * u, wo)  # (e_loc, S_slots, d)
        back = eo.transpose(1, 0, 2).reshape(n_ep, cap, e_loc, d).transpose(0, 2, 1, 3)
        out = jax.lax.all_to_all(back, "model", split_axis=0, concat_axis=0, tiled=False)
        # out: (n_ep expert-groups, e_loc, cap, d) == (e, cap, d) back at sender
        out = out.reshape(e, cap, d)
        got = out[ef, pf].reshape(t_loc, k, d)
        got = jnp.where(keep[..., None], got, 0.0)
        return (got * gv[..., None].astype(got.dtype)).sum(axis=1)

    tok_spec = P(tok_axes if len(tok_axes) > 1 else tok_axes[0])
    y = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(tok_spec[0], None),
            P(None, None),
            P("model", None, None),
            P("model", None, None),
            P("model", None, None),
        ),
        out_specs=P(tok_spec[0], None),
        check_vma=False,
    )(ht, p["router"], _deq(p["wi_gate"]), _deq(p["wi_up"]), _deq(p["wo"]))
    y = (xres + y.astype(x.dtype)).reshape(b, s, d)
    return constrain(y, ("batch", "seq", "embed"))


def _deq(w):
    from repro.core.quantization import QTensor

    if isinstance(w, QTensor):
        return w.q.astype(jnp.bfloat16) * w.scale.astype(jnp.bfloat16)
    return w


def moe_block(p, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    """Dispatch on cfg.moe_impl."""
    if cfg.moe_impl == "a2a":
        return moe_fwd_a2a(p, x, cfg)
    return moe_fwd(p, x, cfg)


def load_balance_loss(logits: jax.Array, gate_idx: jax.Array, n_experts: int) -> jax.Array:
    """Switch-style auxiliary load-balance loss (exposed for training)."""
    probs = jax.nn.softmax(logits, axis=-1)
    me = probs.mean(axis=0)
    ce = jnp.bincount(gate_idx.reshape(-1), length=n_experts) / gate_idx.size
    return n_experts * jnp.sum(me * ce)
