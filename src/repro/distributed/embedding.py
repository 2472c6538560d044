"""Distributed embedding gather (hillclimb lever: the one-hot-matmul fix).

With the embedding table sharded on the vocab axis, GSPMD lowers
``jnp.take(table, ids)`` to a one-hot matmul against the local vocab shard:
T x V/16 x D MACs per device — for gemma-2b train_4k that is 6.6e13 FLOPs
per device, ~2.5x the entire transformer forward.  The classic fix (Megatron
VocabParallelEmbedding) is a shard-local gather + mask + psum:

    each shard gathers ids that fall inside its vocab range (clipped
    dynamic-gather, zero elsewhere) and the partial embeddings all-reduce —
    collective cost = one activation all-reduce, compute cost ~ 0.

Enabled by ``ArchConfig.sharded_embed_gather`` (off for the paper-faithful
baseline; on in the optimized variants).  Falls back to plain take when no
mesh rules are active or the vocab axis is unsharded.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import active_rules


def embedding_gather(table: jax.Array, ids: jax.Array) -> jax.Array:
    """(V, D) table, (...,) int32 ids -> (..., D); vocab-parallel when the
    active sharding rules shard the vocab axis."""
    rules = active_rules()
    if rules is None:
        return jnp.take(table, ids, axis=0)
    vocab_axes = rules.mesh_axes_for("vocab")
    vocab_axes = tuple(a for a in vocab_axes if table.shape[0] % rules.mesh.shape[a] == 0)
    if not vocab_axes:
        return jnp.take(table, ids, axis=0)
    mesh = rules.mesh
    n_shards = int(np.prod([mesh.shape[a] for a in vocab_axes]))
    shard_v = table.shape[0] // n_shards
    batch_axes = rules.mesh_axes_for("batch")

    table_spec = P(vocab_axes if len(vocab_axes) > 1 else vocab_axes[0], None)
    ids_spec = P(batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None))
    out_spec = P(ids_spec[0] if len(ids_spec) else None, None)

    def local_gather(tbl, ids_l):
        # rank of this shard along the vocab axes (row-major combine)
        idx = jax.lax.axis_index(vocab_axes[0])
        for a in vocab_axes[1:]:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        lo = idx * shard_v
        rel = ids_l - lo
        hit = (rel >= 0) & (rel < shard_v)
        rel = jnp.clip(rel, 0, shard_v - 1)
        out = jnp.take(tbl, rel.reshape(-1), axis=0)
        out = jnp.where(hit.reshape(-1, 1), out, 0)
        for a in vocab_axes:
            out = jax.lax.psum(out, a)
        return out.reshape(ids_l.shape + (tbl.shape[1],))

    flat_ids = ids.reshape(ids.shape[0], -1)
    out = jax.shard_map(
        local_gather,
        mesh=mesh,
        in_specs=(table_spec, P(ids_spec[0] if len(ids_spec) else None, None)),
        out_specs=P(out_spec[0], None, None),
        check_vma=False,
    )(table, flat_ids)
    return out.reshape(ids.shape + (table.shape[1],))
