"""Explicit shard_map pull/push — the ICI collective message plane.

Reference parity: this module *is* the rebuild's "distributed communication
backend" (SURVEY.md §2): it replaces Flink's Netty point-to-point keyed
routing (``partitionCustom(hash(paramId) % psParallelism)`` worker→server,
``workerPartitionIndex`` routing server→worker, iteration feedback edge)
with XLA collectives over ICI inside one jitted step.

Routing scheme (block layout): shard ``s`` of the ``ps`` axis owns rows
``[s·R, (s+1)·R)`` of the padded table (R = rows per shard).  For a pull:

  * every ``ps`` shard receives the (replicated-over-ps) id batch,
  * answers the ids it owns, zeros elsewhere,
  * one ``psum`` over ``ps`` assembles the full answer — a single
    all-reduce replaces the reference's two network hops + queueing per
    pull (SURVEY.md §3.1 "Boundary crossings").

For a push each shard keeps only its own rows' deltas and scatter-adds them
locally — zero cross-shard traffic (the partitioning does the routing).

Callers: none inside the package.  ``core/store.pull`` / ``push`` leave the
partitioning to GSPMD; routing ids to their owner with these two is ROADMAP
S9 step 2, whose PR calls them or deletes them (ROADMAP D13).

Skew note: hot ids (Criteo, word2vec) all land on one shard under block
layout just as under the reference's mod-hash; :mod:`..ops.hashing` provides
an affine id-permutation to spread them.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array


def _rows_per_shard(padded_capacity: int, num_shards: int) -> int:
    assert padded_capacity % num_shards == 0
    return padded_capacity // num_shards


def shard_pull(
    table: Array,
    ids: Array,
    *,
    mesh: Mesh,
    ps_axis: str = "ps",
    dp_axis: Optional[str] = "dp",
) -> Array:
    """Sharded gather via one psum over the ``ps`` axis.

    ``table``: (padded_capacity, *value_shape) sharded P(ps_axis, ...).
    ``ids``:   (..., n) int32, sharded along ``dp`` on its leading dim (if a
    dp axis exists) and replicated over ``ps``.
    Returns values with ``ids``' shape + value_shape, sharded like ``ids``.
    """
    num_shards = mesh.shape[ps_axis]
    value_rank = table.ndim - 1
    vspec = (None,) * value_rank

    table_spec = P(ps_axis, *vspec)
    ids_spec = P(dp_axis, *((None,) * (ids.ndim - 1))) if dp_axis else P(
        *((None,) * ids.ndim)
    )
    out_spec = P(*(ids_spec + vspec)) if dp_axis else P(*((None,) * ids.ndim + vspec))

    def body(local_table: Array, local_ids: Array) -> Array:
        rows = local_table.shape[0]
        shard = jax.lax.axis_index(ps_axis)
        lo = shard * rows
        rel = local_ids - lo
        hit = (rel >= 0) & (rel < rows)
        rel = jnp.clip(rel, 0, rows - 1)
        vals = jnp.take(local_table, rel.reshape(-1), axis=0)
        vals = vals.reshape(local_ids.shape + local_table.shape[1:])
        vals = jnp.where(
            hit.reshape(hit.shape + (1,) * value_rank), vals, jnp.zeros_like(vals)
        )
        return jax.lax.psum(vals, ps_axis)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(table_spec, ids_spec),
        out_specs=out_spec,
    )(table, ids)


def shard_push_add(
    table: Array,
    ids: Array,
    deltas: Array,
    mask: Optional[Array] = None,
    *,
    mesh: Mesh,
    ps_axis: str = "ps",
    dp_axis: Optional[str] = "dp",
) -> Array:
    """Sharded scatter-add: each ``ps`` shard folds in only the rows it
    owns.  When a ``dp`` axis exists, each worker's deltas are first
    all-gathered over ``dp`` (the worker→server "shuffle", now one ICI
    collective) and then locally scatter-added.
    """
    value_rank = table.ndim - 1
    vspec = (None,) * value_rank
    table_spec = P(ps_axis, *vspec)
    lead = P(dp_axis) if dp_axis else P(None)
    ids_spec = P(*(lead + (None,) * (ids.ndim - 1)))
    deltas_spec = P(*(lead + (None,) * (deltas.ndim - 1)))
    mask_spec = P(*(lead + (None,) * (ids.ndim - 1)))

    def body(local_table, local_ids, local_deltas, local_mask):
        rows = local_table.shape[0]
        shard = jax.lax.axis_index(ps_axis)
        if dp_axis is not None:
            # Bring every worker's (ids, deltas) to every ps shard.
            local_ids = jax.lax.all_gather(local_ids, dp_axis, tiled=True)
            local_deltas = jax.lax.all_gather(local_deltas, dp_axis, tiled=True)
            local_mask = jax.lax.all_gather(local_mask, dp_axis, tiled=True)
        lo = shard * rows
        rel = local_ids.reshape(-1) - lo
        hit = (rel >= 0) & (rel < rows)
        hit = hit & local_mask.reshape(-1)
        rel = jnp.clip(rel, 0, rows - 1)
        d = local_deltas.reshape((-1,) + local_table.shape[1:])
        d = jnp.where(
            hit.reshape((-1,) + (1,) * value_rank), d, jnp.zeros_like(d)
        ).astype(local_table.dtype)
        return local_table.at[rel].add(d)

    if mask is None:
        mask = jnp.ones(ids.shape, dtype=bool)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(table_spec, ids_spec, deltas_spec, mask_spec),
        out_specs=table_spec,
        # After the all_gather over dp, every dp row computes identical
        # local tables; the checker can't infer that replication statically.
        check_vma=False,
    )(table, ids, deltas, mask)


__all__ = ["shard_pull", "shard_push_add"]
