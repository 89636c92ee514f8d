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

Skew note: hot ids (Criteo, word2vec) all land on one shard under block
layout just as under the reference's mod-hash; :mod:`..ops.hashing` provides
an affine id-permutation to spread them.
"""
from __future__ import annotations

import warnings
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
    impl: str = "xla",
    ids_sorted: bool = False,
) -> Array:
    """Sharded scatter-add: each ``ps`` shard folds in only the rows it
    owns.  When a ``dp`` axis exists, each worker's deltas are first
    all-gathered over ``dp`` (the worker→server "shuffle", now one ICI
    collective) and then locally scatter-added.

    ``impl="pallas"``: each shard's local scatter runs the sorted-run
    duplicate-compressing kernel (:mod:`..ops.pallas_scatter`) — one HBM
    read-modify-write per unique local row under Zipf-hot ids.
    ``impl="xla_sorted"``: the same dedup in pure XLA
    (:mod:`..ops.sorted_scatter`) — no Mosaic shape constraints.

    ``ids_sorted=True`` (xla_sorted only): the caller promises GLOBALLY
    ascending flat ids (batch presort).  The dp split is then contiguous
    chunks of a sorted array and the tiled all_gather reassembles them
    in dp order, so each shard sees ascending ids — the per-shard
    argsort + delta permute are skipped entirely (the op handles each
    shard's out-of-range lanes order-preservingly; see
    :func:`..ops.sorted_scatter.sorted_dedup_scatter_add`).
    """
    value_rank = table.ndim - 1
    if impl == "pallas":
        # Real Mosaic's shape rules:
        # compiled kernels need 128-aligned row widths and 8-aligned
        # per-shard capacities.  Fall back observably, never silently.
        from ..ops.pallas_scatter import supports_shape

        rows_per_shard = table.shape[0] // mesh.shape[ps_axis]
        row_width = 1
        for s in table.shape[1:]:
            row_width *= s
        if jax.default_backend() == "tpu" and not supports_shape(
            rows_per_shard, row_width
        ):
            warnings.warn(
                f"shard_push_add impl='pallas' falling back to XLA "
                f"scatter: per-shard table ({rows_per_shard}, {row_width}) "
                f"violates Mosaic alignment (need rows % 8 == 0, "
                f"width % 128 == 0)",
                RuntimeWarning,
                stacklevel=2,
            )
            impl = "xla"
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
        if impl == "pallas":
            # the public wrapper owns the lane prep (mask→zero-delta,
            # sort, sentinel handling) — don't duplicate it here
            from ..ops.pallas_scatter import scatter_add as pallas_scatter_add

            return pallas_scatter_add(
                local_table,
                rel,
                local_deltas.reshape((-1,) + local_table.shape[1:]),
                hit,
            )
        if impl == "xla_sorted":
            from ..ops.sorted_scatter import sorted_dedup_scatter_add

            # under ids_sorted the op itself keeps invalid lanes
            # order-preserving (zero-delta + monotone clip) — the
            # ascending rel = [negatives][this shard's run][>= rows]
            # needs no caller-side prep
            return sorted_dedup_scatter_add(
                local_table,
                rel,
                local_deltas.reshape((-1,) + local_table.shape[1:]),
                hit,
                oob=rows,
                ids_sorted=ids_sorted,
            )
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
