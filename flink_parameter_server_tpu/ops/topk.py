"""Top-K maximum-inner-product search over the sharded item table.

Reference parity (SURVEY.md §2 #8): the reference's top-K recommendation
uses **LEMP-style pruning** (length-based candidate pruning with LI / COORD
/ INCR strategies) to avoid scoring every item per query on a CPU.  On TPU
the economics invert: a dense ``(B, dim) @ (dim, rows)`` block on the MXU
scores millions of items faster than branchy pruning, so we verify *output
parity, not mechanism parity* (SURVEY.md §7 "Hard parts"): exact top-K via

  1. each ``ps`` shard scores its rows with one matmul and takes a local
     ``lax.top_k`` (the TPU analogue of LEMP's bucket pruning — candidates
     are cut from ``rows`` to ``k`` *before* any communication),
  2. one all-gather of the per-shard (k scores, k ids) over ICI,
  3. a final ``top_k`` over ``shards·k`` candidates.

Communication is ``O(shards·k)`` per query instead of ``O(rows)`` — the
same asymptotic saving LEMP's pruning buys the reference.

All functions keep a static ``(B, k)`` output shape: when fewer than ``k``
candidates exist, the tail is padded with ``-inf`` scores and id ``-1``.

Round-5 decision note: an earlier ``approx_recall`` parameter routed the
row scan to ``jax.lax.approx_max_k`` (the TPU approximate-top-k unit).
Off-TPU that op computes exactly, so its recall/speedup claim at our
shapes was untestable in this environment, and no hardware window opened
across rounds 3–5 to measure it — per the round-4 verdict's decision
rule the unproven parameter was REMOVED from the public surface;
reinstating it is a two-line change once a chip run (recall + speedup at
1M rows) shows a win.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array


def _pad_topk(scores: Array, ids: Array, k: int) -> Tuple[Array, Array]:
    """Pad a (B, k_eff) top-k result out to the requested static k."""
    k_eff = scores.shape[-1]
    if k_eff >= k:
        return scores[..., :k], ids[..., :k]
    pad = k - k_eff
    scores = jnp.pad(scores, ((0, 0), (0, pad)), constant_values=-jnp.inf)
    ids = jnp.pad(ids, ((0, 0), (0, pad)), constant_values=-1)
    return scores, ids


def dense_topk(
    table: Array,
    queries: Array,
    k: int,
    *,
    valid_rows: Optional[int] = None,
) -> Tuple[Array, Array]:
    """Single-device exact top-k: one MXU matmul + ``lax.top_k``.

    Returns (scores (B,k), ids (B,k)); padded with -inf/-1 when the table
    has fewer than ``k`` rows."""
    scores = queries @ table.T  # (B, rows)
    if valid_rows is not None and valid_rows < table.shape[0]:
        pad = jnp.arange(table.shape[0]) >= valid_rows
        scores = jnp.where(pad[None, :], -jnp.inf, scores)
    k_eff = min(k, table.shape[0])
    top_scores, top_ids = jax.lax.top_k(scores, k_eff)
    return _pad_topk(top_scores, top_ids, k)


def sharded_topk(
    table: Array,
    queries: Array,
    k: int,
    *,
    mesh: Mesh,
    ps_axis: str = "ps",
    valid_rows: Optional[int] = None,
) -> Tuple[Array, Array]:
    """Exact top-k over a ps-sharded table (see module docstring).

    ``table``: (padded_rows, dim) sharded P(ps, None).
    ``queries``: (B, dim), replicated.
    Returns replicated (scores (B,k), ids (B,k)) with *global* row ids,
    padded with -inf/-1 when fewer than ``k`` rows exist.
    """
    num_shards = mesh.shape[ps_axis]

    def body(local_table: Array, q: Array):
        rows = local_table.shape[0]
        shard = jax.lax.axis_index(ps_axis)
        lo = shard * rows
        scores = q @ local_table.T  # (B, rows_local) — MXU block
        if valid_rows is not None:
            global_row = lo + jnp.arange(rows)
            scores = jnp.where(
                (global_row >= valid_rows)[None, :], -jnp.inf, scores
            )
        kk = min(k, rows)
        local_scores, local_ids = jax.lax.top_k(scores, kk)
        local_ids = local_ids + lo
        # all-gather candidates over ICI: (shards, B, kk) → (B, shards*kk)
        all_scores = jax.lax.all_gather(local_scores, ps_axis)
        all_ids = jax.lax.all_gather(local_ids, ps_axis)
        all_scores = jnp.moveaxis(all_scores, 0, 1).reshape(q.shape[0], -1)
        all_ids = jnp.moveaxis(all_ids, 0, 1).reshape(q.shape[0], -1)
        k_eff = min(k, num_shards * kk)
        final_scores, pos = jax.lax.top_k(all_scores, k_eff)
        final_ids = jnp.take_along_axis(all_ids, pos, axis=1)
        return _pad_topk(final_scores, final_ids, k)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(ps_axis, None), P(*(None,) * queries.ndim)),
        out_specs=(P(None, None), P(None, None)),
        # After the all_gather every ps shard computes the identical final
        # top-k; the VMA checker can't infer that replication statically.
        check_vma=False,
    )(table, queries)


__all__ = ["dense_topk", "sharded_topk"]
