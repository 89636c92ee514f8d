"""Online MF with top-K recommendation serving.

Reference parity (SURVEY.md §2 #8, §3.3): the reference's
``PSOnlineMatrixFactorizationAndTopK`` interleaves top-K item queries with
the rating stream: per event it serves the querying user's top-K items from
the worker-local user vector + pulled item vectors, pruned LEMP-style.

TPU-first: training stays the batched MF step; serving is
:func:`..ops.topk.sharded_topk` — exact MIPS via per-shard MXU matmul +
hierarchical ``top_k`` (output parity with LEMP, not mechanism parity).
``query_topk`` answers a batch of user queries in one jitted call;
``MFWithTopK`` interleaves a query per training microbatch the way the
reference interleaves query events in the input stream.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.store import ShardedParamStore
from ..ops.topk import dense_topk, sharded_topk
from .matrix_factorization import OnlineMatrixFactorization

Array = jax.Array


def _logical_table(spec, table: Array) -> Array:
    """MIPS needs LOGICAL rows; unpacking a lane-packed table is a
    reshape (+ a slice when the physical row carries pad lanes) — free
    under jit, so serving composes with the packed training layout.
    The unpacked view is (padded_capacity, d); ``valid_rows`` masks the
    padding rows at the topk call sites.

    Gate on the layout alone: even at pack == 1 (row width 65-127) the
    physical rows are lane-PADDED to width 128, so the raw table would
    shape-mismatch ``queries @ table.T`` — ``unpack_table`` handles
    pack == 1 by slicing off the pad lanes."""
    if spec.layout == "packed":
        from ..ops.packed import unpack_table

        return unpack_table(table, spec.padded_capacity, spec.row_width)
    if spec.tile_lanes:  # a narrow rule row's zero lanes
        return table[:, : spec.row_width]
    return table


def query_topk(
    item_store: ShardedParamStore,
    user_vectors: Array,
    user_ids: Array,
    k: int,
    *,
    exclude: Optional[Array] = None,
) -> Tuple[Array, Array]:
    """Top-k items for ``user_ids`` (B,) given worker-state user vectors.

    ``exclude``: optional (B, E) item ids to mask out (already-rated items
    — the reference's recommenders exclude seen pairs).
    Returns (scores (B,k), item_ids (B,k)).  (The former ``approx_recall``
    parameter was removed — see the ops/topk.py decision note.)
    """
    spec = item_store.spec
    queries = jnp.take(user_vectors, user_ids.astype(jnp.int32), axis=0)

    table = _logical_table(spec, item_store.table)

    if exclude is None:
        if spec.mesh is not None:
            return sharded_topk(
                table, queries, k,
                mesh=spec.mesh, ps_axis=spec.ps_axis,
                valid_rows=spec.capacity,
            )
        return dense_topk(table, queries, k, valid_rows=spec.capacity)

    # With exclusions: over-fetch k+E candidates then drop excluded ones.
    e = exclude.shape[1]
    if spec.mesh is not None:
        scores, ids = sharded_topk(
            table, queries, k + e,
            mesh=spec.mesh, ps_axis=spec.ps_axis, valid_rows=spec.capacity,
        )
    else:
        scores, ids = dense_topk(
            table, queries, k + e, valid_rows=spec.capacity,
        )
    banned = (ids[:, :, None] == exclude[:, None, :]).any(-1)
    scores = jnp.where(banned, -jnp.inf, scores)
    re_scores, pos = jax.lax.top_k(scores, k)
    re_ids = jnp.take_along_axis(ids, pos, axis=1)
    # Lanes that survived only as -inf (banned or padding) carry no real
    # candidate: mark them id -1 like the ops-level padding convention.
    re_ids = jnp.where(jnp.isneginf(re_scores), -1, re_ids)
    return re_scores, re_ids


def make_mf_topk_step(logic: OnlineMatrixFactorization, spec, k: int):
    """Fused train+serve step: MF update plus a top-K answer for the
    batch's ``query_user`` ids — the batched analogue of the reference's
    interleaved query events in the rating stream.

    Queries are served against the *pre-push* table (bounded staleness of
    one microbatch — same semantics as training pulls).  Use in place of
    ``make_train_step`` and jit the result.
    """
    from ..core import store as store_mod

    def step(table, state, batch: Dict[str, Array]):
        ids = logic.keys(batch)
        pulled = store_mod.pull(spec, table, ids)
        new_state, req, out = logic.step(state, batch, pulled)
        if "query_user" in batch:
            q = jnp.take(
                new_state, batch["query_user"].astype(jnp.int32), axis=0
            )
            serve_table = _logical_table(spec, table)
            if spec.mesh is not None:
                scores, top_ids = sharded_topk(
                    serve_table, q, k,
                    mesh=spec.mesh, ps_axis=spec.ps_axis,
                    valid_rows=spec.capacity,
                )
            else:
                scores, top_ids = dense_topk(
                    serve_table, q, k, valid_rows=spec.capacity,
                )
            out = dict(out, topk_scores=scores, topk_ids=top_ids)
        table = store_mod.push(spec, table, req.ids, req.deltas, req.mask)
        return table, new_state, out

    return step


__all__ = ["query_topk", "make_mf_topk_step"]
