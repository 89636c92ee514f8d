"""Jitted query kernels over a published snapshot.

Three read ops, all compiled once per (shape, k) and cached by jit:

  * ``lookup(ids)`` — embedding pull: the store's sharded gather
    (:func:`..core.store.pull`) against the snapshot table;
  * ``score(user_ids, item_ids)`` — MF dot-product scoring of explicit
    (user, item) pairs;
  * ``top_k(user_ids, k, exclude=...)`` — exact top-K recommendation
    reusing :func:`..ops.topk.sharded_topk` through
    :func:`..models.topk_recommender.query_topk` (per-shard MXU matmul
    + hierarchical ``top_k``, over-fetch + mask for excluded/seen
    items) — the reference's top-K worker, answered from a snapshot.

The engine reads the snapshot pointer ONCE per call, so every answer is
internally consistent (table + user vectors + version from the same
publish) and carries its staleness as metadata.  User vectors come from
the snapshot's ``aux`` (the driver publishes worker state — MF user
factors) or from a static array passed at construction.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import store as store_mod
from ..core.store import ShardedParamStore
from ..models.topk_recommender import query_topk
from ..telemetry.spans import NULL_TRACER
from .snapshot import SnapshotManager, TableSnapshot

Array = jax.Array


class NoSnapshotError(RuntimeError):
    """Query arrived before the first snapshot publish."""


@dataclasses.dataclass(frozen=True)
class TopKResult:
    """One batch of top-K answers + the snapshot provenance they came
    from.  ``item_ids`` lanes with no real candidate (catalogue smaller
    than k, or excluded) are -1 with ``-inf`` scores — the ops-level
    padding convention."""

    scores: np.ndarray  # (B, k) float
    item_ids: np.ndarray  # (B, k) int
    version: int
    train_step: int
    staleness: int


@dataclasses.dataclass(frozen=True)
class LookupResult:
    values: np.ndarray  # (B, *value_shape)
    version: int
    train_step: int
    staleness: int


class QueryEngine:
    """Snapshot-read kernels with jit-cached programs.

    One engine serves many concurrent callers: jax dispatch is
    thread-safe, snapshots are immutable, and the only mutable state
    here is the jit-function cache (guarded by the GIL — worst case a
    duplicate trace, never a wrong answer).
    """

    def __init__(
        self,
        snapshots: SnapshotManager,
        *,
        user_vectors: Optional[Array] = None,
    ):
        self.snapshots = snapshots
        self._static_user_vectors = user_vectors
        self._fns: Dict[Any, Any] = {}
        # ServingService.attach_tracer hands the driver's: a read then
        # records ``serving.<op>_enqueue`` until its jitted call returns
        # and ``serving.<op>_ready`` until the results are on the host
        self.tracer = NULL_TRACER

    # -- snapshot plumbing -------------------------------------------------
    def _snap(self) -> TableSnapshot:
        snap = self.snapshots.latest()
        if snap is None:
            raise NoSnapshotError(
                "no snapshot published yet (is the trainer running / did "
                "serve_with publish the initial table?)"
            )
        return snap

    def _user_vectors(self, snap: TableSnapshot) -> Array:
        aux = snap.aux
        if aux is not None and hasattr(aux, "ndim") and aux.ndim == 2:
            return aux
        if self._static_user_vectors is not None:
            return self._static_user_vectors
        raise ValueError(
            "top-K needs user vectors: publish the worker state as the "
            "snapshot aux (StreamingDriver.serve_with does) or pass "
            "user_vectors= to the QueryEngine"
        )

    # -- compiled read ops -------------------------------------------------
    def _lookup_fn(self):
        key = "lookup"
        if key not in self._fns:
            spec = self.snapshots.spec

            def fn(table, ids):
                return store_mod.pull(spec, table, ids)

            self._fns[key] = jax.jit(fn)
        return self._fns[key]

    def _score_fn(self):
        key = "score"
        if key not in self._fns:
            spec = self.snapshots.spec

            def fn(table, user_vecs, user_ids, item_ids):
                q = jnp.take(user_vecs, user_ids.astype(jnp.int32), axis=0)
                v = store_mod.pull(spec, table, item_ids)
                return jnp.sum(q * v, axis=-1)

            self._fns[key] = jax.jit(fn)
        return self._fns[key]

    def _topk_fn(self, k: int, has_exclude: bool):
        key = ("topk", int(k), bool(has_exclude))
        if key not in self._fns:
            spec = self.snapshots.spec

            if has_exclude:

                def fn(table, user_vecs, user_ids, exclude):
                    return query_topk(
                        ShardedParamStore(spec, table),
                        user_vecs, user_ids, k, exclude=exclude,
                    )

            else:

                def fn(table, user_vecs, user_ids):
                    return query_topk(
                        ShardedParamStore(spec, table),
                        user_vecs, user_ids, k,
                    )

            self._fns[key] = jax.jit(fn)
        return self._fns[key]

    # -- public query surface ----------------------------------------------
    def lookup(self, ids) -> LookupResult:
        """Batched embedding pull against the latest snapshot."""
        snap = self._snap()
        with self.tracer.span("lookup_enqueue", component="serving"):
            ids = jnp.asarray(np.asarray(ids, dtype=np.int32))
            vals = self._lookup_fn()(snap.table, ids)
        with self.tracer.span("lookup_ready", component="serving"):
            vals = np.asarray(vals)
        return LookupResult(
            values=vals,
            version=snap.version,
            train_step=snap.train_step,
            staleness=self.snapshots.staleness_of(snap),
        )

    def score(self, user_ids, item_ids) -> LookupResult:
        """MF dot-product scores for aligned (user, item) id pairs."""
        snap = self._snap()
        uv = self._user_vectors(snap)
        scores = self._score_fn()(
            snap.table, uv,
            jnp.asarray(np.asarray(user_ids, np.int32)),
            jnp.asarray(np.asarray(item_ids, np.int32)),
        )
        return LookupResult(
            values=np.asarray(scores),
            version=snap.version,
            train_step=snap.train_step,
            staleness=self.snapshots.staleness_of(snap),
        )

    def top_k(
        self, user_ids, k: int, *, exclude=None
    ) -> TopKResult:
        """Exact top-K items for ``user_ids`` (B,), excluding the
        (B, E) ``exclude`` ids (pad unused lanes with -1)."""
        if k < 1:
            raise ValueError(f"k={k}: must be >= 1")
        snap = self._snap()
        uv = self._user_vectors(snap)
        with self.tracer.span("topk_enqueue", component="serving"):
            uids = jnp.asarray(np.asarray(user_ids, np.int32))
            if exclude is not None:
                excl = jnp.asarray(np.asarray(exclude, np.int32))
                scores, ids = self._topk_fn(k, True)(
                    snap.table, uv, uids, excl
                )
            else:
                scores, ids = self._topk_fn(k, False)(snap.table, uv, uids)
        with self.tracer.span("topk_ready", component="serving"):
            scores, ids = np.asarray(scores), np.asarray(ids)
        return TopKResult(
            scores=scores,
            item_ids=ids,
            version=snap.version,
            train_step=snap.train_step,
            staleness=self.snapshots.staleness_of(snap),
        )


__all__ = ["QueryEngine", "TopKResult", "LookupResult", "NoSnapshotError"]
