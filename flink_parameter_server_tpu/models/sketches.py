"""Streaming sketches on the PS: count-min / bloom co-occurrence and
tug-of-war (AMS) sketches, with time-aware decay.

Reference parity (SURVEY.md §2 #10): the reference ships PS-backed
distributed sketches over word/token streams — bloom-filter-based
co-occurrence counting and tug-of-war (AMS) style sketches, including
time-aware variants, used for streaming word-similarity experiments.

TPU-first: a sketch *is* a parameter store — a flat counter table sharded
over ``ps`` — and a sketch update *is* a push: hash the microbatch of items
with a vectorised hash family (one fused kernel,
:mod:`..ops.hashing`), scatter-add the counts.  Queries are pulls + a
min/median reduction.  The time-aware variant decays the whole table with
one fused scalar multiply per window tick (instead of per-cell timestamp
bookkeeping).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.batched import BatchedWorkerLogic, PushRequest
from ..core.store import ShardedParamStore
from ..ops.hashing import bucket_hash, hash_params, pair_key, sign_hash
from ..utils.initializers import zeros

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class CountMinConfig:
    width: int = 4096
    depth: int = 4
    seed: int = 0

    @property
    def capacity(self) -> int:
        return self.width * self.depth


class CountMinSketch(BatchedWorkerLogic):
    """Count-min over a keyed stream.  Batch: ``key`` (B,) int ids,
    optional ``count`` (B,), ``mask`` (B,).  The store is the flat
    (depth·width,) counter table; row d of the sketch occupies ids
    ``[d·width, (d+1)·width)``."""

    def __init__(self, config: CountMinConfig):
        self.config = config
        self._a, self._b = hash_params(config.depth, config.seed)
        self._row_offset = np.arange(config.depth, dtype=np.int64) * config.width

    def cells(self, keys: Array) -> Array:
        """(B, depth) flat cell ids for each key."""
        buckets = bucket_hash(keys, self._a, self._b, self.config.width)
        return buckets + jnp.asarray(self._row_offset, jnp.int32)[None, :]

    # -- BatchedWorkerLogic -------------------------------------------------
    def init_state(self, rng):
        return ()

    def keys(self, batch: Dict[str, Array]) -> Array:
        return self.cells(batch["key"])

    def step(self, state, batch: Dict[str, Array], pulled: Array):
        counts = batch.get("count")
        if counts is None:
            counts = jnp.ones_like(batch["key"], jnp.float32)
        deltas = jnp.broadcast_to(
            counts.astype(jnp.float32)[:, None], pulled.shape
        )
        mask = batch.get("mask")
        lane_mask = (
            jnp.broadcast_to(mask[:, None], deltas.shape) if mask is not None else None
        )
        # Estimate *before* this batch's increment (streaming pre-count).
        out = {"estimate": jnp.min(pulled, axis=1)}
        return state, PushRequest(self.keys(batch), deltas, lane_mask), out

    def make_store(self, *, mesh=None, **store_opts) -> ShardedParamStore:
        # store_opts reaches ShardedParamStore.create (layout=)
        return ShardedParamStore.create(
            self.config.capacity, (), init_fn=zeros(()), mesh=mesh,
            **store_opts,
        )

    def query(self, store: ShardedParamStore, keys: Array) -> Array:
        """Point estimate: min over the depth rows' cells."""
        return jnp.min(store.pull(self.cells(keys)), axis=1)

    def top_k(
        self, store: ShardedParamStore, candidate_ids: Array, k: int
    ) -> Tuple[Array, Array]:
        """Heavy hitters among ``candidate_ids``: (estimates, ids) of the
        k largest estimated counts — the streaming-experiment query the
        reference's sketches serve (estimate-then-rank), as one batched
        pull + ``lax.top_k``.  Static (k,) output: padded with -inf/-1
        when there are fewer candidates (the ops/topk.py convention)."""
        from ..ops.topk import _pad_topk

        est = self.query(store, candidate_ids)
        top_est, pos = jax.lax.top_k(est, min(k, candidate_ids.shape[0]))
        ids = jnp.take(candidate_ids, pos)
        top_est, ids = _pad_topk(top_est[None], ids[None], k)
        return top_est[0], ids[0]


class BloomCooccurrence(CountMinSketch):
    """Co-occurrence counting for unordered word pairs — the reference's
    bloom/co-occurrence sketch.  Batch: ``word_a``/``word_b`` (B,).
    Pair ids are formed with a mixing pairing function then count-min
    counted; :meth:`similarity` gives the normalised co-occurrence score
    used for streaming word similarity."""

    PAIR_SPACE = 1 << 30

    def keys(self, batch: Dict[str, Array]) -> Array:
        pk = pair_key(batch["word_a"], batch["word_b"], self.PAIR_SPACE)
        return self.cells(pk)

    def step(self, state, batch: Dict[str, Array], pulled: Array):
        b2 = dict(batch)
        b2["key"] = pair_key(batch["word_a"], batch["word_b"], self.PAIR_SPACE)
        return super().step(state, b2, pulled)

    def query_pair(self, store: ShardedParamStore, a: Array, b: Array) -> Array:
        return self.query(store, pair_key(a, b, self.PAIR_SPACE))

    def similarity(
        self,
        pair_store: ShardedParamStore,
        word_store: ShardedParamStore,
        word_sketch: "CountMinSketch",
        a: Array,
        b: Array,
        eps: float = 1e-6,
    ) -> Array:
        """Cosine-style similarity  c(a,b) / sqrt(c(a) c(b))."""
        cab = self.query_pair(pair_store, a, b)
        ca = word_sketch.query(word_store, a)
        cb = word_sketch.query(word_store, b)
        return cab / jnp.sqrt(jnp.maximum(ca * cb, eps))


@dataclasses.dataclass(frozen=True)
class TugOfWarConfig:
    """AMS F2 sketch: ``num_estimators = groups × per_group`` ±1 counters;
    estimate = median over groups of the mean of squared counters."""

    groups: int = 8
    per_group: int = 16
    seed: int = 1

    @property
    def num_estimators(self) -> int:
        return self.groups * self.per_group


class TugOfWarSketch(BatchedWorkerLogic):
    """Second-moment (F2) sketch over a keyed stream.  Every item updates
    *all* estimators (dense small push): z_j += s_j(key) · count."""

    def __init__(self, config: TugOfWarConfig):
        self.config = config
        self._a, self._b = hash_params(config.num_estimators, config.seed)
        self._est_ids = np.arange(config.num_estimators, dtype=np.int32)

    def init_state(self, rng):
        return ()

    def keys(self, batch: Dict[str, Array]) -> Array:
        B = batch["key"].shape[0]
        return jnp.broadcast_to(
            jnp.asarray(self._est_ids)[None, :], (B, self.config.num_estimators)
        )

    def step(self, state, batch: Dict[str, Array], pulled: Array):
        counts = batch.get("count")
        if counts is None:
            counts = jnp.ones_like(batch["key"], jnp.float32)
        signs = sign_hash(batch["key"], self._a, self._b)  # (B, E)
        deltas = signs * counts.astype(jnp.float32)[:, None]
        mask = batch.get("mask")
        lane_mask = (
            jnp.broadcast_to(mask[:, None], deltas.shape) if mask is not None else None
        )
        return state, PushRequest(self.keys(batch), deltas, lane_mask), {}

    def make_store(self, *, mesh=None, **store_opts) -> ShardedParamStore:
        return ShardedParamStore.create(
            self.config.num_estimators, (), init_fn=zeros(()), mesh=mesh,
            **store_opts,
        )

    def estimate_f2(self, store: ShardedParamStore) -> Array:
        """Median-of-means estimate of Σ f_x² from the counters."""
        z = store.values().reshape(self.config.groups, self.config.per_group)
        means = jnp.mean(z * z, axis=1)
        return jnp.median(means)


def decay(store: ShardedParamStore, gamma: float) -> ShardedParamStore:
    """Time-aware variant: exponentially decay every counter by ``gamma``
    (one fused multiply over the sharded table) — call once per time
    window, the TPU analogue of the reference's time-aware sketches."""
    return ShardedParamStore(store.spec, store.table * gamma)


__all__ = [
    "CountMinConfig",
    "CountMinSketch",
    "BloomCooccurrence",
    "TugOfWarConfig",
    "TugOfWarSketch",
    "decay",
]
