"""Seeded input streams: the benchmark's copy of the program's sound
``data/movielens.synthetic_ratings``, a Criteo-shaped click record with one
key space a field, and the key distributions a traffic file can ask for.
Everything is a function of the seed alone.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List

import numpy as np


def draw_keys(rng, dist: dict, n, capacity) -> np.ndarray:
    """``n`` (an int or a shape) keys in ``[0, capacity)``: ``zipf`` ranks
    (exponent ``a``) wrapped into the key space, or ``uniform``.  ``capacity``
    may be an array that broadcasts against ``n``: a key space a column."""
    kind = dist["kind"]
    if kind == "zipf":
        return ((rng.zipf(float(dist["a"]), n) - 1) % capacity).astype(np.int32)
    if kind == "uniform":
        return rng.integers(0, capacity, n).astype(np.int32)
    raise ValueError(f"unknown key distribution {kind!r}")


def _batches(one, n_batches: int) -> List[Dict[str, np.ndarray]]:
    """``one(i)`` for every batch, on a few threads: each batch draws from its
    own generator (numpy's draws release the GIL), so the pool is the same
    whatever the thread count and set-up does not wait on one core."""
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as ex:
        return list(ex.map(one, range(n_batches)))


def rating_batches(
    num_users: int, num_items: int, batch: int, n_batches: int, *,
    item_keys: dict, seed: int, rank: int = 8, noise: float = 0.05,
) -> List[Dict[str, np.ndarray]]:
    """``synthetic_ratings``: low-rank ground truth, uniform users, items
    from ``item_keys``; ``n_batches`` full microbatches."""
    truth = np.random.default_rng([seed, 0])
    scale = np.float32(1.0 / np.sqrt(rank))
    p = truth.standard_normal((num_users, rank), np.float32) * scale
    q = truth.standard_normal((num_items, rank), np.float32) * scale
    mask = np.ones(batch, bool)

    def one(i):
        rng = np.random.default_rng([seed, i + 1])
        users = rng.integers(0, num_users, batch).astype(np.int32)
        items = draw_keys(rng, item_keys, batch, num_items)
        ratings = np.einsum("ij,ij->i", p[users], q[items])
        ratings += np.float32(noise) * rng.standard_normal(batch, np.float32)
        return {"user": users, "item": items, "rating": ratings, "mask": mask}

    return _batches(one, n_batches)


def click_batches(
    cardinalities, dense_fields: int, batch: int, n_batches: int, *,
    feature_keys: dict, seed: int,
) -> List[Dict[str, np.ndarray]]:
    """Criteo-shaped examples.  Rows ``[0, dense_fields)`` of the table are
    the integer fields, one fixed row each, active in every example with a
    numeric value uniform in [0, 1); every categorical field then owns
    ``cardinalities[f]`` rows of its own, draws one of them by
    ``feature_keys`` and carries the value 1.  Labels are +-1 with equal
    odds.  (Per-table uniform indices, uniform dense values and labels are
    what ``--data-generation=random`` of facebookresearch/dlrm draws.)"""
    cards = np.asarray(cardinalities, np.int64)
    first = dense_fields + np.concatenate([[0], np.cumsum(cards)[:-1]])
    fields = dense_fields + len(cards)
    dense_ids = np.broadcast_to(
        np.arange(dense_fields, dtype=np.int32), (batch, dense_fields)
    )

    def one(i):
        rng = np.random.default_rng([seed, i + 1])
        cat = draw_keys(rng, feature_keys, (batch, len(cards)), cards) + first
        values = np.ones((batch, fields), np.float32)
        values[:, :dense_fields] = rng.random((batch, dense_fields), np.float32)
        return {
            "ids": np.concatenate([dense_ids, cat.astype(np.int32)], axis=1),
            "values": values,
            "feat_mask": np.ones((batch, fields), bool),
            "label": rng.choice(np.array([-1.0, 1.0], np.float32), batch),
            "mask": np.ones(batch, bool),
        }

    return _batches(one, n_batches)
