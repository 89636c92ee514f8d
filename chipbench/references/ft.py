"""fastText skip-gram (subword bags, negative sampling), SGD: the plain
reference.

One table of vectors in three key spaces (Bojanowski et al., TACL 2017,
section 3.2): input vectors ``z`` of words and of hashed n-gram buckets,
output vectors ``u`` of words.  A batch holds, for every pair, the centre's
BAG of input rows (the word's own, then its n-grams' buckets; ``-1`` in the
lanes a short bag leaves dead), the context's output row and the negatives':

    h     = (1 / m) sum_{q in bag} z_q            (m live lanes, duplicates kept)
    e_0   = sigmoid(<h, u_o>) - 1;  e_j = sigmoid(<h, u_{n_j}>)
    dz_q  = -lr sum_j e_j u_{t_j}                 for EVERY live q, not divided by m
    du_t  = -lr e_j h

One bulk-synchronous step over a microbatch: all rows are read as they
stood before the step, and a row that the batch's LIVE lanes name ``n``
times (as a word, a bucket, a context or a negative) takes the MEAN of the
``n`` deltas: every delta is divided by ``n`` and the quotients are summed
with ``np.add.at`` in batch order (a pair's bag lane by lane, then its
context, then its negatives).  A dead lane and every lane of a masked pair
move nothing and are counted by nothing.  The two blocks are followed
apart, ``"in"`` (the bags' rows) and ``"out"`` (the contexts' and
negatives'); ids are the store's.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def _padded_unique(ids: np.ndarray) -> np.ndarray:
    """``references.padded_unique`` over the ids that name a row: the dead
    lanes' -1 is no row, and the padding keeps the shape the seed's own."""
    ids = ids.reshape(-1)
    distinct = np.unique(ids[ids >= 0])
    return np.concatenate(
        [distinct, np.full(ids.size - distinct.size, distinct[-1], ids.dtype)]
    )


def _out_keys(b: Dict[str, np.ndarray]) -> np.ndarray:
    """``(B, 1 + k)``: a pair's context, then its negatives."""
    return np.concatenate([b["context"][:, None], b["negatives"]], axis=1)


def _live(b: Dict[str, np.ndarray]) -> tuple:
    """The live lanes of the bags ``(B, G)`` and of the output keys."""
    pair = b["mask"].astype(bool)[:, None]
    return (b["bag"] >= 0) & pair, np.broadcast_to(pair, _out_keys(b).shape)


def touched(batches: List[Dict[str, np.ndarray]]) -> Dict[str, np.ndarray]:
    return {
        "in": _padded_unique(np.concatenate([b["bag"] for b in batches])),
        "out": _padded_unique(np.concatenate([_out_keys(b) for b in batches])),
    }


def _sigmoid(x: np.ndarray) -> np.ndarray:
    return (np.float32(1) / (np.float32(1) + np.exp(-x))).astype(np.float32)


def apply(
    cfg: dict, rows: Dict[str, np.ndarray], ids: Dict[str, np.ndarray],
    batches: List[Dict[str, np.ndarray]],
) -> tuple:
    """``rows["in"|"out"]`` (float32, one vector per ``ids`` entry) after
    the batches, in order, and beside them how far every element was moved
    in all (the sum of its deltas' magnitudes: see ``mf.apply``)."""
    lr = np.float32(cfg["learning_rate"])
    z_rows = rows["in"].astype(np.float32).copy()
    u_rows = rows["out"].astype(np.float32).copy()
    moved_z, moved_u = np.zeros_like(z_rows), np.zeros_like(u_rows)
    capacity = 2 * int(cfg["vocab_size"]) + int(cfg["buckets"])
    for b in batches:
        in_bag, in_out = _live(b)
        bag, outs = b["bag"], _out_keys(b)
        # how many live lanes of the batch name each row, all key spaces
        n = np.bincount(
            np.concatenate([bag[in_bag], outs[in_out]]), minlength=capacity
        )
        at_z = np.searchsorted(ids["in"], np.where(in_bag, bag, ids["in"][0]))
        at_u = np.searchsorted(ids["out"], outs)  # (B, 1 + k)
        z = np.where(in_bag[..., None], z_rows[at_z], np.float32(0))
        m = np.maximum(in_bag.sum(axis=1), 1).astype(np.float32)
        h = z.sum(axis=1, dtype=np.float32) * (np.float32(1) / m)[:, None]
        u = u_rows[at_u]  # (B, 1 + k, d)
        e = _sigmoid(np.einsum("bd,bnd->bn", h, u))
        e[:, 0] -= np.float32(1)  # the context is the positive
        dz = -lr * np.einsum("bn,bnd->bd", e, u)  # (B, d), one a bag
        share_z = np.float32(1) / np.maximum(n[np.maximum(bag, 0)], 1).astype(
            np.float32
        )
        share_u = np.float32(1) / np.maximum(n[outs], 1).astype(np.float32)
        dz = (dz[:, None, :] * share_z[..., None])[in_bag]  # live lanes, in order
        du = (-lr * e[..., None] * h[:, None, :] * share_u[..., None])[in_out]
        np.add.at(z_rows, at_z[in_bag], dz)
        np.add.at(u_rows, at_u[in_out], du)
        np.add.at(moved_z, at_z[in_bag], np.abs(dz))
        np.add.at(moved_u, at_u[in_out], np.abs(du))
    # the padding repeats the largest id: every repeat shows that id's row
    at_i = np.searchsorted(ids["in"], ids["in"])
    at_o = np.searchsorted(ids["out"], ids["out"])
    return (
        {"in": z_rows[at_i], "out": u_rows[at_o]},
        {"in": moved_z[at_i], "out": moved_u[at_o]},
    )
