"""Online matrix factorisation by SGD — the plain reference.

One bulk-synchronous step over a microbatch of ratings ``(u, i, r)``: every
record reads the user and item vectors as they stood BEFORE the step,

    e = r - <p_u, q_i>;   dp_u = lr * e * q_i;   dq_i = lr * e * p_u

and the deltas of records that share a user or an item are summed
(``np.add.at``).  No regularisation (the configuration sets none).
Rows are addressed by position in the compact ``touched`` id lists, so the
reference holds only the rows the checked batches touch.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from chipbench.references import padded_unique


def touched(batches: List[Dict[str, np.ndarray]]) -> Dict[str, np.ndarray]:
    return {
        "user": padded_unique(np.concatenate([b["user"] for b in batches])),
        "item": padded_unique(np.concatenate([b["item"] for b in batches])),
    }


def apply(
    cfg: dict, rows: Dict[str, np.ndarray], ids: Dict[str, np.ndarray],
    batches: List[Dict[str, np.ndarray]],
) -> tuple:
    """``rows["user"|"item"]`` (float32, one row per ``ids`` entry) after
    the batches, in order, and beside them how far every element was moved
    in all (the sum of its deltas' magnitudes: what a summation error of the
    system under test can be a share of)."""
    lr = np.float32(cfg["learning_rate"])
    users = rows["user"].astype(np.float32).copy()
    items = rows["item"].astype(np.float32).copy()
    moved_u, moved_i = np.zeros_like(users), np.zeros_like(items)
    for b in batches:
        u = np.searchsorted(ids["user"], b["user"])
        i = np.searchsorted(ids["item"], b["item"])
        p, q = users[u], items[i]
        err = (b["rating"].astype(np.float32) - np.sum(p * q, axis=-1))
        err = (err * b["mask"])[:, None]
        du, di = lr * err * q, lr * err * p
        np.add.at(users, u, du)
        np.add.at(items, i, di)
        np.add.at(moved_u, u, np.abs(du))
        np.add.at(moved_i, i, np.abs(di))
    # the padding repeats the largest id: every repeat shows that id's row
    at_u = np.searchsorted(ids["user"], ids["user"])
    at_i = np.searchsorted(ids["item"], ids["item"])
    return (
        {"user": users[at_u], "item": items[at_i]},
        {"user": moved_u[at_u], "item": moved_i[at_i]},
    )


def topk_holds(
    user_vec: np.ndarray, item_table: np.ndarray, got_ids: np.ndarray,
    got_scores: np.ndarray, *, rtol: float, atol: float,
) -> bool:
    """Is ``(got_ids, got_scores)`` an exact top-K of ``item_table @
    user_vec`` up to rounding?  Scores are compared, not ids: near-ties
    may swap places under the device's matmul rounding."""
    scores = item_table.astype(np.float32) @ user_vec.astype(np.float32)
    k = len(got_ids)
    best = np.sort(scores)[::-1][:k]
    return bool(
        np.allclose(scores[got_ids], got_scores, rtol=rtol, atol=atol)
        and np.allclose(
            np.sort(got_scores)[::-1], best, rtol=rtol, atol=atol
        )
    )
