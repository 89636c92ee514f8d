"""Online matrix factorisation by SGD over keyed workers — the plain
reference.

``references/mf.py``'s step, letter for letter, up to the deltas: every
record of a microbatch reads the user and item vectors as they stood BEFORE
the step, in float32,

    e = r - <p_u, q_i>;   dp_u = lr * e * q_i;   dq_i = lr * e * p_u

and the deltas of records that share a row are summed.  What differs is how
that sum is written down.  ``mf.py`` adds a row's deltas ONE BY ONE onto the
float32 row, in lane order (``np.add.at``), which is also, bit for bit, what
one chip's scatter-add does; every one of those additions rounds to the
ROW's last place, and the Zipf-hot item takes 46,900 of them a step here.
That rounding belongs to one order of one summation on one worker, not to
the result: four workers that each sum their own records and are then added
up (any partition of the stream does so) cannot reproduce it and need not.
So this reference states the step without an order: a row's deltas are
summed exactly (float64 holds the sum of 2^18 float32 values to far below a
float32's last place) and the row moves ONCE a step, rounded to float32
once.  The allowances of the comparison then cover what they name: the
order of a float32 summation of the deltas, rounding inside a delta, and the
rounding of the row the sum lands in.  It knows no workers: the keyed
microbatches are batches like any other.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from chipbench.references.mf import touched  # noqa: F401  (the same rows)


def _move_once(
    rows: np.ndarray, moved: np.ndarray, at: np.ndarray, deltas: np.ndarray
) -> None:
    """In place: every row of ``rows`` (float32) that ``at`` names takes the
    exact sum of its ``deltas`` ONCE, and ``moved`` the sum of their
    magnitudes.  Only the rows this batch touches are visited."""
    touched, slot = np.unique(at, return_inverse=True)
    total = np.zeros((len(touched),) + deltas.shape[1:], np.float64)
    np.add.at(total, slot, deltas.astype(np.float64))
    rows[touched] = (rows[touched].astype(np.float64) + total).astype(np.float32)
    np.add.at(moved, at, np.abs(deltas))


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
        _move_once(users, moved_u, u, du)
        _move_once(items, moved_i, i, di)
    # the padding repeats the largest id: every repeat shows that id's row
    at_u = np.searchsorted(ids["user"], ids["user"])
    at_i = np.searchsorted(ids["item"], ids["item"])
    return (
        {"user": users[at_u], "item": items[at_i]},
        {"user": moved_u[at_u], "item": moved_i[at_i]},
    )
