"""GloVe (Pennington, Socher, Manning, EMNLP 2014; ``stanfordnlp/GloVe``
``src/glove.c``) with AdaGrad's accumulators beside every weight, one
bulk-synchronous step a minibatch — the plain reference.

A word row and a context row are each ``(w[0..d), b, gw[0..d), gb)``: the
vector, its bias, and the accumulated squared gradients of both (``glove.c``'s
``W`` row and its ``gradsq`` row, which starts at 1).  All rows are read as
they stood before the step.  For a record ``(i, j, X)``, a nonzero of the
co-occurrence matrix (the paper's section 3, equations 8 and 9):

    diff = w_i . w~_j + b_i + b~_j - ln X
    f    = min(1, (X / x_max)^alpha)
    s    = f diff
    to word row i:      (s w~_j, s)
    to context row j:   (s w_i,  s)

summed record by record with ``np.add.at`` into ``G`` a row, and then once
for every row the batch names, ``glove.c``'s adaptive update (it multiplies
by ``eta`` before it squares; the accumulator is read BEFORE the square is
added):

    u = eta G;   param' = param - u / sqrt(g);   g' = g + u^2

The two halves of the table are followed apart, ``"word"`` and ``"context"``.

``moved`` is, lane by lane, what the check's ``delta_rtol`` multiplies: how
far a lane can be off for one part in ``1 / delta_rtol`` of error in the sums
it was made from, to first order, because the check knows no rule:

- ``G`` may be off by ``delta_rtol x A``, ``A = sum |addend|`` (the order of a
  float32 sum, the device's ``log`` and power).  ``param'`` takes ``eta /
  sqrt(g)`` of that, ``g'`` takes ``2 |u| eta`` of it.
- a later batch reads rows an earlier one wrote, within their allowance and
  not exactly: ``diff`` may differ by ``sum_k (|w~_jk| allow(w_ik) + |w_ik|
  allow(w~_jk)) + allow(b_i) + allow(b~_j)``, ``s`` by ``f`` times that, and
  every addend by its first-order share (``|w~_j| allow(s) + |s|
  allow(w~_j)``); the rule reads its own row's earlier allowance of ``g``
  (``|u| / (2 g^1.5)`` of it into ``param'``).  All of it enters ``A`` divided
  by ``delta_rtol``, so the check's one product covers it.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from chipbench.references import padded_unique

SIDES = ("word", "context")


def touched(batches: List[Dict[str, np.ndarray]]) -> Dict[str, np.ndarray]:
    return {
        side: padded_unique(np.concatenate([b[side] for b in batches]))
        for side in SIDES
    }


def apply(
    cfg: dict, rows: Dict[str, np.ndarray], ids: Dict[str, np.ndarray],
    batches: List[Dict[str, np.ndarray]],
) -> tuple:
    f32 = np.float32
    dim = int(cfg["dim"])
    p = dim + 1  # parameters of a row: the vector, then its bias
    eta, x_max, alpha = (f32(cfg[k]) for k in ("eta", "x_max", "alpha"))
    check = cfg["reference"]
    rtol, atol = float(check["delta_rtol"]), float(check["delta_atol"])
    ulps = float(check["row_ulps"]) * float(np.finfo(np.float32).eps)
    # the padding repeats the largest id: work on the distinct rows alone
    count = {
        s: int(np.searchsorted(ids[s], ids[s][-1])) + 1 for s in SIDES
    }
    before = {s: rows[s][:count[s]].astype(np.float32) for s in SIDES}
    table = {s: before[s].copy() for s in SIDES}
    moved = {s: np.zeros(before[s].shape) for s in SIDES}
    allow = {s: np.zeros(before[s].shape) for s in SIDES}
    ever = {s: np.zeros(count[s], bool) for s in SIDES}
    for b in batches:
        at = {s: np.searchsorted(ids[s][:count[s]], b[s]) for s in SIDES}
        pulled = {s: table[s][at[s]] for s in SIDES}
        vec = {s: pulled[s][:, :dim] for s in SIDES}
        x = b["count"].astype(np.float32)
        diff = (
            (vec["word"] * vec["context"]).sum(axis=-1, dtype=np.float32)
            + pulled["word"][:, dim] + pulled["context"][:, dim] - np.log(x)
        ).astype(np.float32)
        weight = np.minimum(f32(1), (x / x_max) ** alpha).astype(np.float32)
        s_ = (weight * diff).astype(np.float32)
        # what the gradients may inherit from the rows they were read from
        held = {s: allow[s][at[s]] for s in SIDES}
        diff_allow = (
            (np.abs(vec["context"]) * held["word"][:, :dim]).sum(axis=-1)
            + (np.abs(vec["word"]) * held["context"][:, :dim]).sum(axis=-1)
            + held["word"][:, dim] + held["context"][:, dim]
        )
        s_allow = weight.astype(np.float64) * diff_allow
        live = b["mask"].astype(bool)
        for side, other in (SIDES, SIDES[::-1]):
            n = count[side]
            addend = np.concatenate(
                [s_[:, None] * vec[other], s_[:, None]], axis=-1
            ).astype(np.float32)
            inherits = np.concatenate([
                np.abs(vec[other]) * s_allow[:, None]
                + np.abs(s_)[:, None] * held[other][:, :dim],
                s_allow[:, None],
            ], axis=-1)
            big = np.zeros((n, p), np.float32)
            spread = np.zeros((n, p))
            hit = np.zeros(n, bool)
            np.add.at(big, at[side][live], addend[live])
            np.add.at(
                spread, at[side][live],
                np.abs(addend[live]) + inherits[live] / rtol)
            hit[at[side][live]] = True
            param, acc = table[side][:, :p], table[side][:, p:]
            u = (eta * big).astype(np.float32)
            new = np.concatenate(
                [param - u / np.sqrt(acc), acc + u * u], axis=-1
            ).astype(np.float32)
            root = np.sqrt(acc.astype(np.float64))
            rows_hit = hit[:, None]
            moved[side][:, :p] += np.where(rows_hit, (
                float(eta) * spread / root
                + np.abs(u) / (2 * root ** 3) * allow[side][:, p:] / rtol
            ), 0)
            moved[side][:, p:] += np.where(
                rows_hit, 2 * np.abs(u) * float(eta) * spread, 0)
            table[side] = np.where(rows_hit, new, table[side])
            ever[side] |= hit
        for side in SIDES:
            # what the check allows each lane of a row some batch has named
            allow[side] = np.where(
                ever[side][:, None],
                rtol * moved[side] + atol + ulps * np.maximum(
                    np.abs(table[side]), np.abs(before[side])),
                0,
            )
    # every repeat of the padding shows the largest id's row
    back = {s: np.searchsorted(ids[s][:count[s]], ids[s]) for s in SIDES}
    return (
        {s: table[s][back[s]] for s in SIDES},
        {s: moved[s][back[s]].astype(np.float32) for s in SIDES},
    )
