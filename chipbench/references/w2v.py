"""Skip-gram with negative sampling, SGD — the plain reference.

Every word has an input vector ``v_w`` and an output vector ``u_w`` (Mikolov
et al., NIPS 2013, section 2.2).  For a pair (centre ``c``, context ``o``)
with negatives ``j = 1..k``:

    pos   = <v_c, u_o>;        neg_j = <v_c, u_j>
    g_pos = sigmoid(pos) - 1;  g_j   = sigmoid(neg_j)
    dv_c  = -lr (g_pos u_o + sum_j g_j u_j)
    du_o  = -lr g_pos v_c;     du_j  = -lr g_j v_c

One bulk-synchronous step over a microbatch: all rows are read as they
stood before the step, and a word that the batch names ``n`` times (as a
centre, a context or a negative: its ``(2, dim)`` row is pulled ``n`` times)
takes the MEAN of the ``n`` deltas to its row: every delta is divided by
``n`` and the quotients are summed with ``np.add.at`` in batch order (a
pair's context, then its negatives).  A centre's delta has zeros in the
output slot and the others' in the input slot, so ``n`` counts both.
The two matrices are followed apart, ``"in"`` (the centres' ``v``) and
``"out"`` (the contexts' and negatives' ``u``): a batch moves no other
element, and the slot of a touched row that its batch does not address has
to come back bit-equal (a test holds the system to it).
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from chipbench.references import padded_unique


def _out_keys(b: Dict[str, np.ndarray]) -> np.ndarray:
    """``(B, 1 + k)``: a pair's context, then its negatives."""
    return np.concatenate([b["context"][:, None], b["negatives"]], axis=1)


def touched(batches: List[Dict[str, np.ndarray]]) -> Dict[str, np.ndarray]:
    return {
        "in": padded_unique(np.concatenate([b["center"] for b in batches])),
        "out": padded_unique(
            np.concatenate([_out_keys(b).reshape(-1) for b in batches])
        ),
    }


def _share(cfg: dict, b: Dict[str, np.ndarray]) -> np.ndarray:
    """``(B, 2 + k)`` float32: one over how many of the batch's pulled rows
    (masked pairs' centre, context and negatives alike) are the lane's word."""
    keys = np.concatenate([b["center"][:, None], _out_keys(b)], axis=1)
    live = np.broadcast_to(b["mask"][:, None], keys.shape)
    n = np.bincount(keys[live], minlength=int(cfg["vocab_size"]))
    return np.float32(1) / np.maximum(n[keys], 1).astype(np.float32)


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
    v_rows = rows["in"].astype(np.float32).copy()
    u_rows = rows["out"].astype(np.float32).copy()
    moved_v, moved_u = np.zeros_like(v_rows), np.zeros_like(u_rows)
    for b in batches:
        at_c = np.searchsorted(ids["in"], b["center"])  # (B,)
        at_u = np.searchsorted(ids["out"], _out_keys(b))  # (B, 1 + k)
        v, u = v_rows[at_c], u_rows[at_u]  # (B, d), (B, 1 + k, d)
        g = _sigmoid(np.einsum("bd,bnd->bn", v, u))
        g[:, 0] -= np.float32(1)  # the context is the positive
        g *= b["mask"][:, None]
        share = _share(cfg, b)  # (B, 2 + k): 1 / n of the lane's word
        dv = -lr * np.einsum("bn,bnd->bd", g, u) * share[:, :1]
        du = (
            -lr * g[..., None] * v[:, None, :] * share[:, 1:, None]
        ).reshape(-1, v.shape[1])
        np.add.at(v_rows, at_c, dv)
        np.add.at(u_rows, at_u.reshape(-1), du)
        np.add.at(moved_v, at_c, np.abs(dv))
        np.add.at(moved_u, at_u.reshape(-1), np.abs(du))
    # the padding repeats the largest id: every repeat shows that id's row
    at_v = np.searchsorted(ids["in"], ids["in"])
    at_o = np.searchsorted(ids["out"], ids["out"])
    return (
        {"in": v_rows[at_v], "out": u_rows[at_o]},
        {"in": moved_v[at_v], "out": moved_u[at_o]},
    )
