"""Degree-2 factorisation machine, logistic loss, SGD — the plain reference.

Row ``f`` of the table is ``(w_f, v_f[0..d))``.  For an example with active
features ``f_1..f_K`` and values ``x``:

    s     = sum_k x_k v_k
    y_hat = sum_k w_k x_k + 0.5 * (|s|^2 - sum_k |x_k v_k|^2)
    g     = -y * sigmoid(-y * y_hat)                      (y in {-1, +1})
    dw_k  = -lr * g * x_k;   dv_k = -lr * g * x_k * (s - x_k v_k)

All rows are read as they stood before the step; deltas of duplicate ids
(within an example or across the batch) are summed with ``np.add.at``.
No L2 term (the configuration sets none).
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from chipbench.references import padded_unique


def touched(batches: List[Dict[str, np.ndarray]]) -> Dict[str, np.ndarray]:
    return {"feature": padded_unique(np.concatenate(
        [b["ids"].reshape(-1) for b in batches]
    ))}


def apply(
    cfg: dict, rows: Dict[str, np.ndarray], ids: Dict[str, np.ndarray],
    batches: List[Dict[str, np.ndarray]],
) -> tuple:
    lr = np.float32(cfg["learning_rate"])
    table = rows["feature"].astype(np.float32).copy()
    moved = np.zeros_like(table)  # sum of |delta| an element: see mf.apply
    for b in batches:
        pos = np.searchsorted(ids["feature"], b["ids"])  # (B, K)
        x = np.where(b["feat_mask"], b["values"], 0).astype(np.float32)
        pulled = table[pos]  # (B, K, 1 + d)
        w, v = pulled[..., 0], pulled[..., 1:]
        xv = x[..., None] * v
        s = xv.sum(axis=1)
        y_hat = (w * x).sum(axis=-1) + 0.5 * (
            (s * s).sum(axis=-1) - (xv * xv).sum(axis=(1, 2))
        )
        y = b["label"].astype(np.float32)
        g = -y / (1.0 + np.exp(y * y_hat))
        dw = g[:, None] * x
        dv = g[:, None, None] * (x[..., None] * (s[:, None, :] - xv))
        deltas = -lr * np.concatenate([dw[..., None], dv], axis=-1)
        live = (b["feat_mask"] & b["mask"][:, None])[..., None]
        deltas = (deltas * live).reshape(-1, table.shape[1]).astype(np.float32)
        np.add.at(table, pos.reshape(-1), deltas)
        np.add.at(moved, pos.reshape(-1), np.abs(deltas))
    # the padding repeats the largest id: every repeat shows that id's row
    at = np.searchsorted(ids["feature"], ids["feature"])
    return {"feature": table[at]}, {"feature": moved[at]}
