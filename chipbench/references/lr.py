"""Sparse logistic regression, per-coordinate FTRL-Proximal with L1 and L2
(McMahan et al., KDD 2013, Algorithm 1), in its batch form — the plain
reference.

Row ``f`` of the table is ``(w_f, z_f, n_f)``.  All rows are read as they
stood before the step.  For an example with active features ``f_1..f_K``,
values ``x`` and label ``y`` in {-1, +1} (mapped to {0, 1}):

    p   = sigmoid(sum_k x_k w_k);   g_k = (p - y) x_k

(``p - y`` computed as ``-s / (1 + exp(s margin))``, ``s`` the label's sign:
the same number without the subtraction)

and for every row a batch touches, with ``G = sum g`` and ``S = sum g^2``
over the batch's deltas to it, summed in stream order with ``np.add.at``:

    n' = n + S;   s = (sqrt(n') - sqrt(n)) / alpha;   z' = z + G - s w
    w' = 0 if |z'| <= l1 else -(z' - sgn(z') l1) / ((beta + sqrt(n')) / alpha + l2)

The per-example steps of Algorithm 1 telescope to ``s``, so this is Algorithm
1 over the batch's examples with weights read at the start of the step.
``sqrt(n') - sqrt(n)`` is computed as ``S / (sqrt(n') + sqrt(n))``: the same
number without the cancellation, which in float32 costs a cold row
(``n`` ~ 32, ``S`` ~ 0.1) four of its seven digits.

``moved`` is how far the batches moved each element in magnitudes, so that
the check's ``delta_rtol x moved`` is that element's allowance: for ``z`` the
sum of ``|g|`` plus ``|s w|``, for ``n`` the sum of ``g^2``.  Two things are
carried through the arithmetic, to first order, because the check knows no
term for them:

- ``w`` is a function of the other two, so its allowance is theirs (the
  deltas' part and the rounding of the row they land in) carried through the
  rule: ``allow_z / D + |w'| allow_n / (2 alpha sqrt(n') D)`` with ``D = (beta
  + sqrt(n')) / alpha + l2``.  ``|dw/dz| <= 1 / D`` on BOTH sides of the
  threshold, where ``w`` is continuous (0 at ``|z'| = l1`` from either side):
  an element whose ``z'`` the system rounds to the other side of ``l1`` is
  off by at most its ``z`` error over ``D``, which this covers; none is
  skipped.
- a later batch reads rows an earlier one wrote, within their allowance and
  not exactly: a margin may then differ by ``sum_k |x_k| allow_w(k)``, a
  gradient by a quarter of that times ``|x|`` (``|sigmoid'| <= 1/4``), and
  ``s w`` by ``s allow_w``.  The 13 integer-field rows take every example of a
  batch, so what they inherit reaches every delta of the next batch.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from chipbench.references.fm import touched  # noqa: F401  (the same record)

TINY = np.float32(np.finfo(np.float32).tiny)


def weights(cfg: dict, z: np.ndarray, n: np.ndarray) -> tuple:
    """``(w, D)`` of Algorithm 1 from ``(z, n)``, float32."""
    alpha, beta, l1, l2 = (np.float32(cfg[k]) for k in ("alpha", "beta", "l1", "l2"))
    scale = (beta + np.sqrt(n)) / alpha + l2
    w = -(z - np.sign(z) * l1) / scale
    return np.where(np.abs(z) <= l1, np.float32(0), w).astype(np.float32), scale


def apply(
    cfg: dict, rows: Dict[str, np.ndarray], ids: Dict[str, np.ndarray],
    batches: List[Dict[str, np.ndarray]],
) -> tuple:
    alpha = np.float32(cfg["alpha"])
    check = cfg["reference"]
    rtol, atol = float(check["delta_rtol"]), float(check["delta_atol"])
    ulps = float(check["row_ulps"]) * float(np.finfo(np.float32).eps)
    before = rows["feature"].astype(np.float32)
    table = before.copy()
    rows_n = len(table)
    moved_w, moved_z, moved_n, allow_w = (np.zeros(rows_n) for _ in range(4))
    for b in batches:
        pos = np.searchsorted(ids["feature"], b["ids"])  # (B, K)
        x = np.where(b["feat_mask"], b["values"], 0).astype(np.float32)
        margin = (table[pos][..., 0] * x).sum(axis=-1)
        sign = np.where(b["label"] > 0, 1, -1).astype(np.float32)
        # p - y, written without the subtraction
        err = -sign / (np.float32(1) + np.exp(sign * margin))
        live = (b["feat_mask"] & b["mask"][:, None]).reshape(-1)
        at = pos.reshape(-1)[live]
        g = (err[:, None] * x).reshape(-1)[live].astype(np.float32)
        # what a gradient may inherit from the weights it was computed from
        inherited = (
            0.25 * (allow_w[pos] * np.abs(x)).sum(axis=-1)[:, None] * np.abs(x)
        ).reshape(-1)[live]
        big_g, big_s = (np.zeros(rows_n, np.float32) for _ in range(2))
        sum_abs, sum_sq = np.zeros(rows_n), np.zeros(rows_n)
        hit = np.zeros(rows_n, bool)
        np.add.at(big_g, at, g)
        np.add.at(big_s, at, g * g)
        np.add.at(sum_abs, at, np.abs(g) + inherited / rtol)
        np.add.at(sum_sq, at, g * g + (2 * np.abs(g) + inherited) * inherited / rtol)
        hit[at] = True
        w, z, n = table.T
        n_new = n + big_s
        step = big_s / np.maximum(np.sqrt(n_new) + np.sqrt(n), TINY) / alpha
        z_new = z + big_g - step * w
        w_new, scale = weights(cfg, z_new, n_new)
        new = np.stack([w_new, z_new, n_new], axis=-1)
        moved_z += np.where(hit, sum_abs + step * (np.abs(w) + allow_w / rtol), 0)
        moved_n += np.where(hit, sum_sq, 0)
        table = np.where(hit[:, None], new, table)
        # what the check allows z and n by now, carried through the rule
        allow_z = rtol * moved_z + atol + ulps * np.maximum(
            np.abs(z_new), np.abs(before[:, 1])
        )
        allow_n = rtol * moved_n + atol + ulps * np.maximum(n_new, before[:, 2])
        carried = allow_z / scale + np.abs(w_new) * allow_n / (
            2 * alpha * np.maximum(np.sqrt(n_new), TINY) * scale
        )
        allow_w = np.where(hit, carried + atol + ulps * np.abs(w_new), allow_w)
        moved_w = np.where(hit, carried / rtol, moved_w)
    moved = np.stack([moved_w, moved_z, moved_n], axis=-1).astype(np.float32)
    # the padding repeats the largest id: every repeat shows that id's row
    at = np.searchsorted(ids["feature"], ids["feature"])
    return {"feature": table[at]}, {"feature": moved[at]}
