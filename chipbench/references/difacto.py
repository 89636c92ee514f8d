"""DiFacto (Li et al., WSDM 2016; ``dmlc/difacto`` ``src/sgd/sgd_updater.h``):
a degree-2 factorisation machine whose optimiser is the server's updater,
FTRL on the linear weight and AdaGrad on the embedding, the embedding gated
by the feature's count — the plain reference.

Row ``f`` of the table is ``(w, z, s, c, V[0..k), S[0..k))``; ``s`` and ``S``
hold the square ROOTS of the accumulated squared gradients.  All rows are read
as they stood before the step.  A feature's embedding is live where ``a = (c >
V_threshold) and (w != 0)``.  For an example with active features ``f_1..f_K``,
values ``x`` and label ``y`` in {-1, +1} (mapped to {0, 1}):

    t    = sum_k a_k x_k V_k
    y^   = sum_k w_k x_k + 1/2 (|t|^2 - sum_k a_k |x_k V_k|^2)
    g    = p - y = -sgn / (1 + exp(sgn y^)),  sgn the label's sign
    gw_k = g x_k;   gV_k = a_k g x_k (t - x_k V_k)

and for every row a batch touches, with ``Gw`` and ``GV`` the batch's
gradients to it summed in stream order with ``np.add.at``:

    UpdateW  gw = Gw + l2 w;  s' = sqrt(s^2 + gw^2)
             z' = z - gw + (s' - s) / lr * w
             w' = 0 if |z'| <= l1 else (z' - sgn(z') l1) / ((lr_beta + s') / lr)
    UpdateV  where a held on the row as it stood, for each d:
             gv = GV_d + V_l2 V_d;  S_d' = sqrt(S_d^2 + gv^2)
             V_d' = V_d - V_lr / (S_d' + V_lr_beta) gv
    c' = c

``s' - s`` is computed as ``gw^2 / (s' + s)``: the same number without the
cancellation.

``moved`` is, lane by lane, what the check's ``delta_rtol`` multiplies: how far
a lane's value can be off for one part in ``1 / delta_rtol`` of error in the
sums it was made from, to first order, because the check knows no rule:

- ``Gw`` may be off by ``delta_rtol x sum |gw|`` (the order of a float32 sum
  over up to 32,768 addends).  ``z'`` takes that once directly and once
  through ``(s' - s) w / lr`` (``2 |gw| |w| / ((s' + s) lr)`` of it), plus the
  magnitude of that term; ``s'`` takes ``|gw| / s'`` of it.
- ``w`` is a function of ``(z', s')``: its allowance is theirs (the sums' part
  and the rounding of the row they land in) carried through the rule,
  ``allow_z / D + |w'| allow_s / (lr D)`` with ``D = (lr_beta + s') / lr``.
  ``w`` is continuous at ``|z'| = l1`` (0 from either side, ``|dw/dz| <= 1 /
  D`` on both), so a ``z'`` the system rounds to the other side of the
  threshold is held like any other.
- ``GV_d`` may be off by ``delta_rtol x sum |gV_d|``; ``V_d'`` takes ``V_lr /
  (S_d' + V_lr_beta)`` of it (``|d/dgv [gv / (S' + b)]| <= 1 / (S' + b)``),
  ``S_d'`` takes ``|gv| / S_d'`` of it.
- a later batch reads rows an earlier one wrote, within their allowance and
  not exactly.  A margin may then differ by ``sum_k |x_k| allow_w(k) + sum_kd
  |dy^/dV_kd| allow_V(k, d)``, ``g`` by a quarter of that (``|sigmoid'| <=
  1/4``), and every gradient by its own first-order share of both; the rule
  reads its own row's earlier allowances likewise.
- THE GATE is not continuous: a row whose ``z'`` lies within its allowance of
  ``l1`` has a ``w`` that is zero on one side and not on the other, so the
  next batch may read its gate either way (``ambiguous``).  Every example
  that names such a row inherits what the flip moves (its margin by ``|x V .
  t_others|``, ``t`` by ``|x V|``), and the row's own ``V`` lanes are allowed
  ``V_lr`` (the most one step moves a lane) and its ``S`` lanes the as-if-live
  gradient's magnitude.  A few rows in ten million; none is skipped.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from chipbench.references.fm import touched  # noqa: F401  (the same record)

TINY = np.float32(np.finfo(np.float32).tiny)
STATE = 4  # lanes before V: w, z, s, c


def weights(cfg: dict, z: np.ndarray, s: np.ndarray) -> tuple:
    """``(w, D)`` of ``UpdateW`` from ``(z, s)``, float32."""
    lr, beta, l1 = (np.float32(cfg[k]) for k in ("lr", "lr_beta", "l1"))
    scale = (beta + s) / lr
    w = (z - np.sign(z) * l1) / scale
    return np.where(np.abs(z) <= l1, np.float32(0), w).astype(np.float32), scale


def apply(
    cfg: dict, rows: Dict[str, np.ndarray], ids: Dict[str, np.ndarray],
    batches: List[Dict[str, np.ndarray]],
) -> tuple:
    f32 = np.float32
    lr, l1, l2 = (f32(cfg[k]) for k in ("lr", "l1", "l2"))
    v_lr, v_beta, v_l2 = (f32(cfg[k]) for k in ("V_lr", "V_lr_beta", "V_l2"))
    threshold, k = f32(cfg["V_threshold"]), int(cfg["dim"])
    check = cfg["reference"]
    rtol, atol = float(check["delta_rtol"]), float(check["delta_atol"])
    ulps = float(check["row_ulps"]) * float(np.finfo(np.float32).eps)
    # the padding repeats the largest id: work on the distinct rows alone
    n = int(np.searchsorted(ids["feature"], ids["feature"][-1])) + 1
    row_ids = ids["feature"][:n]
    before = rows["feature"][:n].astype(np.float32)
    table = before.copy()
    v_at, s_at = STATE, STATE + k
    moved_w, moved_z, moved_s, allow_w, allow_s = (np.zeros(n) for _ in range(5))
    moved_v, moved_acc, allow_v, allow_acc = (np.zeros((n, k)) for _ in range(4))
    ambiguous, ever = np.zeros(n, bool), np.zeros(n, bool)
    for b in batches:
        pos = np.searchsorted(row_ids, b["ids"])  # (B, K)
        x = np.where(b["feat_mask"], b["values"], 0).astype(np.float32)
        pulled = table[pos]
        w, c, v = pulled[..., 0], pulled[..., 3], pulled[..., v_at:s_at]
        counted = (c > threshold) & b["feat_mask"]
        a = counted & (w != 0)
        xv = x[..., None] * np.where(a[..., None], v, f32(0))
        t = xv.sum(axis=1)
        y_hat = (w * x).sum(axis=-1) + f32(0.5) * (
            (t * t).sum(axis=-1) - (xv * xv).sum(axis=(1, 2))
        )
        sign = np.where(b["label"] > 0, 1, -1).astype(np.float32)
        g = -sign / (f32(1) + np.exp(sign * y_hat))  # p - y
        gw = g[:, None] * x
        dy_dv = x[..., None] * (t[:, None, :] - xv)
        gv = np.where(a[..., None], g[:, None, None] * dy_dv, f32(0))

        # what the gradients may inherit from the rows they were computed from
        ax = np.abs(x).astype(np.float64)
        flips = ambiguous[pos] & counted
        raw = np.where(flips[..., None], np.abs(x[..., None] * v), 0.0)
        t_allow = (
            (a * ax)[..., None] * allow_v[pos] + raw
        ).sum(axis=1)  # (B, k)
        margin_allow = (
            (ax * allow_w[pos]).sum(axis=-1)
            + (a[..., None] * np.abs(dy_dv) * allow_v[pos]).sum(axis=(1, 2))
            + (raw * np.abs(t[:, None, :] - xv)).sum(axis=(1, 2))
        )
        g_allow = 0.25 * margin_allow
        gw_inherits = ax * g_allow[:, None]
        gv_inherits = a[..., None] * (
            np.abs(dy_dv) * g_allow[:, None, None]
            + (np.abs(g)[:, None] * ax)[..., None] * t_allow[:, None, :]
        )
        # a row whose gate may be read either way: its gradient as if live
        as_if = flips[..., None] * np.abs(g[:, None, None] * dy_dv)

        live = (b["feat_mask"] & b["mask"][:, None]).reshape(-1)
        at = pos.reshape(-1)[live]
        big_w, big_v = np.zeros(n, np.float32), np.zeros((n, k), np.float32)
        abs_w, abs_v, if_v = np.zeros(n), np.zeros((n, k)), np.zeros((n, k))
        hit = np.zeros(n, bool)
        np.add.at(big_w, at, gw.reshape(-1)[live])
        np.add.at(big_v, at, gv.reshape(-1, k)[live])
        np.add.at(abs_w, at, (np.abs(gw) + gw_inherits / rtol).reshape(-1)[live])
        np.add.at(
            abs_v, at, (np.abs(gv) + gv_inherits / rtol).reshape(-1, k)[live]
        )
        np.add.at(if_v, at, as_if.reshape(-1, k)[live])
        hit[at] = True

        # UpdateW
        w, z, s, c = table.T[:STATE]
        big_w = big_w + l2 * w
        s_new = np.sqrt(s * s + big_w * big_w)
        grown = big_w * big_w / np.maximum(s_new + s, TINY)  # s' - s
        z_new = z - big_w + grown / lr * w
        w_new, scale = weights(cfg, z_new, s_new)
        # UpdateV, where the row as it stood is live
        v, acc = table[:, v_at:s_at], table[:, s_at:]
        gate = ((c > threshold) & (w != 0))[:, None]
        big_v = big_v + v_l2 * v
        acc_new = np.sqrt(acc * acc + big_v * big_v)
        v_new = v - v_lr / (acc_new + v_beta) * big_v
        new = np.concatenate(
            [
                np.stack([w_new, z_new, s_new, c], axis=-1),
                np.where(gate, v_new, v), np.where(gate, acc_new, acc),
            ],
            axis=-1,
        ).astype(np.float32)

        # how far each lane can be off for 1 / rtol of error in its sums
        abs_w = abs_w + l2 * allow_w / rtol
        through_w = 2 * np.abs(big_w) * np.abs(w) / (
            np.maximum(s_new + s, TINY) * lr
        )
        moved_z += np.where(hit, abs_w * (1 + through_w) + grown / lr * (
            np.abs(w) + (allow_w + 2 * np.abs(w) * allow_s / np.maximum(
                s_new + s, TINY)) / rtol
        ), 0)
        moved_s += np.where(hit, abs_w * np.abs(big_w) / np.maximum(s_new, TINY), 0)
        updated = hit[:, None] & gate
        abs_v = abs_v + v_l2 * allow_v / rtol
        moved_v += np.where(updated, v_lr * (
            abs_v / (acc_new + v_beta)
            + np.abs(big_v) / (acc_new + v_beta) ** 2 * allow_acc / rtol
        ), 0)
        moved_acc += np.where(
            updated, abs_v * np.abs(big_v) / np.maximum(acc_new, TINY), 0
        )
        either = (hit & ambiguous & (c > threshold))[:, None]
        moved_v += np.where(either, v_lr / rtol, 0)
        moved_acc += np.where(either, (if_v + v_l2 * np.abs(v)) / rtol, 0)

        table = np.where(hit[:, None], new, table)
        ever |= hit
        # what the check allows each lane by now; w's carried through the rule
        allow_z = rtol * moved_z + atol + ulps * np.maximum(
            np.abs(table[:, 1]), np.abs(before[:, 1])
        )
        allow_s = np.where(ever, rtol * moved_s + atol + ulps * np.maximum(
            table[:, 2], before[:, 2]
        ), 0)
        carried = allow_z / scale + np.abs(w_new) * allow_s / (lr * scale)
        allow_w = np.where(hit, carried + atol + ulps * np.abs(w_new), allow_w)
        moved_w = np.where(hit, carried / rtol, moved_w)
        ambiguous = np.where(
            hit, np.abs(np.abs(z_new) - l1) <= allow_z, ambiguous
        )
        lanes = ever[:, None]
        allow_v = np.where(lanes, rtol * moved_v + atol + ulps * np.maximum(
            np.abs(table[:, v_at:s_at]), np.abs(before[:, v_at:s_at])
        ), 0)
        allow_acc = np.where(lanes, rtol * moved_acc + atol + ulps * np.maximum(
            table[:, s_at:], before[:, s_at:]
        ), 0)
    moved = np.concatenate(
        [
            np.stack([moved_w, moved_z, moved_s, np.zeros(n)], axis=-1),
            moved_v, moved_acc,
        ],
        axis=-1,
    ).astype(np.float32)
    # every repeat of the padding shows the largest id's row
    at = np.searchsorted(row_ids, ids["feature"])
    return {"feature": table[at]}, {"feature": moved[at]}
