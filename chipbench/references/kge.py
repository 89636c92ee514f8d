"""PyTorch-BigGraph's ComplEx trainer (Lerer et al., SysML 2019;
``facebookresearch/PyTorch-BigGraph``) with row-wise AdaGrad on the entity
rows and AdaGrad on the relations' operators, one bulk-synchronous step a
batch — the plain reference: numpy, no store, no kernels, the scores as plain
``einsum``, a row's gradients added one by one in stream order
(``np.add.at``).

An entity row is ``(theta[0..d), G)``: ``d / 2`` complex numbers (real parts,
then imaginary parts) and row-wise AdaGrad's ONE accumulator.  Relation ``r``
has two operators, ``a_r`` (forward) and ``b_r`` (reverse), and an AdaGrad
accumulator an element of each.  A batch is chunks of ``n`` edges ``(s, r,
o)`` with ``u`` uniform ids a side; everything is read as it stood before
the step.  Per chunk, ``D = [theta_o; theta_v]``, ``S = [theta_s; theta_u]``:

    q_e = a_{r_e} (.) theta_{s_e}      Sd = Q D^T      (n, n + u)
    p_e = b_{r_e} (.) theta_{o_e}      Ss = P S^T
    loss = sum_e [ -log softmax(Sd[e])[e] - log softmax(Ss[e])[e] ]

    dSd = softmax(Sd) - [column e of row e]
    dQ = dSd D     dD = dSd^T Q     (and the same for the source side)
    to theta_s: conj(a) (.) dQ + dS[:n]    to theta_o: conj(b) (.) dP + dD[:n]
    to theta_u: dS[n:]                     to theta_v: dD[n:]
    to a_r: conj(theta_s) (.) dQ           to b_r: conj(theta_o) (.) dP

then once for every row the batch names, on the sum ``g`` of its gradients
(``torchbiggraph``'s ``row_adagrad`` module: the accumulator is read AFTER
the add):

    G' = G + mean_k(g_k^2);    theta' = theta - lr g / (sqrt(G') + eps)

and for the operators, on the batch's gradients summed by relation, element
by element: ``S' = S + g^2;  p' = p - lr_rel g / (sqrt(S') + eps)``.

Two groups of rows are followed: ``"entity"``, the touched entity rows WITH
their accumulators (``d + 1`` lanes), and ``"operator"``, EVERY relation's
``(a, b, S_a, S_b)`` flat (``4 d`` lanes), named by the batches or not.

``moved`` is, element by element, what the check's ``delta_rtol`` multiplies:
how far the element can be off, to first order, for one part in ``1 /
delta_rtol`` of error in every sum it was made from, because the check knows
no rule.  Every intermediate ``X`` carries ``tol(X)``, what it may be off by:
``delta_rtol x`` the sum of the magnitudes of its addends (the order of a
float32 sum, the passes of a float32 product on the MXU, the device's
``exp``) and, to first order, what it inherits from the ``tol`` of what it
was made from; a later batch reads rows and operators an earlier one wrote
within their allowance and not exactly, so that is where the chain starts.
The rules' own sensitivities end it: ``theta'`` takes ``lr / r`` of
``tol(g_k)`` and ``lr |g_k| / (r^2 sqrt(G') d)`` of ``sum_j |g_j| tol(g_j)``,
``r = sqrt(G') + eps``; ``G'`` takes ``2 / d`` of that sum; an operator's
element takes ``lr_rel / r`` of ``tol(g)``, its accumulator ``2 |g|`` of it.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from chipbench.references import padded_unique

ENDS = ("source", "destination")
KEYS = ("source", "destination", "source_negatives", "destination_negatives")

_dot = np.matmul  # a control runs the same equations with coarser products


def touched(batches: List[Dict[str, np.ndarray]]) -> Dict[str, np.ndarray]:
    return {"entity": padded_unique(np.concatenate(
        [b[k].reshape(-1) for b in batches for k in KEYS]
    ))}


def _times(a: np.ndarray, x: np.ndarray, conj: bool = False) -> np.ndarray:
    """``a (.) x`` (``conj(a) (.) x``), real parts then imaginary parts."""
    h = a.shape[-1] // 2
    a_re, a_im = a[..., :h], (-a[..., h:] if conj else a[..., h:])
    return np.concatenate(
        [a_re * x[..., :h] - a_im * x[..., h:],
         a_re * x[..., h:] + a_im * x[..., :h]], axis=-1)


def _reach(a: np.ndarray, x: np.ndarray) -> np.ndarray:
    """The magnitudes a complex product sums, lane by lane: ``|a| (.) |x|``
    with every sign a plus (float64)."""
    h = a.shape[-1] // 2
    a, x = np.abs(a).astype(np.float64), np.abs(x).astype(np.float64)
    return np.concatenate(
        [a[..., :h] * x[..., :h] + a[..., h:] * x[..., h:],
         a[..., :h] * x[..., h:] + a[..., h:] * x[..., :h]], axis=-1)


def _side(ops, ends, others, tol_ops, tol_ends, tol_others, rtol):
    """One side of a batch, chunk by chunk (``models/kge._side``'s
    equations): the float32 gradients to the operators, the turned ends and
    the others, and beside each what it may be off by (float64)."""
    f32, f64 = np.float32, np.float64
    n = ends.shape[1]
    turned = _times(ops, ends).astype(f32)
    tol_turned = (
        _reach(ops, tol_ends) + _reach(tol_ops, ends)
        + rtol * _reach(ops, ends))
    t = others.transpose(0, 2, 1)
    scores = _dot(turned, t).astype(f32)
    tol_scores = (
        _dot(tol_turned, np.abs(t, dtype=f64))
        + _dot(np.abs(turned, dtype=f64), tol_others.transpose(0, 2, 1))
        + rtol * _dot(np.abs(turned, dtype=f64), np.abs(t, dtype=f64)))
    shifted = np.exp(scores - scores.max(axis=-1, keepdims=True)).astype(f32)
    share = (shifted / shifted.sum(axis=-1, keepdims=True)).astype(f32)
    # d softmax: p (ds - sum p ds), and the device's exp and the sum's order
    wide = share.astype(f64)
    tol_share = wide * (
        tol_scores + (wide * tol_scores).sum(axis=-1, keepdims=True)
        + 2 * rtol)
    d_scores = share.copy()
    d_scores[:, np.arange(n), np.arange(n)] -= f32(1)
    mag = np.abs(d_scores, dtype=f64)
    d_turned = _dot(d_scores, others).astype(f32)
    tol_d_turned = (
        _dot(tol_share, np.abs(others, dtype=f64)) + _dot(mag, tol_others)
        + rtol * _dot(mag, np.abs(others, dtype=f64)))
    back = d_scores.transpose(0, 2, 1)
    d_others = _dot(back, turned).astype(f32)
    tol_d_others = (
        _dot(tol_share.transpose(0, 2, 1), np.abs(turned, dtype=f64))
        + _dot(mag.transpose(0, 2, 1), tol_turned)
        + rtol * _dot(mag.transpose(0, 2, 1), np.abs(turned, dtype=f64)))
    d_ends = _times(ops, d_turned, conj=True).astype(f32)
    tol_d_ends = (
        _reach(ops, tol_d_turned) + _reach(tol_ops, d_turned)
        + rtol * _reach(ops, d_turned))
    d_ops = _times(ends, d_turned, conj=True).astype(f32)
    tol_d_ops = (
        _reach(ends, tol_d_turned) + _reach(tol_ends, d_turned)
        + rtol * _reach(ends, d_turned))
    return (d_ops, tol_d_ops), (d_ends, tol_d_ends), (d_others, tol_d_others)


def apply(
    cfg: dict, rows: Dict[str, np.ndarray], ids: Dict[str, np.ndarray],
    batches: List[Dict[str, np.ndarray]],
) -> tuple:
    f32, f64 = np.float32, np.float64
    dim = int(cfg["dim"])
    lr, lr_rel, eps = (f32(cfg[k]) for k in ("lr", "lr_rel", "eps"))
    check = cfg["reference"]
    rtol, atol = float(check["delta_rtol"]), float(check["delta_atol"])
    ulps = float(check["row_ulps"]) * float(np.finfo(np.float32).eps)
    # the padding repeats the largest id: work on the distinct rows alone
    known = ids["entity"]
    count = int(np.searchsorted(known, known[-1])) + 1
    before = {
        "entity": rows["entity"][:count].astype(f32),
        "operator": rows["operator"].astype(f32),
    }
    table = before["entity"].copy()
    relations = before["operator"].shape[0]
    leaf = before["operator"].reshape(relations, 2, 2, dim)
    operators, op_acc = leaf[:, 0].copy(), leaf[:, 1].copy()  # (R, 2, dim)
    moved = {
        "entity": np.zeros(table.shape), "operator": np.zeros(leaf.shape)}
    allow = {
        "entity": np.zeros(table.shape), "operator": np.zeros(leaf.shape)}
    ever = {
        "entity": np.zeros(count, bool), "operator": np.zeros(relations, bool)}
    for b in batches:
        n = b["source"].shape[1]
        at = {k: np.searchsorted(known[:count], b[k]) for k in KEYS}
        rel = b["relation"]
        pulled = {k: table[at[k]][..., :dim] for k in KEYS}
        held = {k: allow["entity"][at[k]][..., :dim] for k in KEYS}
        every, every_tol = {}, {}
        for end in ENDS:
            every[end] = np.concatenate(
                [pulled[end], pulled[end + "_negatives"]], axis=1)
            every_tol[end] = np.concatenate(
                [held[end], held[end + "_negatives"]], axis=1)
        ops, ops_tol = operators[rel], allow["operator"][rel, 0]
        # the destination side turns the sources by a, the source side the
        # destinations by b; each is scored against every row of the OTHER
        grad = {k: None for k in KEYS}
        d_ops = []
        for which, (end, other) in enumerate((ENDS, ENDS[::-1])):
            to_ops, to_ends, to_others = _side(
                ops[:, :, which], pulled[end], every[other],
                ops_tol[:, :, which], held[end], every_tol[other], rtol)
            d_ops.append(to_ops)
            for key, (g, tol) in (
                (end, to_ends), (other, [x[:, :n] for x in to_others]),
                (other + "_negatives", [x[:, n:] for x in to_others]),
            ):
                grad[key] = (g, tol) if grad[key] is None else (
                    (grad[key][0] + g).astype(f32), grad[key][1] + tol
                    + rtol * (np.abs(grad[key][0]) + np.abs(g)))
        # -- the server: a row's gradients one by one in stream order, the
        # keys as the step lays them (chunk by chunk, KEYS' order)
        where = np.concatenate([at[k] for k in KEYS], axis=1).reshape(-1)
        g_all = np.concatenate([grad[k][0] for k in KEYS], axis=1)
        tol_all = np.concatenate([grad[k][1] for k in KEYS], axis=1)
        g = np.zeros((count, dim), f32)
        tol_g = np.zeros((count, dim))
        hit = np.zeros(count, bool)
        np.add.at(g, where, g_all.reshape(-1, dim))
        np.add.at(
            tol_g, where,
            (tol_all + rtol * np.abs(g_all)).reshape(-1, dim))
        hit[where] = True
        theta, acc = table[:, :dim], table[:, dim:]
        new_acc = (acc + np.mean(g * g, axis=-1, keepdims=True)).astype(f32)
        r = np.sqrt(new_acc) + eps
        new = np.concatenate(
            [theta - lr * g / r, new_acc], axis=-1).astype(f32)
        wide_r = r.astype(f64)
        root = np.maximum(np.sqrt(new_acc.astype(f64)), 1e-300)
        # sum_j |g_j| tol(g_j): what the mean square may be off by, x d / 2
        square_tol = (np.abs(g) * tol_g).sum(axis=-1, keepdims=True)
        acc_tol = 2.0 / dim * square_tol + allow["entity"][:, dim:]
        row_moved = np.concatenate([
            float(lr) / wide_r * tol_g
            + float(lr) * np.abs(g) / wide_r ** 2 / (2 * root) * acc_tol,
            acc_tol - allow["entity"][:, dim:]
            + rtol * np.mean(g.astype(f64) ** 2, axis=-1, keepdims=True),
        ], axis=-1) / rtol
        moved["entity"] += np.where(hit[:, None], row_moved, 0)
        table = np.where(hit[:, None], new, table)
        ever["entity"] |= hit
        # -- the worker: the operators' gradients summed by relation, then
        # AdaGrad on the whole leaf (a relation no edge names has g = 0)
        flat = rel.reshape(-1)
        g_ops = np.zeros(operators.shape, f32)
        tol_ops = np.zeros(operators.shape)
        np.add.at(g_ops, flat, np.stack(
            [d_ops[0][0], d_ops[1][0]], axis=2).reshape(-1, 2, dim))
        np.add.at(tol_ops, flat, np.stack([
            d[1] + rtol * np.abs(d[0]) for d in d_ops
        ], axis=2).reshape(-1, 2, dim))
        named = np.zeros(relations, bool)
        named[flat] = True
        new_op_acc = (op_acc + g_ops * g_ops).astype(f32)
        r_ops = np.sqrt(new_op_acc) + eps
        operators = (operators - lr_rel * g_ops / r_ops).astype(f32)
        root = np.maximum(np.sqrt(new_op_acc.astype(f64)), 1e-300)
        wide_r = r_ops.astype(f64)
        acc_tol = 2 * np.abs(g_ops) * tol_ops + allow["operator"][:, 1]
        moved["operator"][:, 0] += (
            float(lr_rel) / wide_r * tol_ops
            + float(lr_rel) * np.abs(g_ops) / wide_r ** 2 / (2 * root)
            * acc_tol
        ) / rtol
        moved["operator"][:, 1] += (
            2 * np.abs(g_ops) * tol_ops
            + rtol * g_ops.astype(f64) ** 2) / rtol
        op_acc = new_op_acc
        ever["operator"] |= named
        # what the check allows every element some batch has moved
        now = {
            "entity": table,
            "operator": np.stack([operators, op_acc], axis=1),
        }
        for name in now:
            was = before[name].reshape(now[name].shape)
            lead = ever[name].reshape((-1,) + (1,) * (now[name].ndim - 1))
            allow[name] = np.where(
                lead, rtol * moved[name] + atol + ulps * np.maximum(
                    np.abs(now[name]), np.abs(was)), 0)
    # every repeat of the padding shows the largest id's row
    back = np.searchsorted(known[:count], known)
    return (
        {"entity": table[back],
         "operator": np.stack([operators, op_acc], axis=1).reshape(
             relations, -1)},
        {"entity": moved["entity"][back].astype(f32),
         "operator": moved["operator"].reshape(relations, -1).astype(f32)},
    )
