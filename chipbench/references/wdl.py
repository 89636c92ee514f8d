"""Wide & Deep (Cheng et al., DLRS 2016) over TWO tables, one
bulk-synchronous step a batch — the plain reference: numpy float32, forward
and backward pass written out (no autodiff, no store, no kernels), the cross
key by its own integer arithmetic, a row's gradients added one by one in
stream order (``np.add.at``), each rule row by row.

Per example, everything read as it stood BEFORE the step (``i_f`` the id of
field ``f``'s value, a row of the deep table; ``F`` fields, ``J = F``
crosses, cross ``j`` of field ``j`` with field ``j + 1 mod F``):

    e_f = E[i_f][:dim]                          the embedding half of a deep row
    a0 = [e_1; ...; e_F; x]
    a_{k+1} = relu(W_k a_k + b_k)               the hidden layers
    d = w_out . a_last + b_out
    k_j = j m + (fmix32(max(i_l, i_r) 0x9E3779B1 + min(i_l, i_r)) mod m)
    s = sum_j T[k_j][0]                         lane 0 of a wide row (w, z, n)
    logit = d + s + bias;   loss = mean over the live examples of BCE

then, with ``g`` a row's SUMMED gradient over the live lanes that name it:

    deep row       G' = G + g g;   e' = e - lr g / (sqrt(G') + eps)    element-wise
    dense leaf     the same, once a batch, on the whole batch's gradient
    wide row       McMahan et al. 2013, Algorithm 1, in cell 6's batch form
                   (``chipbench/references/lr.py``): n' = n + sum g^2;
                   s = (sqrt(n') - sqrt(n)) / alpha;  z' = z + sum g - s w;
                   w' = 0 if |z'| <= l1 else -(z' - sgn(z') l1) /
                   ((beta + sqrt(n')) / alpha + l2)

ONE group of rows is followed, ``rows["parameters"]``, ``2 dim`` lanes wide:
the touched deep rows WHOLE, addressed by position in the compact ``touched``
id list; the touched wide rows ``(w, z, n)``, flat, a row a CODE of
``touched``'s (:func:`cross_codes`: two codes that hash to one bucket show
the same row, and the reference keeps ONE row a bucket); every dense leaf
and ``bias`` by name, flat, then every accumulator in the same order (zeros
fill a part's last row).  One group, as cells 10 and 15's references and for
their reason, here threefold: a wide row's ``n`` takes ``sum g^2`` of
gradients that carry the loss's ``1 / batch`` (1e-10 beside an ``n`` of
tens: in float32 it does not move, on any system); so does a cold
embedding's accumulator at TensorFlow's initial 0.1; and a ReLU unit no
example lights leaves its column still.  The benchmark's tests ask of every
GROUP that four fifths of its elements move five times their allowance.

``moved`` is, element by element, what the check's ``delta_rtol`` multiplies.
A GRADIENT's is the sum of the magnitudes of its addends (what a summation
error of the system can be a share of) and, over ``delta_rtol``, twice what
THE RELU'S CORNER moves (``reference.relu_ulps``, read here: cell 10's
reference says what it is and why twice; its backward pass is this one's,
``chipbench/references/dlrm._backward``), and what ROUNDING leaves in
the values it was made from: an activation, a backward value and the logit
may each be off by ``relu_ulps`` roundings of the magnitudes they were summed
from (the corner's own measure) and by what they inherit, to first order, a
sum of magnitudes layer by layer.  (A value that cancellation left near zero
is off by a large share of ITSELF; a gradient that ONE example makes alone,
a unit few examples light, inherits that whole, and read 1.1 x an allowance
that knew only the gradient's own addends.)  A rule then carries it to the
row: AdaGrad's ``h(g, G) = g / (sqrt(G + g g) + eps)`` rises with ``g``, so
over ``g +- tol(g)`` it is bounded by its values at the two ends
(``references/dlrm_dcnv2._adagrad``: cell 15's); FTRL's ``z`` takes the sum
of ``|g|`` plus ``|s w|``, ``n`` the sum of ``g^2``, and ``w``, a function of
the two, theirs carried through the rule to first order, as cell 6's
reference carries them (``|dw/dz| <= 1 / D`` on both sides of the threshold).
ONE BATCH is what the cell checks (``reference.batches`` 1, as cell 15's).
A later batch reads what an earlier one wrote within its allowance and not
exactly, and nothing is carried for that: ``delta_rtol`` stands a hundred
times over the float32 sums' own error, and the benchmark's tests hold three
batches of 512 to it.  At 32,768 examples it does not do: a system that
stood on the other side of ONE first-batch corner holds that unit's weights
and bias ``lr g / sqrt(G)`` off (4e-7 on a bias of 1e-2: my chip run, PR 71),
every example of the next batch reads that unit 25 to 46 roundings off where
the corner is 16 wide, one of them turns unmarked and its 26 rows read 41 x
their allowance.  A reference that widens a later batch's corners by what
earlier ones moved could hold two (PERF.md section 7).

Every matrix product of VALUES goes through ``references/dlrm._dot``, so a
control can run the same equations with coarser products.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from chipbench.references import dlrm as mlp
from chipbench.references import padded_unique
from chipbench.references.dlrm_dcnv2 import _adagrad
from chipbench.references.lr import TINY
from chipbench.references.lr import weights as _ftrl_weights  # (w, D) of (z, n)

F32, F64 = np.float32, np.float64
# a touched wide row's code: cross << 2 CODE_BITS | left id << CODE_BITS |
# right id (an id is a row of the deep table: under 2^26 = 67.1 M of them)
CODE_BITS = 26


def cross_codes(ids: np.ndarray) -> np.ndarray:
    """``(B, F)`` ids -> ``(B, F)`` int64 codes, cross ``j`` of field ``j``
    with field ``j + 1 mod F``: what names a wide row before any hash."""
    ids = ids.astype(np.int64)
    cross = np.arange(ids.shape[1], dtype=np.int64)
    return (cross << (2 * CODE_BITS)) | (ids << CODE_BITS) | np.roll(
        ids, -1, axis=1)


def cross_keys(codes: np.ndarray, buckets: int) -> np.ndarray:
    """The wide row of every code: ``j m + (fmix32(hi 0x9E3779B1 + lo) mod
    m)``, ``hi`` / ``lo`` the larger and the smaller id, ``fmix32``
    murmur3's finalizer, every product and sum modulo 2^32, written out in
    64-bit integers."""
    low, wrap = (1 << CODE_BITS) - 1, 0xFFFFFFFF
    left, right = (codes >> CODE_BITS) & low, codes & low
    h = (np.maximum(left, right) * 0x9E3779B1 + np.minimum(left, right)) & wrap
    h ^= h >> 16
    h = (h * 0x85EBCA6B) & wrap
    h ^= h >> 13
    h = (h * 0xC2B2AE35) & wrap
    h ^= h >> 16
    return (codes >> (2 * CODE_BITS)) * buckets + h % buckets


def touched(batches: List[Dict[str, np.ndarray]]) -> Dict[str, np.ndarray]:
    return {
        "deep": padded_unique(np.concatenate(
            [b["ids"].reshape(-1) for b in batches])),
        "wide": padded_unique(np.concatenate(
            [cross_codes(b["ids"]).reshape(-1) for b in batches])),
    }


def leaf_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    """``{leaf: shape}`` of the worker's state without its accumulators: a
    layer's matrix ``(inputs, outputs)`` and its bias, and ``bias``."""
    fields = len(cfg["field_cardinalities"])
    widths = [fields * int(cfg["dim"]) + int(cfg["dense_fields"])] + list(
        cfg["hidden"]) + [1]
    out: Dict[str, Tuple[int, ...]] = {}
    for i, (n, m) in enumerate(zip(widths, widths[1:])):
        out[f"deep{i}_w"], out[f"deep{i}_b"] = (int(n), int(m)), (int(m),)
    out["bias"] = (1,)
    return out


def _laid(flat: np.ndarray, lanes: int) -> np.ndarray:
    return np.pad(flat, (0, -flat.size % lanes)).reshape(-1, lanes)


def split(cfg: dict, block: np.ndarray) -> Tuple[dict, dict]:
    """``(leaves, accumulators)`` of the dense part of a ``parameters``
    block, the leaves by name."""
    flat, at, out = block.reshape(-1), 0, []
    for _ in range(2):
        part = {}
        for name, shape in sorted(leaf_shapes(cfg).items()):
            n = int(np.prod(shape))
            part[name] = flat[at:at + n].reshape(shape)
            at += n
        out.append(part)
    return out[0], out[1]


def join(cfg: dict, leaves: dict, accs: dict, lanes: int) -> np.ndarray:
    return _laid(np.concatenate(
        [part[name].reshape(-1) for part in (leaves, accs)
         for name in sorted(leaf_shapes(cfg))]), lanes)


def apply(
    cfg: dict, rows: Dict[str, np.ndarray], ids: Dict[str, np.ndarray],
    batches: List[Dict[str, np.ndarray]],
) -> tuple:
    dim, lanes = int(cfg["dim"]), 2 * int(cfg["dim"])
    lr, eps, alpha = F32(cfg["learning_rate"]), F32(cfg["eps"]), F32(cfg["alpha"])
    check = cfg["reference"]
    rtol, atol = float(check["delta_rtol"]), float(check["delta_atol"])
    ulps = float(check["row_ulps"]) * float(np.finfo(F32).eps)
    relu_ulps = float(check["relu_ulps"])
    buckets = int(cfg["cross_buckets"])
    names = [k for k in leaf_shapes(cfg) if k.endswith("_w")]
    layers_of = [k[:-2] for k in names]
    block = rows["parameters"].astype(F32)
    # -- the three parts of the block.  The padding repeats the largest id
    # (code): work on the distinct alone
    known, codes = ids["deep"], ids["wide"]
    count = int(np.searchsorted(known, known[-1])) + 1
    deep = block[:count].copy()
    wide_rows = -(-3 * codes.size // lanes)
    wide_seen = block[known.size:known.size + wide_rows].reshape(-1)[
        :3 * codes.size].reshape(-1, 3)
    code_count = int(np.searchsorted(codes, codes[-1])) + 1
    keys, first = np.unique(
        cross_keys(codes[:code_count], buckets), return_index=True)
    before = wide_seen[first]  # ONE row a bucket, however many codes hit it
    wide = before.copy()
    p, acc = split(cfg, block[known.size + wide_rows:])
    p = {k: v.copy() for k, v in p.items()}
    acc = {k: v.copy() for k, v in acc.items()}
    # -- by element, what the check's delta_rtol multiplies
    moved_deep = np.zeros(deep.shape)
    moved_p = {k: np.zeros(v.shape) for k, v in p.items()}
    moved_acc = {k: np.zeros(v.shape) for k, v in acc.items()}
    moved_w, moved_z, moved_n = (np.zeros(len(wide)) for _ in range(3))
    tol = F32(relu_ulps * float(np.finfo(F32).eps))
    for b in batches:
        live = b["mask"]
        pos = np.searchsorted(known[:count], b["ids"])  # (B, F)
        at = np.searchsorted(keys, cross_keys(cross_codes(b["ids"]), buckets))
        # -- forward.  A pre-activation within `relu_ulps` roundings of the
        # magnitudes it was summed from is ON its ReLU's corner (`width`).
        # What rounding leaves in a value goes on through the layers as its
        # SQUARE, `e2`: that width and what the value inherits, the root of
        # the sum of the squares (roundings do not conspire: through four
        # layers whose rows sum to twenty in magnitude and to one in squares
        # a sum of magnitudes reads a thousand times the logit's own error,
        # 1.1e-6 on the chip)
        layers = {  # (as cell 10's reference lays them: the bias a last row)
            k: np.concatenate([p[f"{k}_w"], p[f"{k}_b"][None]])
            for k in layers_of}
        a = np.concatenate([
            deep[:, :dim][pos].reshape(len(live), -1),
            b["dense"].astype(F32)], axis=1)
        e2_a = np.zeros_like(a)
        acts, e2_acts, corner = [a], [], {}
        for k in layers_of:
            w, bias = p[f"{k}_w"], p[f"{k}_b"]
            z = mlp._dot(a, w) + bias
            e2_acts.append(e2_a)
            width = tol * (np.abs(a) @ np.abs(w) + np.abs(bias))
            e2_z = width * width + e2_a @ (w * w)
            if k != layers_of[-1]:
                corner[k] = np.abs(z) <= width
                e2_z = np.where((z > 0) | corner[k], e2_z, F32(0))
                z = np.maximum(z, F32(0))
            a, e2_a = z, e2_z.astype(F32)
            acts.append(a)
        logit = (
            a[:, 0] + wide[:, 0][at].sum(axis=1, dtype=F32) + p["bias"][0]
        ).astype(F32)
        e2_logit = e2_a[:, 0] + np.square(tol * (
            np.abs(wide[:, 0][at]).sum(axis=1) + np.abs(p["bias"][0])))
        # -- backward: p - y = -s / (1 + exp(s logit)), s the label's sign
        sign = np.where(b["label"] > 0, 1, -1).astype(F32)
        n_live = F32(max(int(live.sum()), 1))
        with np.errstate(over="ignore"):  # exp(90) is inf: the gradient 0
            d_logit = (
                np.where(live, -sign / (1 + np.exp(sign * logit)), 0) / n_live
            ).astype(F32)
        # (|d/dlogit| = p (1 - p) / n <= |p - y| / n; the device's exp)
        e2_d_logit = np.square(d_logit) * (e2_logit + tol * tol)
        ds, d_a0 = mlp._backward(
            layers, layers_of, acts, d_logit[:, None],
            {k: False for k in corner})
        # what a backward value may be off by, as a forward one
        e2_ds, e2 = {}, e2_d_logit[:, None]
        for k, out in zip(reversed(layers_of), reversed(acts[1:])):
            if k in corner:
                e2 = np.where(out > 0, e2, F32(0))
            e2_ds[k] = e2
            w = p[f"{k}_w"].T
            e2 = np.square(tol * (np.abs(ds[k]) @ np.abs(w))) + e2 @ (w * w)
        e_d_a0 = np.sqrt(e2)
        # -- every (example, unit) on its ReLU's corner, the other side: a
        # sub-batch with one row a pair and that one unit turned round
        pairs = {k: np.nonzero(c & live[:, None]) for k, c in corner.items()}
        on = np.concatenate([ex for ex, _ in pairs.values()])
        flip, start = {}, 0
        for k, (ex, unit) in pairs.items():
            flip[k] = np.zeros((on.size, corner[k].shape[1]), bool)
            flip[k][start + np.arange(ex.size), unit] = True
            start += ex.size
        ds_on, d_a0_on = mlp._backward(
            layers, layers_of, [x[on] for x in acts], d_logit[on][:, None],
            flip)
        # -- the worker: AdaGrad on every dense leaf and on bias, once.  A
        # gradient's `mg`: over delta_rtol what it may be off by, the sum of
        # its addends' magnitudes, what its factors are off by (the root of
        # the sum of their squares over the examples), and twice what the
        # turned units move
        grads = {"bias": (
            d_logit.sum(dtype=F32)[None],
            (np.abs(d_logit).sum() + np.sqrt(e2_d_logit.sum()) / rtol)[None])}
        for k, x, e2_x in zip(layers_of, acts, e2_acts):
            d = ds[k]
            other = np.abs(ds_on[k] - d[on])
            grads[f"{k}_w"] = (
                mlp._dot(x.T, d),
                np.abs(x).T @ np.abs(d) + np.sqrt(
                    (x * x).T @ e2_ds[k] + e2_x.T @ (d * d)) / rtol
                + np.abs(x[on]).T @ (2 * other / rtol))
            grads[f"{k}_b"] = (
                d.sum(axis=0),
                np.abs(d).sum(axis=0) + (
                    np.sqrt(e2_ds[k].sum(axis=0)) + 2 * other.sum(axis=0)
                ) / rtol)
        for k, (g, mg) in grads.items():
            p[k], acc[k], more, more_acc = _adagrad(
                p[k], acc[k], g.astype(F32), rtol * mg.astype(F64), lr, eps,
                rtol)
            moved_p[k] += more / rtol
            moved_acc[k] += more_acc / rtol
        # -- the deep server: a row's gradients one by one in stream order,
        # the rule row by row
        d_rows = d_a0[:, :pos.shape[1] * dim].reshape(pos.shape + (dim,))
        mg_rows = (np.abs(d_a0) + e_d_a0 / rtol)[
            :, :pos.shape[1] * dim].reshape(d_rows.shape).astype(F64)
        other = np.abs(d_a0_on[:, :pos.shape[1] * dim].reshape(
            (on.size,) + d_rows.shape[1:]) - d_rows[on])
        np.add.at(mg_rows, on, 2 * other / rtol)
        where = np.where(live[:, None], pos, count).reshape(-1)
        g = np.zeros((count + 1, dim), F32)
        mg = np.zeros((count + 1, dim))
        np.add.at(g, where, d_rows.reshape(-1, dim))
        np.add.at(mg, where, mg_rows.reshape(-1, dim))
        hit = np.zeros(count + 1, bool)
        hit[where] = True
        g, mg, hit = g[:count], mg[:count], hit[:count, None]
        new, new_acc, more, more_acc = _adagrad(
            deep[:, :dim], deep[:, dim:], g, rtol * mg, lr, eps, rtol)
        deep = np.where(hit, np.concatenate([new, new_acc], axis=1), deep)
        moved_deep += np.where(
            hit, np.concatenate([more, more_acc], axis=1), 0) / rtol
        # -- the wide server: cell 6's rule on (sum g, sum g^2) a row
        lane = np.where(live[:, None], at, len(wide)).reshape(-1)
        gw = np.broadcast_to(d_logit[:, None], at.shape).reshape(-1)
        big_g, big_s = (np.zeros(len(wide) + 1, F32) for _ in range(2))
        sum_abs, sum_sq = (np.zeros(len(wide) + 1) for _ in range(2))
        np.add.at(big_g, lane, gw)
        np.add.at(big_s, lane, gw * gw)
        e_gw = np.broadcast_to(
            np.sqrt(e2_d_logit)[:, None] / rtol, at.shape).reshape(-1)
        np.add.at(sum_abs, lane, np.abs(gw) + e_gw)
        np.add.at(sum_sq, lane, gw * gw + 2 * np.abs(gw) * e_gw)
        named = np.zeros(len(wide) + 1, bool)
        named[lane] = True
        big_g, big_s, sum_abs, sum_sq, named = (
            x[:-1] for x in (big_g, big_s, sum_abs, sum_sq, named))
        w, z, n = wide.T
        n_new = n + big_s
        step = big_s / np.maximum(np.sqrt(n_new) + np.sqrt(n), TINY) / alpha
        z_new = z + big_g - step * w
        w_new, scale = _ftrl_weights(cfg, z_new, n_new)
        moved_z += np.where(named, sum_abs + step * np.abs(w), 0)
        moved_n += np.where(named, sum_sq, 0)
        wide = np.where(
            named[:, None], np.stack([w_new, z_new, n_new], axis=-1), wide)
        # what the check allows z and n by now, carried through the rule
        allow_z = rtol * moved_z + atol + ulps * np.maximum(
            np.abs(z_new), np.abs(before[:, 1]))
        allow_n = rtol * moved_n + atol + ulps * np.maximum(n_new, before[:, 2])
        carried = allow_z / scale + np.abs(w_new) * allow_n / (
            2 * alpha * np.maximum(np.sqrt(n_new), TINY) * scale)
        moved_w = np.where(named, carried / rtol, moved_w)
    # every repeat of the padding shows the largest id's row, and every code
    # its bucket's
    back = np.searchsorted(known[:count], known)
    of_code = np.searchsorted(keys, cross_keys(codes, buckets))
    moved_wide = np.stack([moved_w, moved_z, moved_n], axis=-1)
    return (
        {"parameters": np.concatenate([
            deep[back], _laid(wide[of_code].reshape(-1), lanes),
            join(cfg, p, acc, lanes)])},
        {"parameters": np.concatenate([
            moved_deep[back], _laid(moved_wide[of_code].reshape(-1), lanes),
            join(cfg, moved_p, moved_acc, lanes),
        ]).astype(F32)},
    )
