"""DLRM by minibatch SGD — the plain reference, forward and backward pass
written out (no autodiff, no kernels), numpy float32.

Per example: the bottom MLP on the dense fields (ReLU after every layer)
gives ``z0``; with the fields' embedding rows ``T = [z0; e_1; ...; e_F]``,
``Z = T T^t`` and ``r = [z0, Z below its diagonal]``, row by row (the
source's ``li, lj``); ``p = sigmoid(MLP_top(r))`` (ReLU between layers);
``loss`` the mean over the live examples of ``BCE(p, y)``.  One step over a
minibatch reads every row and every weight as it stood BEFORE the step:

    every MLP leaf    theta <- theta - lr dL/dtheta
    every named row   E[i]  <- E[i] - lr x (the sum of dL/de over the
                      examples that name it, added one by one in stream
                      order: ``np.add.at``)

ONE group of rows, ``rows["parameters"]``, ``dim`` lanes wide: first the
embedding rows, addressed by position in the compact ``touched`` id list;
then the MLPs WHOLE, flat and laid ``dim`` lanes to a row (zeros fill the
last): layer after layer (bottom first), a layer's weights ``(inputs,
outputs)`` row by row, then its bias.  (One group and not one a layer, nor
the MLPs apart: a ReLU unit that no example of a batch lights leaves its
column without a gradient, 12 % of the MLPs' elements and 21-40 % of three
layers' on this traffic, and with labels that are coin flips another tenth
of them cancel to nothing; the benchmark's tests ask of every GROUP that
four fifths of its elements move five times their allowance: the MLPs alone
read 0.75-0.81, the embedding rows 0.99.)

Every matrix product goes through :func:`_dot`, so that a control can run the
same equations with coarser products.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from chipbench.references import padded_unique

_dot = np.matmul


def touched(batches: List[Dict[str, np.ndarray]]) -> Dict[str, np.ndarray]:
    return {"embedding": padded_unique(np.concatenate(
        [b["ids"].reshape(-1) for b in batches]
    ))}


def layer_shapes(cfg: dict) -> Dict[str, tuple]:
    """``{layer: (inputs + 1, outputs)}``, the bias a layer's last row."""
    vectors = len(cfg["field_cardinalities"]) + 1
    out = {}
    for name, first, widths in (
        ("bot", cfg["dense_fields"], cfg["bottom_mlp"]),
        ("top", cfg["dim"] + vectors * (vectors - 1) // 2, cfg["top_mlp"]),
    ):
        for i, (n, m) in enumerate(zip([first] + list(widths), widths)):
            out[f"{name}{i}"] = (int(n) + 1, int(m))
    return out


def _split(cfg: dict, flat: np.ndarray) -> Dict[str, np.ndarray]:
    out, at = {}, 0
    for name, (rows, cols) in layer_shapes(cfg).items():
        out[name] = flat[at:at + rows * cols].reshape(rows, cols)
        at += rows * cols
    assert at == flat.size, (at, flat.size)
    return out


def _join(cfg: dict, layers: Dict[str, np.ndarray]) -> np.ndarray:
    return np.concatenate([layers[n].reshape(-1) for n in layer_shapes(cfg)])


def unpack(cfg: dict, block: np.ndarray, n: int) -> tuple:
    """``(embedding rows, {layer: rows})`` of a ``parameters`` block whose
    first ``n`` rows are the embedding's."""
    size = sum(r * c for r, c in layer_shapes(cfg).values())
    return block[:n], _split(cfg, block[n:].reshape(-1)[:size])


def pack(cfg: dict, table: np.ndarray, layers: Dict[str, np.ndarray]) -> np.ndarray:
    flat = _join(cfg, layers)
    flat = np.pad(flat, (0, -flat.size % table.shape[1]))
    return np.concatenate([table, flat.reshape(-1, table.shape[1])])


def _forward(layers, names, x, last_relu, relu_ulps):
    """Activations ``[x, a_0, ...]`` and, for every layer with a ReLU, which
    of its units lie ON the ReLU's corner for which example: ``|z|`` within
    ``relu_ulps`` float32 roundings of the magnitudes ``z`` was summed from."""
    acts, corner = [x], {}
    tol = np.float32(relu_ulps * np.finfo(np.float32).eps)
    for k, name in enumerate(names):
        w, b = layers[name][:-1], layers[name][-1]
        z = _dot(acts[-1], w) + b
        if last_relu or k < len(names) - 1:
            corner[name] = np.abs(z) <= tol * (
                np.abs(acts[-1]) @ np.abs(w) + np.abs(b)
            )
            z = np.maximum(z, np.float32(0))
        acts.append(z)
    return acts, corner


def _backward(layers, names, acts, d, flip):
    """Walks the MLP backwards from ``d`` = dL/d(output): ``({layer: dL/dz},
    dL/d(input))``.  A ReLU passes the gradient where its output is
    positive; ``flip[layer]`` (bool, like ``dL/dz``) turns that round."""
    ds = {}
    for k in reversed(range(len(names))):
        name = names[k]
        if name in flip:
            d = np.where((acts[k + 1] > 0) ^ flip[name], d, np.float32(0))
        ds[name] = d
        d = _dot(d, layers[name][:-1].T)
    return ds, d


def _gradients(layers, bot, top, bot_acts, top_acts, t, d_logit, dim, flip):
    """The backward pass of a (sub-)batch: ``({layer: dL/dz}, dL/dT)``."""
    vectors = t.shape[1]
    lower_i, lower_j = np.tril_indices(vectors, -1)
    ds, d_r = _backward(layers, top, top_acts, d_logit[:, None], flip)
    d_z = np.zeros((t.shape[0], vectors, vectors), np.float32)
    d_z[:, lower_i, lower_j] = d_r[:, dim:]
    d_t = _dot(d_z + d_z.transpose(0, 2, 1), t)
    ds_bot, _ = _backward(layers, bot, bot_acts, d_r[:, :dim] + d_t[:, 0], flip)
    return {**ds, **ds_bot}, d_t


def apply(
    cfg: dict, rows: Dict[str, np.ndarray], ids: Dict[str, np.ndarray],
    batches: List[Dict[str, np.ndarray]],
) -> tuple:
    """``rows`` after the batches, in order, and beside them how far every
    element was moved in all: the sum of the magnitudes of its addends, ``lr /
    n x |one example's gradient|`` an example (what a summation error of the
    system under test can be a share of).

    THE RELU'S CORNER.  The loss is continuous in every pre-activation, its
    gradient is not: a unit whose ``z`` is zero to a rounding passes its
    gradient on one side and nothing on the other, and a system whose sums
    round otherwise may stand on the other side.  Such units are marked
    (:func:`_forward`; two examples in a hundred have one), the backward pass
    of such an example is made once more for each of them, with that ONE unit
    turned round, and whatever that moves, element by element, is allowed
    twice over
    (added to ``moved`` divided by ``delta_rtol``, which the check multiplies
    back; twice, so that a system on the other side reads half its
    allowance, as it may elsewhere): an example on a corner is held to both
    sides, none is skipped."""
    lr = np.float32(cfg["learning_rate"])
    dim = int(cfg["dim"])
    rtol = np.float32(cfg["reference"]["delta_rtol"])
    relu_ulps = float(cfg["reference"]["relu_ulps"])
    names = list(layer_shapes(cfg))
    bot, top = names[:len(cfg["bottom_mlp"])], names[len(cfg["bottom_mlp"]):]
    table, layers = unpack(
        cfg, rows["parameters"].astype(np.float32), ids["embedding"].size
    )
    table = table.copy()
    moved = {name: np.zeros_like(layers[name]) for name in names}
    moved["embedding"] = np.zeros_like(table)
    for b in batches:
        pos = np.searchsorted(ids["embedding"], b["ids"])  # (B, F)
        live = b["mask"]
        lower_i, lower_j = np.tril_indices(pos.shape[1] + 1, -1)
        # -- forward
        bot_acts, corner = _forward(
            layers, bot, b["dense"].astype(np.float32), True, relu_ulps
        )
        z0 = bot_acts[-1]
        t = np.concatenate([z0[:, None, :], table[pos]], axis=1)
        z = _dot(t, t.transpose(0, 2, 1))
        r = np.concatenate([z0, z[:, lower_i, lower_j]], axis=1)
        top_acts, top_corner = _forward(layers, top, r, False, relu_ulps)
        corner.update(top_corner)
        logit = top_acts[-1][:, 0]
        # -- backward: p - y = -s / (1 + exp(s logit)), s the label's sign
        sign = np.where(b["label"] > 0, 1, -1).astype(np.float32)
        n = np.float32(max(int(live.sum()), 1))
        d_logit = (
            np.where(live, -sign / (1 + np.exp(sign * logit)), 0) / n
        ).astype(np.float32)
        none = {name: False for name in corner}
        ds, d_t = _gradients(
            layers, bot, top, bot_acts, top_acts, t, d_logit, dim, none
        )
        acts = dict(zip(bot + top, bot_acts[:-1] + top_acts[:-1]))
        # -- every (example, unit) on its ReLU's corner, the other side: a
        # sub-batch with one row a pair and that one unit turned round
        pairs = {
            name: np.nonzero(c & live[:, None]) for name, c in corner.items()
        }
        on = np.concatenate([ex for ex, _ in pairs.values()])
        flip, at = {}, 0
        for name, (ex, unit) in pairs.items():
            flip[name] = np.zeros((on.size, corner[name].shape[1]), bool)
            flip[name][at + np.arange(ex.size), unit] = True
            at += ex.size
        ds_on, d_t_on = _gradients(
            layers, bot, top, [a[on] for a in bot_acts],
            [a[on] for a in top_acts], t[on], d_logit[on], dim, flip,
        )
        # -- the step
        new = {}
        for name in names:
            d, a = ds[name], acts[name]
            grad = np.concatenate([_dot(a.T, d), d.sum(axis=0)[None]])
            new[name] = (layers[name] - lr * grad).astype(np.float32)
            other = 2 * np.abs(ds_on[name] - d[on]) / rtol
            moved[name] += lr * np.concatenate([
                np.abs(a).T @ np.abs(d) + np.abs(a[on]).T @ other,
                (np.abs(d).sum(axis=0) + other.sum(axis=0))[None],
            ])
        layers = new
        deltas = (-lr * d_t[:, 1:]).astype(np.float32)
        allowed = np.abs(deltas)
        np.add.at(
            allowed, on, 2 * np.abs(-lr * d_t_on[:, 1:] - deltas[on]) / rtol
        )
        np.add.at(table, pos.reshape(-1), deltas.reshape(-1, dim))
        np.add.at(moved["embedding"], pos.reshape(-1), allowed.reshape(-1, dim))
    # the padding repeats the largest id: every repeat shows that id's row
    at = np.searchsorted(ids["embedding"], ids["embedding"])
    return (
        {"parameters": pack(cfg, table[at], layers)},
        {"parameters": pack(cfg, moved.pop("embedding")[at], moved)},
    )
