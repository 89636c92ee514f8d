"""MLPerf's DLRM-DCNv2 (``mlcommons/training`` ``recommendation_v2/
torchrec_dlrm``) by minibatch Adagrad, one bulk-synchronous step a batch —
the plain reference: numpy float32, forward and backward pass written out (no
autodiff, no store, no kernels), a row's gradients added one by one in stream
order (``np.add.at``), the rule row by row.

Per example, everything read as it stood BEFORE the step:

    z0 = MLP_bot(x)                      13-512-256-128, ReLU after every layer
    e_f = sum over the bag of E_f[id]    f = 1..26, ``multi_hot_sizes[f]`` ids
    x0 = [z0; e_1; ...; e_26]            3456 lanes
    u_l = V_l x_l;  v_l = W_l u_l + b_l;  x_{l+1} = x0 * v_l + x_l     l = 0, 1, 2
    p = sigmoid(MLP_over(x_3))           3456-1024-1024-512-256-1, ReLU between
    loss = mean over the live examples of BCE(p, y)

    d_v = d_{l+1} * x0;  dx0 += d_{l+1} * v_l;  dW_l = d_v^t u_l;  db_l = sum d_v
    d_u = W_l^t d_v;  dV_l = d_u^t x_l;  d_l = d_{l+1} + V_l^t d_u

then ``torch.optim.Adagrad``'s step, element-wise, on every row the batch
names (on the SUM ``g`` of the gradients of the lanes that name it) and on
every dense leaf (on the whole batch's gradient); the accumulator is read
AFTER the add:

    G' = G + g * g;    theta' = theta - lr * g / (sqrt(G') + eps)

ONE group of rows is followed, ``rows["parameters"]``, ``2 dim`` lanes wide:
first the touched rows WHOLE (``dim`` weights, then their ``dim``
accumulators), addressed by position in the compact ``touched`` id list;
then every dense leaf and then every leaf's accumulator, flat,
:func:`leaf_shapes`' leaves by name (zeros fill the last row).  (One group,
as cell 10's reference and for its reason: with this share's one-row tables
half the over arch's units light for no example of a batch, their weights'
gradients are zero and the elements never move, and the benchmark's tests
ask of every GROUP that four fifths of its elements move five times their
allowance.)

``moved`` is, element by element, what the check's ``delta_rtol`` multiplies:
how far the element can be off for one part in ``1 / delta_rtol`` of error in
every sum it was made from, because the check knows no rule.  Every
intermediate ``X`` carries ``tol(X)``, what it may be off by: ``delta_rtol x``
the magnitudes of its addends (the order of a float32 sum, the passes of a
float32 product on the MXU, the device's ``exp``, ``sqrt`` and divide) and,
to first order, what it inherits from the ``tol`` of what it was made from
(the rows and leaves the batch reads are the system's own, exact).  What a sum
inherits from its addends' ``tol`` is the ROOT OF THE SUM OF THEIR SQUARES,
not their sum: roundings do not conspire, and through eleven layers whose
rows sum to ten in magnitude and to a third in squares a sum of magnitudes
grows by ten a layer (1e5 x the logit itself at the over arch's end) where
the errors themselves shrink.  The room that leaves is the local term's:
``delta_rtol x`` the SUM of an inner product's magnitudes at every stage,
where a float32 sum is off by a hundredth of that.

THE RELU'S CORNER (``reference.relu_ulps``, read here, not by the harness).
A unit whose pre-activation lies within ``relu_ulps`` float32 roundings of
what it was made from (its own ``tol`` with ``relu_ulps x 2^-23`` for
``delta_rtol``) passes its gradient on one side and nothing on the other, and
a system whose sums round otherwise may stand on the other side: the
backward pass of such an example is made once more for each such unit, with
that ONE unit turned round, and whatever that moves, element by element, is
allowed twice over beside the roundings' ``tol`` (twice, so that a system on
the other side reads half its allowance, as it may elsewhere; over a dense
leaf's pairs the root of the sum of their squares, a few of them turn): an
example on a corner is held to both sides, none is skipped.  (Not a term of the chain:
a turned unit moves an example's gradients by a few per cent in ONE
direction, which the root of a sum of squares misjudges lane by lane, and
the rule below turns a misjudged sign into a whole step.)

THE RULE ends the chain, and not to first order: ``h(g, G) = g / (sqrt(G + g
g) + eps)`` rises with ``g``, so over ``g +- tol(g)`` it is bounded by its
values at the two ends, and ``theta'`` is allowed ``lr x`` the farther of the
two from ``h(g, G)``.  An element whose gradient's allowance reaches zero
(``tol(g) >= |g|``) may take its step in either direction, up to ``lr`` each
way: the ends say so, and the element is held to both.  ``G'`` is allowed
``2 |g| tol(g) + tol(g)^2``.

ONE BATCH is what this holds, from the rows and leaves as the system held
them before it, and what the cell checks (``reference.batches`` 1).  From
accumulators at zero the rule's first step is a SIGN step, ``-lr sign(g)``
whatever ``|g|`` is: a unit that ONE turned example lit alone takes ``+-lr``
on its whole column, coherently, every example of the next batch reads that
column, and which side its units then stand on is no one example's matter
any more.  No chain from the FIRST batch's start bounds a second batch
without allowing it everything, so none is carried: a LATER batch (the
benchmark's tests hand every reference three) takes its steps on the
reference's own rows with its gradients allowed anything (``later``: rows and
leaves then held to the size of a step, ``|h| <= 1``, accumulators not held),
and what the first batch alone touched stays held as tightly as it was.  A
second step can be held only from the state the system itself stood in
before it, as ``tests/test_dlrm_dcnv2.py`` holds three, and as a harness
that checks batch by batch could.

Every matrix product of VALUES goes through :func:`_dot`, so that a control
can run the same equations with coarser products.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from chipbench.references import padded_unique

_dot = np.matmul

F32, F64 = np.float32, np.float64


def touched(batches: List[Dict[str, np.ndarray]]) -> Dict[str, np.ndarray]:
    return {"embedding": padded_unique(np.concatenate(
        [b["ids"].reshape(-1) for b in batches]
    ))}


def width(cfg: dict) -> int:
    """Lanes of ``x0``: the bottom MLP's output and a pooled vector a field."""
    return (len(cfg["multi_hot_sizes"]) + 1) * int(cfg["dim"])


def leaf_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    """``{leaf: shape}`` of the dense net, in order: a matrix ``(inputs,
    outputs)`` and its bias; a cross layer is ``cross{l}v`` (``V^t``, no
    bias) and ``cross{l}w`` (``W^t`` and ``b``)."""
    out, wide = {}, width(cfg)

    def layer(name, n, m, bias=True):
        out[f"{name}_w"] = (int(n), int(m))
        if bias:
            out[f"{name}_b"] = (int(m),)

    widths = [int(cfg["dense_fields"])] + list(cfg["bottom_mlp"])
    for i, (n, m) in enumerate(zip(widths, widths[1:])):
        layer(f"bot{i}", n, m)
    for l in range(int(cfg["cross_layers"])):
        layer(f"cross{l}v", wide, cfg["cross_rank"], bias=False)
        layer(f"cross{l}w", cfg["cross_rank"], wide)
    widths = [wide] + list(cfg["over_mlp"])
    for i, (n, m) in enumerate(zip(widths, widths[1:])):
        layer(f"over{i}", n, m)
    return out


def split(cfg: dict, block: np.ndarray) -> Tuple[dict, dict]:
    """``(leaves, accumulators)`` of the dense part of a ``parameters``
    block."""
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
    flat = np.concatenate(
        [part[name].reshape(-1) for part in (leaves, accs)
         for name in sorted(leaf_shapes(cfg))])
    return np.pad(flat, (0, -flat.size % lanes)).reshape(-1, lanes)


# -- values with what they may be off by --------------------------------------
# Beside every value ``x`` its ``e2``: the SQUARE of what it may be off by, in
# units of ``delta_rtol`` (float32: the squares of magnitudes, never of
# allowances, so nothing underflows).
def _mm(a, ea2, b, eb2=None):
    """``(a b, e2)``: the magnitudes of the product's addends, and what its
    operands may be off by, the root of the sum of their squares (``b`` a
    leaf as the system held it, exact, where ``eb2`` is not given)."""
    e2 = np.square(np.matmul(np.abs(a), np.abs(b)))
    e2 += np.matmul(ea2, np.square(b))
    if eb2 is not None:
        e2 += np.matmul(np.square(a), eb2)
    return _dot(a, b).astype(F32), e2


def _times(a, ea2, b, eb2):
    return (a * b).astype(F32), ea2 * b * b + a * a * eb2 + np.square(a * b)


def _plus(a, ea2, b, eb2):
    return (a + b).astype(F32), ea2 + eb2 + np.square(np.abs(a) + np.abs(b))


def _total(d, ed2):
    """The sum over the batch of ``d``'s rows."""
    return d.sum(axis=0).astype(F32), ed2.sum(axis=0) + np.square(
        np.abs(d).sum(axis=0))


class _Net:
    """One batch's forward pass, every value with its ``e2``, and the
    backward pass of any of its examples."""

    def __init__(self, cfg, p, corner):
        """``corner``: ``relu_ulps`` roundings, in units of ``delta_rtol``."""
        self.p, self.corner2 = p, F32(corner * corner)
        self.dim, self.cross = int(cfg["dim"]), int(cfg["cross_layers"])
        self.bot = [f"bot{i}" for i in range(len(cfg["bottom_mlp"]))]
        self.over = [f"over{i}" for i in range(len(cfg["over_mlp"]))]
        # a ReLU after every layer of the bottom MLP, between the over arch's
        self.relu = set(self.bot) | set(self.over[:-1])
        self.inputs, self.on, self.corner = {}, {}, {}

    def _layers(self, names, x):
        for name in names:
            self.inputs[name] = x
            z, ez2 = _plus(
                *_mm(*x, self.p[f"{name}_w"]), self.p[f"{name}_b"], F32(0))
            if name in self.relu:
                self.on[name] = z > 0
                self.corner[name] = z * z <= self.corner2 * ez2
                ez2 = np.where(self.on[name] | self.corner[name], ez2, F32(0))
                z = np.maximum(z, F32(0))
            x = (z, ez2)
        return x

    def forward(self, dense, pooled, e_pooled):
        """The logits ``(value, e2)`` of a batch."""
        z0, ez0 = self._layers(self.bot, (dense, np.zeros_like(dense)))
        self.x0 = x0 = (
            np.concatenate([z0, pooled], axis=1),
            np.concatenate([ez0, e_pooled], axis=1))
        x, self.v = x0, []
        for l in range(self.cross):
            self.inputs[f"cross{l}v"] = x
            u = _mm(*x, self.p[f"cross{l}v_w"])
            self.inputs[f"cross{l}w"] = u
            self.v.append(_plus(
                *_mm(*u, self.p[f"cross{l}w_w"]), self.p[f"cross{l}w_b"],
                F32(0)))
            x = _plus(*_times(*x0, *self.v[-1]), *x)
        logit, e_logit = self._layers(self.over, x)
        return logit[:, 0], e_logit[:, 0]

    def _back(self, names, rows, d, ed2, flip, ds):
        for name in reversed(names):
            if name in self.relu:
                on = self.on[name][rows] ^ flip.get(name, False)
                d, ed2 = np.where(on, d, F32(0)), np.where(on, ed2, F32(0))
            ds[name] = (d, ed2)
            d, ed2 = _mm(d, ed2, self.p[f"{name}_w"].T)
        return d, ed2

    def backward(self, rows, d_logit, e_d_logit, flip):
        """``{site: (dL/d(the site's product), e2)}`` for the examples
        ``rows``, ``flip[layer]`` (bool, like the layer's units) turning a
        ReLU round: the layers' outputs, a cross layer's ``v`` (site
        ``cross{l}w``) and ``u`` (``cross{l}v``), and ``x0``."""
        ds = {}
        x0 = tuple(a[rows] for a in self.x0)
        d = self._back(
            self.over, rows, d_logit[:, None], e_d_logit[:, None], flip, ds)
        d_x0 = (np.zeros_like(x0[0]), np.zeros_like(x0[0]))
        for l in reversed(range(self.cross)):
            v = tuple(a[rows] for a in self.v[l])
            ds[f"cross{l}w"] = d_v = _times(*d, *x0)
            d_x0 = _plus(*d_x0, *_times(*d, *v))
            ds[f"cross{l}v"] = d_u = _mm(*d_v, self.p[f"cross{l}w_w"].T)
            d = _plus(*d, *_mm(*d_u, self.p[f"cross{l}v_w"].T))
        ds["x0"] = d_x0 = _plus(*d_x0, *d)
        self._back(
            self.bot, rows, d_x0[0][:, :self.dim], d_x0[1][:, :self.dim],
            flip, ds)
        return ds

    def corners(self, live):
        """Every (example, unit) on its ReLU's corner as a sub-batch, one
        row a pair: the examples, and ``flip`` with that ONE unit turned."""
        pairs = {
            name: np.nonzero(c & live[:, None])
            for name, c in self.corner.items()}
        rows = np.concatenate([ex for ex, _ in pairs.values()])
        flip, at = {}, 0
        for name, (ex, unit) in pairs.items():
            flip[name] = np.zeros((rows.size, self.on[name].shape[1]), bool)
            flip[name][at + np.arange(ex.size), unit] = True
            at += ex.size
        return rows, flip


def _h(g, acc, eps):
    return g / (np.sqrt(acc + g * g) + eps)


def _adagrad(theta, acc, g, tg, lr, eps, rtol):
    """One rule step where ``hit`` (the caller masks): the new ``(theta,
    G)`` in float32, and what each may be off by (float64): the two ends of
    ``h`` over ``g +- tol(g)``, and the device's square root and divide."""
    new_acc = (acc + g * g).astype(F32)
    new = (theta - lr * g / (np.sqrt(new_acc) + eps)).astype(F32)
    g, acc = g.astype(F64), acc.astype(F64)
    h = _h(g, acc, eps)
    more = float(lr) * (np.maximum(
        _h(g + tg, acc, eps) - h, h - _h(g - tg, acc, eps)) + rtol * np.abs(h))
    more_acc = 2 * np.abs(g) * tg + tg * tg + rtol * g * g
    return new, new_acc, more, more_acc


def apply(
    cfg: dict, rows: Dict[str, np.ndarray], ids: Dict[str, np.ndarray],
    batches: List[Dict[str, np.ndarray]],
) -> tuple:
    dim = int(cfg["dim"])
    lr, eps = F32(cfg["learning_rate"]), F32(cfg["eps"])
    check = cfg["reference"]
    rtol = float(check["delta_rtol"])
    corner = float(check["relu_ulps"]) * float(np.finfo(F32).eps)
    sizes = np.asarray(cfg["multi_hot_sizes"])
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    field_of = np.repeat(np.arange(sizes.size), sizes)
    names = list(leaf_shapes(cfg))
    # the padding repeats the largest id: work on the distinct rows alone
    known = ids["embedding"]
    count = int(np.searchsorted(known, known[-1])) + 1
    block = rows["parameters"].astype(F32)
    table = block[:count].copy()
    p, acc = split(cfg, block[known.size:])
    # by element, what the check's delta_rtol multiplies
    moved = {"embedding": np.zeros(table.shape)}
    moved.update({k: np.zeros(v.shape) for k, v in p.items()})
    moved.update({f"{k}_acc": np.zeros(v.shape) for k, v in acc.items()})
    later = 0.0  # 1 from the second batch on: its gradients are allowed anything
    for b in batches:
        pos = np.searchsorted(known[:count], b["ids"])  # (B, lookups)
        live = b["mask"]
        # -- forward
        pulled = table[:, :dim][pos]
        pooled = np.add.reduceat(pulled, starts, axis=1).astype(F32)
        e_pooled = np.square(np.add.reduceat(np.abs(pulled), starts, axis=1))
        net = _Net(cfg, p, corner)
        logit, e_logit = net.forward(
            b["dense"].astype(F32), pooled.reshape(len(live), -1),
            e_pooled.reshape(len(live), -1))
        # -- backward: p - y = -s / (1 + exp(s logit)), s the label's sign
        sign = np.where(b["label"] > 0, 1, -1).astype(F32)
        n = F32(max(int(live.sum()), 1))
        e = np.exp(sign * logit)
        d_logit = (np.where(live, -sign / (1 + e), 0) / n).astype(F32)
        # |d/dlogit| = p (1 - p) / n; the device's exp and divide twice over
        e_d_logit = (
            np.square(np.where(live, e / (1 + e) ** 2 / n, 0)) * e_logit
            + np.square(2 * d_logit)).astype(F32)
        ds = net.backward(np.arange(len(live)), d_logit, e_d_logit, {})
        # -- every (example, unit) on its ReLU's corner, the other side; of
        # the pairs a few turn: the root of the sum of their squares
        on, flip = net.corners(live)
        other = net.backward(on, d_logit[on], np.zeros_like(d_logit[on]), flip)
        other = {k: np.abs(other[k][0] - ds[k][0][on]) for k in ds}

        def turned(a, d):
            return later + 2 * np.sqrt(
                np.square(a).T @ np.square(d), dtype=F64)

        # -- the leaves' gradients, what rounding may leave them off by and,
        # twice, what the turned units move them by
        grads = {}
        for site, (d, ed2) in ds.items():
            if site == "x0":
                continue
            a, ea2 = net.inputs[site]
            g, eg2 = _mm(a.T, ea2.T, d, ed2)
            grads[f"{site}_w"] = g, rtol * np.sqrt(eg2, dtype=F64) + turned(
                a[on], other[site])
            if f"{site}_b" in p:
                g, eg2 = _total(d, ed2)
                grads[f"{site}_b"] = g, rtol * np.sqrt(eg2, dtype=F64) + turned(
                    np.ones((on.size, 1), F32), other[site])[0]
        # -- the server: a row's gradients one by one in stream order, a
        # bag's pooled gradient to every row of the bag; the rule row by row
        d_rows, e_rows = (
            a[:, dim:].reshape(len(live), sizes.size, dim)[:, field_of]
            for a in ds["x0"])
        where = np.where(live[:, None], pos, count).reshape(-1)
        g = np.zeros((count + 1, dim), F32)
        np.add.at(g, where, d_rows.reshape(-1, dim))
        order = np.argsort(where, kind="stable")
        first = np.flatnonzero(np.diff(where[order], prepend=-1))
        at = where[order][first]
        eg = np.zeros((count + 1, dim))
        eg[at] = np.add.reduceat(
            e_rows.reshape(-1, dim)[order], first, axis=0) + np.square(
            np.add.reduceat(np.abs(d_rows).reshape(-1, dim)[order], first, axis=0))
        # (a turned example's lanes on one row move together: their sum)
        tg = later + rtol * np.sqrt(eg)
        np.add.at(
            tg, where.reshape(len(live), -1)[on].reshape(-1),
            2 * other["x0"][:, dim:].reshape(on.size, sizes.size, dim)[:, field_of]
            .reshape(-1, dim))
        hit = np.zeros(count + 1, bool)
        hit[at] = True
        g, tg, hit = g[:count], tg[:count], hit[:count, None]
        new, new_acc, more, more_acc = _adagrad(
            table[:, :dim], table[:, dim:], g, tg, lr, eps, rtol)
        table = np.where(hit, np.concatenate([new, new_acc], axis=1), table)
        moved["embedding"] += np.where(
            hit, np.concatenate([more, more_acc], axis=1), 0) / rtol
        # -- the worker: Adagrad on every dense leaf, once a batch
        for k in names:
            p[k], acc[k], more, more_acc = _adagrad(
                p[k], acc[k], *grads[k], lr, eps, rtol)
            moved[k] += more / rtol
            moved[f"{k}_acc"] += more_acc / rtol
        later = 1.0
    # every repeat of the padding shows the largest id's row
    back = np.searchsorted(known[:count], known)
    lanes = 2 * dim
    return (
        {"parameters": np.concatenate(
            [table[back], join(cfg, p, acc, lanes)])},
        {"parameters": np.concatenate([
            moved["embedding"][back], join(
                cfg, {k: moved[k] for k in names},
                {k: moved[f"{k}_acc"] for k in names}, lanes),
        ]).astype(F32)},
    )
