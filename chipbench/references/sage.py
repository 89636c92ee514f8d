"""GraphSAGE by sampled mini-batches (Hamilton et al., NeurIPS 2017, as
DistDGL trains it: three ``SAGEConv`` mean layers, fan-outs 15 / 10 / 5, Adam)
-- the plain reference: numpy float32, the sampler's arithmetic, the forward
and the backward pass and Adam written out (no autodiff, no store, nothing of
the program's model or store code).  ``jax.random`` alone gives the random
words, under the key the CONFIGURATION documents (``sampler_key``):

    words(fold)  = jax.random.bits(fold_in(fold_in(key, t), fold), shape, uint32)

``key`` the sampler's key in the worker's state, ``t`` the number of steps
taken before this one.  One step, for seeds ``s`` (depth 0), with ``k_d`` the
fan-out of depth ``d`` (the list read from its END: the seeds draw the last):

    depth d + 1   for a node v of depth d with first = off[v], deg = off[v + 1]
                  - first: child j = nbr[first + words(d)[j, i] mod deg], j <
                  k_d, i the node's lane; no child (DEAD lanes) where deg = 0
                  or v is dead itself; a dead seed is one whose mask is false
    h^0           the feature row of every node of depths 0..3, depth by
                  depth, a sampled block in C order of its (k_d, n_d) shape
    layer l       over the depths 0 .. 3 - l:  pre = h W_self + mean W_neigh
                  + b, mean the sum of a node's live children's h over k_d
                  (zero where it has none); l < 3: h = 2 relu(pre) where
                  words(2 + l)[lane, unit] < 2^31, else 0
    loss          the mean over the live seeds of logsumexp(h^3) - h^3[label]
    Adam          t' = t + 1; b1' = b1 beta1, b2' = b2 beta2 (float32 running
                  powers); m' = beta1 m + (1 - beta1) g; v' = beta2 v + (1 -
                  beta2) g g; w' = w - lr (m' / (1 - b1')) / (sqrt(v' / (1 -
                  b2')) + eps)

The FAMILY reads the adjacency rows and the feature rows this file asks for
through the stores' own ``pull`` (:func:`walk` takes the function), so the
reference holds no table.  ONE group of rows is followed,
``rows["parameters"]``, 128 lanes wide: every leaf, then Adam's ``m`` and
``v`` of every leaf, then ``t`` and the two running powers, then a SAMPLE of
the read-only rows the batches touched (the first seeds' row ends, first
neighbour and features, every word as two 16-bit halves): those must come
back as they were, to the bit; they stand in the one group because the
harness fails a group "training left unchanged".

``moved`` is, element by element, what the check's ``delta_rtol`` multiplies.
A GRADIENT's is the sum of the magnitudes of its addends (the backward pass
run on magnitudes, a ReLU open wherever its pre-activation is above MINUS
``relu_ulps`` roundings of the magnitudes it was summed from), plus, over
``delta_rtol``, twice what THE RELU'S CORNERS move (the addends that pass a
unit within those roundings of zero, which two float32 systems may put on
either side: cell 10's reason) and what ROUNDING leaves in the values it was
made from: an activation may be off by ``relu_ulps`` roundings of the
magnitudes it was summed from and by what it inherits from the layer below
(independent roundings: the root of the sum of their squares),
the logits' share of that goes through the softmax into the backward values,
and both reach a gradient to first order (a value that cancellation left
near zero is off by a large share of ITSELF, and a unit that few seeds light
hands that whole to its column of the last layer's gradient: cell 17's
reason).  Adam carries a gradient's allowance to ``m``
linearly, to ``v`` through ``2 |g| dg + dg^2``, and to ``w`` by the step's
range over the box ``(m +- dm, v +- dv)`` (its four corners), step after
step: a gradient within its rounding of zero takes a first step of either
sign, and the allowance knows it.  What it does NOT know: from the second
step on the leaves THEMSELVES are off by what the first allowed, and every
activation that reads them inherits that.  A unit that few nodes light has
gradients near Adam's ``eps``, its first step is of almost any size, and on
the chip at full size its column of the next step's moments read 2.6 x an
allowance that knew only roundings (PERF.md section 6, PR 75); carried as a
BOUND it makes every second-step allowance a hundredth of its gradient
(3 % of the first layer's weights stand within their allowance of zero).  So
the cell's check holds ONE batch, as cells 15 and 17's do, and the CPU's
tests hold two and three, where float32 against float32 at a small size
stays inside the roundings.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Tuple

import numpy as np

LANES = 128
SAMPLE_SEEDS = 64
F32 = np.float32
ULP = float(np.finfo(np.float32).eps)


# -- shapes ---------------------------------------------------------------------

def leaf_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    widths = [int(w) for w in cfg["widths"]]
    shapes = {}
    for i, (n, m) in enumerate(zip(widths, widths[1:]), 1):
        shapes[f"w{i}_self"] = (n, m)
        shapes[f"w{i}_neigh"] = (n, m)
        shapes[f"b{i}"] = (m,)
    return shapes


def draws(cfg: dict, depth: int) -> int:
    return int(cfg["fanouts"][-1 - depth])


def lanes_at(cfg: dict, seeds: int) -> List[int]:
    lanes = [seeds]
    for depth in range(len(cfg["fanouts"])):
        lanes.append(lanes[-1] * draws(cfg, depth))
    return lanes


def laid(flat: np.ndarray) -> np.ndarray:
    flat = np.asarray(flat, F32).reshape(-1)
    return np.pad(flat, (0, -flat.size % LANES)).reshape(-1, LANES)


def halves(ints: np.ndarray) -> np.ndarray:
    """An array of 32-bit words (int32 ids, or the uint32 view of float32
    values) as float32, exactly: its low and high 16 bits."""
    ints = np.asarray(ints).astype(np.int64).reshape(-1)
    return np.stack([ints & 0xFFFF, ints >> 16]).astype(F32).reshape(-1)


# -- the sampler ------------------------------------------------------------------

def words(key, t: int, fold: int, shape) -> np.ndarray:
    import jax

    k = jax.random.fold_in(jax.random.fold_in(
        np.asarray(key, np.uint32), np.int32(t)), fold)
    return np.asarray(jax.random.bits(k, shape, np.uint32))


def walk(cfg: dict, key, t: int, seeds, mask, pull: Callable) -> dict:
    """One step's sampled tree: ``nodes`` and ``live`` (int32 0/1) of every
    depth, flat in C order of the ``(k_d, n_d)`` blocks.  ``pull(store, ids)
    -> rows`` reads ``off`` and ``nbr`` (int64 numpy ids in, integer rows
    out)."""
    nodes = [np.asarray(seeds, np.int64)]
    live = [np.asarray(mask, bool)]
    for depth in range(len(cfg["fanouts"])):
        k, v = draws(cfg, depth), nodes[depth]
        first = np.asarray(pull("off", v), np.int64)
        degree = np.asarray(pull("off", v + 1), np.int64) - first
        has = live[depth] & (degree > 0)
        pick = words(key, t, depth, (k, v.size)).astype(np.int64) % np.maximum(
            degree, 1)
        at = np.where(has, first + pick, 0).reshape(-1)
        child = np.asarray(pull("nbr", at), np.int64)
        alive = np.broadcast_to(has, (k, v.size)).reshape(-1)
        nodes.append(np.where(alive, child, 0))
        live.append(alive)
    return {"nodes": nodes, "live": [a.astype(np.int32) for a in live]}


# -- one step ---------------------------------------------------------------------

def _means(cfg, h, live, lanes, depths):
    """The children's mean of every destination lane of the depths below
    ``depths``: sum of the live children over the fan-out."""
    out, at = [], lanes[0]
    for depth in range(depths):
        k, n = draws(cfg, depth), lanes[depth]
        alive = live[depth + 1].reshape(k, n).astype(bool)
        block = h[at:at + k * n].reshape(k, n, -1)
        total = np.where(alive[..., None], block, F32(0)).sum(axis=0, dtype=F32)
        out.append(np.where(alive[0][:, None], total / F32(k), F32(0)))
        at += k * n
    return np.concatenate(out)


def _spread(cfg, d_mean, live, lanes, depths, rows, width):
    """:func:`_means` backwards: a destination's ``d_mean / k`` to each of
    its live children, as a block over all ``rows`` input lanes."""
    d_in = np.zeros((rows, width), F32)
    at, done = lanes[0], 0
    for depth in range(depths):
        k, n = draws(cfg, depth), lanes[depth]
        alive = live[depth + 1].reshape(k, n).astype(bool)
        share = d_mean[done:done + n] / F32(k)
        d_in[at:at + k * n] = np.where(
            alive[..., None], share[None], F32(0)).reshape(k * n, width)
        at, done = at + k * n, done + n
    return d_in


def forward(cfg, leaves, x, live, key, t):
    """Every layer's inputs, pre-activations and masks, and the logits."""
    layers = len(cfg["fanouts"])
    lanes = [a.size for a in live]
    h, acts = x, []
    for layer in range(1, layers + 1):
        depths = layers - layer + 1
        dest = sum(lanes[:depths])
        mean = _means(cfg, h, live, lanes, depths)
        pre = (h[:dest] @ leaves[f"w{layer}_self"]
               + mean @ leaves[f"w{layer}_neigh"] + leaves[f"b{layer}"])
        keep = None
        if layer < layers:
            keep = np.ones(pre.shape, bool)
            if float(cfg["dropout"]):
                keep = words(key, t, layers - 1 + layer, pre.shape) < np.uint32(
                    round((1.0 - float(cfg["dropout"])) * 2.0**32))
        acts.append({"h": h, "mean": mean, "pre": pre, "keep": keep,
                     "dest": dest, "depths": depths})
        if layer < layers:
            scale = F32(1.0 / (1.0 - float(cfg["dropout"])))
            h = np.where(keep, np.maximum(pre, F32(0)) * scale, F32(0))
        else:
            h = pre
    return acts, h


def backward(cfg, leaves, acts, live, d_top, opens):
    """The leaves' gradients from the logits' ``d_top``; ``opens[l]`` says
    where layer ``l + 1``'s ReLU passes a gradient.  Run on magnitudes
    (``leaves``, ``acts`` and ``d_top`` all absolute) it gives every
    gradient's sum of magnitudes: every other factor is non-negative."""
    layers = len(cfg["fanouts"])
    lanes = [a.size for a in live]
    grads, d_pre = {}, d_top
    for layer in range(layers, 0, -1):
        a = acts[layer - 1]
        grads[f"w{layer}_self"] = a["h"][:a["dest"]].T @ d_pre
        grads[f"w{layer}_neigh"] = a["mean"].T @ d_pre
        grads[f"b{layer}"] = d_pre.sum(axis=0, dtype=F32)
        if layer == 1:
            break
        rows, width = a["h"].shape
        d_h = _spread(cfg, d_pre @ leaves[f"w{layer}_neigh"].T, live, lanes,
                      a["depths"], rows, width)
        d_h[:a["dest"]] += d_pre @ leaves[f"w{layer}_self"].T
        below = acts[layer - 2]
        scale = F32(1.0 / (1.0 - float(cfg["dropout"])))
        d_pre = np.where(below["keep"] & opens[layer - 2], d_h * scale, F32(0))
    return grads


def one_step(cfg, leaves, x, live, label, key, t, relu_ulps):
    """``(grads, sums, corners, rounding)``: the leaves' gradients, their
    sums of magnitudes with every ReLU's corner open, the part of those sums
    that passes a corner, and what the rounding of the activations and of
    the backward values they were made from may leave in them."""
    acts, logits = forward(cfg, leaves, x, live, key, t)
    seeds_live = live[0].astype(bool)
    examples = F32(max(int(seeds_live.sum()), 1))
    shifted = logits - logits.max(axis=1, keepdims=True)
    p = np.exp(shifted)
    p /= p.sum(axis=1, keepdims=True, dtype=F32)
    onehot = np.zeros_like(p)
    onehot[np.arange(p.shape[0]), label] = 1
    d_top = np.where(seeds_live[:, None], (p - onehot) / examples, F32(0))
    opens = [a["pre"] > 0 for a in acts[:-1]]
    grads = backward(cfg, leaves, acts, live, d_top, opens)
    # magnitudes: what each pre-activation was summed from, and beside it
    # what rounding may have left in every activation: `reach` of its own
    # sum and what it inherits from the layer below, to first order
    mags = {k: np.abs(v) for k, v in leaves.items()}
    scale = F32(1.0 / (1.0 - float(cfg["dropout"])))
    lanes = [a.size for a in live]
    abs_acts, off_acts, wide, narrow = [], [], [], []
    squares = {k: v * v for k, v in leaves.items()}
    off_sq = np.zeros_like(x)  # (the features are exact)
    for layer, a in enumerate(acts, 1):
        summed = (np.abs(a["h"][:a["dest"]]) @ mags[f"w{layer}_self"]
                  + np.abs(a["mean"]) @ mags[f"w{layer}_neigh"]
                  + mags[f"b{layer}"])
        reach = F32(relu_ulps * ULP) * summed
        wide.append(a["pre"] > -reach)
        narrow.append(a["pre"] > reach)
        abs_acts.append({**a, "h": np.abs(a["h"]), "mean": np.abs(a["mean"])})
        # roundings are independent: what a sum inherits adds in squares (a
        # mean's square bounded by the mean of the squares)
        mean_sq = _means(cfg, off_sq, live, lanes, a["depths"])
        off_acts.append({**a, "h": np.sqrt(off_sq), "mean": np.sqrt(mean_sq)})
        pre_sq = (off_sq[:a["dest"]] @ squares[f"w{layer}_self"]
                  + mean_sq @ squares[f"w{layer}_neigh"] + reach * reach)
        off_pre = np.sqrt(pre_sq)
        if layer < len(acts):
            off_sq = np.where(
                a["keep"] & wide[-1], pre_sq * (scale * scale), F32(0))
    # the logits' rounding through the softmax: dp_c <= p_c (dz_c + sum_j
    # p_j dz_j), and the exponential's own
    off_top = np.where(seeds_live[:, None], (
        p * (off_pre + (p * off_pre).sum(axis=1, keepdims=True))
        + F32(relu_ulps * ULP) * (p + onehot)) / examples, F32(0))
    top = np.abs(d_top)
    sums = backward(cfg, mags, abs_acts, live, top, wide[:-1])
    sure = backward(cfg, mags, abs_acts, live, top, narrow[:-1])
    from_top = backward(cfg, mags, abs_acts, live, off_top, wide[:-1])
    from_acts = backward(cfg, mags, off_acts, live, top, wide[:-1])
    corners = {k: np.maximum(sums[k] - sure[k], F32(0)) for k in sums}
    # (a bias' gradient has no activation among its factors)
    rounding = {k: from_top[k] + (from_acts[k] if k[0] == "w" else F32(0))
                for k in sums}
    return grads, sums, corners, rounding


def _adam_step(m_hat, v_hat, eps):
    return m_hat / (np.sqrt(np.maximum(v_hat, F32(0))) + eps)


# -- what the harness calls -------------------------------------------------------

def touched(batches: List[dict]) -> Dict[str, np.ndarray]:
    """The checked batches' own seeds, labels and masks: what the family
    walks the graph from (one shape whatever the seed)."""
    return {
        "seed": np.stack([np.asarray(b["seed"], np.int32) for b in batches]),
        "label": np.stack([np.asarray(b["label"], np.int32) for b in batches]),
        "mask": np.stack([np.asarray(b["mask"], np.int32) for b in batches]),
    }


def unlaid(cfg: dict, parameters: np.ndarray) -> Dict[str, np.ndarray]:
    """``rows["parameters"]`` taken apart again: leaves, moments, scalars,
    and ``sample``, the read-only rows behind them."""
    flat, out, at = np.asarray(parameters, F32).reshape(-1), {}, 0
    shapes = leaf_shapes(cfg)
    for prefix in ("", "m_", "v_"):
        for name in sorted(shapes):
            size = int(np.prod(shapes[name]))
            out[prefix + name] = flat[at:at + size].reshape(shapes[name])
            at += size
    for name in ("t", "beta1_t", "beta2_t"):
        out[name] = flat[at]
        at += 1
    out["sample"] = flat[at:]
    return out


def apply(cfg: dict, rows: dict, ids: dict, batches: List[dict]):
    """``(want, moved)`` of the one group after the batches, from
    ``rows["parameters"]`` before them, the sampler's ``rows["key"]`` and the
    trees the family walked: ``rows["features"]`` ``(batches, lanes, width)``
    and ``rows["live"]`` ``(batches, lanes)``."""
    check = cfg["reference"]
    rtol, relu_ulps = float(check["delta_rtol"]), float(check["relu_ulps"])
    state = unlaid(cfg, rows["parameters"])
    shapes = leaf_shapes(cfg)
    b1, b2 = F32(cfg["beta1"]), F32(cfg["beta2"])
    lr, eps = F32(cfg["learning_rate"]), F32(cfg["eps"])
    lanes = lanes_at(cfg, ids["seed"].shape[1])
    cuts = np.cumsum([0] + lanes)
    t0 = int(state["t"])
    tol = {k: np.zeros(s, F32) for k, s in shapes.items()}       # of w
    tol_m = {k: np.zeros(s, F32) for k, s in shapes.items()}
    tol_v = {k: np.zeros(s, F32) for k, s in shapes.items()}
    for n in range(len(batches)):
        live = [rows["live"][n][a:b] for a, b in zip(cuts, cuts[1:])]
        leaves = {k: state[k] for k in shapes}
        grads, sums, corners, rounding = one_step(
            cfg, leaves, rows["features"][n], live, ids["label"][n],
            rows["key"], t0 + n, relu_ulps)
        state["t"] = F32(state["t"] + 1)
        b1t = state["beta1_t"] = F32(state["beta1_t"] * b1)
        b2t = state["beta2_t"] = F32(state["beta2_t"] * b2)
        for k, g in grads.items():
            dg = F32(rtol) * sums[k] + F32(2.0) * corners[k] + rounding[k]
            m = state["m_" + k] = b1 * state["m_" + k] + F32(1 - b1) * g
            v = state["v_" + k] = b2 * state["v_" + k] + F32(1 - b2) * (g * g)
            dm = tol_m[k] = b1 * tol_m[k] + F32(1 - b1) * dg
            dv = tol_v[k] = b2 * tol_v[k] + F32(1 - b2) * (
                F32(2) * np.abs(g) * dg + dg * dg)
            m_hat, v_hat = m / F32(1 - b1t), v / F32(1 - b2t)
            dm_hat, dv_hat = dm / F32(1 - b1t), dv / F32(1 - b2t)
            # (the box takes m and v apart, which Adam does not: whatever the
            # gradients, by Cauchy-Schwarz m^2 <= (1 - b1)^2 sum_j (b1^2 /
            # b2)^j v / (1 - b2), and |m_hat| / sqrt(v_hat) stays under `most`,
            # here with a twentieth of room: a weight whose gradient stands
            # within its allowance of zero may take the step with either sign,
            # and then reads its whole range over this)
            steps = int(round(float(state["t"])))
            series = sum((float(b1) ** 2 / float(b2)) ** j for j in range(steps))
            most = F32((1 - b1) * np.sqrt(series / (1 - b2))
                       * np.sqrt(1 - b2t) / (1 - b1t) * 1.05)
            ends = [np.clip(_adam_step(m_hat + s * dm_hat, v_hat + u * dv_hat,
                                       eps), -most, most)
                    for s in (F32(-1), F32(1)) for u in (F32(-1), F32(1))]
            tol[k] = tol[k] + lr * (np.maximum.reduce(ends)
                                    - np.minimum.reduce(ends))
            state[k] = state[k] - lr * _adam_step(m_hat, v_hat, eps)
    names = sorted(shapes)
    want = np.concatenate(
        [state[p + k].reshape(-1) for p in ("", "m_", "v_") for k in names]
        + [np.asarray([state["t"], state["beta1_t"], state["beta2_t"]], F32),
           state["sample"]])
    moved = np.concatenate(
        [t[k].reshape(-1) / F32(rtol) for t in (tol, tol_m, tol_v)
         for k in names]
        + [np.asarray([len(batches), 1.0, 1.0], F32),
           np.zeros_like(state["sample"])])
    return {"parameters": laid(want)}, {"parameters": laid(moved)}
