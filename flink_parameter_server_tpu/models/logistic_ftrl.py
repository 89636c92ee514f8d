"""Sparse logistic regression with the optimiser ON THE SERVER: per-coordinate
FTRL-Proximal (McMahan et al., "Ad Click Prediction: a View from the
Trenches", KDD 2013, Algorithm 1) as the store's update rule.

Reference parity: the reference server's ``SimplePSLogic(paramInit,
paramUpdate)`` takes an arbitrary ``paramUpdate: (P, P) => P`` (SURVEY.md §2
#3); this is the model that uses it.  The server's row for feature ``i`` is
``(w_i, z_i, n_i)``: workers pull rows and read the weight (lane 0), and push
for each active feature of an example the delta ``(g_i, 0, g_i^2)``, ``g_i =
(p - y) x_i``.  The store sums the deltas a batch puts on one row and runs the
rule once a touched row (``core/store.push``), with ``G = sum g`` and ``S =
sum g^2``:

    n' = n + S;   s = (sqrt(n') - sqrt(n)) / alpha;   z' = z + G - s w
    w' = 0 if |z'| <= l1 else -(z' - sgn(z') l1) / ((beta + sqrt(n')) / alpha + l2)

The per-example ``s_i`` of Algorithm 1 telescope (``sum_t sqrt(n_t) -
sqrt(n_{t-1}) = sqrt(n') - sqrt(n)``), so this IS Algorithm 1 run over the
batch's examples in any order with the weights read at the start of the step:
the batched path's standing guarantee (bulk-synchronous, weights at most one
microbatch stale), no approximation and no re-chosen rate.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from ..core.batched import BatchedWorkerLogic, PushRequest
from ..core.store import ShardedParamStore
from ..core.transform import transform_batched
from .passive_aggressive import MultiPullWorkerLogic

Array = jax.Array

# lanes of the server's row
W, Z, N = 0, 1, 2


@dataclasses.dataclass(frozen=True)
class FTRLProximal:
    """The rule, with its hyper-parameters as data: ``rule(current,
    combined)`` is a ``StoreSpec.update`` (rows ``(..., 3)``, vectorised
    over the leading axes) and a ``SimplePSLogic`` ``update`` (one row)."""

    alpha: float = 0.1
    beta: float = 1.0
    l1: float = 1.0
    l2: float = 1.0

    def weights(self, z: Array, n: Array) -> Array:
        """The closed-form weight of Algorithm 1 from ``(z, n)``."""
        shrunk = z - jnp.sign(z) * self.l1
        w = -shrunk / ((self.beta + jnp.sqrt(n)) / self.alpha + self.l2)
        return jnp.where(jnp.abs(z) <= self.l1, jnp.zeros_like(w), w)

    def init(self, _param_id=None) -> np.ndarray:
        """A feature nobody has pushed to: ``(w, z, n) = 0``."""
        return np.zeros(3, np.float32)

    def __call__(self, current: Array, combined: Array) -> Array:
        current, combined = jnp.asarray(current), jnp.asarray(combined)
        w, z, n = current[..., W], current[..., Z], current[..., N]
        n_new = n + combined[..., N]
        # sqrt(n') - sqrt(n), written without the cancellation
        root, root_new = jnp.sqrt(n), jnp.sqrt(n_new)
        step = combined[..., N] / jnp.maximum(
            root_new + root, jnp.finfo(current.dtype).tiny
        )
        z_new = z + combined[..., W] - (step / self.alpha) * w
        return jnp.stack(
            [self.weights(z_new, n_new), z_new, n_new], axis=-1
        ).astype(current.dtype)


def example_deltas(x: Array, w: Array, label: Array):
    """``(margin, deltas)`` of examples with active values ``x`` (..., K),
    pulled weights ``w`` (..., K) and labels in {-1, +1}: the deltas are
    ``(g, 0, g^2)`` a feature, ``g = (sigmoid(margin) - y) x``.  ``p - y``
    is written ``-s / (1 + exp(s margin))``, ``s`` the label's sign: the
    same number without the subtraction, which on the TPU turns
    ``jax.nn.sigmoid``'s 1.3e-6 of absolute error into 1e-4 and more of a
    confident example's gradient (PERF.md section 6, PR 34)."""
    margin = jnp.sum(w * x, axis=-1)
    s = jnp.where(label > 0, 1.0, -1.0).astype(margin.dtype)
    g = (-s / (1.0 + jnp.exp(s * margin)))[..., None] * x
    return margin, jnp.stack([g, jnp.zeros_like(g), g * g], axis=-1)


class LogisticFTRL(BatchedWorkerLogic):
    """Batch keys as the PA logics': ``ids`` (B,K) int, ``values`` (B,K)
    float, ``feat_mask`` (B,K) bool, ``label`` (B,) ±1, ``mask`` (B,) bool.
    ``pulled`` is ``(B, K, 3)``; the worker is stateless (weights and
    optimiser state both live on the server)."""

    def init_state(self, rng: Array):
        return ()

    def keys(self, batch: Dict[str, Array]) -> Array:
        return batch["ids"]

    def step(self, state, batch: Dict[str, Array], pulled: Array):
        x = jnp.where(
            batch["feat_mask"], batch["values"].astype(jnp.float32), 0.0
        )
        label = batch["label"].astype(jnp.float32)
        margin, deltas = example_deltas(x, pulled[..., W], label)
        mask = batch["feat_mask"] & batch["mask"][:, None]
        out = {
            "prediction": jax.nn.sigmoid(margin),
            # log-loss as a softplus of the signed margin: a margin of
            # +-20 prints 20, not inf
            "loss": jax.nn.softplus(-jnp.sign(label) * margin) * batch["mask"],
        }
        return state, PushRequest(batch["ids"], deltas, mask), out


def make_store(
    num_features: int, rule: FTRLProximal = FTRLProximal(), *, mesh=None,
    dtype=None,
) -> ShardedParamStore:
    """``(num_features, 3)`` store of ``(w, z, n)`` rows, all zero, whose
    update rule is ``rule``."""
    return ShardedParamStore.create(
        num_features, (3,), dtype=dtype or jnp.float32, update=rule,
        mesh=mesh,
    )


def train_logistic_ftrl(
    data, *, num_features: int, rule: FTRLProximal = FTRLProximal(),
    mesh=None, **kwargs,
):
    """End-to-end training; ``result.store.values()[:, 0]`` is the weight
    vector, lanes 1 and 2 the optimiser's ``z`` and ``n``."""
    store = make_store(num_features, rule, mesh=mesh)
    # the store built here has no other owner: the loop takes it, no copy
    kwargs.setdefault("owns_inputs", True)
    return transform_batched(data, LogisticFTRL(), store, mesh=mesh, **kwargs)


class LogisticFTRLWorkerLogic(MultiPullWorkerLogic):
    """Event-API worker for ``SimplePSLogic(rule.init, rule)``: per example
    a multi-pull of its features' rows, then one push of ``(g, 0, g^2)`` a
    feature; outputs ``(label, probability)``."""

    def complete(self, ids, x, rows, label, ps):
        margin, deltas = example_deltas(
            jnp.asarray(x), jnp.asarray(rows)[:, W], jnp.float32(label)
        )
        for fid, delta in zip(ids, np.asarray(deltas)):
            ps.push(fid, delta)
        ps.output((label, float(jax.nn.sigmoid(margin))))


__all__ = [
    "FTRLProximal",
    "LogisticFTRL",
    "LogisticFTRLWorkerLogic",
    "example_deltas",
    "make_store",
    "train_logistic_ftrl",
]
