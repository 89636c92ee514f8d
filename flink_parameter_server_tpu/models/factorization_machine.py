"""Factorization Machine (degree-2) on the PS — wide sparse embeddings.

Reference parity: BASELINE.json config #4 — "Factorization Machine on
Criteo-1TB (wide sparse embedding table)".  The PS formulation keys the
model by feature id: each id owns a scalar weight w_i and a latent vector
v_i; examples are sparse (pull only present ids), gradients are sparse
pushes — the same multi-pull pattern as passive-aggressive (SURVEY.md
§3.4) with a wider value row.

TPU-first: one store row per feature = ``(1 + dim,)`` (w_i ‖ v_i), so one
sharded gather per microbatch fetches both.  The O(K²) pairwise interaction
uses the standard linear-time identity

    ΣΣ ⟨v_i, v_j⟩ x_i x_j = ½ (‖Σ x_i v_i‖² − Σ ‖x_i v_i‖²)

which is two fused batched reductions on TPU.  Training is logistic (CTR
convention) or squared loss SGD; the global bias is a reserved feature id
(``bias_id``) the data pipeline appends with value 1.0.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp

from ..core.batched import BatchedWorkerLogic, PushRequest
from ..core.store import ShardedParamStore
from ..core.transform import transform_batched
from ..utils.initializers import normal_factor

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class FMConfig:
    num_features: int
    dim: int = 8
    learning_rate: float = 0.05
    l2: float = 0.0
    loss: str = "logistic"  # or "squared"


def forward_gradients(x: Array, w: Array, v: Array, loss_gradient, l2: float):
    """The degree-2 FM on examples with active values ``x`` (B, K), weights
    ``w`` (B, K) and latent vectors ``v`` (B, K, d): ``(y_hat, loss, dw,
    dv)``, the prediction, ``loss_gradient(y_hat) -> (dL/dy_hat, loss)``'s
    loss, and the loss's gradients a (example, feature) with ``l2`` times
    the parameter added.  The forward pass and the gradient algebra of every
    FM logic (``FactorizationMachine``; ``models/difacto.DiFacto``, which
    hands in its gated ``v``)."""
    linear = jnp.sum(w * x, axis=-1)  # (B,)
    xv = x[..., None] * v  # (B, K, d)
    s = jnp.sum(xv, axis=1)  # (B, d)  Σ x_i v_i
    interaction = 0.5 * (jnp.sum(s * s, axis=-1) - jnp.sum(xv * xv, axis=(1, 2)))
    y_hat = linear + interaction  # (B,)
    g, loss = loss_gradient(y_hat)
    # ∂ŷ/∂w_i = x_i ;  ∂ŷ/∂v_i = x_i (s − x_i v_i)
    dw = g[:, None] * x + l2 * w
    dv = g[:, None, None] * (x[..., None] * (s[:, None, :] - xv)) + l2 * v
    return y_hat, loss, dw, dv


class FactorizationMachine(BatchedWorkerLogic):
    """Batch: ``ids`` (B,K) int, ``values`` (B,K) float, ``feat_mask``
    (B,K) bool, ``label`` (B,) (±1 logistic / float squared), ``mask`` (B,).
    """

    def __init__(self, config: FMConfig):
        self.config = config

    def init_state(self, rng: Array):
        return ()

    def keys(self, batch: Dict[str, Array]) -> Array:
        return batch["ids"]

    def step(self, state, batch: Dict[str, Array], pulled: Array):
        cfg = self.config
        x = jnp.where(batch["feat_mask"], batch["values"].astype(jnp.float32), 0.0)

        def loss_gradient(y_hat):
            label = batch["label"].astype(jnp.float32)
            if cfg.loss == "logistic":
                # dL/dy_hat for y ∈ {−1,+1}: −y σ(−y ŷ)
                return (-label * jax.nn.sigmoid(-label * y_hat),
                        jax.nn.softplus(-label * y_hat))
            g = y_hat - label
            return g, 0.5 * g * g

        y_hat, loss, dw, dv = forward_gradients(
            x, pulled[..., 0], pulled[..., 1:], loss_gradient, cfg.l2
        )
        deltas = jnp.concatenate(
            [-cfg.learning_rate * dw[..., None], -cfg.learning_rate * dv], axis=-1
        )  # (B, K, 1+d)

        mask = batch["feat_mask"] & batch["mask"][:, None]
        out = {
            "prediction": y_hat,
            "loss": loss * batch["mask"],
        }
        return state, PushRequest(batch["ids"], deltas, mask), out


def make_store(
    config: FMConfig, *, seed: int = 0, init_stddev: float = 0.01, mesh=None,
    dtype=None, layout: str = "auto",
) -> ShardedParamStore:
    """(num_features, 1+dim) store: w zero-init, v ~ N(0, init_stddev).

    The FM row is NARROW (1+dim = 17 for Criteo shapes), so the default
    ``layout="auto"`` lets the store pick from what it sees
    (``core/store._resolve_layout``): 7 rows to a 128-lane physical row
    (ops/packed.py), on one shard and on every shard of a ``ps`` mesh, so
    pull and push move whole 128-lane rows.  ``"dense"`` and ``"packed"``
    pin a layout."""
    dtype = dtype or jnp.float32
    vinit = normal_factor(seed, (config.dim,), stddev=init_stddev,
                          dtype=dtype)

    def init(ids: Array) -> Array:
        v = vinit(ids)
        return jnp.concatenate([jnp.zeros(ids.shape + (1,), v.dtype), v], axis=-1)

    return ShardedParamStore.create(
        config.num_features, (1 + config.dim,), init_fn=init, mesh=mesh,
        dtype=dtype, layout=layout,
    )


def train_fm(data, config: FMConfig, *, seed: int = 0, mesh=None, **kwargs):
    """End-to-end FM training; ``result.store.values()`` is the
    (num_features, 1+dim) model."""
    logic = FactorizationMachine(config)
    store = make_store(config, seed=seed, mesh=mesh)
    # the store built here has no other owner: the loop takes it, no copy
    kwargs.setdefault("owns_inputs", True)
    return transform_batched(
        data, logic, store, rng=jax.random.PRNGKey(seed), mesh=mesh, **kwargs
    )


__all__ = [
    "FMConfig", "FactorizationMachine", "forward_gradients", "make_store",
    "train_fm",
]
