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

import copy
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


def forward_gradients(
    x: Array, w: Array, v: Array, loss_gradient, l2: float,
    field_axis: int = 1,
):
    """The degree-2 FM on examples with active values ``x``, weights ``w``
    and latent vectors ``v`` (``v``'s last axis the ``d`` latent lanes):
    ``(y_hat, loss, dw, dv)``, the prediction ``(B,)``,
    ``loss_gradient(y_hat) -> (dL/dy_hat, loss)``'s loss, and the loss's
    gradients a (example, feature), shaped as ``w`` and ``v``, with ``l2``
    times the parameter added.  Generic in where the fields lie:
    ``field_axis`` 1 takes ``x``, ``w`` ``(B, K)`` and ``v`` ``(B, K, d)``,
    example-major; ``field_axis`` 0 takes them ``(K, B)`` and ``(K, B, d)``,
    FIELD-major, the batch the minor axis of every buffer (lane-dense on a
    TPU, where a minor axis of 39 fields fills 39 of 128 lanes) and every
    sum over the fields a sum over a leading axis.  The forward pass and the
    gradient algebra of every FM logic (``FactorizationMachine``;
    ``models/difacto.DiFacto``, which hands in its gated ``v``)."""

    def by_field(a: Array) -> Array:
        # an example's number beside each of its fields
        return jnp.expand_dims(a, field_axis)

    linear = jnp.sum(w * x, axis=field_axis)  # (B,)
    xv = x[..., None] * v  # x_i v_i
    s = jnp.sum(xv, axis=field_axis)  # (B, d)  Σ x_i v_i
    interaction = 0.5 * (
        jnp.sum(s * s, axis=-1) - jnp.sum(xv * xv, axis=(field_axis, 2))
    )
    y_hat = linear + interaction  # (B,)
    g, loss = loss_gradient(y_hat)
    # ∂ŷ/∂w_i = x_i ;  ∂ŷ/∂v_i = x_i (s − x_i v_i)
    dw = by_field(g) * x + l2 * w
    dv = by_field(g)[..., None] * (x[..., None] * (by_field(s) - xv)) + l2 * v
    return y_hat, loss, dw, dv


class FieldLanes:
    """The lane order of the logics whose batch is ``(B, K)``, ``B`` examples
    of ``K`` fields: the FM family's (``FactorizationMachine``,
    ``models/difacto.DiFacto``) and ``models/dlrm.DLRM``, whose fields are
    its categorical ones and whose interaction sums over them.

    The logic itself is EXAMPLE-major, as every ``BatchedWorkerLogic`` is:
    ``keys()`` gives the batch's ``ids`` ``(B, K)``, ``pulled`` is ``(B, K,
    d)`` and the push's ids, mask and deltas leave ``(B, K[, d])``, whoever
    calls ``step`` (``cluster/driver.ClusterDriver`` does, with rows it
    pulled itself).

    ``for_workers(1)``, what ``make_train_step`` traces in one place, is a
    copy that computes FIELD-major, the field axis leading and the batch
    axis minor.  Its ``keys()`` are the same ``(B, K)`` (the rows are
    gathered in the stream's order), it says ``pulls_turned``, so
    ``pulled`` comes ``(K, B, d)``; the deltas, the ids and the mask of the
    push leave ``(K, B, d)`` and ``(K, B)``, so the push's lanes are
    field-major, lane ``f B + b``.  The stream's batch is untouched: the
    logic turns its three ``(B, K)`` arrays inside the step.  On a TPU a
    packed store's kernels hand over and take a step's rows feature-major;
    with the batch the minor axis the logic's buffers are ``f32[d, K, B]``
    there, lane-dense, the pull's kernel writes exactly that and the push's
    reads it (``core/store.arms``' ``fields``), where ``(B, K, d)`` costs a
    loop of ``d`` trips through a flat buffer each way (6 ms of cell 2's
    53: PERF.md section 6, PR 63; at DLRM's 64 lanes two copies and two
    flattens of the batch's rows, 5 ms of cell 10's 51: PR 65).  The rows
    are still GATHERED
    example-major, the key block's own order: field-major a field's few
    rows are named 32,768 lanes on end, and the TPU's gather pays 3.5 ms a
    step for that.  Where a row belongs to one field (a key space a field),
    a row's deltas are summed in the order example-major lanes give them,
    by example; where fields share rows the order differs, and with it the
    sum's last bits.

    Over several data-parallel workers the step keeps the logic as it is:
    ``make_train_step`` splits a batch over its workers on the leading
    axis, so a worker's lanes are its examples' only in that order."""

    field_major = False

    def for_workers(self, workers: int):
        if self.field_major == (workers == 1):
            return self
        other = copy.copy(self)
        other.field_major = workers == 1
        return other

    @property
    def pulls_turned(self) -> bool:
        return self.field_major

    @property
    def field_axis(self) -> int:
        return 0 if self.field_major else 1

    def lanes(self, per_key: Array) -> Array:
        """A ``(B, K)`` array of the batch as the step computes with it."""
        return per_key.T if self.field_major else per_key

    def by_field(self, per_example: Array) -> Array:
        """A ``(B,)`` array of the batch beside each of its fields."""
        return jnp.expand_dims(per_example, self.field_axis)


class FactorizationMachine(FieldLanes, BatchedWorkerLogic):
    """Batch: ``ids`` (B,K) int, ``values`` (B,K) float, ``feat_mask``
    (B,K) bool, ``label`` (B,) (±1 logistic / float squared), ``mask`` (B,).
    ``pulled``, the push's ids, deltas and mask are as :class:`FieldLanes`
    has them (``(B, K)`` leading; ``(K, B)`` in the copy a step in one place
    traces); ``prediction`` and ``loss`` are ``(B,)``.
    """

    def __init__(self, config: FMConfig):
        self.config = config

    def init_state(self, rng: Array):
        return ()

    def keys(self, batch: Dict[str, Array]) -> Array:
        return batch["ids"]

    def step(self, state, batch: Dict[str, Array], pulled: Array):
        cfg = self.config
        feat_mask = self.lanes(batch["feat_mask"])
        x = jnp.where(
            feat_mask, self.lanes(batch["values"]).astype(jnp.float32), 0.0
        )

        def loss_gradient(y_hat):
            label = batch["label"].astype(jnp.float32)
            if cfg.loss == "logistic":
                # dL/dy_hat for y ∈ {−1,+1}: −y σ(−y ŷ)
                return (-label * jax.nn.sigmoid(-label * y_hat),
                        jax.nn.softplus(-label * y_hat))
            g = y_hat - label
            return g, 0.5 * g * g

        y_hat, loss, dw, dv = forward_gradients(
            x, pulled[..., 0], pulled[..., 1:], loss_gradient, cfg.l2,
            self.field_axis,
        )
        deltas = jnp.concatenate(
            [-cfg.learning_rate * dw[..., None], -cfg.learning_rate * dv], axis=-1
        )  # (lanes, 1+d)

        mask = feat_mask & self.by_field(batch["mask"])
        out = {
            "prediction": y_hat,
            "loss": loss * batch["mask"],
        }
        return state, PushRequest(self.lanes(batch["ids"]), deltas, mask), out


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
    "FMConfig", "FactorizationMachine", "FieldLanes", "forward_gradients",
    "make_store",
    "train_fm",
]
