"""DiFacto: a factorisation machine whose OPTIMISER lives in the server's row
(Li, Wang, Liu, Smola, "DiFacto: Distributed Factorization Machines", WSDM
2016; ``dmlc/difacto``: the updater under its ``src/sgd``, ``UpdateW`` and
``UpdateV``).

Reference parity: the reference server's ``SimplePSLogic(paramInit,
paramUpdate)`` with a ``paramUpdate`` that is two optimisers over lane ranges
of one row (SURVEY.md §2 #3; ``models/logistic_ftrl.py`` is the scalar case).
The server's row for feature ``i`` is

    (w, z, s, c, V[0..k), S[0..k))        4 + 2 k lanes, 36 for k = 16

``w`` the linear weight, ``(z, s)`` its FTRL state, ``c`` the feature's count
(read only here: a pass over the data makes it), ``V`` the embedding and ``S``
its AdaGrad state; ``s`` and ``S`` are the SQUARE ROOTS of the accumulated
squared gradients, as the source stores them.  A feature's embedding is LIVE
once its count passes ``V_threshold`` and (``l1_shrk``) its weight is not
zero: ``a_i = (c_i > V_threshold) and (w_i != 0)``.

Workers pull the WORKER'S PART of a row, its first ``4 + k`` lanes ``(w, z,
s, c, V)`` (they read ``w``, ``c`` and ``V``; a prefix keeps the row's layout
as it is, and ``S``, the half of the row that is AdaGrad's, never leaves the
server: ``make_store`` sets ``StoreSpec.worker_width``), use ``V_i`` where
``a_i`` and 0 elsewhere in the degree-2 FM of
``models/factorization_machine.forward_gradients``, and push RAW gradients,
no rate, that wide, ``(gw, 0, 0, 0, gV)``: ``gw = g x_i``, ``gV = a_i g x_i
(sum_j a_j V_j x_j - V_i x_i)``, ``g = p - y``.  The store sums a minibatch's
pushes per feature to ``(Gw, GV)`` and runs the rule once a touched row
(``core/store.push``), on the WHOLE row as it stood at the start of the step:

    UpdateW   gw = Gw + l2 w;   s' = sqrt(s^2 + gw^2)
              z' = z - gw + (s' - s) / lr * w
              w' = 0 if |z'| <= l1 else (z' - sgn(z') l1) / ((lr_beta + s') / lr)
    UpdateV   only where a_i held on the CURRENT row, for each d:
              gv = GV_d + V_l2 V_d;   S_d' = sqrt(S_d^2 + gv^2)
              V_d' = V_d - V_lr / (S_d' + V_lr_beta) * gv
    c' = c

The minibatch form is the source's own (its server applies the updater once a
feature a pushed minibatch).  ``z`` has the opposite sign to McMahan's.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ..core.batched import BatchedWorkerLogic, PushRequest
from ..core.store import InitFn, ShardedParamStore
from ..training.tracing import scope
from ..utils.initializers import normal_factor
from .factorization_machine import FieldLanes, forward_gradients

Array = jax.Array

# lanes of the server's row; V and S follow, ``dim`` lanes each
W, Z, S, C = 0, 1, 2, 3
STATE_LANES = 4


@dataclasses.dataclass(frozen=True)
class DiFactoConfig:
    num_features: int
    dim: int = 16

    @property
    def row_lanes(self) -> int:
        return STATE_LANES + 2 * self.dim


def embedding_live(w: Array, c: Array, threshold: float) -> Array:
    """``a_i``: the count past ``threshold`` and a weight that L1 has not
    shrunk to zero; the worker's gate and the server's, one function."""
    return (c > threshold) & (w != 0)


@dataclasses.dataclass(frozen=True)
class DiFactoUpdater:
    """The rule, with its hyper-parameters as data (``dmlc/difacto``'s
    defaults): ``rule(current, combined)`` is a ``StoreSpec.update`` over
    rows ``(..., 4 + 2 dim)``, vectorised over the leading axes.
    ``combined`` is read by lane number, ``W`` and ``[4, 4 + dim)``: the
    sums of whole-row pushes or of the worker's part, ``(..., 4 + dim)``."""

    lr: float = 0.01
    lr_beta: float = 1.0
    l1: float = 1.0
    l2: float = 0.0
    V_lr: float = 0.01
    V_lr_beta: float = 1.0
    V_l2: float = 0.01
    V_threshold: float = 10.0

    def weights(self, z: Array, s: Array) -> Array:
        """The closed-form weight of ``UpdateW`` from ``(z, s)``."""
        w = (z - jnp.sign(z) * self.l1) / ((self.lr_beta + s) / self.lr)
        return jnp.where(jnp.abs(z) <= self.l1, jnp.zeros_like(w), w)

    def __call__(self, current: Array, combined: Array) -> Array:
        current, combined = jnp.asarray(current), jnp.asarray(combined)
        dim = (current.shape[-1] - STATE_LANES) // 2
        v_at, s_at = STATE_LANES, STATE_LANES + dim
        w, z, s, c = (current[..., lane] for lane in (W, Z, S, C))
        V, S_v = current[..., v_at:s_at], current[..., s_at:]
        # UpdateW: FTRL on the linear weight
        gw = combined[..., W] + self.l2 * w
        s_new = jnp.sqrt(s * s + gw * gw)
        # s' - s, written without the cancellation
        grown = gw * gw / jnp.maximum(
            s_new + s, jnp.finfo(current.dtype).tiny
        )
        z_new = z - gw + (grown / self.lr) * w
        # UpdateV: AdaGrad on the embedding, where the CURRENT row is live
        live = embedding_live(w, c, self.V_threshold)[..., None]
        gv = combined[..., v_at:s_at] + self.V_l2 * V
        S_new = jnp.sqrt(S_v * S_v + gv * gv)
        V_new = V - (self.V_lr / (S_new + self.V_lr_beta)) * gv
        return jnp.concatenate(
            [
                jnp.stack(
                    [self.weights(z_new, s_new), z_new, s_new, c], axis=-1
                ),
                jnp.where(live, V_new, V),
                jnp.where(live, S_new, S_v),
            ],
            axis=-1,
        ).astype(current.dtype)


class DiFacto(FieldLanes, BatchedWorkerLogic):
    """Batch keys as ``FactorizationMachine``'s: ``ids`` (B,K) int (-1 in a
    dead lane), ``values`` (B,K) float, ``feat_mask`` (B,K) bool, ``label``
    (B,) ±1, ``mask`` (B,) bool.  ``pulled`` is ``(B, K, 4 + dim)``, the
    worker's part of the rows, and the pushed ids, mask and gradients lie
    so (``(K, B, .)``, the field axis leading, in the copy a step in one
    place traces: ``FieldLanes``, the FM family's lane order); the gradients
    are as wide as the rows came (of a
    store whose spec names no worker's part, one reloaded by
    ``ShardedParamStore.from_values``, whole rows come and whole rows go,
    the lanes past ``V`` zeros: the step answers at the width it was
    handed); the worker is stateless (weights, embeddings and both optimisers' state live
    on the server).  Beside ``prediction`` and ``loss`` the outputs carry two
    counts of the step's lanes, made on the device from the logic's own
    masks: ``fm_live_keys`` (the live lanes of the step's keys) and
    ``fm_v_live_keys`` (those whose embedding the gate let through)."""

    def __init__(
        self, config: DiFactoConfig,
        updater: DiFactoUpdater = DiFactoUpdater(),
    ):
        self.config = config
        # the gate's threshold is the server's: one number, one place
        self.V_threshold = updater.V_threshold

    def init_state(self, rng: Array):
        return ()

    def keys(self, batch: Dict[str, Array]) -> Array:
        return batch["ids"]

    def step(self, state, batch: Dict[str, Array], pulled: Array):
        dim = self.config.dim
        v_at = STATE_LANES
        feat_mask = self.lanes(batch["feat_mask"])
        mask = feat_mask & self.by_field(batch["mask"])
        x = jnp.where(
            feat_mask, self.lanes(batch["values"]).astype(jnp.float32), 0.0
        )
        w = pulled[..., W]
        with scope("ps.gate"):
            # a feature's embedding counts only where its row is live
            v_live = embedding_live(
                w, pulled[..., C], self.V_threshold
            ) & feat_mask
            v = jnp.where(
                v_live[..., None], pulled[..., v_at:v_at + dim], 0.0
            )

        def loss_gradient(y_hat):
            # p - y for y in {0, 1}, written -s / (1 + exp(s y_hat)), s the
            # label's sign: the same number without the subtraction
            # (models/logistic_ftrl.example_deltas)
            sign = jnp.where(batch["label"] > 0, 1.0, -1.0).astype(
                y_hat.dtype
            )
            return (
                -sign / (1.0 + jnp.exp(sign * y_hat)),
                jax.nn.softplus(-sign * y_hat),
            )

        y_hat, loss, gw, gv = forward_gradients(
            x, w, v, loss_gradient, 0.0, self.field_axis
        )
        with scope("ps.gate"):
            gv = jnp.where(v_live[..., None], gv, 0.0)
        with scope("ps.delta_build"):
            # raw gradients at the width the rows came: (gw, 0, 0, 0, gV),
            # the worker's part; whole rows get zeros for the lanes of S
            lead = gw.shape
            past = pulled.shape[-1] - (v_at + dim)
            deltas = jnp.concatenate(
                [gw[..., None], jnp.zeros(lead + (v_at - 1,), gw.dtype), gv]
                + [jnp.zeros(lead + (past,), gw.dtype)] * (past > 0),
                axis=-1,
            )
        out = {
            "prediction": jax.nn.sigmoid(y_hat),
            "loss": loss * batch["mask"],
            "fm_live_keys": jnp.sum(mask, dtype=jnp.int32),
            "fm_v_live_keys": jnp.sum(v_live & mask, dtype=jnp.int32),
        }
        return state, PushRequest(self.lanes(batch["ids"]), deltas, mask), out

    def publish_counts(self, outs, registry, total, peak) -> None:
        # the live lanes of the dispatch's keys, and those whose embedding
        # the gate let through
        registry.gauge("fm_live_keys", component="train").set(
            total(outs["fm_live_keys"]))
        registry.gauge("fm_v_live_keys", component="train").set(
            total(outs["fm_v_live_keys"]))


def fresh_rows(
    config: DiFactoConfig, updater: DiFactoUpdater = DiFactoUpdater(), *,
    seed=0, init_stddev: float = 0.01, dtype=jnp.float32,
) -> InitFn:
    """Rows of a model nobody has trained: ``w, z, s, S = 0``, ``V ~ N(0,
    init_stddev)`` (the source's ``V_init_scale``), and every feature
    counted as seen often enough (``c = V_threshold + 1``: a deployment
    brings its counts from a pass over its data, through ``make_store``'s
    ``init_fn``).  With ``w = 0`` no embedding is live until FTRL gives its
    feature a weight."""
    vinit = normal_factor(seed, (config.dim,), stddev=init_stddev, dtype=dtype)

    def init(ids: Array) -> Array:
        v = vinit(ids)
        state = jnp.zeros(ids.shape + (STATE_LANES,), v.dtype)
        state = state.at[..., C].set(updater.V_threshold + 1)
        return jnp.concatenate([state, v, jnp.zeros_like(v)], axis=-1)

    return init


def make_store(
    config: DiFactoConfig, updater: DiFactoUpdater = DiFactoUpdater(), *,
    seed=0, init_fn: Optional[InitFn] = None, mesh=None, dtype=None,
    layout: str = "auto",
) -> ShardedParamStore:
    """``(num_features, 4 + 2 dim)`` store whose update rule is ``updater``,
    its rows ``init_fn(ids)`` (a model under way: restored, or seeded warm)
    or, by default, :func:`fresh_rows` of ``seed``, initialised in place
    (``ShardedParamStore.create``).  ``seed`` may be traced
    (``jax.jit(lambda seed: make_store(..., seed=seed))``: one program
    whatever the seed).  The rows' place on the chip is
    ``core/store._resolve_layout``'s to choose.  The worker's part of a row
    is ``(w, z, s, c, V)``, ``4 + dim`` lanes (``StoreSpec.worker_width``):
    a step pulls and pushes those, ``S`` stays on the server."""
    dtype = dtype or jnp.float32
    return ShardedParamStore.create(
        config.num_features, (config.row_lanes,), dtype=dtype,
        init_fn=init_fn or fresh_rows(config, updater, seed=seed, dtype=dtype),
        update=updater, mesh=mesh, layout=layout,
        worker_width=STATE_LANES + config.dim,
    )


__all__ = [
    "DiFacto",
    "DiFactoConfig",
    "DiFactoUpdater",
    "embedding_live",
    "fresh_rows",
    "make_store",
]
