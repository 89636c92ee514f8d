"""Online passive-aggressive classification on the parameter server.

Reference parity (SURVEY.md §2 #9, §3.4):
``PassiveAggressiveParameterServer.transformBinary / transformMulticlass``
— online PA linear classification where the model is a weight vector keyed
by feature id, *sparse*: for each labeled example the worker pulls only the
feature ids with nonzero value (multi-pull), waits for all answers, computes
the margin, applies the PA / PA-I / PA-II update rule (aggressiveness C),
pushes ``τ·y·xᵢ`` per feature, and outputs the prediction.

TPU-first mapping: the per-example multi-pull + countdown-until-complete
bookkeeping (reference worker state) disappears — a microbatch of sparse
examples is padded to ``(B, K)`` (ids, values, feature mask) and the whole
multi-pull is ONE sharded gather; the PA update is fused elementwise math;
all pushes are one sharded scatter-add.  Binary keeps scalar weights
(value_shape ``()``); multiclass keeps a per-feature class-weight row
(value_shape ``(num_classes,)``) so one pull fetches every class's weight —
the reference's per-class vectors re-laid-out for one gather instead of C.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp

from ..core.api import WorkerLogic
from ..core.batched import BatchedWorkerLogic, PushRequest
from ..core.store import ShardedParamStore
from ..core.transform import transform_batched
from ..utils.initializers import zeros

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class PARule:
    """PA update-step size τ.  variant: "PA" | "PA-I" | "PA-II" with
    aggressiveness C (the reference algorithms' constructor param)."""

    variant: str = "PA-I"
    C: float = 1.0

    def tau(self, loss: Array, sq_norm: Array) -> Array:
        sq = jnp.maximum(sq_norm, 1e-12)
        if self.variant == "PA":
            return loss / sq
        if self.variant == "PA-I":
            return jnp.minimum(self.C, loss / sq)
        if self.variant == "PA-II":
            return loss / (sq + 1.0 / (2.0 * self.C))
        raise ValueError(f"unknown PA variant {self.variant}")


class PassiveAggressiveBinary(BatchedWorkerLogic):
    """Batch keys: ``ids`` (B,K) int, ``values`` (B,K) float, ``feat_mask``
    (B,K) bool, ``label`` (B,) ±1, ``mask`` (B,) bool."""

    def __init__(self, rule: PARule = PARule()):
        self.rule = rule

    def init_state(self, rng: Array):
        return ()  # stateless worker: the model lives entirely on the PS

    def keys(self, batch: Dict[str, Array]) -> Array:
        return batch["ids"]

    def step(self, state, batch: Dict[str, Array], pulled: Array):
        x = batch["values"].astype(jnp.float32)
        fmask = batch["feat_mask"]
        x = jnp.where(fmask, x, 0.0)
        y = batch["label"].astype(jnp.float32)
        w = pulled  # (B, K) scalar weights per present feature
        margin = jnp.sum(w * x, axis=-1)
        loss = jnp.maximum(0.0, 1.0 - y * margin)
        tau = self.rule.tau(loss, jnp.sum(x * x, axis=-1))
        deltas = (tau * y)[:, None] * x  # (B, K)
        mask = fmask & batch["mask"][:, None]
        out = {
            "prediction": jnp.sign(margin),
            "margin": margin,
            "loss": loss * batch["mask"],
        }
        return state, PushRequest(batch["ids"], deltas, mask), out


class PassiveAggressiveMulticlass(BatchedWorkerLogic):
    """Multiclass PA (max-margin violator): per-feature class-weight rows.

    τ = loss / (2‖x‖²) — the multiclass PA scaling (the update touches two
    class rows per feature, hence the factor 2 in the squared norm).
    """

    def __init__(self, num_classes: int, rule: PARule = PARule()):
        self.num_classes = num_classes
        self.rule = rule

    def init_state(self, rng: Array):
        return ()

    def keys(self, batch: Dict[str, Array]) -> Array:
        return batch["ids"]

    def step(self, state, batch: Dict[str, Array], pulled: Array):
        x = jnp.where(batch["feat_mask"], batch["values"].astype(jnp.float32), 0.0)
        y = batch["label"].astype(jnp.int32)  # (B,) class index
        w = pulled  # (B, K, C)
        scores = jnp.einsum("bk,bkc->bc", x, w)
        B, C = scores.shape
        true_score = jnp.take_along_axis(scores, y[:, None], axis=1)[:, 0]
        # highest-scoring wrong class
        masked = scores.at[jnp.arange(B), y].set(-jnp.inf)
        runner = jnp.argmax(masked, axis=1)
        runner_score = jnp.max(masked, axis=1)
        loss = jnp.maximum(0.0, 1.0 - (true_score - runner_score))
        tau = self.rule.tau(loss, 2.0 * jnp.sum(x * x, axis=-1))
        onehot_y = jax.nn.one_hot(y, C)
        onehot_r = jax.nn.one_hot(runner, C)
        direction = onehot_y - onehot_r  # (B, C)
        deltas = tau[:, None, None] * x[:, :, None] * direction[:, None, :]
        mask = batch["feat_mask"] & batch["mask"][:, None]
        out = {
            "prediction": jnp.argmax(scores, axis=1),
            "loss": loss * batch["mask"],
        }
        return state, PushRequest(batch["ids"], deltas, mask), out


def transform_binary(
    data,
    *,
    num_features: int,
    rule: PARule = PARule(),
    mesh=None,
    **kwargs,
):
    """Reference ``transformBinary`` analogue: returns TransformResult;
    ``result.store.values()`` is the final weight vector."""
    logic = PassiveAggressiveBinary(rule)
    store = ShardedParamStore.create(
        num_features, (), init_fn=zeros(()), mesh=mesh
    )
    # the store built here has no other owner: the loop takes it, no copy
    kwargs.setdefault("owns_inputs", True)
    return transform_batched(data, logic, store, mesh=mesh, **kwargs)


def transform_multiclass(
    data,
    *,
    num_features: int,
    num_classes: int,
    rule: PARule = PARule(),
    mesh=None,
    **kwargs,
):
    logic = PassiveAggressiveMulticlass(num_classes, rule)
    store = ShardedParamStore.create(
        num_features, (num_classes,), init_fn=zeros((num_classes,)), mesh=mesh
    )
    # the store built here has no other owner: the loop takes it, no copy
    kwargs.setdefault("owns_inputs", True)
    return transform_batched(data, logic, store, mesh=mesh, **kwargs)


class MultiPullWorkerLogic(WorkerLogic):
    """Event-API worker for sparse examples ``(ids, values, label)`` — the
    reference's per-example multi-pull with a countdown until all feature
    answers arrive (SURVEY.md §3.4).  ``complete(ids, x, params, label,
    ps)`` runs once an example's answers are all in."""

    def __init__(self):
        import collections

        self.pending: Dict[int, dict] = {}
        # param_id -> FIFO of pending-example keys awaiting that answer:
        # O(1) per pull answer instead of a linear scan over all pending
        # examples (which goes quadratic on real streams).
        self._waiting: Dict[int, "collections.deque"] = (
            collections.defaultdict(collections.deque)
        )
        self._next = 0

    def on_recv(self, data, ps):
        ids, values, label = data
        ex = {
            "ids": list(ids),
            "values": dict(zip(ids, values)),
            "label": label,
            "missing": set(ids),
            "weights": {},
        }
        self.pending[self._next] = ex
        for fid in ids:
            self._waiting[fid].append(self._next)
            ps.pull(fid)
        self._next += 1

    def on_pull_recv(self, param_id, param_value, ps):
        import numpy as np

        done = []
        q = self._waiting.get(param_id)
        # Answers go to the oldest example still missing this id — the
        # same order the previous insertion-ordered scan produced.
        while q:
            key = q.popleft()
            ex = self.pending.get(key)
            if ex is None or param_id not in ex["missing"]:
                continue  # stale entry (duplicate id within one example)
            ex["weights"][param_id] = param_value
            ex["missing"].discard(param_id)
            if not ex["missing"]:
                done.append(key)
            break  # one answer satisfies one outstanding pull
        if q is not None and not q:
            # don't leak one empty deque per distinct feature id ever seen
            del self._waiting[param_id]
        for key in done:
            ex = self.pending.pop(key)
            self.complete(
                ex["ids"],
                np.array([ex["values"][i] for i in ex["ids"]], np.float32),
                np.array([ex["weights"][i] for i in ex["ids"]], np.float32),
                ex["label"], ps,
            )

    def complete(self, ids, x, params, label, ps):
        raise NotImplementedError


class PABinaryWorkerLogic(MultiPullWorkerLogic):
    """Event-API binary PA, for semantics-parity tests."""

    def __init__(self, rule: PARule = PARule()):
        super().__init__()
        self.rule = rule

    def complete(self, ids, x, w, label, ps):
        import numpy as np

        y = float(label)
        margin = float(w @ x)
        loss = max(0.0, 1.0 - y * margin)
        tau = float(self.rule.tau(jnp.asarray(loss), jnp.asarray(float(x @ x))))
        for fid, xi in zip(ids, x):
            ps.push(fid, tau * y * float(xi))
        ps.output((label, np.sign(margin), margin))


__all__ = [
    "PARule",
    "MultiPullWorkerLogic",
    "PassiveAggressiveBinary",
    "PassiveAggressiveMulticlass",
    "PABinaryWorkerLogic",
    "transform_binary",
    "transform_multiclass",
]
