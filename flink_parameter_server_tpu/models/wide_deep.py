"""Wide & Deep: TWO stores in one train step, hashed cross-product weights
under FTRL-Proximal with L1 beside embedding rows under AdaGrad, a ReLU net
in the worker (Cheng et al., "Wide & Deep Learning for Recommender Systems",
DLRS 2016, sections 3.1-3.3 and 4.2; TensorFlow ships it as
``tf.estimator.DNNLinearCombinedClassifier``, ``linear_optimizer='Ftrl'``,
``dnn_optimizer='Adagrad'``, a cross hashed in the graph by
``tf.feature_column.crossed_column``).

Reference parity: the reference server's ``SimplePSLogic(paramInit,
paramUpdate)`` takes any ``paramUpdate: (P, P) => P`` (SURVEY.md §2 #3), one
server logic a job; this is the model whose parameter groups differ in KEY
SPACE, row width and rule at once, so they are two named stores that one
step trains (``core/store.StoreGroup``, ``core/transform.make_train_step``
over a ``GroupSpec``):

- ``wide``: a row a (cross, bucket), ``(w, z, n)`` under
  ``models/logistic_ftrl.FTRLProximal`` (McMahan et al. 2013, Algorithm 1,
  in the batch form cell 6 runs): the worker reads ``w`` and pushes ``(g, 0,
  g^2)`` a named row.
- ``deep``: a row a categorical value, ``(e[dim], G[dim])`` under
  ``models/dlrm_dcnv2.Adagrad``, element-wise, the accumulator read after
  the add; the worker's part is ``e`` (``StoreSpec.worker_width``): it pulls
  those lanes and pushes the RAW gradient that wide, the accumulators never
  leave the server.

Per example with dense ``x`` and ids ``c_1..c_F`` (float32 throughout):

    e_f = E[off_f + c_f]                           f = 1..F, dim lanes
    a0 = [e_1; ...; e_F; x]
    a_{i+1} = relu(W_i a_i + b_i)                  the hidden layers
    d = w_out . a_last + b_out
    k_j = off'_j + pair_key(i_{l_j}, i_{r_j}, buckets)     j = 1..J crosses,
                                                   i_f = off_f + c_f, hashed
                                                   INSIDE the step
    s = sum_j w[k_j]
    logit = d + s + bias;   loss = mean over the live examples of BCE

One ``step`` is the forward pass, the backward pass written out, AdaGrad on
every dense leaf and on ``bias`` in the worker's state (once a minibatch, on
the whole batch's gradient, as ``models/dlrm_dcnv2`` keeps its dense net's)
and two pushes to the keys the step pulled (``PushRequest.ids`` ``None``).
A row that several lanes name takes ONE rule step on the sum of their
deltas (the batched path's standing guarantee).  The dense features feed
the deep part alone, raw.

The matmuls run at ``Precision.HIGHEST`` (``models/dlrm._dot``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.batched import BatchedWorkerLogic, PushRequest
from ..core.store import InitFn, ShardedParamStore, StoreGroup
from ..ops.hashing import pair_key
from ..training.tracing import scope
from ..utils.initializers import ranged_random_factor
from .dlrm import _init_layers, _mlp_backward, _mlp_forward
from .dlrm_dcnv2 import Adagrad
from .logistic_ftrl import FTRLProximal, W

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class WideDeepConfig:
    """``field_rows`` the rows of every categorical field, ``dim`` the deep
    part's embedding width, ``hidden`` its ReLU layers (a logistic output
    follows); the wide part crosses field ``j`` with field ``j + 1 mod F``
    (:attr:`pairs`), each cross hashed into its own ``cross_buckets`` rows; ``learning_rate`` and ``eps`` the deep part's
    AdaGrad's (rows and dense leaves alike), ``acc0`` the accumulators' start
    (TensorFlow's ``initial_accumulator_value``), ``ftrl`` the wide rule."""

    field_rows: Tuple[int, ...]
    dense_features: int = 13
    dim: int = 32
    hidden: Tuple[int, ...] = (1024, 512, 256)
    cross_buckets: int = 1 << 20
    learning_rate: float = 0.05
    eps: float = 1e-8
    acc0: float = 0.1
    ftrl: FTRLProximal = FTRLProximal()

    @property
    def fields(self) -> int:
        return len(self.field_rows)

    @property
    def num_rows(self) -> int:
        """Rows of the deep store."""
        return int(sum(self.field_rows))

    @property
    def pairs(self) -> Tuple[Tuple[int, int], ...]:
        """The crosses' ``(l_j, r_j)``: a ring over the fields."""
        return tuple((j, (j + 1) % self.fields) for j in range(self.fields))

    @property
    def wide_rows(self) -> int:
        """Rows of the wide store: ``cross_buckets`` a cross."""
        return len(self.pairs) * self.cross_buckets

    def layers(self) -> Dict[str, Tuple[int, int]]:
        """``{leaf prefix: (inputs, outputs)}`` of the deep net, in order,
        the logistic output's layer last."""
        widths = (self.fields * self.dim + self.dense_features,
                  ) + tuple(self.hidden) + (1,)
        return {
            f"deep{i}": (int(n), int(m))
            for i, (n, m) in enumerate(zip(widths, widths[1:]))
        }

    @property
    def dense_params(self) -> int:
        """Every weight and bias of the deep net (``bias`` is one more)."""
        return sum(n * m + m for n, m in self.layers().values())

    @property
    def macs_per_example(self) -> int:
        """Multiply-adds of one example's FORWARD pass: every layer."""
        return sum(n * m for n, m in self.layers().values())


class WideAndDeep(BatchedWorkerLogic):
    """Batch: ``dense`` (B, dense_features) float, ``ids`` (B, F) int, each
    the row in the DEEP store (its field's first row added), ``label`` (B,)
    positive for a click, ``mask`` (B,) bool.  ``keys`` answers ``{"wide":
    (B, J), "deep": (B, F)}``, the wide keys hashed from the ids inside the
    step; ``pulled`` is ``{"wide": (B, J, 3), "deep": (B, F, dim)}``.  The
    state is a dict of float32 arrays: ``deep{i}_{w|b}`` (a matrix as
    ``(inputs, outputs)``), ``bias`` ``(1,)`` and, for each leaf ``k``,
    ``k_acc``, AdaGrad's accumulator, ``acc0`` at the start.  The outputs
    are ``prediction`` and ``loss``."""

    def __init__(self, config: WideDeepConfig, *, seed=0):
        self.config = config
        self.seed = seed
        pairs = np.asarray(config.pairs, np.int64).reshape(-1, 2)
        self._left, self._right = pairs[:, 0], pairs[:, 1]
        self._wide_firsts = (
            np.arange(len(pairs), dtype=np.int64) * config.cross_buckets
        ).astype(np.int32)

    def init_state(self, rng: Array) -> Dict[str, Array]:
        """The deep net as ``models/dlrm.py`` starts its layers (``W ~ N(0,
        sqrt(2 / (m + n)))``, ``b ~ N(0, sqrt(1 / m))``, one program), the
        bias 0, every accumulator ``acc0``."""
        seed = self.seed
        if not isinstance(seed, jax.Array):  # a number: no program of its own
            seed = np.asarray(seed).astype(np.uint32)
        state = dict(_init_layers(
            rng, seed, layers=tuple(self.config.layers().items())))
        state["bias"] = jnp.zeros((1,), jnp.float32)
        state.update({
            f"{k}_acc": jnp.full_like(v, self.config.acc0)
            for k, v in state.items()})
        return state

    def keys(self, batch: Dict[str, Array]) -> Dict[str, Array]:
        ids = batch["ids"]
        with scope("ps.cross_hash"):
            # a cross of two fields' values (each by its deep row's id, a
            # name no other value has), then its bucket among the cross's own
            wide = self._wide_firsts + pair_key(
                ids[:, self._left], ids[:, self._right],
                self.config.cross_buckets)
        return {"wide": wide, "deep": ids}

    def step(self, state, batch: Dict[str, Array], pulled: Dict[str, Array]):
        cfg = self.config
        live = batch["mask"]
        x = batch["dense"].astype(jnp.float32)
        rows, weights = pulled["deep"], pulled["wide"][..., W]
        depth, lanes = len(cfg.layers()), cfg.fields * cfg.dim
        with scope("ps.dense_top"):
            a0 = jnp.concatenate([rows.reshape(x.shape[0], lanes), x], axis=1)
            acts = _mlp_forward(state, "deep", a0, depth, False)
            logit = acts[-1][:, 0] + weights.sum(axis=1) + state["bias"][0]
            # p - y for y in {0, 1}, written -s / (1 + exp(s logit)), s the
            # label's sign: the same number without the subtraction
            # (models/logistic_ftrl.example_deltas)
            sign = jnp.where(batch["label"] > 0, 1.0, -1.0)
            examples = jnp.maximum(jnp.sum(live, dtype=jnp.float32), 1.0)
            d_logit = jnp.where(
                live, -sign / (1.0 + jnp.exp(sign * logit)), 0.0
            ) / examples
            grads, d_a0 = _mlp_backward(
                state, "deep", acts, d_logit[:, None], False)
            grads["bias"] = d_logit.sum()[None]
            # raw gradients, a pulled row each: an embedding's at the width
            # it came (cut where the first layer's backward product ends:
            # the compiler fuses the cut into that product, and a fusion
            # carries its root's scope) ...
            d_rows = d_a0[:, :lanes].reshape(rows.shape)
        with scope("ps.delta_build"):
            # ... and a cross weight's as the rule's (g, 0, g^2)
            g = jnp.broadcast_to(d_logit[:, None], weights.shape)
            d_weights = jnp.stack([g, jnp.zeros_like(g), g * g], axis=-1)
        with scope("ps.dense_adagrad"):
            new = {}
            for k, grad in grads.items():
                acc = state[f"{k}_acc"] + grad * grad
                new[k] = state[k] - cfg.learning_rate * grad / (
                    jnp.sqrt(acc) + cfg.eps)
                new[f"{k}_acc"] = acc
        out = {
            "prediction": jax.nn.sigmoid(logit),
            "loss": jax.nn.softplus(-sign * logit) * live,
        }
        return new, {
            # (ids None: the keys the step pulled, the hashed ones too)
            "wide": PushRequest(None, d_weights, jnp.broadcast_to(
                live[:, None], weights.shape)),
            "deep": PushRequest(None, d_rows, jnp.broadcast_to(
                live[:, None], rows.shape[:2])),
        }, out


def warm_rows(
    config: WideDeepConfig, *, seed=0, dtype=jnp.float32, z_max: float = 0.0,
    n_max: float = 0.0, acc_span: float = 0.0,
) -> Dict[str, InitFn]:
    """``{store: init}``, every row from the seed and its own id alone
    (``seed`` may be traced).  ``deep``: a field's embedding ``U(-sqrt(1 /
    C), sqrt(1 / C))``, ``C`` the field's rows (``models/dlrm.uniform_rows``'
    law), its accumulators ``acc0 + U[0, acc_span)``.  ``wide``: ``z ~
    U(-z_max, z_max)``, ``n ~ U[0, n_max)``, ``w`` the rule's own weight of
    them, so that with ``z_max`` past the rule's ``l1`` rows stand on both
    sides of its threshold.  The defaults are a fresh model's: zeros, and
    ``acc0``."""
    deep_unit = ranged_random_factor(
        seed, (2 * config.dim,), low=0.0, high=1.0, dtype=dtype)
    wide_unit = ranged_random_factor(
        seed + np.uint32(1), (2,), low=0.0, high=1.0, dtype=dtype)
    firsts = np.concatenate([[0], np.cumsum(config.field_rows)[:-1]])

    def deep(ids: Array) -> Array:
        bound = jnp.ones(ids.shape, dtype)
        for first, card in zip(firsts, config.field_rows):
            bound = jnp.where(ids >= int(first), np.sqrt(1.0 / card), bound)
        unit = deep_unit(ids)
        return jnp.concatenate([
            bound[:, None] * (2.0 * unit[:, :config.dim] - 1.0),
            config.acc0 + acc_span * unit[:, config.dim:],
        ], axis=-1).astype(dtype)

    def wide(ids: Array) -> Array:
        unit = wide_unit(ids)
        z, n = z_max * (2.0 * unit[:, 0] - 1.0), n_max * unit[:, 1]
        return jnp.stack([config.ftrl.weights(z, n), z, n], axis=-1).astype(
            dtype)

    return {"wide": wide, "deep": deep}


def make_stores(
    config: WideDeepConfig, *, seed=0, mesh=None, dtype=None, **warm,
) -> StoreGroup:
    """The two stores, each initialised in place
    (``ShardedParamStore.create``; :func:`warm_rows` takes ``warm``), their
    rows' place on the chip ``core/store._resolve_layout``'s to choose:
    ``wide`` ``(J cross_buckets, 3)`` under the configuration's FTRL rule, a
    narrow rule store; ``deep`` ``(num_rows, 2 dim)`` under
    :class:`~.dlrm_dcnv2.Adagrad`, a row its embedding and then its
    accumulators, the worker's part the embedding."""
    dtype = dtype or jnp.float32
    init = warm_rows(config, seed=seed, dtype=dtype, **warm)
    return StoreGroup.of({
        "wide": ShardedParamStore.create(
            config.wide_rows, (3,), dtype=dtype, init_fn=init["wide"],
            update=config.ftrl, mesh=mesh, layout="auto"),
        "deep": ShardedParamStore.create(
            config.num_rows, (2 * config.dim,), dtype=dtype,
            init_fn=init["deep"],
            update=Adagrad(config.learning_rate, config.eps), mesh=mesh,
            layout="auto", worker_width=config.dim),
    })


__all__ = ["WideAndDeep", "WideDeepConfig", "make_stores", "warm_rows"]
