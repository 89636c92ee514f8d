"""DLRM-DCNv2: multi-hot embedding bags on the parameter server under
element-wise Adagrad, a low-rank cross network between two MLPs in the worker
(MLPerf Training's recommendation benchmark since v3.0, ``mlcommons/training``
``recommendation_v2/torchrec_dlrm``; Wang et al., "DCN V2", WWW 2021;
torchrec's ``LowRankCrossNet``).

Reference parity: the reference server's ``SimplePSLogic(paramInit,
paramUpdate)`` takes any ``paramUpdate: (P, P) => P`` (SURVEY.md §2 #3); here
``P`` is an embedding row WITH its optimiser state, ``(w[dim], G[dim])``, and
``paramUpdate`` is ``torch.optim.Adagrad``'s step (:class:`Adagrad`).  The
dense net and ITS Adagrad accumulators are the worker's model-side variables
(SURVEY.md §2 #2), a dict of arrays in the worker's state, as
``models/dlrm.py``'s MLPs are.  Per example, float32 throughout:

    z0 = MLP_bot(x)                    x the dense fields; ReLU after every layer
    e_f = sum over the bag of E[id]    field f's bag has ``bag_sizes[f]`` ids,
                                       every field its own rows of ONE store
    x0 = [z0; e_1; ...; e_F]           (F + 1) dim lanes
    x_{l+1} = x0 * (W_l (V_l x_l) + b_l) + x_l     l = 0 .. cross_layers - 1,
                                       V_l rank x width, W_l width x rank, ``*``
                                       element-wise (``LowRankCrossNet``)
    p = sigmoid(MLP_over(x_L))         ReLU between layers
    loss = mean over the live examples of BCE(p, y)

One ``step`` is the forward pass, the backward pass written out, Adagrad on
every dense leaf (once a minibatch, on the whole batch's gradient) and a push
of the RAW gradient ``dL/de_f`` for every row of field ``f``'s bag, ``dim``
lanes, the worker's part of a row (``StoreSpec.worker_width``): the rule is
the server's and the accumulators never leave it.  A row that several lanes
name takes ONE rule step on the sum of their gradients (the batched path's
standing guarantee).  Under a ``dp`` mesh the dense net lies replicated and
the batch split; the reduction of the dense gradients is the partitioner's.

The matmuls run at ``Precision.HIGHEST``: the model's arithmetic is float32,
and the TPU's default (one bfloat16 pass) is 4e-3 of every product.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.batched import BatchedWorkerLogic, PushRequest
from ..core.store import InitFn, ShardedParamStore
from ..training.tracing import scope
from ..utils.initializers import ranged_random_factor
from .dlrm import _mlp_backward, _mlp_forward

Array = jax.Array

_PRECISION = jax.lax.Precision.HIGHEST  # the cross network's products


@dataclasses.dataclass(frozen=True)
class DCNv2Config:
    """The source's flags: ``field_rows`` the rows HELD of every categorical
    field (its ``num_embeddings_per_feature``, or a server's share of them),
    ``field_sizes`` the published count of each (the init's bound reads it;
    by default the rows held), ``bag_sizes`` its ``multi_hot_sizes``, ``dim``
    its ``embedding_dim``, ``bottom_mlp`` / ``over_mlp`` the layer widths
    after the input's (``dense_arch_layer_sizes``, which must end at ``dim``,
    and ``over_arch_layer_sizes``, which must end at 1), ``cross_layers`` and
    ``cross_rank`` its ``dcn_num_layers`` and ``dcn_low_rank_dim``.
    ``learning_rate`` and ``eps`` are the DENSE leaves' Adagrad's (the rows'
    are the store's rule's, :class:`Adagrad`)."""

    field_rows: Tuple[int, ...]
    bag_sizes: Tuple[int, ...]
    field_sizes: Optional[Tuple[int, ...]] = None
    dense_features: int = 13
    dim: int = 128
    bottom_mlp: Tuple[int, ...] = (512, 256, 128)
    cross_layers: int = 3
    cross_rank: int = 512
    over_mlp: Tuple[int, ...] = (1024, 1024, 512, 256, 1)
    learning_rate: float = 0.004
    eps: float = 1e-8

    def __post_init__(self) -> None:
        if self.bottom_mlp[-1] != self.dim or self.over_mlp[-1] != 1:
            raise ValueError(
                f"bottom MLP {self.bottom_mlp} must end at dim={self.dim} "
                f"and over arch {self.over_mlp} at 1"
            )
        sizes = self.field_sizes or self.field_rows
        if not len(self.field_rows) == len(self.bag_sizes) == len(sizes):
            raise ValueError(
                f"{len(self.field_rows)} fields, {len(self.bag_sizes)} bag "
                f"sizes, {len(sizes)} published counts"
            )

    @property
    def num_rows(self) -> int:
        return int(sum(self.field_rows))

    @property
    def fields(self) -> int:
        return len(self.field_rows)

    @property
    def lookups(self) -> int:
        """Ids an example: the bags' sizes in all."""
        return int(sum(self.bag_sizes))

    @property
    def width(self) -> int:
        """Lanes of ``x0``: the bottom MLP's output and a pooled vector a
        field."""
        return (self.fields + 1) * self.dim

    def layers(self) -> Dict[str, Tuple[int, int]]:
        """``{leaf prefix: (inputs, outputs)}`` of every matrix of the dense
        net, in order; a cross layer is ``cross{l}v`` (``V^t``: width x
        rank) and ``cross{l}w`` (``W^t``: rank x width, with the bias)."""
        out = {}
        for i, (n, m) in enumerate(
                zip((self.dense_features,) + self.bottom_mlp, self.bottom_mlp)):
            out[f"bot{i}"] = (int(n), int(m))
        for l in range(self.cross_layers):
            out[f"cross{l}v"] = (self.width, self.cross_rank)
            out[f"cross{l}w"] = (self.cross_rank, self.width)
        for i, (n, m) in enumerate(
                zip((self.width,) + self.over_mlp, self.over_mlp)):
            out[f"over{i}"] = (int(n), int(m))
        return out

    @property
    def dense_params(self) -> int:
        """Every weight and bias of the dense net (``V`` has no bias)."""
        return sum(
            n * m + (0 if name.endswith("v") else m)
            for name, (n, m) in self.layers().items()
        )

    @property
    def macs_per_example(self) -> int:
        """Multiply-adds of one example's FORWARD pass: every matrix."""
        return sum(n * m for n, m in self.layers().values())


@dataclasses.dataclass(frozen=True)
class Adagrad:
    """The rule, with its rate as data: ``rule(current, combined)`` is a
    ``StoreSpec.update`` over rows ``(..., 2 p)``, the first ``p`` lanes the
    weights, the last ``p`` their accumulated squared gradients; ``combined``
    is the sum of the batch's gradients to the row, read over its first
    ``p`` lanes.  ``torch.optim.Adagrad``'s step, ELEMENT-wise, in torch's
    order (``state_sum.addcmul_(g, g); std = state_sum.sqrt().add_(eps);
    param.addcdiv_(g, std, value=-lr)``):

        G' = G + g * g;    w' = w - lr * g / (sqrt(G') + eps)

    the accumulator read AFTER this step's square is added.
    ``models/glove.GloVeAdaGrad`` is element-wise too but, as ``glove.c``,
    reads its accumulators BEFORE the add and has no ``eps``;
    ``models/kge.RowAdaGrad`` reads after the add, as here, but keeps ONE
    accumulator a row, fed by the mean of the squares over the row's
    lanes."""

    lr: float = 0.004
    eps: float = 1e-8

    def __call__(self, current: Array, combined: Array) -> Array:
        current, combined = jnp.asarray(current), jnp.asarray(combined)
        p = current.shape[-1] // 2
        w, acc = current[..., :p], current[..., p:]
        g = combined[..., :p]
        acc = acc + g * g
        return jnp.concatenate(
            [w - self.lr * g / (jnp.sqrt(acc) + self.eps), acc], axis=-1
        ).astype(current.dtype)


def _dot(a: Array, b: Array) -> Array:
    return jnp.dot(a, b, precision=_PRECISION)


def _cross_forward(state, x0: Array, layers: int):
    """``[x_0 .. x_L]`` and each layer's ``(u_l, v_l)``: ``u_l = V_l x_l``,
    ``v_l = W_l u_l + b_l``, ``x_{l+1} = x0 * v_l + x_l``."""
    xs, mids = [x0], []
    for l in range(layers):
        u = _dot(xs[-1], state[f"cross{l}v_w"])
        v = _dot(u, state[f"cross{l}w_w"]) + state[f"cross{l}w_b"]
        mids.append((u, v))
        xs.append(x0 * v + xs[-1])
    return xs, mids


def _cross_backward(state, xs, mids, d: Array):
    """``d`` is dL/dx_L; returns ``(gradients by leaf, dL/dx0)``: through the
    product ``x0 * v_l``, the residual and the two matrices of every layer."""
    grads, x0 = {}, xs[0]
    d_x0 = jnp.zeros_like(x0)
    for l in reversed(range(len(mids))):
        u, v = mids[l]
        d_v = d * x0
        d_x0 = d_x0 + d * v
        grads[f"cross{l}w_w"] = _dot(u.T, d_v)
        grads[f"cross{l}w_b"] = d_v.sum(axis=0)
        d_u = _dot(d_v, state[f"cross{l}w_w"].T)
        grads[f"cross{l}v_w"] = _dot(xs[l].T, d_u)
        d = d + _dot(d_u, state[f"cross{l}v_w"].T)
    return grads, d_x0 + d


class DLRMDCNv2(BatchedWorkerLogic):
    """Batch: ``dense`` (B, dense_features) float, ``ids`` (B, lookups) int,
    field after field the ids of each field's bag, every id the row in the
    ONE store (its field's first row added), ``label`` (B,) positive for a
    click, ``mask`` (B,) bool.  ``pulled`` is ``(B, lookups, dim)``, the
    worker's part of the rows, and the pushed gradients are that wide.  The
    state is a dict of float32 arrays: ``{bot|over}{i}_{w|b}``,
    ``cross{l}v_w``, ``cross{l}w_{w|b}`` (a matrix as ``(inputs, outputs)``)
    and, for each leaf ``k``, ``k_acc``, Adagrad's accumulator, 0 at the
    start.  The outputs are ``prediction`` and ``loss``."""

    def __init__(self, config: DCNv2Config, *, seed=0):
        self.config = config
        self.seed = seed

    def init_state(self, rng: Array) -> Dict[str, Array]:
        """torchrec's init: an MLP layer ``nn.Linear``'s default, ``W`` and
        ``b`` ``U(-1 / sqrt(n), 1 / sqrt(n))`` for ``n`` inputs; a cross
        layer's ``V`` and ``W`` Xavier normal, ``N(0, 2 / (n + m))``, its
        bias 0; every accumulator 0.  From ``rng`` and the logic's seed."""
        key = jax.random.fold_in(rng, self.seed)
        state = {}
        for i, (name, (n, m)) in enumerate(self.config.layers().items()):
            kw, kb = jax.random.split(jax.random.fold_in(key, i))
            if name.startswith("cross"):
                state[f"{name}_w"] = np.sqrt(2.0 / (n + m)) * jax.random.normal(
                    kw, (n, m), jnp.float32)
                if name.endswith("w"):
                    state[f"{name}_b"] = jnp.zeros((m,), jnp.float32)
                continue
            bound = 1.0 / np.sqrt(n)
            state[f"{name}_w"] = jax.random.uniform(
                kw, (n, m), jnp.float32, -bound, bound)
            state[f"{name}_b"] = jax.random.uniform(
                kb, (m,), jnp.float32, -bound, bound)
        state.update({f"{k}_acc": jnp.zeros_like(v) for k, v in state.items()})
        return state

    def keys(self, batch: Dict[str, Array]) -> Array:
        return batch["ids"]

    def step(self, state, batch: Dict[str, Array], pulled: Array):
        cfg = self.config
        dim, live = cfg.dim, batch["mask"]
        x = batch["dense"].astype(jnp.float32)
        n_bot, n_over = len(cfg.bottom_mlp), len(cfg.over_mlp)
        ends = np.cumsum(cfg.bag_sizes)
        # the field of every lane of an example's key block
        field_of = np.repeat(np.arange(cfg.fields), cfg.bag_sizes)

        with scope("ps.bag_pool"):
            # a static segment sum: a field's bag is a fixed run of lanes
            pooled = jnp.stack([
                pulled[:, end - size:end].sum(axis=1)
                for end, size in zip(ends, cfg.bag_sizes)
            ], axis=1)
        with scope("ps.dense_bottom"):
            bot = _mlp_forward(state, "bot", x, n_bot, True)
        with scope("ps.dense_interact"):
            x0 = jnp.concatenate(
                [bot[-1], pooled.reshape(x.shape[0], -1)], axis=1)
            xs, mids = _cross_forward(state, x0, cfg.cross_layers)
        with scope("ps.dense_top"):
            over = _mlp_forward(state, "over", xs[-1], n_over, False)
            logit = over[-1][:, 0]
            # p - y for y in {0, 1}, written -s / (1 + exp(s logit)), s the
            # label's sign: the same number without the subtraction
            # (models/logistic_ftrl.example_deltas)
            sign = jnp.where(batch["label"] > 0, 1.0, -1.0)
            examples = jnp.maximum(jnp.sum(live, dtype=jnp.float32), 1.0)
            d_logit = jnp.where(
                live, -sign / (1.0 + jnp.exp(sign * logit)), 0.0
            ) / examples
            grads, d_x = _mlp_backward(
                state, "over", over, d_logit[:, None], False)
        with scope("ps.dense_interact"):
            cross_grads, d_x0 = _cross_backward(state, xs, mids, d_x)
            grads.update(cross_grads)
        with scope("ps.dense_bottom"):
            bot_grads, _ = _mlp_backward(
                state, "bot", bot, d_x0[:, :dim], True)
            grads.update(bot_grads)
        with scope("ps.bag_grad_spread"):
            # a pooled vector's gradient is that of every row of its bag:
            # raw gradients at the width the rows came, one a pulled row
            d_pooled = d_x0[:, dim:].reshape(x.shape[0], cfg.fields, dim)
            deltas = jnp.take(d_pooled, field_of, axis=1)
        with scope("ps.dense_adagrad"):
            new = {}
            for k, g in grads.items():
                acc = state[f"{k}_acc"] + g * g
                new[k] = state[k] - cfg.learning_rate * g / (
                    jnp.sqrt(acc) + cfg.eps)
                new[f"{k}_acc"] = acc
        out = {
            "prediction": jax.nn.sigmoid(logit),
            "loss": jax.nn.softplus(-sign * logit) * live,
        }
        mask = jnp.broadcast_to(live[:, None], batch["ids"].shape)
        return new, PushRequest(batch["ids"], deltas, mask), out


def fresh_rows(config: DCNv2Config, *, seed=0, dtype=jnp.float32) -> InitFn:
    """The source's start: a field's weights ``U(-sqrt(1 / n), sqrt(1 / n))``,
    ``n`` the field's PUBLISHED row count (``field_sizes``: a server that
    holds a share of a table starts its rows as the whole table's), the
    accumulators 0; every row from the seed and its own id alone.  ``seed``
    may be traced."""
    unit = ranged_random_factor(
        seed, (config.dim,), low=-1.0, high=1.0, dtype=dtype)
    firsts = np.concatenate([[0], np.cumsum(config.field_rows)[:-1]])
    sizes = config.field_sizes or config.field_rows

    def init(ids: Array) -> Array:
        bound = jnp.ones(ids.shape, dtype)
        for first, size in zip(firsts, sizes):
            bound = jnp.where(ids >= int(first), np.sqrt(1.0 / size), bound)
        rows = bound[:, None] * unit(ids)
        return jnp.concatenate([rows, jnp.zeros_like(rows)], axis=-1)

    return init


def make_store(
    config: DCNv2Config, *, seed=0, mesh=None, dtype=None,
) -> ShardedParamStore:
    """``(num_rows, 2 dim)`` store of every field's rows, field after field,
    a row its weights and then their accumulators, whose update rule is
    :class:`Adagrad` at the configuration's rate and ``eps``, its rows
    :func:`fresh_rows` of ``seed``, initialised in place
    (``ShardedParamStore.create``).  The rows' place on
    the chip is ``core/store._resolve_layout``'s to choose: 256 lanes lie
    flat in two whole 128-lane registers.  The worker's part of a row is its
    weights, ``dim`` lanes (``StoreSpec.worker_width``): a step pulls and
    pushes those, the accumulators stay on the server."""
    dtype = dtype or jnp.float32
    return ShardedParamStore.create(
        config.num_rows, (2 * config.dim,), dtype=dtype,
        init_fn=fresh_rows(config, seed=seed, dtype=dtype),
        update=Adagrad(config.learning_rate, config.eps), mesh=mesh,
        layout="auto", worker_width=config.dim,
    )


__all__ = ["Adagrad", "DCNv2Config", "DLRMDCNv2", "fresh_rows", "make_store"]
