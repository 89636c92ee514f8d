"""Knowledge-graph embeddings as PyTorch-BigGraph trains them (Lerer et al.,
"PyTorch-BigGraph: A Large-scale Graph Embedding System", SysML 2019;
``facebookresearch/PyTorch-BigGraph``): ComplEx entity rows on the
parameter server under ROW-WISE AdaGrad, ONE accumulator a row, and the
relations' operators in the worker.

Reference parity: the reference server's ``SimplePSLogic(paramInit,
paramUpdate)`` takes any ``paramUpdate: (P, P) => P`` (SURVEY.md §2 #3); here
``P`` is an entity's embedding with its optimiser state, ``dim + 1`` float32,
and ``paramUpdate`` reduces ACROSS the lanes of the pushed sum.  The relations'
operators are the worker's model-side variables, as DLRM's MLPs are
(SURVEY.md §2 #2): a dict of arrays in the worker's state.

One server row is an entity's embedding and its accumulator:

    (theta[0..dim), G)                       dim + 1 lanes

``theta`` is ``dim / 2`` complex numbers, lanes ``[0, dim / 2)`` their real
parts and ``[dim / 2, dim)`` their imaginary parts; ``a (.) x`` is their
element-wise complex product and ``<x, y>`` the real dot product of all
``dim`` lanes (ComplEx's score ``Re <a (.) s, conj o>``).  Relation ``r`` has
two operators, ``a_r`` for the destination side and ``b_r`` for the source
side (PBG's ``complex_diagonal`` under ``dynamic_relations``: a forward and a
reverse operator a relation).

A batch is ``chunks`` chunks of ``chunk`` edges ``(s_e, r_e, o_e)``; chunk
``c`` also holds ``uniform`` source-side ids ``u_c`` and as many
destination-side ids ``v_c``, drawn by whoever made the batch (PBG's trainer
draws its uniform negatives on the host too).  With the rows as they stood
before the step, ``D_c = [theta_o; theta_v]`` and ``S_c = [theta_s;
theta_u]``, each ``(chunk + uniform, dim)``:

    q_e = a_{r_e} (.) theta_{s_e}        Sd_c = Q_c D_c^T
    p_e = b_{r_e} (.) theta_{o_e}        Ss_c = P_c S_c^T
    loss = sum_e [ -log softmax(Sd_c[e, :])[e] - log softmax(Ss_c[e, :])[e] ]

An edge is scored against its chunk's OTHER endpoints and the chunk's
uniform ids, all of them, as a matrix product; the positive of edge ``e`` is
column ``e`` and no other column is masked (PBG's training masks none).  The
backward pass is written out.  A row's gradient is the SUM over the chunk's
edges that used it, made by the two transposed products: ``dD = dSd^T Q``,
``dS = dSs^T P``, and an endpoint's also ``conj(a) (.) dQ`` / ``conj(b) (.)
dP``.  The worker pushes RAW gradients, ``dim`` lanes, one a pulled row: the
worker's part of a row (``StoreSpec.worker_width``); the accumulator never
leaves the server.

The server's rule, once a distinct row a batch, on the sum ``g`` of the
batch's gradients to the row (:class:`RowAdaGrad`; PBG's
``torchbiggraph``'s ``row_adagrad`` module):

    G' = G + mean_k(g_k^2);   theta' = theta - lr g / (sqrt(G') + eps)

The accumulator is read AFTER this step's mean square is added
(``row_adagrad`` adds, then divides); ``models/glove.GloVeAdaGrad``, as
``glove.c``, reads its accumulators BEFORE the add.

The operators take element-wise AdaGrad in the worker, once a batch, on the
batch's gradients summed by relation: ``S' = S + g^2; p' = p - lr_rel g /
(sqrt(S') + eps)`` over the whole leaf; a relation no edge of the batch names
has ``g = 0`` and stays bit-equal.

PBG trains a bucket with HOGWILD threads, each on a batch of its own; a step
here is bulk-synchronous: every edge of a batch reads the rows and operators
as they stood, a row's gradients are summed and its rule runs once a batch
(the batched path's standing guarantee).

Float32 throughout; the products run at ``Precision.HIGHEST`` (the TPU's
default is one bfloat16 pass, 4e-3 of every product).
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

Array = jax.Array

_PRECISION = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class KGEConfig:
    """``num_entities`` rows; which of them are sources and which
    destinations is whoever makes the batches' to say (a bucket's two
    partitions: the logic reads ids, no range).  ``dim`` is even: ``dim /
    2`` complex numbers.  ``lr_rel`` and ``eps`` are the operators' AdaGrad's
    (the rows' rate and ``eps`` are the store's rule's, :class:`RowAdaGrad`)."""

    num_entities: int
    num_relations: int
    dim: int = 100
    lr_rel: float = 0.01
    eps: float = 1e-10

    def __post_init__(self) -> None:
        if self.dim % 2:
            raise ValueError(f"dim={self.dim}: ComplEx rows hold dim / 2 "
                             "complex numbers")

    @property
    def row_lanes(self) -> int:
        """The embedding and row-wise AdaGrad's one accumulator."""
        return self.dim + 1


@dataclasses.dataclass(frozen=True)
class RowAdaGrad:
    """The rule, with its rate as data: ``rule(current, combined)`` is a
    ``StoreSpec.update`` over rows ``(..., dim + 1)``, the embedding and then
    its ONE accumulator, ``combined`` the sum of the batch's gradients to the
    row, read over its first ``dim`` lanes (whole-row pushes carry a zero
    past them).  The accumulator grows by the MEAN of the squared gradient
    over the row's lanes and is read AFTER it has grown, as
    ``row_adagrad`` reads it."""

    lr: float = 0.1
    eps: float = 1e-10

    def __call__(self, current: Array, combined: Array) -> Array:
        current, combined = jnp.asarray(current), jnp.asarray(combined)
        dim = current.shape[-1] - 1
        theta, acc = current[..., :dim], current[..., dim:]
        g = combined[..., :dim]
        acc = acc + jnp.mean(g * g, axis=-1, keepdims=True)
        step = self.lr * g / (jnp.sqrt(acc) + self.eps)
        return jnp.concatenate([theta - step, acc], axis=-1).astype(
            current.dtype)


def complex_product(a: Array, x: Array, *, conj: bool = False) -> Array:
    """``a (.) x`` lane by lane, or ``conj(a) (.) x``: the last axis holds
    the real parts and then the imaginary parts."""
    half = a.shape[-1] // 2
    a_re, a_im = a[..., :half], a[..., half:]
    x_re, x_im = x[..., :half], x[..., half:]
    if conj:
        a_im = -a_im
    return jnp.concatenate(
        [a_re * x_re - a_im * x_im, a_re * x_im + a_im * x_re], axis=-1)


def _side(ops: Array, ends: Array, others: Array):
    """One side of the scores and their gradients, chunk by chunk: ``ops``
    ``(C, n, dim)`` the edges' operators, ``ends`` ``(C, n, dim)`` the rows
    they turn, ``others`` ``(C, m, dim)`` what each turned row is scored
    against, column ``e`` of it edge ``e``'s own other endpoint.  Returns the
    loss an edge and the gradients to ``ops``, ``ends`` and ``others``."""
    n = ends.shape[1]
    with scope("ps.kge_operator"):
        turned = complex_product(ops, ends)
    with scope("ps.kge_score"):
        scores = jnp.einsum(
            "cnd,cmd->cnm", turned, others, precision=_PRECISION)
        top = jnp.max(scores, axis=-1, keepdims=True)
        shifted = jnp.exp(scores - top)
        total = jnp.sum(shifted, axis=-1, keepdims=True)
        own = jnp.eye(n, scores.shape[-1], dtype=scores.dtype)
        loss = (jnp.log(total) + top)[..., 0] - jnp.sum(scores * own, axis=-1)
        d_scores = shifted / total - own
    with scope("ps.kge_score_grad"):
        d_turned = jnp.einsum(
            "cnm,cmd->cnd", d_scores, others, precision=_PRECISION)
        d_others = jnp.einsum(
            "cnm,cnd->cmd", d_scores, turned, precision=_PRECISION)
    with scope("ps.kge_operator"):
        d_ends = complex_product(ops, d_turned, conj=True)
        d_ops = complex_product(ends, d_turned, conj=True)
    return loss, d_ops, d_ends, d_others


class ComplExNegatives(BatchedWorkerLogic):
    """Batch: ``source``, ``relation``, ``destination`` ``(C, n)`` int, the
    edges chunk by chunk; ``source_negatives`` and ``destination_negatives``
    ``(C, u)`` int, a chunk's uniform ids of each side.  The step's keys are
    ``(C, 2 n + 2 u)``: a chunk's sources, destinations, source-side and
    destination-side uniform ids; ``pulled`` is that by ``dim``, the worker's
    part of the rows, and the pushed gradients are that wide (handed whole
    rows, by a store whose spec names no worker's part, the step answers
    with whole rows, a zero for the accumulator's lane).  The state is a
    dict of two float32 leaves ``(num_relations, 2, dim)``: ``operators``
    (``[:, 0]`` the forward ``a``, ``[:, 1]`` the reverse ``b``; the identity
    at the start: real lanes 1, imaginary 0) and ``operator_acc``, AdaGrad's
    accumulators, 0 at the start."""

    def __init__(self, config: KGEConfig):
        self.config = config

    def init_state(self, rng: Array) -> Dict[str, Array]:
        cfg = self.config
        one = jnp.concatenate([
            jnp.ones((cfg.dim // 2,), jnp.float32),
            jnp.zeros((cfg.dim // 2,), jnp.float32),
        ])
        shape = (cfg.num_relations, 2, cfg.dim)
        return {
            "operators": jnp.broadcast_to(one, shape),
            "operator_acc": jnp.zeros(shape, jnp.float32),
        }

    def keys(self, batch: Dict[str, Array]) -> Array:
        return jnp.concatenate(
            [batch[k].astype(jnp.int32) for k in (
                "source", "destination", "source_negatives",
                "destination_negatives")],
            axis=1,
        )

    def step(self, state, batch: Dict[str, Array], pulled: Array):
        cfg = self.config
        dim, n = cfg.dim, batch["source"].shape[1]
        u = batch["source_negatives"].shape[1]
        rel = batch["relation"].astype(jnp.int32)
        theta = pulled[..., :dim]
        src, dst = theta[:, :n], theta[:, n:2 * n]
        src_all = jnp.concatenate([src, theta[:, 2 * n:2 * n + u]], axis=1)
        dst_all = jnp.concatenate([dst, theta[:, 2 * n + u:]], axis=1)
        with scope("ps.kge_operator"):
            ops = jnp.take(state["operators"], rel, axis=0)  # (C, n, 2, dim)
        # the destination side: a (.) source against every destination
        loss_d, d_a, d_src, d_dst_all = _side(ops[:, :, 0], src, dst_all)
        # the source side: b (.) destination against every source
        loss_s, d_b, d_dst, d_src_all = _side(ops[:, :, 1], dst, src_all)
        with scope("ps.kge_score_grad"):
            # raw gradients at the width the rows came, one a pulled row, in
            # the keys' order; whole rows get a zero for the accumulator
            past = pulled.shape[-1] - dim
            deltas = jnp.concatenate([
                d_src + d_src_all[:, :n], d_dst + d_dst_all[:, :n],
                d_src_all[:, n:], d_dst_all[:, n:],
            ], axis=1)
            if past:
                deltas = jnp.pad(deltas, ((0, 0), (0, 0), (0, past)))
        with scope("ps.kge_operator_update"):
            flat = rel.reshape(-1)
            grad = jnp.zeros_like(state["operators"]).at[flat].add(
                jnp.stack([d_a, d_b], axis=2).reshape(-1, 2, dim))
            acc = state["operator_acc"] + grad * grad
            state = {
                "operators": state["operators"]
                - cfg.lr_rel * grad / (jnp.sqrt(acc) + cfg.eps),
                "operator_acc": acc,
            }
            by_relation = jnp.sort(flat)
            live = 1 + jnp.sum(
                by_relation[1:] != by_relation[:-1], dtype=jnp.int32)
        out = {"loss": loss_d + loss_s, "kge_relations_live": live}
        return state, PushRequest(self.keys(batch), deltas), out

    def publish_counts(self, outs, registry, total, peak) -> None:
        # distinct relations a step: the newest step's
        registry.gauge("kge_relations_live", component="train").set(
            peak(outs["kge_relations_live"]))


def fresh_rows(
    config: KGEConfig, *, seed=0, stddev: float = 1e-3, dtype=jnp.float32
) -> InitFn:
    """PBG's start: an embedding ``N(0, stddev^2)`` a lane, its accumulator
    0; a row is a function of ``seed`` and its own id alone (``seed`` may be
    traced)."""
    theta = normal_factor(seed, (config.dim,), stddev=stddev, dtype=dtype)

    def init(ids: Array) -> Array:
        rows = theta(ids)
        return jnp.concatenate(
            [rows, jnp.zeros(rows.shape[:-1] + (1,), rows.dtype)], axis=-1)

    return init


def make_store(
    config: KGEConfig, rule: RowAdaGrad = RowAdaGrad(), *, seed=0,
    init_fn: Optional[InitFn] = None, mesh=None, dtype=None,
    layout: str = "auto",
) -> ShardedParamStore:
    """``(num_entities, dim + 1)`` store whose update rule is ``rule``, its
    rows ``init_fn(ids)`` or, by default, :func:`fresh_rows` of ``seed``,
    initialised in place (``ShardedParamStore.create``).  ``seed`` may be
    traced.  The rows' place on the chip is ``core/store._resolve_layout``'s
    to choose: 101 lanes lie in one 128-lane register.  The worker's part of
    a row is its embedding, ``dim`` lanes (``StoreSpec.worker_width``): a
    step pulls and pushes those, the accumulator stays on the server."""
    dtype = dtype or jnp.float32
    return ShardedParamStore.create(
        config.num_entities, (config.row_lanes,), dtype=dtype,
        init_fn=init_fn or fresh_rows(config, seed=seed, dtype=dtype),
        update=rule, mesh=mesh, layout=layout, worker_width=config.dim,
    )


__all__ = [
    "ComplExNegatives", "KGEConfig", "RowAdaGrad", "complex_product",
    "fresh_rows", "make_store",
]
