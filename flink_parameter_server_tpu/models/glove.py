"""GloVe (Pennington, Socher, Manning, EMNLP 2014; ``stanfordnlp/GloVe``
``src/glove.c``): weighted least squares on the log of a co-occurrence
matrix, with AdaGrad's per-coordinate accumulators kept and applied ON THE
SERVER, beside every weight they belong to.

Reference parity: the reference server's ``SimplePSLogic(paramInit,
paramUpdate)`` takes any ``paramUpdate: (P, P) => P`` whatever ``P``'s width
(SURVEY.md §2 #3); this is the model whose ``P`` is an embedding with its
optimiser state, 602 float32 for the released 300-d tables.  One server row
is one ``glove.c`` row with its ``gradsq`` row beside it:

    (w[0..dim), b, gw[0..dim), gb)          2 (dim + 1) lanes

word ``i``'s vector at row ``i``, context word ``j``'s at row ``V + j``, as
``glove.c`` lays ``W``.  A record is one nonzero ``(i, j, X)`` of the
co-occurrence matrix.  The worker, with the rows as they stood before the
step (paper section 3, equations 8 and 9):

    d = w_i . w~_j + b_i + b~_j - ln X
    f = min(1, (X / x_max)^alpha)
    s = f d                                  (cost 1/2 f d^2)
    push to row i:      (s w~_j, s)
    push to row V + j:  (s w_i,  s)

The worker reads and writes the WORKER'S PART of a row, its first ``dim + 1``
lanes (``make_store`` sets ``StoreSpec.worker_width``): the accumulators, the
other half of the row, never leave the server.

and the server's rule, once a distinct row a step, ``G`` the sum of the
batch's pushes to the row (:class:`GloVeAdaGrad`; ``glove.c`` multiplies the
gradient by ``eta`` before it squares it):

    u = eta G;   param' = param - u / sqrt(g);   g' = g + u^2

``glove.c`` applies a record at a time (Hogwild); a step here is
bulk-synchronous, every record of a batch reads the rows as they stood, a
row's pushes are summed and the rule runs once a row a batch: the batched
path's standing guarantee.  A coordinate moves by at most ``eta`` a step
whatever the sum (``|u| / sqrt(g + ...)`` with ``g >= 1`` reads ``g`` before
the step, so by ``|u|``; bounded in practice by the accumulator's growth).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ..core.batched import BatchedWorkerLogic, PushRequest
from ..core.store import InitFn, ShardedParamStore
from ..training.tracing import scope
from ..utils.initializers import ranged_random_factor

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class GloVeConfig:
    vocab_size: int
    dim: int
    x_max: float = 100.0
    alpha: float = 0.75

    @property
    def num_rows(self) -> int:
        """Word vectors, then context vectors."""
        return 2 * self.vocab_size

    @property
    def params(self) -> int:
        """Lanes of a row's parameters: the vector and its bias."""
        return self.dim + 1

    @property
    def row_lanes(self) -> int:
        return 2 * self.params


@dataclasses.dataclass(frozen=True)
class GloVeAdaGrad:
    """The rule, with its rate as data: ``rule(current, combined)`` is a
    ``StoreSpec.update`` (rows ``(..., 2 p)``, vectorised over the leading
    axes): the first ``p`` lanes the parameters, the last ``p`` their
    accumulated squared gradients, read BEFORE this step's square is
    added, as ``glove.c`` reads ``gradsq``.  ``combined`` is read over its
    first ``p`` lanes: the sums of whole-row pushes, or of the worker's
    part, ``(..., p)``."""

    eta: float = 0.05

    def __call__(self, current: Array, combined: Array) -> Array:
        current, combined = jnp.asarray(current), jnp.asarray(combined)
        p = current.shape[-1] // 2
        param, g = current[..., :p], current[..., p:]
        u = self.eta * combined[..., :p]
        return jnp.concatenate(
            [param - u / jnp.sqrt(g), g + u * u], axis=-1
        ).astype(current.dtype)


def natural_log(x: Array) -> Array:
    """``ln x`` at the accuracy float32 has.  The TPU's default elementwise
    ``log`` is good to 1.1e-4 ABSOLUTE on the v5e (2.6e-4 of ``ln 12``; its
    ``pow``, ``exp``, ``sqrt`` and divide are good to 1e-7 - 4e-6): ``diff``
    is ``ln X`` and little else while a model is young, so every gradient of
    a step carried that error, 6.5 times what the benchmark's check allows a
    row; asked for its highest accuracy the same op is good to 1.8e-7 of the
    value (PERF.md section 6, PR 55)."""
    return jax.lax.log(
        x.astype(jnp.float32), accuracy=jax.lax.AccuracyMode.HIGHEST)


class GloVe(BatchedWorkerLogic):
    """Batch keys: ``word`` (B,) and ``context`` (B,) int word ids, ``count``
    (B,) float ``X > 0``, ``mask`` (B,) bool.  The step's keys are ``(B,
    2)``: row ``word`` and row ``vocab_size + context``; ``pulled`` is
    ``(B, 2, dim + 1)``, the worker's part of the rows, and the pushed
    gradients are that wide (handed whole rows, by a store whose spec names
    no worker's part, the step answers with whole rows, zeros for the
    accumulators' lanes).  The worker is stateless (vectors, biases and
    accumulators live on the server)."""

    def __init__(self, config: GloVeConfig):
        self.config = config

    def init_state(self, rng: Array):
        return ()

    def keys(self, batch: Dict[str, Array]) -> Array:
        return jnp.stack(
            [batch["word"].astype(jnp.int32),
             batch["context"].astype(jnp.int32) + self.config.vocab_size],
            axis=1,
        )

    def step(self, state, batch: Dict[str, Array], pulled: Array):
        cfg = self.config
        dim = cfg.dim
        x = batch["count"].astype(jnp.float32)
        vec, bias = pulled[..., :dim], pulled[..., dim]
        diff = (
            jnp.sum(vec[:, 0] * vec[:, 1], axis=-1) + bias[:, 0] + bias[:, 1]
            - natural_log(x)
        )
        weight = jnp.minimum(1.0, (x / cfg.x_max) ** cfg.alpha)
        s = weight * diff
        with scope("ps.cooc_grad_rows"):
            # raw gradients at the width the rows came: each side takes the
            # OTHER side's vector times s and s for its bias, the worker's
            # part; whole rows get zeros for the accumulators' lanes
            other = vec[:, ::-1]
            past = pulled.shape[-1] - cfg.params
            deltas = jnp.concatenate(
                [
                    s[:, None, None] * other,
                    jnp.broadcast_to(s[:, None, None], other.shape[:2] + (1,)),
                ] + [jnp.zeros(other.shape[:2] + (past,), other.dtype)]
                * (past > 0),
                axis=-1,
            )
        mask = batch["mask"]
        out = {
            "cost": 0.5 * weight * diff * diff * mask,
            "cooc_live_records": jnp.sum(mask, dtype=jnp.int32),
        }
        live = jnp.broadcast_to(mask[:, None], mask.shape + (2,))
        return state, PushRequest(self.keys(batch), deltas, live), out


def fresh_rows(
    config: GloVeConfig, *, seed=0, dtype=jnp.float32
) -> InitFn:
    """``glove.c``'s start: every parameter, biases too, ``U(-0.5, 0.5) /
    dim``, every accumulator 1; a row is a function of ``seed`` and its own
    id alone (``seed`` may be traced)."""
    params = ranged_random_factor(
        seed, (config.params,), low=-0.5 / config.dim, high=0.5 / config.dim,
        dtype=dtype,
    )

    def init(ids: Array) -> Array:
        rows = params(ids)
        return jnp.concatenate([rows, jnp.ones_like(rows)], axis=-1)

    return init


def make_store(
    config: GloVeConfig, rule: GloVeAdaGrad = GloVeAdaGrad(), *, seed=0,
    init_fn: Optional[InitFn] = None, mesh=None, dtype=None,
    layout: str = "auto",
) -> ShardedParamStore:
    """``(2 vocab_size, 2 (dim + 1))`` store whose update rule is ``rule``,
    its rows ``init_fn(ids)`` or, by default, :func:`fresh_rows` of
    ``seed``, initialised in place (``ShardedParamStore.create``).  ``seed``
    may be traced (``jax.jit(lambda seed: make_store(..., seed=seed))``: one
    program whatever the seed).  The rows' place on the chip is
    ``core/store._resolve_layout``'s to choose: flat in whole registers
    where a row is wider than one.  The worker's part of a row is its
    vector and bias, ``dim + 1`` lanes (``StoreSpec.worker_width``): a step
    pulls and pushes those, the accumulators stay on the server."""
    dtype = dtype or jnp.float32
    return ShardedParamStore.create(
        config.num_rows, (config.row_lanes,), dtype=dtype,
        init_fn=init_fn or fresh_rows(config, seed=seed, dtype=dtype),
        update=rule, mesh=mesh, layout=layout, worker_width=config.params,
    )


__all__ = [
    "GloVe", "GloVeAdaGrad", "GloVeConfig", "fresh_rows", "make_store",
    "natural_log",
]
