"""DLRM: embedding tables on the parameter server, a dense net in the worker
(Naumov et al., "Deep Learning Recommendation Model for Personalization and
Recommendation Systems", 2019; ``facebookresearch/dlrm``, its PyTorch script).

Reference parity: the job parameter servers are run for today.  The server
holds the categorical fields' embedding rows (the reference's
``SimplePSLogic`` with ``paramUpdate = +``, SURVEY.md §2 #3); the worker holds
the two MLPs in its STATE, as the reference's worker keeps its model-side
variables in the ``WorkerLogic`` instance (SURVEY.md §2 #2), and as MF's user
factors are an array there: here the state is a dict of arrays.  Per example,
float32 throughout:

    z0 = MLP_bot(x)                       x the dense fields; ReLU after every layer
    e_f = E[id_f]                         one row a categorical field, every field
                                          its own rows of ONE store
    T = [z0; e_1; ...; e_F]               (F + 1, dim)
    Z = T T^t                             the entries i > j, no diagonal
    r = [z0, Z_lower]
    p = sigmoid(MLP_top(r))               ReLU between layers
    loss = mean over the live examples of BCE(p, y)

One ``step`` is the forward pass, the backward pass written out, plain SGD on
every MLP leaf (once a minibatch, on the whole batch's gradient) and a push
of ``-lr dL/de`` for every pulled row; the store's ``add`` sums the deltas of
a row that several examples name, in stream order.  Under a ``dp`` mesh the
MLPs lie replicated and the batch split (``core/transform.make_train_step``
constrains it); the reduction of the dense gradients over the workers is the
partitioner's, nothing here names it.  Under a mesh of ONE worker group and
``ps`` > 1 servers (the embedding tables over the servers, the source's
model-parallel half) the minibatch's compute lies split over the servers'
own axis, the data-parallel half: the logic declares ``example_blocks``, and
``make_train_step`` constrains ``pulled`` and the deltas to lie split over
``ps`` on the examples' axis, so every chip runs this step's dense ops on its
share of the examples.  Every ``a^t d`` and bias sum is taken over
``example_blocks`` (4) equal blocks of the minibatch, each block's alone, and
the blocks' sums are added in the order of the batch
(``core/batched.sums_by_blocks``): in one place the blocks are slices, on the
chips each holds whole blocks, their sums are gathered and every chip adds
them itself.  So the SGD step is taken on the whole minibatch's gradient on
every chip, and the MLPs do not depend on the number of servers: the
``ps`` = 4 step is the one-place step bit for bit.  ``examples``, the loss's
normaliser, is the count of the WHOLE minibatch's live examples: it is taken
of the mask, which no constraint splits.  Cell 16 computed the whole dense
net on each of its four chips until then (24.1 ms of a 63.5 ms step: PERF.md
section 6, PR 68).

The order of the step's lanes is ``FieldLanes``' (the mixin of the logics
whose batch is examples of fields, ``models/factorization_machine``): the
logic is example-major for whoever calls ``step``, ``pulled`` ``(B, F,
dim)``; the copy a step in one place traces (``for_workers(1)``) takes its
rows TURNED, ``(F, B, dim)``, and pushes ids, mask and deltas ``(F, B[,
dim])``, the batch the minor axis from the pull's lane kernel to the push's.
On a TPU the two lane kernels of a packed store then hand over and take XLA's
own layout of those blocks (``f32[dim, F, B]``; ``core/store.arms``'
``fields``), and nothing stands between them and the interaction's ``T``
and ``dT`` but the ONE transposing copy each way that XLA's batched products
ask for: the axis swap behind the pull is a bitcast, the one in front of the
push that copy.  Example-major, the flat kernels' ``f32[dim, B F]`` reached ``(B,
F, dim)`` and left it through a flatten and a copy each way, four passes
that only turned rows round (4.97 ms of cell 10's 50.8: PERF.md section 6,
PR 65).  The interaction itself is written ONCE, on ``(B, F + 1, dim)``:
which axis of ``T`` and ``Z`` is minor on the chip is XLA's to assign, and
it assigns the same whichever way the products are written.  Every field
owns its rows, so a row's deltas are summed in the order of the examples
either way.

The triangle of ``Z`` is taken by a static gather and PUT BACK by a product
with a constant 0/1 matrix: ``dZ + dZ^t = d_pairs @ both``
(:func:`pair_tables`; 0.68 ms a step at cell 10's size).  A scatter
of the 351 pairs into a zeroed ``dZ`` compiles on the TPU to a loop of 351
column updates (2.28 ms a step); a static GATHER of them (``d_pairs[:,
sym]``), the same bits, compiles and HANGS the v5e in one program with
``ops/row_update``'s tile kernel (PERF.md section 6, PR 65: every form ran
on the chip).  A non-finite pair gradient spreads over its
example's ``dZ`` (0 x inf), where the scatter kept it to two entries; such a
step has lost its MLPs already.

The matmuls run at ``Precision.HIGHEST``: the model's arithmetic is float32,
and the TPU's default (one bfloat16 pass) is 4e-3 of every product.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.batched import BatchedWorkerLogic, PushRequest, sums_by_blocks
from ..core.store import InitFn, ShardedParamStore
from ..training.tracing import scope
from ..utils.initializers import ranged_random_factor
from .factorization_machine import FieldLanes

Array = jax.Array

_PRECISION = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class DLRMConfig:
    """The source's ``--arch-*`` flags: ``field_cardinalities`` its
    ``--arch-embedding-size``, ``dim`` its ``--arch-sparse-feature-size``,
    ``bottom_mlp`` / ``top_mlp`` the layer widths after the input's (the
    bottom MLP must end at ``dim``, the top at 1)."""

    field_cardinalities: Tuple[int, ...]
    dense_features: int = 13
    dim: int = 64
    bottom_mlp: Tuple[int, ...] = (512, 256, 64)
    top_mlp: Tuple[int, ...] = (512, 512, 256, 1)
    learning_rate: float = 0.1

    def __post_init__(self) -> None:
        if self.bottom_mlp[-1] != self.dim or self.top_mlp[-1] != 1:
            raise ValueError(
                f"bottom MLP {self.bottom_mlp} must end at dim={self.dim} "
                f"and top MLP {self.top_mlp} at 1"
            )

    @property
    def num_rows(self) -> int:
        return int(sum(self.field_cardinalities))

    @property
    def fields(self) -> int:
        return len(self.field_cardinalities)

    @property
    def interaction_terms(self) -> int:
        """Entries of ``T T^t`` below its diagonal."""
        return (self.fields + 1) * self.fields // 2

    def layers(self) -> Dict[str, Tuple[int, int]]:
        """``{leaf prefix: (inputs, outputs)}`` of every layer, in order."""
        out = {}
        for name, first, widths in (
            ("bot", self.dense_features, self.bottom_mlp),
            ("top", self.dim + self.interaction_terms, self.top_mlp),
        ):
            for i, (n, m) in enumerate(zip((first,) + widths, widths)):
                out[f"{name}{i}"] = (int(n), int(m))
        return out

    @property
    def dense_params(self) -> int:
        return sum(n * m + m for n, m in self.layers().values())

    @property
    def macs_per_example(self) -> int:
        """Multiply-adds of one example's FORWARD pass: the layers and the
        whole ``T T^t`` (806,720 at the source's Criteo Terabyte sizes)."""
        pairs = (self.fields + 1) ** 2 * self.dim
        return sum(n * m for n, m in self.layers().values()) + pairs


def _dot(a: Array, b: Array) -> Array:
    return jnp.dot(a, b, precision=_PRECISION)


def _mlp_forward(state, name: str, x: Array, depth: int, last_relu: bool):
    """Activations ``[x, a_0, ...]`` of ``depth`` layers ``name0..``: ReLU
    after every layer, after the last only where ``last_relu``."""
    acts = [x]
    for i in range(depth):
        z = _dot(acts[-1], state[f"{name}{i}_w"]) + state[f"{name}{i}_b"]
        acts.append(jnp.maximum(z, 0.0) if last_relu or i < depth - 1 else z)
    return acts


def _over_examples(a: Array, d: Array):
    """``(a^t d, d's column sums)``: a layer's two sums over the examples."""
    return _dot(a.T, d), d.sum(axis=0)


def _over_examples_to_one_output(a: Array, d: Array):
    """:func:`_over_examples` of a layer with ONE output, ``a^t d`` written
    as the weighted sum of ``a``'s rows that it is: a backend's
    matrix-vector forms differ between a block that is a slice and a block
    that is a chip's own (the CPU's by bits), a sum's do not."""
    return (a * d).sum(axis=0)[:, None], d.sum(axis=0)


def _mlp_backward(
    state, name: str, acts, d: Array, last_relu: bool, blocks: int = 1,
):
    """``d`` is dL/d(output) of the MLP; returns ``(gradients by leaf,
    dL/d(input))``.  A ReLU's output is positive exactly where its input
    was, so the mask is read from the activation kept.  ``blocks``: a
    layer's sums over the examples are ``core/batched.sums_by_blocks``'."""
    grads, depth = {}, len(acts) - 1
    for i in reversed(range(depth)):
        if last_relu or i < depth - 1:
            d = jnp.where(acts[i + 1] > 0, d, 0.0)
        sums = _over_examples
        if blocks > 1 and d.shape[-1] == 1:
            sums = _over_examples_to_one_output
        grads[f"{name}{i}_w"], grads[f"{name}{i}_b"] = sums_by_blocks(
            sums, blocks, acts[i], d)
        d = _dot(d, state[f"{name}{i}_w"].T)
    return grads, d


@functools.partial(jax.jit, static_argnames=("layers",))
def _init_layers(rng: Array, seed, *, layers) -> Dict[str, Array]:
    """:meth:`DLRM.init_state`'s leaves, the keys and the ops the eager
    form had, layer by layer, and its BITS: the barrier keeps XLA from
    folding a leaf's scale into ``normal``'s own last multiply, one
    rounding where the eager form had two (up to 2 ulps of 26-65 % of the
    elements on the CPU).  ``seed`` is an argument: baked in, every seed
    would compile its own program."""
    key = jax.random.fold_in(rng, seed)
    unit, scale = {}, {}
    for i, (name, (n, m)) in enumerate(layers):
        kw, kb = jax.random.split(jax.random.fold_in(key, i))
        unit[f"{name}_w"] = jax.random.normal(kw, (n, m), jnp.float32)
        unit[f"{name}_b"] = jax.random.normal(kb, (m,), jnp.float32)
        scale[f"{name}_w"] = np.sqrt(2.0 / (m + n))
        scale[f"{name}_b"] = np.sqrt(1.0 / m)
    unit = jax.lax.optimization_barrier(unit)
    return {leaf: scale[leaf] * x for leaf, x in unit.items()}


def pair_tables(vectors: int):
    """``(lower_i, lower_j, both)`` for the pairs of ``vectors`` vectors: the
    entries of ``T T^t`` below its diagonal, row by row (the source's ``li``,
    ``lj``), pair ``p`` at ``(lower_i[p], lower_j[p])``; and ``both``
    ``(pairs, vectors * vectors)`` float32, row ``p`` a one at ``(i, j)`` AND
    at ``(j, i)`` of the flattened square and zeros elsewhere, the diagonal's
    columns all zeros: ``d_pairs @ both`` is ``dZ + dZ^t`` of the ``dZ`` that
    holds ``d_pairs`` below its diagonal and zeros elsewhere, every element
    ONE product by one (exact at ``Precision.HIGHEST``: the three bfloat16
    pieces of a float32 add up to it) beside products by zero."""
    lower_i, lower_j = np.tril_indices(vectors, -1)
    pair = np.arange(lower_i.size)
    both = np.zeros((lower_i.size, vectors, vectors), np.float32)
    both[pair, lower_i, lower_j] = both[pair, lower_j, lower_i] = 1.0
    return lower_i, lower_j, both.reshape(lower_i.size, -1)


class DLRM(FieldLanes, BatchedWorkerLogic):
    """Batch: ``dense`` (B, dense_features) float, ``ids`` (B, fields) int,
    the row of each categorical field in the ONE store (its field's first row
    added), ``label`` (B,) positive for a click, ``mask`` (B,) bool.
    ``pulled``, the push's ids, deltas and mask are as :class:`FieldLanes`
    has them: ``(B, fields[, dim])``, and ``(fields, B[, dim])`` in the copy
    a step in one place traces.  The state is a dict of float32
    arrays, ``{bot|top}{layer}_{w|b}``, ``w`` as ``(inputs, outputs)``.
    Beside ``prediction`` and ``loss`` the outputs carry two constants of
    the logic for whoever reads outputs: ``dlrm_dense_params`` and
    ``dlrm_dense_flops_per_step`` (model FLOPs: 2 a multiply-add, the
    backward pass twice the forward)."""

    # every op of `step` is a function of one example and of the MLPs, which
    # only sums over the examples change, and those are taken over this many
    # equal blocks of the minibatch and added in the blocks' order
    # (`sums_by_blocks`; `BatchedWorkerLogic.for_workers` says who reads it)
    example_blocks = 4

    def __init__(self, config: DLRMConfig, *, seed=0):
        self.config = config
        self.seed = seed

    def init_state(self, rng: Array) -> Dict[str, Array]:
        """The source's init: ``W ~ N(0, sqrt(2 / (m + n)))``, ``b ~ N(0,
        sqrt(1 / m))`` for ``m`` outputs and ``n`` inputs, from ``rng`` and
        the logic's seed.  ONE program for all the layers
        (:func:`_init_layers`): op by op it was a ``fold_in``, a ``split``,
        two ``normal``s and two multiplies a layer, every shape a program of
        its own that each process traced, lowered and loaded, ~28 of them
        (`setup.compiles` 35 -> 7 in cell 16; the seconds did not follow
        on the chip's host: PERF.md section 6, PR 67)."""
        seed = self.seed
        if not isinstance(seed, jax.Array):  # a number: no program of its own
            seed = np.asarray(seed).astype(np.uint32)
        return _init_layers(
            rng, seed, layers=tuple(self.config.layers().items()))

    def keys(self, batch: Dict[str, Array]) -> Array:
        return batch["ids"]

    def step(self, state, batch: Dict[str, Array], pulled: Array):
        cfg = self.config
        lr = cfg.learning_rate
        live = batch["mask"]
        x = batch["dense"].astype(jnp.float32)
        lower_i, lower_j, both = pair_tables(cfg.fields + 1)
        n_bot, n_top = len(cfg.bottom_mlp), len(cfg.top_mlp)
        blocks = self.example_blocks
        if x.shape[0] % blocks:  # a ragged minibatch is one block
            blocks = 1

        def by_example(rows):
            # the rows of the copy that `pulls_turned`, `(fields, B, dim)`,
            # as `(B, fields, dim)` and back: no pass on the TPU, where the
            # batch stays the minor axis of the buffer
            return jnp.swapaxes(rows, 0, 1) if self.field_major else rows

        with scope("ps.dense_bottom"):
            bot = _mlp_forward(state, "bot", x, n_bot, True)
        with scope("ps.dense_interact"):
            t = jnp.concatenate([bot[-1][:, None, :], by_example(pulled)], axis=1)
            z = jnp.einsum("bid,bjd->bij", t, t, precision=_PRECISION)
            r = jnp.concatenate([bot[-1], z[:, lower_i, lower_j]], axis=1)
        with scope("ps.dense_top"):
            top = _mlp_forward(state, "top", r, n_top, False)
            logit = top[-1][:, 0]
            # p - y for y in {0, 1}, written -s / (1 + exp(s logit)), s the
            # label's sign: the same number without the subtraction
            # (models/logistic_ftrl.example_deltas)
            sign = jnp.where(batch["label"] > 0, 1.0, -1.0)
            examples = jnp.maximum(jnp.sum(live, dtype=jnp.float32), 1.0)
            d_logit = jnp.where(
                live, -sign / (1.0 + jnp.exp(sign * logit)), 0.0
            ) / examples
            grads, d_r = _mlp_backward(
                state, "top", top, d_logit[:, None], False, blocks
            )
        with scope("ps.dense_interact"):
            # dZ + dZ^t as ONE product with a 0/1 matrix: pair (i, j)'s
            # gradient at (i, j) and at (j, i), zeros on the diagonal
            d_z = _dot(d_r[:, cfg.dim:], both).reshape(z.shape)
            # (turned whole, THEN cut: the rows' gradients leave by one slice
            # of the leading axis that the delta build's multiply takes in;
            # cut first, `d_t[:, 1:]` is a pass of its own, 1.6 ms in cell 10)
            d_t = by_example(
                jnp.einsum("bij,bjd->bid", d_z, t, precision=_PRECISION))
            d_z0, d_rows = jnp.split(d_t, [1], axis=self.field_axis)
        with scope("ps.dense_bottom"):
            bot_grads, _ = _mlp_backward(
                state, "bot", bot,
                d_r[:, :cfg.dim] + jnp.squeeze(d_z0, self.field_axis), True,
                blocks,
            )
            grads.update(bot_grads)
        with scope("ps.dense_sgd"):
            state = {k: v - lr * grads[k] for k, v in state.items()}
        with scope("ps.delta_build"):
            deltas = -lr * d_rows
        out = {
            "prediction": jax.nn.sigmoid(logit),
            "loss": jax.nn.softplus(-sign * logit) * live,
            "dlrm_dense_params": jnp.asarray(cfg.dense_params, jnp.int32),
            "dlrm_dense_flops_per_step": jnp.asarray(
                6.0 * cfg.macs_per_example * x.shape[0], jnp.float32
            ),
        }
        ids = self.lanes(batch["ids"])
        mask = jnp.broadcast_to(self.by_field(live), ids.shape)
        return state, PushRequest(ids, deltas, mask), out

    def publish_counts(self, outs, registry, total, peak) -> None:
        # constants of the logic, so the newest step's
        registry.gauge("dlrm_dense_params", component="train").set(
            peak(outs["dlrm_dense_params"]))
        registry.gauge("dlrm_dense_flops_per_step", component="train").set(
            peak(outs["dlrm_dense_flops_per_step"]))


def uniform_rows(config: DLRMConfig, *, seed=0, dtype=jnp.float32) -> InitFn:
    """The source's embedding init: a field's rows ``U(-sqrt(1 / C),
    sqrt(1 / C))``, ``C`` the field's cardinality; every row from the seed
    and its own id alone.  ``seed`` may be traced."""
    unit = ranged_random_factor(
        seed, (config.dim,), low=-1.0, high=1.0, dtype=dtype
    )
    firsts = np.concatenate([[0], np.cumsum(config.field_cardinalities)[:-1]])

    def init(ids: Array) -> Array:
        bound = jnp.ones(ids.shape, dtype)
        for first, card in zip(firsts, config.field_cardinalities):
            bound = jnp.where(ids >= int(first), np.sqrt(1.0 / card), bound)
        return bound[:, None] * unit(ids)

    return init


def make_store(
    config: DLRMConfig, *, seed=0, mesh=None, dtype=None, layout: str = "auto",
) -> ShardedParamStore:
    """``(num_rows, dim)`` add-store of every field's embedding rows, field
    after field, initialised in place (:func:`uniform_rows`;
    ``ShardedParamStore.create``).  The rows' place on the chip is
    ``core/store._resolve_layout``'s to choose: 64 lanes lie two to a
    128-lane physical row."""
    dtype = dtype or jnp.float32
    return ShardedParamStore.create(
        config.num_rows, (config.dim,), dtype=dtype,
        init_fn=uniform_rows(config, seed=seed, dtype=dtype), mesh=mesh,
        layout=layout,
    )


__all__ = ["DLRM", "DLRMConfig", "make_store", "pair_tables", "uniform_rows"]
