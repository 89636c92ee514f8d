"""Online matrix factorization on the parameter server.

Reference parity (SURVEY.md §2 #7, §3.2/§3.3): the canonical example of
``flink-parameter-server`` — ``PSOnlineMatrixFactorization.psOnlineMF``:

  * **user vectors live in worker state** (partitioned across workers),
  * **item vectors live on the PS** (sharded across server subtasks),
  * per rating (u, i, r): pull item vector → SGD on the (user, item) pair →
    update the local user vector, push the item delta,
  * ``SGDUpdater`` carries learning rate + regularisation,
  * per-id deterministic random init (ranged random factor descriptors).

TPU-first mapping: a *microbatch of ratings* is one jitted step.  The user
table is a dp-sharded ``(num_users, dim)`` array (worker state), the item
table a ps-sharded :class:`ShardedParamStore`.  Pull is a sharded gather of
the batch's item ids; the SGD math is one fused elementwise+matmul block on
the MXU; user updates are a local scatter-add; item deltas are one sharded
scatter-add push.  Duplicate users/items inside a batch combine additively —
the same hogwild-style interleaving the reference embraces across workers
(SURVEY.md §2 "Asynchrony"), here bounded to one microbatch.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.api import WorkerLogic
from ..core.batched import BatchedWorkerLogic, PushRequest
from ..core.store import ShardedParamStore
from ..ops import row_update
from ..parallel.mesh import DP_AXIS
from ..training.tracing import scope
from ..utils.initializers import ranged_random_factor

Array = jax.Array

# the arms of the worker-state update (OnlineMatrixFactorization.step)
STATE_ARMS = ("sorted_rows", "xla")


@dataclasses.dataclass(frozen=True)
class SGDUpdater:
    """The reference's ``SGDUpdater`` (learn rate + L2 regularisation) as a
    pure vectorised function over a batch of (user_vec, item_vec, rating)."""

    learning_rate: float = 0.01
    regularization: float = 0.0

    def delta(
        self, rating: Array, user_vec: Array, item_vec: Array
    ) -> Tuple[Array, Array, Array]:
        """Returns (user_delta, item_delta, prediction); batch-shaped."""
        pred = jnp.sum(user_vec * item_vec, axis=-1)
        err = (rating - pred)[..., None]
        lr = self.learning_rate
        reg = self.regularization
        user_delta = lr * (err * item_vec - reg * user_vec)
        item_delta = lr * (err * user_vec - reg * item_vec)
        return user_delta, item_delta, pred


class OnlineMatrixFactorization(BatchedWorkerLogic):
    """Batched MF worker logic: user factors = worker state, item factors =
    PS store.  Batches are dicts with keys ``user``, ``item``, ``rating``,
    ``mask`` (see :func:`..data.streams.microbatches`)."""

    def __init__(
        self,
        num_users: int,
        dim: int,
        *,
        updater: SGDUpdater = SGDUpdater(),
        seed: int = 0,
        init_low: float = -0.01,
        init_high: float = 0.01,
        mesh: Optional[Mesh] = None,
        dp_axis: str = DP_AXIS,
        dtype=jnp.float32,
        dedup_scale: bool = False,
        num_items: Optional[int] = None,
        state_scatter: Optional[str] = None,
    ):
        self.num_users = num_users
        self.dim = dim
        self.updater = updater
        self.seed = seed
        self.init_low = init_low
        self.init_high = init_high
        self.mesh = mesh
        self.dp_axis = dp_axis
        self.dtype = dtype
        # dedup_scale: combine duplicate-id deltas within a batch by MEAN
        # instead of SUM (ops/dedup.py).  At very large microbatches a
        # Zipf-hot user/item otherwise takes count x lr effective steps
        # from one pulled snapshot and SGD diverges; mean-combining keeps
        # the step bounded regardless of batch size (staleness knob).
        self.dedup_scale = dedup_scale
        self.num_items = num_items
        if dedup_scale and num_items is None:
            raise ValueError("dedup_scale=True requires num_items")
        # state_scatter pins the arm of the worker-state update; None (the
        # default) takes what the step can see (``state_update_arm``).  The
        # pin is how a test reaches the kernel, interpreted, off the TPU:
        #   "sorted_rows": the gathered rows and their deltas sorted by
        #     user, one pipelined row write per unique user
        #     (ops/row_update) — where the kernel can run: TPU, no mesh,
        #     float32 rows of 128 lanes;
        #   "xla": the plain scatter-add, one serial read-modify-write a
        #     lane on the TPU (75 ns a row, PERF.md section 6).
        if state_scatter not in (None,) + STATE_ARMS:
            raise ValueError(
                f"state_scatter={state_scatter!r}: one of {STATE_ARMS} "
                f"or None"
            )
        self.state_scatter = state_scatter
        self._fallback_noted = False
        if (state_scatter is None and mesh is None
                and jax.default_backend() == "tpu"
                and row_update.refusal((dim,), dtype) is None):
            # the step of this logic is going to trace the kernel: have
            # Pallas imported by then
            row_update.preload()

    # -- BatchedWorkerLogic ------------------------------------------------
    def init_state(self, rng: Array) -> Array:
        init = ranged_random_factor(
            self.seed, (self.dim,), low=self.init_low, high=self.init_high,
            dtype=self.dtype,
        )
        ids = jnp.arange(self.num_users, dtype=jnp.int32)
        if self.mesh is not None and self.dp_axis in self.mesh.axis_names:
            sharding = NamedSharding(self.mesh, P(self.dp_axis, None))
            return jax.jit(init, out_shardings=sharding)(ids)
        return init(ids)

    def keys(self, batch: Dict[str, Array]) -> Array:
        return batch["item"]

    def state_update_arm(self, state) -> str:
        """The arm ``step`` compiles for this state (one of ``STATE_ARMS``):
        the pinned one, else "sorted_rows" wherever its kernel can run.  On
        a TPU without a mesh a refusal by shape or dtype is counted and
        warned of once (``ops/row_update.refusal_count``)."""
        if self.state_scatter is not None:
            return self.state_scatter
        # under a mesh the state is P(dp, None) and GSPMD partitions the
        # XLA scatter; off the TPU the kernel would be interpreted
        if self.mesh is not None or jax.default_backend() != "tpu":
            return "xla"
        why = row_update.refusal(state.shape[1:], state.dtype)
        if why is None:
            return "sorted_rows"
        if not self._fallback_noted:
            self._fallback_noted = True
            row_update.note_refusal("the MF worker-state update", why)
        return "xla"

    def step(self, state: Array, batch: Dict[str, Array], pulled: Array):
        users = batch["user"].astype(jnp.int32)
        ratings = batch["rating"].astype(self.dtype)
        mask = batch.get("mask")
        if mask is None:
            mask = jnp.ones(users.shape, bool)
        arm = self.state_update_arm(state)

        with scope("ps.state_pull"):
            user_vecs = jnp.take(state, users, axis=0)
        user_delta, item_delta, pred = self.updater.delta(
            ratings, user_vecs, pulled
        )
        if self.dedup_scale:
            from ..ops.dedup import occurrence_scale

            u_scale = occurrence_scale(users, self.num_users, mask)
            i_scale = occurrence_scale(
                batch["item"].astype(jnp.int32), self.num_items, mask
            )
            user_delta = user_delta * u_scale[..., None].astype(self.dtype)
            item_delta = item_delta * i_scale[..., None].astype(self.dtype)
        with scope("ps.state_push"):
            if arm == "sorted_rows":
                # everything else stays in stream order (the item push
                # sums a hot item's deltas in the order it always did);
                # only the rows gathered above and their deltas are
                # brought into user order for the kernel
                state = row_update.row_add(
                    state, users, user_vecs, user_delta, mask
                )
            else:
                user_delta = user_delta * mask[..., None].astype(self.dtype)
                state = state.at[users].add(user_delta, mode="drop")
        out = {"prediction": pred, "error": (ratings - pred) * mask}
        return state, PushRequest(batch["item"], item_delta, mask), out

    def finish(self, state: Array):
        # close()-time worker dump: the final user factors (the reference's
        # workers emit updated (user, vector) records).
        return {"user_factors": state}


def ps_online_mf(
    ratings,
    *,
    num_users: int,
    num_items: int,
    dim: int = 16,
    learning_rate: float = 0.05,
    regularization: float = 0.0,
    seed: int = 0,
    mesh: Optional[Mesh] = None,
    dedup_scale: bool = False,
    layout: str = "dense",
    state_scatter: Optional[str] = None,
    **transform_kwargs,
):
    """End-to-end wrapper mirroring ``PSOnlineMatrixFactorization.psOnlineMF``
    (SURVEY.md §3.3): build the item store + MF worker and run ``transform``.

    ``ratings``: iterable of microbatch dicts (user, item, rating, mask).
    Returns the :class:`TransformResult`; ``result.store.values()`` is the
    final item-factor matrix, ``result.worker_state`` the user factors.

    ``layout`` reaches the item store (see
    :class:`~..core.store.StoreSpec`); ``state_scatter`` the user-state
    update, where None leaves the logic to take the arm it can run
    (``state_update_arm``).
    """
    from ..core.transform import transform_batched

    logic = OnlineMatrixFactorization(
        num_users,
        dim,
        updater=SGDUpdater(learning_rate, regularization),
        seed=seed,
        mesh=mesh,
        dedup_scale=dedup_scale,
        num_items=num_items if dedup_scale else None,
        state_scatter=state_scatter,
    )
    store = ShardedParamStore.create(
        num_items,
        (dim,),
        init_fn=ranged_random_factor(seed + 1, (dim,)),
        mesh=mesh,
        layout=layout,
    )
    # the store built here has no other owner: the loop takes it, no copy
    transform_kwargs.setdefault("owns_inputs", True)
    return transform_batched(
        ratings, logic, store, rng=jax.random.PRNGKey(seed), mesh=mesh,
        **transform_kwargs,
    )


def make_locality_mf_step(
    logic: OnlineMatrixFactorization,
    spec,
    mesh: Mesh,
    *,
    dp_axis: str = DP_AXIS,
    ps_axis: str = "ps",
):
    """The whole MF step fused into ONE ``shard_map`` over (dp × ps) —
    the explicit-collective alternative to the jit-auto path.

    Contract: batches must be partition-aligned by user
    (:func:`..data.streams.partitioned_microbatches` with ``key="user"``,
    ``capacity=num_users``) and ``num_users`` divisible by the dp size;
    the user table is then dp-block-sharded and its gather/scatter is
    purely local.  The only collectives per step are the pull's ``psum``
    over ``ps`` and one ``all_gather`` of (ids, deltas) over ``dp`` for
    the push — the reference's entire message plane as two ICI ops
    (SURVEY.md §2 "TPU-native equivalent").  Out-of-partition users are
    masked out defensively (a violation of the alignment contract drops
    those updates rather than corrupting other shards' rows).

    Use: ``step = jax.jit(make_locality_mf_step(logic, store.spec, mesh))``
    then ``table, state, out = step(store.table, state, batch)``.
    """
    dp = mesh.shape[dp_axis]
    ps = mesh.shape[ps_axis]
    assert spec.padded_capacity % ps == 0, (
        f"store padded capacity {spec.padded_capacity} not divisible by the "
        f"mesh ps size {ps} — build the store with this mesh"
    )
    rows = spec.padded_capacity // ps
    assert logic.num_users % dp == 0, (logic.num_users, dp)
    users_per_shard = logic.num_users // dp
    updater = logic.updater
    dtype = logic.dtype

    def body(local_table, local_state, batch):
        # batches MUST carry a "mask" key (shard_map's in_specs are a
        # fixed pytree); partitioned_microbatches always emits one
        users = batch["user"].astype(jnp.int32)
        items = batch["item"].astype(jnp.int32)
        ratings = batch["rating"].astype(dtype)
        mask = batch["mask"]

        # -- pull: each ps shard answers its rows, one psum assembles ----
        ps_idx = jax.lax.axis_index(ps_axis)
        lo = ps_idx * rows
        rel = items - lo
        hit = (rel >= 0) & (rel < rows)
        vals = jnp.take(local_table, jnp.clip(rel, 0, rows - 1), axis=0)
        vals = jnp.where(hit[:, None], vals, jnp.zeros_like(vals))
        pulled = jax.lax.psum(vals, ps_axis)

        # -- local user state (alignment contract: users live here) ------
        dp_idx = jax.lax.axis_index(dp_axis)
        ulo = dp_idx * users_per_shard
        urel = users - ulo
        uvalid = (urel >= 0) & (urel < users_per_shard) & mask
        urel = jnp.clip(urel, 0, users_per_shard - 1)
        user_vecs = jnp.take(local_state, urel, axis=0)

        user_delta, item_delta, pred = updater.delta(ratings, user_vecs, pulled)
        um = uvalid[:, None].astype(dtype)
        local_state = local_state.at[urel].add(user_delta * um)

        # -- push: all_gather the microbatch over dp, local scatter ------
        # gate on uvalid, not mask: an out-of-partition user's item delta
        # was computed from the wrong (clipped) user row and must be
        # dropped, matching the docstring's contract-violation semantics
        g_items = jax.lax.all_gather(items, dp_axis, tiled=True)
        g_deltas = jax.lax.all_gather(
            item_delta * uvalid[:, None].astype(dtype), dp_axis, tiled=True
        )
        rel2 = g_items - lo
        hit2 = (rel2 >= 0) & (rel2 < rows)
        g_deltas = jnp.where(hit2[:, None], g_deltas, jnp.zeros_like(g_deltas))
        local_table = local_table.at[jnp.clip(rel2, 0, rows - 1)].add(
            g_deltas.astype(local_table.dtype)
        )

        out = {"prediction": pred, "error": (ratings - pred) * uvalid}
        return local_table, local_state, out

    batch_spec = {
        "user": P(dp_axis),
        "item": P(dp_axis),
        "rating": P(dp_axis),
        "mask": P(dp_axis),
    }
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(ps_axis, None), P(dp_axis, None), batch_spec),
        out_specs=(
            P(ps_axis, None),
            P(dp_axis, None),
            {"prediction": P(dp_axis), "error": P(dp_axis)},
        ),
        check_vma=False,
    )


class MFWorkerLogic(WorkerLogic):
    """Event-API MF worker — the literal reference programming model
    (SURVEY.md §3.2): buffer the rating, pull the item vector, on answer run
    SGD, update the local user vector, push the item delta.

    Exists for semantics-parity tests and as the migration example from the
    reference's callback style; the batched logic above is the TPU path.
    """

    def __init__(
        self,
        dim: int,
        updater: SGDUpdater = SGDUpdater(),
        seed: int = 0,
        init_low: float = -0.01,
        init_high: float = 0.01,
    ):
        self.dim = dim
        self.updater = updater
        self._init = ranged_random_factor(seed, (dim,), low=init_low, high=init_high)
        self.user_vectors: Dict[int, Any] = {}
        self.pending: Dict[int, list] = {}

    def _user_vec(self, u: int):
        if u not in self.user_vectors:
            import numpy as np

            self.user_vectors[u] = np.asarray(self._init(jnp.array([u]))[0])
        return self.user_vectors[u]

    def on_recv(self, data, ps):
        u, i, r = data
        self.pending.setdefault(i, []).append((u, r))
        ps.pull(i)

    def on_pull_recv(self, param_id, param_value, ps):
        import numpy as np

        item_vec = np.asarray(param_value)
        for u, r in self.pending.pop(param_id, []):
            user_vec = self._user_vec(u)
            ud, idelta, pred = self.updater.delta(
                jnp.asarray(r), jnp.asarray(user_vec), jnp.asarray(item_vec)
            )
            self.user_vectors[u] = user_vec + np.asarray(ud)
            ps.push(param_id, np.asarray(idelta))
            ps.output((u, param_id, float(pred)))


__all__ = [
    "SGDUpdater",
    "OnlineMatrixFactorization",
    "MFWorkerLogic",
    "make_locality_mf_step",
    "ps_online_mf",
]
