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
from ..parallel.mesh import DP_AXIS, worker_count
from ..training.tracing import scope
from ..utils.initializers import ranged_random_factor

Array = jax.Array

# the arms of the worker-state update (OnlineMatrixFactorization.step)
STATE_ARMS = ("sorted_rows", "xla")


def worker_block_rows(num_users: int, workers: int) -> int:
    """Rows of one keyed worker's block of the user factors: ``num_users``
    over the workers, aligned up to 8 (a float32 sublane tile, so every
    block starts on one).  Worker ``w`` owns users ``[w * rows, (w + 1) *
    rows)``; one worker holds exactly ``num_users`` rows."""
    if workers == 1:
        return num_users
    return -(-num_users // (8 * workers)) * 8


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
    ``mask`` (see :func:`..data.streams.microbatches`).

    Under a mesh whose ``dp`` axis is larger than one the user factors are
    partitioned by user id over ``dp`` KEYED WORKERS, as the reference
    partitions them over its worker subtasks: worker ``w`` holds the
    contiguous block of ``rows_per_worker`` rows that starts at ``w *
    rows_per_worker`` (the state is ``P(dp, None)`` over ``state_rows`` =
    ``workers * rows_per_worker`` rows; the rows past ``num_users`` are
    padding that the per-id initialiser fills and no record names), and the
    stream reaches each worker keyed: lane block ``w`` of a microbatch holds
    worker ``w``'s users (:meth:`key_router`, which the loop and the driver
    put in front of the step).  ``step`` then runs each worker's own records
    against its own block inside a ``shard_map``; a live record whose user is
    not its block's is counted (``keyed_misrouted``) and dropped, never
    applied to another row."""

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
        if (state_scatter is None and (mesh is None or self.workers > 1)
                and jax.default_backend() == "tpu"
                and row_update.refusal((dim,), dtype) is None):
            # the step of this logic is going to trace the kernel: have
            # Pallas imported by then
            row_update.preload()

    # -- keyed workers -----------------------------------------------------
    @property
    def workers(self) -> int:
        """Keyed workers the user factors are partitioned over: the size of
        the mesh's ``dp`` axis (1 without a mesh or without that axis)."""
        return worker_count(self.mesh, self.dp_axis)

    @property
    def rows_per_worker(self) -> int:
        return worker_block_rows(self.num_users, self.workers)

    @property
    def state_rows(self) -> int:
        return self.workers * self.rows_per_worker

    def key_router(self, *, registry=None, tracer=None):
        if self.workers == 1:
            return None
        from ..data.keyed import KeyedRouter

        return KeyedRouter(
            self.workers, self.rows_per_worker, key="user",
            registry=registry, tracer=tracer,
        )

    def publish_counts(self, outs, registry, total, peak) -> None:
        if "keyed_misrouted" in outs:
            # keyed workers: the live records that reached a worker whose
            # block lacks their row (0 behind the router)
            registry.gauge("keyed_misrouted", component="train").set(
                total(outs["keyed_misrouted"]))

    # -- BatchedWorkerLogic ------------------------------------------------
    def init_state(self, rng: Array) -> Array:
        init = ranged_random_factor(
            self.seed, (self.dim,), low=self.init_low, high=self.init_high,
            dtype=self.dtype,
        )
        if self.workers > 1:
            # every worker fills its own block from its own row ids
            dp, rows = self.dp_axis, self.rows_per_worker

            def block():
                first = jax.lax.axis_index(dp) * rows
                return init(first + jnp.arange(rows, dtype=jnp.int32))

            return jax.jit(jax.shard_map(
                block, mesh=self.mesh, in_specs=(), out_specs=P(dp, None),
                check_vma=False,
            ))()
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
        # keyed workers update their own block inside a shard_map, where
        # the kernel sees a plain array; a mesh with one worker leaves the
        # XLA scatter to GSPMD; off the TPU the kernel would be interpreted
        if (self.mesh is not None and self.workers == 1) or (
            jax.default_backend() != "tpu"
        ):
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
        i_scale = None
        if self.dedup_scale and self.workers > 1:
            # an item's records lie with every worker: counted over the
            # whole microbatch, outside the workers' own step
            from ..ops.dedup import occurrence_scale

            i_scale = occurrence_scale(
                batch["item"].astype(jnp.int32), self.num_items, mask
            )
        if self.workers == 1:
            state, item_delta, out = self._worker_step(
                arm, state, users, batch["item"], ratings, mask, pulled
            )
            return state, PushRequest(batch["item"], item_delta, mask), out

        dp, rows = self.dp_axis, self.rows_per_worker

        scales = () if i_scale is None else (i_scale,)

        def on_worker(state, users, items, ratings, mask, pulled, *scales):
            # this worker's block of the state and its lane block of the
            # microbatch: a user's row is its id less the block's first
            local = users - jax.lax.axis_index(dp) * rows
            mine = (local >= 0) & (local < rows)
            live = mask & mine
            state, item_delta, out = self._worker_step(
                arm, state, jnp.clip(local, 0, rows - 1), items, ratings,
                live, pulled, *scales,
            )
            out["keyed_misrouted"] = jnp.sum(
                mask & ~mine, dtype=jnp.int32
            )[None]
            return state, item_delta, live, out

        lanes, block = P(dp), P(dp, None)
        state, item_delta, live, out = jax.shard_map(
            on_worker,
            mesh=self.mesh,
            in_specs=(block, lanes, lanes, lanes, lanes, block)
            + (lanes,) * len(scales),
            out_specs=(
                block, block, lanes,
                {"prediction": lanes, "error": lanes,
                 "keyed_misrouted": lanes},
            ),
            check_vma=False,
        )(state, users, batch["item"], ratings, mask, pulled, *scales)
        # a misrouted record's item delta came of another user's row: the
        # push drops it with the lanes the stream masked
        return state, PushRequest(batch["item"], item_delta, live), out

    def _worker_step(
        self, arm: str, state: Array, users: Array, items: Array,
        ratings: Array, mask: Array, pulled: Array,
        i_scale: Optional[Array] = None,
    ):
        """One worker's records against its own rows of ``state`` (all of it
        where there is one worker): the state's gather, the SGD deltas, the
        state's update.  ``users`` are rows of THIS ``state``."""
        with scope("ps.state_pull"):
            user_vecs = jnp.take(state, users, axis=0)
        user_delta, item_delta, pred = self.updater.delta(
            ratings, user_vecs, pulled
        )
        if self.dedup_scale:
            from ..ops.dedup import occurrence_scale

            # a user's records all lie with its worker
            u_scale = occurrence_scale(users, state.shape[0], mask)
            if i_scale is None:
                i_scale = occurrence_scale(
                    items.astype(jnp.int32), self.num_items, mask
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
        return state, item_delta, out

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
    "worker_block_rows",
    "ps_online_mf",
]
