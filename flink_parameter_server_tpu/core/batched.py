"""Batched (jit-compiled) worker API — the TPU programming model.

Reference parity: this is the compiled counterpart of the reference's
``WorkerLogic`` trait (SURVEY.md §2 #2).  Where the reference invokes
``onRecv`` per record and ``onPullRecv`` per answer on a JVM thread, the TPU
rebuild processes a *microbatch of events per jitted step*:

    ids            = logic.keys(batch)                # which params to pull
    pulled         = store.pull(ids)                  # sharded gather
    state', req, o = logic.step(state, batch, pulled) # the "training math"
    store'         = store.push(req.ids, req.deltas)  # sharded scatter-add

A step may pull in several ROUNDS, as the reference's worker pulls again
from ``onPullRecv`` (it is handed the client in both hooks): after each pull
the logic is asked for the next round's keys, a function of the batch, the
state and the rows pulled so far, inside the one jitted step:

    ids_n  = logic.next_keys(state, batch, (pulled_0, ..., pulled_n-1))
    pulled_n = store.pull(ids_n)                      # until it answers None

and ``step`` then takes every round's rows.  A store that no request names
is only read: its table leaves the step as it came in.

The worker's mutable local state (e.g. MF user vectors) is an explicit
pytree threaded through ``step`` — data-parallel across the ``dp`` mesh axis
the way the reference's worker state is partitioned across
``workerParallelism`` subtasks.
"""
from __future__ import annotations

import abc
import dataclasses
import functools
from typing import Any, Generic, Optional, Tuple, TypeVar

import jax
import jax.numpy as jnp

from ..training.tracing import mesh_scope

Array = jax.Array
State = TypeVar("State")
Batch = TypeVar("Batch")
Out = TypeVar("Out")


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PushRequest:
    """A microbatch of pushes: fold ``deltas[i]`` into param ``ids[i]``.

    ``mask`` marks valid lanes (padding-friendly static shapes).  In a
    step over several stores (below) ``ids`` may be ``None``: the keys the
    step pulled from that store, lane for lane."""

    ids: Optional[Array]
    deltas: Array
    mask: Optional[Array] = None


class BatchedWorkerLogic(abc.ABC, Generic[State, Batch, Out]):
    """Pure-functional worker logic compiled into the jitted step."""

    @abc.abstractmethod
    def init_state(self, rng: Array) -> State:
        """Create the worker-local state pytree (sharded along ``dp``)."""

    @abc.abstractmethod
    def keys(self, batch: Batch) -> Array:
        """Param ids this microbatch needs pulled (static shape; pad +
        mask for variable counts).  The key block in C order is the PULL's
        lanes: lane ``n`` pulls row ``keys.reshape(-1)[n]``, in that order,
        and ``step``'s ``pulled`` is ``keys.shape + row``, whoever calls
        ``step``.  This is the step's FIRST round of pulls; a logic that
        pulls again from what it pulled answers the further rounds' keys
        in :meth:`next_keys`.  The push's lanes are its request's: ``PushRequest.ids``
        in C order, deltas and mask lane for lane, and a row's deltas are
        summed in lane order."""

    def next_keys(
        self, state: State, batch: Batch, pulled: Tuple[Any, ...]
    ) -> Optional[Any]:
        """The key block of the step's NEXT round of pulls, or ``None``
        when the step has pulled all it needs.  A ROUND is one pull a store
        it names; ``pulled`` holds the rows of the rounds so far, in order
        (``pulled[0]`` those of :meth:`keys`), so round ``n``'s keys are a
        function of the batch, the state and the rows of the rounds before
        it (sampled neighbours of the nodes a pull returned:
        ``models/graphsage.py``).  Asked while the step is TRACED
        (``core/transform.make_train_step``): how many rounds there are is
        read from ``len(pulled)``, static, and every round runs inside the
        one jitted step.  A logic that answers a block here is handed
        ``step``'s ``pulled`` as the TUPLE of every round's rows; the
        default, one round, takes round 0's rows as they are."""
        return None

    @abc.abstractmethod
    def step(
        self, state: State, batch: Batch, pulled: Array
    ) -> Tuple[State, PushRequest, Out]:
        """One compiled training step over the microbatch.

        SEVERAL STORES in one step (``core/store.StoreGroup``; Wide & Deep's
        cross weights under one rule beside its embeddings under another:
        ``models/wide_deep.py``): ``keys`` answers ``{store: key block}``,
        a block perhaps computed from the batch, ``pulled`` is ``{store:
        rows}`` and the answer's second part ``{store: PushRequest}``
        (``core/transform.make_train_step`` over a ``GroupSpec``)."""

    def for_workers(self, workers: int) -> "BatchedWorkerLogic":
        """The logic ``make_train_step`` traces, asked once with the worker
        count of the store's mesh (a batch's leaves are split over the
        workers on their leading axis).  Default: itself.  The one caller
        that honours what the answer may declare is ``make_train_step``,
        which reads both with ``getattr`` (a logic need not subclass this):
        an answer whose ``pulls_turned`` is true is handed ``pulled`` with
        the key block's two axes SWAPPED, for keys ``(B, K)`` ``(K, B) +
        row``, ``pulled[f, b]`` the row of ``keys[b, f]``, and pushes a
        block ``(K, B)`` (``core/store.pull`` and ``push_counted``'s
        ``turned``).  Every other caller of ``step`` holds the logic it was
        given, whose ``pulled`` is ``keys.shape + row``
        (``cluster/driver.ClusterDriver``):
        ``models/factorization_machine.FieldLanes`` answers a COPY that
        computes field-major and stays example-major itself.  An answer
        with ``example_blocks`` (a number; ``models/dlrm.DLRM``: a dense
        net) says that its compute is a function of each example and of a
        replicated state that only sums over the examples change, and that
        it takes those sums over that many equal blocks of the minibatch
        and adds the blocks' in their order (:func:`sums_by_blocks`):
        under ONE worker group and ``ps`` > 1 servers
        that divide the blocks ``make_train_step`` then splits the
        minibatch's compute over the servers' own axis, whole blocks a chip,
        and the result is the one-place step's bit for bit (its docstring
        says how; absent, the whole minibatch is computed in every place)."""
        return self

    def key_router(self, *, registry=None, tracer=None):  # noqa: B027
        """The keyed shuffle this logic's step needs in front of it (an
        object with ``route(batches) -> batches``:
        :class:`~..data.keyed.KeyedRouter`), or ``None`` where the step takes
        a microbatch as the stream delivered it.  ``transform_batched`` and
        the StreamingDriver route their input through it; a batch that is
        keyed already passes through untouched."""
        return None

    def publish_counts(  # noqa: B027
        self, outs: dict, registry: Any, total, peak
    ) -> None:
        """What this logic's step counted among its outputs, as gauges of
        ``registry``: the logic that makes a count names its gauge, in a
        literal ``registry.gauge("<name>", component="train")`` call
        (docs/observability.md is the catalog).  ``outs`` are a dispatch's
        outputs; ``total`` and ``peak`` read one of them over the steps the
        dispatch stacked (a count's sum, a constant's maximum).  Whoever
        fetches the outputs calls this where it fetches them anyway (the
        StreamingDriver: at the metrics cadence and once after its loop),
        beside ``core/store.publish_counts``.  Default: nothing."""

    def finish(self, state: State) -> Any:  # noqa: B027
        """Optional close-time worker output (e.g. dump local user
        vectors) — counterpart of ``WorkerLogic.close``."""
        return None


def sums_by_blocks(fn, blocks: int, *per_example):
    """``fn(*per_example)``, a tree of sums over the examples (the leading
    axis of every argument), taken over each of ``blocks`` equal blocks of
    the examples ALONE and added block after block in the order of the
    batch: an association that is written down, so whoever holds a block
    computes the same bits.  In one place the blocks are slices.  Where
    ``make_train_step`` has split the examples over a mesh's chips (it
    traces the logic under that mesh's description then, and under none
    otherwise) each chip runs ``fn`` on the whole blocks it holds, the
    chips' sums are all-gathered, and every chip adds them itself: no sum
    is a collective's, and the result is the one-place step's bit for bit.
    ``blocks`` 1: ``fn`` as it is."""
    if blocks == 1:
        return fn(*per_example)
    mesh = jax.sharding.get_abstract_mesh()

    def by_block(*local, parts=blocks):
        pieces = zip(*(jnp.split(x, parts) for x in local))
        return [fn(*piece) for piece in pieces]

    if mesh.empty:
        parts = by_block(*per_example)
    else:
        over = tuple(name for name, size in mesh.shape.items() if size > 1)
        held = blocks // mesh.size

        def on_chip(*local):
            return jax.tree.map(
                lambda *sums: jnp.stack(sums), *by_block(*local, parts=held))

        # (gathered by the partitioner, which names and places it)
        split = jax.sharding.PartitionSpec(over)
        held_sums = jax.shard_map(on_chip, in_specs=split, out_specs=split)(
            *per_example)
        with mesh_scope("block_sums_gather") as moved:
            gathered = moved(jax.lax.with_sharding_constraint(
                held_sums, jax.sharding.PartitionSpec()))
        parts = [jax.tree.map(lambda s, k=k: s[k], gathered)
                 for k in range(blocks)]
    return functools.reduce(
        lambda done, part: jax.tree.map(jnp.add, done, part), parts)


__all__ = ["PushRequest", "BatchedWorkerLogic", "sums_by_blocks"]
