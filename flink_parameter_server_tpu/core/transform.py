"""``transform`` — the framework entry point.

Reference parity: re-founds ``FlinkParameterServer.transform`` and its
overload family (SURVEY.md §2 #1, §3.1): wire a training stream + worker
logic + server logic together, return the multiplexed worker/server output
streams.  The reference's Flink iteration (feedback edge, per-message Netty
hops, ``iterationWaitTime`` silence-timeout shutdown) is replaced by:

  * ``backend="tpu"`` (the point of this framework): a microbatch of events
    per jitted step; pull = sharded gather, push = sharded scatter-add, all
    collectives over ICI.  Termination is explicit: the input iterator ends,
    the final parameter dump is emitted — no silence-timeout hack
    (SURVEY.md §7 "Termination/close semantics").

  * ``backend="local"``: a host-side event loop running the *exact*
    reference callback API (``on_recv`` / ``on_pull_recv`` / ``answer_pull``)
    with FIFO message queues between worker and server partitions — the
    semantics-fidelity harness (races included when ``input_window`` > 1)
    and the migration path for arbitrary Python logics.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import zlib
from typing import Any, Callable, Generic, Iterable, List, Optional, Tuple, TypeVar, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .api import (
    ParameterServer,
    ParameterServerClient,
    ParameterServerLogic,
    SimplePSLogic,
    WorkerLogic,
)
from .batched import BatchedWorkerLogic
from .entities import Pull, PullAnswer, Push, PSToWorker, WorkerToPS
from .store import ShardedParamStore, StoreGroup
from ..parallel.mesh import DP_AXIS, worker_count
from ..telemetry.compile_ledger import setup_span
from ..telemetry.spans import NULL_TRACER, SpanTracer
from ..training.metrics import InFlight
from ..training.tracing import mesh_scope, mesh_tally, scope

T = TypeVar("T")
P_ = TypeVar("P_")
WOut = TypeVar("WOut")
PSOut = TypeVar("PSOut")


def jnp_copy(x):
    """Device-resident copy preserving sharding (for donation safety)."""
    return jnp.copy(x) if isinstance(x, jax.Array) else x


def stable_route_hash(key) -> int:
    """Routing hash for ``hash(paramId) % psParallelism`` that is stable
    across processes (Python's ``hash`` is PYTHONHASHSEED-randomised for
    strings, which would break cross-process determinism of the event
    backend).  Ints keep identity semantics, matching the reference's
    ``paramId.hashCode`` for Scala Ints."""
    if isinstance(key, bool):
        return int(key)
    if isinstance(key, (int, np.integer)):
        return int(key)
    if isinstance(key, str):
        return zlib.crc32(key.encode("utf-8"))
    if isinstance(key, bytes):
        return zlib.crc32(key)
    return zlib.crc32(repr(key).encode("utf-8"))


@dataclasses.dataclass
class TransformResult(Generic[WOut, PSOut]):
    """The two multiplexed output streams of a PS job — the reference
    returns them as one ``DataStream[Either[WOut, PSOut]]``; we keep them
    separate and offer :meth:`either` for parity."""

    worker_outputs: List[Any]
    server_outputs: List[Any]
    store: Optional[ShardedParamStore] = None
    worker_state: Any = None

    def either(self) -> List[Tuple[str, Any]]:
        return [("left", w) for w in self.worker_outputs] + [
            ("right", s) for s in self.server_outputs
        ]


# ---------------------------------------------------------------------------
# Local (event) backend — reference-exact callback semantics on the host.
# ---------------------------------------------------------------------------


class _LocalClient(ParameterServerClient):
    def __init__(self, runtime: "_LocalRuntime", worker_idx: int):
        self._rt = runtime
        self._widx = worker_idx

    def pull(self, param_id: int) -> None:
        self._rt.send_w2ps(self._widx, WorkerToPS(self._widx, Pull(param_id)))

    def push(self, param_id: int, delta) -> None:
        self._rt.send_w2ps(
            self._widx, WorkerToPS(self._widx, Push(param_id, delta))
        )

    def output(self, w_out) -> None:
        self._rt.worker_outputs.append(w_out)


class _LocalPSIface(ParameterServer):
    def __init__(self, runtime: "_LocalRuntime", server_idx: int):
        self._rt = runtime
        self._sidx = server_idx

    def answer_pull(self, param_id: int, value, worker_idx: int) -> None:
        self._rt.send_ps2w(
            self._sidx, PSToWorker(worker_idx, PullAnswer(param_id, value))
        )

    def output(self, ps_out) -> None:
        self._rt.server_outputs.append(ps_out)


class _LocalRuntime:
    """Single FIFO event loop emulating the Flink iteration.

    Input records are admitted up to ``input_window`` ahead of message
    processing, so pulls/pushes from different workers interleave — the
    async-hazard surface of the reference (SURVEY.md §3.2) reproduced
    deterministically.
    """

    def __init__(
        self,
        worker_logics: List[WorkerLogic],
        ps_logics: List[ParameterServerLogic],
        partitioner: Optional[Callable[[Any, int], int]],
        input_window: int,
        client_sender: Optional["SenderPolicy"] = None,
        ps_sender: Optional["SenderPolicy"] = None,
    ):
        from .senders import SIMPLE, BufferingSender

        self.workers = worker_logics
        self.servers = ps_logics
        self.partitioner = partitioner
        self.input_window = max(1, input_window)
        self.events: collections.deque = collections.deque()
        self.worker_outputs: List[Any] = []
        self.server_outputs: List[Any] = []
        self.ps_ifaces = [
            _LocalPSIface(self, s) for s in range(len(self.servers))
        ]
        self.clients = [_LocalClient(self, i) for i in range(len(self.workers))]
        self.tick = 0
        self.client_senders = [
            BufferingSender(client_sender or SIMPLE) for _ in self.workers
        ]
        self.ps_senders = [
            BufferingSender(ps_sender or SIMPLE) for _ in self.servers
        ]
        # only interval-triggered senders ever flush from poll(); the
        # default SIMPLE config leaves this empty (zero per-event cost)
        self._interval_senders = [
            ("w2ps", s)
            for s in self.client_senders
            if s.policy.interval is not None
        ] + [
            ("ps2w", s)
            for s in self.ps_senders
            if s.policy.interval is not None
        ]

    # -- sender plumbing (the combination-sender layer, SURVEY.md §2 #6) --
    def send_w2ps(self, worker_idx: int, msg: WorkerToPS) -> None:
        for m in self.client_senders[worker_idx].offer(msg, self.tick):
            self.events.append(("w2ps", m))

    def send_ps2w(self, server_idx: int, msg: PSToWorker) -> None:
        for m in self.ps_senders[server_idx].offer(msg, self.tick):
            self.events.append(("ps2w", m))

    def _poll_senders(self) -> None:
        for tag, s in self._interval_senders:
            for m in s.poll(self.tick):
                self.events.append((tag, m))

    def _force_flush_senders(self) -> bool:
        flushed = False
        for s in self.client_senders:
            for m in s.flush(self.tick):
                self.events.append(("w2ps", m))
                flushed = True
        for s in self.ps_senders:
            for m in s.flush(self.tick):
                self.events.append(("ps2w", m))
                flushed = True
        return flushed

    def _route_server(self, param_id: int) -> int:
        # The reference's partitionCustom(hash(paramId) % psParallelism),
        # with a PYTHONHASHSEED-independent hash for determinism.
        return stable_route_hash(param_id) % len(self.servers)

    def run(self, data: Iterable) -> None:
        it = iter(data)
        rr = itertools.cycle(range(len(self.workers)))
        exhausted = False
        in_window = 0
        while True:
            # Admit inputs up to the window.
            while not exhausted and in_window < self.input_window:
                try:
                    record = next(it)
                except StopIteration:
                    exhausted = True
                    break
                widx = (
                    self.partitioner(record, len(self.workers))
                    if self.partitioner
                    else next(rr)
                )
                self.events.append(("input", widx, record))
                in_window += 1
            if not self.events:
                if exhausted:
                    # input done and queue drained: force any buffered
                    # combination-sender messages out before concluding
                    # (the reference's timeout-flush, made explicit)
                    if self._force_flush_senders():
                        continue
                    break
                continue
            ev = self.events.popleft()
            self.tick += 1
            if ev[0] == "input":
                _, widx, record = ev
                in_window -= 1
                self.workers[widx].on_recv(record, self.clients[widx])
            elif ev[0] == "w2ps":
                msg: WorkerToPS = ev[1]
                sidx = self._route_server(msg.message.param_id)
                if isinstance(msg.message, Pull):
                    self.servers[sidx].on_pull_recv(
                        msg.message.param_id,
                        msg.worker_partition_index,
                        self.ps_ifaces[sidx],
                    )
                else:
                    self.servers[sidx].on_push_recv(
                        msg.message.param_id,
                        msg.message.delta,
                        self.ps_ifaces[sidx],
                    )
            else:  # ps2w
                msg2: PSToWorker = ev[1]
                self.workers[msg2.worker_partition_index].on_pull_recv(
                    msg2.answer.param_id,
                    msg2.answer.value,
                    self.clients[msg2.worker_partition_index],
                )
            self._poll_senders()
        # Drain: input exhausted and all in-flight messages delivered →
        # fire close hooks (the reference's iterationWaitTime-timeout moment,
        # made explicit).
        for w in self.workers:
            w.close()
        for sidx, s in enumerate(self.servers):
            s.close(self.ps_ifaces[sidx])


def _instances(factory_or_instance, n: int, what: str) -> List[Any]:
    if callable(factory_or_instance) and not isinstance(
        factory_or_instance, (WorkerLogic, ParameterServerLogic)
    ):
        return [factory_or_instance() for _ in range(n)]
    if n != 1:
        raise ValueError(
            f"{what} parallelism {n} > 1 requires a zero-arg factory, got an "
            f"instance (stateful logics cannot be shared across partitions)"
        )
    return [factory_or_instance]


# ---------------------------------------------------------------------------
# TPU (batched) backend — the compiled hot path.
# ---------------------------------------------------------------------------


def _traced_under(mesh):
    """The context the ``step`` of a logic whose compute is split over
    ``mesh`` is traced in: the mesh by its description, for
    ``core/batched.sums_by_blocks`` to read."""
    if mesh is None:
        return contextlib.nullcontext()
    return jax.sharding.use_abstract_mesh(mesh.abstract_mesh)


def _pull_rounds(logic, state, batch, pull) -> Tuple[list, Any]:
    """A step's pulls, round by round, for the single store's step and the
    group's alike: ``logic.keys(batch)``, then ``logic.next_keys(state,
    batch, rows so far)`` until it answers ``None``
    (``BatchedWorkerLogic.next_keys``: read with ``getattr``, a logic need
    not subclass it), each block handed to ``pull(block, round)``, which
    answers its rows.  Returns every round's key block and what
    ``logic.step`` takes as ``pulled``: round 0's rows as they are where
    that is the only round (the trace a step always had), else the tuple of
    every round's."""
    keys = [logic.keys(batch)]
    rows = [pull(keys[0], 0)]
    more = getattr(logic, "next_keys", None)
    while more is not None:
        block = more(state, batch, tuple(rows))
        if block is None:
            break
        keys.append(block)
        rows.append(pull(block, len(rows)))
    return keys, rows[0] if len(rows) == 1 else tuple(rows)


def _round_lanes(blocks: list):
    """``core/store.step_counts``' ``pull_lanes`` of the key blocks a step
    pulled from one store: the one block's lanes, a number (the outputs a
    step of one round always had), or a tuple, a round each."""
    if len(blocks) == 1:
        return blocks[0].size
    return tuple(block.size for block in blocks)


def _first_left(lefts):
    """What a step's pulls of one store left for ``step_counts`` to count
    (``PulledRows``: a narrow rule store's distinct rows), whichever push
    took them up: the first round's that left any."""
    return next((left for left in lefts if left is not None), None)


def _round_scope(n: int):
    """``round.<n>`` on the ``op_name`` of the ops of a step's pull round
    ``n`` >= 1, inside ``ps.pull``; round 0 opens none (a step of one round
    keeps its text)."""
    return jax.named_scope(f"round.{n}") if n else contextlib.nullcontext()


def make_train_step(logic: BatchedWorkerLogic, spec) -> Callable:
    """Build the fused pull→compute→push step (to be jit-compiled).

    One call = one microbatch of "events": the reference's per-message hot
    loop (SURVEY.md §3.1) collapsed into gather → math → scatter-add with
    zero host round-trips.  The batch is taken as the stream delivered it
    and worker outputs are in stream order.  The pull's LANES are the
    logic's key block in C order (``BatchedWorkerLogic.keys``), the push's
    its request's ids in C order, and the push sums a row's deltas in lane
    order.  For most logics both are stream order.  A batch's leaves are
    split over the mesh's workers on their leading axis, and the logic is
    asked what it is over that many (``for_workers``: this function alone
    asks, and alone honours an answer that ``pulls_turned``).  The FM
    family's answer in one place pulls in stream order, takes the rows
    turned and pushes field-major lanes, ``f B + b`` for example ``b``'s
    field ``f``: stream order within a field, and so within a row wherever
    a field has its own rows (``models/factorization_machine.FieldLanes``).

    Under ONE worker group and ``ps`` > 1 servers a logic that declares
    ``example_blocks`` (read with ``getattr``, as ``pulls_turned`` is: its
    compute is a function of each example and of a replicated state that
    only sums over the examples change, taken over that many equal blocks
    of the minibatch and added in their order) has the minibatch's COMPUTE
    split over the servers' own chips, the deployment's data-parallel half,
    where the servers divide the blocks and the blocks the batch:
    ``pulled`` and the request's deltas are constrained to lie split over
    ``ps`` on the examples' axis, and the partitioner does the rest as it
    does over ``dp``: the pull's sum over the shards leaves each chip its
    examples' rows alone (a reduce-scatter where the all-reduce stood), the
    logic's ops run on a share of the examples each, and the deltas are
    gathered, in the batch's order, in front of a push that is untouched.
    The logic's ``step`` is traced under the mesh's description, by which
    ``core/batched.sums_by_blocks`` has each chip sum the blocks it holds
    and every chip add the gathered sums itself: the state stays equal on
    every chip and is the one-place step's bit for bit.  Keys and mask stay
    whole on every chip, and so does any count of the whole minibatch the
    logic takes of them.  Mesh and declaration alone decide it; the step's
    outputs say into how many parts (``core/store.step_counts``).  Every
    other logic computes the whole minibatch on every chip, which costs it
    nothing worth a scatter and a gather of its rows (FM, DiFacto: 0.16 /
    0.065 ms a step).

    A logic may pull in several ROUNDS (``BatchedWorkerLogic.next_keys``:
    round ``n``'s keys a function of the batch, the state and the rows of
    the rounds before): every round is a pull like the first, under
    ``ps.pull`` and, from the second on, ``round.<n>``
    (:func:`_pull_rounds`, the seam this step and the group's share), all in
    front of the compute.

    ``spec`` may be a ``core/store.GroupSpec``: SEVERAL named stores in the
    one step, each with its own key space, row width and rule
    (:func:`_make_group_train_step`; the step then takes and hands back
    ``{name: table}``).  A single spec's step is the program it always was.
    """
    from . import store as store_mod

    if isinstance(spec, store_mod.GroupSpec):
        return _make_group_train_step(logic, spec)
    workers = worker_count(spec.mesh)
    # (both read with defaults: a logic need not be a BatchedWorkerLogic)
    logic = getattr(logic, "for_workers", lambda workers: logic)(workers)
    turned = getattr(logic, "pulls_turned", False)
    lanes = None
    if workers > 1:
        lanes = NamedSharding(spec.mesh, PartitionSpec(DP_AXIS))

    def over_workers(x):
        # a leaf whose lanes split over the workers lies split over them,
        # however it came: a batch staged on every chip is sliced where it
        # lies, inside this program, not by a program of its own a leaf
        if x.ndim and x.shape[0] % workers == 0:
            return jax.lax.with_sharding_constraint(x, lanes)
        return x

    # the servers' own chips take the minibatch's compute, whole blocks of
    # the examples each, for a logic that sums over them block by block
    blocks = getattr(logic, "example_blocks", 1)
    servers = spec.num_shards
    if workers > 1 or servers == 1 or blocks % servers:
        servers = 1

    # a block's axis of the examples split over `ps`
    examples = PartitionSpec(*(None,) * turned, spec.ps_axis)

    def pulled_over_servers(pulled):
        # constrained as the pull's sum over the shards lies, `(examples x
        # keys, row)`, flat (`core/store._take_on_shards`): the all-reduce
        # and this slice fold into a reduce-scatter of the block as it lies;
        # on `(B, 26, 128)` the TPU's tiles pad 26 fields to 32 first (538
        # MB moved for 436 and a relayout each side: PERF.md section 6)
        rows = jnp.swapaxes(pulled, 0, 1) if turned else pulled
        # (the sum's own name: one transfer, whose bytes `pull` states)
        with mesh_scope("pull_rows_sum"):
            flat = jax.lax.with_sharding_constraint(
                rows.reshape(-1, rows.shape[-1]),
                NamedSharding(spec.mesh, PartitionSpec(spec.ps_axis)))
        rows = flat.reshape(rows.shape)
        return jnp.swapaxes(rows, 0, 1) if turned else rows

    def step(table, state, batch):
        # (what the trace's `mesh.*` sites move leaves with the counts)
        with mesh_tally() as crossings:
            return traced(table, state, batch, crossings)

    def traced(table, state, batch, crossings):
        if lanes is not None:
            batch = jax.tree.map(over_workers, batch)
        lefts, parts_of = [], []

        def pull_round(ids, n):
            parts = servers if ids.shape[0] % blocks == 0 else 1
            # ps.* scopes are metadata on the ops' names
            # (docs/observability.md): a trace reduction finds pull, compute
            # and push by them whatever XLA numbers its fusions
            with scope("ps.pull"), _round_scope(n):
                # (of a rule store whose row has a worker's part, that part)
                # (and what the pull leaves for a push of the same keys)
                pulled, left = store_mod.pull_counted(
                    spec, table, ids, worker_part=True, turned=turned,
                    kept=parts)
                if parts > 1:
                    pulled = pulled_over_servers(pulled)
            lefts.append(left)
            parts_of.append(parts)
            return pulled

        keys, pulled = _pull_rounds(logic, state, batch, pull_round)
        ids, parts = keys[0], parts_of[0]
        # (a step that splits nothing keeps its text to the letter)
        split_over = spec.mesh if parts > 1 else None
        with scope("ps.compute"), _traced_under(split_over):
            state, req, out = logic.step(state, batch, pulled)
        # the rows a pull read are the push's where its ids are the very
        # keys of that round; nothing wrote the table in between
        left = next((held for block, held in zip(keys, lefts)
                     if req.ids is block), None)
        with scope("ps.push"):
            deltas = req.deltas
            if parts > 1:
                # (built on a chip's share of the examples, then gathered)
                deltas = jax.lax.with_sharding_constraint(
                    deltas, NamedSharding(spec.mesh, examples))
                with mesh_scope("push_deltas_gather") as moved:
                    deltas = moved(jax.lax.with_sharding_constraint(
                        deltas, NamedSharding(spec.mesh, PartitionSpec())))
            table, counted = store_mod.push_counted(
                spec, table, req.ids, deltas, req.mask,
                lanes_over_workers=lanes is not None, turned=turned,
                pulled=left,
            )
        if isinstance(out, dict):
            # what the store counted on the device and which arms this
            # trace read leave the step with the logic's outputs
            out = {**out, **store_mod.step_counts(
                spec, counted, pull_lanes=_round_lanes(keys),
                push_lanes=req.ids.size,
                fields=ids.shape[-1] if turned else None,
                compute_parts=parts, crossings=crossings,
                pulled=_first_left(lefts))}
        return table, state, out

    return step


def _make_group_train_step(logic: BatchedWorkerLogic, spec) -> Callable:
    """:func:`make_train_step` over the SEVERAL stores of a
    ``core/store.GroupSpec``: ``step(tables, state, batch)`` with ``tables``
    ``{name: table}``, every one donated with the state.  The logic's
    ``keys`` answers ``{name: key block}``, a block perhaps COMPUTED from
    the batch inside the step (a hashed cross); its ``step`` takes
    ``pulled`` as ``{name: rows}`` and answers ``{name: PushRequest}``.
    ONE pull and ONE push a store, in the group's order, each under its
    phase's scope (``ps.pull`` / ``ps.push``: the pulls of all stores stand
    before the compute, the pushes behind it) and the store's own label
    (``core/store.pull_counted`` / ``push_counted``'s ``store``).  A request
    whose ``ids`` is ``None`` pushes to the keys its store pulled, lane for
    lane, the very array: what the pull left for the push of the same keys
    (``PulledRows``) is then the push's, whether the keys were staged or
    computed.  The stores' counts leave among the outputs as
    ``<count>@<store>`` (``core/store.step_counts``).

    In one place, or under a mesh of ONE worker group: every store takes
    the arms it would take alone.  A turned pull, a split of the compute
    over the servers and a batch split over ``dp`` workers are a single
    store's step's: the logic is traced as it is given (``for_workers`` is
    not asked), and ``dp`` > 1 raises."""
    from . import store as store_mod

    if worker_count(spec.mesh) > 1:
        raise ValueError(
            "a step over several stores runs in one place or under ONE "
            f"worker group: this mesh holds {worker_count(spec.mesh)}")
    names = tuple(spec)

    def step(tables, state, batch):
        lefts = []

        def pull_round(keys, n):
            rows, left = {}, {}
            for name in names:
                if name not in keys:
                    continue
                with scope("ps.pull"), _round_scope(n):
                    rows[name], left[name] = store_mod.pull_counted(
                        spec[name], tables[name], keys[name],
                        worker_part=True, store=name)
            lefts.append(left)
            return rows

        rounds, pulled = _pull_rounds(logic, state, batch, pull_round)
        with scope("ps.compute"):
            state, reqs, out = logic.step(state, batch, pulled)
        tables, counts = dict(tables), {}
        for name in names:
            blocks = [keys[name] for keys in rounds if name in keys]
            req, counted, left, ids = reqs.get(name), None, None, None
            if req is not None:  # (a store no request names is only read)
                ids = req.ids
                if ids is None:
                    # the keys of the ONE round that pulled from the store
                    (ids,) = blocks
                left = next((held[name] for keys, held in zip(rounds, lefts)
                             if keys.get(name) is ids), None)
                with scope("ps.push"):
                    tables[name], counted = store_mod.push_counted(
                        spec[name], tables[name], ids, req.deltas, req.mask,
                        pulled=left, store=name)
            counts.update(store_mod.step_counts(
                spec[name], counted, pull_lanes=_round_lanes(blocks),
                push_lanes=None if ids is None else ids.size,
                pulled=_first_left(held.get(name) for held in lefts),
                store=name))
        if isinstance(out, dict):
            out = {**out, **counts}
        return tables, state, out

    return step


def jit_train_steps(
    logic: BatchedWorkerLogic, spec, steps_per_call: int = 1
) -> Tuple[Callable, Optional[Callable]]:
    """The jitted, donating programs ``transform_batched`` dispatches: the
    single step and, where ``steps_per_call > 1``, the scanned one (else
    ``None``).  ``transform_batched`` builds a pair a call; a caller that
    loops more than once over one logic and spec (the StreamingDriver)
    builds the pair once and hands it to each call as ``steps``, so that a
    second loop finds its step traced, lowered and loaded."""
    step = jax.jit(make_train_step(logic, spec), donate_argnums=(0, 1))
    scan_step = None
    if steps_per_call > 1:
        scan_step = jax.jit(
            make_scan_train_step(logic, spec), donate_argnums=(0, 1)
        )
    return step, scan_step


def _committed_where(table, mesh=None):
    """``table`` committed to the devices it lies on (no copy: the array
    keeps its buffers), and a function that does the same for every leaf of
    the worker state that lies on those same devices.  An UNCOMMITTED leaf
    anywhere else (a state built on the default device beside a table on
    ``mesh``) is laid replicated over the mesh, where ``jit`` would place it
    and where the step hands it back: left as it was, the first dispatch of
    a driver's second ``run`` met other input shardings than the first's
    and traced, lowered and loaded the step once more (DLRM's MLPs beside a
    table over ``ps``: PERF.md section 6, PR 67).  A committed leaf
    elsewhere is the caller's to answer for, as it always was."""
    devices = jax.tree.leaves(table)[0].sharding.device_set
    everywhere = None
    if mesh is not None and set(mesh.devices.flat) == devices:
        everywhere = NamedSharding(mesh, PartitionSpec())

    def commit(x):
        if not isinstance(x, jax.Array):
            return x
        if x.sharding.device_set == devices:
            return jax.device_put(x, x.sharding)
        if everywhere is not None and not x.committed:
            return jax.device_put(x, everywhere)
        return x

    # (a group of stores hands its tables as a pytree: every one)
    return jax.tree.map(commit, table), commit


def scan_group_sharding(batch_sharding):
    """Sharding for (K, batch, ...)-stacked scan inputs: the scan axis
    prepends as unsharded, the per-batch spec shifts right.  ``None``
    passes through; sharding types without a named PartitionSpec are
    rejected loudly — silently skipping the reshard would strand a
    dp-sharded caller's data replicated on the default device."""
    if batch_sharding is None:
        return None
    spec = getattr(batch_sharding, "spec", None)
    if spec is None:
        raise ValueError(
            f"steps_per_call > 1 needs a NamedSharding batch sharding "
            f"(got {type(batch_sharding).__name__}): extending the "
            f"leading scan axis is only defined for named PartitionSpecs"
        )
    return NamedSharding(batch_sharding.mesh, PartitionSpec(None, *spec))


def stack_group(group, scan_sharding=None):
    """Stack K microbatches into (K, ...) leaves for a scanned dispatch.

    Stacks on the HOST (the data iterator yields host arrays — the
    ingestion edge), then ships each byte exactly once: ``jnp.stack``
    would commit a replicated default-device copy first and the reshard
    would move the same bytes a second time.  Device-resident leaves are
    pulled to the host once (np.asarray) — callers chasing the last
    transfer should feed host arrays, as the loaders do."""
    stacked = jax.tree.map(
        lambda *xs: np.stack([np.asarray(x) for x in xs]), *group
    )
    if scan_sharding is not None:
        stacked = jax.tree.map(
            lambda x: jax.device_put(x, scan_sharding), stacked
        )
    return stacked


def make_scan_train_step(logic: BatchedWorkerLogic, spec) -> Callable:
    """K train steps inside ONE jitted call: ``batches`` is a pytree of
    (K, batch, ...) leaves; a ``lax.scan`` runs :func:`make_train_step`'s
    body K times on-device and returns (K, ...)-stacked outputs.

    Dispatch amortization is the point: one host→device round trip per
    K microbatches instead of per microbatch — the collective-era
    analogue of the reference's combination senders (SURVEY.md §2 #6
    batches *messages* to cut per-message overhead; this batches
    *dispatches* to cut per-step host overhead).  What K buys has not
    been measured on the chip (ROADMAP S3).
    """
    base = make_train_step(logic, spec)

    def step(table, state, batches):
        def body(carry, b):
            t, s = carry
            t, s, out = base(t, s, b)
            return (t, s), out

        (table, state), outs = jax.lax.scan(body, (table, state), batches)
        return table, state, outs

    return step


_END = object()  # the data iterator is exhausted


def transform_batched(
    data: Iterable,
    worker_logic: BatchedWorkerLogic,
    store: ShardedParamStore,
    *,
    rng: Optional[jax.Array] = None,
    mesh: Optional[Mesh] = None,
    dp_axis: str = DP_AXIS,
    collect_outputs: bool = True,
    dump_model: bool = True,
    on_step: Optional[Callable[[int, Any], None]] = None,
    state_callback: Optional[Callable[[int, Any, Any, Any], None]] = None,
    group_callback: Optional[
        Callable[[int, int, Any, Any, Any], None]
    ] = None,
    initial_state: Any = None,
    skip_batches: int = 0,
    steps_per_call: int = 1,
    tracer: SpanTracer = NULL_TRACER,
    owns_inputs: bool = False,
    steps: Optional[Tuple[Callable, Optional[Callable]]] = None,
    inflight: Optional[InFlight] = None,
) -> TransformResult:
    """Run the compiled PS loop over an iterable of microbatches.

    Who owns the table: the jitted step donates ``(table, state)``, so by
    default the loop starts from COPIES of ``store.table`` and
    ``initial_state`` and the caller's store stays valid, at the price of
    two tables alive for the length of the run.  ``owns_inputs=True``
    hands both over instead: the loop donates the very buffers it was
    given, ``store.table`` and ``initial_state`` are deleted by the first
    dispatch, and the one live table is the one a callback sees, then
    ``result.store``.  The caller must drop its own references.  Every
    caller that has no further use for what it passes does so: the
    StreamingDriver (a table over half a chip's memory cannot run
    otherwise) and the ``train_*`` / ``ps_online_mf`` / PA helpers, whose
    store is built inside them (an ``initial_state`` passed through them
    is handed over with it; ``owns_inputs=False`` keeps it).

    ``state_callback(step_idx, table, state, out)`` additionally sees the
    live (donated-next-step) table/state — the hook the StreamingDriver
    uses for metrics, checkpoints and profiling windows without
    duplicating this loop.  ``skip_batches`` fast-forwards the iterator
    (resume-from-cursor); ``initial_state`` overrides
    ``worker_logic.init_state`` (restored worker state).

    ``steps_per_call=K`` runs K microbatches per jitted dispatch via
    :func:`make_scan_train_step` — one host round trip per K steps
    (essential when host↔device latency rivals the step time; a
    trailing group shorter than K runs through the single-step program).
    Per-step semantics are unchanged; ``on_step``/``collect_outputs``
    still see one entry per microbatch (unstacked on the host).  The
    unstacked entries are real slices, not views: jax.Array indexing
    dispatches an XLA slice producing an independent buffer, so
    retaining ``worker_outputs`` does NOT pin the (K, ...) scan output
    alive (verified empirically — a retained ``x[0]`` of a 256 MiB
    stack leaves 4 MiB live).
    ``state_callback`` needs the live table BETWEEN steps, which a scan
    cannot surface — combining it with ``steps_per_call > 1`` raises.

    ``group_callback(first_step_idx, n_steps, table, state, outs)`` is
    the GROUP-granular sibling: it fires once per jitted dispatch (any
    ``steps_per_call``) with the live (donated-next-dispatch)
    table/state and the dispatch's RAW output — the single step's
    ``out`` when ``n_steps == 1``, the (K, ...)-stacked scan output
    otherwise (no forced host unstacking; finiteness checks and other
    whole-group reductions work on either form).  This is what lets the
    StreamingDriver run with ``steps_per_call > 1``: checkpoint / NaN /
    metrics cadence rounds up to dispatch boundaries — the honest
    granularity, since between scanned steps there is no host-visible
    table at all.

    ``steps`` is the pair :func:`jit_train_steps` built for this logic,
    this store's spec and this ``steps_per_call``, kept by a caller that
    loops more than once; by default the call builds its own.

    ``tracer`` (the StreamingDriver hands its own; the default records
    nothing) gets two spans a dispatch on the calling thread, never
    overlapping: ``train.batch_wait`` while this loop is blocked on
    ``data`` for the next batch, and ``train.pull_compute_push`` round
    the batch's ``device_put`` and the jitted call — the host INSIDE the
    dispatch, which includes the time the runtime's cap on programs in
    flight holds it.  With an enabled tracer that second span also carries
    the pipeline's depth as its ``args``, ``{"inflight": n, "ready_age_s":
    a}``: the dispatches whose outputs are not yet known to be ready, this
    one included, and how old the newest one seen ready was when it was
    (``training/metrics.InFlight``, polled right after the jitted call
    returns; no span is added for it).  ``inflight`` is the caller's own
    books where it wants to read them too (the StreamingDriver's gauges);
    by default the call keeps its own.  Under ``NULL_TRACER`` none of this
    runs.
    """
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    spec = store.spec
    mesh = mesh or spec.mesh
    if steps_per_call < 1:
        raise ValueError(f"steps_per_call={steps_per_call}: must be >= 1")
    if steps_per_call > 1 and state_callback is not None:
        raise ValueError(
            "steps_per_call > 1 cannot surface the live table between "
            "steps; use steps_per_call=1 with state_callback (the "
            "StreamingDriver's checkpoint/metrics hook needs per-step "
            "access)"
        )

    step, scan_step = (
        steps
        if steps is not None
        else jit_train_steps(worker_logic, spec, steps_per_call)
    )
    # The jitted step donates (table, state); unless the caller handed
    # them over (`owns_inputs`), start from copies so the caller's store
    # (and any restored state they still hold) stays valid — the same
    # contract transform_dense gives (dense.py).  A fresh init_state has
    # no outside owner, so only restored state is copied.
    keep = (lambda x: x) if owns_inputs else jnp_copy
    state = (
        jax.tree.map(keep, initial_state)
        if initial_state is not None
        else worker_logic.init_state(rng)
    )

    batch_sharding = on_mesh = None
    if worker_count(mesh, dp_axis) > 1:
        batch_sharding = NamedSharding(mesh, PartitionSpec(dp_axis))
        on_mesh = set(mesh.devices.flat)

    # the scanned program consumes (K, batch, ...) leaves: the dp shard
    # moves to axis 1 (axis 0 is scan time, resident on every device)
    scan_sharding = (
        scan_group_sharding(batch_sharding) if steps_per_call > 1 else None
    )

    # committed from the first dispatch on, as the step's own outputs are
    # (a batch staged on a device commits them): an uncommitted table or
    # state makes the first dispatch a program of its own, lowered and
    # loaded once more (no copy: the array keeps its buffers; the ledger's
    # `setup.commit` holds that claim)
    with setup_span("commit"):
        table, commit = _committed_where(
            jax.tree.map(keep, store.table), mesh)
        state = jax.tree.map(commit, state)
    worker_outputs: List[Any] = []
    step_idx = 0
    if not tracer.enabled:
        inflight = None
    elif inflight is None:
        inflight = InFlight()

    def to_workers(x):
        # host arrays go to the workers a lane block each; what already
        # lies on the mesh's chips (a staged pool) is taken where it lies,
        # and the step slices it itself (`make_train_step`)
        if isinstance(x, jax.Array) and x.sharding.device_set == on_mesh:
            return x
        return jax.device_put(x, batch_sharding)

    def _run_one(table, state, batch, step_idx):
        with tracer.span("pull_compute_push", component="train") as span:
            if batch_sharding is not None:
                batch = jax.tree.map(to_workers, batch)
            table, state, out = step(table, state, batch)
            if inflight is not None:
                span.args = inflight.dispatched(out)
        if on_step is not None:
            on_step(step_idx, out)
        if state_callback is not None:
            state_callback(step_idx, table, state, out)
        if group_callback is not None:
            group_callback(step_idx, 1, table, state, out)
        if collect_outputs:
            worker_outputs.append(out)
        return table, state

    def _run_group(table, state, group, first_idx):
        with tracer.span("pull_compute_push", component="train") as span:
            stacked = stack_group(group, scan_sharding)
            table, state, outs = scan_step(table, state, stacked)
            if inflight is not None:
                span.args = inflight.dispatched(outs)
        if on_step is not None or collect_outputs:
            for i in range(len(group)):
                out_i = jax.tree.map(lambda x: x[i], outs)
                if on_step is not None:
                    on_step(first_idx + i, out_i)
                if collect_outputs:
                    worker_outputs.append(out_i)
        if group_callback is not None:
            # raw stacked outs — whole-group reductions (finiteness) are
            # cheaper on the stack than on K unstacked slices
            group_callback(first_idx, len(group), table, state, outs)
        return table, state

    group: List[Any] = []
    batches = iter(data)
    router = worker_logic.key_router(tracer=tracer)
    if router is not None:
        # keyed workers: every record to the lane block of the worker that
        # owns its key (what arrives keyed passes through as it is)
        batches = router.route(batches)
    while True:
        with tracer.span("batch_wait", component="train"):
            batch = next(batches, _END)
        if batch is _END:
            break
        if skip_batches > 0:
            skip_batches -= 1
            step_idx += 1
            continue
        if steps_per_call == 1:
            table, state = _run_one(table, state, batch, step_idx)
            step_idx += 1
            continue
        group.append(batch)
        if len(group) == steps_per_call:
            table, state = _run_group(table, state, group, step_idx)
            step_idx += len(group)
            group = []
    # trailing group shorter than K: the single-step program (a second
    # compile only when a tail exists) — never a ragged-K recompile
    for batch in group:
        table, state = _run_one(table, state, batch, step_idx)
        step_idx += 1

    if inflight is not None:
        # the loop has ended: its books hold no output past it
        inflight.pending.clear()
    final_store = spec.store(table)
    server_outputs: List[Any] = []
    if dump_model:
        # close()-time model flush (reference §3.5): emit the final table.
        server_outputs.append(final_store.dump())
    finish = worker_logic.finish(state)
    if finish is not None:
        worker_outputs.append(finish)
    return TransformResult(
        worker_outputs=worker_outputs,
        server_outputs=server_outputs,
        store=final_store,
        worker_state=state,
    )


# ---------------------------------------------------------------------------
# The public overload family.
# ---------------------------------------------------------------------------


def transform(
    data: Iterable,
    worker_logic: Union[WorkerLogic, Callable[[], WorkerLogic], BatchedWorkerLogic],
    ps_logic: Union[
        ParameterServerLogic,
        Callable[[], ParameterServerLogic],
        ShardedParamStore,
        None,
    ] = None,
    *,
    param_init: Optional[Callable[[int], Any]] = None,
    param_update: Optional[Callable[[Any, Any], Any]] = None,
    worker_parallelism: int = 1,
    ps_parallelism: int = 1,
    iteration_wait_time: Optional[float] = None,  # accepted for parity; unused
    partitioner: Optional[Callable[[Any, int], int]] = None,
    input_window: Optional[int] = None,
    client_sender=None,  # SenderPolicy: client→PS combination batching
    ps_sender=None,  # SenderPolicy: PS→worker combination batching
    **batched_kwargs,
) -> TransformResult:
    """Wire ``data`` + worker logic + server logic into a PS job.

    Overloads (mirroring ``FlinkParameterServer.transform``):

    * ``transform(data, worker, param_init=f, param_update=g, ...)`` —
      simple keyed-store server (the reference's ``SimplePSLogic`` overload).
    * ``transform(data, worker, ps_logic, ...)`` — fully custom server
      logic (event API).
    * ``transform(batches, batched_worker, sharded_store, ...)`` — the
      compiled TPU path.

    ``iteration_wait_time`` is accepted for signature parity with the
    reference but ignored: termination is explicit (input exhaustion), not a
    silence timeout.  ``client_sender``/``ps_sender`` (combination
    batching) apply to the event backend only — on the batched TPU path
    the microbatch itself is the combination buffer, so they are ignored.
    """
    if isinstance(worker_logic, BatchedWorkerLogic):
        if not isinstance(ps_logic, (ShardedParamStore, StoreGroup)):
            raise TypeError(
                "batched worker logic requires a ShardedParamStore server "
                "(or a StoreGroup of them)"
            )
        return transform_batched(data, worker_logic, ps_logic, **batched_kwargs)

    if ps_logic is None:
        if param_init is None or param_update is None:
            raise TypeError(
                "provide either ps_logic or (param_init, param_update)"
            )
        ps_logic = lambda: SimplePSLogic(param_init, param_update)  # noqa: E731

    workers = _instances(worker_logic, worker_parallelism, "worker")
    servers = _instances(ps_logic, ps_parallelism, "ps")
    runtime = _LocalRuntime(
        workers,
        servers,
        partitioner,
        input_window if input_window is not None else worker_parallelism,
        client_sender=client_sender,
        ps_sender=ps_sender,
    )
    runtime.run(data)
    return TransformResult(
        worker_outputs=runtime.worker_outputs,
        server_outputs=runtime.server_outputs,
    )


def transform_with_model_load(
    model: Iterable[Tuple[int, Any]],
    data: Iterable,
    worker_logic,
    ps_logic=None,
    **kwargs,
) -> TransformResult:
    """Seed the server from an initial ``(id, value)`` stream before
    training — the reference's ``transformWithModelLoad`` overload
    (SURVEY.md §2 #1, §5 "Checkpoint / resume").

    For the batched path pass a ``ShardedParamStore`` built with
    ``ShardedParamStore.from_values`` instead — this wrapper handles the
    event API.
    """
    model = list(model)

    if isinstance(ps_logic, ShardedParamStore):
        table = ps_logic.table
        ids = np.array([int(i) for i, _ in model])
        vals = jnp.asarray(np.stack([np.asarray(v) for _, v in model]))
        from .store import _physical_rows

        table = table.at[ids].set(
            _physical_rows(ps_logic.spec, vals.astype(table.dtype))
        )
        seeded = ShardedParamStore(ps_logic.spec, table)
        return transform(data, worker_logic, seeded, **kwargs)

    if ps_logic is None:
        param_init = kwargs.pop("param_init", None)
        param_update = kwargs.pop("param_update", None)
        if param_init is None or param_update is None:
            raise TypeError(
                "provide either ps_logic or (param_init, param_update)"
            )
        ps_logic = lambda: SimplePSLogic(param_init, param_update)  # noqa: E731

    # Event path: deliver the model stream as pushes before training data.
    class _Seed(ParameterServer):
        def __init__(self):
            self.outs = []

        def answer_pull(self, *a):  # pragma: no cover - seeds never pull
            raise AssertionError("model-load phase must not answer pulls")

        def output(self, o):
            self.outs.append(o)

    kwargs2 = dict(kwargs)
    ps_par = kwargs2.get("ps_parallelism", 1)
    servers = _instances(ps_logic, ps_par, "ps")
    for pid, value in model:
        target = servers[stable_route_hash(pid) % ps_par]
        if isinstance(target, SimplePSLogic):
            # Model load *sets* the stored value (it is not a delta).
            target.store[pid] = value
        else:
            target.on_push_recv(pid, value, _Seed())

    def server_factory_iter():
        for s in servers:
            yield s

    it = server_factory_iter()
    kwargs2["ps_parallelism"] = ps_par
    return transform(data, worker_logic, lambda: next(it), **kwargs2)


__all__ = [
    "TransformResult",
    "transform",
    "transform_batched",
    "transform_with_model_load",
    "make_train_step",
]
