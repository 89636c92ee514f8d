"""StreamingDriver — the job runtime around the transform loop.

Reference parity: in the reference, Flink provides the operational
envelope — sources feed the iteration, the web UI shows throughput,
checkpointing (such as it is) and shutdown are runtime concerns
(SURVEY.md §1 L1, §5).  This driver is that envelope for the TPU
framework, layered on :func:`..core.transform.transform_batched` (one
loop implementation, hooked — not duplicated):

  * step metrics (updates/sec, the dispatch cadence, the dispatches in
    flight, and what held the host in a long gap between two of them),
  * periodic orbax checkpoints + resume (PS-aware, which Flink iterative
    jobs never had — SURVEY.md §5), with cursor fast-forward,
  * optional profiler tracing of steady-state steps,
  * close-time model dump (the reference's §3.5 flush), host prefetch.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.batched import BatchedWorkerLogic
from ..core.store import ShardedParamStore, publish_counts
from ..core.transform import (
    TransformResult,
    jit_train_steps,
    transform_batched,
)
from ..data.streams import prefetch as prefetch_iter
from ..telemetry import compile_ledger
from ..telemetry.registry import get_registry
from ..telemetry.spans import NULL_TRACER, get_tracer
from . import checkpoint as ckpt
from .metrics import InFlight, StepMetrics
from .tracing import profile_trace


class TrainingDiverged(RuntimeError):
    """Raised by the driver's NaN guard (DriverConfig.nan_check_every).

    ``step`` carries the dispatch-boundary step the guard fired at — the
    supervisor (``resilience/recovery.py``) needs it to size the input
    window it must skip (the window *caused* the divergence; replaying
    it would re-diverge deterministically)."""

    def __init__(self, message: str, step: int = 0):
        super().__init__(message)
        self.step = step


def _is_live(*trees) -> bool:
    """No leaf of the given pytrees is a deleted (donated) array."""
    return not any(
        leaf.is_deleted()
        for tree in trees
        for leaf in jax.tree.leaves(tree)
        if isinstance(leaf, jax.Array)
    )


def _all_finite(*trees) -> jax.Array:
    """Single fused device-side finiteness reduction over every floating
    leaf of the given pytrees (one host transfer at the bool() call)."""
    ok = jnp.asarray(True)
    for tree in trees:
        for leaf in jax.tree.leaves(tree):
            if hasattr(leaf, "dtype") and jnp.issubdtype(
                leaf.dtype, jnp.floating
            ):
                ok = jnp.logical_and(ok, jnp.isfinite(leaf).all())
    return ok


# One output of a dispatch, read over the steps a scanned dispatch stacked:
# a count is their sum, a constant of the step their maximum.
def _total(x) -> float:
    return float(np.sum(np.asarray(x)))


def _peak(x) -> float:
    return float(np.max(np.asarray(x)))


@dataclasses.dataclass
class DriverConfig:
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 0  # steps; 0 = only on close
    metrics_every: int = 0  # steps between metric emissions; 0 = off.
    # Metrics force a per-step device sync (accurate latency); with
    # metrics_every=0 the loop free-runs pipelined (bench mode).
    profile_dir: Optional[str] = None
    # (after_step, last_step): the trace is entered after relative step
    # `after_step` completes and covers steps after_step+1 .. last_step.
    profile_steps: tuple = (10, 13)
    prefetch: int = 2
    dump_model: bool = True
    # Failure detection (SURVEY.md §5): every N steps, verify the step
    # outputs are finite; on NaN/inf raise TrainingDiverged — with a
    # checkpoint_dir configured the driver rolls back to the last durable
    # checkpoint (the crash-recovery path), turning silent divergence
    # into a recoverable fault.  0 = off.
    nan_check_every: int = 0
    # Periodic saves via orbax AsyncCheckpointer: save() returns after the
    # device→host copy, disk writes overlap the next training steps.
    async_checkpoints: bool = False
    # K microbatches per jitted dispatch (core/transform lax.scan path):
    # one host round trip per K steps; amortises host dispatch, not
    # measured on the chip (ROADMAP S3).  The driver runs its envelope
    # at DISPATCH granularity, the honest unit — between scanned steps
    # there is no
    # host-visible table: checkpoint/nan/metrics cadences round UP to
    # the next group boundary (a cadence of 10 with K=4 fires at steps
    # 12, 20, 24, ...), the metrics' interval percentiles time dispatches
    # (K steps each), and the profile window covers whole dispatches.
    steps_per_call: int = 1
    # Preemption-safe shutdown (the reference's stop-with-savepoint
    # analogue; Flink jobs drain + savepoint on SIGTERM): on any of
    # these signals the driver stops feeding batches, finishes the
    # in-flight microbatches, checkpoints, and run() returns the partial
    # result — a later resume() + run() continues from the cursor.
    # E.g. (signal.SIGTERM,) for k8s/TPU-pod eviction.  Handlers are
    # installed only for the duration of run() (main thread only) and
    # the previous handlers are restored after.
    stop_signals: tuple = ()
    # Write-ahead update log (resilience/wal.py): every microbatch
    # consumed from the source is appended (on the ingest edge, BEFORE
    # the step applies it) and each checkpoint save truncates the log —
    # recovery replays checkpoint + tail instead of losing the window.
    # None = off (zero cost).
    wal_dir: Optional[str] = None
    wal_segment_bytes: int = 16 << 20
    wal_fsync_every: int = 1  # records between fsyncs; 0 = never
    wal_max_bytes: Optional[int] = None  # soft budget (warns when over)
    # Unified telemetry plane (telemetry/): step/event counters, the
    # dispatch-interval histogram and live gauges publish to the
    # process-wide MetricsRegistry (scrapeable via TelemetryServer
    # while the run is live), and the host-side phases — ingest wait,
    # WAL append, the pull/compute/push dispatch, snapshot publish,
    # checkpoint save — are recorded as wall-clock spans on the default
    # SpanTracer (Chrome-trace exportable).  False = zero-touch (the
    # overhead A/B lever; tests/test_telemetry.py guards the cost).
    telemetry: bool = True


class StreamingDriver:
    """Run a PS job: ``driver = StreamingDriver(logic, store); driver.run(data)``.

    Resume semantics: after :meth:`resume`, the next :meth:`run` call
    fast-forwards its input iterator by the restored step cursor — i.e.
    re-feed the SAME logical stream from the beginning and the driver
    skips what was already consumed.  Pass ``fast_forward=False`` to feed
    a fresh stream instead.

    Who owns the table: the driver does, from construction on.  ``run``
    hands the table and the worker state it holds to the loop, which
    donates them to its first dispatch (one table alive for the length of
    the run, not two), and takes the loop's final ones back, so the
    ``store`` passed to the constructor is deleted by the first ``run``:
    read ``driver.store`` (or ``result.store``) afterwards.  During a run
    ``driver.store`` carries the spec and no table (``table is None``);
    hooks, publishes and checkpoints get the live table as an argument.
    After a run that raised it holds the last table a dispatch left
    (or, with a ``checkpoint_dir``, the last durable checkpoint's); if the
    dispatch itself failed and no checkpoint exists, none, and ``run`` and
    ``save`` say so.
    """

    def __init__(
        self,
        logic: BatchedWorkerLogic,
        store: ShardedParamStore,
        *,
        config: Optional[DriverConfig] = None,
        rng: Optional[jax.Array] = None,
        metrics_sink=None,
        health=None,
        registry=None,
    ):
        self.logic = logic
        self.store = store
        self.config = config if config is not None else DriverConfig()
        self.rng = rng if rng is not None else jax.random.PRNGKey(0)
        self.metrics_sink = metrics_sink
        self.metrics: Optional[StepMetrics] = None
        # telemetry plane: an explicit registry always wins; otherwise
        # the process-wide default when config.telemetry, else nothing.
        # The tracer mirrors the same switch (a disabled tracer's
        # span() is a shared no-op — call sites stay unconditional).
        if registry is not None:
            self.registry = registry
        else:
            self.registry = get_registry() if self.config.telemetry else None
        self.tracer = get_tracer() if self.config.telemetry else NULL_TRACER
        # the dispatches in flight: the loop writes the books where it
        # dispatches, StepMetrics' gauges read them (training/metrics.py)
        self._inflight = InFlight() if self.tracer.enabled else None
        if self.registry is not None:
            # which layout the store resolved to (fixed when it was built:
            # a stored value, so the registry holds no driver and no table)
            # (a group of stores: one gauge a store, labelled by its name)
            for labels, member in store.spec.named():
                self.registry.gauge(
                    "store_layout_packed", component="train", **labels
                ).set(member.layout == "packed")
            # what the ring has dropped since its last clear(): above 0 a
            # reader of "the run's spans" is reading their tail
            tracer = self.tracer
            self.registry.gauge(
                "tracer_spans_dropped", component="train",
                fn=lambda: tracer.dropped,
            )
        # spans open on the profiler's clock too: this code owns a device
        self.tracer.annotate_with(jax.profiler.TraceAnnotation)
        if self.config.telemetry:
            # what this job traces, lowers, compiles and loads is counted by
            # program from here at the latest (a caller that enabled the
            # compile cache is counting already: this does nothing then)
            compile_ledger.install()
        self.step_idx = 0
        self._state = None
        # the jitted programs of (logic, spec), built by the first `run`
        # and kept: a second run finds its step traced, lowered and loaded
        self._steps = None
        self._pending_skip = 0
        self._stop_requested = False
        self._serving = None
        # resilience wiring: an optional HealthMonitor beaten from the
        # ingest and train threads (resilience/health.py), user group
        # hooks (chaos injection and friends), and the update WAL
        self.health = health
        self._group_hooks = []
        self._last_ckpt_step: Optional[int] = None
        self._wal = None
        if self.config.wal_dir is not None:
            from ..resilience.wal import UpdateWAL

            self._wal = UpdateWAL(
                self.config.wal_dir,
                segment_bytes=self.config.wal_segment_bytes,
                fsync_every=self.config.wal_fsync_every,
                max_bytes=self.config.wal_max_bytes,
            )
        self._ckpt_mgr: Optional[ckpt.JobCheckpointManager] = None
        if self.config.checkpoint_dir is not None:
            self._ckpt_mgr = ckpt.JobCheckpointManager(
                self.config.checkpoint_dir,
                use_async=self.config.async_checkpoints,
            )

    # -- checkpoint/resume -------------------------------------------------
    # Step-directory checkpoints via orbax CheckpointManager: each save
    # commits atomically to its own step dir (a crash mid-write can never
    # destroy the previous durable checkpoint), old steps are pruned, and
    # async mode overlaps disk writes with training.

    def _require_table(self) -> None:
        if self.store.table is None:
            raise RuntimeError(
                "this driver holds no table: a dispatch that failed consumed "
                "the one it was handed; resume() from a checkpoint or build "
                "a new driver"
            )

    def save(self) -> None:
        if self._ckpt_mgr is None:
            return
        self._require_table()
        # force: an explicit save must land even if this step was already
        # checkpointed (orbax otherwise silently skips duplicate steps)
        with self.tracer.span("checkpoint", component="train"):
            self._ckpt_mgr.save(
                self.step_idx, self.store, self._state, force=True
            )
            self._ckpt_mgr.wait()  # the explicit save() contract is durable
        if self.registry is not None:
            self.registry.counter(
                "checkpoints_total", component="train"
            ).inc()
        if self._wal is not None:
            # same one-checkpoint lag as the periodic path: the last
            # interval's WAL stays as the corrupt-latest fallback's
            # replay source (it is one interval of bytes — cheap).
            # Anchor on the RETAINED steps, not the in-memory tracker:
            # a close-time save re-saving the final periodic step would
            # otherwise truncate through itself and strip the fallback's
            # coverage.  (all_steps waits, but so did the save above.)
            steps = self._ckpt_mgr.all_steps()
            if len(steps) >= 2:
                self._wal.truncate_through(steps[-2])
        self._last_ckpt_step = self.step_idx

    @property
    def wal(self):
        """The driver's UpdateWAL (None unless config.wal_dir is set) —
        the supervisor's replay handle."""
        return self._wal

    def add_group_hook(self, hook) -> None:
        """Register ``hook(global_step, n_steps, table, state, outs)``,
        called once per jitted dispatch on the training thread, after
        the dispatch's updates were applied and before the checkpoint /
        NaN cadences run.  This is the injection point chaos testing
        uses (resilience/chaos.py) and the place operator-side
        instrumentation hangs without forking the loop."""
        self._group_hooks.append(hook)

    def request_stop(self) -> None:
        """Programmatic preemption: the current ``run`` stops feeding
        batches, drains in-flight microbatches, checkpoints, and returns
        its partial result (same path as ``stop_signals``)."""
        self._stop_requested = True

    # -- train-while-serve -------------------------------------------------
    def serve_with(self, service=None, **service_kwargs):
        """Attach an online-serving service (``serving/``): the driver
        publishes table snapshots at the service's ``publish_every``
        dispatch cadence — worker state riding along as the query-side
        user vectors — so top-K queries are answered mid-training
        without ever touching the live (donated) buffers.

        Pass a prebuilt :class:`~..serving.ServingService`, or kwargs
        for :meth:`ServingService.for_spec <..serving.ServingService.for_spec>`
        (``publish_every=``, ``max_batch=``, ``max_queue=``, ...).
        Returns the service — ``service.client()`` is the query handle;
        serving starts at ``run()`` entry (the pre-training table is
        published immediately) and keeps answering from the final
        snapshot after ``run()`` returns.  With ``metrics_every`` set,
        serving metrics lines are emitted to ``metrics_sink`` alongside
        the training lines."""
        if service is None:
            from ..serving import ServingService

            service = ServingService.for_spec(
                self.store.spec, **service_kwargs
            )
        elif service_kwargs:
            raise ValueError(
                "pass either a prebuilt service or for_spec kwargs, not both"
            )
        if self.health is not None:
            # one monitor spans the stack: ingest + train beats come
            # from this driver, serving-dispatch beats from the service
            service.attach_health(self.health)
        # one tracer spans the stack too: the service's publish, batch and
        # queue-wait spans land beside this driver's
        service.attach_tracer(self.tracer)
        self._serving = service
        return service

    def resume(self) -> bool:
        """Restore (store, worker state, step cursor) from the latest
        durable checkpoint if one exists; returns True on restore.  See
        class docstring for how the cursor interacts with the next
        ``run``."""
        if self._ckpt_mgr is None:
            return False
        restored = self._ckpt_mgr.restore_latest(self.store.spec)
        if restored is None:
            return False
        self.store, self._state, meta = restored
        self.step_idx = int(meta.get("step", 0))
        self._pending_skip = self.step_idx
        return True

    # -- the loop ----------------------------------------------------------
    def _jitted_steps(self, spec):
        """This driver's jitted step (and scanned step), one pair for its
        logic and ``spec``: a fresh ``jax.jit`` a ``run`` would trace and
        lower the step again on every run after the first."""
        if self._steps is None or self._steps[0] != spec:
            self._steps = (
                spec,
                jit_train_steps(self.logic, spec, self.config.steps_per_call),
            )
        return self._steps[1]

    def _publish_step_counts(self, outs) -> None:
        """What the step counted on the device in the dispatch ``outs``
        came from, as gauges: each count is published by whoever made it,
        the store its ``ps_*`` outputs (``core/store.publish_counts``), the
        logic its own (``BatchedWorkerLogic.publish_counts``).  A fetch of
        a few scalars, made only where the outputs are fetched anyway: at
        the metrics cadence, which syncs the step, and once after the loop
        has ended."""
        if self.registry is None or not isinstance(outs, dict):
            return
        publish_counts(outs, self.registry, _total, _peak)
        self.logic.publish_counts(outs, self.registry, _total, _peak)

    def run(
        self,
        data: Iterable,
        collect_outputs: bool = False,
        fast_forward: bool = True,
    ) -> TransformResult:
        """Train over ``data``.  For the length of the call the loop owns
        the table and the worker state this driver held (they are donated,
        not copied); ``self.store`` carries the spec and no table until the
        loop's final ones come back (class docstring)."""
        self._require_table()
        cfg = self.config
        spec = self.store.spec
        start_step = self.step_idx
        skip = self._pending_skip if fast_forward else 0
        self._pending_skip = 0
        self._stop_requested = False  # a fresh run clears a prior stop
        if self._serving is not None:
            # serving is live from step 0: publish the pre-training
            # table (queries that need worker state answer after the
            # first mid-training publish carries it)
            self._serving.on_train_start(
                self.store, self.step_idx, state=self._state
            )

        import collections

        event_counts: "collections.deque" = collections.deque()

        tracer = self.tracer
        c_ingest = c_wal = None
        if self.registry is not None:
            c_ingest = self.registry.counter(
                "ingest_batches_total", component="ingest"
            )
            c_wal = self.registry.counter(
                "wal_appends_total", component="ingest"
            )

        def counting(source, skipped):
            src = iter(source)
            n = 0
            while True:
                if self._stop_requested:
                    # preemption: stop feeding; the batches already in
                    # the prefetch queue drain, then the loop closes
                    # normally (close-time save below persists the state)
                    return
                # the span makes a frozen source VISIBLE on the host
                # timeline: a long `ingest` bar next to idle dispatches
                # is the straggler study's signature stall shape
                with tracer.span("ingest", component="ingest"):
                    try:
                        b = next(src)
                    except StopIteration:
                        return
                if n >= skipped:  # skipped batches never reach the callback
                    if "mask" in b:
                        event_counts.append(int(np.asarray(b["mask"]).sum()))
                    else:
                        event_counts.append(len(jax.tree.leaves(b)[0]))
                    if c_ingest is not None:
                        c_ingest.inc()
                    if self._wal is not None:
                        # WRITE-AHEAD: durable before the step applies
                        # it (this runs on the ingest/prefetch thread,
                        # ahead of the dispatch that consumes the
                        # batch).  Step numbering matches group_callback
                        # below; appends are idempotent by step, so a
                        # recovery replay re-feeding logged batches
                        # through this same path is a no-op.
                        with tracer.span("wal_append", component="ingest"):
                            self._wal.append(
                                start_step - skip + n, 1,
                                jax.tree.map(np.asarray, b),
                            )
                        if c_wal is not None:
                            c_wal.inc()
                    if self.health is not None:
                        self.health.beat("ingest")
                n += 1
                yield b

        source = iter(data)
        router = self.logic.key_router(registry=self.registry, tracer=tracer)
        if router is not None:
            # the keyed shuffle, on the ingest thread: what is counted,
            # logged ahead and dispatched below is the keyed microbatch
            source = router.route(source)
        it = counting(source, skip)
        if cfg.prefetch:
            it = prefetch_iter(it, cfg.prefetch)

        sync_steps = cfg.metrics_every > 0
        trace_ctx = {"cm": None}
        first_step_of_run = [True]

        def group_callback(first_idx, n_steps, table, state, outs):
            live[:] = table, state
            last_outs[0] = outs
            # One invocation per jitted DISPATCH (n_steps == 1 when
            # steps_per_call == 1 — then this is exactly the old
            # per-step state_callback; n_steps == K for scanned groups,
            # where cadences round up to the boundary: between scanned
            # steps there is no host-visible table to act on).
            if sync_steps:
                jax.block_until_ready(outs)
            prev_global = start_step - skip + first_idx
            global_step = prev_global + n_steps
            events = sum(
                event_counts.popleft() if event_counts else 0
                for _ in range(n_steps)
            )
            if self.metrics is None:
                self.metrics = StepMetrics(
                    events_per_step=events // max(1, n_steps),
                    registry=self.registry,
                    tracer=tracer,
                    inflight=self._inflight,
                )
            if first_step_of_run[0]:
                # this run's first dispatch start was never timestamped
                # (and any previous run's dangling step_start would fold
                # inter-run idle time into the latency window) — count,
                # don't time
                first_step_of_run[0] = False
                if cfg.telemetry:
                    # from here on a step built again stalls a warm
                    # stream: the ledger counts and names it
                    # (compiles_in_run_total)
                    books.enter_context(compile_ledger.get_ledger().warm_run(
                        s.__name__ for s in steps if s is not None
                    ))
                self.metrics.count_untimed(n_steps, events)
                self.metrics.step_start()
            else:
                # the interval percentiles time DISPATCHES (n_steps steps
                # each); totals still count steps and events exactly.  A
                # long gap is explained here, at the dispatch that ends it
                self.metrics.step_end(events, n_steps=n_steps)
            self.step_idx = global_step
            if self.health is not None:
                self.health.beat("train")
            if self._serving is not None:
                # snapshot publish (copy-on-publish, cadence-gated) runs
                # on THIS thread, so the copy is sequenced before the
                # next dispatch donates the table buffer; a publish that
                # copies records its own span (serving/snapshot.py)
                self._serving.on_dispatch(table, state, global_step)
            if self._group_hooks:
                # user/chaos hooks see the applied dispatch before the
                # checkpoint cadence runs — a hook that raises here
                # models the worst-case crash point (updates applied,
                # boundary's checkpoint not yet taken)
                with tracer.span("hooks", component="train"):
                    for hook in self._group_hooks:
                        hook(global_step, n_steps, table, state, outs)

            def crossed(every):
                # did (prev_global, global_step] cross a multiple of
                # `every`?  == the old `global_step % every == 0` when
                # n_steps == 1
                return every and (global_step // every) > (prev_global // every)

            if (
                cfg.profile_dir
                and trace_ctx["cm"] is None
                and not trace_ctx.get("done")
                and global_step - start_step >= cfg.profile_steps[0]
            ):
                trace_ctx["cm"] = profile_trace(cfg.profile_dir)
                trace_ctx["cm"].__enter__()
            elif (
                trace_ctx["cm"] is not None
                and global_step - start_step >= cfg.profile_steps[1]
            ):
                trace_ctx["cm"].__exit__(None, None, None)
                trace_ctx["cm"] = None
                trace_ctx["done"] = True
            is_ckpt_step = crossed(cfg.checkpoint_every)
            if crossed(cfg.nan_check_every) or (
                cfg.nan_check_every and is_ckpt_step
            ):
                # check table+state too (outputs may carry no floats), as
                # ONE fused device reduction + a single host transfer;
                # always check on checkpoint steps so a poisoned table is
                # never persisted as the "recovery" point.  `outs` may be
                # (K, ...)-stacked — the reduction covers every step.
                if not bool(_all_finite(outs, table, state)):
                    raise TrainingDiverged(
                        f"non-finite step output/params at step "
                        f"{global_step}",
                        step=global_step,
                    )
            if crossed(cfg.metrics_every):
                self._publish_step_counts(outs)
                self.metrics.emit(self.metrics_sink)
                if self._serving is not None:
                    self._serving.metrics.emit(self.metrics_sink)
            if is_ckpt_step:
                # Save straight from the live buffers WITHOUT stashing them
                # on self: the next jitted step donates (deletes) them, and
                # self.store must never hold a deleted array.  Both save
                # modes copy the data off-device before returning (the sync
                # path serializes fully; the async path returns after the
                # host copy and writes in the background), so donation is
                # safe either way.
                if self._ckpt_mgr is not None:
                    with tracer.span("checkpoint", component="train"):
                        self._ckpt_mgr.save(
                            global_step, spec.store(table), state,
                        )
                    if self.registry is not None:
                        self.registry.counter(
                            "checkpoints_total", component="train"
                        ).inc()
                    if self._wal is not None and self._last_ckpt_step is not None:
                        # Bound the WAL at the checkpoint cadence —
                        # lagging ONE checkpoint behind, deliberately:
                        # (a) an async save is still in flight here
                        # (truncating through it would wait() and
                        # de-async the loop; the previous one is durable
                        # because orbax serializes async saves), and
                        # (b) if the newest checkpoint proves corrupt at
                        # restore time, restore_latest falls back one
                        # step and the kept WAL interval still replays
                        # the difference — corrupt-latest stays lossless.
                        self._wal.truncate_through(self._last_ckpt_step)
                    self._last_ckpt_step = global_step

        prev_handlers = {}
        if cfg.stop_signals:
            import signal as _signal
            import threading

            def _request_stop(signum, frame):
                self._stop_requested = True

            if threading.current_thread() is threading.main_thread():
                try:
                    for s in cfg.stop_signals:
                        prev_handlers[s] = _signal.signal(s, _request_stop)
                except BaseException:
                    # partial install must not leak handlers past run()
                    for s, h in prev_handlers.items():
                        # None = prior handler installed from C (see the
                        # restore in the finally block below)
                        _signal.signal(
                            s, _signal.SIG_DFL if h is None else h
                        )
                    raise
            # non-main threads can't install handlers; the flag can still
            # be set externally via request_stop()

        # The loop owns table and state for the length of the run: it
        # donates the buffers this driver held, so no second table is
        # alive beside the one being trained (a table over half the
        # chip's memory could not run otherwise).  `self.store` keeps the
        # spec and NO table meanwhile, so it never holds a deleted array;
        # `live` names the buffers the last dispatch left, for the
        # handler below to take back if the run raises.
        handed, self.store = self.store, spec.store(None)
        handed_state, self._state = self._state, None
        live = [handed.table, handed_state]
        last_outs = [None]  # the newest dispatch's outputs, never synced here
        steps = self._jitted_steps(spec)
        books = contextlib.ExitStack()
        if cfg.telemetry:
            # what is traced, lowered, compiled or loaded while this run
            # lasts is a record on its tracer too (compile.*, setup.*)
            books.enter_context(
                compile_ledger.get_ledger().spans_to(self.tracer)
            )
            # ... and so is a garbage collection (host.gc): a gap between
            # two dispatches can then name it
            books.enter_context(self.tracer.gc_spans())

        try:
            result = transform_batched(
                it,
                self.logic,
                handed,
                rng=self.rng,
                collect_outputs=collect_outputs,
                dump_model=cfg.dump_model,
                group_callback=group_callback,
                initial_state=handed_state,
                skip_batches=skip,
                steps_per_call=cfg.steps_per_call,
                tracer=tracer,
                owns_inputs=True,
                steps=steps,
                inflight=self._inflight,
            )
        except BaseException:
            # Leave the driver usable: take back what the last dispatch
            # left (a hook or the source raised between dispatches, so
            # those buffers are live; a dispatch that itself failed may
            # have consumed them, and then the store keeps no table),
            # then reload the last durable checkpoint, if any.
            if _is_live(*live):
                self.store = spec.store(live[0])
                self._state = live[1]
            if self._ckpt_mgr is not None:
                self.resume()
            raise
        finally:
            books.close()
            if prev_handlers:
                import signal as _signal

                for s, h in prev_handlers.items():
                    # A prior handler installed from C reads back as
                    # None; signal.signal(s, None) raises TypeError and
                    # would crash a successful run at exit.  SIG_DFL is
                    # the closest restorable state (the C handler itself
                    # is unrecoverable from Python) and avoids leaking
                    # _request_stop — a closure over self — past run().
                    _signal.signal(s, _signal.SIG_DFL if h is None else h)
            if trace_ctx["cm"] is not None:
                trace_ctx["cm"].__exit__(None, None, None)

        self.store = result.store
        self._state = result.worker_state
        self._publish_step_counts(last_outs[0])
        if self._serving is not None:
            # close-time publish: post-run queries answer from the FINAL
            # table (the serve-path analogue of the §3.5 model flush)
            self._serving.on_dispatch(
                self.store.table, self._state, self.step_idx, force=True,
            )
        self.save()
        return result


__all__ = ["DriverConfig", "StreamingDriver", "TrainingDiverged"]
