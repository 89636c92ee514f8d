"""Set-up on the program's books: what JAX traced, lowered, compiled and
loaded, by program, and the seconds set-up's own work took.

The window of a run is covered by ``spans.py`` and the profiler; what comes
before it (a job's set-up: 12-19 s warm, 40-150 s when the step compiles)
was timed only from outside.  This module is the program's own record of it.

**The compile ledger.**  JAX reports every trace (``jaxpr_trace_duration``),
lowering (``jaxpr_to_mlir_module_duration``) and backend compile
(``backend_compile_duration``: a load from the persistent cache is one too)
to ``jax.monitoring`` listeners, with the function's name and, to the
time-span listeners, its start and end on ``time.time()``.
:func:`install` registers this process's listeners, once.  Each event (of
traces the outermost only: a ``jnp`` function called inside a step's trace,
or by a lowering rule, is traced inside that stage and is no program) becomes

  * a count and its seconds, by program: ``jit_traces_total`` /
    ``jit_trace_seconds_total``, ``jit_lowerings_total`` /
    ``jit_lower_seconds_total``, ``xla_compiles_total`` /
    ``xla_compile_seconds_total`` (``component=compile, program=``);
  * an entry of the ledger's own bounded list (:func:`events`), its stamps
    moved from the wall clock onto the tracers' ``perf_counter`` base
    (``SpanTracer.wall_clock_anchor``).

A miss of the persistent cache is counted and listed beside them
(``compile_cache_misses_total``; JAX names no program there).

**Set-up's own work.**  :func:`setup_span` times the caller's block, lists
it as a ``setup`` event and adds its seconds to a counter the caller hands
it (``core/store.py``: the table's placement; ``ops/row_update.py``: what the
first kernel's trace waited for the import; ``core/transform.py``: the commit).

Counters and list are the process's and always on.  **Span records are a
telemetry-on run's**: for its length ``StreamingDriver.run`` hands the ledger
its tracer (:meth:`CompileLedger.spans_to`), and every event becomes a
``tracer.record`` there (``compile.trace.<program>``, ``setup.store_place``,
... : host clock only, no profiler annotation, since JAX's events arrive
after the fact), so an export shows the dispatch's
``train.pull_compute_push`` CONTAINING the trace, lowering and load it paid
for.  What was listed since the last such run (the store's placement, the
set-up's compiles) is recorded as the run is entered.  A driver with
``telemetry=False`` hands nothing over, and no tracer hears of its compiles.

**Which step recompiled.**  ``StreamingDriver.run`` tells the ledger when it
is past its first completed dispatch, and the names of the programs it
dispatches (:meth:`CompileLedger.warm_run`).  A backend compile OF ONE OF
THOSE that arrives meanwhile (the step, for a batch whose shape changed)
counts in ``compiles_in_run_total{program=}`` and is warned of once a
program.  Whatever else is built while the run is warm (a hook's helper for
the first time, a query bucket, ``convert_element_type`` for one more shape)
is new work and stays silent: JAX names the function and nothing more, and
generic names recur in every sound run.

Nothing here runs on a dispatch that compiles nothing: listeners are called
only where JAX traces, lowers, compiles or loads.
"""
from __future__ import annotations

import contextlib
import logging
import re
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from .registry import Counter, get_registry
from .spans import SpanTracer, get_tracer

logger = logging.getLogger(__name__)

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

STAGES = {TRACE_EVENT: "trace", LOWER_EVENT: "lower", BACKEND_EVENT: "backend"}

CAPACITY = 8192  # events the list keeps: a set-up has tens to hundreds

_MODULE_NAME = re.compile(r"^[a-z_]+\((.+)\)$")  # jit(step), pmap(body)


def program_of(stage: str, fun_name: Any) -> str:
    """JAX's ``fun_name`` as one label over the three stages: a trace names
    the function (``step``), a lowering and a compile the module
    (``jit(step)``)."""
    name = str(fun_name) if fun_name else "unknown"
    if stage != "trace":
        module = _MODULE_NAME.match(name)
        if module:
            return module.group(1)
    return name


def _record(tracer: SpanTracer, event: tuple) -> None:
    _, stage, program, t0, t1 = event
    if stage == "setup":
        tracer.record(program, t0, t1, "setup")
    elif stage in ("trace", "lower", "backend"):
        tracer.record(f"{stage}.{program}", t0, t1, "compile")


class CompileLedger:
    """The listeners' state: the newest ``CAPACITY`` events, the tracers of
    the telemetry-on runs that are open, and the programs of those past
    their first dispatch.  Counters go to the process's default registry as
    it is when an event arrives."""

    def __init__(self):
        self._lock = threading.Lock()
        self._events: deque = deque(maxlen=CAPACITY)  # (n, stage, program, t0, t1)
        self._listed = 0  # events ever listed: the newest one's ``n``
        self._handed = 0  # ... of them, how many some tracer has been handed
        self._sinks: List[SpanTracer] = []
        self._warm: List[frozenset] = []  # a warm run's programs, each
        self._warned: set = set()
        self._open = threading.local()  # .depth: stages open on a thread

    def note(self, stage: str, program: str, t0: float, t1: float) -> None:
        """List one event (stamps on the tracers' ``perf_counter`` base) and
        record it on the tracer of every telemetry-on run that is open."""
        with self._lock:
            self._listed += 1
            event = (self._listed, stage, program, t0, t1)
            self._events.append(event)
            sinks = list(dict.fromkeys(self._sinks))  # each tracer once
            if sinks:
                self._handed = self._listed
        for tracer in sinks:
            _record(tracer, event)

    # -- what JAX calls ----------------------------------------------------
    def on_scalar(self, event: str, value: float, **kwargs) -> None:
        """JAX reports a stage's START as a scalar.  A jitted function called
        while another is being traced (every ``jnp`` function inside a step),
        or by a lowering rule (threefry's), is traced inside that stage: only
        the outermost is a program."""
        if event in (TRACE_EVENT, LOWER_EVENT):
            self._open.depth = getattr(self._open, "depth", 0) + 1

    def on_time_span(
        self, event: str, start_time: float, end_time: float, **kwargs
    ) -> None:
        stage = STAGES.get(event)
        if stage is None:
            return
        if stage != "backend":
            depth = max(0, getattr(self._open, "depth", 1) - 1)
            self._open.depth = depth
            if depth:
                return  # part of the trace or lowering it was called from
        program = program_of(stage, kwargs.get("fun_name"))
        seconds = max(0.0, float(end_time) - float(start_time))
        registry = get_registry()
        if stage == "trace":
            registry.counter(
                "jit_traces_total", component="compile", program=program
            ).inc()
            registry.counter(
                "jit_trace_seconds_total", component="compile", program=program
            ).inc(seconds)
        elif stage == "lower":
            registry.counter(
                "jit_lowerings_total", component="compile", program=program
            ).inc()
            registry.counter(
                "jit_lower_seconds_total", component="compile", program=program
            ).inc(seconds)
        else:
            registry.counter(
                "xla_compiles_total", component="compile", program=program
            ).inc()
            registry.counter(
                "xla_compile_seconds_total", component="compile",
                program=program,
            ).inc(seconds)
            self._built_again(program, seconds)
        wall, perf = get_tracer().wall_clock_anchor()
        t0 = float(start_time) - wall + perf
        self.note(stage, program, t0, t0 + seconds)

    def _built_again(self, program: str, seconds: float) -> None:
        with self._lock:
            again = any(program in run for run in self._warm)
            warn = again and program not in self._warned
            if warn:
                self._warned.add(program)
        if again:
            get_registry().counter(
                "compiles_in_run_total", component="compile", program=program
            ).inc()
        if warn:
            logger.warning(
                "program %r was compiled (or loaded) again, for %.3f s, "
                "inside a StreamingDriver.run past its first dispatch: the "
                "shape, dtype or sharding of a batch changed mid-stream",
                program, seconds,
            )

    def on_event(self, event: str, **kwargs) -> None:
        if event == CACHE_MISS_EVENT:
            get_registry().counter(
                "compile_cache_misses_total", component="compile"
            ).inc()
            now = time.perf_counter()
            self.note("cache_miss", "", now, now)

    # -- what the driver says ----------------------------------------------
    @contextlib.contextmanager
    def spans_to(self, tracer: SpanTracer):
        """For the length of a telemetry-on ``StreamingDriver.run`` every
        event is also a record on its tracer; what was listed since the last
        such run (the store's placement, set-up's compiles) is recorded now."""
        with self._lock:
            backlog = [e for e in self._events if e[0] > self._handed]
            self._handed = self._listed
            self._sinks.append(tracer)
        for event in backlog:
            _record(tracer, event)
        try:
            yield
        finally:
            with self._lock:
                self._sinks.remove(tracer)

    @contextlib.contextmanager
    def warm_run(self, programs):
        """For its length a ``StreamingDriver.run`` is past its first
        completed dispatch: one of ``programs`` (the names of what it
        dispatches) built again stalls a warm stream."""
        run = frozenset(programs)
        with self._lock:
            self._warm.append(run)
        try:
            yield
        finally:
            with self._lock:
                self._warm.remove(run)

    # -- reads -------------------------------------------------------------
    def events(self) -> List[Dict[str, Any]]:
        """The newest events, oldest first: ``stage`` (``trace``, ``lower``,
        ``backend``, ``cache_miss``, ``setup``), ``program`` (the span's name
        for ``setup``, empty for the cache's), ``t0`` and ``t1`` in seconds
        on ``perf_counter``, the clock of ``SpanTracer.spans()``."""
        with self._lock:
            raw = list(self._events)
        return [
            {"stage": s, "program": p, "t0": t0, "t1": t1}
            for _, s, p, t0, t1 in raw
        ]


# -- the process's one ledger --------------------------------------------------
_LEDGER = CompileLedger()
_INSTALL_LOCK = threading.Lock()
_installed = False


def get_ledger() -> CompileLedger:
    return _LEDGER


def events() -> List[Dict[str, Any]]:
    """:meth:`CompileLedger.events` of the process's ledger."""
    return _LEDGER.events()


def install() -> None:
    """Register the process ledger's listeners with ``jax.monitoring``,
    once: a second call does nothing.  ``enable_compile_cache`` calls it
    (every entry point's first call), and ``StreamingDriver.__init__`` for a
    caller that enables no cache."""
    global _installed
    with _INSTALL_LOCK:
        if _installed:
            return
        import jax.monitoring

        jax.monitoring.register_scalar_listener(_LEDGER.on_scalar)
        jax.monitoring.register_event_time_span_listener(_LEDGER.on_time_span)
        jax.monitoring.register_event_listener(_LEDGER.on_event)
        _installed = True


@contextlib.contextmanager
def setup_span(name: str, seconds: Optional[Counter] = None):
    """The caller's block as the process ledger's event ``setup`` /
    ``name`` (a telemetry-on run's tracer records it as ``setup.<name>``),
    and the host seconds inside it added to ``seconds`` (a registry counter
    the caller names)."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        t1 = time.perf_counter()
        if seconds is not None:
            seconds.inc(t1 - t0)
        _LEDGER.note("setup", name, t0, t1)


__all__ = [
    "CompileLedger", "events", "get_ledger", "install", "program_of",
    "setup_span",
]
