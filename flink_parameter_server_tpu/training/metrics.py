"""Step metrics & observability.

Reference parity (SURVEY.md §5 "Metrics / logging"): the reference exposes
only Flink's operator metrics (throughput, backpressure).  The rebuild's
north-star metrics (ROADMAP.md) are measured here: updates/sec/chip and
the cadence of the dispatches, plus a JSON-lines emitter as the
"accumulator" analogue.

With a :class:`~..telemetry.MetricsRegistry` attached the tracker also
publishes through the unified plane (``component=train``): step/event
counters, the dispatch-interval histogram, and live probe gauges (the
windowed rate, the dispatches in flight, the age of the oldest of them),
which is what the ``/metrics`` endpoint scrapes while the run is in
flight.  The JSON emit line stays (now stamped with the shared
``ts``/``run_id``).

The dispatch pipeline's books are kept here too, three readings of one
mechanism:

* :class:`InFlight` — the dispatches whose outputs are not yet known to be
  ready, polled where the dispatch happens (``core/transform``): how many
  are in flight and how old an update is when it lands, carried as
  ``args`` on the ``train.pull_compute_push`` span a dispatch already has;
* the cadence — the interval from one dispatch's return to the next
  (``dispatch_interval_seconds``): free-running this is the step PERIOD,
  not a latency, and is named so;
* the long gap — an interval over both ``GAP_MEDIANS`` x the rolling
  median and ``GAP_MIN_S`` is explained after the fact from the tracer's
  ring and the thread's resource usage, as ONE ``train.dispatch_gap``
  record and one warning (:meth:`StepMetrics._explain_gap`).
"""
from __future__ import annotations

import heapq
import logging
import resource
import statistics
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import numpy as np

from ..telemetry.registry import json_line

logger = logging.getLogger(__name__)

# A gap is an interval between two consecutive dispatches longer than BOTH
# (constants, not options: a publish of 59 ms every sixteenth dispatch of a
# 4 ms step must not trip it; a 56 ms step trips at 0.45 s)
GAP_MEDIANS = 8.0
GAP_MIN_S = 0.1
# ... judged once the rolling median has this many intervals under it
_GAP_MIN_INTERVALS = 8
# other threads' lines in a breakdown: the longest few
_GAP_OTHERS = 8
_RUSAGE_THREAD = getattr(resource, "RUSAGE_THREAD", None)


def _ready(handle) -> bool:
    # an output a hook deleted to free its memory has landed; asking a
    # deleted array `is_ready()` takes the process down (jax 0.9)
    return handle.is_deleted() or handle.is_ready()


class InFlight:
    """The dispatches whose outputs are not yet known to be ready.

    ``pending`` holds ``(t_dispatched, handle)``, oldest first; ``handle``
    is the SMALLEST array leaf of a dispatch's outputs (outputs are never
    donated; table and state are, and are never held here), its place in
    the outputs chosen once.  Written by the dispatching thread alone; a
    scrape reads a copy (:meth:`unready`).
    """

    __slots__ = ("pending", "_leaf")

    def __init__(self):
        self.pending: Deque[Tuple[float, Any]] = deque()
        self._leaf: Optional[int] = None

    def _handle(self, outs):
        leaves = jax.tree.leaves(outs)
        at = self._leaf
        if at is not None and at < len(leaves) and hasattr(
            leaves[at], "is_ready"
        ):
            return leaves[at]
        arrays = [
            (getattr(x, "nbytes", 0), i) for i, x in enumerate(leaves)
            if hasattr(x, "is_ready")
        ]
        if not arrays:
            return None
        self._leaf = min(arrays)[1]
        return leaves[self._leaf]

    def dispatched(self, outs) -> Optional[Dict[str, Any]]:
        """Note the dispatch whose jitted call just returned ``outs``:
        drop from the old end every dispatch now seen ready (a
        non-blocking poll: amortised one hit and one miss a dispatch), add
        this one.  Returns what the dispatch's span carries:
        ``inflight``, the dispatches in flight now, this one included, and
        ``ready_age_s``, dispatch to seen-ready of the newest one dropped
        (``None`` where none was).  The poll sees readiness at the NEXT
        dispatch, so an age reads up to one dispatch interval high.
        ``None`` for outputs that hold no array."""
        handle = self._handle(outs)
        if handle is None:
            return None
        pending = self.pending
        landed = None
        while pending and _ready(pending[0][1]):
            landed = pending.popleft()[0]
        now = time.perf_counter()
        pending.append((now, handle))
        return {
            "inflight": len(pending),
            "ready_age_s": None if landed is None else now - landed,
        }

    def unready(self) -> Tuple[int, float]:
        """For a scrape, from any thread and without touching the books:
        the dispatches not ready at this instant, and the seconds since
        the oldest of them was dispatched (0 with none).  A probe, not the
        last dispatch's stored count: in the middle of a stall that one
        reports the value from before it."""
        entries = list(self.pending)  # one C call: a consistent copy
        for i, (t, handle) in enumerate(entries):
            if not _ready(handle):
                return len(entries) - i, time.perf_counter() - t
        return 0, 0.0


def _self_seconds(
    spans: List[Dict[str, Any]], lo: float, hi: float
) -> Dict[Tuple[int, str], float]:
    """Seconds of ``[lo, hi]`` by ``(tid, "component.name")``, every
    instant counted once a thread: to the span that started last among
    those that cover it (the innermost where spans nest, a ``compile.*``
    record inside the dispatch that paid for it)."""
    out: Dict[Tuple[int, str], float] = {}
    by_thread: Dict[int, list] = {}
    for s in spans:
        a, b = max(s["start"], lo), min(s["start"] + s["dur"], hi)
        if b > a:
            by_thread.setdefault(s["tid"], []).append(
                (a, b, f"{s['component']}.{s['name']}")
            )
    for tid, rows in by_thread.items():
        rows.sort()
        edges = sorted({e for a, b, _ in rows for e in (a, b)})
        active: list = []  # (-start, end, name): the last started on top
        i = 0
        for a, b in zip(edges, edges[1:]):
            while i < len(rows) and rows[i][0] <= a:
                heapq.heappush(active, (-rows[i][0], rows[i][1], rows[i][2]))
                i += 1
            while active and active[0][1] <= a:
                heapq.heappop(active)
            if active:
                key = (tid, active[0][2])
                out[key] = out.get(key, 0.0) + (b - a)
    return out


@dataclass
class StepMetrics:
    """Rolling throughput/cadence tracker for the PS train loop.

    ``events_per_step`` = microbatch size (one "event" = one reference
    record: a rating, an example, a token pair).  What is timed is the
    interval from one dispatch's return to the next one's: with the loop
    synced a step (``metrics_every`` > 0) the full pull→compute→push round
    trip, free-running the step period.

    ``tracer`` (an enabled ``telemetry.SpanTracer``: the driver's, with
    telemetry on) turns the gap record on; ``inflight`` is the loop's
    :class:`InFlight`, read by the probe gauges and the JSON line.
    """

    events_per_step: int
    window: int = 100
    registry: Optional[Any] = None  # telemetry.MetricsRegistry or None
    tracer: Optional[Any] = None  # telemetry.SpanTracer or None
    inflight: Optional[InFlight] = None
    _t_last: Optional[float] = None
    total_steps: int = 0
    total_events: int = 0
    started_at: float = field(default_factory=time.perf_counter)
    dispatch_interval_max: float = 0.0
    gap_count: int = 0

    def __post_init__(self) -> None:
        self._durations: Deque[float] = deque(maxlen=self.window)
        self._window_events: Deque[int] = deque(maxlen=self.window)
        # the last breakdowns of a long gap, newest last
        self.gaps: Deque[Dict[str, Any]] = deque(maxlen=16)
        self._explains = (
            self.tracer is not None and self.tracer.enabled
            and _RUSAGE_THREAD is not None
        )
        self._usage = None
        reg = self.registry
        self._c_steps = self._c_events = self._h_interval = None
        self._c_gaps = self._c_gap_seconds = None
        if reg is not None:
            self._c_steps = reg.counter(
                "train_steps_total", component="train"
            )
            self._c_events = reg.counter(
                "train_events_total", component="train"
            )
            self._h_interval = reg.histogram(
                "dispatch_interval_seconds", component="train"
            )
            self._c_gaps = reg.counter(
                "train_dispatch_gaps_total", component="train"
            )
            self._c_gap_seconds = reg.counter(
                "train_dispatch_gap_seconds_total", component="train"
            )
            # probe gauges: the scrape reads the CURRENT value, at zero
            # per-step cost
            reg.gauge(
                "updates_per_sec", component="train",
                fn=self.updates_per_sec,
            )
            reg.gauge(
                "train_inflight_dispatches", component="train",
                fn=lambda: self._unready()[0],
            )
            reg.gauge(
                "train_update_age_seconds", component="train",
                fn=lambda: self._unready()[1],
            )
            reg.gauge(
                "train_dispatch_interval_max_seconds", component="train",
                fn=lambda: self.dispatch_interval_max,
            )

    def _unready(self) -> Tuple[Optional[int], Optional[float]]:
        if self.inflight is None:
            return None, None
        return self.inflight.unready()

    def count_untimed(self, steps: int, events: int) -> None:
        """Count steps/events that were never timed (a run's first
        dispatch has no prior timestamp; recovery bookkeeping) — totals
        and registry counters stay exact, the cadence stays honest."""
        self.total_steps += steps
        self.total_events += events
        if self._c_steps is not None:
            self._c_steps.inc(steps)
            self._c_events.inc(events)

    def step_start(self) -> None:
        self._t_last = time.perf_counter()
        if self._explains:
            self._usage = resource.getrusage(_RUSAGE_THREAD)

    def step_end(
        self, events: Optional[int] = None, *, n_steps: int = 1
    ) -> None:
        """``events`` overrides the event count for the timed interval
        (e.g. a padded final batch contributes only its masked-in rows).
        ``n_steps`` > 1 records one GROUP dispatch covering that many
        steps (``transform_batched(steps_per_call=K)``): one interval
        — the percentiles then time dispatches — while step/event totals
        and the rate stay exact.  The instant that ends this interval
        starts the next: a loop calls ``step_start`` once."""
        assert self._t_last is not None, "step_start() not called"
        n_events = self.events_per_step * n_steps if events is None else events
        t0, now = self._t_last, time.perf_counter()
        dur = now - t0
        if self._explains:
            # the one thing a sound dispatch pays for the gap record: the
            # thread's usage now, kept for the next interval's difference
            before, self._usage = self._usage, resource.getrusage(
                _RUSAGE_THREAD
            )
            if dur > GAP_MIN_S and len(self._durations) >= _GAP_MIN_INTERVALS:
                median = statistics.median(self._durations)
                if dur > GAP_MEDIANS * median:
                    self._explain_gap(t0, now, median, before, self._usage)
        self._t_last = now
        self._durations.append(dur)
        self._window_events.append(n_events)
        if dur > self.dispatch_interval_max:
            self.dispatch_interval_max = dur
        self.total_steps += n_steps
        self.total_events += n_events
        if self._c_steps is not None:
            self._c_steps.inc(n_steps)
            self._c_events.inc(n_events)
            # one observation per DISPATCH (n_steps steps), matching the
            # percentile semantics of the rolling window
            self._h_interval.observe(dur)

    # -- the long gap -------------------------------------------------------
    def _explain_gap(self, t0, t1, median, before, after) -> None:
        """What held the host between the dispatch that returned at
        ``t0`` and the one that returned at ``t1``, on the calling
        (training) thread, after the fact: the self times of its own ring
        spans that lie in the gap and the rest under none; whether it ran
        (its CPU seconds over the gap, its context switches); who else
        did (other threads' spans in the gap, ``host.gc`` among them).
        One ``train.dispatch_gap`` record with that as its ``args``, the
        two counters, one warning for the first eight gaps of this
        tracker and then every 2^n-th."""
        me = threading.get_ident()
        inside: Dict[str, float] = {}
        others: Dict[str, float] = {}
        names = {t.ident: t.name for t in threading.enumerate()}
        seconds = _self_seconds(
            [
                s for s in self.tracer.spans(overlapping=(t0, t1))
                if s["name"] != "dispatch_gap"
            ],
            t0, t1,
        )
        for (tid, name), s in seconds.items():
            if tid == me:
                inside[name] = round(s, 6)
            else:
                others[f"{names.get(tid, tid)}/{name}"] = round(s, 6)
        gap = t1 - t0
        breakdown = {
            "gap_s": round(gap, 6),
            "median_interval_s": round(median, 6),
            "step": self.total_steps,
            "inside": dict(sorted(inside.items(), key=lambda kv: -kv[1])),
            "unspanned_s": round(max(0.0, gap - sum(inside.values())), 6),
            "cpu_s": round(max(
                0.0, after.ru_utime + after.ru_stime
                - before.ru_utime - before.ru_stime
            ), 6),
            "voluntary_switches": after.ru_nvcsw - before.ru_nvcsw,
            "involuntary_switches": after.ru_nivcsw - before.ru_nivcsw,
            "others": dict(
                sorted(others.items(), key=lambda kv: -kv[1])[:_GAP_OTHERS]
            ),
        }
        self.tracer.record(
            "dispatch_gap", t0, t1, component="train", args=breakdown
        )
        self.gaps.append(breakdown)
        self.gap_count += 1
        if self._c_gaps is not None:
            self._c_gaps.inc()
            self._c_gap_seconds.inc(gap)
        n = self.gap_count
        if n <= 8 or n & (n - 1) == 0:
            logger.warning(
                "dispatch gap %d: %.3f s between two dispatches (median "
                "interval %.4f s) after step %d; the training thread was "
                "inside %s, under no span %.3f s; it ran %.3f s of it "
                "(%d voluntary, %d involuntary context switches); "
                "other threads: %s",
                n, gap, median, self.total_steps,
                ", ".join(f"{k} {v:.3f} s" for k, v in
                          breakdown["inside"].items()) or "no span",
                breakdown["unspanned_s"], breakdown["cpu_s"],
                breakdown["voluntary_switches"],
                breakdown["involuntary_switches"],
                ", ".join(f"{k} {v:.3f} s" for k, v in
                          breakdown["others"].items()) or "no span",
            )

    # -- reporting --------------------------------------------------------
    def updates_per_sec(self) -> float:
        if not self._durations:
            return 0.0
        return sum(self._window_events) / sum(self._durations)

    def interval_percentiles(self) -> Dict[str, float]:
        """Percentiles of the last ``window`` dispatch intervals."""
        if not self._durations:
            return {"p50": 0.0, "p90": 0.0, "p99": 0.0}
        d = np.array(self._durations)
        return {
            "p50": float(np.percentile(d, 50)),
            "p90": float(np.percentile(d, 90)),
            "p99": float(np.percentile(d, 99)),
        }

    def snapshot(self) -> Dict[str, Any]:
        lat = self.interval_percentiles()
        inflight, age = self._unready()
        return {
            "steps": self.total_steps,
            "events": self.total_events,
            "updates_per_sec": round(self.updates_per_sec(), 1),
            "dispatch_interval_p50_ms": round(lat["p50"] * 1e3, 3),
            "dispatch_interval_p90_ms": round(lat["p90"] * 1e3, 3),
            "dispatch_interval_p99_ms": round(lat["p99"] * 1e3, 3),
            "dispatch_interval_max_ms": round(
                self.dispatch_interval_max * 1e3, 3
            ),
            "inflight": inflight,
            "update_age_ms": None if age is None else round(age * 1e3, 3),
            "wall_s": round(time.perf_counter() - self.started_at, 3),
        }

    def emit(self, sink=None) -> str:
        """One single-line JSON sample (shared ``ts``/``run_id`` stamped
        by the unified plane; guaranteed to round-trip ``json.loads``)."""
        return json_line(
            self.snapshot(), sink,
            run_id=self.registry.run_id if self.registry else None,
        )


__all__ = ["GAP_MEDIANS", "GAP_MIN_S", "InFlight", "StepMetrics"]
