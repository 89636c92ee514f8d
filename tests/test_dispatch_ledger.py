"""The dispatch pipeline on the program's books (docs/observability.md, "The
span table"; ``training/metrics.py``): how many dispatches are in flight and
how old an update is when it lands, carried as ``args`` on the span a
dispatch already has; the cadence under its right name; the long gap between
two dispatches explained after the fact; the ring's count of what it drops.
Counted and attributed on the CPU, never timed."""
import collections
import gc
import json
import logging
import time
import types

import pytest

from flink_parameter_server_tpu import telemetry as tm
from flink_parameter_server_tpu.core.store import ShardedParamStore
from flink_parameter_server_tpu.data.movielens import synthetic_ratings
from flink_parameter_server_tpu.data.streams import microbatches
from flink_parameter_server_tpu.models.matrix_factorization import (
    OnlineMatrixFactorization,
    SGDUpdater,
)
from flink_parameter_server_tpu.telemetry.spans import NULL_TRACER
from flink_parameter_server_tpu.training import metrics as metrics_mod
from flink_parameter_server_tpu.training.driver import (
    DriverConfig,
    StreamingDriver,
)
from flink_parameter_server_tpu.training.metrics import InFlight, StepMetrics
from flink_parameter_server_tpu.utils.initializers import (
    ranged_random_factor,
)

pytestmark = pytest.mark.telemetry


class Handle:
    """Stands in for a dispatch's output leaf: ``is_ready`` flips by hand."""

    def __init__(self, nbytes=4):
        self.nbytes, self.ready, self.polls = nbytes, False, 0

    def is_deleted(self):
        return False

    def is_ready(self):
        self.polls += 1
        return self.ready


@pytest.fixture()
def plane():
    """A tracer and a registry of the test's own as the process defaults."""
    old_tracer, old_registry = tm.get_tracer(), tm.get_registry()
    tracer, registry = tm.SpanTracer(), tm.MetricsRegistry()
    tm.set_tracer(tracer)
    tm.set_registry(registry)
    try:
        yield tracer, registry
    finally:
        tm.set_tracer(old_tracer)
        tm.set_registry(old_registry)


def _driver(**cfg):
    logic = OnlineMatrixFactorization(64, 4, updater=SGDUpdater(0.05))
    store = ShardedParamStore.create(
        96, (4,), init_fn=ranged_random_factor(0, (4,))
    )
    return StreamingDriver(
        logic, store, config=DriverConfig(dump_model=False, **cfg)
    )


def _stream(n=30, sleep_at=None, seconds=0.3):
    data = synthetic_ratings(64, 96, n * 128, rank=3, seed=0)
    for i, batch in enumerate(microbatches(data, 128, shuffle_seed=1)):
        if i == sleep_at:
            time.sleep(seconds)
        yield batch


def _dispatch_spans(tracer):
    return [s for s in tracer.spans() if s["name"] == "pull_compute_push"]


# ---------------------------------------------------------------------------
# the in-flight record
# ---------------------------------------------------------------------------


def test_depth_counts_this_dispatch_and_age_is_of_the_newest_seen_ready():
    books, handles = InFlight(), [Handle() for _ in range(5)]
    seen = [books.dispatched({"loss": h}) for h in handles[:3]]
    assert [a["inflight"] for a in seen] == [1, 2, 3]
    assert [a["ready_age_s"] for a in seen] == [None, None, None]
    handles[0].ready = handles[1].ready = True
    t_second = books.pending[1][0]
    fourth = books.dispatched({"loss": handles[3]})
    # two dropped, the third and this one in flight; the age is the second's
    assert fourth["inflight"] == 2
    assert fourth["ready_age_s"] == pytest.approx(
        books.pending[-1][0] - t_second
    )
    assert [h for _, h in books.pending] == handles[2:4]
    # nothing became ready: one miss, no age
    polls = handles[2].polls
    fifth = books.dispatched({"loss": handles[4]})
    assert fifth == {"inflight": 3, "ready_age_s": None}
    assert handles[2].polls == polls + 1 and handles[3].polls == 0


def test_the_handle_is_the_smallest_array_leaf_chosen_once():
    books = InFlight()
    big, small = Handle(nbytes=4096), Handle(nbytes=4)
    books.dispatched({"a": big, "b": small, "note": "no array"})
    assert books.pending[-1][1] is small
    # the place stays, whatever the sizes of a later dispatch's leaves
    big2, small2 = Handle(nbytes=1), Handle(nbytes=8)
    books.dispatched({"a": big2, "b": small2, "note": "no array"})
    assert books.pending[-1][1] is small2


@pytest.mark.parametrize("outs", [None, {}, {"n": 3, "name": "x"}, (1.0, 2)])
def test_outputs_that_hold_no_array_report_nothing(outs):
    books = InFlight()
    assert books.dispatched(outs) is None and not books.pending


def test_an_output_a_hook_deleted_counts_as_landed_and_is_never_asked():
    import jax.numpy as jnp

    books = InFlight()
    freed = jnp.zeros((4,)) + 1
    books.dispatched({"loss": freed})
    freed.delete()  # is_ready() of a deleted array crashes the process
    assert books.unready() == (0, 0.0)
    assert books.dispatched({"loss": jnp.zeros((4,))})["inflight"] == 1


def test_the_probe_reads_what_is_unready_now_without_touching_the_books():
    books, handles = InFlight(), [Handle() for _ in range(3)]
    for h in handles:
        books.dispatched([h])
    n, age = books.unready()
    assert n == 3 and age > 0
    handles[0].ready = True
    n, age = books.unready()
    assert n == 2 and age == pytest.approx(
        time.perf_counter() - books.pending[1][0], abs=0.05
    )
    assert len(books.pending) == 3  # a scrape drops nothing
    handles[1].ready = handles[2].ready = True
    assert books.unready() == (0, 0.0)


# ---------------------------------------------------------------------------
# args on a span
# ---------------------------------------------------------------------------


def test_args_ride_on_the_span_in_spans_and_in_the_chrome_export():
    tracer = tm.SpanTracer()
    with tracer.span("pull_compute_push", component="train") as span:
        span.args = {"inflight": 7, "ready_age_s": 0.25}
    with tracer.span("batch_wait", component="train"):
        pass
    tracer.record("dispatch_gap", 1.0, 3.0, "train", args={"gap_s": 2.0})
    first, second, third = tracer.spans()
    assert first["args"] == {"inflight": 7, "ready_age_s": 0.25}
    assert second["args"] is None
    assert third["args"] == {"gap_s": 2.0} and third["dur"] == 2.0
    events = json.loads(tracer.export_chrome_trace())
    assert events[0]["args"] == {
        "depth": 0, "inflight": 7, "ready_age_s": 0.25,
    }
    assert events[1]["args"] == {"depth": 0}
    assert events[2]["args"] == {"depth": 0, "gap_s": 2.0}


def test_spans_overlapping_an_interval():
    tracer = tm.SpanTracer()
    for t0, t1 in ((0.0, 1.0), (0.5, 2.5), (3.0, 4.0), (1.0, 2.0)):
        tracer.record("x", t0, t1)
    got = [(s["start"], s["dur"]) for s in tracer.spans(overlapping=(1.0, 3.0))]
    assert got == [(0.5, 2.0), (1.0, 1.0)]


def test_a_disabled_tracer_takes_no_args_and_records_nothing():
    with NULL_TRACER.span("pull_compute_push", component="train") as span:
        assert not hasattr(span, "args")
    NULL_TRACER.record("x", 0.0, 1.0, args={"a": 1})
    assert NULL_TRACER.spans() == []


# ---------------------------------------------------------------------------
# the ring counts what it drops; collections
# ---------------------------------------------------------------------------


def test_the_ring_counts_what_it_drops_and_clear_starts_again():
    tracer = tm.SpanTracer(capacity=4)
    for i in range(4):
        tracer.record("x", i, i + 1)
    assert tracer.dropped == 0 and len(tracer) == 4
    for i in range(3):
        with tracer.span("y"):
            pass
    assert tracer.dropped == 3 and len(tracer) == 4
    assert [s["name"] for s in tracer.spans()] == ["x", "y", "y", "y"]
    tracer.clear()
    assert tracer.dropped == 0 and len(tracer) == 0


def test_a_full_collection_is_a_host_gc_record_on_both_clocks():
    from tests.test_program_spans import FakeAnnotations

    notes, tracer = FakeAnnotations(), tm.SpanTracer()
    tracer.annotate_with(notes)
    before = list(gc.callbacks)
    with tracer.gc_spans():
        with tracer.gc_spans():  # a second run on the tracer: one callback
            assert len(gc.callbacks) == len(before) + 1
            gc.collect()
        assert len(gc.callbacks) == len(before) + 1
    assert gc.callbacks == before
    full = [
        s for s in tracer.spans()
        if (s["component"], s["name"]) == ("host", "gc")
        and s["args"] == {"generation": 2}
    ]
    assert len(full) == 1 and full[0]["dur"] > 0
    assert notes.log.count(("enter", "fps.host.gc")) == 1
    assert notes.log.count(("exit", "fps.host.gc")) == 1
    recorded = len(tracer)
    gc.collect()  # outside the block: nobody listens
    assert len(tracer) == recorded


def test_collections_are_not_listened_for_under_a_disabled_tracer():
    before = list(gc.callbacks)
    with NULL_TRACER.gc_spans():
        assert gc.callbacks == before


# ---------------------------------------------------------------------------
# self times in a gap
# ---------------------------------------------------------------------------


def _span(tid, name, start, end, component="train"):
    return {"tid": tid, "name": name, "component": component,
            "start": start, "dur": end - start}


def test_an_instant_of_a_gap_goes_to_the_span_that_started_last():
    spans = [
        _span(1, "pull_compute_push", 0.0, 4.0),
        _span(1, "backend.step", 1.0, 3.0, "compile"),  # a record inside it
        _span(1, "batch_wait", 4.0, 9.0),  # clipped at the gap's end
        _span(2, "ingest", 0.0, 6.0, "ingest"),
        _span(2, "key_route", 2.0, 3.0, "ingest"),
        _span(3, "topk", 20.0, 21.0, "serving"),  # outside
    ]
    got = metrics_mod._self_seconds(spans, 0.5, 6.0)
    assert got == {
        (1, "train.pull_compute_push"): pytest.approx(1.5),
        (1, "compile.backend.step"): pytest.approx(2.0),
        (1, "train.batch_wait"): pytest.approx(2.0),
        (2, "ingest.ingest"): pytest.approx(4.5),
        (2, "ingest.key_route"): pytest.approx(1.0),
    }


# ---------------------------------------------------------------------------
# the cadence and the gap, on a clock the test moves
# ---------------------------------------------------------------------------


class Clock:
    def __init__(self):
        self.now = 100.0

    def perf_counter(self):
        return self.now


@pytest.fixture()
def clock(monkeypatch):
    clock = Clock()
    monkeypatch.setattr(
        metrics_mod, "time", types.SimpleNamespace(perf_counter=clock.perf_counter)
    )
    return clock


def _tick(metrics, clock, seconds):
    clock.now += seconds
    metrics.step_end()


@pytest.mark.parametrize("step_s, long_s, gaps", [
    (0.004, 0.059, 0),  # a publish every sixteenth dispatch of a 4 ms step
    (0.004, 0.099, 0),  # 25 medians, under the floor of 0.1 s
    (0.004, 0.101, 1),
    (0.056, 0.440, 0),  # over the floor, under 8 medians
    (0.056, 0.460, 1),
])
def test_a_gap_is_over_eight_medians_and_over_a_tenth_of_a_second(
    clock, step_s, long_s, gaps
):
    metrics = StepMetrics(
        events_per_step=1, tracer=tm.SpanTracer(), registry=tm.MetricsRegistry()
    )
    metrics.step_start()
    for i in range(48):
        _tick(metrics, clock, long_s if i % 16 == 15 else step_s)
    assert metrics.gap_count == gaps * 3
    assert len(metrics.gaps) == gaps * 3
    assert metrics.dispatch_interval_max == pytest.approx(long_s)


def test_no_gap_is_judged_before_the_median_has_intervals_under_it(clock):
    metrics = StepMetrics(events_per_step=1, tracer=tm.SpanTracer())
    metrics.step_start()
    for seconds in (0.001, 0.001, 5.0, 0.001):
        _tick(metrics, clock, seconds)
    assert metrics.gap_count == 0


def test_a_tracker_without_a_tracer_explains_nothing(clock):
    for tracer in (None, NULL_TRACER):
        metrics = StepMetrics(events_per_step=1, tracer=tracer)
        metrics.step_start()
        for i in range(20):
            _tick(metrics, clock, 5.0 if i == 15 else 0.001)
        assert metrics.gap_count == 0 and metrics._usage is None
        assert metrics.dispatch_interval_max == pytest.approx(5.0)


def test_one_warning_a_gap_for_the_first_eight_then_every_power_of_two(
    clock, caplog
):
    tracer, registry = tm.SpanTracer(), tm.MetricsRegistry()
    metrics = StepMetrics(events_per_step=1, tracer=tracer, registry=registry)
    metrics.step_start()
    with caplog.at_level(logging.WARNING, logger=metrics_mod.__name__):
        for i in range(20 * 16):
            _tick(metrics, clock, 1.0 if i % 16 == 15 else 0.001)
    assert metrics.gap_count == 20
    warned = [r for r in caplog.records if "dispatch gap" in r.getMessage()]
    assert len(warned) == 9  # gaps 1-8 and 16
    assert len(metrics.gaps) == 16  # the last sixteen breakdowns
    records = [s for s in tracer.spans() if s["name"] == "dispatch_gap"]
    assert len(records) == 20 and all(
        s["component"] == "train" and s["dur"] == pytest.approx(1.0)
        and s["args"]["gap_s"] == pytest.approx(1.0) for s in records
    )
    snap = registry.snapshot()
    assert snap["train_dispatch_gaps_total"][0]["value"] == 20
    assert snap["train_dispatch_gap_seconds_total"][0]["value"] == (
        pytest.approx(20.0)
    )


def test_the_json_line_names_the_cadence_for_what_it_is(clock):
    books, handle = InFlight(), Handle()
    metrics = StepMetrics(events_per_step=10, inflight=books)
    metrics.step_start()
    for seconds in (0.002, 0.004, 0.006):
        _tick(metrics, clock, seconds)
    books.dispatched([handle])
    clock.now += 0.5
    line = json.loads(metrics.emit())
    assert line["dispatch_interval_p50_ms"] == pytest.approx(4.0)
    assert line["dispatch_interval_max_ms"] == pytest.approx(6.0)
    assert line["inflight"] == 1 and line["update_age_ms"] > 0
    assert {"dispatch_interval_p90_ms", "dispatch_interval_p99_ms"} <= set(line)
    assert not [k for k in line if k.startswith("pull_push")]
    handle.ready = True
    assert metrics.snapshot()["inflight"] == 0
    # without the loop's books the line says so, and invents no zero
    bare = StepMetrics(events_per_step=10).snapshot()
    assert bare["inflight"] is None and bare["update_age_ms"] is None


# ---------------------------------------------------------------------------
# through the driver
# ---------------------------------------------------------------------------


def test_every_dispatch_span_carries_depth_and_age_and_none_is_added(plane):
    tracer, registry = plane
    driver = _driver()
    driver.add_group_hook(lambda *a: None)
    driver.run(_stream(n=30))
    dispatches = _dispatch_spans(tracer)
    assert len(dispatches) == 30
    assert all(set(s["args"]) == {"inflight", "ready_age_s"} for s in dispatches)
    assert all(1 <= s["args"]["inflight"] <= 30 for s in dispatches)
    assert dispatches[0]["args"] == {"inflight": 1, "ready_age_s": None}
    ages = [s["args"]["ready_age_s"] for s in dispatches[1:]]
    assert any(a is not None and a > 0 for a in ages)
    # the ring's spans a dispatch are what they were: four, as in the
    # benchmark's cell 1 (its hook is the one registered here)
    mine = collections.Counter(
        s["name"] for s in tracer.spans()
        if s["component"] in ("train", "ingest")
    )
    assert mine == {
        "batch_wait": 31, "pull_compute_push": 30, "hooks": 30, "ingest": 31,
    }
    snap = registry.snapshot()
    assert snap["dispatch_interval_seconds"][0]["value"]["count"] == 29
    for name in (
        "train_inflight_dispatches", "train_update_age_seconds",
        "train_dispatch_interval_max_seconds", "tracer_spans_dropped",
        "train_dispatch_gaps_total", "train_dispatch_gap_seconds_total",
    ):
        assert snap[name][0]["labels"] == {"component": "train"}, name
    assert snap["tracer_spans_dropped"][0]["value"] == 0
    assert snap["train_dispatch_interval_max_seconds"][0]["value"] > 0
    assert "pull_push_latency_seconds" not in snap


def test_a_synced_loop_reads_depth_one(plane):
    tracer, _ = plane
    _driver(metrics_every=1).run(_stream(n=12))
    assert [s["args"]["inflight"] for s in _dispatch_spans(tracer)] == [1] * 12


def test_a_scanned_group_is_one_dispatch_on_the_books(plane):
    tracer, _ = plane
    _driver(steps_per_call=4).run(_stream(n=12))
    dispatches = _dispatch_spans(tracer)
    assert len(dispatches) == 3
    assert all(s["args"]["inflight"] >= 1 for s in dispatches)


def test_telemetry_off_never_polls_and_sets_no_args(plane, monkeypatch):
    tracer, registry = plane
    polls = []
    monkeypatch.setattr(
        metrics_mod, "_ready", lambda h: polls.append(h) or True
    )
    monkeypatch.setattr(
        InFlight, "dispatched",
        lambda self, outs: pytest.fail("the books were kept"),
    )
    driver = _driver(telemetry=False)
    driver.run(_stream(n=12))
    assert driver._inflight is None and not polls
    assert tracer.spans() == []
    assert not [
        name for name, entries in registry.snapshot().items()
        if any(e["labels"].get("component") == "train" for e in entries)
    ]
    assert driver.metrics.snapshot()["inflight"] is None
    assert driver.metrics.gap_count == 0


def test_the_loop_alone_keeps_the_books_under_an_enabled_tracer():
    from flink_parameter_server_tpu.core.transform import transform_batched

    tracer = tm.SpanTracer()
    logic = OnlineMatrixFactorization(64, 4, updater=SGDUpdater(0.05))
    store = ShardedParamStore.create(
        96, (4,), init_fn=ranged_random_factor(0, (4,))
    )
    books = InFlight()
    transform_batched(
        _stream(n=6), logic, store, tracer=tracer, inflight=books,
        dump_model=False, collect_outputs=False,
    )
    dispatches = _dispatch_spans(tracer)
    assert len(dispatches) == 6 and all(s["args"] for s in dispatches)
    assert dispatches[-1]["args"]["inflight"] >= 1
    assert not books.pending  # the loop has ended: no output is held past it
    # ... and under the default tracer nothing is polled, whoever asks
    quiet = InFlight()
    transform_batched(
        _stream(n=3), logic, store, inflight=quiet, dump_model=False,
        collect_outputs=False,
    )
    assert not quiet.pending


@pytest.mark.parametrize("where, held_by", [
    ("hook", "train.hooks"), ("source", "train.batch_wait"),
])
def test_a_provoked_gap_is_one_record_one_warning_and_names_what_held_it(
    plane, caplog, where, held_by
):
    tracer, registry = plane
    driver = _driver()
    if where == "hook":
        driver.add_group_hook(
            lambda step, *a: time.sleep(0.3) if step == 20 else None
        )
    with caplog.at_level(logging.WARNING, logger=metrics_mod.__name__):
        driver.run(_stream(n=30, sleep_at=20 if where == "source" else None))
    # a loaded machine may add a gap of its own; the provoked one is there
    # once, and every gap has its record, its count and its warning
    gaps = list(driver.metrics.gaps)
    provoked = [g for g in gaps if g["gap_s"] >= 0.29]
    assert len(provoked) == 1
    gap = provoked[0]
    assert next(iter(gap["inside"])) == held_by
    assert gap["inside"][held_by] == pytest.approx(0.3, abs=0.05)
    assert gap["unspanned_s"] < 0.05 and gap["cpu_s"] < 0.2
    assert gap["voluntary_switches"] >= 1  # it slept: blocked, not pre-empted
    if where == "source":
        assert gap["others"]["fps-prefetch/ingest.ingest"] == pytest.approx(
            0.3, abs=0.05
        )
    records = [s for s in tracer.spans() if s["name"] == "dispatch_gap"]
    assert [s["args"] for s in records] == gaps
    snap = registry.snapshot()
    assert snap["train_dispatch_gaps_total"][0]["value"] == len(gaps)
    warned = [r for r in caplog.records if "dispatch gap" in r.getMessage()]
    assert len(warned) == len(gaps)
    assert any(held_by in r.getMessage() for r in warned)
    assert driver.metrics.snapshot()["dispatch_interval_max_ms"] >= 290
