"""The three ``driver.*`` layer metrics of the dispatch pipeline
(``driver.inflight_depth``, ``driver.update_age_p50_ms``,
``driver.dispatch_gap_max_ms``): each reader on a hand-made window of spans,
``None`` where the program keeps no such books (the parent) or its ring
dropped spans, and their entries in ``BENCHMARK.json``."""
import pytest

from chipbench import dispatch_ledger, lint, spec
from flink_parameter_server_tpu import telemetry as tm

BENCH = spec.load_benchmark()
DEPTH, AGE, GAP = (
    "driver.inflight_depth", "driver.update_age_p50_ms",
    "driver.dispatch_gap_max_ms",
)
NAMES = (DEPTH, AGE, GAP)
AGE_CELLS = ["mf-hugewiki-k128.train-zipf", "fm-criteo.train-fields-uniform"]


def _span(name, start, dur, args=None, component="train", **more):
    return {"name": name, "component": component, "start": start, "dur": dur,
            "depth": 0, "tid": 1, "args": args, **more}


def _dispatch(start, inflight, age):
    return _span("pull_compute_push", start, 0.003,
                 {"inflight": inflight, "ready_age_s": age})


# dispatches every 4 ms; between the third and the fourth a hook of 2 s (the
# harness's profiler) in an interval of 2.012 s; between the fifth and the
# sixth 40 ms under no hook: the longest gap is that one
WINDOW = [
    _dispatch(10.000, 31, None),
    _span("hooks", 10.0032, 0.0001),
    _dispatch(10.004, 32, 0.120),
    _dispatch(10.008, 33, 0.124),
    _span("hooks", 10.0115, 2.0),
    _span("batch_wait", 12.0116, 0.008),
    _dispatch(12.020, 2, 0.128),
    _dispatch(12.024, 3, 0.004),
    _span("publish", 12.0275, 0.036),
    _dispatch(12.064, 4, None),
    _span("topk", 12.01, 0.5, component="serving"),
]


def _read(name, spans):
    return spec.metric_reader(name).read({"spans": spans})


def test_depth_and_age_are_medians_over_the_windows_dispatches():
    assert _read(DEPTH, WINDOW) == pytest.approx(17.5)  # of 2 3 4 31 32 33
    assert _read(AGE, WINDOW) == pytest.approx(122.0)  # of 4 120 124 128 ms


def test_the_hooks_time_is_taken_out_of_the_gap_it_lies_in():
    assert _read(GAP, WINDOW) == pytest.approx(40.0)
    no_hook = [s for s in WINDOW if s["name"] != "hooks"]
    assert _read(GAP, no_hook) == pytest.approx(2012.0)
    # a hook that outlasts the interval it starts in takes no more than that
    late = WINDOW[:4] + [_span("hooks", 10.0115, 5.0)] + WINDOW[5:]
    assert _read(GAP, late) == pytest.approx(40.0)


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_books_gives_none(name):
    parent = [{k: v for k, v in s.items() if k != "args"} for s in WINDOW]
    assert _read(name, parent) is None
    unset = [dict(s, args=None) for s in WINDOW]
    assert _read(name, unset) is None
    assert _read(name, []) is None


def test_one_dispatch_has_no_gap_and_no_age_reports_no_age():
    assert _read(GAP, WINDOW[:1]) is None
    assert _read(AGE, WINDOW[:1]) is None
    assert _read(DEPTH, WINDOW[:1]) == 31


@pytest.mark.parametrize("name", NAMES)
def test_a_ring_that_dropped_spans_gives_none_and_says_why(name, capsys):
    old, small = tm.get_tracer(), tm.SpanTracer(capacity=2)
    tm.set_tracer(small)
    try:
        for i in range(5):
            small.record("x", i, i + 1)
        assert _read(name, WINDOW) is None
        assert "dropped 3 spans" in capsys.readouterr().err
        small.clear()
        assert _read(name, WINDOW) is not None
    finally:
        tm.set_tracer(old)


def test_the_window_is_read_oldest_first_whatever_the_rings_order():
    assert [s["start"] for s in dispatch_ledger.dispatches(
        {"spans": WINDOW[::-1]}
    )] == [10.0, 10.004, 10.008, 12.02, 12.024, 12.064]
    assert _read(GAP, WINDOW[::-1]) == pytest.approx(40.0)


def test_the_three_entries_are_there_by_name_under_the_driver_layer():
    mine = {m["name"]: m for m in BENCH["per_layer"] if m["name"] in NAMES}
    assert set(mine) == set(NAMES)
    assert {
        n: (m["unit"], m["better"], m["source"], m["layer"], m["moves"])
        for n, m in mine.items()
    } == {
        DEPTH: ("count", "lower", "program_counter", "driver", "pull_push_p50_ms"),
        AGE: ("ms", "lower", "program_span", "driver", "pull_push_p50_ms"),
        GAP: ("ms", "lower", "program_span", "driver", "updates_per_s_chip"),
    }
    # the two that move pull_push_p50_ms list the two cells that report it;
    # the gap is read in every cell, those later PRs add too
    assert mine[DEPTH]["workloads"] == mine[AGE]["workloads"] == AGE_CELLS
    assert "workloads" not in mine[GAP]
    for name in NAMES:
        assert spec.metric_reader(name) is not None
    for cell in (w["name"] for w in BENCH["workloads"]):
        listed = {m["name"] for m in spec.metrics_of(BENCH, "per_layer", cell)}
        assert GAP in listed
        assert ({DEPTH, AGE} <= listed) == (cell in AGE_CELLS)


def test_lint_says_nothing_about_them():
    found = lint.problems(spec.ROOT)
    assert not [line for line in found if any(n in line for n in NAMES)]
    assert not [line for line in found if "dispatch_ledger" in line]
