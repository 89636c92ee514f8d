"""The six ``setup.*`` layer metrics: each reader on synthetic books (the
program's compile ledger and registry), the cut at the window's first span,
``None`` where the program keeps no such books (the parent), their entries
in ``BENCHMARK.json`` and a traced dry run that lists them."""
import collections
import json
import os
import subprocess
import sys

import pytest

from chipbench import lint, setup_ledger, spec
from flink_parameter_server_tpu import telemetry as tm
from flink_parameter_server_tpu.telemetry import compile_ledger

BENCH = spec.load_benchmark()
FROM_EVENTS = (
    "setup.compiles", "setup.cache_misses", "setup.trace_lower_s",
    "setup.compile_load_s",
)
FROM_COUNTERS = {
    "setup.store_place_s": "setup_store_place_seconds_total",
    "setup.kernel_import_s": "setup_kernel_import_seconds_total",
}
NAMES = FROM_EVENTS + tuple(FROM_COUNTERS)
KERNEL_CELLS = [
    "mf-hugewiki-k128.train-zipf", "mf-hugewiki-k128.train-zipf-serve-topk",
    "w2v-googlenews-300.train-pairs-zipf",
    "lr-ftrl-criteo-40m.train-fields-uniform",
]


def _event(stage, program, t0, seconds=0.0):
    return {"stage": stage, "program": program, "t0": t0, "t1": t0 + seconds}


# set-up ends where the window's first span starts, at 100 on the tracer's
# clock: the step's three stages, two eager helpers and a placement before
# it, the harness's `_all_finite` and a read-back after it
EVENTS = [
    _event("trace", "build", 10.0, 0.25),
    _event("lower", "build", 10.25, 0.5),
    _event("cache_miss", "", 12.0),
    _event("backend", "build", 10.75, 2.0),
    _event("trace", "step", 20.0, 0.5),
    _event("lower", "step", 20.5, 0.25),
    _event("setup", "store_place", 9.5, 3.5),  # no compile stage: skipped
    _event("backend", "step", 20.75, 0.125),
    _event("trace", "_take", 30.0, 0.0625),
    _event("lower", "_take", 30.0625, 0.0625),
    _event("setup", "kernel_import_wait", 20.1, 0.3),
    _event("backend", "_take", 30.125, 0.03125),
    _event("backend", "late_starter", 99.5, 4.0),  # began before the cut
    _event("trace", "<lambda>", 140.0, 1.0),
    _event("lower", "<lambda>", 141.0, 1.0),
    _event("cache_miss", "", 142.5),
    _event("backend", "<lambda>", 142.0, 8.0),
]
CTX = {"spans": [
    {"name": "pull_compute_push", "component": "train", "start": 100.5, "dur": 0.1},
    {"name": "batch_wait", "component": "train", "start": 100.0, "dur": 0.5},
]}
EXPECTED = {
    "setup.compiles": 4.0,
    "setup.cache_misses": 1.0,
    "setup.trace_lower_s": 0.25 + 0.5 + 0.5 + 0.25 + 0.0625 + 0.0625,
    "setup.compile_load_s": 2.0 + 0.125 + 0.03125 + 4.0,
}


@pytest.fixture()
def books(monkeypatch):
    """Synthetic events in the ledger's place and an empty registry."""
    monkeypatch.setattr(compile_ledger, "events", lambda: list(EVENTS))
    registry, old = tm.MetricsRegistry(), tm.get_registry()
    tm.set_registry(registry)
    try:
        yield registry
    finally:
        tm.set_registry(old)


@pytest.fixture()
def no_books(monkeypatch, books):
    """The parent's program: no compile ledger to import, no counters."""
    monkeypatch.delattr(tm, "compile_ledger", raising=False)
    monkeypatch.setitem(sys.modules, compile_ledger.__name__, None)


@pytest.mark.parametrize("name", FROM_EVENTS)
def test_a_reader_counts_what_began_before_the_windows_first_span(books, name):
    assert spec.metric_reader(name).read(CTX) == EXPECTED[name]


@pytest.mark.parametrize("name", FROM_EVENTS)
def test_a_window_without_spans_cuts_nothing(books, name):
    everything = spec.metric_reader(name).read({"spans": []})
    assert everything > EXPECTED[name]


def test_the_cut_moves_with_the_first_span(books):
    read = spec.metric_reader("setup.compiles").read
    assert read({"spans": [{"start": 25.0}]}) == 2.0
    assert read({"spans": [{"start": 10.75}]}) == 0.0  # began AT the cut: out
    assert read({"spans": [{"start": 500.0}]}) == 5.0


@pytest.mark.parametrize("name", FROM_EVENTS)
def test_an_empty_ledger_reads_zero_and_not_nothing(books, monkeypatch, name):
    # a warm run's `setup.cache_misses` is 0, and the line has to carry it
    monkeypatch.setattr(compile_ledger, "events", lambda: [])
    value = spec.metric_reader(name).read(CTX)
    assert value == 0.0 and value is not None


@pytest.mark.parametrize("name", sorted(FROM_COUNTERS))
def test_a_reader_sums_the_programs_counter(books, name):
    reader = spec.metric_reader(name)
    assert reader.read(CTX) is None  # nothing registered it: no such work
    books.counter(FROM_COUNTERS[name], component="setup").inc(0.75)
    books.counter(FROM_COUNTERS[name], component="setup").inc(0.5)
    assert reader.read(CTX) == 1.25
    assert reader.read({"spans": []}) == 1.25  # a counter has no cut


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_books_reports_nothing(no_books, name):
    assert spec.metric_reader(name).read(CTX) is None


def test_the_shared_reads(books):
    got = setup_ledger.events_before_window(CTX, ("backend",))
    assert [e["program"] for e in got] == ["build", "step", "_take", "late_starter"]
    assert setup_ledger.counter_total("no_such_counter") is None


def test_the_readers_read_the_ledger_the_program_writes(books, monkeypatch):
    """End to end on the CPU: a program compiled under the real listeners
    shows in the readers, and one compiled after the cut does not."""
    import jax
    import jax.numpy as jnp

    monkeypatch.undo()  # the real ``events`` again ...
    # ... of a list this test alone fills (the process's may be full and roll)
    monkeypatch.setattr(
        compile_ledger.get_ledger(), "_events", collections.deque(maxlen=64)
    )
    tracer, old = tm.SpanTracer(), tm.get_tracer()
    tm.set_tracer(tracer)
    try:
        compile_ledger.install()
        x, y = jnp.arange(11.0), jnp.arange(13.0)  # their programs: before
        ctx = {"spans": []}
        read = {n: spec.metric_reader(n).read for n in FROM_EVENTS}
        before = {n: read[n](ctx) for n in FROM_EVENTS}
        jax.jit(lambda v: (v * 7).sum(), inline=False)(x)
        with tracer.span("batch_wait", component="train"):
            pass
        ctx = {"spans": tracer.spans()[-1:]}
        after = {n: read[n](ctx) for n in FROM_EVENTS}
        assert after["setup.compiles"] == before["setup.compiles"] + 1
        assert after["setup.trace_lower_s"] > before["setup.trace_lower_s"]
        assert after["setup.compile_load_s"] > before["setup.compile_load_s"]
        jax.jit(lambda v: (v * 9).sum(), inline=False)(y)
        assert {n: read[n](ctx) for n in FROM_EVENTS} == after
    finally:
        tm.set_tracer(old)


# ---------------------------------------------------------------------------
# the entries
# ---------------------------------------------------------------------------


def test_the_six_entries_are_one_layer_under_setup_s_and_lint_clean():
    # by name, not by place: later metrics are appended after these
    mine = [m for m in BENCH["per_layer"] if m["name"] in NAMES]
    assert [m["name"] for m in mine] == [
        "setup.compiles", "setup.cache_misses", "setup.trace_lower_s",
        "setup.compile_load_s", "setup.store_place_s", "setup.kernel_import_s",
    ]
    at = [BENCH["per_layer"].index(m) for m in mine]
    assert at == list(range(at[0], at[0] + 6))
    for m in mine:
        assert (m["layer"], m["moves"], m["better"]) == ("set-up", "setup_s", "lower")
        assert spec.metric_reader(m["name"]) is not None
    assert {m["name"]: (m["unit"], m["source"]) for m in mine} == {
        "setup.compiles": ("count", "program_counter"),
        "setup.cache_misses": ("count", "program_counter"),
        "setup.trace_lower_s": ("s", "program_span"),
        "setup.compile_load_s": ("s", "program_span"),
        "setup.store_place_s": ("s", "program_span"),
        "setup.kernel_import_s": ("s", "program_span"),
    }
    assert [m.get("workloads") for m in mine] == [None] * 5 + [KERNEL_CELLS]
    # the first layer under the one end-to-end metric every cell reports
    assert set(NAMES) <= {
        m["name"] for m in BENCH["per_layer"] if m["moves"] == "setup_s"
    }
    assert lint.problems(spec.ROOT) == []


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_reports_the_layer(cell):
    per_layer = {m["name"] for m in spec.metrics_of(BENCH, "per_layer", cell)}
    assert set(NAMES) - per_layer == (
        set() if cell in KERNEL_CELLS else {"setup.kernel_import_s"}
    )
    assert "setup_s" in {
        m["name"] for m in spec.metrics_of(BENCH, "end_to_end", cell)
    }


# the CPU traces no kernel, so a dry run's program never asks for Pallas: the
# test starts the import and asks for the modules as a step traced on the TPU
# does, then runs the cell
DRY_RUN = """
import sys
from flink_parameter_server_tpu.ops import row_update
if sys.argv[1] == "kernel":
    row_update.preload()
    row_update._pallas()
from chipbench import run
sys.exit(run.main(sys.argv[2:]))
"""


@pytest.mark.parametrize("program, absent", [
    ("kernel", set()), ("no-kernel", {"setup.kernel_import_s"}),
])
def test_a_traced_dry_run_lists_the_layer(program, absent):
    cell = "mf-hugewiki-k128.train-zipf"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.ROOT)
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, "-c", DRY_RUN, program, "--workload", cell, "--seed",
         str(2**31 + 36), "--seconds", "0.5", "--trace", "1", "--cpu-dry-run"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] and last["failed"] == 0 and last["failures"] == []
    assert set(NAMES) - set(last["metric_names"]) == absent
    info = json.loads(
        [ln for ln in done.stderr.splitlines() if ln.startswith("[chipbench] {")][-1]
        [len("[chipbench] "):]
    )
    # what the harness counts from outside, less what it compiles after the
    # window (`_all_finite`): the program's own count of its set-up
    assert info["metrics"]["setup.compiles"] == info["compiles_total"] - 1
    assert info["metrics"]["setup.store_place_s"] > 0
    assert info["metrics"]["setup.trace_lower_s"] > 0
