"""Set-up on the program's books (``telemetry/compile_ledger.py``,
docs/observability.md "The span table"): every trace, lowering and compile
counted by program from JAX's own monitoring events, their records on the
tracer of a telemetry-on run inside the dispatch that paid for them (and on
no tracer otherwise), a program built again inside a warm ``run`` named and
warned of once, and set-up's own spans with seconds that outlive the tracer's
ring.  Counted, never timed."""
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_parameter_server_tpu import telemetry as tm
from flink_parameter_server_tpu.core.store import ShardedParamStore
from flink_parameter_server_tpu.core.transform import transform_batched
from flink_parameter_server_tpu.models.matrix_factorization import (
    OnlineMatrixFactorization,
    SGDUpdater,
)
from flink_parameter_server_tpu.ops import row_update
from flink_parameter_server_tpu.telemetry import compile_ledger
from flink_parameter_server_tpu.training.driver import (
    DriverConfig,
    StreamingDriver,
)
from flink_parameter_server_tpu.utils.compile_cache import enable_compile_cache
from flink_parameter_server_tpu.utils.initializers import (
    ranged_random_factor,
)

pytestmark = pytest.mark.telemetry

NUM_USERS, NUM_ITEMS, DIM = 40, 64, 4
STAGE_COUNTERS = ("jit_traces_total", "jit_lowerings_total", "xla_compiles_total")


@pytest.fixture()
def books():
    """A registry and a tracer of this test's own as the process defaults,
    the ledger listening, and no program warned of yet."""
    registry, tracer = tm.MetricsRegistry(), tm.SpanTracer()
    old = tm.get_registry(), tm.get_tracer()
    tm.set_registry(registry)
    tm.set_tracer(tracer)
    ledger = compile_ledger.get_ledger()
    warned, ledger._warned = ledger._warned, set()
    ledger._handed = ledger._listed  # earlier tests' events are no run's
    compile_ledger.install()
    try:
        yield registry, tracer
    finally:
        ledger._warned = warned
        tm.set_registry(old[0])
        tm.set_tracer(old[1])


def _logic_and_store():
    logic = OnlineMatrixFactorization(NUM_USERS, DIM, updater=SGDUpdater(0.05))
    store = ShardedParamStore.create(
        NUM_ITEMS, (DIM,), init_fn=ranged_random_factor(1, (DIM,))
    )
    return logic, store


def _batches(n, size=8, seed=0):
    r = np.random.default_rng(seed)
    return [
        {
            "user": r.integers(0, NUM_USERS, size).astype(np.int32),
            "item": r.integers(0, NUM_ITEMS, size).astype(np.int32),
            "rating": r.random(size).astype(np.float32),
        }
        for _ in range(n)
    ]


def _driver(**config):
    return StreamingDriver(
        *_logic_and_store(), config=DriverConfig(dump_model=False, **config)
    )


def _value(registry, name, **labels):
    """The instrument's value, ``None`` where nothing registered it."""
    for entry in registry.snapshot().get(name, []):
        if all(entry["labels"].get(k) == v for k, v in labels.items()):
            return entry["value"]
    return None


def _listed(stage, since):
    """The process ledger's events of ``stage`` that began at or after
    ``since`` (``time.perf_counter()``): this test's own."""
    return [
        e for e in compile_ledger.events()
        if e["stage"] == stage and e["t0"] >= since - 1e-3
    ]


def _step_counts(registry):
    return [_value(registry, name, program="step") for name in STAGE_COUNTERS]


# ---------------------------------------------------------------------------
# the compile ledger
# ---------------------------------------------------------------------------


def test_a_driver_traces_lowers_and_compiles_its_step_once_over_two_runs(books):
    registry, _ = books
    driver = _driver()
    driver.run(iter(_batches(3)))
    driver.run(iter(_batches(3, seed=1)))
    assert _step_counts(registry) == [1, 1, 1]
    for seconds in ("jit_trace_seconds_total", "jit_lower_seconds_total",
                    "xla_compile_seconds_total"):
        assert _value(registry, seconds, program="step") > 0
    assert _value(registry, "compiles_in_run_total", program="step") is None


def test_a_second_jit_of_the_step_shows_as_a_second_trace(books):
    registry, _ = books
    driver = _driver()
    driver.run(iter(_batches(2)))
    # a direct call builds its own ``jax.jit`` of the step, as a driver did
    # for every ``run`` before it kept one
    transform_batched(_batches(2), *_logic_and_store(), dump_model=False)
    assert _step_counts(registry) == [2, 2, 2]


def test_installing_twice_counts_once(books):
    registry, _ = books
    compile_ledger.install()
    enable_compile_cache()  # every entry point's first call installs too
    compile_ledger.install()
    jax.jit(lambda x: x * 3 + 1, inline=False)(jnp.arange(7.0))
    assert [
        _value(registry, name, program="<lambda>") for name in STAGE_COUNTERS
    ] == [1, 1, 1]


def test_a_function_traced_inside_a_trace_is_no_program_of_its_own(books):
    registry, _ = books

    @jax.jit
    def inner_helper(x):
        return jnp.where(x > 0, x, 0.0)

    @jax.jit
    def outer_program(x):
        return inner_helper(x).sum() + jnp.clip(x, 0, 1).sum()

    outer_program(jnp.arange(5.0))
    assert _value(registry, "jit_traces_total", program="outer_program") == 1
    assert _value(registry, "jit_traces_total", program="inner_helper") is None
    assert _value(registry, "jit_traces_total", program="clip") is None
    inner_helper(jnp.arange(5.0))  # called from Python it is one
    assert _value(registry, "jit_traces_total", program="inner_helper") == 1


def test_the_compile_records_lie_inside_the_first_dispatch(books):
    _, tracer = books
    _driver().run(iter(_batches(3)))
    spans = tracer.spans()
    first = next(s for s in spans if s["name"] == "pull_compute_push")
    stages = {
        s["name"]: s for s in spans if s["component"] == "compile"
        and s["name"].endswith(".step")
    }
    assert sorted(stages) == ["backend.step", "lower.step", "trace.step"]
    slack = 1e-3  # two clocks, one anchor
    for s in stages.values():
        assert first["start"] - slack <= s["start"]
        assert s["start"] + s["dur"] <= first["start"] + first["dur"] + slack
    assert (
        stages["trace.step"]["start"] < stages["lower.step"]["start"]
        < stages["backend.step"]["start"]
    )
    # ... and the later dispatches paid for none
    later = [s for s in spans if s["name"] == "pull_compute_push"][1:]
    assert len(later) == 2
    for s in later:
        assert s["start"] >= stages["backend.step"]["start"]


def test_the_ledgers_own_events_outlive_the_tracers_ring(books):
    _, tracer = books
    began = time.perf_counter()  # the tracer's clock, and the ledger's
    _driver().run(iter(_batches(2)))
    tracer.clear()
    assert not tracer.spans()
    mine = [
        e for e in compile_ledger.events()
        if e["program"] == "step" and e["t0"] >= began - 1e-3
    ]
    assert [e["stage"] for e in mine] == ["trace", "lower", "backend"]
    assert all(e["t1"] >= e["t0"] for e in mine)
    # on the tracer's clock: a span opened now starts after them
    with tracer.span("now"):
        pass
    assert tracer.spans()[0]["start"] >= mine[-1]["t1"] - 1e-3


def test_a_batch_shape_that_changes_mid_run_is_named_once(books, caplog):
    registry, _ = books
    driver = _driver()
    stream = _batches(2) + _batches(2, size=16) + _batches(1, size=24)
    with caplog.at_level(logging.WARNING, logger=compile_ledger.logger.name):
        driver.run(iter(stream))
    assert _value(registry, "compiles_in_run_total", program="step") == 2
    warnings = [r for r in caplog.records if r.name == compile_ledger.logger.name]
    assert len(warnings) == 1
    assert "'step'" in warnings[0].getMessage()
    assert _step_counts(registry) == [3, 3, 3]


def test_one_shape_change_counts_one_compile_in_the_run(books, caplog):
    registry, _ = books
    with caplog.at_level(logging.WARNING, logger=compile_ledger.logger.name):
        _driver().run(iter(_batches(2) + _batches(2, size=16)))
    assert _value(registry, "compiles_in_run_total", program="step") == 1
    assert len(caplog.records) == 1


def test_what_else_is_built_while_the_run_is_warm_is_no_step_built_again(
    books, caplog
):
    """The query buckets a serving warm-up builds, a publish's ``copy``, a
    checkpoint's ``isfinite``, ``convert_element_type`` for one more shape:
    new work, whether or not the process built that name before."""
    registry, _ = books
    driver = _driver()

    @jax.jit
    def a_helper(x):
        return x.sum()

    a_helper(jnp.zeros(2))  # set-up built it once already
    sizes = iter(range(3, 99))
    driver.add_group_hook(lambda *a: a_helper(jnp.zeros(next(sizes))))
    with caplog.at_level(logging.WARNING, logger=compile_ledger.logger.name):
        driver.run(iter(_batches(4)))
    assert _value(registry, "xla_compiles_total", program="a_helper") == 5
    assert registry.snapshot().get("compiles_in_run_total") is None
    assert not caplog.records


def test_a_runs_first_dispatch_and_the_time_between_runs_are_not_in_a_run(books):
    registry, _ = books
    driver = _driver()
    driver.run(iter(_batches(2)))
    jax.jit(lambda x: x - 2, inline=False)(jnp.arange(3.0))  # between runs
    driver.run(iter(_batches(2, size=16)))  # a new shape, first dispatch
    assert registry.snapshot().get("compiles_in_run_total") is None
    assert _step_counts(registry) == [2, 2, 2]


def test_a_run_that_raises_leaves_no_run_open(books):
    registry, _ = books
    driver = _driver()

    def failing():
        yield from _batches(2)
        raise KeyError("source")

    with pytest.raises(KeyError):
        driver.run(failing())
    jax.jit(lambda x: x + 5, inline=False)(jnp.arange(3.0))
    assert registry.snapshot().get("compiles_in_run_total") is None


def test_telemetry_off_marks_no_run_and_hands_no_tracer_over(books):
    registry, tracer = books
    began = time.perf_counter()
    _driver(telemetry=False).run(iter(_batches(2) + _batches(2, size=16)))
    assert registry.snapshot().get("compiles_in_run_total") is None
    assert len(tracer) == 0
    # the process's counters and list are no driver's to switch off
    assert _step_counts(registry) == [2, 2, 2]
    assert len(_listed("backend", began)) >= 2 and _listed("setup", began)


@pytest.mark.parametrize("stage, fun_name, program", [
    ("trace", "step", "step"),
    ("lower", "jit(step)", "step"),
    ("backend", "jit(step)", "step"),
    ("backend", "jit(_narrow_pull)", "_narrow_pull"),
    ("lower", "pmap(body)", "body"),
    ("trace", "jit_me", "jit_me"),
    ("backend", "jit", "jit"),
    ("backend", None, "unknown"),
])
def test_one_program_label_over_the_three_stages(stage, fun_name, program):
    assert compile_ledger.program_of(stage, fun_name) == program


def test_a_cache_miss_is_counted_and_listed_and_nothing_else_of_the_cache(books):
    registry, _ = books
    ledger = compile_ledger.CompileLedger()
    ledger.on_event("/jax/compilation_cache/cache_hits")
    ledger.on_event(compile_ledger.CACHE_MISS_EVENT)
    ledger.on_event("/jax/compilation_cache/compile_requests_use_cache")
    assert _value(registry, "compile_cache_misses_total") == 1
    assert [e["stage"] for e in ledger.events()] == ["cache_miss"]
    assert sorted(registry.snapshot()) == ["compile_cache_misses_total"]


def test_the_ledgers_list_is_bounded_and_moves_wall_stamps_onto_the_tracer(
    books, monkeypatch
):
    registry, tracer = books
    monkeypatch.setattr(compile_ledger, "CAPACITY", 2)
    ledger = compile_ledger.CompileLedger()
    wall, perf = tracer.wall_clock_anchor()
    for i in range(3):
        ledger.on_time_span(
            compile_ledger.BACKEND_EVENT, wall + 10 + i, wall + 10.5 + i,
            fun_name="jit(synthetic)",
        )
    ledger.on_time_span("/jax/some/other_event", wall, wall + 1, fun_name="x")
    events = ledger.events()
    assert len(events) == 2
    assert events[-1] == {
        "stage": "backend", "program": "synthetic",
        "t0": pytest.approx(perf + 12), "t1": pytest.approx(perf + 12.5),
    }
    assert _value(registry, "xla_compiles_total", program="synthetic") == 3
    assert _value(
        registry, "xla_compile_seconds_total", program="synthetic"
    ) == pytest.approx(1.5)
    assert len(tracer) == 0  # no run has handed the ledger a tracer


def test_a_run_hands_its_tracer_the_backlog_once_and_then_each_event(books):
    _, tracer = books
    ledger = compile_ledger.CompileLedger()
    wall, perf = tracer.wall_clock_anchor()

    def compiled(i, name):
        ledger.on_time_span(
            compile_ledger.BACKEND_EVENT, wall + i, wall + i + 0.5,
            fun_name=f"jit({name})",
        )

    compiled(1, "before")
    ledger.note("setup", "store_place", perf + 2, perf + 3)
    ledger.on_event(compile_ledger.CACHE_MISS_EVENT)  # an event, no span
    assert len(tracer) == 0
    with ledger.spans_to(tracer):
        with ledger.spans_to(tracer):  # a second run on the same tracer
            compiled(4, "inside")
    compiled(5, "after")
    assert [(s["component"], s["name"]) for s in tracer.spans()] == [
        ("compile", "backend.before"), ("setup", "store_place"),
        ("compile", "backend.inside"),
    ]
    assert tracer.spans()[0]["start"] == pytest.approx(perf + 1)
    other = tm.SpanTracer()
    with ledger.spans_to(other):  # the next run: what came since the last
        pass
    assert [s["name"] for s in other.spans()] == ["backend.after"]
    assert len(tracer) == 3


# ---------------------------------------------------------------------------
# set-up's own spans
# ---------------------------------------------------------------------------


def _setup_spans(tracer):
    return [s["name"] for s in tracer.spans() if s["component"] == "setup"]


@pytest.mark.parametrize("build", ["create", "from_values", "from_spec_values"])
def test_store_placement_is_listed_and_its_seconds_survive_a_clear(books, build):
    registry, tracer = books
    values = np.ones((NUM_ITEMS, DIM), np.float32)
    spec = ShardedParamStore.create(NUM_ITEMS, (DIM,)).spec
    began = time.perf_counter()
    if build == "create":
        ShardedParamStore.create(NUM_ITEMS, (DIM,))
    elif build == "from_values":
        ShardedParamStore.from_values(values)
    else:
        ShardedParamStore.from_spec_values(spec, jnp.asarray(values))
    assert [e["program"] for e in _listed("setup", began)] == ["store_place"]
    assert len(tracer) == 0  # no run is open: the books, and no tracer
    tracer.clear()
    assert _value(registry, "setup_store_place_seconds_total") > 0


def test_a_telemetry_on_run_records_the_set_up_that_came_before_it(books):
    _, tracer = books
    driver = _driver()  # builds its store: ``setup.store_place``
    assert len(tracer) == 0
    driver.run(iter(_batches(2)))
    spans = tracer.spans()
    first = next(s for s in spans if s["name"] == "pull_compute_push")
    placed = [s for s in spans if (s["component"], s["name"]) == (
        "setup", "store_place"
    )]
    assert len(placed) == 1
    assert placed[0]["start"] + placed[0]["dur"] <= first["start"]
    # ... with the programs the placement traced and built inside it
    inside = [
        s for s in spans if s["component"] == "compile"
        and placed[0]["start"] - 1e-3 <= s["start"]
        <= placed[0]["start"] + placed[0]["dur"]
    ]
    assert inside


def test_the_kernel_import_is_listed_and_only_the_wait_is_counted(
    books, monkeypatch
):
    registry, tracer = books
    began = time.perf_counter()
    row_update._import_pallas()  # what ``preload`` runs on its thread
    assert [e["program"] for e in _listed("setup", began)] == ["kernel_import"]
    assert not [n for n in registry.snapshot() if n.startswith("setup_")]
    monkeypatch.setattr(row_update, "_PALLAS", None)
    pl, pltpu = row_update._pallas()  # the first kernel to ask
    assert hasattr(pl, "pallas_call") and hasattr(pltpu, "PrefetchScalarGridSpec")
    assert [e["program"] for e in _listed("setup", began)] == [
        "kernel_import", "kernel_import_wait",
    ]
    waited = _value(registry, "setup_kernel_import_seconds_total")
    assert waited > 0
    row_update._pallas()  # the second asks nothing
    assert len(_listed("setup", began)) == 2
    assert _value(registry, "setup_kernel_import_seconds_total") == waited
    tracer.clear()  # the counter is not the ring's
    assert _value(registry, "setup_kernel_import_seconds_total") == waited


def test_preload_starts_one_import_however_often_it_is_asked(books, monkeypatch):
    import threading

    started = []
    monkeypatch.setattr(row_update, "_PRELOAD", threading.Lock())
    monkeypatch.setattr(
        row_update, "_import_pallas", lambda: started.append(1)
    )
    for _ in range(3):
        row_update.preload()
    for t in threading.enumerate():
        if t.name == "pallas-import":
            t.join(60)
    assert started == [1]


def test_the_loops_commit_is_listed_and_recorded_by_a_telemetry_on_run(books):
    _, tracer = books
    began = time.perf_counter()
    transform_batched(_batches(1), *_logic_and_store(), dump_model=False)
    assert "commit" in [e["program"] for e in _listed("setup", began)]
    assert len(tracer) == 0  # a direct call hands no tracer over
    _driver().run(iter(_batches(1)))
    commits = [
        s for s in tracer.spans()
        if (s["component"], s["name"]) == ("setup", "commit")
    ]
    first = next(s for s in tracer.spans() if s["name"] == "pull_compute_push")
    assert len(commits) == 2  # the direct call's from the backlog, the run's
    assert commits[-1]["start"] + commits[-1]["dur"] <= first["start"]


def test_setup_span_without_a_counter_is_an_event_only(books):
    registry, tracer = books
    began = time.perf_counter()
    with compile_ledger.setup_span("anything"):
        pass
    assert [e["program"] for e in _listed("setup", began)] == ["anything"]
    assert not [n for n in registry.snapshot() if n.startswith("setup_")]
