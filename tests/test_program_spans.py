"""The program's own spans and scopes (docs/observability.md, "The span
table"): ``SpanTracer.span`` opens on the profiler's clock too, the driver
and the serving service record their phases where the work happens, and
the jitted step's ops carry ``ps.*`` scopes.  Counted, never timed."""
import collections
import re

import jax
import numpy as np
import pytest

from flink_parameter_server_tpu import telemetry as tm
from flink_parameter_server_tpu.core.store import ShardedParamStore
from flink_parameter_server_tpu.core.transform import (
    make_scan_train_step,
    make_train_step,
)
from flink_parameter_server_tpu.data.movielens import synthetic_ratings
from flink_parameter_server_tpu.data.streams import microbatches
from flink_parameter_server_tpu.models.factorization_machine import (
    FactorizationMachine,
    FMConfig,
    make_store as make_fm_store,
)
from flink_parameter_server_tpu.models.matrix_factorization import (
    OnlineMatrixFactorization,
    SGDUpdater,
)
from flink_parameter_server_tpu.training.driver import (
    DriverConfig,
    StreamingDriver,
)
from flink_parameter_server_tpu.utils.initializers import (
    ranged_random_factor,
)

pytestmark = pytest.mark.telemetry


# ---------------------------------------------------------------------------
# spans that open on both clocks
# ---------------------------------------------------------------------------


class FakeAnnotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs enter / exit."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        log = self.log

        class Note:
            def __enter__(self):
                log.append(("enter", name))

            def __exit__(self, *exc):
                log.append(("exit", name))

        return Note()


def test_a_span_opens_one_annotation_named_by_component_and_name():
    notes, tracer = FakeAnnotations(), tm.SpanTracer()
    tracer.annotate_with(notes)
    with tracer.span("publish", component="train"):
        with tracer.span("publish_sync", component="train"):
            pass
    assert notes.log == [
        ("enter", "fps.train.publish"), ("enter", "fps.train.publish_sync"),
        ("exit", "fps.train.publish_sync"), ("exit", "fps.train.publish"),
    ]
    assert [s["name"] for s in tracer.spans()] == ["publish_sync", "publish"]


def test_the_annotation_closes_when_the_body_raises():
    notes, tracer = FakeAnnotations(), tm.SpanTracer()
    tracer.annotate_with(notes)
    with pytest.raises(KeyError):
        with tracer.span("ingest", component="ingest"):
            raise KeyError("source")
    assert notes.log == [
        ("enter", "fps.ingest.ingest"), ("exit", "fps.ingest.ingest"),
    ]
    assert len(tracer) == 1


def test_record_and_a_disabled_tracer_open_no_annotation():
    notes, tracer = FakeAnnotations(), tm.SpanTracer()
    tracer.annotate_with(notes)
    tracer.record("queue_wait", 1.0, 2.0, component="serving")
    off = tm.SpanTracer(enabled=False)
    off.annotate_with(notes)
    with off.span("publish", component="train") as sp:
        assert sp.span_id is None and sp.trace_id is None
    assert notes.log == [] and len(tracer) == 1 and len(off) == 0


def test_the_first_factory_stays_and_a_tracer_without_one_opens_nothing():
    first, second, tracer = FakeAnnotations(), FakeAnnotations(), tm.SpanTracer()
    with tracer.span("ingest", component="ingest"):
        pass
    tracer.annotate_with(first)
    tracer.annotate_with(second)
    with tracer.span("ingest", component="ingest"):
        pass
    assert len(first.log) == 2 and second.log == [] and len(tracer) == 2


def test_spans_module_imports_no_jax():
    import subprocess
    import sys

    code = (
        "import sys, importlib.util as u;"
        "s = u.spec_from_file_location('spans', sys.argv[1]);"
        "m = u.module_from_spec(s); s.loader.exec_module(m);"
        "assert 'jax' not in sys.modules, 'spans.py imported jax'"
    )
    subprocess.run(
        [sys.executable, "-c", code, tm.spans.__file__], check=True, timeout=60
    )


# ---------------------------------------------------------------------------
# the driver's and the service's spans, on a CPU run with serving
# ---------------------------------------------------------------------------

NUM_USERS, NUM_ITEMS, DIM, PUBLISH_EVERY = 40, 64, 4, 3


def _mf_logic_and_store():
    logic = OnlineMatrixFactorization(NUM_USERS, DIM, updater=SGDUpdater(0.05))
    store = ShardedParamStore.create(
        NUM_ITEMS, (DIM,), init_fn=ranged_random_factor(1, (DIM,))
    )
    return logic, store


def _driver(**config):
    return StreamingDriver(
        *_mf_logic_and_store(), config=DriverConfig(dump_model=False, **config)
    )


@pytest.fixture(scope="module")
def served_run():
    """One StreamingDriver run with serving attached and queries sent from
    a group hook; every PendingRequest the dispatch thread served is kept."""
    tracer, notes = tm.SpanTracer(), FakeAnnotations()
    tracer.annotate_with(notes)
    old = tm.get_tracer()
    tm.set_tracer(tracer)
    try:
        driver = _driver()
        service = driver.serve_with(
            publish_every=PUBLISH_EVERY, max_batch=8, max_delay_ms=1.0
        )
        served, futures, versions = [], [], []
        serve_batch = service._serve_batch

        def keep(batch):
            served.extend(batch)
            serve_batch(batch)

        service._serve_batch = keep
        publish = service.snapshots.publish

        def counted(*args, **kwargs):
            snap = publish(*args, **kwargs)
            versions.append(snap.version)
            return snap

        service.snapshots.publish = counted

        def hook(step, n_steps, table, state, outs):
            if step >= PUBLISH_EVERY:  # a snapshot with user vectors is out
                futures.extend(
                    service.submit_topk(u, 5) for u in range(step % 7 + 1)
                )
            if step % 5 == 0:
                futures.append(service.submit_lookup([1, 2, 3]))

        driver.add_group_hook(hook)
        data = synthetic_ratings(NUM_USERS, NUM_ITEMS, 10_000, rank=4, seed=0)
        batches = list(microbatches(data, 512, epochs=1, shuffle_seed=0))
        driver.run(batches)
        answers = [f.result(timeout=30) for f in futures]
        service.stop()
    finally:
        tm.set_tracer(old)
    return {
        "spans": tracer.spans(), "notes": notes.log, "batches": len(batches),
        "served": served, "answers": answers, "published": len(versions),
    }


def _named(run, name, component):
    return [
        s for s in run["spans"]
        if s["name"] == name and s["component"] == component
    ]


def test_one_pull_compute_push_and_one_batch_wait_a_dispatch(served_run):
    assert served_run["batches"] >= 15
    dispatches = _named(served_run, "pull_compute_push", "train")
    waits = _named(served_run, "batch_wait", "train")
    assert len(dispatches) == served_run["batches"]
    # one wait a batch, and the one that finds the stream at its end
    assert len(waits) == served_run["batches"] + 1
    assert len(_named(served_run, "hooks", "train")) == served_run["batches"]
    assert len(_named(served_run, "ingest", "ingest")) >= served_run["batches"]


def test_batch_wait_and_dispatch_never_overlap_on_the_training_thread(served_run):
    mine = sorted(
        (s["start"], s["start"] + s["dur"], s["name"])
        for s in served_run["spans"]
        if s["component"] == "train"
        and s["name"] in ("batch_wait", "pull_compute_push", "hooks", "publish")
        and s["depth"] == 0
    )
    assert len({s["tid"] for s in _named(served_run, "batch_wait", "train")}) == 1
    for (_, end, a), (start, _, b) in zip(mine, mine[1:]):
        assert end <= start, f"{a} overlaps {b}"
    # a dispatch follows its wait with nothing of the callback in between
    order = [n for _, _, n in mine if n in ("batch_wait", "pull_compute_push")]
    assert order[:-1] == ["batch_wait", "pull_compute_push"] * (len(order) // 2)


def test_a_publish_span_a_snapshot_published_each_with_its_two_children(served_run):
    publishes = _named(served_run, "publish", "train")
    # the pre-training table, one a cadence, the close-time one
    assert served_run["published"] >= 2 + served_run["batches"] // PUBLISH_EVERY - 1
    assert len(publishes) == served_run["published"]
    for kind in ("publish_enqueue", "publish_sync"):
        children = _named(served_run, kind, "train")
        assert len(children) == len(publishes)
        for parent, child in zip(publishes, children):
            assert child["depth"] == parent["depth"] + 1
            assert parent["start"] <= child["start"]
            assert (
                child["start"] + child["dur"] <= parent["start"] + parent["dur"]
            )


def test_every_answered_query_has_one_queue_wait_under_its_batch_span(served_run):
    served, answers = served_run["served"], served_run["answers"]
    assert len(answers) == len(served) > 20
    waits = _named(served_run, "queue_wait", "serving")
    assert len(waits) == len(served)
    batch_spans = {
        s["span_id"]: s for s in served_run["spans"]
        if s["component"] == "serving" and s["name"] in ("topk", "lookup")
    }
    assert None not in batch_spans
    by_stamp = collections.Counter(w["start"] for w in waits)
    for p in served:
        assert by_stamp[p.t_submit] == 1  # starts at its admission stamp
    for w in waits:
        parent = batch_spans[w["parent_id"]]
        assert w["trace_id"] == parent["trace_id"]
        assert w["dur"] >= 0 and w["start"] + w["dur"] <= parent["start"]
    served_under = collections.Counter(w["parent_id"] for w in waits)
    assert set(served_under) == set(batch_spans)  # no batch span serves nobody
    for kind in ("topk", "lookup"):
        mine = [s for s in batch_spans.values() if s["name"] == kind]
        assert mine
        for child in (f"{kind}_enqueue", f"{kind}_ready"):
            children = _named(served_run, child, "serving")
            assert len(children) == len(mine)
            assert {c["parent_id"] for c in children} == {
                s["span_id"] for s in mine
            }
    assert _named(served_run, "batch_wait", "serving")


def test_every_program_span_opened_its_annotation(served_run):
    entered = collections.Counter(
        name for what, name in served_run["notes"] if what == "enter"
    )
    exited = collections.Counter(
        name for what, name in served_run["notes"] if what == "exit"
    )
    assert entered == exited
    recorded = collections.Counter(
        f"fps.{s['component']}.{s['name']}" for s in served_run["spans"]
        # record(): host clock only (the waits; the compile ledger's books;
        # a garbage collection younger than a full one)
        if s["name"] != "queue_wait"
        and s["component"] not in ("compile", "setup")
        and not (s["name"] == "gc" and s["args"]["generation"] < 2)
    )
    assert entered == recorded
    assert not [n for n in entered if n.startswith(("fps.compile.", "fps.setup."))]
    assert {
        "fps.train.batch_wait", "fps.train.pull_compute_push",
        "fps.train.hooks", "fps.train.publish", "fps.train.publish_enqueue",
        "fps.train.publish_sync", "fps.ingest.ingest", "fps.serving.batch_wait",
        "fps.serving.topk", "fps.serving.topk_enqueue",
        "fps.serving.topk_ready", "fps.serving.lookup",
    } <= set(entered)


def test_telemetry_off_records_and_opens_nothing():
    tracer, notes = tm.SpanTracer(), FakeAnnotations()
    tracer.annotate_with(notes)
    old = tm.get_tracer()
    tm.set_tracer(tracer)
    try:
        driver = _driver(telemetry=False)
        service = driver.serve_with(publish_every=2)
        driver.add_group_hook(lambda *a: None)
        data = synthetic_ratings(NUM_USERS, NUM_ITEMS, 2_000, rank=4, seed=0)
        driver.run(microbatches(data, 512, epochs=1, shuffle_seed=0))
        assert service.client().top_k(1, k=3).item_ids.shape == (3,)
        service.stop()
    finally:
        tm.set_tracer(old)
    assert len(tracer) == 0 and notes.log == []


def test_a_scanned_group_is_one_dispatch_span():
    tracer = tm.SpanTracer()
    old = tm.get_tracer()
    tm.set_tracer(tracer)
    try:
        driver = _driver(steps_per_call=4)
        data = synthetic_ratings(NUM_USERS, NUM_ITEMS, 512 * 9, rank=4, seed=0)
        batches = list(microbatches(data, 512, epochs=1, shuffle_seed=0))
        driver.run(batches)
    finally:
        tm.set_tracer(old)
    names = collections.Counter(s["name"] for s in tracer.spans())
    groups, tail = divmod(len(batches), 4)
    assert names["pull_compute_push"] == groups + tail
    assert names["batch_wait"] == len(batches) + 1


# ---------------------------------------------------------------------------
# ps.* scopes in the jitted step
# ---------------------------------------------------------------------------

OP_NAME = re.compile(r'^\s*(?:ROOT )?%?(\S+) = .*? (\S+?)\(.*op_name="([^"]*)"')


def _ops(lowered):
    """(opcode, op_name) of every instruction of the compiled module."""
    out = []
    for line in lowered.compile().as_text().splitlines():
        m = OP_NAME.match(line)
        if m:
            out.append((m.group(2), m.group(3)))
    return out


def _innermost(op_name):
    scopes = [part for part in op_name.split("/") if part.startswith("ps.")]
    return scopes[-1] if scopes else None


def _mf():
    logic, store = _mf_logic_and_store()
    batch = {
        "user": np.zeros(32, np.int32), "item": np.zeros(32, np.int32),
        "rating": np.zeros(32, np.float32),
    }
    return logic, store, batch


def _fm():
    config = FMConfig(num_features=NUM_ITEMS, dim=DIM)
    logic, store = FactorizationMachine(config), make_fm_store(config)
    batch = {
        "ids": np.zeros((32, 5), np.int32),
        "values": np.ones((32, 5), np.float32),
        "feat_mask": np.ones((32, 5), bool),
        "label": np.ones(32, np.float32), "mask": np.ones(32, bool),
    }
    return logic, store, batch


@pytest.mark.parametrize("family, scopes", [
    (_mf, {"ps.pull", "ps.compute", "ps.push", "ps.state_pull", "ps.state_push"}),
    (_fm, {"ps.pull", "ps.compute", "ps.push"}),
])
def test_the_lowered_step_carries_the_scopes(family, scopes):
    logic, store, batch = family()
    state = logic.init_state(jax.random.PRNGKey(0))
    lowered = jax.jit(make_train_step(logic, store.spec)).lower(
        store.table, state, batch
    )
    ops = _ops(lowered)
    assert {_innermost(name) for _, name in ops} - {None} == scopes
    gathers = {_innermost(n) for op, n in ops if op == "gather"}
    scatters = {_innermost(n) for op, n in ops if op == "scatter"}
    pulls = {"ps.pull", "ps.state_pull"} & scopes
    pushes = {"ps.push", "ps.state_push"} & scopes
    assert gathers and gathers <= pulls, gathers
    assert scatters and scatters <= pushes, scatters
    # a logic's own scopes nest inside ps.compute
    for _, name in ops:
        if "ps.state_" in name:
            assert "ps.compute/" in name.split("ps.state_")[0]


def test_the_scanned_step_carries_the_scopes_through_its_loop():
    logic, store, batch = _mf()
    state = logic.init_state(jax.random.PRNGKey(0))
    stacked = jax.tree.map(lambda x: np.stack([x] * 3), batch)
    lowered = jax.jit(make_scan_train_step(logic, store.spec)).lower(
        store.table, state, stacked
    )
    found = {_innermost(name) for _, name in _ops(lowered)} - {None}
    assert found == {
        "ps.pull", "ps.compute", "ps.push", "ps.state_pull", "ps.state_push",
    }
