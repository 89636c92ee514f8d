"""The program-named reduction (``chipbench/program_trace.py``) on timelines
small enough to work out by hand and on a trace recorded on the chip, and
the readers that report it: nothing without a trace, nothing without the
program's names."""
import json
import os

import pytest

from chipbench import lint, program_trace, spec, trace

FIXTURE = os.path.join(spec.BENCH_DIR, "fixtures", "mf_serve_topk_v5e_spans.json")
MS = 1_000_000
NEW_METRICS = [
    "store.pull_device_ms", "store.push_device_ms",
    "step.state_update_device_ms", "step.compute_device_ms",
    "step.unscoped_share", "driver.batch_wait_ms", "driver.dispatch_ms",
    "device.idle_attributed_share", "serving.publish_idle_ms",
    "serving.queue_wait_p50_ms", "serving.queue_wait_p95_ms",
    "serving.topk_device_wait_ms",
]


def _trace(ops, modules, threads):
    return {
        "devices": [{"name": "/device:TPU:0", "modules": modules, "ops": ops}],
        "host": [{"name": "python3", "events": events} for events in threads],
    }


def _hand_built():
    # window 0..40 ms.  Step A 0..10: pull 0..2, state_pull 2..3, compute
    # 3..4, state_push 4..8, push 8..9, a hole 9..10 inside the program.
    # Step B 10..20, the same ops with no hole between the programs.  Then
    # the publish's copy 20..24 (another program), the device idle 24..30,
    # step C 30..40.  The training thread: publish 19..26 (enqueue 19..20,
    # sync 20..25), hooks 26..27, batch_wait 27..29.5, pull_compute_push
    # 29.5..30; the serving thread sits in batch_wait 23..31.
    step = [("%gather.1 = f32[8,128]{1,0} gather(%t)", 0, 2, "ps.pull"),
            ("%gather.2 = f32[8,128]{1,0} gather(%s)", 2, 1, "ps.state_pull"),
            ("%fusion.1 = f32[8,128]{1,0} fusion(%p)", 3, 1, "ps.compute"),
            ("%fusion.2 = f32[99,128]{1,0} fusion(%p)", 4, 4, "ps.state_push"),
            ("%fusion.3 = f32[64,128]{1,0} fusion(%p)", 8, 1, "ps.push")]
    ops = [
        [trace._short(n), (at + s) * MS, d * MS, scope]
        for at in (0, 10, 30) for n, s, d, scope in step
    ]
    ops[9][2] = 2 * MS  # step B's push runs to the program's end
    ops.append(["copy.1 f32[99,128]", 20 * MS, 4 * MS, None])
    modules = [["jit_step(1)", 0, 10 * MS], ["jit_step(1)", 10 * MS, 10 * MS],
               ["jit_copy(2)", 20 * MS, 4 * MS], ["jit_step(1)", 30 * MS, 10 * MS]]
    half = MS // 2
    train = [[trace.WINDOW, 0, 40 * MS],
             ["fps.train.publish", 19 * MS, 7 * MS],
             ["fps.train.publish_enqueue", 19 * MS, MS],
             ["fps.train.publish_sync", 20 * MS, 5 * MS],
             ["fps.train.hooks", 26 * MS, MS],
             ["fps.train.batch_wait", 27 * MS, 2 * MS + half],
             ["fps.train.pull_compute_push", 29 * MS + half, half]]
    serving = [["fps.serving.batch_wait", 23 * MS, 8 * MS]]
    return _trace(ops, modules, [train, serving])


def test_scopes_sum_to_the_step():
    got = program_trace.reduce(_hand_built(), "jit_step")
    assert got["steps"] == 3
    assert got["step_mean_ms"] == got["step_median_ms"] == pytest.approx(10.0)
    assert got["scope_ms"] == {
        "ps.pull": pytest.approx(2.0), "ps.state_pull": pytest.approx(1.0),
        "ps.compute": pytest.approx(1.0), "ps.state_push": pytest.approx(4.0),
        "ps.push": pytest.approx(4 / 3),
    }
    # two steps leave a 1 ms hole under no scope: 2 of 30 ms
    assert got["unscoped_share"] == pytest.approx(2 / 30)
    assert sum(got["scope_ms"].values()) == pytest.approx(
        got["step_mean_ms"] * (1 - got["unscoped_share"])
    )


def test_the_innermost_span_of_each_thread_wins_a_gap():
    got = program_trace.reduce(_hand_built(), "jit_step")
    # the 6 ms gap 24..30: sync covers 1, publish 2, hooks 1, batch_wait 2.5:
    # no span of the training thread covers more than half; the serving
    # thread's batch_wait covers all of it
    assert got["idle_between_programs_ms"] == pytest.approx(6.0)
    assert got["idle_by_thread_ms"] == {
        "train": {program_trace.NO_SPAN: pytest.approx(6.0)},
        "serving": {"fps.serving.batch_wait": pytest.approx(6.0)},
    }
    assert got["idle_attributed_share"] == pytest.approx(1.0)
    # 24..26 of the gap lies under the one publish
    assert got["publishes"] == 1
    assert got["publish_idle_ms"] == pytest.approx(2.0)

    # with the wait for the next batch stretched over the gap, its innermost
    # span wins it: batch_wait 24.5..29.5 covers 5 of the 6 ms, sync 20..25 one
    longer = _hand_built()
    longer["host"][0]["events"][5] = [
        "fps.train.batch_wait", 24 * MS + MS // 2, 5 * MS,
    ]
    del longer["host"][0]["events"][4]  # hooks
    got = program_trace.reduce(longer, "jit_step")
    assert got["idle_by_thread_ms"]["train"] == {
        "fps.train.batch_wait": pytest.approx(6.0),
    }

    # a child that covers most of a gap wins over the parent that covers all
    nested = _hand_built()
    nested["host"][0]["events"][1:4] = [
        ["fps.train.publish", 19 * MS, 11 * MS],
        ["fps.train.publish_enqueue", 19 * MS, MS],
        ["fps.train.publish_sync", 20 * MS, 9 * MS],
    ]
    del nested["host"][0]["events"][4:]
    got = program_trace.reduce(nested, "jit_step")
    assert got["idle_by_thread_ms"]["train"] == {
        "fps.train.publish_sync": pytest.approx(6.0),
    }
    assert got["publish_idle_ms"] == pytest.approx(6.0)


def test_a_gap_inside_a_program_is_the_devices():
    got = program_trace.reduce(_hand_built(), "jit_step")
    # step A's hole 9..10 and step C's 39..40
    assert got["idle_in_program_ms"] == pytest.approx(2.0)
    # no host thread is charged with them
    assert sum(got["idle_by_thread_ms"]["train"].values()) == pytest.approx(6.0)


def test_an_op_nested_in_another_counts_once_and_takes_its_scope():
    # a while 0..6 under ps.push holds two body ops, one with no name of its
    # own; 2 ms of the while are its own
    ops = [["while.1 (f32[8])", 0, 6 * MS, "ps.push"],
           ["fusion.1 f32[8]", 1 * MS, 2 * MS, None],
           ["fusion.2 f32[8]", 3 * MS, 2 * MS, "ps.pull"]]
    got = program_trace.reduce(
        _trace(ops, [["jit_step(1)", 0, 6 * MS]], []), "jit_step"
    )
    assert got["scope_ms"] == {
        "ps.push": pytest.approx(4.0), "ps.pull": pytest.approx(2.0),
    }
    assert got["unscoped_share"] == pytest.approx(0.0)
    # no fps.* span at all: nothing to attribute idling to
    assert got["idle_attributed_share"] is None and got["publish_idle_ms"] is None


def test_a_program_without_the_names_reduces_to_empty_tables():
    bare = _hand_built()
    bare["host"] = [{"name": "python3", "events": [[trace.WINDOW, 0, 40 * MS]]}]
    for op in bare["devices"][0]["ops"]:
        op[3] = None
    got = program_trace.reduce(bare, "jit_step")
    assert got["scope_ms"] == {} and got["unscoped_share"] is None
    assert got["idle_by_thread_ms"] == {} and got["idle_attributed_share"] is None
    assert program_trace.reduce({"devices": [], "host": []}, "jit_step") is None


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _message(*fields):
    """A serialized protobuf message from (number, int | bytes | str |
    float) fields: varint, length-delimited, or a fixed 64-bit double."""
    import struct

    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        elif isinstance(value, float):
            out += _varint(number << 3 | 1) + struct.pack("<d", value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(value)) + value
    return out


def test_an_ops_scope_is_the_innermost_of_its_metadatas_tf_op(tmp_path):
    # xplane.proto's numbers: XSpace.planes 1; XPlane.name 2, .lines 3,
    # .event_metadata 4, .stat_metadata 5; a map entry's key 1, value 2;
    # XEventMetadata.id 1, .name 2, .stats 5; XStat.metadata_id 1,
    # .double_value 2 (fixed 64), .uint64_value 3, .str_value 5, .ref_value 7
    def event(key, name, *stats):
        return (4, _message((1, key), (2, _message(
            (1, key), (2, name), *[(5, _message(*st)) for st in stats]
        ))))

    def stat_name(key, name):
        return (5, _message((1, key), (2, _message((1, key), (2, name)))))

    push = "jit(step)/jit(main)/ps.compute/ps.state_push/scatter-add:"
    device = _message(
        (1, 3), (2, "/device:TPU:0"), (3, _message((1, 9), (2, "XLA Ops"))),
        event(1, "%fusion.2 = f32[8,128]{1,0} fusion(%p)",
              ((1, 11), (3, 4096)), ((1, 10), (5, push))),
        event(2, "%gather.1 = f32[8,128]{1,0} gather(%t)",
              ((1, 12), (2, 1.5)), ((1, 10), (7, 20))),
        event(3, "%copy.1 = f32[8]{0} copy(%q)", ((1, 10), (5, "jit(copy)/copy:"))),
        event(4, "%fusion.9 = f32[8]{0} fusion(%q)"),
        stat_name(10, "tf_op"), stat_name(11, "flops"), stat_name(12, "scale"),
        stat_name(20, "jit(step)/while/body/ps.pull/gather:"),
    )
    host = _message(
        (2, "/host:CPU"),
        event(1, "fps.train.publish", ((1, 10), (5, "x/ps.pull/y"))),
        stat_name(10, "tf_op"),
    )
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_message((1, device), (1, host), (4, "hostname")))
    assert program_trace.op_scopes(str(path)) == {"/device:TPU:0": {
        "%fusion.2 = f32[8,128]{1,0} fusion(%p)": "ps.state_push",
        "%gather.1 = f32[8,128]{1,0} gather(%t)": "ps.pull",
    }}
    scope = program_trace._innermost_scope
    assert scope("jit(step)/jit(main)/ps.pull/gather") == "ps.pull"
    assert scope("jit(step)/jit(main)/maps.pull/mul") is None
    assert scope("ps.push") == "ps.push" and scope("") is None


def test_the_walk_skips_fixed_width_fields_and_hands_out_nested_bytes():
    blob = _message((1, 300), (2, "name"), (3, 1.5), (4, _message((1, 2**40))))
    got = {k: (bytes(v) if isinstance(v, memoryview) else v)
           for k, v in program_trace._fields(memoryview(blob))}
    assert got == {1: 300, 2: b"name", 3: None, 4: _message((1, 2**40))}


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        return program_trace.reduce(json.load(f)["trace"], "jit_step")


# Seventeen whole step programs of cell 3 round one publish on a TPU v5e (my
# chip run, PR 26).  The figures were worked out once outside the reduction,
# by painting every op, program and fps.* span onto a nanosecond timeline of
# the 131,907,905 ns window: the step programs cover 122,393,194 ns, of which
# ops under a ps.* scope cover 122,386,290 (pull 9,534,560, state_pull
# 9,292,204, compute 1,545,011, state_push 86,687,961, push 15,326,554); of
# 423 idle runs those that lie wholly inside one program make 871 ns, the
# others 1,435,564 ns, of which some fps.* span covers 1,434,148 and the one
# fps.train.publish (and its publish_sync) 1,431,202.
@pytest.mark.parametrize("key, value", [
    ("window_s", 0.131907905),
    ("steps", 17),
    ("step_mean_ms", 122393194 / 17 / 1e6),
    ("step_median_ms", 7.199205),
    ("unscoped_share", 1 - 122386290 / 122393194),
    ("idle_in_program_ms", 871e-6),
    ("idle_between_programs_ms", 1.435564),
    ("idle_attributed_share", 1434148 / 1435564),
    ("publishes", 1),
    ("publish_idle_ms", 1.431202),
])
def test_recorded_trace_reduces_to_the_pinned_numbers(recorded, key, value):
    assert recorded[key] == pytest.approx(value, rel=1e-9, abs=1e-15)


def test_recorded_trace_by_scope_and_by_thread(recorded):
    assert recorded["scope_ms"] == {
        "ps.pull": pytest.approx(9534560 / 17e6, rel=1e-9),
        "ps.state_pull": pytest.approx(9292204 / 17e6, rel=1e-9),
        "ps.compute": pytest.approx(1545011 / 17e6, rel=1e-9),
        "ps.state_push": pytest.approx(86687961 / 17e6, rel=1e-9),
        "ps.push": pytest.approx(15326554 / 17e6, rel=1e-9),
    }
    assert sum(recorded["scope_ms"].values()) == pytest.approx(
        recorded["step_mean_ms"] * (1 - recorded["unscoped_share"]), rel=1e-9
    )
    # the device runs dry while the training thread is still inside the
    # publish's block_until_ready, and the serving thread inside its fetch
    gaps = recorded["idle_by_thread_ms"]
    assert gaps["train"] == {
        "fps.train.publish_sync": pytest.approx(1.431202),
        "fps.train.pull_compute_push": pytest.approx(0.002946),
        program_trace.NO_SPAN: pytest.approx(0.001416),
    }
    assert gaps["serving"] == {
        "fps.serving.topk_ready": pytest.approx(1.417052),
        program_trace.NO_SPAN: pytest.approx(0.018512),
    }
    assert gaps["ingest"] == {program_trace.NO_SPAN: pytest.approx(1.435564)}


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------


def _ctx(spans=()):
    return {
        "cfg": {"name": "mf-hugewiki-k128", "family": "mf"},
        "traffic": {"name": "no-such-traffic"}, "chips": 1, "trace": None,
        "peaks": None, "spans": list(spans), "counters": {},
    }


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_reports_nothing_without_a_trace_or_a_span(name):
    reader = spec.metric_reader(name)
    assert reader is not None and reader.__doc__
    assert reader.read(_ctx()) is None
    # a trace was reduced, but the directory holds no file to read again
    assert reader.read({**_ctx(), "trace": {"step_device_ms": 7.2}}) is None


def _span(component, name, ms, parent_id=None):
    return {"component": component, "name": name, "start": 0.0,
            "dur": ms / 1e3, "parent_id": parent_id}


@pytest.mark.parametrize("name, want", [
    ("driver.batch_wait_ms", 0.004),
    ("driver.dispatch_ms", 7.0),
    ("serving.queue_wait_p50_ms", 30.0),
    ("serving.queue_wait_p95_ms", 45.0),
    ("serving.topk_device_wait_ms", 60.0),
])
def test_a_span_reader_reads_its_own_component_and_name(name, want):
    spans = [
        _span("train", "batch_wait", 0.004), _span("serving", "batch_wait", 2.0),
        _span("train", "pull_compute_push", 6.0),
        _span("train", "pull_compute_push", 7.0),
        _span("train", "pull_compute_push", 9.0),
        # three queries in the batch that waited 60 ms for the device, one
        # in each of two that waited 1 ms: a query's median is 60, a batch's 1
        _span("serving", "queue_wait", 10.0, "a"),
        _span("serving", "queue_wait", 30.0, "a"),
        _span("serving", "queue_wait", 50.0, "a"),
        _span("serving", "topk_ready", 60.0, "a"), _span("serving", "topk", 61.0),
        _span("serving", "queue_wait", 30.0, "b"),
        _span("serving", "topk_ready", 1.0, "b"),
        _span("serving", "queue_wait", 30.0, "c"),
        _span("serving", "topk_ready", 1.0, "c"),
        _span("serving", "lookup_ready", 500.0, "d"),
        _span("serving", "queue_wait", 30.0, "d"),
    ]
    assert spec.metric_reader(name).read(_ctx(spans)) == pytest.approx(want)


def test_the_new_entries_are_appended_and_lint_clean():
    bench = spec.load_benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-len(NEW_METRICS):] == NEW_METRICS
    assert lint.problems(spec.ROOT) == []
    serve = "mf-hugewiki-k128.train-zipf-serve-topk"
    reported = {m["name"] for m in spec.metrics_of(bench, "per_layer", serve)}
    assert set(NEW_METRICS) <= reported
    fm = {m["name"] for m in spec.metrics_of(
        bench, "per_layer", "fm-criteo.train-fields-uniform"
    )}
    assert "step.state_update_device_ms" not in fm
    assert not {n for n in NEW_METRICS if n.startswith("serving.")} & fm
