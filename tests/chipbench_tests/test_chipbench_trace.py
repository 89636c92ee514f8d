"""The trace reduction, pinned on a trace recorded on the chip and on
timelines small enough to work out by hand."""
import json
import os

import pytest

from chipbench import spec, trace

FIXTURE = os.path.join(spec.BENCH_DIR, "fixtures", "mf_train_zipf_v5e.json")
MS = 1_000_000


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        return trace.reduce(json.load(f)["planes"], "jit_step")


# Five whole step programs of cell 1 on a TPU v5e (my chip run, PR 25).  The
# figures were worked out once outside the reduction, by painting every op
# onto a nanosecond timeline of the 39,506,882 ns window: 39,498,606 ns
# painted, 8,276 ns not — 8,072 ns of it between programs and 204 ns inside
# one; programs of 7184233, 7184971, 7183718, 7176218, 7179201 ns with gaps
# of 979, 1256, 978, 977 ns.
@pytest.mark.parametrize("key, value", [
    ("window_s", 0.039506882),
    ("busy_s", 0.039498606),
    ("idle_share", 8276 / 39506882),
    ("chips", 1),
    ("steps", 5),
    ("step_device_ms", 7.183718),
    ("host_gap_ms", 0.0009785),
    ("collective_ms_per_step", 0.0),
])
def test_recorded_trace_reduces_to_the_pinned_numbers(recorded, key, value):
    assert recorded[key] == pytest.approx(value, rel=1e-9, abs=1e-15)


def test_recorded_trace_breakdown(recorded):
    ops = dict(recorded["breakdown"]["device_ops"])
    assert len(ops) == 10
    # the dense pass over the whole user array is most of the step
    top = recorded["breakdown"]["device_ops"][0]
    assert top[0] == "fusion.2 f32[5008260,128]"
    assert top[1] == pytest.approx(0.025961471, rel=1e-9)
    gaps = dict(recorded["breakdown"]["idle_gaps"])
    assert gaps == {
        "chipbench.driver_dispatch": pytest.approx(8072e-9),
        "inside a device program": pytest.approx(204e-9),
    }


def _planes(ops, modules, notes, device="/device:TPU:0"):
    return [
        {"name": device, "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops},
        ]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": notes}]},
    ]


def test_hand_built_timeline():
    # window 0..16 ms; program A 0..7 (ops 0..3 and 4..7: 1 ms hole inside),
    # program B 8..15 (ops 8..11 and an all-reduce 11..15); idle 7..8 while
    # the host dispatches, idle 15..16 covered by nothing
    planes = _planes(
        ops=[["%fusion.1 = f32[8,128]{1,0} fusion(%p)", 0, 3 * MS],
             ["%copy.2 = f32[8]{0} copy(%q)", 4 * MS, 3 * MS],
             ["%fusion.1 = f32[8,128]{1,0} fusion(%p)", 8 * MS, 3 * MS],
             ["%all-reduce.3 = f32[4]{0} all-reduce(%r)", 11 * MS, 4 * MS]],
        modules=[["jit_step(1)", 0, 7 * MS], ["jit_step(1)", 8 * MS, 7 * MS],
                 ["jit_other(2)", 20 * MS, MS]],
        notes=[[trace.WINDOW, 0, 16 * MS],
               ["chipbench.driver_dispatch", 7 * MS, MS // 2],
               ["chipbench.hook", 7 * MS + MS // 2, MS // 4]],
    )
    got = trace.reduce(planes, "jit_step")
    assert got["window_s"] == pytest.approx(0.016)
    assert got["busy_s"] == pytest.approx(0.013)
    assert got["idle_share"] == pytest.approx(3 / 16)
    assert got["steps"] == 2 and got["step_device_ms"] == 7.0
    assert got["host_gap_ms"] == 1.0
    assert got["collective_ms_per_step"] == 2.0
    assert got["breakdown"]["device_ops"] == [
        ["fusion.1 f32[8,128]", 0.006], ["all-reduce.3 f32[4]", 0.004],
        ["copy.2 f32[8]", 0.003],
    ]
    assert dict(got["breakdown"]["idle_gaps"]) == {
        "inside a device program": 0.001,
        "chipbench.driver_dispatch": 0.001,
        "unattributed": 0.001,
    }


def test_busy_is_a_union_and_averaged_over_chips():
    # chip 0: overlapping ops 0..4 and 2..6 (union 6 of 10); chip 1: 0..2
    one = _planes(
        ops=[["a", 0, 4 * MS], ["b", 2 * MS, 4 * MS]],
        modules=[["jit_step(1)", 0, 6 * MS]],
        notes=[[trace.WINDOW, 0, 10 * MS]],
    )
    two = one + [{"name": "/device:TPU:1", "lines": [
        {"name": "XLA Modules", "events": [["jit_step(1)", 0, 2 * MS]]},
        {"name": "XLA Ops", "events": [["a", 0, 2 * MS]]},
    ]}]
    assert trace.reduce(one, "jit_step")["busy_s"] == pytest.approx(0.006)
    both = trace.reduce(two, "jit_step")
    assert both["chips"] == 2
    assert both["busy_s"] == pytest.approx(0.004)  # (6 + 2) / 2
    assert both["step_device_ms"] == 6.0  # the busiest chip's


def test_gap_between_steps_excludes_other_programs_work():
    # 4 ms between two steps, 3 of them a publish copy: 1 ms of idle
    planes = _planes(
        ops=[["s", 0, 2 * MS], ["%copy.1 = f32[9]{0} copy(%t)", 2 * MS, 3 * MS],
             ["s", 6 * MS, 2 * MS]],
        modules=[["jit_step(1)", 0, 2 * MS], ["jit_copy(3)", 2 * MS, 3 * MS],
                 ["jit_step(1)", 6 * MS, 2 * MS]],
        notes=[[trace.WINDOW, 0, 8 * MS]],
    )
    assert trace.reduce(planes, "jit_step")["host_gap_ms"] == 1.0


def test_events_are_clipped_to_the_window_and_no_device_means_none():
    planes = _planes(
        ops=[["a", -2 * MS, 4 * MS], ["b", 9 * MS, 4 * MS]],
        modules=[["jit_step(1)", -2 * MS, 4 * MS]],
        notes=[[trace.WINDOW, 0, 10 * MS]],
    )
    got = trace.reduce(planes, "jit_step")
    assert got["busy_s"] == pytest.approx(0.003) and got["steps"] == 0
    assert got["step_device_ms"] is None and got["host_gap_ms"] is None
    assert trace.reduce(planes[1:], "jit_step") is None


def test_union_merges_touching_and_nested_intervals():
    assert trace.union([(5, 7), (0, 2), (1, 3), (3, 4), (6, 6)]) == [
        (0, 4), (5, 7),
    ]
