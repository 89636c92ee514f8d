"""Cell 4, ``fm-criteo-ps4.train-fields-uniform``: the deployment's arithmetic
as its configuration file states it, the real cell's dry run on four virtual
devices, and the reader of ``collectives.device_ms`` on timelines small enough
to work out by hand."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import lint, spec, trace
from flink_parameter_server_tpu.core.store import StoreSpec

CELL = "fm-criteo-ps4.train-fields-uniform"
BENCH = spec.load_benchmark()
CFG = spec.resolve(BENCH, CELL, dry_run=False)["cfg"]
SHARDS = CFG["mesh"]["ps"]
MS = 1_000_000


class _FourShards:
    """All ``StoreSpec`` asks of a mesh for its arithmetic."""
    axis_names, shape = ("dp", "ps"), CFG["mesh"]


# the program's own partition of the table, nothing allocated
STORE = StoreSpec(
    capacity=CFG["num_features"], value_shape=(1 + CFG["dim"],), mesh=_FourShards()
)


def _ids_per_example_by_shard(cards, dense: int, per_shard: int) -> np.ndarray:
    """How many of an example's ids each contiguous row block owns: the
    integer fields are one fixed row each, a categorical field is uniform
    over its own rows (``chipbench/datagen.click_batches``)."""
    owned = np.zeros(SHARDS)
    owned[0] += dense  # rows 0..dense-1
    first = dense
    for card in cards:
        for s in range(SHARDS):
            lo, hi = s * per_shard, (s + 1) * per_shard
            owned[s] += max(0, min(first + card, hi) - max(first, lo)) / card
        first += card
    return owned


@pytest.mark.parametrize("what, got, want", [
    ("categorical fields", len(CFG["field_cardinalities"]), 26),
    ("categorical rows", sum(CFG["field_cardinalities"]), 187_767_399),
    ("rows", CFG["num_features"], 187_767_399 + 13),
    ("rows = integer fields + categorical rows", CFG["num_features"],
     CFG["dense_fields"] + sum(CFG["field_cardinalities"])),
    ("fields", CFG["fields"], 13 + 26),
    ("rows a shard", STORE.rows_per_shard, 46_941_856),
    ("padding rows", STORE.padded_capacity - STORE.capacity, 12),
    ("rows a shard, as the file says",
     CFG["assumed"]["partitioning"]["rows_per_shard"], 46_941_856),
    ("the source's sizes are the ones run", CFG["source_sizes"]["field_cardinalities"],
     CFG["field_cardinalities"]),
    ("index range", max(CFG["field_cardinalities"]) <= CFG["source_sizes"]["max_ind_range"]
     == 40_000_000, True),
    ("nothing reduced", CFG["reduced"], []),
    ("mesh", CFG["mesh"], {"dp": 1, "ps": 4}),
    # 24 padded lanes of float32 a row: more than one 16 GB chip, under four
    ("one chip cannot hold it", 24 * 4 * CFG["num_features"] > 16e9, True),
    ("a shard fits a chip twice (the driver keeps a copy)",
     2 * 24 * 4 * STORE.rows_per_shard < 16e9, True),
])
def test_the_deployments_arithmetic(what, got, want):
    assert got == want, what


@pytest.mark.parametrize("shard, percent", enumerate([56.9, 25.9, 3.7, 13.5]))
def test_id_share_by_shard_from_the_cardinalities(shard, percent):
    owned = _ids_per_example_by_shard(
        CFG["field_cardinalities"], CFG["dense_fields"], STORE.rows_per_shard,
    )
    assert owned.sum() == pytest.approx(CFG["fields"])
    assert 100 * owned[shard] / CFG["fields"] == pytest.approx(percent, abs=0.05)
    stated = CFG["assumed"]["partitioning"]
    assert stated["id_share_percent_by_shard"][shard] == percent
    assert stated["ids_per_example_by_shard"][shard] == pytest.approx(
        owned[shard], abs=0.005
    )


def test_same_shapes_reference_and_allowances_as_fm_criteo():
    one_chip = spec.resolve(
        BENCH, "fm-criteo.train-fields-uniform", dry_run=False
    )["cfg"]
    for key in (
        "family", "dense_fields", "fields", "dim", "dtype", "batch",
        "learning_rate", "init_scale", "pool_batches", "driver", "reference",
    ):
        assert CFG[key] == one_chip[key], key
    assert CFG["guarantees"][:2] == one_chip["guarantees"]


def test_dry_run_sizes_split_into_four_equal_aligned_shards():
    dry = CFG["dry_run"]
    assert dry["num_features"] == 13 + sum(dry["field_cardinalities"])
    assert dry["num_features"] % (8 * SHARDS) == 0
    assert min(dry["field_cardinalities"]) == 3  # hot rows stay in


def test_the_real_cell_dry_runs_on_four_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.ROOT)
    env.pop("XLA_FLAGS", None)  # run.py asks for the cell's four itself
    done = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL, "--seed",
         str(2**31 + 11), "--seconds", "0.5", "--trace", "0", "--cpu-dry-run"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] and last["failed"] == 0 and last["failures"] == []
    # and not pull_push_p50_ms, which the cell does not report
    assert last["metric_names"] == ["setup_s", "updates_per_s_chip"]
    assert '"mesh": {"dp": 1, "ps": 4}' in done.stderr


# -- collectives.device_ms ---------------------------------------------------
def _device(n, ops, modules):
    return {"name": f"/device:TPU:{n}", "lines": [
        {"name": "XLA Modules", "events": modules},
        {"name": "XLA Ops", "events": ops},
    ]}


def _host(window_ms):
    return {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        [trace.WINDOW, 0, window_ms * MS],
    ]}]}


def _step(at_ms, collectives, gather_ms=4):
    """One 10 ms step program from ``at_ms``: a gather fusion that ends 4 ms
    in, then the given ``(name, ms)`` collectives back to back, then a
    scatter fusion to the program's end."""
    t = (at_ms + 4) * MS
    ops = [["%fusion.1 = f32[8,17]{0,1} fusion(...)", t - gather_ms * MS, gather_ms * MS]]
    for name, ms in collectives:
        ops.append([f"%{name} = f32[8,39,17]{{1,0,2}} {name}(...)", t, int(ms * MS)])
        t += int(ms * MS)
    ops.append(["%fusion.2 = f32[64,17]{0,1} fusion(...)", t, (at_ms + 10) * MS - t])
    return ops, [["jit_step(1)", at_ms * MS, 10 * MS]]


def _chip(n, per_step, **kw):
    ops, modules = [], []
    for i, collectives in enumerate(per_step):
        o, m = _step(10 * i, collectives, **kw)
        ops, modules = ops + o, modules + m
    return _device(n, ops, modules)


TWO_CHIPS = [
    # chip 0 idles a millisecond at the head of each step program...
    _chip(0, [[("all-reduce", 2.0)]] * 2, gather_ms=3),
    # ... so chip 1 is the busiest: 2 + 1 ms of collectives in each of 2 steps
    _chip(1, [[("all-reduce", 2.0), ("all-gather", 1.0)]] * 2),
    _host(20),
]
ONE_CHIP = [_chip(0, [[]] * 2), _host(20)]
# a collective with an async start and done counts from its first op's start
# to its last op's end once (the union), not twice
OVERLAPPING = [
    _chip(0, [[]], gather_ms=3), _device(1, _step(0, [])[0] + [
        ["%all-reduce-start = f32[8]{0} all-reduce-start(...)", 4 * MS, 2 * MS],
        ["%all-reduce-done = f32[8]{0} all-reduce-done(...)", 5 * MS, 2 * MS],
    ], [["jit_step(1)", 0, 10 * MS]]), _host(10),
]


@pytest.mark.parametrize("planes, want", [
    (TWO_CHIPS, 3.0),
    (ONE_CHIP, 0.0),
    (OVERLAPPING, 3.0),
], ids=["busiest-of-two-chips", "one-chip-no-collective", "start-done-overlap"])
def test_collectives_device_ms_on_hand_made_planes(planes, want):
    reader = spec.metric_reader("collectives.device_ms")
    reduced = trace.reduce(planes, "jit_step")
    assert reader.read({"trace": reduced}) == pytest.approx(want)


def test_collectives_device_ms_reports_nothing_without_a_trace():
    # an untraced run, or a dry run whose profile holds no device plane
    assert spec.metric_reader("collectives.device_ms").read({"trace": None}) is None


def test_benchmark_lints_clean_with_its_one_four_chip_cell():
    assert lint.problems(spec.ROOT) == []
    assert [w["name"] for w in BENCH["workloads"] if w["chips"] == 4] == [CELL]


@pytest.mark.parametrize("cell, reports, lacks", [
    ("mf-hugewiki-k128.train-zipf-serve-topk", [], ["collectives.device_ms"]),
    ("fm-criteo.train-fields-uniform", [], ["collectives.device_ms"]),
    (CELL, ["collectives.device_ms", "store.pull_device_ms", "store.push_device_ms",
            "step.device_ms", "step.unscoped_share", "device.peak_hbm_bytes",
            "store.gather_scatter_roofline"],
     ["step.state_update_device_ms", "serving.publish_idle_ms"]),
])
def test_which_cell_reports_which_layer_metric(cell, reports, lacks):
    listed = {m["name"] for m in spec.metrics_of(BENCH, "per_layer", cell)}
    assert set(reports) <= listed and not set(lacks) & listed


def test_benchmark_entry_of_the_metric_names_only_the_four_chip_cell():
    entry = next(m for m in BENCH["per_layer"] if m["name"] == "collectives.device_ms")
    assert entry["workloads"] == [CELL] and entry["moves"] == "updates_per_s_chip"
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 4 and cell["traffic"] == "train-fields-uniform"
    reports = [m["name"] for m in spec.metrics_of(BENCH, "end_to_end", CELL)]
    assert reports == ["updates_per_s_chip", "setup_s"]
