"""``mf-hugewiki-k128-dp4``: Hugewiki uncut over four keyed workers, the
numbers of its partitioning, the keyed pool the family stages, the plain
reference's allowances on the keyed step (a bfloat16 delta fails them), its
four readers, and the cell's dry run on four virtual devices."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import lint, peaks, program_trace, run, spec
from flink_parameter_server_tpu.core.transform import make_train_step
from flink_parameter_server_tpu.models import matrix_factorization as mfm

CELL = "mf-hugewiki-k128-dp4.train-zipf"
CONFIG = "mf-hugewiki-k128-dp4"
BENCH = spec.load_benchmark()
FULL = spec.resolve(BENCH, CELL, dry_run=False)
DRY = spec.resolve(BENCH, CELL, dry_run=True)
FAM = spec.family("mf_keyed")
READERS = (
    "collectives.delta_reduce_device_ms", "step.keyed_state_update_device_ms",
    "step.keyed_live_share", "store.state_update_roofline",
)


def test_the_configuration_is_hugewiki_uncut_over_four_workers():
    cfg = FULL["cfg"]
    one = spec.load_json(os.path.join(spec.BENCH_DIR, "configs", "mf-hugewiki-k128.json"))
    assert cfg["reduced"] == [] and cfg["family"] == "mf_keyed"
    assert cfg["mesh"] == {"dp": 4, "ps": 1}
    assert cfg["source_sizes"] == one["source_sizes"]
    for key in ("num_users", "num_items", "dim", "dtype"):
        assert cfg[key] == cfg["source_sizes"][key], key
    assert (cfg["num_users"], cfg["num_items"], cfg["dim"]) == (50_082_603, 39_780, 128)
    assert (cfg["batch"], cfg["batch_per_worker"], cfg["pool_batches"]) == (
        262_144, 65_536, 64)
    assert cfg["batch"] == 4 * cfg["batch_per_worker"] == 4 * one["batch"]
    assert cfg["batch"] * cfg["pool_batches"] == one["batch"] * one["pool_batches"]
    assert (cfg["learning_rate"], cfg["init_scale"], cfg["worker_state_seed"]) == (
        5e-5, 0.1, 0)
    assert cfg["driver"] == {"steps_per_call": 1, "dump_model": False}
    assert cfg["guarantees"][:2] == one["guarantees"][:2] and len(cfg["guarantees"]) == 3
    assert "partitioned by user id over 4 workers" in cfg["guarantees"][2]
    assert len(cfg["source"]) <= 200 and FULL["traffic"] == "train-zipf"
    assert {"learning_rate", "batch_per_worker", "pool_batches", "partition", "mesh"} <= set(
        cfg["assumed"])
    check, theirs = cfg["reference"], one["reference"]
    assert check["file"] == "chipbench/references/mf_keyed.py"
    assert (check["batches"], check["delta_rtol"]) == (theirs["batches"], theirs["delta_rtol"])
    # the second term scales with the rate; the row is rounded once a step
    assert check["delta_atol"] == pytest.approx(
        theirs["delta_atol"] * cfg["learning_rate"] / one["learning_rate"])
    assert 0 < check["row_ulps"] <= theirs["row_ulps"]


def test_the_partitioning_numbers_are_the_programs():
    cfg, part = FULL["cfg"], FULL["cfg"]["partition"]
    rows = mfm.worker_block_rows(cfg["num_users"], cfg["mesh"]["dp"])
    assert rows == part["rows_per_worker"] == 12_520_656 and rows % 8 == 0
    assert part["workers"] == cfg["mesh"]["dp"] == 4
    assert 4 * rows - cfg["num_users"] == part["padding_rows"] == 21
    assert cfg["num_users"] % 4 == 3  # 4 does not divide it
    assert cfg["num_users"] - 3 * rows == 12_520_635  # the last block's users
    assert rows * 128 * 4 == part["state_bytes_per_worker"] == 6_410_575_872
    hbm = peaks.PEAKS["TPU v5 lite"]["hbm_bytes"]
    assert 0.40 < part["state_bytes_per_worker"] / hbm < 0.41  # over the 25% floor
    assert cfg["num_users"] * 128 * 4 > hbm  # what one chip cannot hold
    dry = DRY["cfg"]
    assert dry["num_users"] % 4 and dry["batch"] == 4 * dry["batch_per_worker"]


def test_the_family_stages_a_full_keyed_pool_through_the_programs_router(monkeypatch):
    from flink_parameter_server_tpu.telemetry import registry as registry_mod

    fresh = registry_mod.MetricsRegistry()
    monkeypatch.setattr(registry_mod, "get_registry", lambda: fresh)
    monkeypatch.setattr(
        "flink_parameter_server_tpu.data.keyed.get_registry", lambda: fresh)
    cfg = DRY["cfg"]
    pool = FAM.host_batches(cfg, DRY["traffic_spec"], 2**31 + 5, cfg["pool_batches"])
    assert len(pool) == cfg["pool_batches"]
    rows = mfm.worker_block_rows(cfg["num_users"], 4)
    block = np.repeat(np.arange(4), cfg["batch_per_worker"])
    for b in pool:
        assert b["mask"].all() and len(b["user"]) == cfg["batch"]
        assert (b["user"] // rows == block).all()
        assert b["user"].dtype == np.int32 and b["user"].max() < cfg["num_users"]
    # the records are the flat stream's, each once
    from chipbench import datagen

    flat = datagen.rating_batches(
        cfg["num_users"], cfg["num_items"], cfg["batch"],
        cfg["pool_batches"] + FAM.SPARE, item_keys=DRY["traffic_spec"]["keys"],
        seed=2**31 + 5,
    )
    sent = np.concatenate([b["rating"] for b in flat])
    got = np.concatenate([b["rating"] for b in pool])
    assert np.isin(got, sent).all() and len(got) == cfg["pool_batches"] * cfg["batch"]
    assert fresh.snapshot()["keyed_records"][0]["value"] == len(got)
    reader = spec.metric_reader("step.keyed_live_share")
    assert reader.__doc__ and reader.read(_ctx()) == 100.0
    assert FAM.hbm_bytes_per_step(FULL["cfg"]) == 3.0 * 262_144 * 256 * 4
    assert FAM.STEP_PROGRAM == "jit_step"


def _checked(cfg, seed, logic_of=None):
    """The check ``chipbench/run.py`` makes, in process at the dry-run sizes
    on four virtual devices: the checked batches through the jitted step
    under the configuration's mesh, then ``_check_rows``."""
    ref = spec.reference(cfg)
    logic, store = FAM.build(cfg, seed, None)
    assert dict(logic.mesh.shape) == cfg["mesh"] and logic.workers == 4
    if logic_of is not None:
        logic = logic_of(logic)
    batches = FAM.host_batches(
        cfg, DRY["traffic_spec"], seed, cfg["reference"]["batches"]
    )
    ids = ref.touched(batches)
    state = logic.init_state(None)
    before = FAM.rows(store, state, ids)
    step = jax.jit(make_train_step(logic, store.spec))
    table = store.table
    for b in batches:
        table, state, out = step(table, state, b)
        assert int(np.sum(out["keyed_misrouted"])) == 0
    got = FAM.rows(type(store)(store.spec, table), state, ids)
    return run._check_rows(
        cfg["reference"], ref.apply(cfg, before, ids, batches), got, before
    )


@pytest.mark.parametrize("seed", [3, 2**31 + 12, 900_000_011])
def test_the_keyed_step_is_within_the_reference_s_allowances(seed):
    failures, worst = _checked(DRY["cfg"], seed)
    assert failures == [] and 0 < worst["share"] < 0.7, worst


def test_a_bfloat16_delta_fails_the_check():
    def rounded(logic):
        class Updater(mfm.SGDUpdater):
            def delta(self, rating, user_vec, item_vec):
                du, di, pred = super().delta(rating, user_vec, item_vec)
                coarse = lambda d: d.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
                return coarse(du), coarse(di), pred

        logic.updater = Updater(logic.updater.learning_rate)
        return logic

    failures, worst = _checked(DRY["cfg"], 3, logic_of=rounded)
    assert len(failures) == 2 and worst["share"] > 20, worst


def test_the_sequential_reference_is_not_this_cells_yardstick():
    # references/mf.py adds a hot row's deltas one by one onto the float32
    # row; workers that sum their own records first differ from THAT by the
    # roundings of its order, which no allowance names (mf_keyed.py's head)
    cfg = {**DRY["cfg"], "reference": {
        **DRY["cfg"]["reference"], "file": "chipbench/references/mf.py"}}
    failures, worst = _checked(cfg, 3)
    assert worst["rows"] == "item" and worst["share"] > 0.7, worst


def _ctx(**over):
    return {
        "cfg": FULL["cfg"], "traffic": FULL["traffic_spec"], "chips": 4,
        "trace": None, "peaks": None, "spans": [],
        "counters": {"peak_hbm_bytes": 0}, **over,
    }


@pytest.mark.parametrize("name, scopes", [
    ("collectives.delta_reduce_device_ms", ("ps.delta_reduce",)),
    ("step.keyed_state_update_device_ms", ("ps.state_pull", "ps.state_push")),
])
def test_the_scope_readers_read_their_scopes_and_nothing_without_them(
        name, scopes, monkeypatch):
    reader = spec.metric_reader(name)
    assert reader.__doc__ and reader.read(_ctx()) is None
    where = os.path.join(run.OUT_DIR, "trace", CELL)
    by_scope = {"ps.pull": 0.6, "ps.push": 0.9, **{s: 1.25 for s in scopes}}
    monkeypatch.setitem(program_trace._RUNS, where, {"scope_ms": by_scope})
    traced = _ctx(trace={"step_device_ms": 5.0})
    assert reader.read(traced) == pytest.approx(1.25 * len(scopes))
    # the all-reduce is not the push's: an op counts under its innermost scope
    assert spec.metric_reader("store.push_device_ms").read(traced) == pytest.approx(0.9)
    # the parent's program has no such scope: the line leaves the metric out
    monkeypatch.setitem(program_trace._RUNS, where, {"scope_ms": {"ps.pull": 0.6}})
    assert reader.read(traced) is None


def test_the_roofline_reader_counts_one_workers_rows(monkeypatch):
    reader = spec.metric_reader("store.state_update_roofline")
    v5e = peaks.PEAKS["TPU v5 lite"]
    assert reader.__doc__ and reader.read(_ctx(peaks=v5e)) is None
    where = os.path.join(run.OUT_DIR, "trace", CELL)
    monkeypatch.setitem(program_trace._RUNS, where, {
        "scope_ms": {"ps.state_pull": 0.5, "ps.state_push": 1.5}})
    traced = _ctx(trace={"step_device_ms": 5.0}, peaks=v5e)
    least_ms = 3 * 65_536 * 128 * 4 / 819e9 * 1e3  # 0.123 ms of row traffic
    assert reader.read(traced) == pytest.approx(100 * least_ms / 2.0)
    assert 6.0 < reader.read(traced) < 6.3
    assert reader.read(_ctx(trace={"step_device_ms": 5.0})) is None  # no peaks: a dry run


def test_the_live_share_reader_reads_the_routers_counters(monkeypatch):
    from flink_parameter_server_tpu.telemetry import registry as registry_mod

    reader = spec.metric_reader("step.keyed_live_share")
    fresh = registry_mod.MetricsRegistry()
    monkeypatch.setattr(registry_mod, "get_registry", lambda: fresh)
    assert reader.read(_ctx()) is None  # no router ran: one worker, the parent
    fresh.counter("keyed_records", component="ingest").inc(3 * 262_144)
    fresh.counter("keyed_padded_lanes", component="ingest")
    assert reader.read(_ctx()) == 100.0
    fresh.counter("keyed_padded_lanes", component="ingest").inc(262_144)
    assert reader.read(_ctx()) == pytest.approx(75.0)


def test_the_scopes_are_in_the_lowered_step_and_one_worker_has_no_reduce():
    logic, store = FAM.build(DRY["cfg"], 1, None)
    (b,) = FAM.host_batches(DRY["cfg"], DRY["traffic_spec"], 1, 1)
    text = jax.jit(make_train_step(logic, store.spec)).lower(
        store.table, logic.init_state(None), b
    ).compile().as_text()
    for scope in ("jit(step)/ps.push/ps.delta_reduce/reduce_sum",
                  "jit(step)/ps.compute/shard_map/ps.state_pull/",
                  "jit(step)/ps.compute/shard_map/ps.state_push/",
                  "jit(step)/ps.pull/"):
        assert scope in text, scope
    for name, scope in [
        ("jit(step)/ps.push/ps.delta_reduce/reduce_sum", "ps.delta_reduce"),
        ("jit(step)/ps.compute/shard_map/ps.state_pull/jit(_take)/gather",
         "ps.state_pull"),
    ]:
        assert program_trace.SCOPE.findall(name)[-1] == scope
    one = spec.resolve(BENCH, "mf-hugewiki-k128.train-zipf", dry_run=True)["cfg"]
    one_logic, one_store = spec.family("mf").build(one, 1, None)
    (b1,) = spec.family("mf").host_batches(one, DRY["traffic_spec"], 1, 1)
    text = jax.jit(make_train_step(one_logic, one_store.spec)).lower(
        one_store.table, one_logic.init_state(None), b1
    ).as_text(debug_info=True)
    assert "ps.state_push" in text and "ps.delta_reduce" not in text


def test_the_cells_entries_and_its_dry_run():
    # by name, not by place: later cells are appended after this one
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 4 and cell["config"] == CONFIG
    assert cell["traffic"] == "train-zipf"
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == [] and entry["source"] == FULL["cfg"]["source"]
    assert entry["file"] == "chipbench/configs/mf-hugewiki-k128-dp4.json"
    mine = [m for m in BENCH["per_layer"] if m["name"] in READERS]
    assert [m["name"] for m in mine] == list(READERS)
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "updates_per_s_chip"
    assert [m["layer"] for m in mine] == [
        "collectives", "worker step", "worker step", "store gather/scatter"]
    assert [m["source"] for m in mine] == [
        "device_trace", "device_trace", "program_counter", "device_trace"]
    per_layer = {m["name"] for m in spec.metrics_of(BENCH, "per_layer", CELL)}
    assert set(READERS) | {
        "store.pull_device_ms", "store.push_device_ms", "step.compute_device_ms",
        "store.gather_scatter_roofline", "step.unscoped_share", "step.device_ms",
    } <= per_layer
    assert "step.state_update_device_ms" not in per_layer  # cells 1 and 3 list it
    assert {m["name"] for m in spec.metrics_of(BENCH, "end_to_end", CELL)} == {
        "updates_per_s_chip", "setup_s",
    }
    # two of eight cells on four chips: a quarter, rounded down
    four = [w["name"] for w in BENCH["workloads"] if w["chips"] == 4]
    assert CELL in four and len(four) <= max(1, len(BENCH["workloads"]) // 4)
    assert lint.problems(spec.ROOT) == []
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.ROOT)
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL, "--seed",
         str(2**31 + 12), "--seconds", "0.5", "--trace", "1", "--cpu-dry-run"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] and last["failed"] == 0 and last["failures"] == []
    assert "metrics" not in last
    assert {"driver.dispatch_ms", "step.keyed_live_share"} <= set(last["metric_names"])
