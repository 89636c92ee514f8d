"""Cell 12: DiFacto at MLPerf's 40 M index range over four key-partitioned
servers (`difacto-criteo-40m-ps4.train-fields-uniform`): cell 9's rows, rule
and reference on cell 4's record and mesh, the rule run by the shard that
owns the row (`core/store._push_rule_on_shards`)."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import lint, program_trace, run, spec
from flink_parameter_server_tpu.core.transform import make_train_step
from flink_parameter_server_tpu.parallel.mesh import make_mesh

CELL = "difacto-criteo-40m-ps4.train-fields-uniform"
CONFIG = "difacto-criteo-40m-ps4"
BENCH = spec.load_benchmark()
FULL = spec.resolve(BENCH, CELL, dry_run=False)
DRY = spec.resolve(BENCH, CELL, dry_run=True)
CFG = FULL["cfg"]
FAM = spec.family("difacto")
SHARDS = 4
READERS = (
    "store.shard_rule_path_device_ms", "store.shard_rule_path_roofline",
    "store.rule_owner_max_share", "collectives.rule_pull_device_ms",
)


def _config(name):
    return spec.load_json(os.path.join(spec.BENCH_DIR, "configs", name + ".json"))


def test_the_entries_by_name_lint_and_the_quarter_rule():
    # by name, never by place: later cells are appended after this one
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 4 and cell["config"] == CONFIG
    assert cell["traffic"] == "train-fields-uniform" and len(cell["why"]) <= 200
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == [] == CFG["reduced"]
    assert entry["source"] == CFG["source"] and len(entry["source"]) <= 200
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert CFG["family"] == "difacto" and CFG["mesh"] == {"dp": 1, "ps": 4}
    mine = [m for m in BENCH["per_layer"] if m["name"] in READERS]
    assert [m["name"] for m in mine] == list(READERS)
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "updates_per_s_chip"
        reader = spec.metric_reader(m["name"])
        assert reader is not None and reader.__doc__
    assert [m["layer"] for m in mine] == 3 * ["store gather/scatter"] + ["collectives"]
    assert [m["unit"] for m in mine] == ["ms", "%", "%", "ms"]
    assert [m["better"] for m in mine] == ["lower", "higher", "lower", "lower"]
    assert [m["source"] for m in mine] == [
        "device_trace", "device_trace", "program_counter", "device_trace"]
    per_layer = {m["name"] for m in spec.metrics_of(BENCH, "per_layer", CELL)}
    # the general metrics list no cells and read this one as they read cell 4
    assert set(READERS) | {
        "step.device_ms", "store.pull_device_ms", "store.push_device_ms",
        "store.gather_scatter_roofline", "device.idle_share",
        "device.peak_hbm_bytes", "step.unscoped_share", "driver.dispatch_ms",
    } <= per_layer
    # cell 4's and cell 9's own list their cell alone
    assert not {
        "collectives.device_ms", "store.rule_path_device_ms",
        "store.rule_path_roofline", "store.rule_distinct_share",
    } & per_layer
    assert {m["name"] for m in spec.metrics_of(BENCH, "end_to_end", CELL)} == {
        "updates_per_s_chip", "setup_s",
    }
    assert lint.problems(spec.ROOT) == []
    # a quarter of the cells, rounded down, and one always, may take 4 chips
    four = [w["name"] for w in BENCH["workloads"] if w["chips"] == 4]
    assert CELL in four and len(four) <= max(1, len(BENCH["workloads"]) // 4)


def test_the_record_is_cell_4s_and_the_rows_and_rule_are_cell_9s():
    ps4, lr = _config("fm-criteo-ps4"), _config("lr-ftrl-criteo-40m")
    one_chip = _config("difacto-criteo-10m")
    for key in ("dense_fields", "field_cardinalities", "num_features", "fields",
                "mesh"):
        assert CFG[key] == ps4[key], key
    assert CFG["field_cardinalities"] == lr["field_cardinalities"]
    assert CFG["source_sizes"]["field_cardinalities"] == (
        ps4["source_sizes"]["field_cardinalities"])
    assert CFG["source_sizes"]["max_ind_range"] == 40_000_000
    assert sum(CFG["field_cardinalities"]) == 187_767_399
    assert CFG["num_features"] == 13 + 187_767_399 == 187_767_412
    for key in FAM.RULE_KEYS + (
            "dim", "dtype", "batch", "pool_batches", "V_init_scale", "driver",
            "reference", "dry_run"):
        assert CFG[key] == one_chip[key], key
    assert CFG["reference"]["file"] == "chipbench/references/difacto.py"
    assert CFG["source_sizes"]["updater"] == one_chip["source_sizes"]["updater"]
    assert CFG["source_sizes"]["model"] == one_chip["source_sizes"]["model"]
    # the one size of the start that goes with the record: four times the
    # rows, four times the examples, so the big fields straddle the gate
    assert CFG["warm_start"] == {**one_chip["warm_start"], "examples": 4e8}
    assert 4e8 / max(CFG["field_cardinalities"]) == pytest.approx(10.0, abs=0.01)
    assert CFG["guarantees"][:4] == one_chip["guarantees"]
    assert CFG["guarantees"][4] == ps4["guarantees"][2]
    assert "on the shard that owns the row" in CFG["guarantees"][5]
    assert set(one_chip["assumed"]) <= set(CFG["assumed"])


def _spec():
    """The store's spec at full size over four (virtual) devices, no table."""
    from flink_parameter_server_tpu.models import difacto as df

    mesh = make_mesh(1, SHARDS, devices=jax.devices()[:SHARDS])
    rule = df.DiFactoUpdater(**{k: float(CFG[k]) for k in FAM.RULE_KEYS})
    model = df.DiFactoConfig(int(CFG["num_features"]), int(CFG["dim"]))
    return jax.eval_shape(lambda: df.make_store(
        model, rule, mesh=mesh, dtype=jnp.dtype(CFG["dtype"]))).spec


def test_the_bytes_are_the_files_and_the_stores():
    store = _spec()
    rows, lanes = CFG["num_features"], 4 + 2 * CFG["dim"]
    assert lanes == 36 and store.value_shape == (36,)
    assert rows * lanes * 4 == 27_038_507_328  # 27.04 GB logical
    assert store.layout == "packed" and store.pack == 3
    assert -(-rows // 3) == 62_589_138  # physical rows of 512 B
    assert store.rows_per_shard == 15_647_288
    assert store.table_shape() == (4 * 15_647_288, 128)
    table = 4 * 15_647_288 * 512
    assert table == 32_045_645_824  # 32.05 GB packed
    assert table // 4 == 8_011_411_456  # 8.01 GB a chip, half a v5e's 16 GB
    assert 0.50 < table / 4 / 16e9 < 0.51
    for number in ("27,038,507,328", "32,045,645,824", "8,011,411,456",
                   "15,647,288", "46,941,864"):
        assert number in CFG["reduced_why"], number
    stated = CFG["assumed"]["partitioning"]
    assert stated["physical_rows_per_shard"] == store.rows_per_shard
    assert stated["rows_per_shard"] == store.rows_per_shard * store.pack
    # the whole step's least bytes: the family's, at this record's batch
    assert FAM.hbm_bytes_per_step(CFG) == pytest.approx(
        4 * (1_277_952 * (18 + 17) + 2 * 36 * FAM.distinct_rows_per_step(CFG)))


def _shares_by_shard(block: int):
    """``(keys an example, distinct rows a batch)`` of each contiguous block
    of ``block`` rows, expected from the cardinalities: the integer fields
    are one fixed row each, a categorical field is uniform over its own rows
    (``chipbench/datagen.click_batches``)."""
    keys, rows = np.zeros(SHARDS), np.zeros(SHARDS)
    keys[0] += CFG["dense_fields"]
    rows[0] += CFG["dense_fields"]
    first, batch = CFG["dense_fields"], CFG["batch"]
    for card in CFG["field_cardinalities"]:
        for s in range(SHARDS):
            lo, hi = s * block, (s + 1) * block
            owned = max(0, min(first + card, hi) - max(first, lo))
            keys[s] += owned / card
            rows[s] += owned * (1 - (1 - 1 / card) ** batch)
        first += card
    return keys, rows


def test_the_shards_shares_are_the_cardinalities():
    stated = CFG["assumed"]["partitioning"]
    keys, rows = _shares_by_shard(stated["rows_per_shard"])
    assert keys.sum() == pytest.approx(CFG["fields"])
    assert rows.sum() == pytest.approx(FAM.distinct_rows_per_step(CFG), rel=1e-6)
    np.testing.assert_allclose(
        keys, stated["ids_per_example_by_shard"], atol=0.005)
    np.testing.assert_allclose(
        100 * keys / keys.sum(), stated["id_share_percent_by_shard"], atol=0.05)
    np.testing.assert_allclose(
        rows, stated["distinct_rows_a_batch_by_shard"], atol=1.0)
    np.testing.assert_allclose(
        100 * rows / rows.sum(), stated["distinct_row_share_percent_by_shard"],
        atol=0.05)
    # shard 0 combines most keys; shard 1 rewrites most rows: 32.4 %
    assert keys.argmax() == 0 and rows.argmax() == 1
    assert 100 * rows.max() / rows.sum() == pytest.approx(32.43, abs=0.01)


@pytest.mark.parametrize("seed", [3, 2**31 + 12])
def test_the_sharded_step_is_within_the_reference_s_allowances(seed):
    """The check ``chipbench/run.py`` makes, in process at the dry-run sizes
    under the configuration's own warm start, over ``ps`` = 4: two batches
    through the jitted step, the touched rows read back from whichever shard
    owns them, against the plain reference within the file's tolerances; and
    the counts the push hands out add up to numpy's, shard by shard."""
    cfg = {**DRY["cfg"], "warm_start": {**CFG["warm_start"], "examples": 5000.0}}
    mesh = make_mesh(1, SHARDS, devices=jax.devices()[:SHARDS])
    ref = spec.reference(cfg)
    logic, store = FAM.build(cfg, seed % (2**31 - 1), mesh)
    assert store.spec.layout == "packed" and store.spec.num_shards == SHARDS
    assert store.table.sharding.is_equivalent_to(store.spec.sharding(), 2)
    batches = FAM.host_batches(
        cfg, DRY["traffic_spec"], seed, cfg["reference"]["batches"])
    ids = ref.touched(batches)
    before = FAM.rows(store, (), ids)
    step = jax.jit(make_train_step(logic, store.spec))
    table, outs = store.table, None
    for b in batches:
        table, _, outs = step(table, (), b)
    assert table.sharding.is_equivalent_to(store.spec.sharding(), 2)
    got = FAM.rows(type(store)(store.spec, table), (), ids)
    failures, worst = run._check_rows(
        cfg["reference"], ref.apply(cfg, before, ids, batches), got, before)
    assert failures == [] and worst["share"] < 0.5, worst
    # the last batch's counts: every key is live, the rows are its distinct
    # ids, and the fullest shard's are the largest block's
    last = np.asarray(batches[-1]["ids"]).reshape(-1)
    block = store.spec.rows_per_shard * store.spec.pack
    owner = last // block
    keys = np.bincount(owner, minlength=SHARDS)
    rows = [np.unique(last[owner == s]).size for s in range(SHARDS)]
    assert int(outs["ps_rule_keys"]) == last.size == keys.sum()
    assert int(outs["ps_rule_rows"]) == np.unique(last).size == sum(rows)
    assert int(outs["ps_rule_keys_max_shard"]) == keys.max()
    assert int(outs["ps_rule_rows_max_shard"]) == max(rows)


def test_the_rule_s_scopes_are_in_the_lowered_step_inside_the_shard_map():
    mesh = make_mesh(1, SHARDS, devices=jax.devices()[:SHARDS])
    logic, store = FAM.build(DRY["cfg"], 1, mesh)
    (b,) = FAM.host_batches(DRY["cfg"], DRY["traffic_spec"], 1, 1)
    text = jax.jit(make_train_step(logic, store.spec)).lower(
        store.table, (), b
    ).as_text(debug_info=True)
    # the push is ONE shard_map under ps.push; the rule's scopes stay the
    # scopes inside it (a shard_map's body names its ops from its own root)
    assert text.count('"jit(step)/ps.push/shard_map"') == 1
    for name in ("ps.combine/sort", "ps.combine/scatter-add",
                 "while/body/ps.rule/jit(_take)", "while/body/scatter"):
        assert f'"{name}' in text, name
    innermost = {
        "jit(step)/ps.push/shard_map/ps.combine/sort": "ps.combine",
        "jit(step)/ps.push/shard_map/while/body/ps.rule/gather": "ps.rule",
        "jit(step)/ps.push/shard_map/while/body/scatter": "ps.push",
    }
    for op_name, scope in innermost.items():
        assert program_trace.SCOPE.findall(op_name)[-1] == scope
    # nothing of the table crosses chips: the text holds no collective but
    # the counts' gather (the pull's all-reduce is the partitioner's, later)
    assert text.count("all_gather") >= 1 and "all_to_all" not in text
    assert "collective_permute" not in text


def _ctx(**over):
    return {
        "cfg": CFG, "traffic": FULL["traffic_spec"], "chips": 4,
        "trace": None, "peaks": None, "spans": [],
        "counters": {"peak_hbm_bytes": 0}, **over,
    }


def test_the_four_readers_on_a_synthetic_run(monkeypatch):
    from chipbench import peaks
    from flink_parameter_server_tpu.telemetry import registry as registry_mod

    ms, roof, share, coll = (spec.metric_reader(n) for n in READERS)
    fresh = registry_mod.MetricsRegistry()
    monkeypatch.setattr(registry_mod, "get_registry", lambda: fresh)
    # the parent (no such gauges), and a run without a trace: nothing
    for reader in (ms, roof, share, coll):
        assert reader.read(_ctx()) is None
    where = os.path.join(run.OUT_DIR, "trace", CELL)
    chips = [
        {"ps.pull": 20.0, "ps.combine": 30.0, "ps.rule": 2.0, "ps.push": 3.0},
        {"ps.pull": 20.0, "ps.combine": 24.0, "ps.rule": 2.5, "ps.push": 3.5},
        {"ps.pull": 20.0, "ps.combine": 18.0, "ps.rule": 1.0, "ps.push": 1.0},
        {"ps.pull": 20.0, "ps.combine": 20.0, "ps.rule": 2.0, "ps.push": 2.0},
    ]
    monkeypatch.setitem(program_trace._RUNS, where + "#by_chip", {"chips": chips})
    traced = _ctx(
        trace={"step_device_ms": 70.0, "collective_ms_per_step": 9.5},
        peaks=peaks.peaks_for("TPU v5 lite"),
    )
    # the chip where the rule path takes longest: shard 0's 35 ms
    assert ms.read(traced) == pytest.approx(35.0)
    assert coll.read(traced) == 9.5
    assert roof.read(traced) is None and share.read(traced) is None
    # the program's counts of the fullest shard (the file's expected values)
    fresh.gauge("store_rule_keys", component="train").set(1_277_952)
    fresh.gauge("store_rule_rows", component="train").set(355_419)
    assert share.read(traced) is None
    fresh.gauge("store_rule_keys_max_shard", component="train").set(726_810)
    fresh.gauge("store_rule_rows_max_shard", component="train").set(115_264)
    assert share.read(traced) == pytest.approx(32.43, abs=0.01)
    least = roof.shard_rule_path_bytes(CFG, 726_810, 115_264)
    assert least == 4 * (726_810 * 17 + 2 * 115_264 * 36) == 82_619_112
    assert roof.read(traced) == pytest.approx(100 * least / 819e9 / 35e-3)
    assert 0 < roof.read(traced) < 1
    # a shard cannot move less than its share of what the family counts for
    # the whole step's server side
    assert least < FAM.rule_path_bytes_per_step(CFG) < 4 * least
    # an add store under a mesh has ps.push and no ps.combine: no rule path
    monkeypatch.setitem(program_trace._RUNS, where + "#by_chip", {"chips": [
        {"ps.pull": 5.0, "ps.push": 9.0}] * 4})
    assert ms.read(traced) is None and roof.read(traced) is None


def test_the_cells_dry_run_on_four_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.ROOT)
    env.pop("XLA_FLAGS", None)  # the dry run takes its four devices itself
    done = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL, "--seed",
         str(2**31 + 12), "--seconds", "0.5", "--trace", "1", "--cpu-dry-run"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] and last["failed"] == 0 and last["failures"] == []
    assert "metrics" not in last
    # the program's counters reach the line; the device's need a chip
    assert {"driver.dispatch_ms", "store.rule_owner_max_share"} <= set(
        last["metric_names"])
    assert not {"store.shard_rule_path_device_ms",
                "collectives.rule_pull_device_ms"} & set(last["metric_names"])
    info = json.loads(done.stderr[done.stderr.rindex('{"workload"'):].splitlines()[0])
    assert info["mesh"] == {"dp": 1, "ps": 4}
