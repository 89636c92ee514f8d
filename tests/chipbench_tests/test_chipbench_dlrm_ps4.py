"""Cell 16: MLPerf's DLRM (128-lane rows, the 40 M index range) at one
four-chip host's share of two (`dlrm-criteo-40m-ps4.train-fields-uniform`):
cell 10's family, reference and traffic at MLPerf's widths, 93.9 M x 128 f32
rows in ONE add store over `ps` = 4, the add push run by the shard that owns
the row (`core/store._push_add_on_shards`)."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import lint, run, spec
from flink_parameter_server_tpu.core.transform import make_train_step
from flink_parameter_server_tpu.parallel.mesh import make_mesh

CELL = "dlrm-criteo-40m-ps4.train-fields-uniform"
CONFIG = "dlrm-criteo-40m-ps4"
BENCH = spec.load_benchmark()
FULL = spec.resolve(BENCH, CELL, dry_run=False)
DRY = spec.resolve(BENCH, CELL, dry_run=True)
CFG = FULL["cfg"]
FAM = spec.family("dlrm")
SHARDS = 4
READERS = (
    "store.add_owner_max_share", "collectives.add_pull_device_ms",
    "step.replicated_dense_device_ms",
)


def _config(name):
    return spec.load_json(os.path.join(spec.BENCH_DIR, "configs", name + ".json"))


def test_the_entries_by_name_lint_and_four_four_chip_cells_of_sixteen():
    # by name, never by place: later cells are appended after this one
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 4 and cell["config"] == CONFIG
    assert cell["traffic"] == "train-fields-uniform" and len(cell["why"]) <= 200
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["field_cardinalities"] == CFG["reduced"]
    assert entry["source"] == CFG["source"] and len(entry["source"]) <= 200
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert CFG["family"] == "dlrm" and CFG["mesh"] == {"dp": 1, "ps": 4}
    mine = [m for m in BENCH["per_layer"] if m["name"] in READERS]
    assert [m["name"] for m in mine] == list(READERS)
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "updates_per_s_chip"
        assert m["better"] == "lower"
        reader = spec.metric_reader(m["name"])
        assert reader is not None and reader.__doc__
    assert [m["layer"] for m in mine] == [
        "store gather/scatter", "collectives", "worker step"]
    assert [m["unit"] for m in mine] == ["%", "ms", "ms"]
    assert [m["source"] for m in mine] == [
        "program_counter", "device_trace", "device_trace"]
    per_layer = {m["name"] for m in spec.metrics_of(BENCH, "per_layer", CELL)}
    # the general metrics list no cells and read this one as they read cell 4
    assert set(READERS) | {
        "step.device_ms", "store.pull_device_ms", "store.push_device_ms",
        "store.gather_scatter_roofline", "device.idle_share",
        "device.peak_hbm_bytes", "step.unscoped_share", "driver.dispatch_ms",
    } <= per_layer
    # the accepted metrics that list their cells are not edited: cell 4's
    # collectives, cell 10's dense net and tile rows, cell 12's owner share
    assert not {
        "collectives.device_ms", "step.dense_device_ms", "step.dense_mxu_share",
        "store.push_tile_rows_share", "store.rule_owner_max_share",
        "collectives.rule_pull_device_ms",
    } & per_layer
    assert {m["name"] for m in spec.metrics_of(BENCH, "end_to_end", CELL)} == {
        "updates_per_s_chip", "setup_s",
    }
    assert lint.problems(spec.ROOT) == []
    # a quarter of the cells, rounded down, may take 4 chips: the fourth
    # four-chip place opened with the sixteenth cell, and this cell took it
    cells = BENCH["workloads"]
    four = [w["name"] for w in cells if w["chips"] == 4]
    assert len(cells) >= 16 and [w["name"] for w in cells].index(CELL) == 15
    assert four[:4] == [
        "fm-criteo-ps4.train-fields-uniform", "mf-hugewiki-k128-dp4.train-zipf",
        "difacto-criteo-40m-ps4.train-fields-uniform", CELL]
    assert len(four) <= max(1, len(cells) // 4)
    assert len(BENCH["configs"]) >= 14 and len(BENCH["per_layer"]) >= 78


def test_the_family_reference_and_traffic_are_cell_10s_at_mlperfs_widths():
    ten = _config("dlrm-criteo-10m")
    for key in ("family", "dense_fields", "fields", "dtype", "batch",
                "pool_batches", "driver"):
        assert CFG[key] == ten[key], key
    # cell 10's reference for the ONE batch the cell checks, bit for bit (the
    # file imports it); a later batch, which the accepted tests hand every
    # reference, is not held (`chipbench/references/dlrm_one_step.py` says why)
    assert ten["reference"]["file"] == "chipbench/references/dlrm.py"
    assert CFG["reference"]["file"] == "chipbench/references/dlrm_one_step.py"
    # cell 10's limits but two, each with its readings in `reference.why`: ONE
    # checked batch (at the script's rate 1.0 a second batch is ill-conditioned
    # between two float32 systems) and twice its delta_rtol
    assert CFG["reference"]["batches"] == 1 and ten["reference"]["batches"] == 2
    assert CFG["reference"]["delta_rtol"] == 2 * ten["reference"]["delta_rtol"]
    for key in ("delta_atol", "row_ulps", "relu_ulps"):
        assert CFG["reference"][key] == ten["reference"][key], key
    assert "ONE BATCH" in CFG["reference"]["why"]
    ten_cell = next(
        w for w in BENCH["workloads"] if w["config"] == "dlrm-criteo-10m")
    assert ten_cell["traffic"] == "train-fields-uniform"
    # MLPerf's widths, none cut
    assert CFG["dim"] == 128 == CFG["source_sizes"]["sparse_feature_size"]
    assert [13] + CFG["bottom_mlp"] == CFG["source_sizes"]["bottom_mlp"] == [
        13, 512, 256, 128]
    assert [479] + CFG["top_mlp"] == CFG["source_sizes"]["top_mlp"] == [
        479, 1024, 1024, 512, 256, 1]
    assert CFG["learning_rate"] == CFG["source_sizes"]["learning_rate"] == 1.0
    assert FAM.layer_shapes(CFG)["top0"] == (128 + 351, 1024)
    # cell 10's four guarantees and cell 4's one logical table, none weakened
    assert CFG["guarantees"][:4] == ten["guarantees"]
    assert CFG["guarantees"][4:] == [_config("fm-criteo-ps4")["guarantees"][2]]
    assert "does not depend on the number of shards" in CFG["guarantees"][4]
    assert set(ten["assumed"]) - {"learning_rate"} <= set(CFG["assumed"]) | {
        "learning_rate"}


def test_the_arithmetic_of_one_host_of_two():
    source = CFG["source_sizes"]["field_cardinalities"]
    assert source == _config("fm-criteo-ps4")["source_sizes"]["field_cardinalities"]
    assert sum(source) == 187_767_399 == CFG["source_sizes"]["num_rows"]
    assert sum(source) * 128 * 4 == 96_136_908_288  # 96.14 GB: over a host's 64
    held = CFG["field_cardinalities"]
    assert held == [-(-c // 2) for c in source]
    assert held[:5] == [19_942_203, 19_522, 8_645, 3_710, 10_132]
    assert sorted(held)[:4] == [2, 2, 5, 7]  # the tables a batch hammers
    assert CFG["num_rows"] == sum(held) == 93_883_705
    assert CFG["num_rows"] * 512 == 48_068_456_960
    # the rate of lookups a row is the deployment's: half the batch, half the rows
    assert 65_536 * 26 / sum(source) == pytest.approx(
        CFG["batch"] * 26 / sum(held), rel=2e-7)
    for number in ("93,883,705", "48,068,456,960", "23,470,928",
                   "12,017,115,136", "75.1 %", "19,942,203", "313.6 k"):
        assert number in CFG["reduced_why"], number
    # one host of THREE, the fallback that was not needed
    assert sum(-(-c // 3) for c in source) == 62_589_143
    # the dense net: 2,368,897 parameters, 2,458,496 multiply-adds an example
    shapes = FAM.layer_shapes(CFG)
    assert sum(n * m + m for n, m in shapes.values()) == 2_368_897
    assert sum(n * m for n, m in shapes.values()) + 27 * 27 * 128 == 2_458_496
    assert FAM.dense_flops_per_step(CFG) == 483_359_981_568
    assert FAM.hbm_bytes_per_step(CFG) == 3 * 851_968 * 128 * 4
    dry = DRY["cfg"]
    assert max(dry["field_cardinalities"]) == 509
    assert dry["num_rows"] == sum(dry["field_cardinalities"])
    for key in ("dim", "bottom_mlp", "top_mlp", "learning_rate", "mesh"):
        assert dry[key] == CFG[key], key  # every width kept


def _spec(cfg=CFG):
    """The store's spec over four (virtual) devices, no table."""
    from flink_parameter_server_tpu.models import dlrm

    mesh = make_mesh(1, SHARDS, devices=jax.devices()[:SHARDS])
    model = dlrm.DLRMConfig(tuple(cfg["field_cardinalities"]), dim=cfg["dim"],
                            bottom_mlp=tuple(cfg["bottom_mlp"]),
                            top_mlp=tuple(cfg["top_mlp"]))
    return jax.eval_shape(lambda: dlrm.make_store(
        model, mesh=mesh, dtype=jnp.dtype(cfg["dtype"]))).spec


def test_the_bytes_the_blocks_and_the_arm_are_the_stores(monkeypatch):
    from flink_parameter_server_tpu.core import store as store_mod

    store = _spec()
    assert (store.layout, store.pack, store.update) == ("dense", 1, "add")
    assert store.value_shape == (128,)  # exactly ONE register a row
    assert store.rows_per_shard == 23_470_928
    assert store.table_shape() == (4 * 23_470_928, 128)
    assert store.padded_capacity - store.capacity == 7
    assert store.rows_per_shard * 512 == 12_017_115_136  # a chip's block
    assert 0.751 < store.rows_per_shard * 512 / 16e9 < 0.752  # over the 25 %
    stated = CFG["assumed"]["partitioning"]
    assert stated["rows_per_shard"] == store.rows_per_shard
    # the arm a TPU reads: the batch's lanes x 8 are under a SHARD's rows
    lanes = CFG["batch"] * CFG["fields"]
    assert lanes == 851_968 and lanes * 8 <= store.rows_per_shard
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert store_mod.arms(
        store, pull_lanes=lanes, push_lanes=lanes, fields=CFG["fields"]
    ) == store_mod.Arms("take", "tile_add", "", "", "", True)


def _shares_by_shard(block: int):
    """``(ids an example, distinct rows a batch)`` of each contiguous block
    of ``block`` rows, expected from the HELD cardinalities: a field is
    uniform over its own rows (``chipbench/datagen.click_batches``)."""
    ids, rows = np.zeros(SHARDS), np.zeros(SHARDS)
    first, batch = 0, CFG["batch"]
    for card in CFG["field_cardinalities"]:
        for s in range(SHARDS):
            lo, hi = s * block, (s + 1) * block
            owned = max(0, min(first + card, hi) - max(first, lo))
            ids[s] += owned / card
            rows[s] += owned * (1 - (1 - 1 / card) ** batch)
        first += card
    return ids, rows


def test_the_shards_shares_are_the_cardinalities():
    stated = CFG["assumed"]["partitioning"]
    ids, rows = _shares_by_shard(stated["rows_per_shard"])
    assert ids.sum() == pytest.approx(CFG["fields"])
    np.testing.assert_allclose(ids, stated["ids_per_example_by_shard"], atol=0.005)
    assert stated["ids_per_example_by_shard"] == [9.18, 10.12, 1.44, 5.26]
    np.testing.assert_allclose(
        100 * ids / ids.sum(), stated["id_share_percent_by_shard"], atol=0.05)
    np.testing.assert_allclose(
        CFG["batch"] * ids, stated["lanes_a_batch_by_shard"], atol=50)
    np.testing.assert_allclose(
        rows, stated["distinct_rows_a_batch_by_shard"], atol=1.0)
    assert rows.sum() == pytest.approx(313_624, abs=1)  # 36.8 % of 851,968
    # shard 1 takes most lanes: what `store.add_owner_max_share` should read
    assert ids.argmax() == 1
    assert 100 * ids.max() / ids.sum() == pytest.approx(38.9, abs=0.05)


def _stepped(cfg, seed, mesh, batches):
    """Two batches through the jitted step as ``chipbench/run.py`` checks
    them: ``(failures, worst, rows after, the last step's outputs)``."""
    ref = spec.reference(cfg)
    logic, store = FAM.build(cfg, seed % (2**31 - 1), mesh)
    state = logic.init_state(jax.random.PRNGKey(0))
    ids = ref.touched(batches)
    before = FAM.rows(store, state, ids)
    step = jax.jit(make_train_step(logic, store.spec))
    table, outs = store.table, None
    for b in batches:
        table, state, outs = step(table, state, b)
    if mesh is not None:
        assert table.sharding.is_equivalent_to(store.spec.sharding(), 2)
    got = FAM.rows(type(store)(store.spec, table), state, ids)
    failures, worst = run._check_rows(
        cfg["reference"], ref.apply(cfg, before, ids, batches), got, before)
    return failures, worst, got, outs, store.spec


@pytest.mark.parametrize("seed", [3, 2**31 + 12])
def test_the_sharded_step_is_the_reference_s_and_the_one_place_step_s(
        seed, steer_arms):
    """The check ``chipbench/run.py`` makes, in process at the dry-run sizes
    (every width as published) over ``ps`` = 4 with the add push steered onto
    the shards (the tile kernel interpreted): TWO batches (the cell checks one
    on the chip; here the two systems' weights agree to a rounding) against the plain
    reference within the file's tolerances, and against the SAME step in one
    place BIT FOR BIT (a pulled row is the owner's row plus three zeros, the
    dense net sees the same numbers in the same order, and a row's deltas
    are added one by one in the order of the batch by the shard that owns
    it); the counts the push hands out are numpy's, shard by shard."""
    cfg = DRY["cfg"]
    mesh = make_mesh(1, SHARDS, devices=jax.devices()[:SHARDS])
    batches = FAM.host_batches(cfg, DRY["traffic_spec"], seed, 2)
    failures, worst, want, outs, _ = _stepped(cfg, seed, None, batches)
    assert failures == [] and worst["share"] < 0.8, worst
    assert "ps_push_kernel_lanes" not in outs  # XLA's arm counts nothing
    steer_arms(push="tile_add", on_shards=lambda spec: spec.mesh is not None)
    failures, worst, got, outs, store = _stepped(cfg, seed, mesh, batches)
    assert failures == [] and worst["share"] < 0.8, worst
    assert store.num_shards == SHARDS and store.layout == "dense"
    # ... and ONE batch, what the cell checks, against the PLAIN reference
    # (cell 10's file) with every element held: the one-step file's own bits
    failures, worst, first, _, _ = _stepped(cfg, seed, mesh, batches[:1])
    assert failures == [] and 0 < worst["share"] < 0.6, worst
    from chipbench.references import dlrm as plain

    ids = plain.touched(batches[:1])
    logic, fresh = FAM.build(cfg, seed % (2**31 - 1), None)
    before = FAM.rows(fresh, logic.init_state(jax.random.PRNGKey(0)), ids)
    ours, theirs = (r.apply(cfg, before, ids, batches[:1])
                    for r in (spec.reference(cfg), plain))
    for mine, plains in zip(ours, theirs):
        assert mine["parameters"].tobytes() == plains["parameters"].tobytes()
    assert run._check_rows(cfg["reference"], theirs, first, before)[0] == []
    assert got["parameters"].tobytes() == want["parameters"].tobytes()
    # the last batch's counts: every lane is live, and the fullest shard's
    # are the largest block's
    last = np.asarray(batches[-1]["ids"]).reshape(-1)
    owner = last // store.rows_per_shard
    lanes = np.bincount(owner, minlength=SHARDS)
    tile_rows = [np.unique(last[owner == s] // 8).size for s in range(SHARDS)]
    assert int(outs["ps_push_kernel_lanes"]) == last.size == lanes.sum()
    assert int(outs["ps_push_lanes_max_shard"]) == lanes.max()
    assert int(outs["ps_push_tile_rows"]) == sum(tile_rows)
    assert int(outs["ps_push_tile_rows_max_shard"]) == max(tile_rows)


def test_the_two_hosts_shares_change_the_rows_the_uncut_reference_changes():
    """What ties the share to the deployment, at a small size with every
    width kept.  TWO hosts, each given its own half of every table
    (``ceil(C / 2)`` and ``floor(C / 2)`` rows of a table of ``C``) and its
    own half of a global batch, through the SYSTEM over ``ps`` = 4; and the
    UNCUT plain reference, given every table whole (each host's half as the
    host holds it, one after the other) and the union of the two batches,
    each lookup renumbered to where its host's half lies in the whole
    table.  The rows the reference changes in a host's half are the rows
    that host changed, by that host's change HALVED (the uncut mean is over
    twice the examples), no row of the other host's half among them; and
    the dense net, which every host computes alike from its own half of the
    batch, is counted ONCE: the reference's dense step is the MEAN of the
    two hosts' steps (what the deployment's all-reduce of dense gradients
    would hand every host; nothing here builds it), not their sum."""
    from chipbench.references import dlrm as reference

    dry = DRY["cfg"]
    whole = [40, 3, 2, 500, 7, 19]
    halves = [[-(-c // 2) for c in whole], [c // 2 for c in whole]]
    mesh = make_mesh(1, SHARDS, devices=jax.devices()[:SHARDS])
    batch, rng = 64, np.random.default_rng(66)
    hosts = []
    for held in halves:
        cfg = {**dry, "field_cardinalities": held, "num_rows": sum(held),
               "fields": len(held), "batch": batch}
        firsts = np.concatenate([[0], np.cumsum(held)[:-1]])
        local = rng.integers(0, held, (batch, len(held)))
        b = {"dense": rng.random((batch, 13), np.float32),
             "ids": (local + firsts).astype(np.int32),
             "label": rng.integers(0, 2, batch).astype(np.float32),
             "mask": np.ones(batch, bool)}
        logic, store = FAM.build(cfg, 5, mesh)
        assert store.spec.num_shards == SHARDS
        state = logic.init_state(jax.random.PRNGKey(0))
        values = np.asarray(store.values())
        table, after, _ = jax.jit(make_train_step(logic, store.spec))(
            store.table, state, b)
        moved = np.asarray(type(store)(store.spec, table).values()) - values
        hosts.append({
            "held": np.asarray(held), "firsts": firsts, "batch": b,
            "values": values, "moved": moved, "state": state, "after": after,
        })
    a, b = hosts
    for k in a["state"]:  # one dense net, the same on both hosts
        assert np.array_equal(np.asarray(a["state"][k]), np.asarray(b["state"][k]))
    # the uncut tables: host 0's half, then host 1's, table after table
    assert [int(x + y) for x, y in zip(a["held"], b["held"])] == whole
    firsts = np.concatenate([[0], np.cumsum(whole)[:-1]])
    rows = np.concatenate([
        np.concatenate([a["values"][fa:fa + ca], b["values"][fb:fb + cb]])
        for fa, ca, fb, cb in zip(a["firsts"], a["held"], b["firsts"], b["held"])])
    assert rows.shape == (sum(whole), 128)
    where = [firsts, firsts + a["held"]]  # the first row of a host's half
    union = {
        k: np.concatenate([a["batch"][k], b["batch"][k]])
        for k in ("dense", "label", "mask")}
    union["ids"] = np.concatenate([
        (h["batch"]["ids"] - h["firsts"] + where[i]).astype(np.int32)
        for i, h in enumerate(hosts)])
    uncut = {**dry, "field_cardinalities": whole, "num_rows": sum(whole),
             "fields": len(whole), "batch": 2 * batch}
    ids = reference.touched([union])
    touched = ids["embedding"]
    before = FAM.rows(_Rows(rows), a["state"], ids)
    want, _ = reference.apply(uncut, before, ids, [union])
    new_rows, new_layers = reference.unpack(
        uncut, want["parameters"], touched.size)
    old_rows, old_layers = reference.unpack(
        uncut, before["parameters"], touched.size)
    changed = np.unique(touched[(new_rows != old_rows).any(axis=1)])
    seen = 0
    for i, h in enumerate(hosts):
        mine = np.flatnonzero((h["moved"] != 0).any(axis=1))
        np.testing.assert_array_equal(mine, np.unique(h["batch"]["ids"]))
        # this host's rows as the uncut tables number them
        field = np.searchsorted(h["firsts"], mine, side="right") - 1
        there = mine - h["firsts"][field] + where[i][field]
        in_half = np.zeros(changed.size, bool)
        for f in range(len(whole)):
            in_half |= (changed >= where[i][f]) & (
                changed < where[i][f] + h["held"][f])
        np.testing.assert_array_equal(np.sort(there), changed[in_half])
        seen += there.size
        # by this host's change HALVED: the mean is over twice the examples
        # (a difference of two roundings of a row of magnitude <= sqrt(1/2),
        # one for every example that names the row, on either side)
        at = np.searchsorted(touched, there)
        times = np.bincount(h["batch"]["ids"].reshape(-1))[mine]
        np.testing.assert_array_less(
            np.abs(new_rows[at] - old_rows[at] - h["moved"][mine] / 2),
            times[:, None] * 1.5 * 2.0 ** -24 + 2e-4 * np.abs(h["moved"][mine]))
        assert (times == 1).sum() > 30 and times.max() >= 20
    assert seen == changed.size  # every changed row lies in one host's half
    # the dense net counted once: the MEAN of the hosts' steps, not their sum
    for name, new in new_layers.items():
        steps = [np.concatenate([
            np.asarray(h["after"][f"{name}_w"]) - np.asarray(h["state"][f"{name}_w"]),
            (np.asarray(h["after"][f"{name}_b"])
             - np.asarray(h["state"][f"{name}_b"]))[None]]) for h in hosts]
        mean = (steps[0] + steps[1]) / 2
        scale = np.abs(mean).max()
        np.testing.assert_allclose(
            new - old_layers[name], mean, rtol=0, atol=5e-4 * scale)
        assert np.abs(new - old_layers[name] - 2 * mean).max() > 0.3 * scale


class _Rows:
    """A table of plain rows behind the family's ``rows`` (its ``pull``)."""

    def __init__(self, rows):
        self.rows = rows

    def pull(self, ids):
        return self.rows[np.asarray(ids)]


def _ctx(**over):
    return {
        "cfg": CFG, "traffic": FULL["traffic_spec"], "chips": 4,
        "trace": None, "peaks": None, "spans": [],
        "counters": {"peak_hbm_bytes": 0}, **over,
    }


def test_the_three_readers_on_a_fixture_and_on_nothing(monkeypatch):
    from chipbench import program_trace
    from flink_parameter_server_tpu.telemetry import registry as registry_mod

    share, coll, dense = (spec.metric_reader(n) for n in READERS)
    fresh = registry_mod.MetricsRegistry()
    monkeypatch.setattr(registry_mod, "get_registry", lambda: fresh)
    # the parent (no such gauges, no such scopes), a run without a trace
    for reader in (share, coll, dense):
        assert reader.read(_ctx()) is None
    where = os.path.join(run.OUT_DIR, "trace", CELL)
    monkeypatch.setitem(program_trace._RUNS, where, {"scope_ms": {
        "ps.pull": 18.0, "ps.push": 8.0, "ps.dense_bottom": 3.0,
        "ps.dense_interact": 17.5, "ps.dense_top": 14.5, "ps.dense_sgd": 0.1,
        "ps.delta_build": 0.4}})
    traced = _ctx(
        trace={"step_device_ms": 60.0, "collective_ms_per_step": 9.25})
    assert coll.read(traced) == 9.25
    # the three scopes of the dense net, the SGD and the deltas left out
    assert dense.read(traced) == pytest.approx(35.0)
    # a trace of a program without the dense scopes: nothing
    monkeypatch.setitem(program_trace._RUNS, where, {"scope_ms": {
        "ps.pull": 18.0, "ps.push": 8.0}})
    assert dense.read(traced) is None
    # the program's counts: nothing until both gauges are there
    assert share.read(traced) is None
    fresh.gauge("store_push_kernel_lanes", component="train").set(851_968)
    assert share.read(traced) is None  # a store in one place (cell 10)
    fresh.gauge("store_push_lanes_max_shard", component="train").set(331_600)
    assert share.read(traced) == pytest.approx(38.92, abs=0.01)


def test_the_driver_sets_the_two_gauges_from_the_last_dispatch(steer_arms):
    """``StreamingDriver`` -> ``make_train_step`` -> ``push_counted`` ->
    ``publish_counts``: the fullest shard's lanes and tile rows of the last
    dispatch, what ``store.add_owner_max_share`` reads."""
    from flink_parameter_server_tpu import DriverConfig, StreamingDriver
    from flink_parameter_server_tpu.telemetry.registry import MetricsRegistry

    cfg = {**DRY["cfg"], "batch": 128}
    mesh = make_mesh(1, SHARDS, devices=jax.devices()[:SHARDS])
    batches = FAM.host_batches(cfg, DRY["traffic_spec"], 9, 2)
    steer_arms(push="tile_add", on_shards=lambda spec: spec.mesh is not None)
    logic, store = FAM.build(cfg, 9, mesh)
    rows = store.spec.rows_per_shard
    registry = MetricsRegistry()
    StreamingDriver(
        logic, store, registry=registry, config=DriverConfig(**cfg["driver"]),
    ).run(iter(batches))
    gauges = {k: v[0]["value"] for k, v in registry.snapshot().items()}
    last = np.asarray(batches[-1]["ids"]).reshape(-1)
    owner = last // rows
    assert gauges["store_push_kernel_lanes"] == last.size
    assert gauges["store_push_lanes_max_shard"] == np.bincount(owner).max()
    assert gauges["store_push_tile_rows"] == sum(
        np.unique(last[owner == s] // 8).size for s in range(SHARDS))
    assert gauges["store_push_tile_rows_max_shard"] == max(
        np.unique(last[owner == s] // 8).size for s in range(SHARDS))


def test_the_cells_dry_run_on_four_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.ROOT)
    env.pop("XLA_FLAGS", None)  # the dry run takes its four devices itself
    done = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL, "--seed",
         str(2**31 + 12), "--seconds", "0.5", "--trace", "1", "--cpu-dry-run"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] and last["failed"] == 0 and last["failures"] == []
    assert "metrics" not in last
    # the program's counters reach the line; the device's need a chip, and
    # off the TPU XLA's scatter-add takes the push: no owner's share either
    assert "driver.dispatch_ms" in last["metric_names"]
    assert not set(READERS) & set(last["metric_names"])
    info = json.loads(done.stderr[done.stderr.rindex('{"workload"'):].splitlines()[0])
    assert info["mesh"] == {"dp": 1, "ps": 4}
    assert 0 < info["reference_worst"]["share"] < 0.8
