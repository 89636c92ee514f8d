"""Cell 15: MLPerf's DLRM-DCNv2 at one server's share of thirty-two on one
chip (`dlrm-dcnv2-mlperf-s32.train-multihot-uniform`): 6,380,781 x 256 f32
rule rows of TWO registers (128 weights, Adagrad's 128 accumulators), 2,048
examples of 214 lookups a step, the low-rank cross network beside them, the
plain reference and the eight readers."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from chipbench import lint, program_trace, run, spec
from flink_parameter_server_tpu.core.transform import make_train_step

CELL = "dlrm-dcnv2-mlperf-s32.train-multihot-uniform"
CONFIG = "dlrm-dcnv2-mlperf-s32"
BENCH = spec.load_benchmark()
FULL = spec.resolve(BENCH, CELL, dry_run=False)
DRY = spec.resolve(BENCH, CELL, dry_run=True)
CFG = FULL["cfg"]
FAM = spec.family("dlrm_dcnv2")
READERS = (
    "store.bag_rule_path_device_ms", "store.bag_rule_path_roofline",
    "store.bag_rule_distinct_share", "step.multihot_pool_device_ms",
    "step.cross_device_ms", "step.dcn_dense_device_ms",
    "step.dcn_dense_mxu_share", "store.bag_push_tile_rows_share",
)


def _ctx(**over):
    return {
        "cfg": CFG, "traffic": FULL["traffic_spec"], "chips": 1,
        "trace": None, "peaks": None, "spans": [],
        "counters": {"peak_hbm_bytes": 0}, **over,
    }


def test_the_entries_by_name_appended_and_lint_clean():
    # by name, never by place: later cells are appended after this one
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    assert cell["traffic"] == "train-multihot-uniform"
    assert len(cell["why"]) <= 200
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["field_cardinalities"] == CFG["reduced"]
    assert entry["source"] == CFG["source"] and len(entry["source"]) <= 200
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert CFG["family"] == "dlrm_dcnv2" and CFG["mesh"] is None
    assert CFG["traffic"] == cell["traffic"]
    names = [w["name"] for w in BENCH["workloads"]]
    assert names.index(CELL) >= 14  # after the fourteen cells that were there
    assert len({w["config"] for w in BENCH["workloads"][:names.index(CELL) + 1]}
               ) == 13
    mine = [m for m in BENCH["per_layer"] if m["name"] in READERS]
    assert [m["name"] for m in mine] == list(READERS)
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "updates_per_s_chip"
        reader = spec.metric_reader(m["name"])
        assert reader is not None and reader.__doc__
    assert [m["layer"] for m in mine] == (
        3 * ["store gather/scatter"] + 4 * ["worker step"]
        + ["store gather/scatter"])
    assert [m["unit"] for m in mine] == [
        "ms", "%", "%", "ms", "ms", "ms", "%", "%"]
    assert [m["better"] for m in mine] == [
        "lower", "higher", "lower", "lower", "lower", "lower", "higher",
        "lower"]
    assert [m["source"] for m in mine] == [
        "device_trace", "device_trace", "program_counter", "device_trace",
        "device_trace", "device_trace", "device_trace", "program_counter"]
    # no other entry names the cell
    assert [m["name"] for m in BENCH["per_layer"]
            if CELL in m.get("workloads", ())] == list(READERS)
    per_layer = {m["name"] for m in spec.metrics_of(BENCH, "per_layer", CELL)}
    # the general metrics list no cells and read this one as they read cell 14
    assert set(READERS) | {
        "step.device_ms", "store.pull_device_ms", "store.push_device_ms",
        "store.gather_scatter_roofline", "step.compute_device_ms",
        "device.idle_share", "device.peak_hbm_bytes", "step.unscoped_share",
        "driver.dispatch_ms", "setup.compiles",
    } <= per_layer
    assert {m["name"] for m in spec.metrics_of(BENCH, "end_to_end", CELL)} == {
        "updates_per_s_chip", "setup_s",
    }
    assert lint.problems(spec.ROOT) == []
    four = [w["name"] for w in BENCH["workloads"] if w["chips"] == 4]
    assert CELL not in four and len(four) <= max(1, len(BENCH["workloads"]) // 4)


def test_the_configuration_is_one_server_s_share_of_thirty_two():
    from flink_parameter_server_tpu.models import dlrm_dcnv2 as dcn

    sizes = CFG["source_sizes"]
    published = sizes["num_embeddings_per_feature"]
    assert len(published) == 26 == sizes["categorical_fields"] == CFG["fields"]
    assert sum(published) == 204_184_588 == sizes["num_embeddings"]
    assert sum(sizes["multi_hot_sizes"]) == 214 == FAM.lookups(CFG)
    assert CFG["multi_hot_sizes"] == sizes["multi_hot_sizes"]
    # the cut: ceil(n / 32) rows of every table, and nothing else
    assert CFG["servers"] == 32
    assert CFG["field_cardinalities"] == FAM.held_rows(CFG)
    assert sum(CFG["field_cardinalities"]) == 6_380_781 == CFG["num_rows"]
    assert sorted(CFG["field_cardinalities"])[:4] == [1, 1, 1, 1]
    assert CFG["batch"] * CFG["servers"] == 65_536 == sizes["global_batch_size"]
    assert FAM.keys_per_step(CFG) == 438_272
    # every width as published
    assert CFG["dim"] == 128 == sizes["embedding_dim"]
    assert CFG["bottom_mlp"] == sizes["dense_arch_layer_sizes"]
    assert CFG["over_mlp"] == sizes["over_arch_layer_sizes"]
    assert CFG["cross_layers"] == sizes["dcn_num_layers"] == 3
    assert CFG["cross_rank"] == sizes["dcn_low_rank_dim"] == 512
    assert CFG["learning_rate"] == sizes["learning_rate"] == 0.004
    assert CFG["eps"] == sizes["adagrad_eps"] == 1e-8
    assert CFG["dtype"] == "float32"
    for key in ("dim", "batch", "cross_rank"):
        assert lint.WIDTH.search(key) and key not in CFG["reduced"]
    assert "65,536" in CFG["reduced_why"] and "batch" in CFG["assumed"]
    # the store: ONE array of two whole registers a row, 40.8 % of the chip
    model = dcn.DCNv2Config(
        tuple(CFG["field_cardinalities"]), tuple(CFG["multi_hot_sizes"]),
        tuple(published))
    assert model.num_rows == 6_380_781 and model.lookups == 214
    assert model.width == 3456
    assert model.dense_params == 16_044_545 == FAM.dense_params(CFG)
    spec_ = jax.eval_shape(lambda: dcn.make_store(model)).spec
    assert spec_.layout == "packed" and spec_.pack == 1
    assert spec_.table_shape() == (6_380_784, 256) and spec_.worker_width == 128
    assert 6_380_784 * 256 * 4 == 6_533_922_816
    assert 0.40 < 6_533_922_816 / 16e9 < 0.41
    # the family's closed forms
    assert FAM.dense_flops_per_step(CFG) == 6 * 16_030_464 * 2048
    assert FAM.dense_flops_per_step(CFG) == pytest.approx(196.98e9, rel=1e-4)
    assert 6 * model.macs_per_example == pytest.approx(96.18e6, rel=1e-4)
    distinct = FAM.distinct_rows_per_step(CFG)
    assert distinct == pytest.approx(319_000, rel=0.01)
    assert distinct / 438_272 == pytest.approx(0.728, abs=0.002)
    # the pushed gradients once at 128 lanes, a distinct row twice at 256
    assert FAM.rule_path_bytes_per_step(CFG) == pytest.approx(
        4 * (438_272 * 128 + 2 * 256 * distinct))
    assert FAM.hbm_bytes_per_step(CFG) == pytest.approx(
        FAM.rule_path_bytes_per_step(CFG) + 4 * 438_272 * 128
        + 4 * 4 * 16_044_545)
    # the dry run keeps every width
    for key in ("dim", "bottom_mlp", "cross_rank", "over_mlp",
                "multi_hot_sizes", "learning_rate", "eps"):
        assert DRY["cfg"][key] == CFG[key]
    assert sum(DRY["cfg"]["field_cardinalities"]) == DRY["cfg"]["num_rows"]


def test_the_batches_are_a_function_of_the_seed_and_stay_in_their_fields():
    cfg = DRY["cfg"]
    a, b = (FAM.host_batches(cfg, DRY["traffic_spec"], 2**31 + 7, 2)
            for _ in range(2))
    for x, y in zip(a, b):
        assert all(np.array_equal(x[k], y[k]) for k in x)
    other = FAM.host_batches(cfg, DRY["traffic_spec"], 8, 1)[0]
    assert not np.array_equal(a[0]["ids"], other["ids"])
    cards = np.asarray(cfg["field_cardinalities"])
    sizes = np.asarray(cfg["multi_hot_sizes"])
    first = np.repeat(np.concatenate([[0], np.cumsum(cards)[:-1]]), sizes)
    ids = a[0]["ids"]
    assert ids.shape == (cfg["batch"], 214) and ids.dtype == np.int32
    assert (ids >= first).all() and (ids < first + np.repeat(cards, sizes)).all()
    assert a[0]["dense"].shape == (cfg["batch"], 13)
    assert set(np.unique(a[0]["label"])) == {0.0, 1.0} and a[0]["mask"].all()
    # the closed form of the distinct rows against a draw
    drawn = np.mean([np.unique(x["ids"]).size for x in a])
    assert FAM.distinct_rows_per_step(cfg) == pytest.approx(drawn, rel=0.03)


def _checked(cfg, seed, n=1, keys=None):
    ref = spec.reference(cfg)
    logic, store = FAM.build(cfg, seed, None)
    batches = FAM.host_batches(
        cfg, {"keys": keys or {"kind": "uniform"}}, seed, n)
    ids = ref.touched(batches)
    state = logic.init_state(jax.random.PRNGKey(0))
    before = FAM.rows(store, state, ids)
    step = jax.jit(make_train_step(logic, store.spec))
    table = store.table
    for b in batches:
        table, state, _ = step(table, state, b)
    got = FAM.rows(type(store)(store.spec, table), state, ids)
    return run._check_rows(
        cfg["reference"], ref.apply(cfg, before, ids, batches), got, before)


@pytest.mark.parametrize("seed", [77, 2**31 + 12])
def test_the_system_is_within_the_reference_s_allowances(seed):
    failures, worst = _checked(DRY["cfg"], seed % (2**31 - 1))
    assert failures == [] and 0 < worst["share"] <= 1.0, worst


def test_the_cross_network_at_one_bfloat16_pass_fails_the_check(monkeypatch):
    # the nearest precision below, stood in for on the CPU: the operands of
    # the cross network's products rounded to bfloat16
    import jax.numpy as jnp

    from flink_parameter_server_tpu.models import dlrm_dcnv2 as dcn

    def coarse(a, b):
        return jnp.dot(
            a.astype(jnp.bfloat16).astype(jnp.float32),
            b.astype(jnp.bfloat16).astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST)

    monkeypatch.setattr(dcn, "_dot", coarse)
    failures, worst = _checked(DRY["cfg"], 77)
    assert len(failures) == 1 and worst["share"] > 10, worst


def test_the_eight_readers_on_a_synthetic_run(monkeypatch):
    from chipbench import peaks
    from flink_parameter_server_tpu.telemetry import registry as registry_mod

    ms, roof, share, pool, cross, dense, mxu, tiles = (
        spec.metric_reader(n) for n in READERS)
    fresh = registry_mod.MetricsRegistry()
    monkeypatch.setattr(registry_mod, "get_registry", lambda: fresh)
    # the parent (no such scope or gauge), and a run without a trace: nothing
    for reader in (ms, roof, share, pool, cross, dense, mxu, tiles):
        assert reader.__doc__ and reader.read(_ctx()) is None
    where = os.path.join(run.OUT_DIR, "trace", CELL)
    monkeypatch.setitem(program_trace._RUNS, where, {"scope_ms": {
        "ps.pull": 5.0, "ps.compute": 0.3, "ps.bag_pool": 0.4,
        "ps.bag_grad_spread": 0.6, "ps.dense_bottom": 0.5,
        "ps.dense_interact": 12.0, "ps.dense_top": 7.5,
        "ps.dense_adagrad": 0.7,
        "ps.combine": 4.0, "ps.rule": 5.0, "ps.push": 8.0,
    }})
    traced = _ctx(
        trace={"step_device_ms": 45.0}, peaks=peaks.peaks_for("TPU v5 lite"))
    assert ms.read(traced) == pytest.approx(17.0)
    assert pool.read(traced) == pytest.approx(1.0)
    assert cross.read(traced) == pytest.approx(12.0)
    assert dense.read(traced) == pytest.approx(20.0)
    least_ms = FAM.rule_path_bytes_per_step(CFG) / 819e9 * 1e3
    assert least_ms == pytest.approx(1.072, abs=5e-3)
    assert roof.read(traced) == pytest.approx(100 * least_ms / 17.0)
    assert 0 < roof.read(traced) < 100
    # 197 GFLOP in 20 ms over 197 TFLOP/s
    assert mxu.read(traced) == pytest.approx(100 * 196.98e9 / 20e-3 / 197e12, rel=1e-3)
    assert 0 < mxu.read(traced) < 17
    # without the chip's peaks (a dry run) the shares are left out
    for reader in (roof, mxu):
        assert reader.read(_ctx(trace={"step_device_ms": 45.0})) is None
    # an add store with no dense net: ps.push, no ps.combine, no such scope
    monkeypatch.setitem(
        program_trace._RUNS, where, {"scope_ms": {"ps.pull": 5.0, "ps.push": 9.0}})
    for reader in (ms, roof, pool, cross, dense, mxu):
        assert reader.read(traced) is None
    # the program's counts of a step, closed form: 72.8 % distinct
    assert share.read(traced) is None
    fresh.gauge("store_rule_keys", component="train").set(438_272)
    assert share.read(traced) is None
    fresh.gauge("store_rule_rows", component="train").set(319_050)
    assert share.read(traced) == pytest.approx(72.8, abs=0.1)
    # the write-back's tile rows of eight over the rows it rewrote; XLA's
    # set opens none, and the add store's tile kernel's gauges are not these
    assert tiles.read(traced) is None
    fresh.gauge("store_rule_tiles", component="train").set(0)
    fresh.gauge("store_push_tile_rows", component="train").set(7)
    fresh.gauge("store_push_kernel_lanes", component="train").set(9)
    assert tiles.read(traced) is None
    fresh.gauge("store_rule_tiles", component="train").set(300_000)
    assert tiles.read(traced) == pytest.approx(100 * 300_000 / 319_050)
    assert spec.metric_reader("store.push_tile_rows_share").read(
        traced) == pytest.approx(100 * 7 / 9)
    # the whole step's roofline reads the family's bytes
    whole = spec.metric_reader("store.gather_scatter_roofline")
    monkeypatch.setitem(program_trace._RUNS, where, {"scope_ms": {"ps.pull": 2.0}})
    traced["counters"]["hbm_bytes_per_step"] = FAM.hbm_bytes_per_step(CFG)
    assert whole.read(traced) == pytest.approx(
        100 * FAM.hbm_bytes_per_step(CFG) / 819e9 * 1e3 / 45.0, rel=1e-6)
    assert 0 < whole.read(traced) < 100


def test_the_cells_dry_run_ends_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.ROOT)
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
    assert {"driver.dispatch_ms", "store.bag_rule_distinct_share"} <= set(
        last["metric_names"])
    # (on the CPU XLA's set writes the rows back: no tile row is opened)
    assert not {"store.bag_rule_path_device_ms", "step.cross_device_ms",
                "step.dcn_dense_mxu_share", "step.multihot_pool_device_ms",
                "store.bag_push_tile_rows_share"} & set(last["metric_names"])
