"""Cell 13: GloVe at the released 840B table on one chip
(`glove-840b-300.train-cooc-zipf`): 4,392,034 x 602 f32 rule rows flat in five
registers, the key law of a co-occurrence matrix's nonzeros in closed form,
the plain reference and the four readers."""
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

CELL = "glove-840b-300.train-cooc-zipf"
CONFIG = "glove-840b-300"
BENCH = spec.load_benchmark()
FULL = spec.resolve(BENCH, CELL, dry_run=False)
DRY = spec.resolve(BENCH, CELL, dry_run=True)
CFG = FULL["cfg"]
FAM = spec.family("glove")
READERS = (
    "store.wide_rule_path_device_ms", "store.wide_rule_path_roofline",
    "store.wide_rule_distinct_share", "step.cooc_grad_rows_device_ms",
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
    assert cell["traffic"] == "train-cooc-zipf" and len(cell["why"]) <= 200
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == [] == CFG["reduced"]
    assert entry["source"] == CFG["source"] and len(entry["source"]) <= 200
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert CFG["family"] == "glove" and CFG["mesh"] is None
    assert CFG["traffic"] == cell["traffic"]
    names = [w["name"] for w in BENCH["workloads"]]
    assert names.index(CELL) >= 12  # after the twelve cells that were there
    mine = [m for m in BENCH["per_layer"] if m["name"] in READERS]
    assert [m["name"] for m in mine] == list(READERS)
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "updates_per_s_chip"
        reader = spec.metric_reader(m["name"])
        assert reader is not None and reader.__doc__
    assert [m["layer"] for m in mine] == 3 * ["store gather/scatter"] + ["worker step"]
    assert [m["unit"] for m in mine] == ["ms", "%", "%", "ms"]
    assert [m["better"] for m in mine] == ["lower", "higher", "lower", "lower"]
    assert [m["source"] for m in mine] == [
        "device_trace", "device_trace", "program_counter", "device_trace"]
    per_layer = {m["name"] for m in spec.metrics_of(BENCH, "per_layer", CELL)}
    # the general metrics list no cells and read this one as they read cell 9
    assert set(READERS) | {
        "step.device_ms", "store.pull_device_ms", "store.push_device_ms",
        "store.gather_scatter_roofline", "step.compute_device_ms",
        "device.idle_share", "device.peak_hbm_bytes", "step.unscoped_share",
        "driver.dispatch_ms", "setup.compiles",
    } <= per_layer
    assert not {"store.rule_path_device_ms", "store.rule_path_roofline",
                "store.rule_distinct_share", "store.rule_rows_share"} & per_layer
    assert {m["name"] for m in spec.metrics_of(BENCH, "end_to_end", CELL)} == {
        "updates_per_s_chip", "setup_s",
    }
    assert lint.problems(spec.ROOT) == []
    four = [w["name"] for w in BENCH["workloads"] if w["chips"] == 4]
    assert CELL not in four and len(four) <= max(1, len(BENCH["workloads"]) // 4)


def test_the_configuration_is_the_released_table_uncut_at_602_lanes():
    from flink_parameter_server_tpu.models import glove as gl

    sizes = CFG["source_sizes"]
    for key in ("vocab_size", "dim", "x_max", "alpha", "eta", "corpus_tokens",
                "window"):
        assert CFG[key] == sizes[key], key
    assert (CFG["vocab_size"], CFG["dim"]) == (2_196_017, 300)
    assert (CFG["x_max"], CFG["alpha"], CFG["eta"]) == (100.0, 0.75, 0.05)
    assert CFG["batch"] == 32_768 and CFG["pool_batches"] == 64
    assert CFG["dtype"] == "float32" and CFG["driver"] == {
        "steps_per_call": 1, "dump_model": False}
    model = gl.GloVeConfig(CFG["vocab_size"], CFG["dim"])
    assert model.num_rows == 4_392_034 and model.row_lanes == 602
    assert model.num_rows * model.row_lanes * 4 == 10_576_017_872
    spec_ = jax.eval_shape(lambda: gl.make_store(model)).spec
    assert spec_.layout == "packed" and spec_.pack == 1
    assert spec_.table_shape() == (4_392_040, 640)
    assert 4_392_040 * 640 * 4 == 11_243_622_400
    assert "11,243,622,400" in CFG["reduced_why"]
    assert 0.70 < 11_243_622_400 / 16e9 < 0.71
    for word in ("from memory", "bulk-synchronous", "Hogwild"):
        assert word in json.dumps(CFG["assumed"]), word
    assert any("ONE rule step" in g for g in CFG["guarantees"])
    assert (DRY["cfg"]["vocab_size"], DRY["cfg"]["batch"]) == (512, 512)
    assert DRY["cfg"]["pool_batches"] == 4


def test_the_law_s_closed_form_numbers_are_the_traffic_file_s():
    keys = FULL["traffic_spec"]["keys"]
    assert keys == {"kind": "zipf", "a": 1.3}
    assert FAM.pair_mass(CFG) == pytest.approx(4.9207e12, rel=1e-4)
    got = FAM.law_numbers(CFG, keys)
    assert got["nonzeros"] == pytest.approx(1.835e10, rel=2e-3)
    assert got["hottest_share"] == pytest.approx(1.197e-4, rel=2e-3)
    assert got["top_1000_share"] == pytest.approx(0.0921, abs=2e-4)
    assert got["top_100000_share"] == pytest.approx(0.5709, abs=2e-4)
    assert got["distinct_a_side"] == pytest.approx(27_771, abs=2)
    assert got["hottest_lanes_a_batch"] == pytest.approx(3.92, abs=0.01)
    said = FULL["traffic_spec"]["keys_source"]
    for number in ("1.83e10", "0.012 %", "9.2 %", "57 %", "27,771", "3.9"):
        assert number in said, number
    found = FAM.law(CFG, keys)
    assert found["cdf"][-1] == pytest.approx(1.0) and (np.diff(found["cdf"]) >= 0).all()
    assert found["size"].sum() == CFG["vocab_size"] and found["first"][0] == 0
    assert (found["size"][:FAM.SINGLE_RANKS - 1] == 1).all()
    # a word's shares sum to one over the vocabulary
    assert (found["marginal"] * found["size"]).sum() == pytest.approx(1.0)


def test_the_batches_are_a_function_of_the_seed_and_follow_the_law():
    keys = FULL["traffic_spec"]
    a = FAM.host_batches(CFG, keys, 2**31 + 5, 2)
    b = FAM.host_batches(CFG, keys, 2**31 + 5, 3)
    for x, y in zip(a, b):
        assert all(np.array_equal(x[k], y[k]) for k in x)
    assert not np.array_equal(a[0]["word"], a[1]["word"])
    first = a[0]
    assert first["word"].dtype == np.int32 and first["count"].dtype == np.float32
    assert first["mask"].all() and first["word"].shape == (32_768,)
    for side in ("word", "context"):
        assert 0 <= first[side].min() and first[side].max() < CFG["vocab_size"]
        # ~27,771 distinct of 32,768 in closed form
        assert len(np.unique(first[side])) == pytest.approx(27_771, abs=250)
    p = FAM.unigram(CFG, keys["keys"])
    want = np.maximum(1.0, FAM.pair_mass(CFG) * p[first["word"]] * p[first["context"]])
    assert np.allclose(first["count"], want, rtol=1e-6) and first["count"].min() == 1
    # both branches of the weight: rare pairs under x_max, hot pairs over it
    assert 0.9 < (first["count"] < CFG["x_max"]).mean() < 0.995
    dry = FAM.host_batches(DRY["cfg"], DRY["traffic_spec"], 3, 1)[0]
    assert (dry["count"] < 100).any() and (dry["count"] > 100).any()


def test_the_bytes_a_step_must_move_on_a_hand_counted_batch():
    keys = FULL["traffic_spec"]["keys"]
    distinct = FAM.distinct_rows_per_step(CFG, keys)
    assert distinct == pytest.approx(2 * 27_771, abs=3)
    # 65,536 keys x 301 live gradient lanes read once; every distinct row's
    # 602 lanes read once and written once
    rule = FAM.rule_path_bytes_per_step(CFG)
    assert rule == pytest.approx(4 * (65_536 * 301 + 2 * 602 * distinct))
    assert rule == pytest.approx(346.4e6, rel=2e-3)
    # the pull: 301 lanes a key
    assert FAM.hbm_bytes_per_step(CFG) == pytest.approx(
        4 * 65_536 * 301 + rule)
    # two records, three distinct rows, at a toy width, counted by hand
    tiny = {**CFG, "dim": 3, "batch": 2, "vocab_size": 5}
    few = FAM.distinct_rows_per_step(tiny, {"kind": "uniform"})
    want = 4 * (2 * 2 * 4 + 2 * 8 * few)
    assert FAM.rule_path_bytes_per_step({**tiny, "traffic": "train-uniform"}) == (
        pytest.approx(want))
    assert 2 < few < 4  # of 2 draws from 5 a side: 1.8 distinct a side


def _checked(cfg, seed, logic=None):
    """The check ``chipbench/run.py`` makes, in process at the dry-run sizes."""
    ref = spec.reference(cfg)
    own, store = FAM.build(cfg, seed, None)
    batches = FAM.host_batches(
        cfg, DRY["traffic_spec"], seed, cfg["reference"]["batches"])
    ids = ref.touched(batches)
    before = FAM.rows(store, (), ids)
    step = jax.jit(make_train_step(logic or own, store.spec))
    table = store.table
    for b in batches:
        table, _, _ = step(table, (), b)
    got = FAM.rows(type(store)(store.spec, table), (), ids)
    return run._check_rows(
        cfg["reference"], ref.apply(cfg, before, ids, batches), got, before)


@pytest.mark.parametrize("seed", [3, 77, 2**31 + 12, 900_000_011])
def test_the_system_is_within_the_reference_s_allowances(seed):
    failures, worst = _checked(DRY["cfg"], seed % (2**31 - 1))
    assert failures == [] and worst["share"] < 0.5, worst


def test_bfloat16_gradients_fail_the_check():
    from flink_parameter_server_tpu.models import glove as gl

    class Rounded(gl.GloVe):
        def step(self, state, batch, pulled):
            state, req, out = super().step(state, batch, pulled)
            req.deltas = req.deltas.astype(jnp.bfloat16).astype(jnp.float32)
            return state, req, out

    cfg = DRY["cfg"]
    logic = Rounded(gl.GloVeConfig(cfg["vocab_size"], cfg["dim"]))
    failures, worst = _checked(cfg, 3, logic=logic)
    assert len(failures) >= 1 and worst["share"] > 20, worst


def test_the_reference_is_the_equations_record_by_record():
    ref = spec.reference(CFG)
    rng = np.random.default_rng(1)
    cfg = {**CFG, "dim": 4}
    ids = {"word": np.array([2, 5, 5]), "context": np.array([1, 3, 3])}
    rows = {
        s: np.concatenate(
            [rng.normal(size=(3, 5)) * 0.1, 1 + rng.random((3, 5))], axis=1
        ).astype(np.float32) for s in ("word", "context")
    }
    batch = {
        "word": np.array([5, 2, 5, 5], np.int32),
        "context": np.array([3, 3, 1, 3], np.int32),
        "count": np.array([1.0, 40.0, 250.0, 7.0], np.float32),
        "mask": np.array([True, True, True, False]),
    }
    want, moved = ref.apply(cfg, rows, ids, [batch])
    f64 = {s: rows[s].astype(np.float64) for s in rows}
    at = {"word": {2: 0, 5: 1}, "context": {1: 0, 3: 1}}
    grad = {s: np.zeros((2, 5)) for s in rows}
    for i, j, x, live in zip(batch["word"], batch["context"], batch["count"],
                             batch["mask"]):
        if not live:
            continue
        wi, cj = f64["word"][at["word"][i]], f64["context"][at["context"][j]]
        diff = wi[:4] @ cj[:4] + wi[4] + cj[4] - np.log(x)
        s = min(1.0, (x / 100.0) ** 0.75) * diff
        grad["word"][at["word"][i]] += np.append(s * cj[:4], s)
        grad["context"][at["context"][j]] += np.append(s * wi[:4], s)
    for side in rows:
        for r in range(2):
            u = 0.05 * grad[side][r]
            row = f64[side][r]
            assert np.allclose(want[side][r, :5], row[:5] - u / np.sqrt(row[5:]),
                               rtol=1e-5, atol=1e-7)
            assert np.allclose(want[side][r, 5:], row[5:] + u * u, rtol=1e-6)
        # the padding's repeat shows the largest id's row
        assert np.array_equal(want[side][2], want[side][1])
        assert (moved[side][:2] > 0).all()


def test_the_four_readers_on_a_synthetic_run(monkeypatch):
    from chipbench import peaks
    from flink_parameter_server_tpu.telemetry import registry as registry_mod

    ms, roof, share, grad = (spec.metric_reader(n) for n in READERS)
    fresh = registry_mod.MetricsRegistry()
    monkeypatch.setattr(registry_mod, "get_registry", lambda: fresh)
    # the parent (no such scope or gauge), and a run without a trace: nothing
    for reader in (ms, roof, share, grad):
        assert reader.__doc__ and reader.read(_ctx()) is None
    where = os.path.join(run.OUT_DIR, "trace", CELL)
    monkeypatch.setitem(program_trace._RUNS, where, {"scope_ms": {
        "ps.pull": 2.0, "ps.compute": 0.3, "ps.cooc_grad_rows": 0.7,
        "ps.combine": 3.0, "ps.rule": 2.5, "ps.push": 3.5,
    }})
    traced = _ctx(
        trace={"step_device_ms": 12.5}, peaks=peaks.peaks_for("TPU v5 lite"))
    assert ms.read(traced) == pytest.approx(9.0)
    assert grad.read(traced) == 0.7
    least_ms = FAM.rule_path_bytes_per_step(CFG) / 819e9 * 1e3
    assert least_ms == pytest.approx(0.423, abs=2e-3)
    assert roof.read(traced) == pytest.approx(100 * least_ms / 9.0)
    assert 0 < roof.read(traced) < 100
    # without the chip's peaks (a dry run) the share is left out
    assert roof.read(_ctx(trace={"step_device_ms": 12.5})) is None
    # an add store has ps.push and no ps.combine, and no such logic scope
    monkeypatch.setitem(
        program_trace._RUNS, where, {"scope_ms": {"ps.pull": 5.0, "ps.push": 9.0}})
    for reader in (ms, roof, grad):
        assert reader.read(traced) is None
    # the program's counts of a step, closed form: 85 % distinct
    assert share.read(traced) is None
    fresh.gauge("store_rule_keys", component="train").set(65_536)
    assert share.read(traced) is None
    fresh.gauge("store_rule_rows", component="train").set(55_542)
    assert share.read(traced) == pytest.approx(84.75, abs=0.01)
    # the whole step's roofline reads the family's bytes
    whole = spec.metric_reader("store.gather_scatter_roofline")
    monkeypatch.setitem(program_trace._RUNS, where, {"scope_ms": {"ps.pull": 2.0}})
    traced["counters"]["hbm_bytes_per_step"] = FAM.hbm_bytes_per_step(CFG)
    assert whole.read(traced) == pytest.approx(
        100 * (least_ms + 4 * 65_536 * 301 / 819e9 * 1e3) / 12.5, rel=1e-6)


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
    assert {"driver.dispatch_ms", "store.wide_rule_distinct_share"} <= set(
        last["metric_names"])
    assert not {"store.wide_rule_path_device_ms",
                "step.cooc_grad_rows_device_ms"} & set(last["metric_names"])
