"""Cell 14: PyTorch-BigGraph's full-Freebase run at one bucket of its sixteen
partitions on one chip (`pbg-freebase-d100-p16.train-edges-uniform`):
15,152,092 x 101 f32 rule rows of ONE register, 40,000 edges a step scored
against in-chunk and uniform negatives, the plain reference and the six
readers."""
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

CELL = "pbg-freebase-d100-p16.train-edges-uniform"
CONFIG = "pbg-freebase-d100-p16"
BENCH = spec.load_benchmark()
FULL = spec.resolve(BENCH, CELL, dry_run=False)
DRY = spec.resolve(BENCH, CELL, dry_run=True)
CFG = FULL["cfg"]
FAM = spec.family("kge")
READERS = (
    "store.reg_rule_path_device_ms", "store.reg_rule_path_roofline",
    "store.reg_rule_distinct_share", "step.neg_score_device_ms",
    "step.neg_score_mxu_share", "step.operator_device_ms",
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
    assert cell["traffic"] == "train-edges-uniform" and len(cell["why"]) <= 200
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_entities"] == CFG["reduced"]
    assert entry["source"] == CFG["source"] and len(entry["source"]) == 194
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert CFG["family"] == "kge" and CFG["mesh"] is None
    assert CFG["traffic"] == cell["traffic"]
    names = [w["name"] for w in BENCH["workloads"]]
    assert names.index(CELL) >= 13  # after the thirteen cells that were there
    mine = [m for m in BENCH["per_layer"] if m["name"] in READERS]
    assert [m["name"] for m in mine] == list(READERS)
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "updates_per_s_chip"
        reader = spec.metric_reader(m["name"])
        assert reader is not None and reader.__doc__
    assert [m["layer"] for m in mine] == (
        3 * ["store gather/scatter"] + 3 * ["worker step"])
    assert [m["unit"] for m in mine] == ["ms", "%", "%", "ms", "%", "ms"]
    assert [m["better"] for m in mine] == [
        "lower", "higher", "lower", "lower", "higher", "lower"]
    assert [m["source"] for m in mine] == [
        "device_trace", "device_trace", "program_counter", "device_trace",
        "device_trace", "device_trace"]
    # no other entry names the cell
    assert [m["name"] for m in BENCH["per_layer"]
            if CELL in m.get("workloads", ())] == list(READERS)
    per_layer = {m["name"] for m in spec.metrics_of(BENCH, "per_layer", CELL)}
    # the general metrics list no cells and read this one as they read cell 13
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


def test_the_configuration_is_one_bucket_of_the_source_s_sixteen_partitions():
    from flink_parameter_server_tpu.models import kge

    sizes = CFG["source_sizes"]
    assert (sizes["num_entities"], sizes["num_relations"], sizes["num_edges"]) == (
        121_216_723, 25_291, 2_725_070_599)
    assert (sizes["dimension"], sizes["batch_size"], sizes["num_batch_negs"],
            sizes["num_uniform_negs"]) == (100, 1_000, 50, 50)
    assert sizes["num_partitions"] == [1, 4, 8, 16]
    assert sizes["memory_gb_by_partitions"] == {
        "1": 59.6, "4": 30.4, "8": 15.5, "16": 6.8}
    # no width is cut; the rows are one off-diagonal bucket of 16 x 16
    assert (CFG["dim"], CFG["chunk"], CFG["uniform_negatives"]) == (100, 50, 50)
    assert CFG["num_relations"] == sizes["num_relations"]
    assert CFG["num_partitions"] == 16
    assert FAM.partition_rows(CFG) == 7_576_046 == -(-121_216_723 // 16)
    assert FAM.bucket_rows(CFG) == 15_152_092 == CFG["num_entities"]
    assert CFG["batch"] == 40_000 and FAM.chunks_per_step(CFG) == 800
    assert FAM.keys_per_step(CFG) == 160_000
    # the pool is one pass over the bucket's own edges: the source's edges
    # over its 16 x 16 buckets, in batches of 40,000
    assert int(FAM.edges_per_bucket(CFG)) == 10_644_807 == 2_725_070_599 // 256
    assert CFG["pool_batches"] == 266 == round(
        FAM.edges_per_bucket(CFG) / CFG["batch"])
    assert "10,644,807" in CFG["assumed"]["pool_batches"]
    assert CFG["dtype"] == "float32"
    assert CFG["driver"] == {"steps_per_call": 1, "dump_model": False}
    assert (CFG["lr"], CFG["lr_rel"], CFG["eps"]) == (0.1, 0.01, 1e-10)
    model = kge.KGEConfig(CFG["num_entities"], CFG["num_relations"], CFG["dim"])
    assert model.row_lanes == 101
    spec_ = jax.eval_shape(lambda: kge.make_store(model)).spec
    assert spec_.layout == "packed" and spec_.pack == 1
    assert spec_.worker_width == 100 and spec_.value_shape == (101,)
    # ONE register a row: 7.76 GB, x 1.02 at most on the chip
    assert spec_.table_shape() == (15_152_096, 128)
    assert 15_152_092 * 128 * 4 == 7_757_871_104
    assert 15_152_096 * 128 * 4 <= 1.02 * 7_757_871_104
    assert "7,757,871,104" in CFG["reduced_why"]
    assert 0.48 < 7_757_871_104 / 16e9 < 0.49
    for word in ("from memory", "bulk-synchronous", "HOGWILD", "uniform"):
        assert word in json.dumps(CFG["assumed"]), word
    assert any("ONE rule step" in g for g in CFG["guarantees"])
    assert any("bit-equal" in g for g in CFG["guarantees"])
    dry = DRY["cfg"]
    assert (dry["dim"], dry["chunk"], dry["uniform_negatives"]) == (100, 50, 50)
    assert dry["num_entities"] < 1e5 and dry["batch"] % dry["chunk"] == 0
    assert CFG["reference"]["batches"] == 2 and dry["reference"]["batches"] == 1
    assert {k: v for k, v in dry["reference"].items() if k not in (
        "batches", "why")} == {k: v for k, v in CFG["reference"].items()
                               if k not in ("batches", "why")}


def test_the_closed_form_counts_of_a_step():
    # 99.5 % of a side's 80,000 keys are rows of their own under the uniform
    # law: m (1 - (1 - 1/m)^keys), m a partition's rows
    assert FULL["traffic_spec"]["keys"] == {"kind": "uniform"}
    distinct = FAM.distinct_rows_per_step(CFG)
    assert distinct / 2 == pytest.approx(79_579, abs=1)
    assert distinct / FAM.keys_per_step(CFG) == pytest.approx(0.9947, abs=1e-4)
    for number in ("79,579", "80,000"):
        assert number in FULL["traffic_spec"]["keys_source"], number
        assert number in CFG["assumed"]["batch"], number
    # 800 chunks x 2 sides x 3 products x 2 x 50 x 100 x 100
    assert FAM.score_flops_per_step(CFG) == 4.8e9
    # the server side: 160,000 x 100 pushed lanes read, every distinct row's
    # 101 lanes read once and written once; the pull 100 lanes a key
    rule = FAM.rule_path_bytes_per_step(CFG)
    assert rule == pytest.approx(4 * (160_000 * 100 + 2 * 101 * distinct))
    assert rule == pytest.approx(192.6e6, rel=2e-3)
    assert FAM.hbm_bytes_per_step(CFG) == pytest.approx(4 * 160_000 * 100 + rule)
    # two chunks of three edges and two negatives a side, counted by hand
    tiny = {**CFG, "batch": 6, "chunk": 3, "uniform_negatives": 2,
            "num_entities": 40, "dim": 4}
    assert FAM.keys_per_step(tiny) == 2 * 2 * 5
    assert FAM.score_flops_per_step(tiny) == 2 * 2 * 3 * 2 * 3 * 5 * 4
    few = FAM.distinct_rows_per_step(tiny)
    assert 2 * 7 < few < 20  # ten draws from twenty rows a side
    assert FAM.rule_path_bytes_per_step(tiny) == pytest.approx(
        4 * (20 * 4 + 2 * 5 * few))


def test_the_batches_are_a_function_of_the_seed_and_stay_in_their_partitions():
    traffic = FULL["traffic_spec"]
    a = FAM.host_batches(CFG, traffic, 2**31 + 5, 2)
    b = FAM.host_batches(CFG, traffic, 2**31 + 5, 3)
    for x, y in zip(a, b):
        assert all(np.array_equal(x[k], y[k]) for k in x)
    assert not np.array_equal(a[0]["source"], a[1]["source"])
    first, half = a[0], 7_576_046
    assert all(v.dtype == np.int32 for v in first.values())
    assert first["source"].shape == first["relation"].shape == (800, 50)
    assert first["source_negatives"].shape == (800, 50)
    for side, lo in (("source", 0), ("destination", half)):
        for name in (side, side + "_negatives"):
            assert lo <= first[name].min() and first[name].max() < lo + half
        keys = np.concatenate([first[side], first[side + "_negatives"]], axis=1)
        # ~79,579 distinct of 80,000 in closed form
        assert len(np.unique(keys)) == pytest.approx(79_579, abs=120)
    assert 0 <= first["relation"].min() and first["relation"].max() < 25_291
    assert len(np.unique(first["relation"])) == pytest.approx(
        25_291 * (1 - np.exp(-40_000 / 25_291)), rel=0.02)
    # the step's keys are the four groups side by side, 200 a chunk
    from flink_parameter_server_tpu.models import kge

    logic = kge.ComplExNegatives(kge.KGEConfig(15_152_092, 25_291))
    assert logic.keys(first).shape == (800, 200)


def _checked(cfg, seed, logic=None):
    """The check ``chipbench/run.py`` makes, in process at the dry-run sizes."""
    ref = spec.reference(cfg)
    own, store = FAM.build(cfg, seed, None)
    logic = logic or own
    # (two batches, as the cell checks: the second reads what the first wrote)
    batches = FAM.host_batches(
        cfg, DRY["traffic_spec"], seed, CFG["reference"]["batches"])
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


@pytest.mark.parametrize("seed", [3, 77, 2**31 + 12, 900_000_011])
def test_the_system_is_within_the_reference_s_allowances(seed):
    failures, worst = _checked(DRY["cfg"], seed % (2**31 - 1))
    assert failures == [] and 0 < worst["share"] < 0.5, worst


@pytest.mark.parametrize("fault", ["bfloat16_gradients", "default_precision"])
def test_the_nearest_precision_below_fails_the_check(fault, monkeypatch):
    from flink_parameter_server_tpu.models import kge

    class Rounded(kge.ComplExNegatives):
        def step(self, state, batch, pulled):
            state, req, out = super().step(state, batch, pulled)
            req.deltas = req.deltas.astype(jnp.bfloat16).astype(jnp.float32)
            return state, req, out

    cfg = DRY["cfg"]
    model = kge.KGEConfig(cfg["num_entities"], cfg["num_relations"], cfg["dim"])
    if fault == "default_precision":
        # one bfloat16 pass a product, stood in for on the CPU
        real = jnp.einsum
        monkeypatch.setattr(kge.jnp, "einsum", lambda eq, a, b, **kw: real(
            eq, a.astype(jnp.bfloat16).astype(jnp.float32),
            b.astype(jnp.bfloat16).astype(jnp.float32), **kw))
        failures, worst = _checked(cfg, 3)
    else:
        failures, worst = _checked(cfg, 3, logic=Rounded(model))
    assert len(failures) >= 1 and worst["share"] > 10, worst


def test_the_reference_is_the_equations_edge_by_edge():
    ref = spec.reference(CFG)
    rng = np.random.default_rng(1)
    dim, rels = 4, 3
    cfg = {**CFG, "dim": dim}
    # one chunk of two edges and one uniform id a side; entity 2 is the
    # source of both edges, entity 7 a destination AND the uniform negative
    batch = {
        "source": np.array([[2, 2]], np.int32),
        "destination": np.array([[7, 5]], np.int32),
        "relation": np.array([[1, 1]], np.int32),
        "source_negatives": np.array([[3]], np.int32),
        "destination_negatives": np.array([[7]], np.int32),
    }
    ids = ref.touched([batch])
    known = np.unique(ids["entity"])
    assert list(known) == [2, 3, 5, 7] and ids["entity"].size == 6
    rows = {
        "entity": np.concatenate([
            rng.normal(size=(6, dim)) * 0.3, rng.random((6, 1)) * 0.01,
        ], axis=1).astype(np.float32),
        "operator": np.concatenate([
            rng.normal(size=(rels, 2 * dim)), rng.random((rels, 2 * dim)) * 0.1,
        ], axis=1).astype(np.float32),
    }
    rows["entity"][4:] = rows["entity"][3]  # the padding repeats the last row
    want, moved = ref.apply(cfg, rows, ids, [batch])
    f64 = rows["entity"][:4].astype(np.float64)
    at = {e: i for i, e in enumerate(known)}
    a, b = (rows["operator"][1, k * dim:(k + 1) * dim].astype(np.float64)
            for k in (0, 1))

    def cx(v):
        return v[:dim // 2] + 1j * v[dim // 2:]

    def flat(z):
        return np.concatenate([z.real, z.imag])

    grad = np.zeros((4, dim))
    d_a, d_b = np.zeros(dim), np.zeros(dim)
    src, dst = [2, 2], [7, 5]
    all_dst, all_src = dst + [7], src + [3]
    for e in range(2):
        s, o = f64[at[src[e]], :dim], f64[at[dst[e]], :dim]
        for op, end, others, own_end, d_op in (
            (a, s, all_dst, src[e], d_a), (b, o, all_src, dst[e], d_b),
        ):
            turned = flat(cx(op) * cx(end))
            scores = np.array([turned @ f64[at[m], :dim] for m in others])
            p = np.exp(scores - scores.max())
            p /= p.sum()
            p[e] -= 1.0
            d_turned = sum(p[m] * f64[at[others[m]], :dim] for m in range(3))
            for m in range(3):
                grad[at[others[m]]] += p[m] * turned
            grad[at[own_end]] += flat(np.conj(cx(op)) * cx(d_turned))
            d_op += flat(np.conj(cx(end)) * cx(d_turned))
    for r in range(4):
        acc = f64[r, dim] + np.mean(grad[r] ** 2)
        assert np.allclose(want["entity"][r, dim], acc, rtol=1e-5)
        assert np.allclose(
            want["entity"][r, :dim],
            f64[r, :dim] - 0.1 * grad[r] / (np.sqrt(acc) + 1e-10),
            rtol=1e-4, atol=1e-6)
    # the padding's repeats show the largest id's row
    assert np.array_equal(want["entity"][4], want["entity"][3])
    assert (moved["entity"][:4] > 0).all()
    ops = rows["operator"].astype(np.float64)
    for k, g in ((0, d_a), (1, d_b)):
        acc = ops[1, (2 + k) * dim:(3 + k) * dim] + g * g
        assert np.allclose(
            want["operator"][1, (2 + k) * dim:(3 + k) * dim], acc, rtol=1e-5)
        assert np.allclose(
            want["operator"][1, k * dim:(k + 1) * dim],
            ops[1, k * dim:(k + 1) * dim] - 0.01 * g / (np.sqrt(acc) + 1e-10),
            rtol=1e-4, atol=1e-6)
    # a relation no edge names: bit-equal, and nothing allowed for deltas
    for r in (0, 2):
        assert want["operator"][r].tobytes() == rows["operator"][r].tobytes()
        assert not moved["operator"][r].any()


def test_the_six_readers_on_a_synthetic_run(monkeypatch):
    from chipbench import peaks
    from flink_parameter_server_tpu.telemetry import registry as registry_mod

    ms, roof, share, score, mxu, oper = (
        spec.metric_reader(n) for n in READERS)
    fresh = registry_mod.MetricsRegistry()
    monkeypatch.setattr(registry_mod, "get_registry", lambda: fresh)
    # the parent (no such scope or gauge), and a run without a trace: nothing
    for reader in (ms, roof, share, score, mxu, oper):
        assert reader.__doc__ and reader.read(_ctx()) is None
    where = os.path.join(run.OUT_DIR, "trace", CELL)
    monkeypatch.setitem(program_trace._RUNS, where, {"scope_ms": {
        "ps.pull": 2.0, "ps.compute": 0.3, "ps.kge_operator": 0.6,
        "ps.kge_score": 1.5, "ps.kge_score_grad": 2.5,
        "ps.kge_operator_update": 0.9,
        "ps.combine": 3.0, "ps.rule": 2.5, "ps.push": 3.5,
    }})
    traced = _ctx(
        trace={"step_device_ms": 17.3}, peaks=peaks.peaks_for("TPU v5 lite"))
    assert ms.read(traced) == pytest.approx(9.0)
    assert score.read(traced) == pytest.approx(4.0)
    assert oper.read(traced) == pytest.approx(1.5)
    least_ms = FAM.rule_path_bytes_per_step(CFG) / 819e9 * 1e3
    assert least_ms == pytest.approx(0.2352, abs=2e-3)
    assert roof.read(traced) == pytest.approx(100 * least_ms / 9.0)
    assert 0 < roof.read(traced) < 100
    # 4.8 GFLOP in 4 ms over 197 TFLOP/s
    assert mxu.read(traced) == pytest.approx(100 * 4.8e9 / 4e-3 / 197e12)
    assert 0 < mxu.read(traced) < 17
    # without the chip's peaks (a dry run) the shares are left out
    for reader in (roof, mxu):
        assert reader.read(_ctx(trace={"step_device_ms": 17.3})) is None
    # an add store has ps.push and no ps.combine, and no such logic scope
    monkeypatch.setitem(
        program_trace._RUNS, where, {"scope_ms": {"ps.pull": 5.0, "ps.push": 9.0}})
    for reader in (ms, roof, score, mxu, oper):
        assert reader.read(traced) is None
    # the program's counts of a step, closed form: 99.5 % distinct
    assert share.read(traced) is None
    fresh.gauge("store_rule_keys", component="train").set(160_000)
    assert share.read(traced) is None
    fresh.gauge("store_rule_rows", component="train").set(159_158)
    assert share.read(traced) == pytest.approx(99.47, abs=0.01)
    # the whole step's roofline reads the family's bytes
    whole = spec.metric_reader("store.gather_scatter_roofline")
    monkeypatch.setitem(program_trace._RUNS, where, {"scope_ms": {"ps.pull": 2.0}})
    traced["counters"]["hbm_bytes_per_step"] = FAM.hbm_bytes_per_step(CFG)
    assert whole.read(traced) == pytest.approx(
        100 * (least_ms + 4 * 160_000 * 100 / 819e9 * 1e3) / 17.3, rel=1e-6)


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
    assert {"driver.dispatch_ms", "store.reg_rule_distinct_share"} <= set(
        last["metric_names"])
    assert not {"store.reg_rule_path_device_ms", "step.neg_score_device_ms",
                "step.neg_score_mxu_share", "step.operator_device_ms",
                } & set(last["metric_names"])
