"""Cell 18: GraphSAGE on one machine's share of ogbn-papers100M
(`sage-papers100m-p8.train-seeds-uniform`): three stores a step only reads
(13,882,496 row ends and 403,921,468 neighbour ids, int32 scalar rows;
13,882,495 feature rows of 128 lanes) and a step that pulls seven rounds from
them, each round's keys computed from the rows before; the plain reference
with its own sampler arithmetic, the byte and operation laws, the six
readers."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import lint, program_trace, run, spec, store_trace

CELL = "sage-papers100m-p8.train-seeds-uniform"
CONFIG = "sage-papers100m-p8"
BENCH = spec.load_benchmark()
FULL = spec.resolve(BENCH, CELL, dry_run=False)
DRY = spec.resolve(BENCH, CELL, dry_run=True)
CFG = FULL["cfg"]
FAM = spec.family("sage")
REF = spec.reference(CFG)
READERS = (
    "step.sample_device_ms", "store.feature_pull_device_ms",
    "store.feature_pull_roofline", "step.sage_dense_device_ms",
    "step.sage_dense_mxu_share", "step.sample_live_share",
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
    assert cell["traffic"] == "train-seeds-uniform" and len(cell["why"]) <= 200
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == CFG["reduced"] == [
        "num_nodes", "num_edges", "num_train_nodes"]
    assert entry["source"] == CFG["source"] and len(entry["source"]) <= 200
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert CFG["family"] == "sage" and CFG["mesh"] is None
    assert CFG["architecture"] is None  # a deployment, not a catalog model
    names = [w["name"] for w in BENCH["workloads"]]
    assert names.index(CELL) >= 17  # after the seventeen cells that were there
    mine = [m for m in BENCH["per_layer"] if m["name"] in READERS]
    assert [m["name"] for m in mine] == list(READERS)
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "updates_per_s_chip"
        reader = spec.metric_reader(m["name"])
        assert reader is not None and reader.__doc__
    assert [m["source"] for m in mine] == 5 * ["device_trace"] + [
        "program_counter"]
    assert [m["unit"] for m in mine] == ["ms", "ms", "%", "ms", "%", "%"]
    assert [m["name"] for m in BENCH["per_layer"]
            if CELL in m.get("workloads", ())] == list(READERS)
    # the one general metric that finds nothing to read in a step that
    # pushes nothing lists the seventeen cells that stood
    push = next(m for m in BENCH["per_layer"]
                if m["name"] == "store.push_device_ms")
    assert push["workloads"] == names[:names.index(CELL)]
    assert {m["name"] for m in spec.metrics_of(BENCH, "end_to_end", CELL)} == {
        "updates_per_s_chip", "setup_s"}
    four = [w["name"] for w in BENCH["workloads"] if w["chips"] == 4]
    assert CELL not in four and len(four) <= max(1, len(names) // 4)
    assert lint.problems(spec.ROOT) == []


def test_the_configuration_is_the_source_s_model_on_a_share_of_eight():
    sizes = CFG["source_sizes"]
    assert CFG["widths"] == [128, 256, 256, 172]
    assert CFG["fanouts"] == [15, 10, 5] and CFG["batch"] == 1000
    assert (CFG["dropout"], CFG["learning_rate"]) == (0.5, 0.003)
    assert (CFG["beta1"], CFG["beta2"], CFG["eps"]) == (0.9, 0.999, 1e-8)
    assert sizes["num_nodes"] == 111_059_956
    assert sizes["num_edges_bidirected"] == 2 * sizes["num_edges"]
    assert CFG["num_nodes"] == -(-sizes["num_nodes"] // 8) == 13_882_495
    assert CFG["num_edges"] == sizes["num_edges_bidirected"] // 8
    assert CFG["num_train_nodes"] == sizes["num_train_nodes"] // 8
    held = (CFG["num_nodes"] * 128 * 4 + CFG["num_edges"] * 4
            + (CFG["num_nodes"] + 1) * 4)
    assert 8.0e9 < held < 9.0e9 and held / 16e9 > 0.5
    assert sum(n * m for n, m in zip(CFG["widths"], CFG["widths"][1:])
               ) * 2 + sum(CFG["widths"][1:]) == 285_356
    # the degree law's mean is the source's, its thresholds integers
    law = CFG["degree_law"]
    d = np.arange(1, law["cap"] + 1, dtype=np.float64)
    p = d ** -law["exponent"]
    assert abs((p * d).sum() / p.sum() - sizes["mean_degree_bidirected"]) < 1e-3
    for key in ("machines", "graph", "sampler", "sampler_key", "fanout_order",
                "initialisation", "matmul_precision", "bulk_synchronous"):
        assert key in CFG["assumed"], key
    ref = CFG["reference"]
    # ONE checked batch on the chip (`reference.why` says what a second
    # inherits from Adam's first step), two in the dry run, three in
    # tests/chipbench_tests/test_chipbench_references.py
    assert ref["batches"] == 1 and len(ref["why"]) > 400
    assert DRY["cfg"]["reference"]["batches"] == 2
    assert {k: v for k, v in DRY["cfg"]["reference"].items()
            if k not in ("batches", "why")} == {
        k: v for k, v in ref.items() if k not in ("batches", "why")}
    assert DRY["cfg"]["widths"] == CFG["widths"]
    assert DRY["cfg"]["fanouts"] == CFG["fanouts"]
    assert FULL["traffic_spec"]["keys"] == {"kind": "uniform"}


def test_the_step_s_lanes_bytes_and_operations_in_closed_form():
    assert REF.lanes_at(CFG, 1000) == [1000, 5000, 50_000, 750_000]
    assert FAM.keys_per_step(CFG) == {
        "off": 112_000, "nbr": 805_000, "feat": 806_000}
    assert FAM.feature_pull_bytes_per_step(CFG) == 806_000 * 512 * 2
    assert FAM.hbm_bytes_per_step(CFG) == (
        8 * 917_000 + 4 * 806_000 + 806_000 * 1024)
    forward = 2 * (56_000 * 128 * 256 + 6_000 * 256 * 256 + 1_000 * 256 * 172)
    assert forward == 4_544_512_000
    assert FAM.dense_flops_per_step(CFG) == 2.0 * (
        3 * forward - 2 * 56_000 * 128 * 256)
    model = FAM._model(CFG)
    assert model.macs_per_step(1000) == forward
    assert model.nodes_at(1000) == (1000, 5000, 50_000, 750_000)


def test_the_reference_is_plain_and_names_nothing_of_the_program():
    with open(os.path.join(spec.ROOT, CFG["reference"]["file"])) as f:
        text = f.read()
    assert "flink_parameter_server_tpu" not in text
    assert "import jax" in text and "jax.random" in text
    assert "grad(" not in text and "jit(" not in text


def test_the_batches_are_seeds_of_the_share_s_training_nodes():
    cfg = DRY["cfg"]
    a = FAM.host_batches(cfg, {"keys": {"kind": "uniform"}}, 2**31 + 9, 3)
    b = FAM.host_batches(cfg, {"keys": {"kind": "uniform"}}, 2**31 + 9, 3)
    for x, y in zip(a, b):
        assert all(np.array_equal(x[k], y[k]) for k in x)
    seeds = np.concatenate([x["seed"] for x in a])
    labels = np.concatenate([x["label"] for x in a])
    assert seeds.dtype == labels.dtype == np.int32
    assert 0 <= seeds.min() and seeds.max() < cfg["num_train_nodes"]
    assert 0 <= labels.min() and labels.max() < 172
    # a node keeps its label
    first = {}
    for s, c in zip(seeds, labels):
        assert first.setdefault(int(s), int(c)) == int(c)
    assert all(x["mask"].all() for x in a)


def test_a_moved_row_of_a_read_only_store_fails_the_check():
    """The sample of the stores' rows stands in the one group: one bit
    of one sampled feature, or of one row end, and the
    check fails; as they were, it passes."""
    import jax

    from flink_parameter_server_tpu.core.transform import make_train_step

    cfg = DRY["cfg"]
    logic, store = FAM.build(cfg, 7, None)
    batches = FAM.host_batches(cfg, {"keys": {"kind": "uniform"}}, 7, 2)
    ids = REF.touched(batches)
    state = logic.init_state(jax.random.PRNGKey(0))
    before = FAM.rows(store, state, ids)
    assert {"features", "live", "key"} <= set(before)
    step = jax.jit(make_train_step(logic, store.spec))
    table = store.table
    for b in batches:
        table, state, _ = step(table, state, b)
    got = FAM.rows(type(store)(store.spec, table), state, ids)
    assert "features" not in got  # (the trees are walked from a fresh state)
    want = REF.apply(cfg, before, ids, batches)
    assert run._check_rows(cfg["reference"], want, got, before)[0] == []
    sample = REF.unlaid(cfg, got["parameters"])["sample"]
    flat = got["parameters"].reshape(-1)
    at = flat.size - sample.size
    for lane in (at + 3, at + sample.size // 2):  # an id's half, a feature's
        moved = flat.copy()
        moved[lane] += 1.0  # (the last bit of a half-word)
        failures, _ = run._check_rows(
            cfg["reference"], want,
            {**got, "parameters": moved.reshape(got["parameters"].shape)},
            before)
        assert failures


def test_the_labels_of_a_chained_step_s_ops():
    assert store_trace.label_of(
        "jit(step)/ps.pull/round.5/store.nbr/jit(packed_pull)/gather"
    ) == "pull.nbr"
    assert store_trace.label_of(
        "jit(step)/ps.pull/store.off/jit(packed_pull)/gather") == "pull.off"
    assert store_trace.label_of(
        "jit(step)/ps.pull/round.6/store.feat/jit(_take)/gather"
    ) == "pull.feat"
    assert store_trace.label_of("jit(step)/ps.sample/rem") is None
    assert program_trace._innermost_scope(
        "jit(step)/ps.sample/jit(_uniform)/threefry2x32") == "ps.sample"
    assert program_trace._innermost_scope(
        "jit(step)/ps.compute/ps.sage_dense/transpose(jvp(dot_general))"
    ) == "ps.sage_dense"
    assert program_trace._innermost_scope(
        "jit(step)/ps.pull/round.3/store.nbr/gather") == "ps.pull"


def test_the_six_readers_on_a_synthetic_run(monkeypatch):
    """Nothing to read (the parent, every other family): nothing reported.
    With the scopes' and the labels' milliseconds in hand: the sums, and the
    shares against the laws and the peaks."""
    for name in READERS:
        assert spec.metric_reader(name).read(_ctx()) is None
    scopes = {"ps.sample": 1.5, "ps.sage_dense": 3.0, "ps.dense_adam": 0.5,
              "ps.pull": 9.0}
    labels = {"pull.off": 0.5, "pull.nbr": 3.0, "pull.feat": 4.0}
    monkeypatch.setattr(
        program_trace, "scope_ms", lambda ctx, *names: sum(
            scopes[n] for n in names if n in scopes) or None)
    monkeypatch.setattr(
        store_trace, "store_ms", lambda ctx, *names: sum(
            labels[n] for n in names if n in labels) or None)
    ctx = _ctx(trace={"step_device_ms": 13.0}, peaks={
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    read = {name: spec.metric_reader(name).read(ctx) for name in READERS[:5]}
    assert read["step.sample_device_ms"] == 5.0
    assert read["store.feature_pull_device_ms"] == 4.0
    assert read["step.sage_dense_device_ms"] == 3.5
    assert read["store.feature_pull_roofline"] == pytest.approx(
        100 * (806_000 * 1024 / 819e9) / 4.0e-3)
    assert read["store.feature_pull_roofline"] < 100
    assert read["step.sage_dense_mxu_share"] == pytest.approx(
        100 * (FAM.dense_flops_per_step(CFG) / 197e12) / 3.5e-3)
    # another family's configuration has no such law: nothing
    other = spec.resolve(
        BENCH, "wdl-criteo-10m.train-fields-uniform", dry_run=False)["cfg"]
    assert spec.metric_reader("store.feature_pull_roofline").read(
        {**ctx, "cfg": other}) is None


def test_the_cells_dry_run_ends_correct_and_reads_its_counters():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.ROOT)
    done = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL, "--seed",
         str(2**31 + 75), "--seconds", "0.5", "--trace", "1", "--cpu-dry-run"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] and last["failed"] == 0 and last["failures"] == []
    assert "metrics" not in last
    # the program's spans and counters reach the line; the device's need a chip
    assert {"driver.dispatch_ms", "setup.compiles", "step.sample_live_share"
            } <= set(last["metric_names"])
    assert not set(READERS[:5]) & set(last["metric_names"])
