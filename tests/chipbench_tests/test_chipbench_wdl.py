"""Cell 17: Wide & Deep on the Criteo Terabyte record, TWO stores in one
train step (`wdl-criteo-10m.train-fields-uniform`): 27,262,976 hashed-cross
FTRL rows of 3 lanes beside 49,126,297 AdaGrad rows of 32 + 32 lanes, the
keys of the first hashed inside the step; the plain reference with its own
cross key, the eight readers and the trace's store labels."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from chipbench import lint, program_trace, run, spec, store_trace
from flink_parameter_server_tpu import DriverConfig, StreamingDriver

CELL = "wdl-criteo-10m.train-fields-uniform"
CONFIG = "wdl-criteo-10m"
BENCH = spec.load_benchmark()
FULL = spec.resolve(BENCH, CELL, dry_run=False)
DRY = spec.resolve(BENCH, CELL, dry_run=True)
CFG = FULL["cfg"]
FAM = spec.family("wdl")
REF = spec.reference(CFG)
READERS = (
    "store.wide_pull_device_ms", "store.wide_push_device_ms",
    "store.deep_pull_device_ms", "store.deep_push_device_ms",
    "step.cross_hash_device_ms", "step.wdl_dense_device_ms",
    "step.wdl_dense_mxu_share", "store.two_store_gather_scatter_roofline",
)
# the deployment's own hyper-parameters at the dry run's sizes
AT_SOURCE_RATES = {
    **DRY["cfg"], "acc0": CFG["acc0"], "warm_start": CFG["warm_start"]}


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
    assert cell["traffic"] == "train-fields-uniform"
    assert len(cell["why"]) <= 200
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == [] == CFG["reduced"]
    assert entry["source"] == CFG["source"] and len(entry["source"]) <= 200
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert CFG["family"] == "wdl" and CFG["mesh"] is None
    names = [w["name"] for w in BENCH["workloads"]]
    assert names.index(CELL) >= 16  # after the sixteen cells that were there
    assert len({w["config"] for w in BENCH["workloads"][:names.index(CELL) + 1]}
               ) == 15
    mine = [m for m in BENCH["per_layer"] if m["name"] in READERS]
    assert [m["name"] for m in mine] == list(READERS)
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "updates_per_s_chip"
        assert m["source"] == "device_trace"
        reader = spec.metric_reader(m["name"])
        assert reader is not None and reader.__doc__
    assert [m["layer"] for m in mine] == (
        4 * ["store gather/scatter"] + 3 * ["worker step"]
        + ["store gather/scatter"])
    assert [m["unit"] for m in mine] == 6 * ["ms"] + 2 * ["%"]
    assert [m["better"] for m in mine] == 6 * ["lower"] + 2 * ["higher"]
    # no other entry names the cell
    assert [m["name"] for m in BENCH["per_layer"]
            if CELL in m.get("workloads", ())] == list(READERS)
    per_layer = {m["name"] for m in spec.metrics_of(BENCH, "per_layer", CELL)}
    # the nineteen general metrics list no cells and read this one too
    general = {m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    assert len(general) == 19 and general | set(READERS) == per_layer
    assert {m["name"] for m in spec.metrics_of(BENCH, "end_to_end", CELL)} == {
        "updates_per_s_chip", "setup_s",
    }
    assert lint.problems(spec.ROOT) == []
    four = [w["name"] for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(BENCH["workloads"]) >= 17 and CELL not in four
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


def test_the_configuration_is_the_papers_model_on_cell_10_s_record():
    sizes = CFG["source_sizes"]
    ten = spec.load_json(os.path.join(
        spec.ROOT, "chipbench/configs/dlrm-criteo-10m.json"))
    assert CFG["field_cardinalities"] == sizes["field_cardinalities"] == ten[
        "field_cardinalities"]
    assert sizes["rows"].startswith("the record of dlrm-criteo-10m.json")
    assert CFG["num_rows"] == sum(CFG["field_cardinalities"]) == 49_126_297
    assert CFG["fields"] == 26 == sizes["crosses"]
    assert CFG["dim"] == 32 == sizes["embedding_dim"]
    assert CFG["hidden"] == [1024, 512, 256] == sizes["hidden_units"]
    assert CFG["cross_buckets"] == 2**20 == sizes["hash_bucket_size"]
    assert CFG["wide_rows"] == 26 * 2**20 == 27_262_976
    assert CFG["learning_rate"] == 0.05 and CFG["acc0"] == 0.1
    assert [CFG[k] for k in ("alpha", "beta", "l1", "l2")] == [0.1, 1, 1, 1]
    # the byte arithmetic of reduced_why
    deep = CFG["num_rows"] * 64 * 4
    wide = CFG["wide_rows"] * 3 * 4
    net = 2 * 4 * FAM.dense_params(CFG)
    assert FAM.dense_params(CFG) == 1_522_689 + 1
    assert (deep, wide, net) == (12_576_332_032, 327_155_712, 12_181_520)
    for number in ("12,576,332,032", "327,155,712", "12,181,520",
                   "12,915,669,264", "1,522,689"):
        assert number in CFG["reduced_why"], number
    assert deep + wide + net == 12_915_669_264
    assert 0.80 < (deep + wide + net) / 16e9 < 0.81
    # every item the issue put under `assumed`, and the guarantees
    for key in ("crosses", "hash", "wide_rule", "deep_rule", "accumulators",
                "warm_start", "batch", "labels_and_dense_values",
                "mlp_in_the_worker", "matmul_precision"):
        assert CFG["assumed"][key], key
    assert any("applied once to its row in its store before the next pull"
               in g for g in CFG["guarantees"])
    # a warm start on both sides of FTRL's threshold, accumulators past 0.1
    assert CFG["warm_start"]["z_max"] > CFG["l1"]
    assert CFG["warm_start"]["acc_span"] > 0
    # the model's own books agree with the family's
    from flink_parameter_server_tpu.models import wide_deep as wd

    model = wd.WideDeepConfig(tuple(CFG["field_cardinalities"]))
    assert (model.dim, model.hidden, model.cross_buckets) == (
        32, (1024, 512, 256), 2**20)
    assert model.dense_params == 1_522_689
    assert model.macs_per_example == 1_520_896
    assert FAM.dense_flops_per_step(CFG) == 6.0 * 1_520_896 * 32_768
    assert model.wide_rows == CFG["wide_rows"]
    assert model.num_rows == CFG["num_rows"]
    assert {k: v for k, v in REF.leaf_shapes(CFG).items() if k != "bias"} == {
        f"{k}_{leaf}": ((n, m) if leaf == "w" else (m,))
        for k, (n, m) in model.layers().items() for leaf in "wb"}


def test_the_step_s_least_bytes_and_distinct_rows_in_closed_form():
    deep, wide = FAM.deep_distinct_rows(CFG), FAM.wide_distinct_rows(CFG)
    assert deep == pytest.approx(352_305, abs=1)
    assert wide == pytest.approx(685_479, abs=1)
    assert FAM.distinct_rows_per_step(CFG) == deep + wide
    assert FAM.keys_per_step(CFG) == 851_968
    assert FAM.hbm_bytes_per_step(CFG) == pytest.approx(4 * (
        2 * 851_968 * 33 + 4 * 32 * deep + 6 * wide))
    # 422 MB: 0.515 ms at the v5e's 819 GB/s
    assert FAM.hbm_bytes_per_step(CFG) / 819e9 * 1e3 == pytest.approx(
        0.515, abs=1e-3)
    # against a count: one dry-run batch's distinct rows, both stores'
    cfg = DRY["cfg"]
    counted = {"deep": [], "wide": []}
    for seed in range(8):
        (b,) = FAM.host_batches(cfg, {"keys": {"kind": "uniform"}}, seed, 1)
        counted["deep"].append(len(np.unique(b["ids"])))
        counted["wide"].append(len(np.unique(REF.cross_keys(
            REF.cross_codes(b["ids"]), cfg["cross_buckets"]))))
    assert np.mean(counted["deep"]) == pytest.approx(
        FAM.deep_distinct_rows(cfg), rel=0.01)
    assert np.mean(counted["wide"]) == pytest.approx(
        FAM.wide_distinct_rows(cfg), rel=0.01)


def test_the_reference_s_cross_key_is_the_program_s_on_a_million_pairs():
    import jax.numpy as jnp

    from flink_parameter_server_tpu.ops.hashing import pair_key

    rng = np.random.default_rng(71)
    left, right = rng.integers(0, CFG["num_rows"], (2, 1_000_000))
    left[:1000] = right[:1000]  # a value crossed with itself
    for buckets in (2**20, 4096, 1_000_003):
        ids = np.stack([left, right], axis=1).astype(np.int32)
        codes = REF.cross_codes(ids)  # cross 0: (left, right); 1: (right, left)
        keys = REF.cross_keys(codes, buckets)
        want = np.asarray(pair_key(
            jnp.asarray(left.astype(np.int32)),
            jnp.asarray(right.astype(np.int32)), buckets)).astype(np.int64)
        assert np.array_equal(keys[:, 0], want)
        assert np.array_equal(keys[:, 1], buckets + want)  # symmetric
        assert 0 <= want.min() and want.max() < buckets
    # a code holds what names the row: the cross and both ids, to the last
    big = np.array([[CFG["num_rows"] - 1, 0, 2**26 - 1]], np.int32)
    codes = REF.cross_codes(big)
    assert (codes >> 52).tolist() == [[0, 1, 2]]
    assert ((codes >> 26) & (2**26 - 1)).tolist() == big.tolist()
    assert (codes & (2**26 - 1)).tolist() == np.roll(big, -1, 1).tolist()


def _records(cfg, seed, n):
    """``n`` batches of the cell's stream with what the stream itself never
    holds: the same example twice (its crosses twice in a batch) and two
    masked examples."""
    batches = FAM.host_batches(cfg, {"keys": {"kind": "uniform"}}, seed, n)
    for key in ("ids", "dense", "label"):
        batches[0][key][1] = batches[0][key][0]
    batches[-1]["mask"] = batches[-1]["mask"].copy()
    batches[-1]["mask"][[3, 5]] = False
    return batches


def _checked(cfg, seed, *, batches=None, through_driver=True, n=3):
    """The harness's own comparison at ``cfg``'s sizes: the system's rows
    after the batches against the plain reference's."""
    logic, store = FAM.build(cfg, seed, None)
    batches = batches or _records(cfg, seed, n)
    ids = REF.touched(batches)
    state = logic.init_state(jax.random.PRNGKey(0))
    before = FAM.rows(store, state, ids)
    if through_driver:
        driver = StreamingDriver(
            logic, store, config=DriverConfig(**cfg["driver"]))
        done = driver.run(iter(batches))
        got = FAM.rows(done.store, done.worker_state, ids)
    else:
        from flink_parameter_server_tpu.core.transform import make_train_step

        step = jax.jit(make_train_step(logic, store.spec))
        table = store.table
        for b in batches:
            table, state, _ = step(table, state, b)
        got = FAM.rows(type(store)(store.spec, table), state, ids)
    return run._check_rows(
        cfg["reference"], REF.apply(cfg, before, ids, batches), got, before)


@pytest.mark.parametrize("rates", ["dry_run", "source"])
@pytest.mark.parametrize("seed", [77, 2**31 + 12])
def test_through_the_driver_the_system_is_within_the_reference_s_allowances(
        seed, rates):
    """Both stores and the MLP after three batches through
    ``StreamingDriver``, a batch with duplicate crosses and one with masked
    examples among them, at the dry run's small accumulators and at the
    deployment's own (TensorFlow's 0.1)."""
    cfg = DRY["cfg"] if rates == "dry_run" else AT_SOURCE_RATES
    failures, worst = _checked(cfg, seed % (2**31 - 1))
    assert failures == [] and 0 < worst["share"] <= 1.0, worst


def _broken(monkeypatch, what):
    """The program with ONE thing wrong, as a faulty system would have it."""
    import jax.numpy as jnp

    from flink_parameter_server_tpu.models import dlrm, dlrm_dcnv2
    from flink_parameter_server_tpu.models import wide_deep as wd

    if what == "one bfloat16 pass in the MLP":
        def coarse(a, b):
            return jnp.dot(
                a.astype(jnp.bfloat16).astype(jnp.float32),
                b.astype(jnp.bfloat16).astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)

        monkeypatch.setattr(dlrm, "_dot", coarse)
    elif what == "a dropped cross":
        keys = wd.WideAndDeep.keys

        def dropped(self, batch):
            out = keys(self, batch)
            # cross 5 reads and writes cross 4's buckets
            wide = out["wide"].at[:, 5].set(out["wide"][:, 4])
            return {**out, "wide": wide}

        monkeypatch.setattr(wd.WideAndDeep, "keys", dropped)
    elif what == "a missing accumulator update":
        def stale(self, current, combined):
            current = jnp.asarray(current)
            p = current.shape[-1] // 2
            w, acc = current[..., :p], current[..., p:]
            g = jnp.asarray(combined)[..., :p]
            new = acc + g * g
            return jnp.concatenate(
                [w - self.lr * g / (jnp.sqrt(new) + self.eps), acc], axis=-1)

        monkeypatch.setattr(dlrm_dcnv2.Adagrad, "__call__", stale)
    elif what == "a rule applied per occurrence":
        def each(spec, table, ids, deltas, mask=None, **_):
            # no combine: every lane its own rule step, in lane order
            ids = jnp.where(
                mask.reshape(-1) if mask is not None else True,
                ids.reshape(-1), -1)
            rows = deltas.reshape(ids.shape[0], -1)
            for i in range(ids.shape[0]):
                table, _ = real(
                    spec, table, ids[i:i + 1], rows[i:i + 1])
            return table, {}

        from flink_parameter_server_tpu.core import store as store_mod

        real = store_mod.push_counted

        def per_lane(spec, table, ids, deltas, mask=None, **kw):
            if spec.worker_width is not None:  # the deep store's alone
                return each(spec, table, ids, deltas, mask)
            return real(spec, table, ids, deltas, mask, **kw)

        monkeypatch.setattr(store_mod, "push_counted", per_lane)
    else:
        raise AssertionError(what)


@pytest.mark.parametrize("what", [
    "one bfloat16 pass in the MLP", "a dropped cross",
    "a missing accumulator update", "a rule applied per occurrence"])
def test_a_faulty_system_fails_the_check(monkeypatch, what):
    cfg = AT_SOURCE_RATES
    if what == "a rule applied per occurrence":
        # (lane by lane, so a small batch; its first example twice and the
        # small fields' rows many times.  The DEEP store's: AdaGrad's G
        # takes g1^2 + g2^2 for (g1 + g2)^2 and the second step reads the
        # first's accumulator.  FTRL's per-example steps TELESCOPE to the
        # batch form, which is why cell 6's rule may run once a row at all:
        # the wide store would read the same either way)
        cfg = {**DRY["cfg"], "batch": 6}
        # (sizes no other test holds the unbroken system to)
        assert _checked(cfg, 77, n=2, through_driver=False)[0] == []
    elif what == "a missing accumulator update":
        cfg = DRY["cfg"]  # where every accumulator's g^2 registers
    _broken(monkeypatch, what)
    failures, worst = _checked(cfg, 77, n=2, through_driver=False)
    assert len(failures) == 1 and worst["share"] > 3, (what, worst)


def test_the_labels_of_a_two_store_step_s_ops():
    label = store_trace.label_of
    assert label("jit(step)/ps.pull/store.wide/sort") == "pull.wide"
    assert label(
        "jit(step)/ps.push/store.deep/ps.combine/while/body/add"
    ) == "push.deep"
    assert label("jit(step)/jit(main)/ps.push/store.deep/ps.rule/x"
                 ) == "push.deep"
    assert label("jit(step)/ps.pull/jit(_narrow_pull)/store.wide/gather"
                 ) == "pull.wide"
    # a step over one store, the compute, the hash: no store's
    for name in ("jit(step)/ps.pull/gather", "jit(step)/ps.push/ps.combine/x",
                 "jit(step)/ps.compute/ps.dense_top/dot_general",
                 "jit(step)/ps.cross_hash/mul", "store.wide/x",
                 "jit(step)/ps.compute/store.wide/x"):
        assert label(name) is None, name
    # ... and the program's own rule for a scope is as it was
    assert program_trace._innermost_scope(
        "jit(step)/ps.push/store.deep/ps.combine/add") == "ps.combine"
    assert program_trace._innermost_scope("jit(step)/ps.pull/store.wide/g"
                                          ) == "ps.pull"


def test_the_compiled_step_carries_both_labels_under_both_phases():
    """The lowered two-store step's op names, read by both reductions."""
    cfg = DRY["cfg"]
    logic, store = FAM.build(cfg, 5, None)
    (batch,) = FAM.host_batches(cfg, {"keys": {"kind": "uniform"}}, 5, 1)
    from flink_parameter_server_tpu.core.transform import make_train_step

    text = jax.jit(make_train_step(logic, store.spec)).lower(
        store.table, logic.init_state(jax.random.PRNGKey(0)), batch,
    ).as_text(debug_info=True)
    import re

    names = set(re.findall(r'"(jit\(step\)/[^"]*)"', text))
    by_label, by_scope = {}, {}
    for name in names:
        by_label.setdefault(store_trace.label_of(name), set()).add(name)
        by_scope.setdefault(
            program_trace._innermost_scope(name), set()).add(name)
    assert {"pull.wide", "pull.deep", "push.wide", "push.deep"} <= set(by_label)
    assert {"ps.pull", "ps.push", "ps.combine", "ps.rule", "ps.cross_hash",
            "ps.dense_top", "ps.dense_adagrad", "ps.delta_build"} <= set(by_scope)
    # a rule's combine and rule stand under the store's push
    for store_name in ("wide", "deep"):
        assert any("ps.combine" in n for n in by_label[f"push.{store_name}"])
        assert any("ps.rule" in n for n in by_label[f"push.{store_name}"])
    # the hash and the net belong to no store
    assert not [n for n in by_scope["ps.cross_hash"] if store_trace.label_of(n)]
    assert not [n for n in by_scope["ps.dense_top"] if store_trace.label_of(n)]


def test_the_eight_readers_on_a_synthetic_run(monkeypatch):
    from chipbench import peaks

    w_pull, w_push, d_pull, d_push, hash_ms, dense, mxu, roof = (
        spec.metric_reader(n) for n in READERS)
    readers = (w_pull, w_push, d_pull, d_push, hash_ms, dense, mxu, roof)
    # the parent (no such label or scope), and a run without a trace: nothing
    for reader in readers:
        assert reader.__doc__ and reader.read(_ctx()) is None
    where = os.path.join(run.OUT_DIR, "trace", CELL)
    monkeypatch.setitem(program_trace._RUNS, where, {"scope_ms": {
        "ps.pull": 20.5, "ps.compute": 0.02, "ps.cross_hash": 0.07,
        "ps.dense_top": 10.0, "ps.dense_adagrad": 0.2, "ps.delta_build": 0.3,
        "ps.combine": 15.0, "ps.rule": 4.0, "ps.push": 17.7,
    }})
    monkeypatch.setitem(store_trace._RUNS, where, {
        "pull.wide": 10.0, "push.wide": 16.0, "pull.deep": 10.5,
        "push.deep": 21.5})
    traced = _ctx(
        trace={"step_device_ms": 69.0}, peaks=peaks.peaks_for("TPU v5 lite"))
    traced["counters"]["hbm_bytes_per_step"] = FAM.hbm_bytes_per_step(CFG)
    assert [r.read(traced) for r in (w_pull, w_push, d_pull, d_push)] == [
        10.0, 16.0, 10.5, 21.5]
    assert hash_ms.read(traced) == pytest.approx(0.07)
    assert dense.read(traced) == pytest.approx(10.0)
    # 299 GFLOP in 10 ms over 197 TFLOP/s
    assert mxu.read(traced) == pytest.approx(
        100 * 299.02e9 / 10e-3 / 197e12, rel=1e-3)
    assert 0 < mxu.read(traced) < 17
    least_ms = FAM.hbm_bytes_per_step(CFG) / 819e9 * 1e3
    assert roof.read(traced) == pytest.approx(100 * least_ms / 58.0)
    assert 0 < roof.read(traced) < 100
    # the whole step's roofline reads the same bytes over the whole step
    whole = spec.metric_reader("store.gather_scatter_roofline")
    assert whole.read(traced) == pytest.approx(100 * least_ms / 69.0)
    assert whole.read(traced) < roof.read(traced)
    # without the chip's peaks (a dry run) the shares are left out
    bare = _ctx(trace={"step_device_ms": 69.0})
    bare["counters"]["hbm_bytes_per_step"] = 1.0
    for reader in (mxu, roof):
        assert reader.read(bare) is None
    # a step over one store: the scopes without the labels
    monkeypatch.setitem(store_trace._RUNS, where, {})
    for reader in (w_pull, w_push, d_pull, d_push, roof):
        assert reader.read(traced) is None
    assert spec.metric_reader("store.pull_device_ms").read(traced) == 20.5


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
    # the program's spans and counters reach the line; the device's need a chip
    assert {"driver.dispatch_ms", "setup.store_place_s", "setup.compiles"
            } <= set(last["metric_names"])
    assert not set(READERS) & set(last["metric_names"])
