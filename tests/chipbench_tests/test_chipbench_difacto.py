"""``difacto-criteo-10m``: the record uncut, the bytes a step must move, the
warm start, the plain reference's allowances (bfloat16 gradients and bfloat16
accumulators fail them; a row on the gate's threshold is held, not skipped),
the five readers, and the cell's entries and dry run."""
import dataclasses
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

CELL = "difacto-criteo-10m.train-fields-uniform"
BENCH = spec.load_benchmark()
FULL = spec.resolve(BENCH, CELL, dry_run=False)
DRY = spec.resolve(BENCH, CELL, dry_run=True)
FAM = spec.family("difacto")
# the dry-run sizes under the configuration's OWN warm start (both sides of
# the threshold and of the gate; the dry_run block's own opens most gates, for
# test_chipbench_references.py): a field of 509 rows after 5,000 examples
BOTH = {
    **DRY["cfg"],
    "warm_start": {**FULL["cfg"]["warm_start"], "examples": 5000.0},
}
READERS = (
    "store.rule_path_device_ms", "store.rule_path_roofline",
    "store.rule_distinct_share", "step.v_live_share", "step.grad_rows_device_ms",
)


def test_the_configuration_is_the_dlrm_record_uncut_at_36_lanes():
    cfg = FULL["cfg"]
    fm = spec.load_json(os.path.join(spec.BENCH_DIR, "configs", "fm-criteo.json"))
    assert cfg["reduced"] == [] and cfg["family"] == "difacto" and cfg["mesh"] is None
    assert cfg["field_cardinalities"] == fm["field_cardinalities"]
    assert cfg["field_cardinalities"] == cfg["source_sizes"]["field_cardinalities"]
    assert cfg["num_features"] == 13 + sum(cfg["field_cardinalities"]) == 49_126_310
    assert (cfg["fields"], cfg["dense_fields"], cfg["batch"], cfg["pool_batches"]) == (
        39, 13, 32_768, 32)
    assert cfg["dim"] == 16 and 4 + 2 * cfg["dim"] == 36
    assert cfg["num_features"] * 36 * 4 == 7_074_188_640  # 44.2 % of 16 GB
    assert [cfg[k] for k in FAM.RULE_KEYS] == [0.01, 1, 1, 0, 0.01, 1, 0.01, 10]
    assert cfg["V_init_scale"] == 0.01
    assert cfg["driver"] == {"steps_per_call": 1, "dump_model": False}
    assert len(cfg["source"]) <= 200 and FULL["traffic"] == "train-fields-uniform"
    assert len(cfg["guarantees"]) == 4 and "store" in cfg["assumed"]
    assert cfg["reference"]["delta_atol"] == 4e-9 and cfg["reference"]["row_ulps"] == 8


def test_the_rows_and_the_bytes_a_step_must_move():
    cfg = FULL["cfg"]
    expected = FAM.distinct_rows_per_step(cfg)
    assert expected == pytest.approx(352_318, abs=1)
    (batch,) = FAM.host_batches(cfg, FULL["traffic_spec"], 2**31 + 5, 1)
    assert batch["ids"].shape == (32_768, 39) and batch["ids"].max() < cfg["num_features"]
    assert len(np.unique(batch["ids"])) == pytest.approx(expected, rel=2e-3)
    keys = 1_277_952
    assert 100 * expected / keys == pytest.approx(27.57, abs=0.01)
    # the server side: 17 lanes of gradient a key, each distinct row read and
    # written once at 36 lanes; the pull: 18 lanes a key (w, c, V)
    rule_path = 4 * (17 * keys + 2 * 36 * expected)
    assert FAM.rule_path_bytes_per_step(cfg) == pytest.approx(rule_path)
    assert FAM.hbm_bytes_per_step(cfg) == pytest.approx(4 * 18 * keys + rule_path)
    assert FAM.hbm_bytes_per_step(cfg) == pytest.approx(280.4e6, rel=1e-3)
    assert FAM.rule_path_bytes_per_step(cfg) == pytest.approx(188.4e6, rel=1e-3)
    assert FAM.field_firsts(cfg)[0] == 13
    assert FAM.field_firsts(cfg)[-1] + 36 == cfg["num_features"]


def test_build_starts_warm_in_place_on_both_sides_of_threshold_and_gate():
    from flink_parameter_server_tpu.models import difacto as df

    cfg = BOTH
    logic, store = FAM.build(cfg, 77, None)
    _, other = FAM.build(cfg, 2**31 + 6, None)
    assert isinstance(logic, df.DiFacto) and store.spec.layout == "dense"
    rule = store.spec.update
    assert isinstance(rule, df.DiFactoUpdater) and logic.V_threshold == 10.0
    assert [getattr(rule, k) for k in FAM.RULE_KEYS] == [cfg[k] for k in FAM.RULE_KEYS]
    values = np.asarray(store.values())
    assert values.shape == (cfg["num_features"], 36)
    w, z, s, c = values[:, :4].T
    assert abs(z.std() - 2.0) < 0.1 and 0 <= s.min() and s.max() < 8
    want = np.asarray(rule.weights(jnp.asarray(z), jnp.asarray(s)))
    assert np.allclose(w, want, rtol=1e-6, atol=0) and ((w == 0) == (want == 0)).all()
    assert 0.3 < (w == 0).mean() < 0.45  # |z| <= l1 with z ~ N(0, 2): 38 %
    assert abs(values[:, 4:20].std() - 0.01) < 5e-4
    assert 0 <= values[:, 20:].min() and values[:, 20:].max() < 8
    assert (c == np.floor(c)).all() and (c[:13] > 100).all()
    # a field of 509 rows after 5,000 examples: mean count ~9.8, both sides
    big = slice(13, 13 + 509)
    assert 0.2 < (c[big] > 10).mean() < 0.5
    small = FAM.field_firsts(cfg)[5]
    assert (c[small:small + 3] > 10).all()
    assert not np.array_equal(values, np.asarray(other.values()))
    # the warm start's live shares at seed 77, rows and a batch's keys
    live = (c > 10) & (w != 0)
    assert live.mean() == pytest.approx(0.20995, abs=2e-4)
    (batch,) = FAM.host_batches(cfg, DRY["traffic_spec"], 77, 1)
    # the dry run's own start opens most gates (its "why")
    dry_values = np.asarray(FAM.build(DRY["cfg"], 77, None)[1].values())
    assert ((dry_values[:, 3] > 10) & (dry_values[:, 0] != 0)).mean() > 0.85
    assert live[batch["ids"]].mean() == pytest.approx(0.47406, abs=2e-4)


def _checked(cfg, seed, logic=None, update=None, batches=None):
    """The check ``chipbench/run.py`` makes, in process at the dry-run sizes:
    the configuration's checked batches through the jitted step, then
    ``_check_rows`` against the plain reference."""
    ref = spec.reference(cfg)
    own, store = FAM.build(cfg, seed, None)
    if update is not None:
        store = type(store)(
            dataclasses.replace(store.spec, update=update), store.table
        )
    batches = batches or FAM.host_batches(
        cfg, DRY["traffic_spec"], seed, cfg["reference"]["batches"]
    )
    ids = ref.touched(batches)
    before = FAM.rows(store, (), ids)
    step = jax.jit(make_train_step(logic or own, store.spec))
    table = store.table
    for b in batches:
        table, _, _ = step(table, (), b)
    got = FAM.rows(type(store)(store.spec, table), (), ids)
    return run._check_rows(
        cfg["reference"], ref.apply(cfg, before, ids, batches), got, before
    )


@pytest.mark.parametrize("start", ["the configuration's", "the dry run's"])
@pytest.mark.parametrize("seed", [3, 77, 2**31 + 12, 900_000_011])
def test_the_system_is_within_the_reference_s_allowances(seed, start):
    cfg = BOTH if start == "the configuration's" else DRY["cfg"]
    failures, worst = _checked(cfg, seed)
    assert failures == [] and worst["share"] < 0.5, worst


def test_bfloat16_gradients_fail_the_check():
    from flink_parameter_server_tpu.models import difacto as df

    class Rounded(df.DiFacto):
        def step(self, state, batch, pulled):
            state, req, out = super().step(state, batch, pulled)
            req.deltas = req.deltas.astype(jnp.bfloat16).astype(jnp.float32)
            return state, req, out

    cfg = BOTH
    logic = Rounded(df.DiFactoConfig(cfg["num_features"], cfg["dim"]))
    failures, worst = _checked(cfg, 3, logic=logic)
    assert len(failures) == 1 and worst["share"] > 20, worst


def test_bfloat16_accumulators_fail_the_check():
    from flink_parameter_server_tpu.models import difacto as df

    rule = df.DiFactoUpdater()

    def coarse(current, combined):
        new = rule(current, combined)
        roots = new.at[..., 2].set(
            new[..., 2].astype(jnp.bfloat16).astype(jnp.float32)
        )
        return roots.at[..., 20:].set(
            new[..., 20:].astype(jnp.bfloat16).astype(jnp.float32)
        )

    failures, worst = _checked(BOTH, 3, update=coarse)
    assert len(failures) == 1 and worst["share"] > 20, worst


def _one_key_batch(row, label=-1.0):
    return {
        "ids": np.array([[row]], np.int32), "values": np.ones((1, 1), np.float32),
        "feat_mask": np.ones((1, 1), bool), "label": np.array([label], np.float32),
        "mask": np.ones(1, bool),
    }


def test_the_reference_is_the_equations_on_one_row():
    cfg = {**DRY["cfg"], "num_features": 4}
    ref = spec.reference(cfg)
    ids = {"feature": np.arange(4, dtype=np.int32)}
    row = np.zeros(36, np.float32)
    row[:4] = [0.02, 5.0, 1.0, 50.0]  # w = (5 - 1) / ((1 + 1) / 0.01), live
    row[4:20], row[20:] = 0.01, 2.0
    rows = np.tile(row, (4, 1))
    (want,), (moved,) = (
        list(t.values())
        for t in ref.apply(cfg, {"feature": rows}, ids, [_one_key_batch(1)])
    )
    # one live feature: t = x V, so the interaction and gV are 0; y^ = w
    g = 1 / (1 + np.exp(-0.02))  # p - y, y = 0
    s_new = np.sqrt(1 + g * g)
    z_new = 5.0 - g + (s_new - 1) / 0.01 * 0.02
    assert want[1, 2] == pytest.approx(s_new, rel=1e-6)
    assert want[1, 1] == pytest.approx(z_new, rel=1e-6)
    assert want[1, 0] == pytest.approx((z_new - 1) / ((1 + s_new) / 0.01), rel=1e-6)
    assert want[1, 3] == 50.0
    gv = 0.01 * 0.01  # V_l2 V alone
    acc = np.sqrt(4 + gv * gv)
    assert np.allclose(want[1, 20:], acc, rtol=1e-6)
    assert np.allclose(want[1, 4:20], 0.01 - 0.01 / (acc + 1) * gv, rtol=1e-6)
    assert (moved[[0, 2, 3]] == 0).all() and np.array_equal(want[0], rows[0])
    assert (moved[1, [0, 1, 2]] > 0).all() and moved[1, 3] == 0
    # gated off by its count: V and S stay to the bit, and nothing is allowed
    rows[:, 3] = 10.0
    (want,), (moved,) = (
        list(t.values())
        for t in ref.apply(cfg, {"feature": rows}, ids, [_one_key_batch(1)])
    )
    assert np.array_equal(want[1, 4:], rows[1, 4:]) and not moved[1, 3:].any()
    assert want[1, 1] == pytest.approx(z_new, rel=1e-6)


def test_a_row_on_the_gates_threshold_is_held_not_skipped():
    """A ``z'`` within its allowance of ``l1`` leaves a ``w`` that is zero on
    one side and not on the other: the ``w`` lane is continuous there and is
    held like any other; the next batch may read the row's gate either way,
    so its ``V`` lanes are allowed the most a step moves them."""
    cfg = {**DRY["cfg"], "num_features": 4}
    ref = spec.reference(cfg)
    ids = {"feature": np.arange(4, dtype=np.int32)}
    row = np.zeros(36, np.float32)
    row[4:20], row[20:], row[3] = 0.01, 2.0, 50.0
    g = np.float32(0.5)  # w = 0 read, margin 0, y = 0
    # z' = z - g lands on l1 to a rounding: w' is 0 or a few 1e-10
    row[1], row[2] = np.float32(1.0) + g, 3.0
    rows = np.tile(row, (4, 1))
    one = _one_key_batch(1)
    (want,), (moved,) = (
        list(t.values()) for t in ref.apply(cfg, {"feature": rows}, ids, [one])
    )
    assert want[1, 1] == pytest.approx(1.0, abs=1e-6) and abs(want[1, 0]) < 1e-8
    check = cfg["reference"]
    allow_w = check["delta_rtol"] * moved[1, 0]
    allow_z = check["delta_rtol"] * moved[1, 1] + 8 * 2**-23
    scale = (1 + np.sqrt(9.25)) / 0.01
    assert allow_w >= 0.99 * allow_z / scale  # z's whole allowance, carried
    # on the threshold itself the weight is 0 from either side
    zs = np.array([1.0, np.nextafter(np.float32(1), 2), -1.0], np.float32)
    w, _ = ref.weights(cfg, zs, np.full(3, 3, np.float32))
    assert w[0] == 0 and w[2] == 0 and 0 < w[1] < 1e-8
    # a second batch names the row again: its gate may be read either way
    (want2,), (moved2,) = (
        list(t.values())
        for t in ref.apply(cfg, {"feature": rows}, ids, [one, one])
    )
    assert (check["delta_rtol"] * moved2[1, 4:20] >= 0.01).all()
    # ... while a row well clear of the threshold is held to its sums alone
    rows[:, 1] = 5.0
    rows[:, 0] = ref.weights(cfg, rows[:, 1], rows[:, 2])[0]
    (_,), (clear,) = (
        list(t.values())
        for t in ref.apply(cfg, {"feature": rows}, ids, [one, one])
    )
    assert (check["delta_rtol"] * clear[1, 4:20] < 1e-6).all()


def test_a_later_batch_inherits_what_the_check_allows_the_rows_it_reads():
    cfg = DRY["cfg"]
    ref = spec.reference(cfg)
    _, store = FAM.build(cfg, 9, None)
    one, two = FAM.host_batches(cfg, DRY["traffic_spec"], 9, 2)
    ids = ref.touched([one, two])
    before = FAM.rows(store, (), ids)
    _, moved_first = ref.apply(cfg, before, ids, [one])
    _, moved_both = ref.apply(cfg, before, ids, [one, two])
    after_one = ref.apply(cfg, before, ids, [one])[0]
    _, moved_second = ref.apply(cfg, after_one, ids, [two])
    hot = np.searchsorted(ids["feature"], np.arange(cfg["dense_fields"]))
    z = 1
    # the two batches together allow the hot rows more than each alone: the
    # difference is what the second's gradients inherit from the first's
    extra = moved_both["feature"][hot, z] - moved_first["feature"][hot, z]
    assert (extra > moved_second["feature"][hot, z]).all()
    # a row only the first batch touched is held to delta_rtol of its sums
    only_first = np.setdiff1d(one["ids"], two["ids"])
    at = np.searchsorted(ids["feature"], only_first)
    assert np.array_equal(moved_both["feature"][at], moved_first["feature"][at])


def _ctx(**over):
    return {
        "cfg": FULL["cfg"], "traffic": FULL["traffic_spec"], "chips": 1,
        "trace": None, "peaks": None, "spans": [],
        "counters": {"peak_hbm_bytes": 0}, **over,
    }


def test_the_rule_path_readers_sum_three_scopes_and_hold_them_to_the_roofline(
        monkeypatch):
    from chipbench import peaks

    ms = spec.metric_reader("store.rule_path_device_ms")
    share = spec.metric_reader("store.rule_path_roofline")
    assert ms.__doc__ and share.__doc__
    assert ms.read(_ctx()) is None and share.read(_ctx()) is None
    where = os.path.join(run.OUT_DIR, "trace", CELL)
    reduced = {"scope_ms": {
        "ps.pull": 70.0, "ps.combine": 20.0, "ps.rule": 25.0, "ps.push": 40.0,
        "ps.delta_build": 3.5,
    }}
    monkeypatch.setitem(program_trace._RUNS, where, reduced)
    traced = _ctx(
        trace={"step_device_ms": 170.0}, peaks=peaks.peaks_for("TPU v5 lite")
    )
    assert ms.read(traced) == pytest.approx(85.0)
    least_ms = FAM.rule_path_bytes_per_step(FULL["cfg"]) / 819e9 * 1e3
    assert least_ms == pytest.approx(0.230, abs=1e-3)
    assert share.read(traced) == pytest.approx(100 * least_ms / 85.0)
    assert 0 < share.read(traced) < 100
    assert spec.metric_reader("step.grad_rows_device_ms").read(traced) == 3.5
    # an add store has ps.push and no ps.combine: no rule path to read
    monkeypatch.setitem(
        program_trace._RUNS, where, {"scope_ms": {"ps.pull": 5.0, "ps.push": 9.0}}
    )
    assert ms.read(traced) is None and share.read(traced) is None
    assert spec.metric_reader("step.grad_rows_device_ms").read(traced) is None
    # the whole step's roofline reads the family's 280 MB
    whole = spec.metric_reader("store.gather_scatter_roofline")
    traced["counters"]["hbm_bytes_per_step"] = FAM.hbm_bytes_per_step(FULL["cfg"])
    assert whole.read(traced) == pytest.approx(100 * 0.3424 / 170.0, rel=1e-3)


@pytest.mark.parametrize("name, over, under, reads", [
    ("store.rule_distinct_share", "store_rule_rows", "store_rule_keys", 27.57),
    ("step.v_live_share", "fm_v_live_keys", "fm_live_keys", 27.57),
])
def test_the_share_readers_read_the_program_s_gauges(
        name, over, under, reads, monkeypatch):
    from flink_parameter_server_tpu.telemetry import registry as registry_mod

    reader = spec.metric_reader(name)
    fresh = registry_mod.MetricsRegistry()
    monkeypatch.setattr(registry_mod, "get_registry", lambda: fresh)
    assert reader.__doc__ and reader.read(_ctx()) is None  # the parent
    fresh.gauge(under, component="train").set(1_277_952)
    assert reader.read(_ctx()) is None
    fresh.gauge(over, component="train").set(352_318)
    assert reader.read(_ctx()) == pytest.approx(reads, abs=0.01)


def test_the_scopes_are_in_the_lowered_step_innermost_where_they_should_be():
    logic, store = FAM.build(DRY["cfg"], 1, None)
    (b,) = FAM.host_batches(DRY["cfg"], DRY["traffic_spec"], 1, 1)
    text = jax.jit(make_train_step(logic, store.spec)).lower(
        store.table, (), b
    ).as_text(debug_info=True)
    for scope in ("ps.compute/ps.gate", "ps.compute/ps.delta_build",
                  "ps.push/ps.combine", "ps.rule"):
        assert scope in text, scope
    innermost = {
        "jit(step)/ps.compute/ps.gate/select_n": "ps.gate",
        "jit(step)/ps.compute/ps.delta_build/concatenate": "ps.delta_build",
        "jit(step)/ps.compute/mul": "ps.compute",
        "jit(step)/ps.push/while/body/ps.rule/jit(_take)/gather": "ps.rule",
        "jit(step)/ps.push/ps.combine/sort": "ps.combine",
    }
    for op_name, scope in innermost.items():
        assert program_trace.SCOPE.findall(op_name)[-1] == scope
    # cell 2's logic shares the forward pass and has neither scope
    fm = spec.resolve(BENCH, "fm-criteo.train-fields-uniform", dry_run=True)["cfg"]
    fm_logic, fm_store = spec.family("fm").build(fm, 1, None)
    text = jax.jit(make_train_step(fm_logic, fm_store.spec)).lower(
        fm_store.table, (), b
    ).as_text(debug_info=True)
    assert "ps.gate" not in text and "ps.delta_build" not in text


def test_the_cells_entries_by_name_and_its_dry_run():
    # by name, never by place: later cells are appended after this one
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == "difacto-criteo-10m"
    assert cell["traffic"] == "train-fields-uniform" and len(cell["why"]) <= 200
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == [] and entry["source"] == FULL["cfg"]["source"]
    assert entry["file"] == "chipbench/configs/difacto-criteo-10m.json"
    mine = [m for m in BENCH["per_layer"] if m["name"] in READERS]
    assert [m["name"] for m in mine] == list(READERS)
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "updates_per_s_chip"
        assert spec.metric_reader(m["name"]) is not None
    assert [m["layer"] for m in mine] == 3 * ["store gather/scatter"] + 2 * ["worker step"]
    assert [m["better"] for m in mine] == ["lower", "higher", "lower", "higher", "lower"]
    assert [m["source"] for m in mine] == [
        "device_trace", "device_trace", "program_counter", "program_counter",
        "device_trace",
    ]
    per_layer = {m["name"] for m in spec.metrics_of(BENCH, "per_layer", CELL)}
    assert set(READERS) | {
        "store.pull_device_ms", "store.push_device_ms", "step.compute_device_ms",
        "store.gather_scatter_roofline", "step.unscoped_share", "step.device_ms",
    } <= per_layer
    # cell 6's twins list cell 6 alone
    assert not {"store.rule_rows_share", "step.delta_build_device_ms"} & per_layer
    assert {m["name"] for m in spec.metrics_of(BENCH, "end_to_end", CELL)} == {
        "updates_per_s_chip", "setup_s",
    }
    assert lint.problems(spec.ROOT) == []
    # a quarter of the cells, rounded down, and one always, may take 4 chips
    cells = BENCH["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
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
    assert {
        "driver.dispatch_ms", "store.rule_distinct_share", "step.v_live_share",
    } <= set(last["metric_names"])
