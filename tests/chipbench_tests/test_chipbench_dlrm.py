"""``dlrm-criteo-10m``: the record uncut at the source's widths, the operations
and bytes a step must do, cell 2's draws, the plain reference's allowances
(the system inside them, the same equations with bfloat16 products outside),
the four readers, and the cell's entries and dry run."""
import json
import os
import subprocess
import sys

import jax
import ml_dtypes
import numpy as np
import pytest

from chipbench import lint, program_trace, run, spec
from flink_parameter_server_tpu.core.transform import make_train_step

CELL = "dlrm-criteo-10m.train-fields-uniform"
BENCH = spec.load_benchmark()
FULL = spec.resolve(BENCH, CELL, dry_run=False)
DRY = spec.resolve(BENCH, CELL, dry_run=True)
FAM = spec.family("dlrm")
READERS = (
    "step.dense_device_ms", "step.interact_device_ms", "step.dense_mxu_share",
    "step.dense_share",
)


def test_the_configuration_is_the_sources_at_its_widths_nothing_cut():
    cfg = FULL["cfg"]
    fm = spec.load_json(os.path.join(spec.BENCH_DIR, "configs", "fm-criteo.json"))
    assert cfg["reduced"] == [] and cfg["family"] == "dlrm" and cfg["mesh"] is None
    assert cfg["field_cardinalities"] == fm["field_cardinalities"]
    assert cfg["field_cardinalities"] == cfg["source_sizes"]["field_cardinalities"]
    assert cfg["num_rows"] == sum(cfg["field_cardinalities"]) == 49_126_297
    assert (cfg["fields"], cfg["dense_fields"], cfg["dim"]) == (26, 13, 64)
    assert cfg["num_rows"] * 64 * 4 == 12_576_332_032  # 78.6 % of 16 GB
    assert 0.785 < cfg["num_rows"] * 64 * 4 / 16e9 < 0.787
    assert [13] + cfg["bottom_mlp"] == cfg["source_sizes"]["bottom_mlp"] == [13, 512, 256, 64]
    assert [415] + cfg["top_mlp"] == cfg["source_sizes"]["top_mlp"] == [415, 512, 512, 256, 1]
    assert (cfg["batch"], cfg["pool_batches"], cfg["learning_rate"]) == (32_768, 32, 0.1)
    assert cfg["dtype"] == "float32"
    assert cfg["driver"] == {"steps_per_call": 1, "dump_model": False}
    assert len(cfg["source"]) <= 200 and FULL["traffic"] == "train-fields-uniform"
    for flag in ("--arch-sparse-feature-size=64", "--arch-mlp-bot=13-512-256-64",
                 "--arch-mlp-top=512-512-256-1", "--max-ind-range=10000000"):
        assert flag in cfg["source"]
    assert len(cfg["guarantees"]) == 4 and "store" in cfg["assumed"]
    check = cfg["reference"]
    assert (check["batches"], check["delta_rtol"], check["delta_atol"],
            check["row_ulps"], check["relu_ulps"]) == (2, 4e-4, 1e-12, 4, 16)
    # the dry run keeps every width and caps the cardinalities as cell 9's
    dry = DRY["cfg"]
    assert (dry["dim"], dry["bottom_mlp"], dry["top_mlp"]) == (64, [512, 256, 64], [512, 512, 256, 1])
    assert max(dry["field_cardinalities"]) == 509 and dry["num_rows"] == sum(dry["field_cardinalities"])
    assert [c for c in dry["field_cardinalities"] if c < 509] == [
        c for c in cfg["field_cardinalities"] if c < 509]


def test_the_operations_and_bytes_a_step_must_do():
    from flink_parameter_server_tpu.models import dlrm

    cfg = FULL["cfg"]
    assert FAM.layer_shapes(cfg) == {
        "bot0": (13, 512), "bot1": (512, 256), "bot2": (256, 64),
        "top0": (415, 512), "top1": (512, 512), "top2": (512, 256), "top3": (256, 1),
    }
    macs = 6_656 + 131_072 + 16_384 + 212_480 + 262_144 + 131_072 + 256 + 27 * 27 * 64
    assert macs == 806_720
    assert FAM.dense_flops_per_step(cfg) == 2 * macs * 3 * 32_768 == 158_607_605_760
    assert FAM.hbm_bytes_per_step(cfg) == 3 * 32_768 * 26 * 64 * 4 == 654_311_424
    # the benchmark's own copy and the program's agree
    model = dlrm.DLRMConfig(tuple(cfg["field_cardinalities"]))
    assert model.macs_per_example == macs and model.layers() == FAM.layer_shapes(cfg)
    assert spec.reference(cfg).layer_shapes(cfg) == {
        k: (n + 1, m) for k, (n, m) in FAM.layer_shapes(cfg).items()
    }
    # at HIGHEST a float32 product is six bfloat16 passes: a sixth of the peak
    assert 100 * 158_607_605_760 / 197e12 / (158_607_605_760 * 6 / 197e12) == pytest.approx(16.67, abs=0.01)


def test_the_batches_are_cell_2s_draws_without_its_integer_rows():
    cfg, fm = DRY["cfg"], spec.resolve(BENCH, "fm-criteo.train-fields-uniform", dry_run=True)["cfg"]
    seed = 2**31 + 7
    (mine,) = FAM.host_batches(cfg, DRY["traffic_spec"], seed, 1)
    (theirs,) = spec.family("fm").host_batches(
        {**fm, "field_cardinalities": cfg["field_cardinalities"], "batch": cfg["batch"]},
        DRY["traffic_spec"], seed, 1,
    )
    assert np.array_equal(mine["ids"], theirs["ids"][:, 13:] - 13)
    assert np.array_equal(mine["dense"], theirs["values"][:, :13])
    assert np.array_equal(mine["label"] > 0, theirs["label"] > 0)
    assert set(np.unique(mine["label"])) == {0.0, 1.0} and mine["mask"].all()
    assert mine["ids"].shape == (512, 26) and mine["ids"].dtype == np.int32
    cards = np.asarray(cfg["field_cardinalities"])
    first = np.concatenate([[0], np.cumsum(cards)[:-1]])
    assert (mine["ids"] >= first).all() and (mine["ids"] < first + cards).all()
    assert mine["dense"].shape == (512, 13) and mine["dense"].dtype == np.float32
    (full,) = FAM.host_batches(FULL["cfg"], FULL["traffic_spec"], seed, 1)
    assert full["ids"].shape == (32_768, 26) and full["ids"].max() < 49_126_297


def test_build_packs_two_rows_in_place_and_takes_the_seed_as_data():
    from flink_parameter_server_tpu.models import dlrm

    cfg = DRY["cfg"]
    logic, store = FAM.build(cfg, 77, None)
    _, other = FAM.build(cfg, 2**31 + 6, None)
    assert isinstance(logic, dlrm.DLRM) and logic.config.learning_rate == 0.1
    assert (store.spec.layout, store.spec.pack, store.spec.update) == ("packed", 2, "add")
    assert store.table.shape == (-(-cfg["num_rows"] // 16) * 8, 128)
    values = np.asarray(store.values())
    assert values.shape == (cfg["num_rows"], 64)
    assert np.abs(values[:509]).max() <= np.sqrt(1 / 509)
    assert not np.array_equal(values, np.asarray(other.values()))
    state = logic.init_state(jax.random.PRNGKey(0))
    rows = FAM.rows(store, state, {"embedding": np.arange(20, dtype=np.int32)})
    # one group: the embedding rows, then the MLPs flat, 64 lanes to a row
    assert rows["parameters"].shape == (20 + -(-762_177 // 64), 64) and len(rows) == 1
    ref = spec.reference(cfg)
    table, split = ref.unpack(cfg, rows["parameters"], 20)
    assert np.array_equal(table, values[:20])
    assert np.array_equal(split["top0"][:-1], np.asarray(state["top0_w"]))
    assert np.array_equal(split["bot2"][-1], np.asarray(state["bot2_b"]))
    assert np.array_equal(ref.pack(cfg, table, split), rows["parameters"])
    assert not rows["parameters"].reshape(-1)[20 * 64 + 762_177:].any()


def _checked(cfg, seed, reference=None):
    """The check ``chipbench/run.py`` makes, in process at the dry-run sizes:
    the configuration's checked batches through the jitted step, then
    ``_check_rows`` against ``reference`` (the plain one by default)."""
    ref = reference or spec.reference(cfg)
    logic, store = FAM.build(cfg, seed % (2**31 - 1), None)
    batches = FAM.host_batches(
        cfg, DRY["traffic_spec"], seed, cfg["reference"]["batches"]
    )
    ids = ref.touched(batches)
    state = logic.init_state(jax.random.PRNGKey(0))
    before = FAM.rows(store, state, ids)
    step = jax.jit(make_train_step(logic, store.spec))
    table = store.table
    for b in batches:
        table, state, _ = step(table, state, b)
    got = FAM.rows(type(store)(store.spec, table), state, ids)
    return run._check_rows(
        cfg["reference"], ref.apply(cfg, before, ids, batches), got, before
    )


@pytest.mark.parametrize("seed", [3, 77, 2**31 + 12, 900_000_011, 5, 31337])
def test_the_system_is_within_the_reference_s_allowances(seed):
    cfg = DRY["cfg"]
    failures, worst = _checked(cfg, seed)
    assert failures == [] and worst["share"] < 0.5, worst


@pytest.fixture()
def bfloat16_products(monkeypatch):
    """The plain reference with the operands of every matrix product rounded
    to bfloat16 (what the MXU's default does to float32 operands; the sums
    stay float32): the same equations, coarser products."""
    ref = spec.reference(DRY["cfg"])

    def coarse(a, b):
        def rounded(x):
            return x.astype(ml_dtypes.bfloat16).astype(np.float32)

        return np.matmul(rounded(a), rounded(b))

    monkeypatch.setattr(ref, "_dot", coarse)
    return ref


@pytest.mark.parametrize("seed", [3, 77])
def test_bfloat16_products_fail_the_check(seed, bfloat16_products):
    cfg = DRY["cfg"]
    failures, worst = _checked(cfg, seed, reference=bfloat16_products)
    assert len(failures) == 1 and worst["share"] > 20, worst


def test_most_compared_elements_move_past_five_times_their_allowance():
    cfg = DRY["cfg"]
    ref, check = spec.reference(cfg), cfg["reference"]
    logic, store = FAM.build(cfg, 5, None)
    batches = FAM.host_batches(cfg, DRY["traffic_spec"], 5, check["batches"])
    ids = ref.touched(batches)
    before = FAM.rows(store, logic.init_state(jax.random.PRNGKey(0)), ids)
    (want,), (moved,) = (
        list(t.values()) for t in ref.apply(cfg, before, ids, batches))
    (before,) = before.values()
    ulp = check["row_ulps"] * float(np.finfo(np.float32).eps)
    net = np.abs(want - before)
    allowed = check["delta_rtol"] * moved + check["delta_atol"] + (
        ulp * np.maximum(np.abs(want), np.abs(before)))
    past = net > 5 * allowed
    n = ids["embedding"].size
    assert np.mean(past) > 0.8 and np.median(net / allowed) > 20
    # the embedding rows nearly all; of the MLPs' elements an eighth belong
    # to a ReLU unit no example lights, and coin-flip labels cancel more
    assert np.mean(past[:n]) > 0.95 and (moved[:n] > 0).all()
    mlps = moved[n:].reshape(-1)[:762_177]
    assert 0.05 < np.mean(mlps == 0) < 0.2
    assert 0.7 < np.mean(past[n:].reshape(-1)[:762_177]) < 0.9


def test_an_example_on_a_relus_corner_is_held_to_both_sides_not_skipped():
    """A unit whose pre-activation is zero to a rounding passes its gradient
    on one side and nothing on the other; the reference marks it, and allows
    every element that example moves what turning the unit round moves."""
    cfg = {**DRY["cfg"], "batch": 8}
    ref, check = spec.reference(cfg), cfg["reference"]
    logic, store = FAM.build(cfg, 5, None)
    (batch,) = FAM.host_batches(cfg, DRY["traffic_spec"], 5, 1)
    ids = ref.touched([batch])
    n = ids["embedding"].size
    (before,) = FAM.rows(
        store, logic.init_state(jax.random.PRNGKey(0)), ids).values()
    table, layers = ref.unpack(cfg, before, n)
    layers = {k: v.copy() for k, v in layers.items()}
    # unit 7 of the top MLP's first layer, example 0: its bias puts z two
    # roundings above zero, or two below
    bot, top = ["bot0", "bot1", "bot2"], ["top0", "top1", "top2", "top3"]
    z0 = ref._forward(layers, bot, batch["dense"], True, 0.0)[0][-1]
    t = np.concatenate([
        z0[:, None], table[np.searchsorted(ids["embedding"], batch["ids"])]
    ], axis=1)
    pairs = np.matmul(t, t.transpose(0, 2, 1))[:, *np.tril_indices(27, -1)]
    r = np.concatenate([z0, pairs], axis=1)
    z = r[0] @ layers["top0"][:-1, 7]
    sides = {}
    for side, nudge in (("above", 2e-7), ("below", -2e-7)):
        layers["top0"][-1, 7] = np.float32(-z + nudge)
        rows = {"parameters": ref.pack(cfg, table, layers)}
        _, corner = ref._forward(layers, top, r, False, check["relu_ulps"])
        assert corner["top0"][0, 7] and corner["top0"][0].sum() == 1
        marked = ref.apply(cfg, rows, ids, [batch])
        blind = ref.apply(
            {**cfg, "reference": {**check, "relu_ulps": 0.0}}, rows, ids, [batch]
        )
        sides[side] = tuple(
            list(t.values())[0] for pair in (marked, blind) for t in pair)
    want, moved, blind_want, blind_moved = sides["above"]
    other_want = sides["below"][0]
    assert np.array_equal(want, blind_want)  # the rows are the same
    # example 0's rows are allowed more, the other examples' what they were
    mine = np.searchsorted(ids["embedding"], batch["ids"][0])
    others = np.setdiff1d(
        np.searchsorted(ids["embedding"], batch["ids"][1:].reshape(-1)), mine
    )
    assert (moved[mine] > blind_moved[mine]).any()
    assert np.array_equal(moved[others], blind_moved[others])
    assert (moved[n:] >= blind_moved[n:]).all()
    assert (moved[n:] > blind_moved[n:]).sum() > 1000
    # the other side moves the rows by more than the blind allowance, and by
    # half of the marked one (the unit's own bias apart, which differs by the
    # nudge, and the next layer's weights on the unit, whose gradient is the
    # unit's activation, 2e-7 on one side and 0 on the other)
    where = ref.unpack(cfg, np.arange(want.size).reshape(want.shape), n)[1]
    off = np.abs(other_want - want)
    off.reshape(-1)[where["top0"][-1, 7]] = 0
    off.reshape(-1)[where["top1"][7]] = 0
    ulp = check["row_ulps"] * float(np.finfo(np.float32).eps) * np.abs(want)
    allowed = check["delta_rtol"] * moved + check["delta_atol"] + ulp
    blind = check["delta_rtol"] * blind_moved + check["delta_atol"] + ulp
    for part in (slice(0, n), slice(n, None)):  # the embedding rows, the MLPs
        assert (off[part] > blind[part]).any()
        share = (off / allowed)[part]
        at = np.unravel_index(np.argmax(share), share.shape)
        assert share[at] <= 0.51, (part, at, share[at], off[part][at], moved[part][at], blind_moved[part][at], want[part][at])


def _ctx(**over):
    return {
        "cfg": FULL["cfg"], "traffic": FULL["traffic_spec"], "chips": 1,
        "trace": None, "peaks": None, "spans": [],
        "counters": {"peak_hbm_bytes": 0}, **over,
    }


def test_the_readers_sum_the_dense_scopes_and_hold_them_to_the_peak(monkeypatch):
    from chipbench import peaks

    readers = {name: spec.metric_reader(name) for name in READERS}
    for reader in readers.values():
        assert reader.__doc__ and reader.read(_ctx()) is None
    where = os.path.join(run.OUT_DIR, "trace", CELL)
    monkeypatch.setitem(program_trace._RUNS, where, {"scope_ms": {
        "ps.pull": 12.0, "ps.push": 20.0, "ps.compute": 0.5,
        "ps.dense_bottom": 1.0, "ps.dense_interact": 12.0, "ps.dense_top": 5.0,
        "ps.dense_sgd": 0.1, "ps.delta_build": 2.5,
    }})
    traced = _ctx(
        trace={"step_device_ms": 60.0}, peaks=peaks.peaks_for("TPU v5 lite")
    )
    assert readers["step.dense_device_ms"].read(traced) == pytest.approx(18.0)
    assert readers["step.interact_device_ms"].read(traced) == pytest.approx(12.0)
    assert readers["step.dense_share"].read(traced) == pytest.approx(30.0)
    least_ms = 158_607_605_760 / 197e12 * 1e3
    assert least_ms == pytest.approx(0.8051, abs=1e-4)
    assert readers["step.dense_mxu_share"].read(traced) == pytest.approx(
        100 * least_ms / 18.0)
    # all six passes of HIGHEST at the peak would read a sixth
    monkeypatch.setitem(program_trace._RUNS, where, {"scope_ms": {
        "ps.dense_top": 6 * least_ms}})
    assert readers["step.dense_mxu_share"].read(traced) == pytest.approx(100 / 6)
    # a program without the scopes (the parent, every other logic): nothing
    monkeypatch.setitem(
        program_trace._RUNS, where, {"scope_ms": {"ps.pull": 5.0, "ps.push": 9.0}}
    )
    for reader in readers.values():
        assert reader.read(traced) is None
    # the store's roofline reads the family's 654 MB
    whole = spec.metric_reader("store.gather_scatter_roofline")
    traced["counters"]["hbm_bytes_per_step"] = FAM.hbm_bytes_per_step(FULL["cfg"])
    assert whole.read(traced) == pytest.approx(100 * 0.7989 / 60.0, rel=1e-3)


def test_the_scopes_are_in_the_lowered_step_innermost_where_they_should_be():
    logic, store = FAM.build(DRY["cfg"], 1, None)
    (b,) = FAM.host_batches(DRY["cfg"], DRY["traffic_spec"], 1, 1)
    text = jax.jit(make_train_step(logic, store.spec)).lower(
        store.table, logic.init_state(jax.random.PRNGKey(0)), b
    ).as_text(debug_info=True)
    innermost = {
        "jit(step)/ps.compute/ps.dense_bottom/dot_general": "ps.dense_bottom",
        "jit(step)/ps.compute/ps.dense_interact/bid,bjd->bij/dot_general": "ps.dense_interact",
        "jit(step)/ps.compute/ps.dense_interact/bij,bjd->bid/dot_general": "ps.dense_interact",
        "jit(step)/ps.compute/ps.dense_top/dot_general": "ps.dense_top",
        "jit(step)/ps.compute/ps.dense_sgd/sub": "ps.dense_sgd",
        "jit(step)/ps.compute/ps.delta_build/mul": "ps.delta_build",
    }
    for op_name, scope in innermost.items():
        assert f'"{op_name}"' in text, op_name
        assert program_trace.SCOPE.findall(op_name)[-1] == scope
    assert "ps.pull" in text and "ps.push/scatter-add" in text
    assert "ps.combine" not in text and "ps.rule" not in text  # an add store


def test_the_cells_entries_by_name_and_its_dry_run():
    # by name, never by place: later cells are appended after this one
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == "dlrm-criteo-10m"
    assert cell["traffic"] == "train-fields-uniform" and len(cell["why"]) <= 200
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == [] and entry["source"] == FULL["cfg"]["source"]
    assert entry["file"] == "chipbench/configs/dlrm-criteo-10m.json"
    mine = [m for m in BENCH["per_layer"] if m["name"] in READERS]
    assert [m["name"] for m in mine] == list(READERS)
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "updates_per_s_chip"
        assert m["layer"] == "worker step" and m["source"] == "device_trace"
        assert spec.metric_reader(m["name"]) is not None
    assert [m["better"] for m in mine] == ["lower", "lower", "higher", "lower"]
    assert [m["unit"] for m in mine] == ["ms", "ms", "%", "%"]
    per_layer = {m["name"] for m in spec.metrics_of(BENCH, "per_layer", CELL)}
    assert set(READERS) | {
        "store.pull_device_ms", "store.push_device_ms", "step.compute_device_ms",
        "store.gather_scatter_roofline", "device.peak_hbm_bytes", "step.device_ms",
    } <= per_layer
    assert not {"store.rule_path_device_ms", "step.state_update_device_ms"} & per_layer
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
    assert {"driver.dispatch_ms", "setup.compiles"} <= set(last["metric_names"])
