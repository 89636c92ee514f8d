"""``models/difacto.py``: the updater against a per-feature numpy loop, the
gate on both sides, the logic through ``make_train_step`` and the driver, the
store's in-place init of a long dense table and, since PR 47, the packed
layout the store resolves for 36 lanes."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_parameter_server_tpu import DriverConfig, StreamingDriver
from flink_parameter_server_tpu.core import store as store_mod
from flink_parameter_server_tpu.core.store import ShardedParamStore
from flink_parameter_server_tpu.core.transform import make_train_step
from flink_parameter_server_tpu.models import difacto as df
from flink_parameter_server_tpu.models import factorization_machine as fmm

DIM = 4
LANES = 4 + 2 * DIM
RULE = df.DiFactoUpdater()


def _rows(seed, n=64, dim=DIM):
    """Seeded rows on both sides of the threshold and of the gate."""
    rng = np.random.default_rng(seed)
    z = rng.normal(0, 2, n).astype(np.float32)
    s = rng.uniform(0, 8, n).astype(np.float32)
    w = np.asarray(RULE.weights(jnp.asarray(z), jnp.asarray(s)))
    c = rng.integers(0, 21, n).astype(np.float32)
    v = rng.normal(0, 0.01, (n, dim)).astype(np.float32)
    acc = rng.uniform(0, 8, (n, dim)).astype(np.float32)
    return np.concatenate([np.stack([w, z, s, c], axis=-1), v, acc], axis=-1)


def _one_feature(rule, row, combined):
    """``UpdateW`` / ``UpdateV`` for one feature's entry, from the module
    docstring's equations, float64."""
    dim = (len(row) - 4) // 2
    w, z, s, c = (float(t) for t in row[:4])
    v, acc = row[4:4 + dim].astype(float), row[4 + dim:].astype(float)
    gw = float(combined[0]) + rule.l2 * w
    s_new = np.sqrt(s * s + gw * gw)
    z_new = z - gw + (s_new - s) / rule.lr * w
    w_new = 0.0 if abs(z_new) <= rule.l1 else (
        (z_new - np.sign(z_new) * rule.l1) / ((rule.lr_beta + s_new) / rule.lr)
    )
    if c > rule.V_threshold and w != 0:
        gv = combined[4:4 + dim].astype(float) + rule.V_l2 * v
        acc_new = np.sqrt(acc * acc + gv * gv)
        v, acc = v - rule.V_lr / (acc_new + rule.V_lr_beta) * gv, acc_new
    return np.concatenate([[w_new, z_new, s_new, c], v, acc])


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_the_updater_is_the_per_feature_loop(seed):
    rows = _rows(seed)
    rng = np.random.default_rng(seed + 100)
    combined = rng.normal(0, 1, rows.shape).astype(np.float32)
    got = np.asarray(RULE(jnp.asarray(rows), jnp.asarray(combined)))
    want = np.stack([_one_feature(RULE, r, g) for r, g in zip(rows, combined)])
    assert np.allclose(got, want, rtol=2e-5, atol=1e-7)
    # both branches of w's threshold and both sides of the gate were there
    assert (got[:, 0] == 0).any() and (got[:, 0] != 0).any()
    live = (rows[:, 3] > RULE.V_threshold) & (rows[:, 0] != 0)
    assert live.any() and (~live).any()
    assert (got[:, 0] == 0).tolist() == (np.abs(want[:, 1]) <= RULE.l1).tolist()


def test_a_gated_off_rows_v_and_s_come_back_bit_equal_and_c_never_changes():
    rows = _rows(7)
    combined = np.random.default_rng(8).normal(0, 3, rows.shape).astype(np.float32)
    got = np.asarray(RULE(jnp.asarray(rows), jnp.asarray(combined)))
    live = (rows[:, 3] > RULE.V_threshold) & (rows[:, 0] != 0)
    assert np.array_equal(got[~live, 4:], rows[~live, 4:])
    assert (got[live, 4:] != rows[live, 4:]).all()
    assert np.array_equal(got[:, 3], rows[:, 3])
    # an AdaGrad lane moves at most V_lr a step, whatever the sum it is handed
    huge = np.full_like(rows, 1e6)
    moved = np.asarray(RULE(jnp.asarray(rows), jnp.asarray(huge)))[:, 4:4 + DIM]
    assert (np.abs(moved - rows[:, 4:4 + DIM]) <= RULE.V_lr * (1 + 1e-6)).all()


def test_the_updater_takes_any_leading_axes_and_one_row():
    rows = _rows(5, n=6)
    combined = np.ones_like(rows)
    flat = np.asarray(RULE(jnp.asarray(rows), jnp.asarray(combined)))
    stacked = np.asarray(RULE(
        jnp.asarray(rows.reshape(2, 3, LANES)),
        jnp.asarray(combined.reshape(2, 3, LANES)),
    ))
    assert np.array_equal(stacked.reshape(6, LANES), flat)
    assert np.array_equal(np.asarray(RULE(rows[0], combined[0])), flat[0])


def _batch(ids, rng, values=None):
    ids = np.asarray(ids, np.int32)
    shape = ids.shape
    return {
        "ids": ids,
        "values": np.ones(shape, np.float32) if values is None else values,
        "feat_mask": ids >= 0,
        "label": rng.choice(np.array([-1.0, 1.0], np.float32), shape[0]),
        "mask": np.ones(shape[0], bool),
    }


def _store(rows, rule=RULE):
    return ShardedParamStore.from_values(jnp.asarray(rows), update=rule)


def _reference_step(rule, table, batch):
    """One minibatch in numpy float64: forward, per-(example, feature)
    gradients, per-feature sums, the per-feature loop."""
    dim = (table.shape[1] - 4) // 2
    ids, x = batch["ids"], np.where(batch["feat_mask"], batch["values"], 0.0)
    pulled = table[np.clip(ids, 0, None)].astype(float)
    w, c, v = pulled[..., 0], pulled[..., 3], pulled[..., 4:4 + dim]
    a = (c > rule.V_threshold) & (w != 0) & batch["feat_mask"]
    xv = x[..., None] * np.where(a[..., None], v, 0.0)
    t = xv.sum(axis=1)
    y_hat = (w * x).sum(-1) + 0.5 * ((t * t).sum(-1) - (xv * xv).sum((1, 2)))
    g = 1 / (1 + np.exp(-y_hat)) - (batch["label"] > 0)
    sums = np.zeros(table.shape)
    live = batch["feat_mask"] & batch["mask"][:, None]
    np.add.at(sums[:, 0], ids[live], (g[:, None] * x)[live])
    gv = a[..., None] * g[:, None, None] * x[..., None] * (t[:, None, :] - xv)
    np.add.at(sums[:, 4:4 + dim], ids[live], gv[live])
    new = table.astype(float).copy()
    for f in np.unique(ids[live]):
        new[f] = _one_feature(rule, table[f], sums[f])
    return new, y_hat, a & live


@pytest.mark.parametrize("seed", [11, 12])
def test_the_step_is_forward_gradients_sums_and_the_rule(seed):
    rng = np.random.default_rng(seed)
    rows = _rows(seed, n=200)
    ids = rng.integers(0, 200, (32, 6))
    batch = _batch(ids, rng, rng.uniform(0, 1, ids.shape).astype(np.float32))
    store = _store(rows)
    logic = df.DiFacto(df.DiFactoConfig(200, DIM))
    table, _, out = jax.jit(make_train_step(logic, store.spec))(
        store.table, (), batch
    )
    want, y_hat, v_live = _reference_step(RULE, rows, batch)
    assert np.allclose(np.asarray(table), want, rtol=2e-5, atol=2e-7)
    assert np.allclose(out["prediction"], 1 / (1 + np.exp(-y_hat)), atol=1e-6)
    assert int(out["fm_live_keys"]) == ids.size
    assert int(out["fm_v_live_keys"]) == int(v_live.sum()) > 0
    assert int(out["ps_rule_rows"]) == len(np.unique(ids))


def test_duplicates_are_summed_before_the_rule():
    # one hot row named by every example, and twice by the first
    rng = np.random.default_rng(3)
    rows = _rows(3, n=50)
    rows[0, 3], rows[0, 1], rows[0, 2] = 20, 3.0, 1.0  # live, a weight
    rows[0, 0] = np.asarray(RULE.weights(jnp.float32(3.0), jnp.float32(1.0)))
    ids = np.concatenate(
        [np.zeros((16, 1), int), rng.integers(1, 50, (16, 3))], axis=1
    )
    ids[0, 1] = 0
    batch = _batch(ids, rng)
    store = _store(rows)
    logic = df.DiFacto(df.DiFactoConfig(50, DIM))
    table, _, out = jax.jit(make_train_step(logic, store.spec))(
        store.table, (), batch
    )
    want, _, _ = _reference_step(RULE, rows, batch)
    table = np.asarray(table)[:50]  # the store pads its rows to whole tiles
    assert np.allclose(table, want, rtol=2e-5, atol=2e-7)
    # one update of the hot row from the SUM of its 17 gradients: 17 updates
    # of FTRL's accumulator would have grown s by more
    assert int(out["ps_rule_rows"]) == len(np.unique(ids))
    assert table[0, 2] == pytest.approx(want[0, 2], rel=1e-6)


def test_a_dead_lane_moves_nothing_and_is_not_counted():
    rng = np.random.default_rng(4)
    rows = _rows(4, n=30)
    ids = rng.integers(1, 30, (8, 5))
    ids[:, 4] = -1  # dead: feat_mask false, id -1 (pull reads row 0)
    batch = _batch(ids, rng)
    batch["values"][:, 4] = np.nan  # whatever a dead lane holds
    store = _store(rows)
    logic = df.DiFacto(df.DiFactoConfig(30, DIM))
    table, _, out = jax.jit(make_train_step(logic, store.spec))(
        store.table, (), batch
    )
    table = np.asarray(table)[:30]
    assert np.isfinite(table).all()
    assert np.array_equal(table[0], rows[0])  # row 0 was never named
    assert int(out["fm_live_keys"]) == int(out["ps_rule_keys"]) == 8 * 4
    want, _, _ = _reference_step(RULE, rows, batch)
    assert np.allclose(table, want, rtol=2e-5, atol=2e-7)
    # a masked example pushes nothing either
    batch["mask"][:] = False
    same, _, out = jax.jit(make_train_step(logic, store.spec))(
        store.table, (), batch
    )
    assert np.array_equal(np.asarray(same)[:30], rows)
    assert int(out["fm_live_keys"]) == int(out["fm_v_live_keys"]) == 0


def test_with_every_gate_open_the_forward_pass_and_gradients_are_fms():
    """Cell 2's logic and this one call one function: where every embedding
    is live, DiFacto's raw gradients are FM's deltas over ``-lr``."""
    rng = np.random.default_rng(6)
    n, dim = 40, DIM
    fm_rows = rng.normal(0, 0.1, (n, 1 + dim)).astype(np.float32)
    rows = np.zeros((n, 4 + 2 * dim), np.float32)
    rows[:, 0], rows[:, 3], rows[:, 4:4 + dim] = fm_rows[:, 0], 99, fm_rows[:, 1:]
    ids = rng.integers(0, n, (16, 5))
    batch = _batch(ids, rng, rng.uniform(0, 1, ids.shape).astype(np.float32))
    cfg = fmm.FMConfig(num_features=n, dim=dim, learning_rate=0.5)
    _, fm_req, fm_out = fmm.FactorizationMachine(cfg).step(
        (), batch, jnp.asarray(fm_rows)[ids]
    )
    _, req, out = df.DiFacto(df.DiFactoConfig(n, dim)).step(
        (), batch, jnp.asarray(rows)[ids]
    )
    assert np.allclose(
        out["prediction"], jax.nn.sigmoid(fm_out["prediction"]), atol=1e-6
    )
    assert np.allclose(out["loss"], fm_out["loss"], rtol=1e-5, atol=1e-6)
    raw = np.asarray(req.deltas)
    fm = np.asarray(fm_req.deltas) / -0.5
    assert np.allclose(raw[..., 0], fm[..., 0], rtol=1e-4, atol=1e-7)
    assert np.allclose(raw[..., 4:4 + dim], fm[..., 1:], rtol=1e-4, atol=1e-7)
    # the optimiser's lanes carry nothing
    assert not raw[..., 1:4].any() and not raw[..., 4 + dim:].any()
    assert int(out["fm_v_live_keys"]) == int(out["fm_live_keys"]) == ids.size


@pytest.mark.parametrize("dim, layout, table", [
    (16, "packed", (104, 128)),   # 36 lanes: three rows to a physical row
    (2, "dense", (384, 8)),       # 8 lanes: the narrow rule store's tile
    (32, "packed", (304, 128)),   # 68 lanes: one to a register (PR 61)
])
def test_make_store_is_a_rule_store_of_fresh_rows_packed_by_its_width(
        dim, layout, table):
    """``make_store`` leaves the layout to the store: DiFacto's 36 lanes (k =
    16) lie three to a 128-lane physical row since PR 47, 68 one to a register since PR 61;
    the widths that stay dense are the dense store they were."""
    cfg = df.DiFactoConfig(300, dim)
    lanes = 4 + 2 * dim
    store = df.make_store(cfg, seed=5)
    assert store.spec.layout == layout and store.spec.update == RULE
    assert store.spec.value_shape == (lanes,) and cfg.row_lanes == lanes
    assert store.table.shape == table
    values = np.asarray(store.values())
    assert values.shape == (300, lanes)
    assert not values[:, :3].any() and not values[:, 4 + dim:].any()
    assert (values[:, 3] == RULE.V_threshold + 1).all()
    assert 0.008 < values[:, 4:4 + dim].std() < 0.012
    other = df.make_store(cfg, seed=6)
    assert not np.array_equal(values, np.asarray(other.values()))
    # either layout may be pinned, the rows are the same
    for pinned in ("dense", "packed"):
        made = df.make_store(cfg, seed=5, layout=pinned)
        assert made.spec.layout == pinned
        assert np.array_equal(np.asarray(made.values()), values)
    if layout == "packed":
        # the pad lanes 108-127 are zeros, the padding rows fresh rows
        assert not np.asarray(store.table)[:, 108:].any()
    # the seed may be traced: one program whatever the seed
    traced = jax.jit(lambda s: df.make_store(cfg, seed=s).table)(5)
    assert np.array_equal(np.asarray(traced), np.asarray(store.table))
    # a model under way comes in through init_fn
    warm = df.make_store(
        cfg, init_fn=lambda ids: jnp.ones(ids.shape + (lanes,), jnp.float32)
    )
    assert (np.asarray(warm.values()) == 1).all()


def test_the_benchmarks_build_starts_warm_in_place_on_both_sides_of_the_gate():
    """``chipbench.families.difacto.build``'s warm start at the dry-run
    sizes under the configuration's own ``warm_start``: the assertions of
    ``tests/chipbench_tests/test_chipbench_difacto.py::test_build_starts_warm_
    in_place_on_both_sides_of_threshold_and_gate``, which stops at its
    ``layout == "dense"`` line since PR 47 and is the benchmark's to edit;
    they guard here, with the layout the store now resolves."""
    from chipbench import spec
    from flink_parameter_server_tpu.core import store as store_mod

    bench = spec.load_benchmark()
    cell = "difacto-criteo-10m.train-fields-uniform"
    full = spec.resolve(bench, cell, dry_run=False)
    dry = spec.resolve(bench, cell, dry_run=True)
    fam = spec.family("difacto")
    cfg = {
        **dry["cfg"],
        "warm_start": {**full["cfg"]["warm_start"], "examples": 5000.0},
    }
    logic, store = fam.build(cfg, 77, None)
    _, other = fam.build(cfg, 2**31 + 6, None)
    assert isinstance(logic, df.DiFacto)
    assert store.spec.layout == "packed" and store.spec.pack == 3
    assert store.table.shape == (store.spec.rows_per_shard, 128)
    assert not np.asarray(store.table)[:, 108:].any()
    rule = store.spec.update
    assert isinstance(rule, df.DiFactoUpdater) and logic.V_threshold == 10.0
    assert [getattr(rule, k) for k in fam.RULE_KEYS] == [
        cfg[k] for k in fam.RULE_KEYS]
    values = np.asarray(store.values())
    assert values.shape == (cfg["num_features"], 36)
    w, z, s, c = values[:, :4].T
    assert abs(z.std() - 2.0) < 0.1 and 0 <= s.min() and s.max() < 8
    want = np.asarray(rule.weights(jnp.asarray(z), jnp.asarray(s)))
    assert np.allclose(w, want, rtol=1e-6, atol=0)
    assert ((w == 0) == (want == 0)).all()
    assert 0.3 < (w == 0).mean() < 0.45  # |z| <= l1 with z ~ N(0, 2): 38 %
    assert abs(values[:, 4:20].std() - 0.01) < 5e-4
    assert 0 <= values[:, 20:].min() and values[:, 20:].max() < 8
    assert (c == np.floor(c)).all() and (c[:13] > 100).all()
    # a field of 509 rows after 5,000 examples: mean count ~9.8, both sides
    big = slice(13, 13 + 509)
    assert 0.2 < (c[big] > 10).mean() < 0.5
    small = fam.field_firsts(cfg)[5]
    assert (c[small:small + 3] > 10).all()
    assert not np.array_equal(values, np.asarray(other.values()))
    # the warm start's live shares at seed 77, rows and a batch's keys: the
    # rows are a function of the seed and the id, not of where they lie
    live = (c > 10) & (w != 0)
    assert live.mean() == pytest.approx(0.20995, abs=2e-4)
    (batch,) = fam.host_batches(cfg, dry["traffic_spec"], 77, 1)
    dry_values = np.asarray(fam.build(dry["cfg"], 77, None)[1].values())
    assert ((dry_values[:, 3] > 10) & (dry_values[:, 0] != 0)).mean() > 0.85
    assert live[batch["ids"]].mean() == pytest.approx(0.47406, abs=2e-4)
    # in place: the rows of a dense store made by the same init, bit for bit
    init = fam.warm_rows(cfg, rule, jax.random.PRNGKey(77))
    dense = df.make_store(
        df.DiFactoConfig(cfg["num_features"], cfg["dim"]), rule,
        init_fn=init, layout="dense")
    assert np.asarray(dense.values()).tobytes() == values.tobytes()
    # and packed chunk by chunk gives what one chunk gives
    whole = store_mod._PACK_CHUNK
    try:
        store_mod._PACK_CHUNK = 64  # several trips, the last one early
        _, chunked = fam.build(cfg, 77, None)
    finally:
        store_mod._PACK_CHUNK = whole
    assert np.asarray(chunked.table).tobytes() == np.asarray(store.table).tobytes()


@pytest.mark.parametrize("seed", [21, 22])
def test_the_step_on_the_packed_store_is_the_step_on_the_dense_one(seed):
    """``make_train_step`` over the layout ``make_store`` resolves (packed,
    three rows to a physical row) against the dense store on the same
    batch: the same rows, the same outputs, and the count of physical rows
    the write-back wrote among them (``tests/test_store.py`` holds the two
    pushes to each other bit for bit, op by op)."""
    rng = np.random.default_rng(seed)
    dim = 16
    rows = _rows(seed, n=200, dim=dim)
    ids = rng.integers(0, 200, (32, 6))
    ids[:, 0] = np.arange(32) % 3  # rows 0, 1, 2: one physical row, hot
    batch = _batch(ids, rng, rng.uniform(0, 1, ids.shape).astype(np.float32))
    logic = df.DiFacto(df.DiFactoConfig(200, dim))
    packed = ShardedParamStore.from_values(
        jnp.asarray(rows), update=RULE, layout="auto")
    dense = _store(rows)
    assert packed.spec.layout == "packed" and dense.spec.layout == "dense"
    got, _, out = jax.jit(make_train_step(logic, packed.spec))(
        packed.table, (), batch)
    want, _, want_out = jax.jit(make_train_step(logic, dense.spec))(
        dense.table, (), batch)
    got = np.asarray(ShardedParamStore(packed.spec, got).values())
    want = np.asarray(want)[:200]
    # the same function on the same numbers, compiled into other fusions:
    # equal to a rounding where touched, bit for bit where not
    hit = np.zeros(200, bool)
    hit[np.unique(ids)] = True
    assert np.allclose(got[hit], want[hit], rtol=1e-6, atol=1e-9)
    assert (got[hit] != rows[hit]).any(axis=1).all()
    assert got[~hit].tobytes() == want[~hit].tobytes() == rows[~hit].tobytes()
    for name in ("prediction", "loss", "fm_live_keys", "fm_v_live_keys",
                 "ps_rule_keys", "ps_rule_rows"):
        assert np.array_equal(np.asarray(out[name]), np.asarray(want_out[name]))
    assert "ps_rule_packed_rows" not in want_out
    assert int(out["ps_rule_packed_rows"]) == len(np.unique(np.unique(ids) // 3))
    reference, _, _ = _reference_step(RULE, rows, batch)
    assert np.allclose(got, reference, rtol=2e-5, atol=2e-7)


@pytest.mark.parametrize("value_shape, update", [
    ((36,), RULE), ((3,), RULE), ((128,), "add"), ((), "add"),
])
def test_a_long_dense_table_is_initialised_in_blocks_to_the_same_rows(
        value_shape, update, monkeypatch):
    def init(ids):
        lanes = 1
        for s in value_shape:
            lanes *= s
        rows = ids[:, None] * 1000 + jnp.arange(lanes)[None, :]
        return rows.reshape(ids.shape + value_shape).astype(jnp.float32)

    def create():
        return ShardedParamStore.create(
            1000, value_shape, init_fn=init, update=update
        )

    whole = create()
    monkeypatch.setattr(store_mod, "_INIT_BLOCK", 384)  # 3 blocks, the last early
    blocks = create()
    assert blocks.spec == whole.spec and blocks.table.shape == whole.table.shape
    assert np.array_equal(np.asarray(blocks.table), np.asarray(whole.table))
    assert np.array_equal(
        np.asarray(blocks.values())[:, ...], np.asarray(init(jnp.arange(1000)))
    )


def test_the_driver_sets_the_gate_s_gauges_after_the_loop():
    from flink_parameter_server_tpu.telemetry.registry import MetricsRegistry

    rng = np.random.default_rng(9)
    rows = _rows(9, n=100)
    batches = [_batch(rng.integers(0, 100, (16, 4)), rng) for _ in range(3)]
    registry = MetricsRegistry()
    driver = StreamingDriver(
        df.DiFacto(df.DiFactoConfig(100, DIM)), _store(rows),
        config=DriverConfig(steps_per_call=1, dump_model=False),
        registry=registry,
    )
    result = driver.run(iter(batches), collect_outputs=True)
    gauges = registry.snapshot()
    live = gauges["fm_live_keys"][0]["value"]
    gated = gauges["fm_v_live_keys"][0]["value"]
    assert live == 64 and 0 < gated < 64
    last = result.worker_outputs[-1]
    assert gated == float(np.sum(last["fm_v_live_keys"]))
    assert gauges["store_rule_keys"][0]["value"] == 64
    assert np.isfinite(np.asarray(result.store.table)).all()


@pytest.mark.parametrize("store", ["make_store", "reloaded", "fm"])
def test_the_driver_publishes_the_lanes_of_a_row_that_crossed(store):
    """``store_pull_row_lanes`` / ``store_push_row_lanes``: ``make_store``'s
    rows have a worker's part, ``(w, z, s, c, V)``, and a step pulls and
    pushes those ``4 + dim`` lanes; a store that names no part (a reload by
    ``from_values``; an FM's add store) moves whole rows and its step hands
    out no such count: the gauges stay unset, the whole row's width is the
    spec's to say."""
    from flink_parameter_server_tpu.telemetry.registry import MetricsRegistry

    rng = np.random.default_rng(59)
    batches = [_batch(rng.integers(0, 100, (16, 4)), rng) for _ in range(2)]
    cfg = df.DiFactoConfig(100, DIM)
    logic = df.DiFacto(cfg)
    if store == "make_store":
        made = df.make_store(cfg, seed=3)
        assert made.spec.worker_width == df.STATE_LANES + DIM == 8
    elif store == "reloaded":
        made = _store(_rows(9, n=100))
        assert made.spec.worker_width is None
    else:
        fm_cfg = fmm.FMConfig(num_features=100, dim=DIM)
        logic, made = fmm.FactorizationMachine(fm_cfg), fmm.make_store(fm_cfg)
        assert made.spec.worker_width is None
    width = made.spec.row_width
    registry = MetricsRegistry()
    driver = StreamingDriver(
        logic, made, registry=registry,
        config=DriverConfig(steps_per_call=1, dump_model=False))
    result = driver.run(iter(batches), collect_outputs=True)
    gauges = registry.snapshot()
    last = result.worker_outputs[-1]
    if store == "make_store":
        assert gauges["store_pull_row_lanes"][0]["value"] == 8 < width == 12
        assert gauges["store_push_row_lanes"][0]["value"] == 8
        assert int(last["ps_pull_row_lanes"]) == 8
    else:
        assert "store_pull_row_lanes" not in gauges
        assert "store_push_row_lanes" not in gauges
        assert "ps_pull_row_lanes" not in last
    assert np.isfinite(np.asarray(result.store.table)).all()


@pytest.mark.parametrize("arm", ["xla", "row_kernel"])
def test_the_driver_publishes_the_descriptors_the_combine_issued(
        arm, monkeypatch, steer_arms):
    """``store_combine_kernel_writes`` beside ``store_combine_kernel_lanes``:
    with the row kernel steered on (interpreted here) ONE copy a block of
    256 sorted lanes of the last dispatch (its sums are neighbours: the
    dense plan), where the lanes are its live keys; both 0 where XLA's
    scatter-add summed the rows."""
    from flink_parameter_server_tpu.core import store as store_mod
    from flink_parameter_server_tpu.telemetry.registry import MetricsRegistry

    if arm == "row_kernel":
        steer_arms(combine="row_kernel")
    rng = np.random.default_rng(54)
    ids = rng.integers(0, 100, (3, 128, 4))
    ids[:, :, 0] = 7  # an integer field's row: every example names it
    batches = [_batch(i, rng) for i in ids]
    registry = MetricsRegistry()
    driver = StreamingDriver(
        df.DiFacto(df.DiFactoConfig(100, DIM)), _store(_rows(9, n=100)),
        config=DriverConfig(steps_per_call=1, dump_model=False),
        registry=registry,
    )
    driver.run(iter(batches))
    gauges = registry.snapshot()
    lanes = gauges["store_combine_kernel_lanes"][0]["value"]
    writes = gauges["store_combine_kernel_writes"][0]["value"]
    if arm == "xla":
        assert lanes == 0 == writes
        return
    rows = gauges["store_rule_rows"][0]["value"]
    assert lanes == gauges["store_rule_keys"][0]["value"] == 512
    assert rows == len(np.unique(ids[-1])) > 50
    assert writes == 2 == lanes // 256  # a copy a block, not a DMA a row


def test_the_logics_scopes_are_in_the_lowered_step():
    store = _store(_rows(1, n=20))
    logic = df.DiFacto(df.DiFactoConfig(20, DIM))
    batch = _batch(np.zeros((4, 3), int), np.random.default_rng(0))
    text = jax.jit(make_train_step(logic, store.spec)).lower(
        store.table, (), batch
    ).as_text(debug_info=True)
    for scope in ("ps.compute/ps.gate", "ps.compute/ps.delta_build",
                  "ps.push/ps.combine", "ps.rule"):
        assert scope in text, scope


def test_the_threshold_is_the_updaters_on_both_sides():
    rule = dataclasses.replace(RULE, V_threshold=3.0)
    logic = df.DiFacto(df.DiFactoConfig(10, DIM), rule)
    assert logic.V_threshold == 3.0
    rows = _rows(2, n=10)
    rows[:, 3] = 5  # past 3, short of 10
    rows[:, 1], rows[:, 2] = 4.0, 1.0
    rows[:, 0] = np.asarray(rule.weights(jnp.float32(4.0), jnp.float32(1.0)))
    batch = _batch(np.arange(10).reshape(2, 5), np.random.default_rng(0))
    _, _, out = logic.step((), batch, jnp.asarray(rows)[batch["ids"]])
    assert int(out["fm_v_live_keys"]) == 10
    _, _, out = df.DiFacto(df.DiFactoConfig(10, DIM)).step(
        (), batch, jnp.asarray(rows)[batch["ids"]]
    )
    assert int(out["fm_v_live_keys"]) == 0
    fresh = df.fresh_rows(df.DiFactoConfig(10, DIM), rule)(jnp.arange(10))
    assert (np.asarray(fresh)[:, 3] == 4.0).all()


@pytest.mark.parametrize("width, n", [(5, 1000), (36, 4096), (36, 777), (127, 300)])
def test_wide_rows_are_summed_in_stream_order_bit_for_bit(width, n):
    """Rows wider than a sort carries are scatter-added where they lie: a
    row's gradients are summed one by one in the order of the stream, which
    is what ``np.add.at`` does in float32 (and what the benchmark's plain
    reference does)."""
    from flink_parameter_server_tpu.ops.dedup import combine_runs

    rng = np.random.default_rng(width + n)
    sentinel = 5000
    ids = rng.integers(0, 60, n).astype(np.int32)
    ids[: n // 3] = 11  # one hot row
    ids[rng.random(n) < 0.1] = sentinel  # lanes to drop
    vals = rng.normal(size=(n, width)).astype(np.float32)
    row_ids, sums, _ = jax.jit(combine_runs, static_argnums=(2, 3))(
        ids, vals, sentinel, "scatter_add")
    distinct = np.unique(ids[ids < sentinel])
    assert np.array_equal(np.asarray(row_ids)[: len(distinct)], distinct)
    assert (np.asarray(row_ids)[len(distinct):] == sentinel).all()
    want = np.zeros((sentinel + 1, width), np.float32)
    np.add.at(want, ids, vals)
    assert np.array_equal(np.asarray(sums)[: len(distinct)], want[distinct])


# The other side of that choice (``core/store.arms``' ``combine``: a TPU,
# float32, rows of 5 to 128 lanes): the rows permuted once into sorted order
# at 128 lanes and every run summed by ``ops/row_update``'s row kernel, block
# by block on the MXU, NOT in the order of the stream.  Here the kernel is
# interpreted and a call's lanes are cut to 512, so that a few hundred lanes
# walk several blocks of 256 and several stretches.
def _kernel_traffic(kind, rng, n, sentinel):
    """``n`` ids in [0, 60), ``sentinel`` on the lanes to drop."""
    ids = rng.integers(0, 60, n).astype(np.int32)
    ids[rng.random(n) < 0.1] = sentinel
    if kind == "mixed":  # a hot row on a third of the lanes, a tenth dropped
        ids[: n // 3] = 11
    elif kind == "run_over_a_block":  # 300 lanes > 256, inside one stretch
        ids[:] = np.where(ids < 30, ids + 30, ids)
        ids[100:400] = 2
    elif kind == "run_over_three_stretches":  # sorted lanes ~100 to ~1300
        ids[150:1350] = 7
    elif kind == "no_lane_dropped":  # the run of dropped lanes is empty
        ids[ids == sentinel] = 59
    elif kind == "every_lane_dropped":
        ids[:] = sentinel
    elif kind == "every_lane_its_own_row":  # whole stretches, nothing padded
        ids = rng.permutation(n).astype(np.int32)
    elif kind != "nan_stays_in_its_row":
        raise AssertionError(kind)
    return rng.permutation(ids)


@pytest.mark.parametrize("width, n, traffic", [
    (5, 1000, "mixed"), (17, 1100, "mixed"), (36, 4096, "mixed"),
    (36, 777, "mixed"), (127, 600, "mixed"), (128, 300, "mixed"),
    (36, 700, "run_over_a_block"), (36, 1500, "run_over_three_stretches"),
    (36, 900, "no_lane_dropped"), (36, 300, "every_lane_dropped"),
    (36, 1024, "every_lane_its_own_row"), (36, 1100, "nan_stays_in_its_row"),
])
def test_wide_rows_are_summed_along_sorted_lanes_by_the_row_kernel(
        width, n, traffic, monkeypatch):
    """``combine_runs(..., "row_kernel")``: the distinct ids exactly as the other
    arm hands them out, and every run's total within 2 ulps of the sum of
    its addends' magnitudes of the float64 sum (a blocked float32 sum: not
    ``np.add.at``'s bits), nothing in the rows past the distinct ones."""
    from flink_parameter_server_tpu.ops import row_update
    from flink_parameter_server_tpu.ops.dedup import combine_runs

    monkeypatch.setattr(row_update, "MAX_LANES", 512)
    calls = []
    real = row_update.sorted_run_sums
    monkeypatch.setattr(
        row_update, "sorted_run_sums",
        lambda *a, **kw: calls.append(a[1].shape[0]) or real(*a, **kw))
    rng = np.random.default_rng([width, n])
    sentinel = 5000
    ids = _kernel_traffic(traffic, rng, n, sentinel)
    vals = rng.normal(size=(n, width)).astype(np.float32)
    nan_at = None
    if traffic == "nan_stays_in_its_row":
        lane = int(np.flatnonzero(ids == 11)[3])
        nan_at, vals[lane, 2] = (11, 2), np.nan
        vals[ids == sentinel] = np.nan  # a dropped lane's reaches nothing
    row_ids, sums, sent = jax.jit(
        lambda i, v: combine_runs(i, v, sentinel, "row_kernel", interpret=True)
    )(ids, vals)
    # one traced kernel under a loop, whole blocks, no more lanes than a call holds
    assert len(calls) == 1 and calls[0] <= 512 and calls[0] % 256 == 0
    # the walk pays by the block: the stretches that hold a live lane, ONE
    # copy a block of 256 lanes in which a run ends (its sums are neighbours)
    walked = -(-int((ids < sentinel).sum()) // calls[0])
    runs = len(np.unique(ids[ids < sentinel]))
    assert (runs > 0) <= int(sent) <= min(runs + walked, walked * (calls[0] // 256))
    row_ids, sums = np.asarray(row_ids), np.array(sums)
    assert row_ids.shape == (n,) and sums.shape == (n, width)
    assert sums.dtype == np.float32
    distinct = np.unique(ids[ids < sentinel])
    assert np.array_equal(row_ids[: len(distinct)], distinct)
    assert (row_ids[len(distinct):] == sentinel).all()
    live = ids < sentinel
    want = np.zeros((sentinel, width))
    size = np.zeros((sentinel, width))
    np.add.at(want, ids[live], vals[live].astype(np.float64))
    np.add.at(size, ids[live], np.abs(vals[live]).astype(np.float64))
    got, want, size = sums[: len(distinct)], want[distinct], size[distinct]
    if nan_at is not None:
        at = (int(np.searchsorted(distinct, nan_at[0])), nan_at[1])
        assert np.isnan(got[at]) and np.isnan(want[at])
        got[at] = want[at] = size[at] = 0.0
    assert np.isfinite(got).all()
    eps = float(np.finfo(np.float32).eps)
    assert (np.abs(got - want) <= 2 * eps * size).all(), float(
        (np.abs(got - want) / (eps * size + 1e-30)).max())
    assert not sums[len(distinct):].any()
