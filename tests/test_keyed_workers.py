"""Keyed workers: the host-side keyed shuffle (``data/keyed.KeyedRouter``)
and the MF step that runs each worker's own records against its own block
of the user factors (``OnlineMatrixFactorization`` under a ``dp`` mesh), on
four virtual devices with a user count 4 does not divide."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import run as bench_run
from chipbench import spec as bench_spec
from flink_parameter_server_tpu import (
    DriverConfig,
    ShardedParamStore,
    StreamingDriver,
)
from flink_parameter_server_tpu.core import store as store_mod
from flink_parameter_server_tpu.core.transform import make_train_step
from flink_parameter_server_tpu.data.keyed import KeyedRouter
from flink_parameter_server_tpu.models.matrix_factorization import (
    OnlineMatrixFactorization,
    SGDUpdater,
    worker_block_rows,
)
from flink_parameter_server_tpu.parallel.mesh import make_mesh
from flink_parameter_server_tpu.telemetry.registry import MetricsRegistry
from flink_parameter_server_tpu.telemetry.spans import SpanTracer
from flink_parameter_server_tpu.utils.initializers import ranged_random_factor

WORKERS, USERS, ITEMS, DIM = 4, 1003, 256, 128
ROWS = worker_block_rows(USERS, WORKERS)  # 256: 1003 / 4 aligned up to 8
CHECK = bench_spec.load_json(
    bench_spec.ROOT + "/chipbench/configs/mf-hugewiki-k128-dp4.json"
)["reference"]
LR = 0.05


@pytest.fixture(scope="module")
def workers(mesh_devices):
    return make_mesh(WORKERS, 1, devices=mesh_devices[:WORKERS])


def _stream(seed, n_batches, lanes, *, users=USERS, live=1.0):
    """A flat stream; ``rating`` numbers the records in stream order."""
    rng = np.random.default_rng(seed)
    out = []
    for b in range(n_batches):
        out.append({
            "user": rng.integers(0, users, lanes).astype(np.int32),
            "item": rng.integers(0, ITEMS, lanes).astype(np.int32),
            "rating": (b * lanes + np.arange(lanes)).astype(np.float32),
            "mask": rng.random(lanes) < live,
        })
    return out


def _router(**kw):
    kw.setdefault("registry", MetricsRegistry())
    return KeyedRouter(WORKERS, ROWS, **kw)


def _live(batches):
    return {
        k: np.concatenate([b[k][b["mask"]] for b in batches])
        for k in ("user", "item", "rating")
    }


# -- the router ---------------------------------------------------------------
@pytest.mark.parametrize("live", [1.0, 0.8], ids=["full", "masked_lanes"])
@pytest.mark.parametrize("lanes", [64, 256])
@pytest.mark.parametrize("seed", [0, 1, 2**31 + 7])
def test_router_routes_every_record_exactly_once_in_order(seed, lanes, live):
    flat = _stream(seed, 9, lanes, live=live)
    keyed = list(_router().route(iter(flat)))
    sent, got = _live(flat), _live(keyed)
    # exactly once: the same records, whatever their order
    order_in, order_out = np.argsort(sent["rating"]), np.argsort(got["rating"])
    for k in sent:
        np.testing.assert_array_equal(sent[k][order_in], got[k][order_out])
    block = lanes // WORKERS
    for b in keyed:
        assert set(b) == {"user", "item", "rating", "mask"}
        assert all(len(v) == lanes for v in b.values())
        for w in range(WORKERS):
            at = slice(w * block, (w + 1) * block)
            # pure blocks: a live lane of block w names a user of worker w,
            # a padded lane the worker's first row
            assert (b["user"][at] // ROWS == w).all()
            # live lanes first, and within a worker in stream order
            m = b["mask"][at]
            assert not m[np.argmin(m):].any() or m.all()
    for w in range(WORKERS):
        mine = got["rating"][got["user"] // ROWS == w]
        assert (np.diff(mine) > 0).all()


def test_router_holds_the_remainder_and_flushes_it_padded():
    registry = MetricsRegistry()
    router = _router(registry=registry)
    flat = _stream(3, 5, 64)
    per_worker = np.bincount(
        np.concatenate([b["user"] for b in flat]) // ROWS, minlength=WORKERS
    )
    keyed = list(router.route(iter(flat)))
    # every batch before the flush has every block full
    full = int(per_worker.min()) // 16
    assert all(b["mask"].all() for b in keyed[:full])
    assert len(keyed) == -(-int(per_worker.max()) // 16)
    assert not keyed[-1]["mask"].all()
    counts = registry.snapshot()
    assert counts["keyed_records"][0]["value"] == 5 * 64
    assert counts["keyed_padded_lanes"][0]["value"] == len(keyed) * 64 - 5 * 64
    assert 0 < counts["keyed_buffered_max"][0]["value"] <= 5 * 64


def test_router_buffer_is_bounded_under_skew():
    # every record is worker 0's: the others starve, the buffer may not grow
    registry = MetricsRegistry()
    router = _router(registry=registry, max_buffered_blocks=2)
    flat = _stream(4, 12, 64, users=ROWS)
    emitted_before_the_end = []
    it = router.route(iter(flat))
    for b in it:
        emitted_before_the_end.append(b)
        if len(emitted_before_the_end) == 8:
            break
    assert all(int(b["mask"].sum()) == 16 for b in emitted_before_the_end)
    assert registry.snapshot()["keyed_buffered_max"][0]["value"] <= 2 * 16 + 64
    rest = list(it)
    assert sum(int(b["mask"].sum()) for b in emitted_before_the_end + rest) == 12 * 64


def test_router_hands_on_what_is_keyed_already():
    registry = MetricsRegistry()
    first = list(_router().route(iter(_stream(5, 4, 64))))
    full = [b for b in first if b["mask"].all()]
    again = list(_router(registry=registry).route(iter(full)))
    assert all(a is b for a, b in zip(again, full)) and len(again) == len(full)
    assert registry.snapshot()["keyed_records"][0]["value"] == 0
    staged = [jax.device_put(b) for b in full]
    assert all(a is b for a, b in zip(_router().route(iter(staged)), staged))


def test_router_refuses_lanes_that_do_not_split_and_records_a_span():
    with pytest.raises(ValueError, match="does not split"):
        list(_router().route(iter(_stream(6, 1, 66))))
    tracer = SpanTracer()
    list(_router(tracer=tracer).route(iter(_stream(6, 3, 64))))
    names = [(s["component"], s["name"]) for s in tracer.spans()]
    assert names == [("ingest", "key_route")] * 3


# -- the step -----------------------------------------------------------------
def _logic(mesh, **kw):
    return OnlineMatrixFactorization(
        USERS, DIM, updater=SGDUpdater(LR), mesh=mesh, init_low=-0.1,
        init_high=0.1, **kw,
    )


def _store(mesh):
    return ShardedParamStore.create(
        ITEMS, (DIM,), init_fn=ranged_random_factor(1, (DIM,), low=-0.1, high=0.1),
        mesh=mesh,
    )


def _ratings(seed, n_batches, lanes, balanced=False):
    """A flat stream with plausible ratings.  ``balanced``: every batch holds
    ``lanes / WORKERS`` users of each worker, in shuffled lanes, so the router
    emits one microbatch a flat batch, of the same records."""
    rng = np.random.default_rng(seed)
    out = _stream(seed, n_batches, lanes)
    for b in out:
        if balanced:
            last = USERS - (WORKERS - 1) * ROWS
            users = np.concatenate([
                w * ROWS + rng.integers(
                    0, last if w == WORKERS - 1 else ROWS, lanes // WORKERS)
                for w in range(WORKERS)
            ])
            b["user"] = rng.permutation(users).astype(np.int32)
        b["rating"] = rng.normal(0, 0.35, lanes).astype(np.float32)
        b["mask"] = np.ones(lanes, bool)
    return out


def _trained(mesh, batches, **kw):
    logic = _logic(mesh, **kw)
    driver = StreamingDriver(
        logic, _store(mesh), config=DriverConfig(dump_model=False, telemetry=False),
        registry=MetricsRegistry(),
    )
    res = driver.run(iter(batches))
    return np.array(res.worker_state)[:USERS], np.array(res.store.values()), driver


def _reference_rows(name, batches):
    ref = bench_spec.reference({"reference": {"file": f"chipbench/references/{name}.py"}})
    ids = ref.touched(batches)
    state = np.asarray(_logic(None).init_state(None))
    table = np.asarray(_store(None).values())
    before = {"user": state[ids["user"]], "item": table[ids["item"]]}
    want = ref.apply({"learning_rate": LR}, before, ids, batches)
    return ids, before, want


@pytest.mark.parametrize("arm", [None, "sorted_rows"], ids=["xla", "row_kernel"])
def test_keyed_step_matches_the_plain_reference_on_the_flat_stream(workers, arm):
    # chipbench/references/mf.py knows no workers and is given the FLAT
    # stream; the driver routes it (one keyed microbatch a flat batch)
    flat = _ratings(11, 3, 256, balanced=True)
    state, table, driver = _trained(workers, flat, state_scatter=arm)
    ids, before, want = _reference_rows("mf", flat)
    got = {"user": state[ids["user"]], "item": table[ids["item"]]}
    check = {**CHECK, "delta_atol": CHECK["delta_atol"] * LR / 5e-5}
    failures, worst = bench_run._check_rows(check, want, got, before)
    assert failures == [] and 0.0 < worst["share"] <= 1.0, worst
    assert driver.registry.snapshot()["keyed_misrouted"][0]["value"] == 0


@pytest.mark.parametrize("arm", [None, "sorted_rows"], ids=["xla", "row_kernel"])
@pytest.mark.parametrize("seed", [21, 22])
def test_keyed_step_matches_the_one_chip_step_on_the_same_records(
        workers, seed, arm):
    keyed = list(_logic(workers).key_router(registry=MetricsRegistry()).route(
        iter(_ratings(seed, 6, 256))
    ))
    assert not keyed[-1]["mask"].all()  # the flush, padded lanes and all
    state, table, _ = _trained(workers, keyed, state_scatter=arm)
    state1, table1, _ = _trained(None, keyed)
    # the two differ only in the order a row's deltas are summed
    ulp = CHECK["row_ulps"] * np.finfo(np.float32).eps
    assert np.abs(state - state1).max() <= ulp * np.abs(state1).max()
    assert np.abs(table - table1).max() <= ulp * np.abs(table1).max() + (
        CHECK["delta_rtol"] * LR
    )
    assert not np.array_equal(state, np.asarray(_logic(None).init_state(None)))


def test_the_mean_combiner_counts_a_user_with_its_worker_an_item_over_all(workers):
    # dedup_scale: a user's records all lie with its worker, an item's with
    # every worker; both means are the one-chip step's on the same records
    keyed = list(_logic(workers).key_router(registry=MetricsRegistry()).route(
        iter(_ratings(61, 3, 256))
    ))
    kw = {"dedup_scale": True, "num_items": ITEMS}
    state, table, _ = _trained(workers, keyed, **kw)
    state1, table1, _ = _trained(None, keyed, **kw)
    np.testing.assert_allclose(state, state1, rtol=0, atol=1e-7)
    np.testing.assert_allclose(table, table1, rtol=0, atol=1e-6)
    summed, _, _ = _trained(None, keyed)
    assert np.abs(state1 - summed).max() > 1e-5  # the combiner did something


def test_the_row_kernel_runs_interpreted_under_the_shard_map(workers):
    keyed = list(_logic(workers).key_router(registry=MetricsRegistry()).route(
        iter(_ratings(31, 2, 256))
    ))
    logic = _logic(workers, state_scatter="sorted_rows")
    store = _store(workers)
    step = jax.jit(make_train_step(logic, store.spec))
    state0 = logic.init_state(None)
    assert logic.state_update_arm(state0) == "sorted_rows"
    text = step.lower(store.table, state0, keyed[0]).as_text(debug_info=True)
    assert "sdy.manual_computation" in text and "ps.delta_reduce" in text
    table, state, out = step(store.table, state0, keyed[0])
    table_x, state_x, out_x = jax.jit(
        make_train_step(_logic(workers, state_scatter="xla"), store.spec)
    )(store.table, logic.init_state(None), keyed[0])
    np.testing.assert_allclose(state, state_x, rtol=0, atol=2e-8)
    np.testing.assert_array_equal(table, table_x)
    np.testing.assert_array_equal(out["prediction"], out_x["prediction"])
    assert int(np.sum(out["keyed_misrouted"])) == 0


def test_a_bfloat16_delta_fails_the_check(workers):
    flat = _ratings(41, 3, 256, balanced=True)
    state, table, _ = _trained(workers, flat)
    ids, before, want = _reference_rows("mf_keyed", flat)
    got = {"user": state[ids["user"]], "item": table[ids["item"]]}
    check = {**CHECK, "delta_atol": CHECK["delta_atol"] * LR / 5e-5}
    assert bench_run._check_rows(check, want, got, before)[0] == []
    coarse = {
        name: before[name] + np.asarray(
            jnp.asarray(got[name] - before[name]).astype(jnp.bfloat16),
            np.float32,
        )
        for name in got
    }
    failures, worst = bench_run._check_rows(check, want, coarse, before)
    assert len(failures) == 2 and worst["share"] > 3.0


def test_a_misrouted_record_is_counted_and_dropped_never_applied(workers):
    # the step alone, handed a FLAT batch: lane block w holds users of every
    # worker, and only those of worker w may move anything
    (flat,) = _ratings(51, 1, 256)
    logic, store = _logic(workers), _store(workers)
    table, state, out = jax.jit(make_train_step(logic, store.spec))(
        store.table, logic.init_state(None), flat
    )
    block = np.repeat(np.arange(WORKERS), 256 // WORKERS)
    mine = flat["user"] // ROWS == block
    assert 0 < mine.sum() < 256
    assert int(np.sum(out["keyed_misrouted"])) == int((~mine).sum())
    np.testing.assert_array_equal(np.asarray(out["error"])[~mine], 0.0)
    kept = {k: np.where(mine, v, 0) if k != "mask" else mine for k, v in flat.items()}
    kept["user"] = np.where(mine, flat["user"], block * ROWS).astype(np.int32)
    table1, state1, _ = jax.jit(make_train_step(_logic(None), _store(None).spec))(
        _store(None).table, _logic(None).init_state(None), kept
    )
    np.testing.assert_allclose(
        np.asarray(state)[:USERS], state1, rtol=0, atol=2e-8)
    np.testing.assert_allclose(
        np.asarray(ShardedParamStore(store.spec, table).values()),
        np.asarray(table1)[:ITEMS], rtol=0, atol=1e-7,
    )


def test_worker_state_lies_in_padded_blocks_over_the_workers(workers):
    logic = _logic(workers)
    assert (logic.workers, logic.rows_per_worker, logic.state_rows) == (4, 256, 1024)
    state = logic.init_state(None)
    assert state.shape == (1024, DIM)
    assert state.sharding.is_equivalent_to(
        jax.sharding.NamedSharding(workers, jax.sharding.PartitionSpec("dp", None)), 2
    )
    # every row, the 21 past the last user included, is its id's own
    init = ranged_random_factor(0, (DIM,), low=-0.1, high=0.1)
    np.testing.assert_array_equal(state, init(jnp.arange(1024, dtype=jnp.int32)))
    one = _logic(None)
    assert (one.workers, one.rows_per_worker, one.state_rows) == (1, USERS, USERS)
    assert one.key_router() is None
    np.testing.assert_array_equal(np.asarray(state)[:USERS], one.init_state(None))


@pytest.mark.parametrize("users,workers_,want", [
    (50_082_603, 4, 12_520_656), (50_082_603, 1, 50_082_603),
    (1003, 4, 256), (1024, 4, 256), (1025, 4, 264), (7, 2, 8),
])
def test_worker_block_rows(users, workers_, want):
    assert worker_block_rows(users, workers_) == want
    assert want * workers_ >= users


# -- the item push across workers ---------------------------------------------
@pytest.mark.parametrize("layout", ["dense", "packed"])
@pytest.mark.parametrize("shape", [(4, 1), (2, 2), (2, 4)], ids=str)
def test_push_over_workers_matches_a_plain_scatter_add(mesh_devices, shape, layout):
    mesh = make_mesh(*shape, devices=mesh_devices[: shape[0] * shape[1]])
    dim = 128 if layout == "dense" else 16
    rng = np.random.default_rng(7)
    values = rng.normal(size=(96, dim)).astype(np.float32)
    store = ShardedParamStore.from_values(jnp.asarray(values), mesh=mesh, layout=layout)
    ids = rng.integers(-1, 97, 256).astype(np.int32)  # a dead lane, one past
    ids[:40] = 5
    deltas = rng.normal(size=(256, dim)).astype(np.float32)
    mask = rng.random(256) < 0.9
    assert store_mod.arms(
        store.spec, push_lanes=256, lanes_over_workers=True
    ).push == "worker_reduce"
    assert store_mod.arms(store.spec, push_lanes=256).push == "xla_add"
    got = jax.jit(lambda t, i, d, m: store_mod.push_counted(
        store.spec, t, i, d, m, lanes_over_workers=True)[0])(
        store.table, ids, deltas, mask
    )
    # a bare push is ONE scatter-add in the batch's order whatever the mesh
    assert "ps.delta_reduce" not in jax.jit(
        lambda t, i, d, m: store_mod.push(store.spec, t, i, d, m)
    ).lower(store.table, ids, deltas, mask).as_text(debug_info=True)
    want = values.astype(np.float64)
    live = mask & (ids >= 0) & (ids < 96)
    np.add.at(want, ids[live], deltas[live].astype(np.float64))
    np.testing.assert_allclose(
        np.asarray(ShardedParamStore(store.spec, got).values()), want,
        rtol=0, atol=2e-5,
    )


@pytest.mark.parametrize("shape,capacity,lanes,want", [
    (None, 96, 256, False),        # no mesh
    ((1, 4), 96, 256, False),      # one worker
    ((4, 1), 96, 256, True),
    ((4, 1), 96, 254, False),      # lanes do not split over the workers
    ((4, 1), 4096, 256, False),    # a table larger than the batch's deltas
    ((2, 2), 512, 256, True),      # 256 rows a shard
])
def test_the_worker_reduce_is_read_from_mesh_table_and_batch(
        mesh_devices, shape, capacity, lanes, want):
    mesh = shape and make_mesh(*shape, devices=mesh_devices[: shape[0] * shape[1]])
    spec = store_mod.StoreSpec(capacity, (DIM,), mesh=mesh)
    arm = store_mod.arms(spec, push_lanes=lanes, lanes_over_workers=True)
    assert arm.push == ("worker_reduce" if want else "xla_add")
