"""Who owns the table during a run.  ``StreamingDriver.run`` hands the table
and the worker state it holds to the loop, which donates them (one table
alive, not two); ``driver.store`` never holds a deleted array at a point a
hook, a publish, a checkpoint or an exception handler can see it.  A direct
``transform_batched`` keeps its contract: the caller's store stays valid."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_parameter_server_tpu.core.store import ShardedParamStore
from flink_parameter_server_tpu.core.transform import transform_batched
from flink_parameter_server_tpu.data.movielens import synthetic_ratings
from flink_parameter_server_tpu.data.streams import microbatches
from flink_parameter_server_tpu.models.matrix_factorization import (
    OnlineMatrixFactorization,
    SGDUpdater,
)
from flink_parameter_server_tpu.training.driver import (
    DriverConfig,
    StreamingDriver,
)
from flink_parameter_server_tpu.utils.initializers import ranged_random_factor


def _parts():
    logic = OnlineMatrixFactorization(64, 4, updater=SGDUpdater(0.05))
    store = ShardedParamStore.create(
        96, (4,), init_fn=ranged_random_factor(0, (4,))
    )
    return logic, store


def _stream(n=12, seed=0):
    data = synthetic_ratings(64, 96, n * 128, rank=3, seed=seed)
    return microbatches(data, 128, shuffle_seed=1)


def _live(driver):
    """``driver.store`` and the worker state hold arrays one can read."""
    assert not driver.store.table.is_deleted()
    assert all(not x.is_deleted() for x in jax.tree.leaves(driver._state))
    return np.array(driver.store.values())


class _Seen:
    """A group hook that looks at ``driver.store`` as a hook may."""

    def __init__(self, driver, raise_at=None):
        self.driver, self.raise_at, self.tables = driver, raise_at, []

    def __call__(self, global_step, n_steps, table, state, outs):
        # the driver holds the spec and no table; the live one is handed in
        assert self.driver.store.table is None
        assert self.driver.store.spec.capacity == 96
        assert not table.is_deleted()
        self.tables.append(np.array(table))  # a copy: a view would pin the buffer
        if global_step == self.raise_at:
            raise RuntimeError("a hook's own failure")


@pytest.mark.parametrize("steps_per_call", [1, 4])
def test_run_donates_the_table_it_was_given_and_keeps_one_live(steps_per_call):
    logic, store = _parts()
    given, before = store.table, np.array(store.values())
    driver = StreamingDriver(logic, store, config=DriverConfig(
        steps_per_call=steps_per_call, dump_model=False,
    ))
    seen = _Seen(driver)
    driver.add_group_hook(seen)
    result = driver.run(_stream())
    assert given.is_deleted()  # no copy was made: the loop took this buffer
    assert result.store is driver.store
    after = _live(driver)
    assert not np.array_equal(after[:96], before)
    assert np.array_equal(seen.tables[-1][:96], after)
    # a second run takes the first run's table and state in turn
    first_table, first_state = driver.store.table, driver._state
    driver.run(_stream(seed=1))
    assert first_table.is_deleted() and first_state.is_deleted()
    assert not np.array_equal(_live(driver), after)


def test_the_run_is_the_same_arithmetic_as_the_copying_loop():
    logic, store = _parts()
    direct = transform_batched(_stream(), logic, store, dump_model=False)
    assert not store.table.is_deleted()  # the default contract: a copy ran
    driver = StreamingDriver(logic, store, config=DriverConfig(dump_model=False))
    driver.run(_stream())
    assert np.array_equal(_live(driver), np.asarray(direct.store.values()))
    assert np.array_equal(
        np.asarray(driver._state), np.asarray(direct.worker_state)
    )


def test_a_direct_call_leaves_the_callers_store_and_state_valid():
    logic, store = _parts()
    before = np.array(store.values())
    first = transform_batched(_stream(4), logic, store, dump_model=False)
    again = transform_batched(
        _stream(4), logic, store, dump_model=False,
        initial_state=first.worker_state,
    )
    assert np.array_equal(np.asarray(store.values()), before)
    assert not first.worker_state.is_deleted()
    assert not np.array_equal(np.asarray(again.store.values()), before)
    # handed over, both are gone and the result is the one live copy
    owned = transform_batched(
        _stream(4), logic, store, dump_model=False,
        initial_state=first.worker_state, owns_inputs=True,
    )
    assert store.table.is_deleted() and first.worker_state.is_deleted()
    assert np.array_equal(
        np.asarray(owned.store.values()), np.asarray(again.store.values())
    )


def _sparse_rows(rng, n=3, batch=32, fan=4, features=50):
    return [{
        "ids": rng.integers(0, features, (batch, fan)).astype(np.int32),
        "values": rng.normal(0, 1, (batch, fan)).astype(np.float32),
        "feat_mask": np.ones((batch, fan), bool),
        "label": rng.choice([-1.0, 1.0], batch).astype(np.float32),
        "mask": np.ones(batch, bool),
    } for _ in range(n)]


def _skipgram(rng, **kwargs):
    from flink_parameter_server_tpu.models.word2vec import train_skipgram

    pairs = [{
        "center": rng.integers(0, 50, 32).astype(np.int32),
        "context": rng.integers(0, 50, 32).astype(np.int32),
        "negatives": rng.integers(0, 50, (32, 3)).astype(np.int32),
        "mask": np.ones(32, bool),
    } for _ in range(3)]
    return train_skipgram(pairs, vocab_size=50, dim=8, **kwargs)


def _mf(rng, **kwargs):
    from flink_parameter_server_tpu.models.matrix_factorization import (
        ps_online_mf,
    )

    return ps_online_mf(_stream(3), num_users=64, num_items=96, dim=4, **kwargs)


def _fm(rng, **kwargs):
    from flink_parameter_server_tpu.models.factorization_machine import (
        FMConfig,
        train_fm,
    )

    return train_fm(_sparse_rows(rng), FMConfig(50, dim=4), **kwargs)


def _pa_binary(rng, **kwargs):
    from flink_parameter_server_tpu.models.passive_aggressive import (
        transform_binary,
    )

    return transform_binary(_sparse_rows(rng), num_features=50, **kwargs)


def _pa_multiclass(rng, **kwargs):
    from flink_parameter_server_tpu.models.passive_aggressive import (
        transform_multiclass,
    )

    rows = _sparse_rows(rng)
    for b in rows:
        b["label"] = rng.integers(0, 3, 32).astype(np.int32)
    return transform_multiclass(rows, num_features=50, num_classes=3, **kwargs)


@pytest.mark.parametrize(
    "helper", [_skipgram, _mf, _fm, _pa_binary, _pa_multiclass],
    ids=lambda f: f.__name__.strip("_"),
)
def test_a_helper_that_builds_its_own_store_hands_it_over(helper, monkeypatch):
    """``train_*`` / ``ps_online_mf`` / the PA transforms build a store no
    caller ever sees, so the loop takes it: no copy of the table runs."""
    from flink_parameter_server_tpu.core import transform

    copies = []
    real = transform.jnp_copy
    monkeypatch.setattr(
        transform, "jnp_copy", lambda x: copies.append(x.shape) or real(x)
    )
    result = helper(np.random.default_rng(0), dump_model=False)
    assert copies == []
    values = np.asarray(result.store.values())
    assert not result.store.table.is_deleted() and np.isfinite(values).all()
    # the same run on a copy, for a caller who says so: the same table
    kept = helper(np.random.default_rng(0), dump_model=False, owns_inputs=False)
    assert len(copies) == 1
    assert np.array_equal(np.asarray(kept.store.values()), values)


def test_after_a_run_that_raises_the_store_is_the_last_dispatchs():
    logic, store = _parts()
    driver = StreamingDriver(logic, store, config=DriverConfig(dump_model=False))
    seen = _Seen(driver, raise_at=5)
    driver.add_group_hook(seen)
    with pytest.raises(RuntimeError, match="a hook's own failure"):
        driver.run(_stream())
    assert driver.step_idx == 5 and store.table.is_deleted()
    assert np.array_equal(_live(driver), seen.tables[-1][:96])
    # and the driver runs on from there
    seen.raise_at = None
    driver.run(_stream(4, seed=2))
    assert driver.step_idx == 9
    _live(driver)


def test_a_source_that_raises_before_the_first_dispatch_loses_nothing():
    logic, store = _parts()
    before = np.array(store.values())
    driver = StreamingDriver(logic, store, config=DriverConfig(
        dump_model=False, prefetch=0,
    ))

    def broken():
        raise OSError("the source broke")
        yield

    with pytest.raises(OSError, match="the source broke"):
        driver.run(broken())
    assert driver.store.table is store.table and driver._state is None
    assert np.array_equal(_live(driver), before)


def test_a_driver_left_without_a_table_says_so(tmp_path):
    """A dispatch that itself fails may consume the buffers it was given;
    with no checkpoint to reload the driver then holds no table, and
    ``run`` and ``save`` name the cause instead of failing on ``None``."""
    logic, store = _parts()
    driver = StreamingDriver(logic, store, config=DriverConfig(
        checkpoint_dir=str(tmp_path), dump_model=False,
    ))
    driver.store = ShardedParamStore(store.spec, None)
    with pytest.raises(RuntimeError, match="holds no table.*resume\\(\\)"):
        driver.run(_stream(2))
    with pytest.raises(RuntimeError, match="holds no table"):
        driver.save()


def test_with_a_checkpoint_cadence_and_after_resume(tmp_path):
    logic, store = _parts()
    config = DriverConfig(
        checkpoint_dir=str(tmp_path), checkpoint_every=4, dump_model=False,
    )
    driver = StreamingDriver(logic, store, config=config)
    seen = _Seen(driver, raise_at=10)
    driver.add_group_hook(seen)
    with pytest.raises(RuntimeError):
        driver.run(_stream())
    # rolled back to the last durable checkpoint, which is live
    assert driver.step_idx == 8 and 8 in driver._ckpt_mgr.all_steps()
    assert np.array_equal(_live(driver), seen.tables[7][:96])
    # a fresh driver resumes and runs: its restored table is handed over too
    logic2, store2 = _parts()
    second = StreamingDriver(logic2, store2, config=config)
    assert second.resume() and second.step_idx == 8
    restored = second.store.table
    assert np.array_equal(_live(second), seen.tables[7][:96])
    second.add_group_hook(_Seen(second))
    second.run(_stream())
    assert second.step_idx == 12 and restored.is_deleted()
    # the same stream, uninterrupted, ends on the same table
    logic3, store3 = _parts()
    whole = StreamingDriver(logic3, store3, config=DriverConfig(dump_model=False))
    whole.run(_stream())
    assert np.array_equal(_live(second), _live(whole))


def test_with_serving_publishing_snapshots_stay_readable():
    logic, store = _parts()
    driver = StreamingDriver(logic, store, config=DriverConfig(dump_model=False))
    service = driver.serve_with(publish_every=2)
    published = []

    def hook(global_step, n_steps, table, state, outs):
        assert driver.store.table is None
        snap = service.snapshots.latest()
        if snap is not None:
            # a snapshot is a copy: later dispatches donate the live table,
            # never this one
            assert not snap.table.is_deleted()
            published.append(snap.version)

    driver.add_group_hook(hook)
    try:
        driver.run(_stream())
        final = service.snapshots.latest()
        assert len(set(published)) >= 3
        assert np.array_equal(
            np.asarray(final.store().values()), _live(driver)
        )
        answer = service.client().top_k(3, k=5, timeout=30.0)
        assert len(np.asarray(answer.item_ids)) == 5
    finally:
        service.stop()


class _CountingLogic(OnlineMatrixFactorization):
    """Counts how often its step is TRACED."""

    traced = 0

    def step(self, state, batch, pulled):
        type(self).traced += 1
        return super().step(state, batch, pulled)


def test_a_drivers_second_run_finds_its_step_traced_and_lowered():
    """A driver keeps the jitted step it built for its logic and spec, and
    the loop commits the table before the first dispatch: a driver that
    runs twice (a benchmark's checked batches, then its window) traces and
    lowers its step once, not twice and three times.  A direct
    ``transform_batched`` builds a step of its own each call, as before."""
    _CountingLogic.traced = 0
    logic = _CountingLogic(64, 4, updater=SGDUpdater(0.05))
    store = ShardedParamStore.create(
        96, (4,), init_fn=ranged_random_factor(0, (4,))
    )
    batches = [
        {k: jax.device_put(v, jax.devices()[0]) for k, v in b.items()}
        for b in _stream(6)
    ]  # staged on a device, as a pool is: they commit the step's outputs
    driver = StreamingDriver(logic, store, config=DriverConfig(dump_model=False))
    driver.run(iter(batches[:2]))
    step, scan_step = driver._steps[1]
    assert scan_step is None and step._cache_size() == 1
    after_first = np.array(driver.store.values())
    driver.run(iter(batches[2:]))
    assert driver._steps[1][0] is step
    assert _CountingLogic.traced == 1
    assert step._cache_size() == 1  # one program, not three
    assert not np.array_equal(np.array(driver.store.values()), after_first)
    # a logic changed between two direct calls is traced as it then stands
    for _ in range(2):
        transform_batched(iter(batches[:1]), logic, driver.store, dump_model=False)
    assert _CountingLogic.traced == 3


def test_a_state_off_the_tables_mesh_is_left_for_jit_to_place():
    """Under a mesh with no ``dp`` axis to shard it by, MF's user state is
    an uncommitted array on the default device beside a table committed to
    the mesh: the loop commits what lies where the table lies and leaves
    the rest, so the step runs (committing that state to its one device
    would make ``jit`` refuse the pair), in a direct call and in a driver
    that runs twice."""
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:4]), ("ps",))
    data = list(_stream(4))

    def parts():
        store = ShardedParamStore.create(
            96, (4,), init_fn=ranged_random_factor(0, (4,)), mesh=mesh
        )
        return OnlineMatrixFactorization(64, 4, updater=SGDUpdater(0.05)), store

    logic, store = parts()
    state = logic.init_state(jax.random.PRNGKey(0))
    assert state.sharding.device_set != store.table.sharding.device_set
    direct = transform_batched(iter(data), logic, store, dump_model=False)
    logic, store = parts()
    driver = StreamingDriver(logic, store, config=DriverConfig(dump_model=False))
    driver.run(iter(data[:2]))
    driver.run(iter(data[2:]))
    np.testing.assert_array_equal(
        np.array(driver.store.values()), np.array(direct.store.values())
    )
    np.testing.assert_array_equal(
        np.array(driver._state), np.array(direct.worker_state)
    )
