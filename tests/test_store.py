"""ShardedParamStore unit tests: pull/push semantics, sharding, init.

Mirrors the reference's server-side semantics (SimplePSLogic:
getOrElseUpdate + user update fn — SURVEY.md §2 #3) at microbatch
granularity.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_parameter_server_tpu.core.store import ShardedParamStore
from flink_parameter_server_tpu.parallel.collectives import (
    shard_pull,
    shard_push_add,
)
from flink_parameter_server_tpu.utils.initializers import (
    ranged_random_factor,
    zeros,
)


def test_pull_returns_initialized_values():
    init = ranged_random_factor(seed=7, value_shape=(4,), low=-0.5, high=0.5)
    store = ShardedParamStore.create(100, (4,), init_fn=init)
    ids = jnp.array([3, 17, 3, 99])
    vals = store.pull(ids)
    assert vals.shape == (4, 4)
    # Deterministic per id: duplicate ids pull identical vectors.
    np.testing.assert_allclose(vals[0], vals[2])
    # And match a fresh evaluation of the initializer.
    np.testing.assert_allclose(np.asarray(vals), np.asarray(init(ids)), rtol=1e-6)


def test_push_add_with_duplicates_matches_sequential():
    store = ShardedParamStore.create(10, (2,), init_fn=zeros((2,)))
    ids = jnp.array([1, 1, 3, 1])
    deltas = jnp.array([[1.0, 0.0], [2.0, 0.0], [5.0, 5.0], [4.0, 1.0]])
    out = store.push(ids, deltas)
    expect = np.zeros((10, 2))
    for i, d in zip([1, 1, 3, 1], np.asarray(deltas)):
        expect[i] += d  # sequential reference semantics; add is commutative
    np.testing.assert_allclose(np.asarray(out.values()), expect)


def test_push_mask_drops_padding_lanes():
    store = ShardedParamStore.create(8, (), init_fn=zeros(()))
    ids = jnp.array([2, 5, 0])
    deltas = jnp.array([10.0, 20.0, 99.0])
    mask = jnp.array([True, True, False])
    out = store.push(ids, deltas, mask)
    got = np.asarray(out.values())
    assert got[2] == 10.0 and got[5] == 20.0 and got[0] == 0.0


def test_generic_update_fn():
    # Custom non-add update: exponential moving average of combined deltas.
    def ema(current, combined):
        return 0.5 * current + 0.5 * combined

    store = ShardedParamStore.create(6, (), init_fn=zeros(()), update=ema)
    store = store.push(jnp.array([0, 1]), jnp.array([8.0, 4.0]))
    got = np.asarray(store.values())
    assert got[0] == 4.0 and got[1] == 2.0
    # Untouched rows must remain untouched by the generic dense path.
    assert got[2] == 0.0
    store = store.push(jnp.array([0]), jnp.array([0.0]))
    assert np.asarray(store.values())[0] == 2.0


def test_sharded_store_matches_single_device(mesh):
    init = ranged_random_factor(seed=3, value_shape=(8,))
    sharded = ShardedParamStore.create(64, (8,), init_fn=init, mesh=mesh)
    local = ShardedParamStore.create(64, (8,), init_fn=init)
    np.testing.assert_allclose(
        np.asarray(sharded.values()), np.asarray(local.values()), rtol=1e-6
    )
    ids = jnp.array([0, 5, 63, 31, 5])
    deltas = jnp.ones((5, 8))
    a = sharded.push(ids, deltas)
    b = local.push(ids, deltas)
    np.testing.assert_allclose(np.asarray(a.values()), np.asarray(b.values()), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(a.pull(ids)), np.asarray(b.pull(ids)), rtol=1e-6
    )


def test_from_values_model_load(mesh):
    values = jnp.arange(20.0).reshape(10, 2)
    store = ShardedParamStore.from_values(values, mesh=mesh)
    np.testing.assert_allclose(np.asarray(store.values()), np.asarray(values))
    np.testing.assert_allclose(
        np.asarray(store.pull(jnp.array([7]))), [[14.0, 15.0]]
    )


@pytest.mark.parametrize("origin", [
    "numpy", "uncommitted", "one_device", "other_device", "smaller_mesh",
    "same_mesh_sharded", "same_mesh_replicated",
])
def test_model_load_pads_values_from_anywhere(origin, mesh_devices):
    """12 rows over ``ps = 4`` need 20 padding rows (shards of 8).  Values
    that already lie on the mesh are padded by a jitted program of that mesh
    (``core/store._pad_rows``: a table larger than a chip cannot be padded
    any other way); values from anywhere else are padded where they are and
    then moved, as a restore onto another layout needs."""
    from jax.sharding import NamedSharding, PartitionSpec

    from flink_parameter_server_tpu.core import store as store_mod
    from flink_parameter_server_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(1, 4, devices=mesh_devices[:4])
    want = np.arange(24.0, dtype=np.float32).reshape(12, 2)
    values = {
        "numpy": lambda: want,
        "uncommitted": lambda: jnp.asarray(want),
        "one_device": lambda: jax.device_put(want, mesh_devices[0]),
        "other_device": lambda: jax.device_put(want, mesh_devices[7]),
        # a store on TWO chips resharded onto four
        "smaller_mesh": lambda: ShardedParamStore.from_values(
            want, mesh=make_mesh(1, 2, devices=mesh_devices[4:6])
        ).values(),
        "same_mesh_sharded": lambda: jax.device_put(
            want, NamedSharding(mesh, PartitionSpec("ps"))
        ),
        "same_mesh_replicated": lambda: jax.device_put(
            want, NamedSharding(mesh, PartitionSpec())
        ),
    }[origin]()
    spec = ShardedParamStore.from_values(want, mesh=mesh).spec
    assert (spec.padded_capacity, spec.rows_per_shard) == (32, 8)
    assert store_mod._lives_on_mesh(spec, values) == origin.startswith("same")
    for store in (
        ShardedParamStore.from_values(values, mesh=mesh),
        ShardedParamStore.from_spec_values(spec, jnp.asarray(values)
                                           if origin == "numpy" else values),
    ):
        assert store.table.sharding == spec.sharding()
        np.testing.assert_array_equal(np.asarray(store.values()), want)
        np.testing.assert_array_equal(np.asarray(store.table)[12:], 0.0)
        np.testing.assert_array_equal(
            np.asarray(store.pull(jnp.array([11, 0]))), want[[11, 0]]
        )


def test_model_load_under_a_trace_pads_abstractly(mesh):
    # ``eval_shape`` / ``jit`` hand ``_place`` a tracer, which lies nowhere
    traced = jax.eval_shape(
        lambda: ShardedParamStore.from_values(jnp.ones((12, 2)), mesh=mesh)
    )
    assert traced.table.shape == (32, 2)


class TestExplicitCollectives:
    """shard_map pull/push — the explicit ICI message plane."""

    def test_shard_pull_matches_take(self, mesh):
        table = jnp.arange(64 * 4, dtype=jnp.float32).reshape(64, 4)
        store = ShardedParamStore.from_values(table, mesh=mesh)
        # ids: leading dim sharded over dp (2 workers x 3 ids each)
        ids = jnp.array([[0, 17, 63], [5, 5, 32]], dtype=jnp.int32)
        got = shard_pull(store.table, ids, mesh=mesh)
        want = jnp.take(table, ids.reshape(-1), axis=0).reshape(2, 3, 4)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want))

    def test_shard_push_matches_scatter_add(self, mesh):
        table = jnp.zeros((64, 4), jnp.float32)
        store = ShardedParamStore.from_values(table, mesh=mesh)
        ids = jnp.array([[1, 1, 40], [40, 2, 63]], dtype=jnp.int32)
        deltas = jnp.ones((2, 3, 4), jnp.float32)
        mask = jnp.array([[True, True, True], [True, True, False]])
        got = shard_push_add(store.table, ids, deltas, mask, mesh=mesh)
        want = np.zeros((64, 4))
        for i, m in zip(np.asarray(ids).reshape(-1), np.asarray(mask).reshape(-1)):
            if m:
                want[i] += 1.0
        np.testing.assert_allclose(np.asarray(got), want)

    def test_pull_under_jit(self, mesh):
        table = jnp.arange(64.0).reshape(64, 1)
        store = ShardedParamStore.from_values(table, mesh=mesh)
        ids = jnp.array([[3, 9], [60, 0]], dtype=jnp.int32)

        f = jax.jit(lambda t, i: shard_pull(t, i, mesh=mesh))
        got = f(store.table, ids)
        np.testing.assert_allclose(
            np.asarray(got).reshape(-1), [3.0, 9.0, 60.0, 0.0]
        )


def test_push_out_of_range_ids_are_dropped():
    """OOB pushes must be dropped (mode='drop'), not clipped onto a real
    row — parity with shard_push_add's hit-mask semantics."""
    store = ShardedParamStore.create(10, (), init_fn=zeros(()))
    out = store.push(jnp.array([50, -3, 9]), jnp.array([1.0, 1.0, 2.0]))
    got = np.asarray(out.values())
    assert got[9] == 2.0
    assert got.sum() == 2.0  # nothing else was touched


def test_generic_update_fn_sharded(mesh):
    """Custom (non-add) update path on a sharded mesh matches the
    single-device result."""
    def ema(current, combined):
        return 0.5 * current + 0.5 * combined

    def run(m):
        s = ShardedParamStore.create(12, (2,), init_fn=zeros((2,)),
                                     update=ema, mesh=m)
        s = s.push(jnp.array([0, 3, 0]), jnp.ones((3, 2)) * 4.0)
        s = s.push(jnp.array([3]), jnp.zeros((1, 2)))
        return np.asarray(s.values())

    np.testing.assert_allclose(run(mesh), run(None), atol=1e-6)


def test_push_wrong_value_shape_clear_error():
    store = ShardedParamStore.create(10, (4,), init_fn=zeros((4,)))
    with pytest.raises(ValueError, match=r"deltas shape \(1, 3\)"):
        store.push(jnp.array([1]), jnp.ones((1, 3)))
    # batch-count mismatch (trailing dim coincidentally == value shape)
    with pytest.raises(ValueError, match=r"does not match ids"):
        store.push(jnp.arange(4), jnp.ones((4,)))
    # scalar stores get the guard too
    s0 = ShardedParamStore.create(6, (), init_fn=zeros(()))
    with pytest.raises(ValueError, match=r"does not match ids"):
        s0.push(jnp.array([0, 1]), jnp.ones((3,)))


def test_push_mask_shape_mismatch_clear_error():
    store = ShardedParamStore.create(8, (), init_fn=zeros(()))
    with pytest.raises(ValueError, match="mask shape"):
        store.push(jnp.array([2, 5, 0]), jnp.ones(3), mask=jnp.array([False]))
