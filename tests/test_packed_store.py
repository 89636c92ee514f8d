"""Lane-packed store layout (ops/packed.py + StoreSpec.layout="packed").

The packed layout must be OBSERVATIONALLY IDENTICAL to the dense layout
through the whole store protocol (pull / push / values / checkpoint) —
it is purely a physical-layout change (k narrow rows per 128-lane
physical row) that buys full vector lanes for the reference's narrow
value shapes (MF dim 64, FM dim 17, PA scalars).  Push and pull of the
packed store against a float64 reference: tests/test_store.py's case table.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from flink_parameter_server_tpu.core import store as store_mod
from flink_parameter_server_tpu.core.store import ShardedParamStore, StoreSpec
from flink_parameter_server_tpu.ops.packed import (
    lane_shift_deltas,
    lane_unshift,
    pack_k,
    pack_table,
    packed_pull,
    phys_width,
    unpack_table,
)


def _rand_init(dim):
    def init(ids):
        # deterministic per id, shape (n, dim)
        base = (ids[:, None] * 31 + jnp.arange(dim)[None, :] * 7) % 13
        return (base.astype(jnp.float32) - 6.0) / 10.0

    return init


def test_pack_unpack_roundtrip():
    rng = np.random.default_rng(0)
    for cap, d in [(10, 17), (64, 64), (7, 1), (5, 128), (3, 200)]:
        v = jnp.asarray(rng.normal(0, 1, (cap, d)).astype(np.float32))
        packed = pack_table(v)
        assert packed.shape[1] % 128 == 0
        out = unpack_table(packed, cap, d)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(v))


def test_packed_pull_matches_take():
    rng = np.random.default_rng(1)
    cap, d = 50, 17
    v = jnp.asarray(rng.normal(0, 1, (cap, d)).astype(np.float32))
    packed = pack_table(v)
    ids = jnp.asarray(rng.integers(0, cap, 200).astype(np.int32))
    got = packed_pull(packed, ids, d)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(v)[np.asarray(ids)])


# The forms ops/packed.py had before PR 29, one ``take_along_axis`` each: a
# gather of scalars, ~10 ns an element on the TPU.  They stay here as the
# reference the ``select`` forms must equal bit for bit.
def _slice_by_gather(rows, ids, d):
    cols = (ids % pack_k(d))[:, None] * d + jnp.arange(d)[None, :]
    return jnp.take_along_axis(rows, cols, axis=1)


def _shift_by_gather(deltas, ids, d):
    k, w = pack_k(d), phys_width(d)
    src = jnp.arange(w)[None, :] - (ids % k)[:, None] * d
    padded = jnp.pad(deltas, ((0, 0), (0, w - d)))
    out = jnp.take_along_axis(padded, jnp.clip(src, 0, w - 1), axis=1)
    return jnp.where((src >= 0) & (src < d), out, jnp.zeros_like(out))


@pytest.mark.parametrize("d", [1, 4, 17, 64, 100])
def test_select_forms_equal_the_gather_forms_bit_for_bit(d):
    """``packed_pull`` / ``lane_shift_deltas`` / ``lane_unshift`` choose
    among ``k`` static lane slices: the same bits as a per-element gather,
    non-finite deltas included (a 0/1 matmul or a masked sum would spread
    one NaN over its whole physical row), and -0.0 kept."""
    rng = np.random.default_rng(d)
    cap, n = 300, 1000
    ids = jnp.asarray(rng.integers(0, cap, n).astype(np.int32))
    deltas = rng.normal(0, 1, (n, d)).astype(np.float32)
    for bad in (np.nan, np.inf, -np.inf, -0.0):
        deltas[rng.integers(0, n, 20), rng.integers(0, d, 20)] = bad
    deltas = jnp.asarray(deltas)
    table = rng.normal(0, 1, (cap, d)).astype(np.float32)
    table[rng.integers(0, cap, 10), rng.integers(0, d, 10)] = np.nan
    packed = pack_table(jnp.asarray(table))

    def bits(x):
        return np.asarray(x).view(np.uint32)

    shifted = lane_shift_deltas(deltas, ids, d)
    np.testing.assert_array_equal(
        bits(shifted), bits(_shift_by_gather(deltas, ids, d)))
    np.testing.assert_array_equal(
        bits(lane_unshift(shifted, ids, d)),
        bits(_slice_by_gather(shifted, ids, d)))
    np.testing.assert_array_equal(
        bits(lane_unshift(shifted, ids, d)), bits(deltas))
    np.testing.assert_array_equal(
        bits(packed_pull(packed, ids, d)),
        bits(_slice_by_gather(
            jnp.take(packed, ids // pack_k(d), axis=0), ids, d)))
    np.testing.assert_array_equal(
        bits(packed_pull(packed, ids, d)), bits(table[np.asarray(ids)]))


@pytest.mark.parametrize("origin", ["numpy", "uncommitted", "committed"])
@pytest.mark.parametrize("rows,d", [(1000, 17), (3, 17), (56, 17), (321, 64),
                                    (50, 100), (300, 1)])
def test_packing_in_one_jitted_program_equals_the_eager_packing(
        rows, d, origin, monkeypatch):
    """``_place`` packs a table that lies on one shard chunk by chunk inside
    one jitted program (the eager ops keep four buffers of the table's size
    alive): the same table as ``pack_table``'s, for whole chunks, a last
    chunk that overlaps the one before, a last physical row that is not
    full and the zero rows up to a multiple of 8."""
    monkeypatch.setattr(store_mod, "_PACK_CHUNK", 8)
    want = np.random.default_rng(rows).normal(size=(rows, d)).astype(np.float32)
    values = {
        "numpy": lambda: want,
        "uncommitted": lambda: jnp.asarray(want),
        "committed": lambda: jax.device_put(want, jax.devices()[-1]),
    }[origin]()
    spec = StoreSpec(capacity=rows, value_shape=(d,), layout="packed")
    table = ShardedParamStore._place(spec, values)
    assert table.shape == spec.table_shape()
    np.testing.assert_array_equal(
        np.asarray(table),
        np.asarray(pack_table(jnp.asarray(want), spec.rows_per_shard)))
    if origin == "committed":
        assert table.devices() == {jax.devices()[-1]}
    store = ShardedParamStore.from_spec_values(spec, jnp.asarray(values))
    np.testing.assert_array_equal(np.asarray(store.values()), want)


@pytest.mark.parametrize("origin", ["numpy", "uncommitted", "on_the_mesh"])
def test_packing_under_a_mesh_is_the_same_program(origin, mesh, monkeypatch):
    """A packed table under ``ps > 1`` is packed by ``_pack_rows`` too (no
    second, eager way to pack), then handed to the shards: rows padded to
    the shards' aligned blocks, the table ``ps``-sharded."""
    monkeypatch.setattr(store_mod, "_PACK_CHUNK", 8)
    rows, d = 1000, 17
    want = np.random.default_rng(7).normal(size=(rows, d)).astype(np.float32)
    spec = StoreSpec(capacity=rows, value_shape=(d,), layout="packed",
                     mesh=mesh)
    values = {
        "numpy": lambda: want,
        "uncommitted": lambda: jnp.asarray(want),
        "on_the_mesh": lambda: jax.device_put(
            want, jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec("ps", None))),
    }[origin]()
    table = ShardedParamStore._place(spec, values)
    assert table.shape == spec.table_shape()
    assert table.sharding == spec.sharding()
    np.testing.assert_array_equal(
        np.asarray(table),
        np.asarray(pack_table(
            jnp.asarray(want), spec.rows_per_shard * spec.num_shards)))
    np.testing.assert_array_equal(
        np.asarray(ShardedParamStore(spec, table).values()), want)


def test_lane_shift_scatter_equivalence():
    """scatter-add at phys granularity == logical scatter-add."""
    rng = np.random.default_rng(2)
    cap, d, n = 40, 17, 300
    k = pack_k(d)
    v = jnp.asarray(rng.normal(0, 1, (cap, d)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, cap, n).astype(np.int32))
    deltas = jnp.asarray(rng.normal(0, 1, (n, d)).astype(np.float32))
    packed = pack_table(v)
    shifted = lane_shift_deltas(deltas, ids, d)
    new_packed = packed.at[ids // k].add(shifted)
    out = unpack_table(new_packed, cap, d)
    ref = v.at[ids].add(deltas)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5)


def test_auto_layout_resolution():
    s = ShardedParamStore.create(10, (17,), layout="auto")
    assert s.spec.layout == "packed"
    s = ShardedParamStore.create(10, (256,), layout="auto")
    assert s.spec.layout == "dense"
    with pytest.raises(ValueError, match="packed"):
        ShardedParamStore.create(
            10, (17,), update=lambda c, d: c + 2 * d, layout="packed"
        )


def test_fm_store_packs_on_one_shard_and_stays_dense_under_ps(mesh_devices):
    """``make_store`` leaves the layout to the store, which reads the row
    width (17 < 128 lanes), the update rule and the shard count: packed
    with the table on one shard; dense under ``ps = 4``, said once and
    counted (``_place`` cannot yet pack a table larger than a chip)."""
    from flink_parameter_server_tpu.models import factorization_machine as fmm
    from flink_parameter_server_tpu.parallel.mesh import make_mesh

    cfg = fmm.FMConfig(num_features=100, dim=16)
    n0 = store_mod.packed_refusal_count()
    assert fmm.make_store(cfg).spec.layout == "packed"
    one_shard = make_mesh(4, 1, devices=mesh_devices[:4])
    assert fmm.make_store(cfg, mesh=one_shard).spec.layout == "packed"
    assert fmm.make_store(cfg, layout="dense").spec.layout == "dense"
    assert store_mod.packed_refusal_count() == n0
    ps4 = make_mesh(1, 4, devices=mesh_devices[:4])
    with pytest.warns(RuntimeWarning, match="sharded over ps=4.*dense") as w:
        store = fmm.make_store(cfg, mesh=ps4)
    assert len(w) == 1
    assert store.spec.layout == "dense"
    assert store_mod.packed_refusal_count() == n0 + 1
    # where an operator reads it: the driver's gauges
    from flink_parameter_server_tpu.telemetry.registry import MetricsRegistry
    from flink_parameter_server_tpu.training.driver import StreamingDriver

    seen = {}
    for name, st in (("one", fmm.make_store(cfg)), ("ps4", store)):
        reg = MetricsRegistry()
        StreamingDriver(fmm.FactorizationMachine(cfg), st, registry=reg)
        seen[name] = {k: v[0]["value"] for k, v in reg.snapshot().items()
                      if k.startswith("store_")}
    assert seen["one"] == {"store_layout_packed": 1.0,
                           "store_packed_refusals": n0 + 1}
    assert seen["ps4"] == {"store_layout_packed": 0.0,
                           "store_packed_refusals": n0 + 1}
    # a pinned layout asks no question, so it is refused nothing
    assert fmm.make_store(cfg, mesh=ps4, layout="packed").spec.layout == "packed"
    assert fmm.make_store(cfg, mesh=ps4, layout="dense").spec.layout == "dense"
    assert store_mod.packed_refusal_count() == n0 + 1


def test_packed_checkpoint_roundtrip(tmp_path):
    from flink_parameter_server_tpu.training import checkpoint as ckpt

    rng = np.random.default_rng(5)
    cap, d = 30, 17
    store = ShardedParamStore.create(
        cap, (d,), init_fn=_rand_init(d), layout="packed"
    )
    store = store.push(
        jnp.asarray([1, 5, 29], jnp.int32),
        jnp.asarray(rng.normal(0, 1, (3, d)).astype(np.float32)),
    )
    path = str(tmp_path / "ck")
    ckpt.save(path, store, worker_state=None, step=3)
    restored, _, meta = ckpt.restore(path, store.spec)
    assert restored.spec.layout == "packed"
    np.testing.assert_allclose(
        np.asarray(restored.values()), np.asarray(store.values()), rtol=1e-6
    )
