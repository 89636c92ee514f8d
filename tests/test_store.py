"""ShardedParamStore unit tests: pull/push semantics, sharding, init.

Mirrors the reference's server-side semantics (SimplePSLogic:
getOrElseUpdate + user update fn — SURVEY.md §2 #3) at microbatch
granularity.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_parameter_server_tpu.core.batched import PushRequest
from flink_parameter_server_tpu.core.store import ShardedParamStore
from flink_parameter_server_tpu.core.transform import make_train_step
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


# -- push then pull, one case table ------------------------------------------
# What every change to ``core/store.push`` / ``pull`` is held to: the store
# against a float64 ``np.add.at`` on the same values, over both layouts, the
# row widths the models use (PA / sketch scalars, FM's 17, MF's 64 and 128,
# 100 = padded not packed) and the traffic that has broken a scatter before.

CAP = 61  # no multiple of 8, of a pack factor or of a shard count
WIDTHS = [1, 4, 17, 64, 100, 128]
TRAFFIC = [
    "uniform", "zipf_hot", "one_row", "half_masked", "neg_and_oob",
    "hot_run_over_512", "ids_2d_lane_mask", "dead_lanes_minus_one",
]


def _init_values(cap, shape, dtype=np.float32):
    base = (np.arange(cap)[:, None] * 31 + np.arange(int(np.prod(shape)) or 1)
            * 7) % 13
    return ((base - 6.0) / 10.0).astype(dtype).reshape((cap,) + shape)


def _traffic(kind, rng, cap, shape):
    """(ids, deltas, mask or None) for one push."""
    n = 96
    mask = None
    if kind == "uniform":
        ids = rng.integers(0, cap, n)
    elif kind == "zipf_hot":
        ids = (rng.zipf(1.2, n) - 1) % cap
        ids[rng.permutation(n)[: n // 8]] = 7  # one id on an eighth of the lanes
    elif kind == "one_row":
        ids = np.full(n, cap - 1)
    elif kind == "half_masked":
        ids = rng.integers(0, cap, n)
        mask = np.arange(n) % 2 == 0
    elif kind == "neg_and_oob":
        ids = rng.integers(-5, cap + 5, n)
        ids[:4] = [-1, cap, -(2 ** 31), 2 ** 31 - 1]
    elif kind == "hot_run_over_512":
        n = 640
        ids = rng.integers(0, cap, n)
        ids[40:600] = 3  # 560 lanes in a row
    elif kind == "ids_2d_lane_mask":  # FM's and PA's (B, K) pulls
        ids = rng.integers(0, cap, (24, 4))
        mask = rng.random((24, 4)) > 0.3
    elif kind == "dead_lanes_minus_one":
        # fastText's ragged bags: a dead lane carries id -1 and NO mask
        # covers it; the push drops it whatever its delta holds, and so it
        # does an id past the table
        ids = rng.integers(0, cap, (24, 8))
        ids[rng.random((24, 8)) < 0.46] = -1
        ids[3, :3] = [cap, cap + 100, 2 ** 31 - 1]
    else:
        raise AssertionError(kind)
    ids = np.asarray(ids, np.int32)
    deltas = rng.normal(0, 1, ids.shape + shape).astype(np.float32)
    if kind == "dead_lanes_minus_one":
        deltas[(ids < 0) | (ids >= cap)] = np.nan
    return ids, deltas, mask


def _reference(values, ids, deltas, mask):
    """(want, magnitude) in float64: ``np.add.at`` over the lanes the store
    keeps (mask true, 0 <= id < capacity)."""
    cap = values.shape[0]
    flat = ids.reshape(-1)
    keep = (flat >= 0) & (flat < cap)
    if mask is not None:
        keep &= mask.reshape(-1)
    d = deltas.reshape((flat.size,) + values.shape[1:]).astype(np.float64)
    want, mag = values.astype(np.float64), np.abs(values).astype(np.float64)
    np.add.at(want, flat[keep], d[keep])
    np.add.at(mag, flat[keep], np.abs(d[keep]))
    return want, mag


# one program a spec and a batch shape, shared by the cases that have both
_push = jax.jit(lambda store, ids, deltas, mask: store.push(ids, deltas, mask))
_pull = jax.jit(lambda store, ids: store.pull(ids))


def _check_push_pull(store, values, ids, deltas, mask, ulps=64.0, push=None):
    eps = float(jnp.finfo(store.spec.dtype).eps)
    pushed = (push or _push)(
        store, jnp.asarray(ids), jnp.asarray(deltas),
        None if mask is None else jnp.asarray(mask),
    )
    got = np.asarray(pushed.values()).astype(np.float64)
    want, mag = _reference(values, ids, deltas, mask)
    off = np.abs(got - want)
    # rounding of the sums only; a lost or doubled delta is ~1/n of `mag`
    assert (off <= ulps * eps * mag + 1e-30).all(), float(
        (off / (eps * mag + 1e-30)).max()
    )
    # the padding rows took nothing
    assert pushed.table.shape == store.table.shape
    # pull: the rows as they now stand; a negative id is clipped to row 0,
    # one past the capacity to a padding row (callers mask those lanes)
    pulled = np.asarray(_pull(pushed, jnp.asarray(ids)))
    assert pulled.shape == ids.shape + values.shape[1:]
    below = ids < values.shape[0]
    rows = np.asarray(pushed.values())[np.clip(ids, 0, values.shape[0] - 1)]
    np.testing.assert_array_equal(pulled[below], rows[below])


@pytest.mark.parametrize("traffic", TRAFFIC)
@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("layout", ["dense", "packed"])
def test_push_pull_case_table(layout, width, traffic):
    shape = () if width == 1 and layout == "dense" else (width,)
    rng = np.random.default_rng([width, TRAFFIC.index(traffic)])
    values = _init_values(CAP, shape)
    store = ShardedParamStore.from_values(jnp.asarray(values), layout=layout)
    assert store.spec.layout == layout
    if layout == "packed":
        assert store.table.shape[1] % 128 == 0
    _check_push_pull(store, values, *_traffic(traffic, rng, CAP, shape))


# Rows of several 128-lane registers: on a TPU (no mesh, float32) ``push``
# takes ``ops/row_update``'s tile kernel for them, everywhere else XLA's
# scatter-add.  Off the TPU the chooser is steered in the test and the kernel
# is interpreted; both arms are held to the same reference.
# Rows of ONE register (a dense 128-lane row, 64-lane rows two to a physical
# row) take the same arm where the batch is short against the table
# (``core/store.arms``' ``push``: the rule's edges are
# ``tests/test_row_update.py``'s); steered here, they are two more rows of the
# case table.
WIDE_ROWS = [(256,), (300,), (640,), (2, 300), (600,), (128,), (64,)]
WIDE_TRAFFIC = TRAFFIC + ["run_over_a_block_and_a_call", "tile_of_8_over_a_call"]


def _wide_traffic(kind, rng, cap, shape):
    if kind == "run_over_a_block_and_a_call":
        n = 1100  # no multiple of the kernel's block
        ids = rng.integers(0, cap, n)
        ids[100:900] = 3  # 800 lanes in a row: blocks of 256, calls of 512
        ids = rng.permutation(ids)
    elif kind == "tile_of_8_over_a_call":
        # rows 8..15 are one tile of the table; their lanes lie round the
        # 512th sorted lane, so two calls read and write that tile
        ids = np.concatenate([
            np.zeros(500, np.int64), np.repeat(np.arange(8, 16), 3),
            rng.integers(16, cap, 600),
        ])
        ids = rng.permutation(ids)
    else:
        return _traffic(kind, rng, cap, shape)
    ids = np.asarray(ids, np.int32)
    deltas = rng.normal(0, 1, ids.shape + shape).astype(np.float32)
    return ids, deltas, rng.random(ids.shape) > 0.1


@pytest.mark.parametrize("traffic", WIDE_TRAFFIC)
@pytest.mark.parametrize("shape", WIDE_ROWS, ids=str)
@pytest.mark.parametrize("arm", ["xla", "tile_kernel"])
def test_push_pull_case_table_wide_rows(arm, shape, traffic, monkeypatch,
        steer_arms):
    from flink_parameter_server_tpu.core import store as store_mod
    from flink_parameter_server_tpu.ops import row_update

    rng = np.random.default_rng([len(shape), shape[-1],
                                 WIDE_TRAFFIC.index(traffic)])
    values = _init_values(CAP, shape)
    store = ShardedParamStore.from_values(jnp.asarray(values), layout="auto")
    # one axis of whole registers stays as it is; every other row is held
    # flat, padded to whole registers
    whole = len(shape) == 1 and shape[0] % 128 == 0
    assert store.spec.layout == ("dense" if whole else "packed")
    assert store.table.shape == (
        64 // store.spec.pack, -(-int(np.prod(shape)) // 128) * 128)
    push = _push
    if arm == "tile_kernel":
        assert store_mod.arms(store.spec).push == "xla_add"  # this is a CPU
        steer_arms(push="tile_add")
        monkeypatch.setattr(row_update, "MAX_LANES", 512)
        calls, handed = [], set()
        real = row_update._sorted_tile_add_counted

        def noted(*a):  # (table, sorted ids, rows, ...)
            calls.append(a[1].shape[0])
            handed.add(a[2].shape[1])
            return real(*a)

        monkeypatch.setattr(row_update, "_sorted_tile_add_counted", noted)
        # not `_push`: a program traced for the other arm would be reused
        push = jax.jit(lambda st, i, d, m: st.push(i, d, m))
    _check_push_pull(
        store, values, *_wide_traffic(traffic, rng, CAP, shape), push=push)
    if arm == "tile_kernel":
        assert calls and max(calls) <= 512, calls
        # a row that lies one to a physical row (cell 5's (2, 300) and 600 in
        # 640, cell 7's 300 in 384) reaches the kernel at its OWN width: no
        # pass pads the batch to whole registers for it
        flat = int(np.prod(shape))
        assert handed == {flat if store.spec.pack == 1 else 128}, handed


# The one-register arm against XLA's, BIT FOR BIT: what ``correct`` rests on
# in cell 10 (the benchmark's reference adds a row's deltas one by one, in the
# order of the batch) is that the kernel's sort is stable and its adds single.
ONE_REGISTER_CAP = 1001  # odd: the last physical row of a pack-2 store is half
ONE_REGISTER_TRAFFIC = [
    "hot_row_thousands", "both_halves_of_a_physical_row", "dead_and_masked",
    "nan_inf_negative_zero", "over_max_lanes_a_tile_row_across_two_calls",
]


def _one_register_traffic(kind, rng, cap, width):
    n, mask = 700, None
    ids = rng.integers(0, cap, n)
    special = None
    if kind == "hot_row_thousands":
        n = 4000
        ids = rng.integers(0, cap, n)
        ids[rng.permutation(n)[:3000]] = 501  # 3,000 deltas on one row
    elif kind == "both_halves_of_a_physical_row":
        # logical rows 2r and 2r + 1 share physical row r at two to a row;
        # at one to a row they are neighbours in one tile row
        ids = rng.choice(np.arange(40, 56), n)
    elif kind == "dead_and_masked":
        ids[rng.random(n) < 0.3] = -1
        ids[:3] = [cap + 20, cap + 1000, 2 ** 31 - 1]  # past the padding too
        mask = rng.random(n) > 0.25
    elif kind == "nan_inf_negative_zero":
        special = np.array([np.nan, np.inf, -np.inf, -0.0, 0.0], np.float32)
    elif kind == "over_max_lanes_a_tile_row_across_two_calls":
        # calls of 512 sorted lanes: rows 16-31 (one tile row dense, one or
        # two packed) lie round the 512th sorted lane; a hot row spans calls
        ids = np.concatenate([
            np.zeros(490, np.int64), np.repeat(np.arange(16, 32), 4),
            np.full(700, 700), rng.integers(32, cap, 500),
        ])
        ids = rng.permutation(ids)
        n = ids.size
    else:
        raise AssertionError(kind)
    ids = np.asarray(ids, np.int32)
    deltas = rng.normal(0, 1, (ids.size, width)).astype(np.float32)
    if special is not None:
        at = rng.random(deltas.shape) < 0.02
        deltas[at] = rng.choice(special, int(at.sum()))
    if kind == "dead_and_masked":
        deltas[(ids < 0) | (ids >= cap)] = np.nan  # dropped unread
    return ids, deltas, mask


@pytest.mark.parametrize("traffic", ONE_REGISTER_TRAFFIC)
@pytest.mark.parametrize("width", [128, 64])
def test_one_register_push_through_the_tile_kernel_is_xlas_bit_for_bit(
        width, traffic, monkeypatch, steer_arms):
    from flink_parameter_server_tpu.core import store as store_mod
    from flink_parameter_server_tpu.ops import row_update

    cap = ONE_REGISTER_CAP
    rng = np.random.default_rng([width, ONE_REGISTER_TRAFFIC.index(traffic)])
    values = _init_values(cap, (width,))
    values[::7] = -0.0  # a masked lane adds +0.0 to it on both arms
    store = ShardedParamStore.from_values(jnp.asarray(values), layout="auto")
    k = 128 // width
    assert store.spec.pack == k and store.table.shape == (-(-cap // k // 8) * 8, 128)
    ids, deltas, mask = _one_register_traffic(traffic, rng, cap, width)
    args = (jnp.asarray(ids), jnp.asarray(deltas),
            None if mask is None else jnp.asarray(mask))
    want, counted = store_mod.push_counted(store.spec, store.table, *args)
    assert counted is None  # XLA's arm counts nothing
    steer_arms(push="tile_add")
    monkeypatch.setattr(row_update, "MAX_LANES", 512)
    got, counted = store_mod.push_counted(store.spec, store.table, *args)
    np.testing.assert_array_equal(
        np.asarray(got).view(np.uint32), np.asarray(want).view(np.uint32))
    # what the plan counted: the kept lanes, and the tile rows of each call
    kept = ids[(ids >= 0) & (ids < cap)]
    assert int(counted["ps_push_kernel_lanes"]) == kept.size
    dropped = 2 ** 30  # sorts last, opens nothing
    phys = np.sort(np.where((ids >= 0) & (ids < cap), ids // k, dropped))
    calls = -(-ids.size // 512)
    size = -(-ids.size // (calls * 256)) * 256
    tile_rows = sum(
        np.unique(part[part < dropped] // 8).size
        for part in (phys[lo:lo + size] for lo in range(0, ids.size, size)))
    assert int(counted["ps_push_tile_rows"]) == tile_rows
    if traffic == "over_max_lanes_a_tile_row_across_two_calls":
        assert calls == 4 and tile_rows > np.unique(kept // k // 8).size
    if traffic == "dead_and_masked":
        assert kept.size < ids.size and np.isfinite(np.asarray(got)).all()


# A RULE store's rows wider than a sort carries: on a TPU (no mesh, float32,
# at most 128 lanes) ``_push_rule`` sums each row's deltas along the SORTED
# lanes through ``ops/row_update``'s row kernel, everywhere else by one
# scatter-add in the order of the stream.  With the rule ``current +
# combined`` such a store is held to the reference every add store is held
# to.  Off the TPU the chooser is steered in the test and the kernel is
# interpreted, a call's lanes cut to 512 so that the long cases take several.
WIDE_RULE_TRAFFIC = TRAFFIC + ["run_over_a_block_and_a_call"]


def _add_rule(current, combined):
    return current + combined


def _rule_arms(spec):
    """``(combine, write_back)`` of the record ``core/store.arms`` reads."""
    from flink_parameter_server_tpu.core import store as store_mod

    arm = store_mod.arms(spec)
    return arm.combine, arm.write_back


@pytest.mark.parametrize("traffic", WIDE_RULE_TRAFFIC)
@pytest.mark.parametrize("shape", [(5,), (36,), (128,), (2, 9)], ids=str)
@pytest.mark.parametrize("arm", ["xla", "row_kernel"])
def test_push_pull_case_table_wide_rule_rows(arm, shape, traffic, monkeypatch,
        steer_arms):
    from flink_parameter_server_tpu.core import store as store_mod
    from flink_parameter_server_tpu.ops import row_update

    rng = np.random.default_rng([len(shape), shape[-1],
                                 WIDE_RULE_TRAFFIC.index(traffic)])
    values = _init_values(CAP, shape)
    store = ShardedParamStore.from_values(
        jnp.asarray(values), update=_add_rule)
    # (a row of 5 lanes is held at 8, the set kernel's tile: its sums go
    # through the wide arm all the same)
    assert store.spec.layout == "dense"
    assert store_mod.arms(store.spec).combine == "scatter_add"  # a CPU
    ids, deltas, mask = _wide_traffic(traffic, rng, CAP, shape)
    calls = []
    if arm == "row_kernel":
        steer_arms(combine="row_kernel")
        monkeypatch.setattr(row_update, "MAX_LANES", 512)
        real = row_update.sorted_run_sums
        monkeypatch.setattr(
            row_update, "sorted_run_sums",
            lambda *a, **kw: calls.append(a[1].shape[0]) or real(*a, **kw))
    # not `_push`: a program traced for the other arm would be reused
    push = jax.jit(lambda st, i, d, m: st.push(i, d, m))
    _check_push_pull(store, values, ids, deltas, mask, push=push)
    assert len(calls) == (arm == "row_kernel"), calls
    # what the push counted: the kernel's lanes are the batch's live lanes
    table, counted = store_mod.push_counted(
        store.spec, store.table, jnp.asarray(ids), jnp.asarray(deltas),
        None if mask is None else jnp.asarray(mask))
    live = (ids >= 0) & (ids < store.spec.padded_capacity)
    if mask is not None:
        live &= mask
    assert int(counted["ps_rule_keys"]) == live.sum()
    assert int(counted["ps_rule_rows"]) == len(np.unique(ids[live]))
    assert int(counted["ps_combine_kernel_lanes"]) == (
        live.sum() if arm == "row_kernel" else 0)


@pytest.mark.parametrize("why,shape,dtype,meshed,noted", [
    ("off_the_tpu", (36,), jnp.float32, False, False),
    ("a_sort_carries_the_row", (3,), jnp.float32, False, False),
    ("a_sort_carries_four_lanes", (4,), jnp.float32, False, False),
    ("under_a_mesh", (36,), jnp.float32, True, False),
    ("bfloat16", (36,), jnp.bfloat16, False, True),
    ("wider_than_a_register", (200,), jnp.float32, False, True),
    ("rank_2_over_a_register", (2, 100), jnp.float32, False, True),
])
def test_the_combine_kernel_arm_says_no(
        why, shape, dtype, meshed, noted, mesh, monkeypatch):
    """What keeps the scatter-add in stream order: the CPU; rows a sort
    carries (they never reach the wide arm); a mesh (the sums are GSPMD's);
    bfloat16 and rows over 128 lanes (noted and counted, as the other arms'
    refusals are).  Every other rule store on a TPU takes the kernel, rows
    of rank 2 flat; an add store has no combine."""
    from flink_parameter_server_tpu.core import store as store_mod
    from flink_parameter_server_tpu.ops import row_update

    def spec_of(shape, dtype, update=_add_rule, on=None):
        return jax.eval_shape(lambda: ShardedParamStore.create(
            1000, shape, dtype=dtype, update=update, mesh=on)).spec

    spec = spec_of(shape, dtype, on=mesh if meshed else None)
    monkeypatch.setattr(store_mod, "_REFUSALS_NOTED", set())
    n0 = row_update.refusal_count()
    kept = "sort" if int(np.prod(shape)) <= 4 else "scatter_add"
    with warnings.catch_warnings():
        # (the record is read whole: a mesh of two workers is refused the
        # push on its shards, on any backend, noted once as every refusal)
        warnings.simplefilter("ignore")
        assert store_mod.arms(spec).combine == kept  # this is a CPU
    assert row_update.refusal_count() == n0 + meshed
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if why == "off_the_tpu":
        for takes in (spec, spec_of((5,), dtype), spec_of((128,), dtype),
                      spec_of((2, 9), dtype)):
            assert store_mod.arms(takes).combine == "row_kernel"
        assert store_mod.arms(
            spec_of(shape, dtype, update="add")).combine == ""
        assert row_update.refusal_count() == n0
        return
    if noted:
        with pytest.warns(RuntimeWarning, match="sum of a rule's wide rows"):
            assert store_mod.arms(spec).combine == kept
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # and warns once
        assert store_mod.arms(spec).combine == kept
    assert row_update.refusal_count() == n0 + noted + meshed


# Rows packed several to a physical row: on a TPU a float32 ``pull`` of a
# block or more slices the rows it gathered in ``ops/packed``'s kernel, which
# hands them over feature-major; everywhere else in XLA's selects.  Off the
# TPU the chooser is steered in the test and the kernel is interpreted, at a
# block the case table's batches fill; both arms are held to the same rows.
@pytest.mark.parametrize("traffic", TRAFFIC)
@pytest.mark.parametrize("width", [1, 4, 17, 64])
@pytest.mark.parametrize("shards", ["one_shard", "dp_x_ps"])
def test_push_pull_case_table_slice_kernel_arm(
        shards, width, traffic, mesh, monkeypatch, steer_arms):
    from flink_parameter_server_tpu.core import store as store_mod
    from flink_parameter_server_tpu.ops import packed

    rng = np.random.default_rng([width, TRAFFIC.index(traffic)])
    values = _init_values(CAP, (width,))
    store = ShardedParamStore.from_values(
        jnp.asarray(values), layout="packed",
        mesh=mesh if shards == "dp_x_ps" else None)
    assert store.spec.pack == 128 // width
    assert store_mod.arms(
        store.spec, pull_lanes=4096).pull == "packed_selects"  # a CPU
    steer_arms(pull="packed_kernel")
    monkeypatch.setattr(packed, "SLICE_BLOCK", 32)
    calls = []
    real = packed.sub_row_slice_kernel
    monkeypatch.setattr(
        packed, "sub_row_slice_kernel",
        lambda rows, *a, **kw: calls.append(rows.shape) or real(rows, *a, **kw))
    ids, deltas, mask = _traffic(traffic, rng, CAP, (width,))
    pushed = _push(
        store, jnp.asarray(ids), jnp.asarray(deltas),
        None if mask is None else jnp.asarray(mask))
    # not `_pull`: a program traced for the other arm would be reused; and
    # the jitted pulls forget the case before, whose trace made its call
    packed.packed_pull.clear_cache()
    store_mod._packed_pull_on_shards.clear_cache()
    pulled = np.asarray(jax.jit(lambda st, i: st.pull(i))(
        pushed, jnp.asarray(ids)))
    # every shard slices its worker's half of the lanes, all of them alone
    lanes = ids.size // 2 if shards == "dp_x_ps" else ids.size
    assert calls == [(lanes, 128)], calls
    assert pulled.shape == ids.shape + (width,)
    rows = np.asarray(pushed.values())[np.clip(ids, 0, CAP - 1)]
    below = ids < CAP
    np.testing.assert_array_equal(
        pulled[below].view(np.uint32), rows[below].view(np.uint32))
    # and the select arm reads the same bits, clipped lanes included
    monkeypatch.undo()
    np.testing.assert_array_equal(
        pulled.view(np.uint32),
        np.asarray(pushed.pull(jnp.asarray(ids))).view(np.uint32))


@pytest.mark.parametrize("why,shape,dtype,mesh_shape,n,noted", [
    ("off_the_tpu", (17,), jnp.float32, None, 4096, False),
    ("bfloat16", (17,), jnp.bfloat16, None, 4096, True),
    ("k_is_1", (100,), jnp.float32, None, 4096, False),
    ("k_is_1_wide", (2, 300), jnp.float32, None, 4096, False),
    ("one_shard_mesh", (17,), jnp.float32, (4, 1), 4096, False),
    ("a_worker_s_lanes_under_a_block", (17,), jnp.float32, (2, 4), 4094, True),
])
def test_the_slice_arm_says_no(
        why, shape, dtype, mesh_shape, n, noted, mesh_devices, monkeypatch):
    """What keeps ``_sub_row_slice``: the CPU; bfloat16 rows (noted and
    counted, as the other arms' refusals are); rows that lie one to a
    physical row, which have nothing to select; a mesh that does not shard
    the table (the pull is GSPMD's); fewer ids a shard than a block.
    Everything else on a TPU takes the kernel."""
    from flink_parameter_server_tpu.core import store as store_mod
    from flink_parameter_server_tpu.ops import packed, row_update
    from flink_parameter_server_tpu.parallel.mesh import make_mesh

    mesh = None
    if mesh_shape is not None:
        mesh = make_mesh(*mesh_shape, devices=mesh_devices[
            :mesh_shape[0] * mesh_shape[1]])
    spec = jax.eval_shape(lambda: ShardedParamStore.create(
        1000, shape, dtype=dtype, layout="packed", mesh=mesh)).spec
    monkeypatch.setattr(store_mod, "_REFUSALS_NOTED", set())
    monkeypatch.setattr(packed, "SLICE_BLOCK", 2048)
    n0 = row_update.refusal_count()

    def sliced(n=None):
        return store_mod.arms(spec, pull_lanes=n).pull == "packed_kernel"

    if why != "off_the_tpu":
        assert not sliced(n)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if noted:
        with pytest.warns(RuntimeWarning, match="lane slice of a packed pull"):
            assert not sliced(n)
    assert not sliced(n)
    assert row_update.refusal_count() == n0 + noted
    # asking for the store as a whole (the preload) notes nothing
    sliced()
    assert row_update.refusal_count() == n0 + noted
    if why == "off_the_tpu":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert sliced(n)
        assert sliced()
        assert row_update.refusal_count() == n0
    if why == "a_worker_s_lanes_under_a_block":
        # 2 workers: 2047 lanes each of 4094; 2048 each of 4096; 4095 do not
        # split and every shard slices them all
        assert sliced(4096)
        assert sliced(4095)


# The push's mirror of that arm: on a TPU a float32 ``add`` push of a block or
# more of lanes into a store packed several rows to a physical row shifts its
# deltas to their windows in ``ops/packed``'s other kernel, which reads them
# feature-major and leaves a masked lane out itself; everywhere else in XLA's
# pads and selects.  Steered and interpreted here, at a block the case table's
# batches fill: the same table as the select arm's, bit for bit.
@pytest.mark.parametrize("traffic", TRAFFIC)
@pytest.mark.parametrize("width", [1, 4, 17, 64])
@pytest.mark.parametrize("shards", ["one_shard", "dp_x_ps"])
def test_push_pull_case_table_shift_kernel_arm(
        shards, width, traffic, mesh, monkeypatch, steer_arms):
    from flink_parameter_server_tpu.core import store as store_mod
    from flink_parameter_server_tpu.ops import packed

    rng = np.random.default_rng([width, TRAFFIC.index(traffic)])
    values = _init_values(CAP, (width,))
    store = ShardedParamStore.from_values(
        jnp.asarray(values), layout="packed",
        mesh=mesh if shards == "dp_x_ps" else None)
    ids, deltas, mask = _traffic(traffic, rng, CAP, (width,))
    args = (jnp.asarray(ids), jnp.asarray(deltas),
            None if mask is None else jnp.asarray(mask))
    # not `_push`: a program traced for one arm would be reused by the other
    selects = jax.jit(lambda st, i, d, m: st.push(i, d, m))(store, *args)
    assert store_mod.arms(
        store.spec, push_lanes=4096).shift == "selects"  # a CPU
    steer_arms(shift="kernel")
    monkeypatch.setattr(packed, "SLICE_BLOCK", 32)
    calls = []
    real = packed.lane_shift_kernel
    monkeypatch.setattr(
        packed, "lane_shift_kernel",
        lambda by_lane, ids, d, mask=None: calls.append(
            (by_lane.shape, mask is not None)) or real(by_lane, ids, d, mask))
    push = jax.jit(lambda st, i, d, m: st.push(i, d, m))
    _check_push_pull(store, values, ids, deltas, mask, push=push)
    # every chip shifts all the lanes, whatever the mesh; the mask rides
    # into the kernel where there is one
    assert calls == [((width, ids.size), mask is not None)], calls
    np.testing.assert_array_equal(
        np.asarray(push(store, *args).table).view(np.uint32),
        np.asarray(selects.table).view(np.uint32))


# A key block of two axes pulled TURNED (``pull(..., turned=True)``, what a
# step asks for a logic that ``pulls_turned``): the pull's rows with the
# block's axes swapped, in every arm; the arm ``packed_kernel_by_field`` writes
# them so (``ops/packed.turned_slice_kernel``: steered and interpreted here),
# the others turn what they gathered.  And the push's mirror, under the SAME
# declaration (``push_counted(..., turned=True)``): the shift
# ``kernel_by_field`` is handed the deltas ``(d, K, B)``.  Which batch takes
# the by-field forms is ``arms``' to say (``test_the_arms_table``); here the
# arm is steered to what ``arms`` reads on a TPU.
TURNED_STORES = [
    # what, row, update, layout, the arm a CPU reads, a kernel arm to steer to
    ("dense_add", (128,), "add", "dense", "take", False),
    ("narrow_rule", (3,), "rule", "auto", "narrow", False),
    ("packed_selects", (17,), "add", "packed", "packed_selects", False),
    ("packed_kernel", (17,), "add", "packed", "packed_selects", True),
    ("packed_rule_part", (36,), "rule", "packed", "packed_selects", True),
]


def _by_field(placed, examples, fields):
    """Whether a TPU's ``arms`` reads the by-field forms for a block of
    ``examples x fields`` (under ``dp`` a worker's half of the examples)."""
    from flink_parameter_server_tpu.ops import packed

    mine = examples // 2 if placed == "dp_x_ps" else examples
    return packed.by_field(fields, mine), (mine, fields)


@pytest.mark.parametrize("block", [(256, 5), (512, 39), (100, 3)], ids=str)
@pytest.mark.parametrize("placed", ["one_place", "ps_4", "dp_x_ps"])
@pytest.mark.parametrize("what", [row[0] for row in TURNED_STORES])
def test_a_turned_pull_is_the_pull_with_its_blocks_axes_swapped(
        what, placed, block, mesh, mesh_devices, monkeypatch, steer_arms):
    from flink_parameter_server_tpu.core import store as store_mod
    from flink_parameter_server_tpu.ops import packed
    from flink_parameter_server_tpu.parallel.mesh import make_mesh

    _, row, update, layout, cpu_arm, steered = next(
        r for r in TURNED_STORES if r[0] == what)
    cap = 1000
    on = {"one_place": None, "ps_4": make_mesh(1, 4, devices=mesh_devices[:4]),
          "dp_x_ps": mesh}[placed]
    rule = (lambda current, combined: current + combined[..., :1])
    part = 20 if row == (36,) else None
    store = ShardedParamStore.create(
        cap, row, init_fn=lambda ids: jnp.asarray(
            _init_values(cap, row))[ids],
        update="add" if update == "add" else rule, layout=layout, mesh=on,
        worker_width=part)
    rng = np.random.default_rng([len(what), block[0]])
    ids = rng.integers(-3, cap + 5, block).astype(np.int32)
    if cpu_arm == "narrow" and on is not None:
        cpu_arm = "take"  # (a narrow row keeps its tile in one place only)
    # off the TPU the declaration changes no arm
    assert store_mod.arms(
        store.spec, pull_lanes=ids.size, fields=block[1]).pull == cpu_arm
    calls = []
    fielded, lanes = _by_field(placed, *block)
    if steered:
        steer_arms(
            pull="packed_kernel_by_field" if fielded else "packed_kernel")
        monkeypatch.setattr(packed, "SLICE_BLOCK", 32)
        real = packed.turned_slice_kernel
        monkeypatch.setattr(
            packed, "turned_slice_kernel",
            lambda rows, ids, *a, **kw: calls.append(ids.shape) or real(
                rows, ids, *a, **kw))
        packed.packed_pull.clear_cache()
        store_mod._packed_pull_on_shards.clear_cache()

    def pull(turned):
        return np.asarray(jax.jit(lambda table, i: store_mod.pull(
            store.spec, table, i, worker_part=True, turned=turned))(
                store.table, jnp.asarray(ids)))

    got = pull(True)
    assert got.shape == block[::-1] + ((part,) if part else row)
    # the by-field arm hands the kernel each shard's block; no other does
    assert calls == ([lanes] if steered and fielded else []), calls
    want = np.swapaxes(pull(False), 0, 1)
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
    if steered:  # and the arm the CPU reads gives the same bits
        monkeypatch.undo()
        packed.packed_pull.clear_cache()
        store_mod._packed_pull_on_shards.clear_cache()
        np.testing.assert_array_equal(
            got.view(np.uint32), pull(True).view(np.uint32))


@pytest.mark.parametrize("masked", [False, True], ids=["all_live", "masked"])
@pytest.mark.parametrize("block", [(5, 256), (39, 512), (3, 100)], ids=str)
@pytest.mark.parametrize("placed", ["one_place", "ps_4", "dp_x_ps"])
def test_a_turned_push_goes_to_the_shift_kernel_a_field_at_a_time(
        placed, block, masked, mesh, mesh_devices, monkeypatch, steer_arms):
    """The table after a push of ``(K, B)`` ids and ``(K, B, d)`` deltas is
    the table after the same lanes pushed flat, bit for bit, in the select
    arm and in both of the kernel's; ``kernel_by_field`` is handed ``(d, K,
    B)``, ``kernel`` the flat ``(d, K B)``."""
    from flink_parameter_server_tpu.core import store as store_mod
    from flink_parameter_server_tpu.ops import packed
    from flink_parameter_server_tpu.parallel.mesh import make_mesh

    width, cap = 17, 1000
    on = {"one_place": None, "ps_4": make_mesh(1, 4, devices=mesh_devices[:4]),
          "dp_x_ps": mesh}[placed]
    values = _init_values(cap, (width,))
    store = ShardedParamStore.from_values(
        jnp.asarray(values), layout="packed", mesh=on)
    rng = np.random.default_rng([block[0], masked])
    ids = jnp.asarray(rng.integers(-3, cap + 5, block).astype(np.int32))
    deltas = jnp.asarray(rng.normal(size=block + (width,)).astype(np.float32))
    mask = jnp.asarray(rng.random(block) < 0.7) if masked else None

    def push(*args, turned=False):  # (a fresh function a call: no reuse)
        return np.asarray(jax.jit(
            lambda table, i, d, m: store_mod.push_counted(
                store.spec, table, i, d, m, turned=turned)[0])(
                    store.table, *args))

    flat = (ids.reshape(-1), deltas.reshape(-1, width),
            None if mask is None else mask.reshape(-1))
    want = push(*flat)
    np.testing.assert_array_equal(
        push(ids, deltas, mask, turned=True).view(np.uint32),
        want.view(np.uint32))
    fielded = packed.by_field(*block)
    steer_arms(shift="kernel_by_field" if fielded else "kernel")
    monkeypatch.setattr(packed, "SLICE_BLOCK", 32)
    calls = []
    real = packed.lane_shift_kernel
    monkeypatch.setattr(
        packed, "lane_shift_kernel",
        lambda by_lane, ids, d, mask=None: calls.append(
            (by_lane.shape, ids.shape)) or real(by_lane, ids, d, mask))
    got = push(ids, deltas, mask, turned=True)
    assert calls == [
        ((width,) + block, block) if fielded
        else ((width, ids.size), (ids.size,))], calls
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))


def test_a_turned_pull_or_push_of_a_flat_batch_is_refused():
    """``turned`` declares a block of two axes; a flat batch under it is a
    caller's mistake and says so, on both sides."""
    from flink_parameter_server_tpu.core import store as store_mod

    store = ShardedParamStore.create(100, (17,), layout="packed")
    ids = jnp.arange(8, dtype=jnp.int32)
    with pytest.raises(ValueError, match=r"turned pull .* two axes"):
        store_mod.pull(store.spec, store.table, ids, turned=True)
    with pytest.raises(ValueError, match=r"turned push .* two axes"):
        store_mod.push_counted(
            store.spec, store.table, ids, jnp.zeros((8, 17)), turned=True)


@pytest.mark.parametrize("why,update,shape,dtype,mesh_shape,n,noted", [
    ("off_the_tpu", "add", (17,), jnp.float32, None, 4096, False),
    ("bfloat16", "add", (17,), jnp.bfloat16, None, 4096, True),
    ("pack_is_1", "add", (100,), jnp.float32, None, 4096, False),
    ("a_rule_store", "rule", (36,), jnp.float32, None, 4096, False),
    ("under_a_block", "add", (17,), jnp.float32, None, 2047, True),
    ("one_shard_mesh", "add", (17,), jnp.float32, (4, 1), 4096, False),
])
def test_the_shift_arm_says_no(
        why, update, shape, dtype, mesh_shape, n, noted, mesh_devices,
        monkeypatch):
    """What keeps ``lane_shift_deltas``' selects: the CPU; bfloat16 rows and
    a push of under a block of lanes (noted and counted, as the slice's
    refusals are); rows that lie one to a physical row, which have nothing
    to shift; a store whose update is a rule (its write-back shifts and
    merges a chunk at a time: ``_rewrite_packed``), with no note; a mesh
    that does not shard the table."""
    from flink_parameter_server_tpu.core import store as store_mod
    from flink_parameter_server_tpu.ops import packed, row_update
    from flink_parameter_server_tpu.parallel.mesh import make_mesh

    mesh = None
    if mesh_shape is not None:
        mesh = make_mesh(*mesh_shape, devices=mesh_devices[
            :mesh_shape[0] * mesh_shape[1]])
    rule = "add" if update == "add" else (lambda row, delta: row + delta)
    spec = jax.eval_shape(lambda: ShardedParamStore.create(
        1000, shape, dtype=dtype, layout="packed", mesh=mesh,
        update=rule)).spec
    monkeypatch.setattr(store_mod, "_REFUSALS_NOTED", set())
    monkeypatch.setattr(packed, "SLICE_BLOCK", 2048)
    n0 = row_update.refusal_count()

    def shifted(n):
        return store_mod.arms(spec, push_lanes=n).shift == "kernel"

    if why != "off_the_tpu":
        assert not shifted(n)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if noted:
        with pytest.warns(RuntimeWarning, match="lane shift of a packed push"):
            assert not shifted(n)
    assert not shifted(n)
    assert row_update.refusal_count() == n0 + noted
    if why == "off_the_tpu":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert shifted(n)
        assert row_update.refusal_count() == n0
    if why == "a_rule_store":  # its PULL takes the slice kernel all the same
        assert store_mod.arms(spec, push_lanes=n).shift == ""
        assert store_mod.arms(spec, pull_lanes=n).pull == "packed_kernel"


@pytest.mark.parametrize("cell", ["cell_2", "cell_4", "cell_10"])
def test_the_shift_arm_takes_the_criteo_add_cells(
        cell, mesh_devices, monkeypatch):
    """The three benchmark cells whose store is an ``add`` store packed
    several rows to a physical row, at their own sizes (nothing allocated):
    on a TPU each pushes its whole batch through the kernel, and a step of
    theirs says so among its outputs; off it none does."""
    from flink_parameter_server_tpu.core import store as store_mod
    from flink_parameter_server_tpu.models import dlrm
    from flink_parameter_server_tpu.models import factorization_machine as fmm
    from flink_parameter_server_tpu.parallel.mesh import make_mesh

    if cell == "cell_10":
        from chipbench import spec as bench_spec

        cfg = bench_spec.load_json("chipbench/configs/dlrm-criteo-10m.json")
        model = dlrm.DLRMConfig(tuple(cfg["field_cardinalities"]))
        spec = jax.eval_shape(
            lambda: dlrm.make_store(model, dtype=jnp.float32)).spec
        lanes, pack = 32_768 * 26, 2
    else:
        rows, mesh = 49_126_310, None
        if cell == "cell_4":
            rows, mesh = 187_767_412, make_mesh(1, 4, devices=mesh_devices[:4])
        spec = jax.eval_shape(lambda: fmm.make_store(
            fmm.FMConfig(num_features=rows, dim=16), mesh=mesh,
            dtype=jnp.float32)).spec
        lanes, pack = 32_768 * 39, 7
    assert (spec.layout, spec.pack, spec.update) == ("packed", pack, "add")
    assert store_mod.arms(spec, push_lanes=lanes).shift == "selects"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert store_mod.arms(spec, push_lanes=lanes).shift == "kernel"


@pytest.mark.parametrize("layout", ["dense", "packed"])
def test_push_pull_case_table_int32_exact_past_2_24(layout):
    """Counts are summed as integers: a float32 detour drops increments
    past 2**24."""
    values = np.full((CAP, 4), 2 ** 24, np.int32)
    store = ShardedParamStore.from_values(jnp.asarray(values), layout=layout)
    ids = np.asarray(np.arange(640) % 5, np.int32)
    pushed = store.push(jnp.asarray(ids), jnp.ones((640, 4), jnp.int32))
    want = values.copy()
    np.add.at(want, ids, 1)
    np.testing.assert_array_equal(np.asarray(pushed.values()), want)
    np.testing.assert_array_equal(
        np.asarray(pushed.pull(jnp.asarray(ids[:7]))), want[ids[:7]]
    )


@pytest.mark.parametrize("layout,width", [("dense", 128), ("packed", 64)])
def test_push_pull_case_table_bfloat16(layout, width):
    """bfloat16 tables (half the gather and scatter bytes): XLA rounds every
    add, so a row's error grows with its duplicates; few of them here."""
    rng = np.random.default_rng(width)
    values = np.asarray(
        jnp.asarray(_init_values(CAP, (width,)), jnp.bfloat16), np.float32)
    store = ShardedParamStore.from_values(
        jnp.asarray(values, jnp.bfloat16), layout=layout)
    ids, deltas, mask = _traffic("half_masked", rng, CAP, (width,))
    deltas = np.asarray(jnp.asarray(deltas, jnp.bfloat16), np.float32)
    _check_push_pull(store, values, ids, deltas, mask, ulps=4.0)


@pytest.mark.parametrize("width", [17, 128])
@pytest.mark.parametrize("layout", ["dense", "packed"])
def test_push_pull_case_table_under_a_dp_x_ps_mesh(layout, width, mesh):
    """The same table row-sharded over ``ps = 4`` (``dp = 2`` beside it):
    GSPMD partitions the one gather and the one scatter-add."""
    rng = np.random.default_rng(width)
    values = _init_values(CAP, (width,))
    store = ShardedParamStore.from_values(
        jnp.asarray(values), layout=layout, mesh=mesh)
    assert store.spec.num_shards == 4 and store.spec.layout == layout
    ids, deltas, _ = _traffic("zipf_hot", rng, CAP, (width,))
    ids[:6] = [-1, CAP, CAP + 40, 0, CAP - 1, 7]
    mask = rng.random(ids.shape) > 0.2
    _check_push_pull(store, values, ids, deltas, mask)
    pushed = store.push(jnp.asarray(ids), jnp.asarray(deltas))
    assert pushed.table.sharding == store.spec.sharding()


def _mf_case(rng, n):
    from flink_parameter_server_tpu.models.matrix_factorization import (
        OnlineMatrixFactorization,
        SGDUpdater,
    )

    logic = OnlineMatrixFactorization(40, 8, updater=SGDUpdater(0.05))
    store = ShardedParamStore.from_values(
        jnp.asarray(_init_values(CAP, (8,))))
    batch = {
        "user": rng.integers(0, 40, n).astype(np.int32),
        "item": ((rng.zipf(1.2, n) - 1) % CAP).astype(np.int32),
        "rating": rng.normal(0, 1, n).astype(np.float32),
        "mask": rng.random(n) > 0.1,
    }
    return logic, store, batch


def _fm_case(rng, n):
    from flink_parameter_server_tpu.models import factorization_machine as fm

    cfg = fm.FMConfig(num_features=CAP, dim=16)
    store = fm.make_store(cfg, init_stddev=0.1)
    assert store.spec.layout == "packed"
    batch = {
        "ids": rng.integers(0, CAP, (n, 5)).astype(np.int32),
        "values": rng.random((n, 5)).astype(np.float32),
        "feat_mask": rng.random((n, 5)) > 0.2,
        "label": (rng.integers(0, 2, n) * 2 - 1).astype(np.float32),
        "mask": rng.random(n) > 0.1,
    }
    return fm.FactorizationMachine(cfg), store, batch


@pytest.mark.parametrize("case", [_mf_case, _fm_case], ids=["mf", "fm"])
def test_train_step_outputs_are_in_stream_order(case):
    """Record ``i``'s output is at position ``i``: a batch handed over in
    another order gives the same outputs in that order (every record reads
    the rows as they stood before the step) and, up to the order of the
    sums, the same table."""
    rng = np.random.default_rng(5)
    n = 64
    logic, store, batch = case(rng, n)
    perm = rng.permutation(n)
    step = jax.jit(make_train_step(logic, store.spec))
    state = logic.init_state(jax.random.PRNGKey(0))
    table_a, _, out_a = step(store.table, state, batch)
    table_b, _, out_b = step(
        store.table, state, {k: v[perm] for k, v in batch.items()})
    assert set(out_a) >= {"prediction"}
    # a record's outputs; the step's own scalars (`ps_*`) are the same
    per_record = [name for name in out_a if not name.startswith("ps_")]
    for name in set(out_a) - set(per_record):
        assert out_a[name].shape == () and out_a[name] == out_b[name]
    for name in per_record:
        assert out_a[name].shape[0] == n
        np.testing.assert_allclose(
            np.asarray(out_a[name])[perm], np.asarray(out_b[name]),
            rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(
        np.asarray(table_a), np.asarray(table_b), rtol=1e-5, atol=1e-6)


def test_topk_exact_dense_matches_sharded(mesh):
    """The exact serving path agrees between the dense and ps-sharded
    stores."""
    from flink_parameter_server_tpu.models.topk_recommender import query_topk

    rng = np.random.default_rng(9)
    items, d, k = 512, 32, 10
    vals = rng.normal(size=(items, d)).astype(np.float32)
    store = ShardedParamStore.from_values(jnp.asarray(vals))
    sharded = ShardedParamStore.from_values(jnp.asarray(vals), mesh=mesh)
    vecs = jnp.asarray(rng.normal(size=(8, d)), jnp.float32)
    uids = jnp.arange(8, dtype=jnp.int32)
    s_ex, i_ex = query_topk(store, vecs, uids, k)
    s_sh, i_sh = query_topk(sharded, vecs, uids, k)
    np.testing.assert_array_equal(np.asarray(i_ex), np.asarray(i_sh))
    np.testing.assert_allclose(
        np.asarray(s_ex), np.asarray(s_sh), atol=1e-5
    )


# -- wide rows: which layout "auto" resolves, and that it holds ---------------
# A row of 128 lanes or more that has two axes or is no multiple of 128 lies
# ONE to a physical row, flat and zero-padded to whole 128-lane registers
# (the packed layout with a pack factor of 1); a row that is one axis of whole
# registers stays as it is.  word2vec's (2, 300), its flat (600,), one of its
# vectors (300,), and MF's (128,).
WIDE = [((2, 300), "packed", 640), ((600,), "packed", 640),
        ((300,), "packed", 384), ((2, 64), "packed", 128),
        ((128,), "dense", 128), ((256,), "dense", 256)]


@pytest.mark.parametrize("shape, layout, lanes", WIDE, ids=[str(w[0]) for w in WIDE])
def test_auto_layout_of_wide_rows_agrees_with_numpy(shape, layout, lanes):
    rng = np.random.default_rng(lanes + len(shape))
    values = rng.normal(0, 1, (CAP,) + shape).astype(np.float32)
    store = ShardedParamStore.from_values(jnp.asarray(values), layout="auto")
    assert store.spec.layout == layout and store.spec.pack == 1
    assert store.spec.value_shape == shape
    want_table = (64, lanes) if layout == "packed" else (64,) + shape
    assert store.table.shape == want_table == store.spec.table_shape()
    # the round trip is bit-equal, and so is a pull of every row
    np.testing.assert_array_equal(np.asarray(store.values()), values)
    np.testing.assert_array_equal(
        np.asarray(store.pull(jnp.arange(CAP))), values
    )
    for traffic in ("zipf_hot", "neg_and_oob", "ids_2d_lane_mask"):
        _check_push_pull(store, values, *_traffic(traffic, rng, CAP, shape))
    # `create` initialises the same rows (in blocks, in place) as `from_values`
    # places, padding rows and lanes zero
    created = ShardedParamStore.create(
        CAP, shape, layout="auto",
        init_fn=lambda ids: jnp.asarray(values)[jnp.clip(ids, 0, CAP - 1)]
        * (ids < CAP).reshape((-1,) + (1,) * len(shape)),
    )
    assert created.spec == store.spec
    np.testing.assert_array_equal(
        np.asarray(created.table), np.asarray(store.table)
    )
    if layout == "packed":
        width = int(np.prod(shape))
        assert (np.asarray(store.table)[:, width:] == 0).all()
        # a pinned "dense" keeps the rows as they are
        pinned = ShardedParamStore.from_values(jnp.asarray(values), layout="dense")
        assert pinned.table.shape == (64,) + shape


def test_a_wide_rank_2_row_under_a_ps_mesh(mesh):
    rng = np.random.default_rng(5)
    shape = (2, 300)
    values = rng.normal(0, 1, (CAP,) + shape).astype(np.float32)
    store = ShardedParamStore.from_values(
        jnp.asarray(values), layout="auto", mesh=mesh)
    assert store.spec.layout == "packed" and store.spec.num_shards == 4
    assert store.table.shape == (64, 640)
    assert store.table.sharding == store.spec.sharding()
    np.testing.assert_array_equal(np.asarray(store.values()), values)
    ids, deltas, _ = _traffic("zipf_hot", rng, CAP, shape)
    _check_push_pull(store, values, ids, deltas, rng.random(ids.shape) > 0.2)


def test_auto_leaves_a_128_lane_store_and_its_step_as_they_were():
    """Cell 1's program must not move: ``"auto"`` on one axis of whole
    registers is the dense layout, spec for spec and step text for text."""
    from flink_parameter_server_tpu.models.matrix_factorization import (
        OnlineMatrixFactorization,
        SGDUpdater,
    )

    _, _, batch = _mf_case(np.random.default_rng(0), 64)
    logic = OnlineMatrixFactorization(40, 128, updater=SGDUpdater(0.05))
    values = jnp.asarray(_init_values(CAP, (128,)))
    texts = []
    for layout in ("dense", "auto"):
        store = ShardedParamStore.from_values(values, layout=layout)
        state = logic.init_state(jax.random.PRNGKey(0))
        texts.append(jax.jit(make_train_step(logic, store.spec)).lower(
            store.table, state, batch
        ).as_text())
        assert store.spec.layout == "dense"
    assert texts[0] == texts[1]


# -- the rule arm (``update`` not "add"): work with the batch, not the table --


def _capacity_arm(spec, table, ids, deltas, mask):
    """The arm ``core/store.push`` had for a custom ``update`` until PR 34,
    kept here as what the batch-sized arm is held to: duplicates combined
    into a zeroed table, ``update`` over the WHOLE table, a table-sized
    ``where``.  O(capacity) a step and three table-sized temporaries."""
    flat_ids = ids.reshape(-1).astype(jnp.int32)
    flat_ids = jnp.where(flat_ids < 0, spec.padded_capacity, flat_ids)
    flat_deltas = deltas.reshape((-1,) + spec.value_shape)
    ones = jnp.ones(flat_ids.shape, jnp.int32)
    if mask is not None:
        keep = mask.reshape((-1,) + (1,) * len(spec.value_shape))
        flat_deltas = jnp.where(keep, flat_deltas, 0)
        ones = jnp.where(mask.reshape(-1), ones, 0)
    combined = jnp.zeros_like(table).at[flat_ids].add(
        flat_deltas.astype(table.dtype), mode="drop"
    )
    counts = jnp.zeros((spec.padded_capacity,), jnp.int32).at[flat_ids].add(
        ones, mode="drop"
    )
    touched = (counts > 0).reshape((-1,) + (1,) * len(spec.value_shape))
    return jnp.where(touched, spec.update(table, combined), table)


def _ema(current, combined):
    return 0.5 * current + 0.5 * combined


def _ftrl():
    from flink_parameter_server_tpu.models.logistic_ftrl import FTRLProximal

    return FTRLProximal()


@pytest.mark.parametrize("sharded", [False, True], ids=["one_device", "ps_mesh"])
@pytest.mark.parametrize("rule, shape, lanes, masked", [
    ("ema", (), 64, False),
    ("ema", (2,), 200, True),
    ("ema", (17,), 300, True),       # wider than the sort carries: permuted
    ("ema", (2, 3), 150, True),
    ("ftrl", (3,), 400, True),
    ("ftrl", (3,), 40_000, False),   # more than one chunk of the rule's loop
])
def test_the_rule_arm_is_the_capacity_arm(rule, shape, lanes, masked, sharded, mesh):
    from flink_parameter_server_tpu.core import store as store_mod

    rng = np.random.default_rng(lanes)
    update = _ema if rule == "ema" else _ftrl()
    capacity = 50 if lanes < 1000 else 30_000
    values = rng.normal(size=(capacity,) + shape).astype(np.float32)
    if rule == "ftrl":
        values[:, 2] = np.abs(values[:, 2]) * 30
    store = ShardedParamStore.from_values(
        jnp.asarray(values), update=update, mesh=mesh if sharded else None
    )
    ids = rng.integers(-3, capacity + 4, lanes).astype(np.int32)
    ids[: lanes // 4] = 5                          # one hot row
    deltas = rng.normal(size=(lanes,) + shape).astype(np.float32)
    if rule == "ftrl":
        deltas[:, 2] = deltas[:, 0] ** 2
    mask = jnp.asarray(rng.random(lanes) < 0.8) if masked else None
    got = np.asarray(store.push(jnp.asarray(ids), jnp.asarray(deltas), mask).values())
    # the table as the capacity arm knew it: logical rows, padding rows too
    rows = np.array(store.table)
    if store.spec.tile_lanes:
        rows = rows[:, : store.spec.row_width]
    want = np.asarray(_capacity_arm(
        store.spec, jnp.asarray(rows), jnp.asarray(ids),
        jnp.asarray(deltas), mask,
    ))[:capacity]
    live = np.ones(lanes, bool) if mask is None else np.asarray(mask)
    hit = np.zeros(capacity, bool)
    hit[ids[live & (ids >= 0) & (ids < capacity)]] = True
    assert hit.any()
    # untouched rows bit for bit; touched ones to the order of a run's sum
    assert np.array_equal(got[~hit], values[~hit])
    assert np.allclose(got[hit], want[hit], rtol=2e-5, atol=2e-5)
    assert not np.array_equal(got[hit], values[hit])
    table, counted = store_mod.push_counted(
        store.spec, store.table, jnp.asarray(ids), jnp.asarray(deltas), mask
    )
    # padding rows are addressable rows too: an id counts up to there
    kept = live & (ids >= 0) & (ids < store.spec.padded_capacity)
    assert int(counted["ps_rule_rows"]) == len(np.unique(ids[kept]))
    assert int(counted["ps_rule_keys"]) == kept.sum()


@pytest.mark.parametrize("lanes", [3, 0], ids=["all_masked", "empty"])
def test_a_push_of_no_live_lane_to_a_rule_store_changes_nothing(lanes):
    from flink_parameter_server_tpu.core import store as store_mod

    store = ShardedParamStore.create(9, (2,), init_fn=zeros((2,)), update=_ema)
    store = store.push(jnp.array([1, 1, 4]), jnp.ones((3, 2)))
    before = np.asarray(store.values())
    push = (
        jnp.array([1, 2, 3][:lanes], jnp.int32), jnp.ones((lanes, 2)),
        jnp.zeros(lanes, bool),
    )
    assert np.array_equal(np.asarray(store.push(*push).values()), before)
    _, counted = store_mod.push_counted(store.spec, store.table, *push)
    assert int(counted["ps_rule_rows"]) == int(counted["ps_rule_keys"]) == 0
    assert int(counted["ps_rule_tiles"]) == 0
    assert before[1, 0] == 1.0 and before[4, 0] == 0.5  # 0.5 * (1 + 1), 0.5 * 1


@pytest.mark.parametrize("width, lanes", [
    (1, 1), (2, 2), (3, 4), (4, 4), (5, 8), (7, 8), (8, 8), (9, 0), (17, 0),
])
def test_a_narrow_rule_row_is_held_at_its_sublane_tile(width, lanes):
    """``from_values`` -> ``pull`` / ``values()`` round trip of a rule store
    whose physical row carries zero lanes, and ``create`` the same."""
    rng = np.random.default_rng(width)
    capacity = 300
    values = rng.normal(size=(capacity, width)).astype(np.float32)
    store = ShardedParamStore.from_values(jnp.asarray(values), update=_ema)
    spec = store.spec
    assert spec.tile_lanes == lanes
    if lanes:  # whole tiles of 128 rows, the row's lanes then zeros
        assert spec.padded_capacity == 384
        assert store.table.shape == (384, lanes)
        table = np.asarray(store.table)
        assert np.array_equal(table[:capacity, :width], values)
        assert not table[capacity:].any() and not table[:, width:].any()
    else:  # as every dense table: the row as it is, tiles of 8 rows
        assert store.table.shape == (304, width)
    assert np.array_equal(np.asarray(store.values()), values)
    ids = np.array([[0, 299, 7], [7, 150, 298]], np.int32)
    assert np.array_equal(np.asarray(store.pull(jnp.asarray(ids))), values[ids])
    made = ShardedParamStore.create(
        capacity, (width,), init_fn=lambda i: jnp.asarray(values)[i % capacity],
        update=_ema,
    )
    assert made.table.shape == store.table.shape
    assert np.array_equal(np.asarray(made.values()), values)
    pushed = made.push(jnp.asarray(ids), jnp.ones(ids.shape + (width,)))
    got = np.asarray(pushed.values())
    assert np.array_equal(got[7], 0.5 * values[7] + 1.0)  # two deltas summed
    assert np.array_equal(got[150], 0.5 * values[150] + 0.5)
    if lanes:
        assert not np.asarray(pushed.table)[:, width:].any()
    # a store under a mesh, or of another dtype, holds its rows as they are
    assert ShardedParamStore.from_values(
        jnp.asarray(values, jnp.bfloat16), update=_ema
    ).spec.tile_lanes == 0
    assert ShardedParamStore.from_values(
        jnp.asarray(values)
    ).spec.tile_lanes == 0


@pytest.mark.parametrize("rule, width, lanes, masked", [
    ("ema", 1, 300, True), ("ema", 2, 900, False), ("ftrl", 3, 400, True),
    ("ftrl", 3, 40_000, False),  # more than one chunk of the rule's loop
    ("ema", 4, 700, True), ("ema", 5, 1200, True), ("ema", 8, 600, False),
])
def test_the_set_kernel_arm_is_the_xla_arm_bit_for_bit(
        rule, width, lanes, masked, monkeypatch, steer_arms):
    """The rule store's push with the write-back steered through
    ``ops/row_update.sorted_tile_set`` (interpreted: this is a CPU) against
    the same push with XLA's row ``set``: every bit of the table, the same
    counts, and the tiles the kernel moved."""
    from flink_parameter_server_tpu.core import store as store_mod

    rng = np.random.default_rng([width, lanes])
    update = _ema if rule == "ema" else _ftrl()
    capacity = 900 if lanes < 1000 else 30_000
    values = rng.normal(size=(capacity, width)).astype(np.float32)
    if rule == "ftrl":
        values[:, 2] = np.abs(values[:, 2]) * 30
    store = ShardedParamStore.from_values(jnp.asarray(values), update=update)
    ids = rng.integers(-3, capacity + 200, lanes).astype(np.int32)
    ids[: lanes // 4] = 5                          # one hot row
    deltas = rng.normal(size=(lanes, width)).astype(np.float32)
    mask = jnp.asarray(rng.random(lanes) < 0.8) if masked else None
    args = (jnp.asarray(ids), jnp.asarray(deltas), mask)
    assert store_mod.arms(store.spec).write_back == "xla_set"  # a CPU
    want, counted = store_mod.push_counted(store.spec, store.table, *args)
    assert int(counted["ps_rule_tiles"]) == 0
    steer_arms(write_back="tile_set")
    got, kernel_counted = store_mod.push_counted(store.spec, store.table, *args)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for name in ("ps_rule_keys", "ps_rule_rows"):
        assert int(kernel_counted[name]) == int(counted[name])
    live = np.ones(lanes, bool) if mask is None else np.asarray(mask)
    kept = np.unique(ids[live & (ids >= 0) & (ids < store.spec.padded_capacity)])
    chunk = store_mod._RULE_CHUNK  # a tile two chunks share is moved twice
    assert int(kernel_counted["ps_rule_tiles"]) == sum(
        len(np.unique(kept[lo:lo + chunk] // 128))
        for lo in range(0, len(kept), chunk)
    )
    assert int(kernel_counted["ps_rule_tiles"]) > 0
    # a descriptor in and one out a copy, of a touched tile or of a span
    # that holds several (the next test counts them)
    assert 2 <= int(kernel_counted["ps_rule_descriptors"]) <= 2 * int(
        kernel_counted["ps_rule_tiles"])
    assert int(kernel_counted["ps_rule_span_tiles"]) >= int(
        kernel_counted["ps_rule_tiles"])
    assert "ps_rule_descriptors" not in counted  # XLA's set issues none


@pytest.mark.parametrize("span", [1, 4, 16])
def test_the_step_hands_out_the_descriptors_its_write_back_issued(
        span, monkeypatch, steer_arms):
    """``ps_rule_descriptors`` / ``ps_rule_span_tiles`` (gauges
    ``store_rule_descriptors`` / ``store_rule_span_tiles``): what the set
    kernel's plan issued, a descriptor in and one out for every copy, and
    the tiles those copies moved, counted here from the ids alone.  The
    span is judged by the PUSH's lanes, chunk by chunk of its distinct
    rows; the table is XLA's arm's bit for bit at every span."""
    from flink_parameter_server_tpu.core import store as store_mod
    from flink_parameter_server_tpu.ops import row_update
    from flink_parameter_server_tpu.telemetry.registry import MetricsRegistry

    rng = np.random.default_rng(span)
    capacity, lanes = 128 * 50, 3000
    store = ShardedParamStore.from_values(
        jnp.asarray(rng.normal(size=(capacity, 3)).astype(np.float32)),
        update=_ema)
    # half the table dense, the other half a row here and there
    ids = np.concatenate([
        rng.integers(0, capacity // 2, lanes - 40),
        rng.integers(capacity // 2, capacity, 40)]).astype(np.int32)
    deltas = rng.normal(size=(lanes, 3)).astype(np.float32)
    want, _ = store_mod.push_counted(
        store.spec, store.table, jnp.asarray(ids), jnp.asarray(deltas))
    seen = []
    monkeypatch.setattr(
        row_update, "set_span",
        lambda tiles, lanes: seen.append((tiles, lanes)) or span)
    steer_arms(write_back="tile_set")
    got, counted = jax.jit(
        lambda t, i, d: store_mod.push_counted(store.spec, t, i, d)
    )(store.table, jnp.asarray(ids), jnp.asarray(deltas))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert seen == [(50, lanes)]
    touched = np.unique(np.unique(ids) // 128)
    groups, in_group = np.unique(touched // span, return_counts=True)
    spanned = (in_group >= 2) & (groups < 50 // span)
    copies = int(spanned.sum() + in_group[~spanned].sum())
    assert int(counted["ps_rule_tiles"]) == len(touched)
    assert int(counted["ps_rule_descriptors"]) == 2 * copies
    assert int(counted["ps_rule_span_tiles"]) == int(
        span * spanned.sum() + in_group[~spanned].sum())
    if span > 1:
        assert copies < len(touched)  # what the chip is spared
    registry = MetricsRegistry()
    store_mod.publish_counts(counted, registry, int, int)
    gauges = {i.name: i.value for i in registry.instruments()}
    assert gauges["store_rule_descriptors"] == 2 * copies
    assert gauges["store_rule_span_tiles"] == int(counted["ps_rule_span_tiles"])


@pytest.mark.parametrize("width", [1, 3, 4, 5, 8, 9, 17, 36])
def test_combine_runs_sums_each_id_and_moves_the_distinct_first(width):
    from flink_parameter_server_tpu.ops.dedup import combine_runs

    rng = np.random.default_rng(width)
    n, sentinel = 777, 1000
    ids = rng.integers(0, 40, n).astype(np.int32)
    ids[rng.random(n) < 0.1] = sentinel            # lanes to drop
    ids[:200] = 7                                   # a long run
    vals = rng.normal(size=(n, width)).astype(np.float32)
    arm = "sort" if width <= 4 else "scatter_add"  # what `arms` reads here
    row_ids, sums, writes = jax.jit(combine_runs, static_argnums=(2, 3))(
        ids, vals, sentinel, arm)
    # a narrow row rides through the sort; off the TPU nothing issues a DMA
    assert writes is None if width <= 4 else int(writes) == 0
    row_ids, sums = np.asarray(row_ids), np.asarray(sums)
    distinct = np.unique(ids[ids < sentinel])
    assert row_ids.shape == (n,) and sums.shape == (n, width)
    assert np.array_equal(row_ids[: len(distinct)], distinct)
    assert (row_ids[len(distinct):] == sentinel).all()
    want = np.zeros((1001, width))
    np.add.at(want, ids, vals.astype(np.float64))
    assert np.allclose(sums[: len(distinct)], want[distinct], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("update, shape, want", [
    ("add", (17,), "packed"), ("add", (128,), "dense"), ("add", (2, 300), "packed"),
    ("rule", (3,), "dense"), ("rule", (), "dense"), ("rule", (17,), "packed"),
    ("rule", (128,), "dense"), ("rule", (2, 300), "dense"),
    ("rule", (8,), "dense"), ("rule", (9,), "packed"), ("rule", (36,), "packed"),
    ("rule", (64,), "packed"), ("rule", (65,), "packed"),
    ("rule", (101,), "packed"), ("rule", (127,), "packed"),
    ("rule", (129,), "packed"), ("rule", (2, 9), "dense"), ("rule", (1,), "dense"),
])
def test_auto_layout_reads_row_shape_and_update_rule(update, shape, want):
    from flink_parameter_server_tpu.core.store import _resolve_layout

    rule = "add" if update == "add" else _ema
    assert _resolve_layout("auto", rule, shape) == want
    # either layout may be pinned, for a rule as for an add-store
    for pinned in ("dense", "packed"):
        assert _resolve_layout(pinned, rule, shape) == pinned
    with pytest.raises(ValueError, match="layout must be"):
        _resolve_layout("tiled", rule, shape)


# -- a PACKED rule store: k = 128 // width logical rows to a physical row -----
# What `_resolve_layout("auto")` gives a rule row of 9 to 64 lanes.  Its push
# reads and writes whole physical rows, the touched logical rows of one merged
# by selects (`core/store._rewrite_packed`); it is held to the DENSE rule store
# on the same ids and deltas, bit for bit, untouched rows and the untouched
# neighbours inside a touched physical row included.

PACKED_RULE_WIDTHS = [9, 17, 36, 64]
# ... and of 65 to 127 lanes (PR 61): the row alone in ONE register, k = 1
# (PBG's 101: an embedding and row-wise AdaGrad's one accumulator)
ONE_REGISTER_RULE_WIDTHS = [65, 101, 127]
PACKED_RULE_CASES = [
    "uniform", "two_of_one_physical_row", "a_whole_physical_row",
    "across_a_chunk_edge", "nan_inf_and_minus_zero_neighbours",
    "dead_and_out_of_range_lanes", "empty_batch", "the_last_rows_and_padding",
    "half_masked_hot_row",
]


def _sticky_rule(current, combined):
    # not linear, and it moves a row even by a zero sum: a row the push
    # rewrote shows, and one it should have left does too
    return 0.5 * current + 0.25 * combined + 1.0


def _packed_rule_traffic(case, rng, cap, width):
    """``(ids, deltas, mask or None, rule chunk or None)`` of one push."""
    k = 128 // width
    n, mask, chunk = 300, None, None
    ids = rng.integers(0, cap, n)
    if case == "two_of_one_physical_row":
        ids = np.array([k * 5, k * 5 + 1, k * 9 + (k - 1), k * 9, 3])
    elif case == "a_whole_physical_row":
        ids = np.concatenate([k * 7 + np.arange(k), k * 8 + np.arange(k)])
    elif case == "across_a_chunk_edge":
        # 16 sorted distinct ids a trip: physical rows lie across the edges
        ids, chunk = np.arange(11, 11 + 50), 16
        assert k == 1 or any((11 + e) % k for e in (16, 32, 48))
    elif case == "nan_inf_and_minus_zero_neighbours":
        ids = np.array([k * 4 + 1, k * 6, 2 * k + (k - 1)])
    elif case == "dead_and_out_of_range_lanes":
        ids = rng.integers(-5, cap + 40, n)
        ids[:5] = [-1, cap, cap + 1000, -(2 ** 31), 2 ** 31 - 1]
    elif case == "empty_batch":
        ids = np.zeros((0,), np.int64)
    elif case == "the_last_rows_and_padding":
        # the store pads its rows to whole physical rows and tiles of them:
        # the padding rows are addressable, rewritten and never handed out
        ids = np.arange(cap - 5, cap + 6)
    elif case == "half_masked_hot_row":
        ids[: n // 3] = 7
        mask = rng.random(n) < 0.5
    elif case != "uniform":
        raise AssertionError(case)
    ids = np.asarray(ids, np.int32)
    deltas = rng.normal(size=ids.shape + (width,)).astype(np.float32)
    if case == "dead_and_out_of_range_lanes":
        deltas[(ids < 0) | (ids >= cap)] = np.nan
    return ids, deltas, mask, chunk


@pytest.mark.parametrize("case", PACKED_RULE_CASES)
@pytest.mark.parametrize(
    "width", PACKED_RULE_WIDTHS + ONE_REGISTER_RULE_WIDTHS + [8, 128])
@pytest.mark.parametrize("arm", ["xla", "row_set_kernel"])
def test_a_packed_rule_store_is_the_dense_one_bit_for_bit(
        arm, width, case, monkeypatch, steer_arms):
    from flink_parameter_server_tpu.core import store as store_mod

    rng = np.random.default_rng([width, PACKED_RULE_CASES.index(case)])
    cap = 61 * max(1, 128 // width) + 2  # no whole number of physical rows
    values = rng.normal(size=(cap, width)).astype(np.float32)
    if case == "nan_inf_and_minus_zero_neighbours":
        k = max(1, 128 // width)
        # the untouched rows beside the touched ones, in their physical rows
        values[k * 4] = np.nan
        values[k * 6 + 1] = -0.0
        values[2 * k if k > 1 else 0] = np.inf
        values[k * 6 + 1, ::2] = -np.inf
    ids, deltas, mask, chunk = _packed_rule_traffic(case, rng, cap, width)
    if chunk:
        monkeypatch.setattr(store_mod, "_RULE_CHUNK", chunk)
    auto = ShardedParamStore.from_values(
        jnp.asarray(values), update=_sticky_rule, layout="auto")
    dense = ShardedParamStore.from_values(
        jnp.asarray(values), update=_sticky_rule, layout="dense")
    packs = width in PACKED_RULE_WIDTHS + ONE_REGISTER_RULE_WIDTHS
    assert auto.spec.layout == ("packed" if packs else "dense")
    assert dense.spec.layout == "dense"
    if not packs:
        assert auto.spec == dense.spec
    else:
        k = 128 // width
        assert auto.spec.pack == k and auto.table.shape == (64, 128)
        table = np.asarray(auto.table)
        assert not table[:, k * width:].any()  # the pad lanes
        assert table[:, : k * width].reshape(-1, width)[:cap].tobytes() == (
            values.tobytes())
    # `values()` / `from_values` round trip, bit for bit
    assert np.asarray(auto.values()).tobytes() == values.tobytes()
    assert store_mod.arms(auto.spec).write_back == "xla_set"  # a CPU
    args = (jnp.asarray(ids), jnp.asarray(deltas),
            None if mask is None else jnp.asarray(mask))
    want, _ = store_mod.push_counted(dense.spec, dense.table, *args)
    if arm == "row_set_kernel" and packs:
        # off the TPU the arm is steered and the kernel interpreted
        steer_arms(write_back="row_set")
    got, counted = store_mod.push_counted(auto.spec, auto.table, *args)
    pushed = ShardedParamStore(auto.spec, got)
    want = np.asarray(ShardedParamStore(dense.spec, want).values())
    assert np.asarray(pushed.values()).tobytes() == want.tobytes()
    live = (ids >= 0) & (ids < auto.spec.padded_capacity)
    if mask is not None:
        live &= mask
    kept = np.unique(ids[live])
    hit = np.zeros(cap, bool)
    hit[kept[kept < cap]] = True
    # every touched row moved, every other row is what it was
    assert (want[hit] != values[hit]).any(axis=1).all()
    assert want[~hit].tobytes() == values[~hit].tobytes()
    assert int(counted["ps_rule_keys"]) == live.sum()
    assert int(counted["ps_rule_rows"]) == len(kept)
    pulled = np.asarray(pushed.pull(jnp.asarray(np.clip(ids, 0, cap - 1))))
    assert pulled.tobytes() == want[np.clip(ids, 0, cap - 1)].tobytes()
    if not packs:
        assert "ps_rule_packed_rows" not in counted
        return
    # the physical rows written: one a run of neighbours in a trip's chunk
    k, step = 128 // width, chunk or store_mod._RULE_CHUNK
    assert int(counted["ps_rule_packed_rows"]) == sum(
        len(np.unique(kept[lo:lo + step] // k))
        for lo in range(0, len(kept), step))
    assert int(counted["ps_rule_tiles"]) == 0
    table = np.asarray(got)
    assert table.shape == (64, 128) and not table[:, k * width:].any()
    if case == "across_a_chunk_edge" and k > 1:
        assert int(counted["ps_rule_packed_rows"]) > len(np.unique(kept // k))


@pytest.mark.parametrize("width", PACKED_RULE_WIDTHS)
def test_a_packed_rule_store_under_a_ps_mesh_is_the_one_device_store(
        width, mesh):
    """The XLA arm under GSPMD: a packed rule store sharded over ``ps``
    (each shard its own packed block) against the same store in one place,
    bit for bit, pull and counts too; no kernel is asked for a mesh."""
    from flink_parameter_server_tpu.core import store as store_mod

    rng = np.random.default_rng(width)
    k, cap = 128 // width, 500
    values = rng.normal(size=(cap, width)).astype(np.float32)
    ids = rng.integers(-2, cap + 3, 400).astype(np.int32)
    ids[:k] = k * 11 + np.arange(k)  # one physical row whole
    deltas = rng.normal(size=(400, width)).astype(np.float32)
    one = ShardedParamStore.from_values(
        jnp.asarray(values), update=_sticky_rule, layout="auto")
    sharded = ShardedParamStore.from_values(
        jnp.asarray(values), update=_sticky_rule, mesh=mesh, layout="auto")
    assert sharded.spec.layout == one.spec.layout == "packed"
    assert sharded.table.sharding == sharded.spec.sharding()
    assert np.asarray(sharded.values()).tobytes() == values.tobytes()
    args = (jnp.asarray(ids), jnp.asarray(deltas))
    want, counted_one = store_mod.push_counted(one.spec, one.table, *args)
    got, counted = jax.jit(
        lambda t, i, d: store_mod.push_counted(sharded.spec, t, i, d)
    )(sharded.table, *args)
    want = np.asarray(ShardedParamStore(one.spec, want).values())
    pushed = ShardedParamStore(sharded.spec, got)
    assert np.asarray(pushed.values()).tobytes() == want.tobytes()
    for name in ("ps_rule_keys", "ps_rule_rows"):
        assert int(counted[name]) == int(counted_one[name])
    # (a shard's padding moves where an id's physical row lies, not how many)
    kept = np.unique(ids[(ids >= 0) & (ids < cap)])
    assert int(counted["ps_rule_packed_rows"]) >= len(np.unique(kept // k))
    probe = jnp.asarray(np.clip(ids, 0, cap - 1))
    assert np.asarray(pushed.pull(probe)).tobytes() == (
        want[np.asarray(probe)].tobytes())


# A RULE store whose table is sharded over ``ps`` under ONE worker runs its
# push ON the shards (``core/store._push_rule_on_shards``): every shard takes
# the keys that fall in its block and runs the one-place push on it.  On the
# CPU (no kernel) that is the one-place store bit for bit; the kernels are
# steered and interpreted inside the ``shard_map``.
@pytest.fixture(scope="module")
def ps_mesh(mesh_devices):
    from flink_parameter_server_tpu.parallel.mesh import make_mesh

    return make_mesh(1, 4, devices=mesh_devices[:4])


SHARD_RULE_CASES = [
    "uniform", "half_masked_hot_row", "ids_out_of_range",
    "a_whole_physical_row", "a_physical_row_on_a_chunks_edge",
    "an_empty_shard", "empty_batch",
]


def _shard_rule_traffic(case, rng, cap, width, block):
    """``(ids, deltas, mask or None, rule chunk or None)`` of one push into a
    store whose shards own ``block`` logical rows each."""
    k = max(1, 128 // width)
    n, mask, chunk = 400, None, None
    ids = rng.integers(0, cap, n)
    if case == "half_masked_hot_row":
        ids[: n // 3] = block + 1  # shard 1's
        mask = rng.random(n) < 0.5
    elif case == "ids_out_of_range":
        ids = rng.integers(-5, cap + 40, n)
        # (past BOTH stores' padding rows, which differ and are addressable)
        ids = np.where(ids >= cap, ids + 4 * block, ids)
        ids[:6] = [-1, -(2 ** 31), 2 ** 31 - 1, cap + 4000, 4 * block, 4 * block + 1]
    elif case == "a_whole_physical_row":
        # every logical row of one physical row, in two shards
        ids = np.concatenate([k * 7 + np.arange(k), block + k * 3 + np.arange(k)])
    elif case == "a_physical_row_on_a_chunks_edge":
        # 16 sorted distinct ids a trip of each shard's loop
        ids, chunk = np.arange(5, 5 + 4 * 50) % cap, 16
    elif case == "an_empty_shard":
        ids = ids[(ids < 2 * block) | (ids >= 3 * block)]
        assert ids.size > 100
    elif case == "empty_batch":
        ids = np.zeros((0,), np.int64)
    elif case != "uniform":
        raise AssertionError(case)
    ids = np.asarray(ids, np.int32)
    deltas = rng.normal(size=ids.shape + (width,)).astype(np.float32)
    return ids, deltas, mask, chunk


@pytest.mark.parametrize("case", SHARD_RULE_CASES)
@pytest.mark.parametrize("width,arm", [
    (3, "xla"), (9, "xla"), (17, "xla"), (36, "xla"), (64, "xla"),
    (100, "xla"), (128, "xla"), (17, "row_set_kernel"), (36, "row_set_kernel"),
    (101, "row_set_kernel"),
])
def test_a_rule_store_on_its_shards_is_the_one_place_store_and_the_shares_add_up(
        arm, width, case, ps_mesh, monkeypatch, steer_arms):
    from flink_parameter_server_tpu.core import store as store_mod

    rng = np.random.default_rng([width, SHARD_RULE_CASES.index(case)])
    cap = 500
    values = rng.normal(size=(cap, width)).astype(np.float32)
    one = ShardedParamStore.from_values(
        jnp.asarray(values), update=_sticky_rule, layout="auto")
    sharded = ShardedParamStore.from_values(
        jnp.asarray(values), update=_sticky_rule, mesh=ps_mesh, layout="auto")
    spec = sharded.spec
    packs = width in PACKED_RULE_WIDTHS + [100, 101]
    assert spec.layout == one.spec.layout == ("packed" if packs else "dense")
    # a narrow rule row is NOT held at its sublane tile under a mesh
    assert spec.tile_lanes == 0 and (width != 3 or one.spec.tile_lanes == 4)
    assert store_mod.arms(spec).on_shards
    block = spec.rows_per_shard * spec.pack
    ids, deltas, mask, chunk = _shard_rule_traffic(case, rng, cap, width, block)
    if chunk:
        monkeypatch.setattr(store_mod, "_RULE_CHUNK", chunk)
    args = (jnp.asarray(ids), jnp.asarray(deltas),
            None if mask is None else jnp.asarray(mask))
    want, counted_one = store_mod.push_counted(one.spec, one.table, *args)
    if arm == "row_set_kernel":
        # off the TPU the arm is steered and the kernel interpreted,
        # here inside every shard's part of the shard_map
        steer_arms(write_back="row_set")
    got, counted = jax.jit(
        lambda t, i, d, m: store_mod.push_counted(spec, t, i, d, m)
    )(sharded.table, *args)
    assert got.sharding == spec.sharding()
    want = np.asarray(ShardedParamStore(one.spec, want).values())
    pushed = ShardedParamStore(spec, got)
    assert np.asarray(pushed.values()).tobytes() == want.tobytes()
    # the shares add up: ownership is disjoint, so the counts summed over the
    # shards are the one-place push's, and the fullest shard's are numpy's
    live = (ids >= 0) & (ids < min(spec.padded_capacity, one.spec.padded_capacity))
    if mask is not None:
        live &= mask
    owner = ids[live] // block
    keys = np.bincount(owner, minlength=4)
    rows = np.array([np.unique(ids[live][owner == s]).size for s in range(4)])
    if case == "an_empty_shard":
        assert keys[2] == 0 and rows[2] == 0 and keys.sum() > 100
    for name in ("ps_rule_keys", "ps_rule_rows", "ps_rule_tiles"):
        assert int(counted[name]) == int(counted_one[name]), name
    assert int(counted["ps_rule_keys"]) == keys.sum() == live.sum()
    assert int(counted["ps_rule_rows"]) == rows.sum()
    assert int(counted["ps_rule_keys_max_shard"]) == keys.max()
    assert int(counted["ps_rule_rows_max_shard"]) == rows.max()
    assert "ps_rule_rows_max_shard" not in counted_one
    # the rows each shard rewrote lie in its own block, are disjoint, and
    # their union is what the one-place store rewrote
    moved = np.flatnonzero((want != values).any(axis=1))
    assert np.array_equal(moved, np.unique(ids[live][ids[live] < cap]))
    blocks = np.asarray(got).reshape(4, spec.rows_per_shard, -1)
    before = np.asarray(sharded.table).reshape(blocks.shape)
    touched_phys = [(blocks[s] != before[s]).any(axis=1).sum() for s in range(4)]
    k = spec.pack
    assert touched_phys == [
        np.unique(ids[live][owner == s] // k).size for s in range(4)]
    if packs:
        assert int(counted["ps_combine_kernel_lanes"]) == 0  # a CPU
        assert int(counted["ps_rule_packed_rows"]) >= sum(touched_phys)
        if chunk is None:
            assert int(counted["ps_rule_packed_rows"]) == sum(touched_phys)
    probe = jnp.asarray(np.clip(ids, 0, cap - 1))
    assert np.asarray(pushed.pull(probe)).tobytes() == (
        want[np.asarray(probe)].tobytes())


@pytest.mark.parametrize("width", [17, 36])
def test_the_row_kernels_inside_the_shard_map_sum_and_write_what_xla_does(
        width, ps_mesh, monkeypatch, steer_arms):
    """Both kernels steered on and interpreted inside the ``shard_map``: the
    combine sums a run along sorted lanes (a blocked float32 sum, not the
    stream's order), so the rows are the XLA arm's within float32 rounding,
    a row named once bit for bit, and the counts are the XLA arm's."""
    from flink_parameter_server_tpu.core import store as store_mod
    from flink_parameter_server_tpu.ops import row_update

    rng = np.random.default_rng(width)
    cap = 500
    values = rng.normal(size=(cap, width)).astype(np.float32)
    ids = rng.integers(-3, cap + 5, 700).astype(np.int32)
    ids[:200] = 123  # a hot row, a run over a kernel block
    deltas = rng.normal(size=(700, width)).astype(np.float32)
    mask = rng.random(700) < 0.9
    sharded = ShardedParamStore.from_values(
        jnp.asarray(values), update=_sticky_rule, mesh=ps_mesh, layout="auto")
    spec = sharded.spec
    args = (jnp.asarray(ids), jnp.asarray(deltas), jnp.asarray(mask))
    want, counted_xla = jax.jit(
        lambda t, i, d, m: store_mod.push_counted(spec, t, i, d, m)
    )(sharded.table, *args)
    calls = []
    steer_arms(write_back="row_set", combine="row_kernel")
    monkeypatch.setattr(row_update, "MAX_LANES", 512)
    for name in ("sorted_run_sums", "sorted_row_set"):
        real = getattr(row_update, name)
        monkeypatch.setattr(
            row_update, name,
            lambda *a, _real=real, _name=name, **kw: (
                calls.append(_name) or _real(*a, **kw)))
    got, counted = jax.jit(
        lambda t, i, d, m: store_mod.push_counted(spec, t, i, d, m)
    )(sharded.table, *args)
    assert {"sorted_run_sums", "sorted_row_set"} <= set(calls)
    want = np.asarray(ShardedParamStore(spec, want).values())
    got = np.asarray(ShardedParamStore(spec, got).values())
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)
    live = (ids >= 0) & (ids < spec.padded_capacity) & mask
    named, times = np.unique(ids[live], return_counts=True)
    once = named[(times == 1) & (named < cap)]
    assert once.size > 50 and got[once].tobytes() == want[once].tobytes()
    for name in counted_xla:
        if not name.startswith("ps_combine_kernel_"):
            assert int(counted[name]) == int(counted_xla[name]), name
    assert int(counted_xla["ps_combine_kernel_lanes"]) == 0
    assert int(counted["ps_combine_kernel_lanes"]) == live.sum()
    # ONE copy a block of 256 sorted lanes that ends a run, on every shard
    assert int(counted_xla["ps_combine_kernel_writes"]) == 0
    assert 4 <= int(counted["ps_combine_kernel_writes"]) <= 4 * -(-700 // 256)


# An ADD store over ``ps`` under one worker (PR 67): where ``arms`` reads the
# tile kernel for a store of ONE shard's block, the push runs in a
# ``shard_map`` on the shard that owns the row
# (``core/store._push_add_on_shards``).  Off the TPU the arm is steered and the
# kernel interpreted inside every shard's part.
ADD_SHARD_CASES = ONE_REGISTER_TRAFFIC + ["an_empty_shard"]
ADD_SHARD_ROWS = [  # (row width, rows to a physical row, physical lanes)
    (128, 1, 128),  # cell 16's: dense, a row a register
    (64, 2, 128),  # cell 10's row, two to a register
    (300, 1, 384),  # three registers, flat
]


@pytest.mark.parametrize("case", ADD_SHARD_CASES)
@pytest.mark.parametrize("shards", [4, 2])
@pytest.mark.parametrize("width, k, lanes", ADD_SHARD_ROWS)
def test_an_add_push_on_its_shards_is_the_one_place_push_and_the_counts_add_up(
        width, k, lanes, shards, case, mesh_devices, monkeypatch, steer_arms):
    from flink_parameter_server_tpu.core import store as store_mod
    from flink_parameter_server_tpu.ops import row_update
    from flink_parameter_server_tpu.parallel.mesh import make_mesh

    cap = ONE_REGISTER_CAP
    rng = np.random.default_rng([width, shards, ADD_SHARD_CASES.index(case)])
    values = _init_values(cap, (width,))
    values[::7] = -0.0  # a masked lane adds +0.0 to it, wherever it is added
    mesh = make_mesh(1, shards, devices=mesh_devices[:shards])
    one = ShardedParamStore.from_values(jnp.asarray(values), layout="auto")
    sharded = ShardedParamStore.from_values(
        jnp.asarray(values), layout="auto", mesh=mesh)
    spec = sharded.spec
    assert spec.pack == k and spec.table_shape()[1] == lanes
    assert spec.rows_per_shard % 8 == 0  # a tile row has one owner
    block = spec.rows_per_shard * k  # a shard's LOGICAL rows
    if case == "an_empty_shard":
        mask = None
        ids = rng.integers(0, cap, 900)
        ids = ids[ids // block != shards - 2].astype(np.int32)
        deltas = rng.normal(0, 1, (ids.size, width)).astype(np.float32)
    else:
        ids, deltas, mask = _one_register_traffic(case, rng, cap, width)
    args = (jnp.asarray(ids), jnp.asarray(deltas),
            None if mask is None else jnp.asarray(mask))
    # off the TPU neither store has the kernel, and nothing runs on shards
    assert not store_mod.arms(spec, push_lanes=ids.size).on_shards
    want, counted = store_mod.push_counted(one.spec, one.table, *args)
    assert counted is None
    steer_arms(push="tile_add", on_shards=lambda spec: spec.mesh is not None)
    if case == "over_max_lanes_a_tile_row_across_two_calls":
        monkeypatch.setattr(row_update, "MAX_LANES", 512)
    # (both under a jit of this test's own: an eager push is one cached
    # program a shape, traced at whatever `MAX_LANES` an earlier test held)
    _, counted_one = jax.jit(
        lambda t, i, d, m: store_mod.push_counted(one.spec, t, i, d, m)
    )(one.table, *args)
    got, counted = jax.jit(
        lambda t, i, d, m: store_mod.push_counted(spec, t, i, d, m)
    )(sharded.table, *args)
    assert got.sharding == spec.sharding()
    want = np.asarray(ShardedParamStore(one.spec, want).values())
    pushed = ShardedParamStore(spec, got)
    assert np.asarray(pushed.values()).tobytes() == want.tobytes()
    # a batch over a call: on the shards ONE kernel call in a loop that ends
    # with a shard's last live call (PR 67), in one place a call a stretch
    many = case == "over_max_lanes_a_tile_row_across_two_calls"
    if many or case == "an_empty_shard":
        traced = {
            name: str(jax.make_jaxpr(
                lambda t, i, d, m: store_mod.push_counted(sp, t, i, d, m)
            )(tb, *args)).count("pallas_call[")
            for name, sp, tb in (("one", one.spec, one.table),
                                 ("sharded", spec, sharded.table))}
        assert traced == {"one": 4 if many else 1, "sharded": 1}
    # the counts: a shard keeps the lanes whose row it owns (a masked lane
    # keeps its id and adds zeros), and opens the tile rows of each of ITS
    # sorted batch's calls
    # (a shard's block ends on a tile row, so the sharded table has padding
    # rows the one-place table lacks: row `cap + 20` is a row of it)
    live = (ids >= 0) & (ids < spec.padded_capacity)
    live_one = (ids >= 0) & (ids < one.spec.padded_capacity)
    if case == "dead_and_masked":
        assert not live.all() and live.sum() - live_one.sum() == (
            spec.padded_capacity > cap + 20 >= one.spec.padded_capacity)
    owner = ids[live] // block
    kept = np.bincount(owner, minlength=shards)
    calls = -(-ids.size // row_update.MAX_LANES)
    size = -(-ids.size // (calls * 256)) * 256
    dropped = 2 ** 30
    tile_rows = []
    for s in range(shards):
        rel = np.sort(np.where(
            live & (ids // block == s), ids // k - s * spec.rows_per_shard,
            dropped))
        tile_rows.append(sum(
            np.unique(part[part < dropped] // 8).size
            for part in (rel[lo:lo + size] for lo in range(0, ids.size, size))))
    assert int(counted["ps_push_kernel_lanes"]) == kept.sum() == live.sum()
    assert int(counted["ps_push_lanes_max_shard"]) == kept.max()
    assert int(counted["ps_push_tile_rows"]) == sum(tile_rows)
    assert int(counted["ps_push_tile_rows_max_shard"]) == max(tile_rows)
    assert set(counted_one) == {"ps_push_kernel_lanes", "ps_push_tile_rows"}
    assert int(counted_one["ps_push_kernel_lanes"]) == live_one.sum()
    if calls == 1:  # blocks start on a tile row: the sum is the one-place push's
        assert int(counted_one["ps_push_tile_rows"]) == sum(tile_rows) - (
            live.sum() - live_one.sum())
    else:
        assert calls == 4 and sum(tile_rows) != int(counted_one["ps_push_tile_rows"])
    if case == "an_empty_shard":
        assert kept[shards - 2] == 0 == tile_rows[shards - 2] and kept.sum() > 300
        # ... and that shard's block came back as it went in, bit for bit
        blocks = np.asarray(got).reshape(shards, spec.rows_per_shard, -1)
        before = np.asarray(sharded.table).reshape(blocks.shape)
        assert blocks[shards - 2].tobytes() == before[shards - 2].tobytes()
    if case == "dead_and_masked":
        assert np.isfinite(want).all()  # a dead lane's NaN unread
    # (GSPMD's pull sums the shards' answers: a -0.0 comes back +0.0)
    probe = jnp.asarray(np.clip(ids, 0, cap - 1))
    np.testing.assert_array_equal(
        np.asarray(pushed.pull(probe)), want[np.asarray(probe)])


def test_an_add_push_under_dp_x_ps_keeps_the_one_place_push_under_gspmd(
        mesh_devices, monkeypatch):
    """``dp`` = 2 x ``ps`` = 2: the batch's lanes lie split over the workers,
    no shard could take its keys without being sent the other worker's, so
    where a TPU would have read the tile kernel the push stays XLA's under
    GSPMD (or ``worker_reduce``), noted once; the table is the one-place
    push's."""
    import dataclasses

    from flink_parameter_server_tpu.core import store as store_mod
    from flink_parameter_server_tpu.ops import row_update
    from flink_parameter_server_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(66)
    cap, n = 40_000, 2_048
    values = _init_values(cap, (128,))
    mesh = make_mesh(2, 2, devices=mesh_devices[:4])
    one = ShardedParamStore.from_values(jnp.asarray(values), layout="auto")
    sharded = ShardedParamStore.from_values(
        jnp.asarray(values), layout="auto", mesh=mesh)
    ids = rng.integers(-2, cap + 2, n).astype(np.int32)
    deltas = rng.normal(0, 1, (n, 128)).astype(np.float32)
    args = (jnp.asarray(ids), jnp.asarray(deltas))
    want, _ = store_mod.push_counted(one.spec, one.table, *args)
    got, counted = jax.jit(
        lambda t, i, d: store_mod.push_counted(sharded.spec, t, i, d)
    )(sharded.table, *args)
    assert counted is None
    assert np.asarray(ShardedParamStore(sharded.spec, got).values()).tobytes() == (
        np.asarray(ShardedParamStore(one.spec, want).values()).tobytes())
    # on a TPU: 2,048 x 8 <= 20,000 rows a shard, the kernel's ground, refused
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(store_mod, "_REFUSALS_NOTED", set())
    n0 = row_update.refusal_count()
    with pytest.warns(RuntimeWarning, match="split over dp = 2 workers"):
        arm = store_mod.arms(sharded.spec, push_lanes=n)
    assert (arm.push, arm.on_shards) == ("xla_add", False)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert store_mod.arms(
            sharded.spec, push_lanes=n, lanes_over_workers=True
        ).push == "xla_add"  # (a shard longer than the batch: no worker sums)
    assert row_update.refusal_count() == n0 + 1
    # ... and a shard shorter than eight batches never was the kernel's
    assert store_mod.arms(sharded.spec, push_lanes=8 * n).push == "xla_add"
    one_worker = dataclasses.replace(
        sharded.spec, mesh=make_mesh(1, 4, devices=mesh_devices[:4]))
    assert store_mod.arms(one_worker, push_lanes=n // 2).on_shards
    assert not store_mod.arms(one_worker, push_lanes=n).on_shards  # 10,000 rows
    assert row_update.refusal_count() == n0 + 1


def _combine_descriptors(ids, lanes, size, block=256):
    """numpy: the DMAs ``ops/dedup._kernel_sums`` starts for a batch of
    ``lanes`` lanes whose LIVE ids are ``ids``, a stretch of ``size``
    sorted lanes a call: the stretches that hold a live lane are walked,
    and a block of a walked stretch in which a run ends sends ONE copy, its
    sums being neighbours (until PR 62 ``ceil(count / 8)`` trips of eight
    single-row descriptors for its ``count`` lanes that end a run)."""
    dead = np.iinfo(np.int32).max
    calls = -(-lanes // size)
    slot = np.full(calls * size, dead, np.int64)
    slot[:len(ids)] = np.unique(ids, return_inverse=True)[1]  # sorted ranks
    slot[:len(ids)].sort()
    sent = 0
    for lo in range(0, -(-len(ids) // size) * size, size):
        call = slot[lo:lo + size]
        last = np.concatenate([call[1:] != call[:-1], [True]]) & (call < dead)
        sent += int((last.reshape(-1, block).sum(axis=1) > 0).sum())
    return sent


SHARD_OWNED = ["no_key", "one_key", "every_key", "spread"]


@pytest.mark.parametrize("placed", ["one_place", "ps4"])
@pytest.mark.parametrize("owned", SHARD_OWNED)
def test_the_walk_pays_by_what_it_writes_and_the_push_is_the_parents(
        owned, placed, ps_mesh, monkeypatch, steer_arms):
    """A packed rule store's push with both kernels steered on and
    interpreted, in one place and on the shards of a ``ps`` = 4 mesh: the
    table is, bit for bit, what the walk of PR 54's parent gives (the row
    kernel under the plan that sends a DMA a LANE of a block that writes,
    its old rows zeros but a run's that a call before began; the
    write-back's rows set by XLA, which ``sorted_row_set`` is bit for bit:
    tests/test_row_update.py), and ``ps_combine_kernel_writes`` says what
    the walk paid: nothing for a shard that owns no key, one copy for one
    key, every stretch for every key (shard 2's, under the mesh)."""
    from flink_parameter_server_tpu.core import store as store_mod
    from flink_parameter_server_tpu.ops import row_update

    width, cap, n, size = 36, 4800, 2048, 512
    rng = np.random.default_rng([SHARD_OWNED.index(owned), placed == "ps4"])
    values = rng.normal(size=(cap, width)).astype(np.float32)
    store = ShardedParamStore.from_values(
        jnp.asarray(values), update=_sticky_rule, layout="auto",
        mesh=ps_mesh if placed == "ps4" else None)
    spec = store.spec
    assert spec.layout == "packed" and spec.pack == 3
    block = spec.rows_per_shard * spec.pack if placed == "ps4" else cap
    lo = 2 * block if placed == "ps4" else 0  # shard 2's rows
    other = np.concatenate([np.arange(0, lo), np.arange(lo + block, cap)])
    if owned == "spread" or (placed == "one_place" and owned == "every_key"):
        ids = rng.integers(0, cap, n)
        ids[:300] = lo + 7  # a run over a kernel block
    elif owned == "every_key":
        ids = lo + rng.integers(0, block, n)
        ids[:300] = lo + 7
    elif placed == "one_place":  # the whole batch: no key, one key
        ids = np.full(n, -1)
        ids[1234:1234 + (owned == "one_key")] = 77
    else:  # shard 2 owns none or one of the batch's keys
        ids = rng.choice(other, n)
        ids[1234:1234 + (owned == "one_key")] = lo + 77
    ids = ids.astype(np.int32)
    deltas = rng.normal(size=(n, width)).astype(np.float32)
    steer_arms(write_back="row_set", combine="row_kernel")
    monkeypatch.setattr(row_update, "MAX_LANES", size)

    def push():
        table, counted = jax.jit(
            lambda t, i, d: store_mod.push_counted(spec, t, i, d)
        )(store.table, jnp.asarray(ids), jnp.asarray(deltas))
        return np.asarray(table), {k: int(v) for k, v in counted.items()}

    got, counted = push()

    def a_dma_a_lane(block, slots, rows, **kw):
        old = row_update._open_run_reread(
            block, slots, jnp.zeros(rows.shape, jnp.float32))
        return row_update.sorted_row_update_counted(
            block, slots, old, rows, plan="lane", **kw)

    monkeypatch.setattr(row_update, "sorted_run_sums", a_dma_a_lane)
    monkeypatch.setattr(
        row_update, "sorted_row_set",
        lambda state, at, new, **kw: state.at[at].set(new, mode="drop"))
    parents, counted_parents = push()
    assert got.tobytes() == parents.tobytes()
    writes = counted.pop("ps_combine_kernel_writes")
    lanes_sent = counted_parents.pop("ps_combine_kernel_writes")
    assert counted == counted_parents
    live = ids[ids >= 0]
    shards = [live] if placed == "one_place" else [
        live[live // block == s] for s in range(4)]
    assert writes == sum(_combine_descriptors(k, n, size) for k in shards)
    assert counted["ps_combine_kernel_lanes"] == len(live)
    distinct = sum(len(np.unique(k)) for k in shards)
    # (a copy a block where the compact plan sent a descriptor a row and
    # the parent's a lane)
    assert counted["ps_rule_rows"] == distinct <= lanes_sent
    assert writes <= min(distinct, lanes_sent // 256)
    # shard 2's keys (the whole batch's in one place): the stretches walked
    mine = shards[-1] if placed == "one_place" else shards[2]
    walked = -(-len(mine) // size)
    before = np.asarray(store.table).reshape(got.shape)
    rows = spec.rows_per_shard if placed == "ps4" else got.shape[0]
    at = slice(2 * rows, 3 * rows) if placed == "ps4" else slice(None)
    touched = int((got[at] != before[at]).any(axis=1).sum())
    if owned == "no_key":
        assert walked == 0 == touched == _combine_descriptors(mine, n, size)
    elif owned == "one_key":
        assert walked == 1 == touched == _combine_descriptors(mine, n, size)
    else:
        assert touched == len(np.unique(mine // spec.pack))
        if owned == "every_key":
            assert walked == n // size == 4
            # every key in one place: the shards' sum IS the one-place count
            assert writes == _combine_descriptors(live, n, size)
    if placed == "one_place":
        assert writes == _combine_descriptors(live, n, size)


@pytest.mark.parametrize("width,meshed,names", [
    (3, False, {"ps_rule_keys", "ps_rule_rows", "ps_rule_tiles"}),  # cell 6
    (4, False, {"ps_rule_keys", "ps_rule_rows", "ps_rule_tiles"}),
    (128, False, {"ps_rule_keys", "ps_rule_rows", "ps_rule_tiles",
                  "ps_combine_kernel_lanes", "ps_combine_kernel_writes"}),
    (101, False, {"ps_rule_keys", "ps_rule_rows", "ps_rule_tiles",
                  "ps_combine_kernel_lanes", "ps_combine_kernel_writes",
                  "ps_rule_packed_rows"}),  # cell 14: packed, one a register
    (36, False, {"ps_rule_keys", "ps_rule_rows", "ps_rule_tiles",
                 "ps_combine_kernel_lanes", "ps_combine_kernel_writes",
                 "ps_rule_packed_rows"}),  # cell 9
    (36, True, {"ps_rule_keys", "ps_rule_rows", "ps_rule_tiles",
                "ps_combine_kernel_lanes", "ps_combine_kernel_writes",
                "ps_rule_packed_rows", "ps_rule_keys_max_shard",
                "ps_rule_rows_max_shard"}),  # cell 12
])
@pytest.mark.parametrize("lanes", [0, 64])
def test_what_a_rule_store_counts_goes_with_its_row(
        width, meshed, names, lanes, ps_mesh):
    """A narrow rule store's step gains no output from the combine's kernel
    (its row rides through the sort: cell 6's lowered text is pinned on
    that); a wide one counts the kernel's lanes and its descriptors, both 0
    off the TPU; an ``add`` store XLA's scatter-add took counts nothing."""
    from flink_parameter_server_tpu.core import store as store_mod

    values = jnp.zeros((600, width), jnp.float32)
    store = ShardedParamStore.from_values(
        values, update=_sticky_rule, layout="auto",
        mesh=ps_mesh if meshed else None)
    ids = jnp.arange(lanes, dtype=jnp.int32) % 50
    deltas = jnp.ones((lanes, width), jnp.float32)
    _, counted = store_mod.push_counted(store.spec, store.table, ids, deltas)
    assert set(counted) == names
    for name in names & {"ps_combine_kernel_lanes", "ps_combine_kernel_writes"}:
        assert int(counted[name]) == 0
    add = ShardedParamStore.from_values(values)
    assert store_mod.push_counted(add.spec, add.table, ids, deltas)[1] is None


@pytest.mark.parametrize("shape,dp,backend,combine,write_back,on_shards", [
    ((36,), 1, "tpu", True, True, True),    # cell 12: DiFacto over ps = 4
    ((17,), 1, "tpu", True, True, True),
    ((9,), 1, "tpu", True, True, True),
    ((3,), 1, "tpu", False, False, True),   # dense under a mesh: XLA's set
    ((100,), 1, "tpu", True, True, True),   # one register, packed k = 1
    ((36,), 1, "cpu", False, False, True),
    ((36,), 2, "tpu", False, False, False),  # dp > 1: GSPMD's, as it was
    ((3,), 2, "tpu", False, False, False),
])
def test_a_rule_store_s_arms_under_a_mesh_are_read_from_its_workers(
        shape, dp, backend, combine, write_back, on_shards, mesh_devices,
        monkeypatch):
    from flink_parameter_server_tpu.core import store as store_mod
    from flink_parameter_server_tpu.ops import row_update
    from flink_parameter_server_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(dp, 4 // dp, devices=mesh_devices[:4])
    spec = jax.eval_shape(lambda: ShardedParamStore.create(
        1000, shape, update=_sticky_rule, mesh=mesh, layout="auto")).spec
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(store_mod, "_REFUSALS_NOTED", set())
    n0 = row_update.refusal_count()
    want = (
        "row_kernel" if combine else "sort" if shape[0] <= 4 else "scatter_add",
        "row_set" if write_back else "xla_set", on_shards)

    def read(spec):
        arm = store_mod.arms(spec)
        return arm.combine, arm.write_back, arm.on_shards

    if on_shards:
        with warnings.catch_warnings():
            warnings.simplefilter("error")  # the arms themselves note nothing
            assert read(spec) == want
        assert row_update.refusal_count() == n0
        return
    # a batch split over dp > 1 workers keeps the one-place push under
    # GSPMD, and says so once as every declined arm does
    with pytest.warns(RuntimeWarning, match="split over dp = 2 workers") as w:
        assert read(spec) == want
    assert len(w) == 1  # the combine and the write-back note nothing
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert read(spec) == want
    assert row_update.refusal_count() == n0 + 1
    # neither a one-place store nor an add store under any mesh is asked
    one = jax.eval_shape(lambda: ShardedParamStore.create(
        1000, shape, update=_sticky_rule, layout="auto")).spec
    assert not store_mod.arms(one).on_shards
    assert not store_mod.arms(jax.eval_shape(lambda: ShardedParamStore.create(
        1000, shape, mesh=mesh, layout="auto")).spec).on_shards
    assert row_update.refusal_count() == n0 + 1


# -- a FLAT WIDE rule store: one axis of more than 128 lanes, k = 1 -----------
# What `_resolve_layout("auto")` gives a rule row wider than a register
# (GloVe's 602 lanes of weights, bias and accumulators in five): the flat
# whole-register layout an add store of that width has.  Its push sums the
# deltas at their own width into whole registers, reads whole physical rows,
# and writes the new rows' own lanes into them (the tile kernels take a row of
# `w` <= `W` lanes: nothing pads the batch); it is held to the DENSE rule
# store, bit for bit.

FLAT_WIDE_WIDTHS = [129, 200, 256, 602]
FLAT_WIDE_CASES = [
    "uniform", "a_hot_row", "across_a_chunk_edge", "dead_and_out_of_range_lanes",
    "nan_inf_and_minus_zero_neighbours", "the_last_rows_and_padding",
    "empty_batch", "half_masked",
]


def _flat_wide_traffic(case, rng, cap, width):
    n, mask, chunk = 200, None, None
    ids = rng.integers(0, cap, n)
    if case == "a_hot_row":
        ids[: n // 2] = 11
    elif case == "across_a_chunk_edge":
        ids, chunk = np.arange(3, 3 + 50), 16
    elif case == "dead_and_out_of_range_lanes":
        ids = rng.integers(-5, cap + 40, n)
        ids[:5] = [-1, cap, cap + 1000, -(2 ** 31), 2 ** 31 - 1]
    elif case == "nan_inf_and_minus_zero_neighbours":
        ids = np.array([9, 17, 18, 40])  # rows beside them share their tiles
    elif case == "the_last_rows_and_padding":
        ids = np.arange(cap - 5, cap + 3)
    elif case == "empty_batch":
        ids = np.zeros((0,), np.int64)
    elif case == "half_masked":
        ids[: n // 3] = 7
        mask = rng.random(n) < 0.5
    ids = np.asarray(ids, np.int32)
    deltas = rng.normal(size=ids.shape + (width,)).astype(np.float32)
    if case == "dead_and_out_of_range_lanes":
        deltas[(ids < 0) | (ids >= cap)] = np.nan
    return ids, deltas, mask, chunk


@pytest.mark.parametrize("case", FLAT_WIDE_CASES)
@pytest.mark.parametrize("width", FLAT_WIDE_WIDTHS)
@pytest.mark.parametrize("arm", ["xla", "tile_kernels"])
def test_a_flat_wide_rule_store_is_the_dense_one_bit_for_bit(
        arm, width, case, monkeypatch, steer_arms):
    from flink_parameter_server_tpu.core import store as store_mod

    rng = np.random.default_rng([width, FLAT_WIDE_CASES.index(case)])
    cap = 77  # no whole number of tiles of eight rows
    values = rng.normal(size=(cap, width)).astype(np.float32)
    if case == "nan_inf_and_minus_zero_neighbours":
        values[8], values[16], values[19] = np.nan, -0.0, np.inf
        values[41, ::2] = -np.inf
    ids, deltas, mask, chunk = _flat_wide_traffic(case, rng, cap, width)
    if chunk:
        monkeypatch.setattr(store_mod, "_RULE_CHUNK", chunk)
    auto = ShardedParamStore.from_values(
        jnp.asarray(values), update=_sticky_rule, layout="auto")
    dense = ShardedParamStore.from_values(
        jnp.asarray(values), update=_sticky_rule, layout="dense")
    lanes = -(-width // 128) * 128
    assert auto.spec.layout == "packed" and auto.spec.pack == 1
    assert auto.table.shape == (80, lanes) and dense.spec.layout == "dense"
    table = np.asarray(auto.table)
    assert not table[:, width:].any() and (
        table[:cap, :width].tobytes() == values.tobytes())
    # `values()` / `from_values` round trip, bit for bit; `create` likewise
    assert np.asarray(auto.values()).tobytes() == values.tobytes()
    made = ShardedParamStore.create(
        cap, (width,), init_fn=lambda i: jnp.asarray(values)[i],
        update=_sticky_rule, layout="auto")
    assert made.spec == auto.spec
    assert np.asarray(made.table)[:cap].tobytes() == table[:cap].tobytes()
    assert _rule_arms(auto.spec) == (
        "scatter_add", "xla_set")  # this is a CPU
    args = (jnp.asarray(ids), jnp.asarray(deltas),
            None if mask is None else jnp.asarray(mask))
    want, _ = store_mod.push_counted(dense.spec, dense.table, *args)
    handed = set()
    if arm == "tile_kernels":
        from flink_parameter_server_tpu.ops import row_update

        # off the TPU the arms are steered and the kernels interpreted
        steer_arms(combine="tile_kernel", write_back="tile_assign")
        real = row_update._sorted_tile_add_counted
        monkeypatch.setattr(
            row_update, "_sorted_tile_add_counted",
            lambda *a: handed.add(a[2].shape[1]) or real(*a))
    got, counted = store_mod.push_counted(auto.spec, auto.table, *args)
    pushed = ShardedParamStore(auto.spec, got)
    want = np.asarray(ShardedParamStore(dense.spec, want).values())
    assert np.asarray(pushed.values()).tobytes() == want.tobytes()
    assert not np.asarray(got)[:, width:].any()  # the pad lanes stay zero
    # the sums' and the write-back's kernels both took rows of `width` lanes
    assert handed == ({width} if arm == "tile_kernels" and len(ids) else set())
    if case == "nan_inf_and_minus_zero_neighbours" and width % 128:
        # ... and whatever the table's pad lanes hold comes back bit for bit,
        # in the rows the push rewrote too
        junk = np.asarray(auto.table).copy()
        junk[:, width:] = np.nan
        junk[::3, width:] = -0.0
        again, _ = store_mod.push_counted(auto.spec, jnp.asarray(junk), *args)
        again = np.asarray(again)
        assert again[:, :width].tobytes() == np.asarray(got)[:, :width].tobytes()
        assert again[:, width:].tobytes() == junk[:, width:].tobytes()
    live = (ids >= 0) & (ids < auto.spec.padded_capacity)
    if mask is not None:
        live &= mask
    kept = np.unique(ids[live])
    hit = np.zeros(cap, bool)
    hit[kept[kept < cap]] = True
    assert (want[hit] != values[hit]).any(axis=1).all()
    assert want[~hit].tobytes() == values[~hit].tobytes()
    assert int(counted["ps_rule_keys"]) == live.sum()
    assert int(counted["ps_rule_rows"]) == len(kept)
    assert int(counted["ps_rule_packed_rows"]) == len(kept)
    pulled = np.asarray(pushed.pull(jnp.asarray(np.clip(ids, 0, cap - 1))))
    assert pulled.tobytes() == want[np.clip(ids, 0, cap - 1)].tobytes()
    step = chunk or store_mod._RULE_CHUNK
    if arm == "xla":
        assert int(counted["ps_rule_tiles"]) == 0
        assert int(counted["ps_combine_kernel_lanes"]) == 0
        assert int(counted["ps_combine_kernel_writes"]) == 0
        return
    # the tile rows of eight the write-back read and wrote, chunk by chunk,
    # and those of the combine's block: the slots are the ranks
    assert int(counted["ps_rule_tiles"]) == sum(
        len(np.unique(kept[lo:lo + step] // 8))
        for lo in range(0, len(kept), step))
    assert int(counted["ps_combine_kernel_lanes"]) == live.sum()
    assert int(counted["ps_combine_kernel_writes"]) == -(-len(kept) // 8)


# A rule store's push hands a masked lane's delta on AS IT IS (until PR 57 a
# select over the whole pushed block zeroed it first): `_push_rule` sends the
# lane to the sentinel, and whatever it holds must reach no kept row in any of
# the combine's four arms.
MASKED_ARMS = [
    (3, "the_sort_carries_it"), (36, "wide_runs_scatter_add"),
    (36, "kernel_sums"), (602, "wide_runs_scatter_add"), (602, "tile_sums"),
]


@pytest.mark.parametrize("bad", [np.nan, np.inf, -np.inf])
@pytest.mark.parametrize("width, arm", MASKED_ARMS)
def test_a_masked_lanes_nan_reaches_no_kept_row_in_any_combine_arm(
        width, arm, bad, monkeypatch, steer_arms):
    """The table after a push whose masked lanes hold NaN or Inf is, bit for
    bit, the table after the same push with zeros there (what the parent's
    `_zero_masked` made of them).  The masked lanes keep LIVE ids, ids that
    live lanes of the same batch name too, and lie between live lanes: every
    block of 256 sorted lanes of the row kernel's MXU sums, and every tile of
    the tile kernel's, holds both."""
    from flink_parameter_server_tpu.core import store as store_mod

    rng = np.random.default_rng([width, MASKED_ARMS.index((width, arm))])
    cap, n = 77, 700
    values = rng.normal(size=(cap, width)).astype(np.float32)
    store = ShardedParamStore.from_values(
        jnp.asarray(values), update=_sticky_rule, layout="auto")
    kernels = arm in ("kernel_sums", "tile_sums")
    steer_arms(combine={
        "kernel_sums": "row_kernel", "tile_sums": "tile_kernel",
    }.get(arm, "scatter_add"))  # (a row the sort carries keeps the sort)
    if arm == "tile_sums":
        steer_arms(write_back="tile_assign")
    ids = rng.integers(0, cap, n).astype(np.int32)
    ids[: n // 4] = 11  # a hot row, a third of its lanes masked
    mask = rng.random(n) > 0.3
    # the same rows, live elsewhere (all but a row or two that no live lane
    # names, which must then stay as it is)
    assert len(set(ids[~mask]) & set(ids[mask])) > 60
    deltas = rng.normal(size=(n, width)).astype(np.float32)
    zeroed = np.where(mask[:, None], deltas, 0).astype(np.float32)
    planted = np.where(mask[:, None], deltas, bad).astype(np.float32)
    push = jax.jit(
        lambda table, d: store_mod.push_counted(
            store.spec, table, jnp.asarray(ids), d, jnp.asarray(mask)))
    want, counted_want = push(store.table, zeroed)
    got, counted = push(store.table, planted)
    assert np.isfinite(np.asarray(got)).all()
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    assert {k: int(v) for k, v in counted.items()} == {
        k: int(v) for k, v in counted_want.items()}
    assert int(counted["ps_rule_keys"]) == mask.sum()
    if width > 4:  # the arm is the one the case names
        assert (int(counted["ps_combine_kernel_lanes"]) > 0) == kernels


# The case table's flat wide stores on the arm that hands the tile kernel
# their rows UNPADDED (PR 57) against the arm that pads them to whole
# registers first (XLA's, the parent's path for both arms): the same table,
# every bit, the pad lanes too.
@pytest.mark.parametrize("traffic", [
    "half_masked", "zipf_hot", "neg_and_oob", "dead_lanes_minus_one",
    "run_over_a_block_and_a_call"])
@pytest.mark.parametrize("kind, shape", [
    ("add", (600,)), ("add", (2, 300)), ("add", (300,)), ("rule", (602,))],
    ids=str)
def test_push_pull_case_table_flat_wide_rows_unpadded_are_the_padded_bit_for_bit(
        kind, shape, traffic, monkeypatch, steer_arms):
    from flink_parameter_server_tpu.core import store as store_mod
    from flink_parameter_server_tpu.ops import row_update

    rng = np.random.default_rng(
        [len(shape), shape[-1], WIDE_TRAFFIC.index(traffic)])
    values = _init_values(CAP, shape)
    values[::5] = -0.0  # a masked lane that keeps a live id adds +0.0 to it
    store = ShardedParamStore.from_values(
        jnp.asarray(values), layout="auto",
        **({"update": _sticky_rule} if kind == "rule" else {}))
    width, lanes = int(np.prod(shape)), store.table.shape[1]
    assert store.spec.pack == 1 and width < lanes and lanes % 128 == 0
    ids, deltas, mask = _wide_traffic(traffic, rng, CAP, shape)
    if mask is not None:  # masked lanes keep ids that live lanes name too
        flat, on = ids.reshape(-1), mask.reshape(-1)
        assert set(flat[~on]) & set(flat[on])
    args = (jnp.asarray(ids), jnp.asarray(deltas),
            None if mask is None else jnp.asarray(mask))
    want, _ = store_mod.push_counted(store.spec, store.table, *args)
    handed = set()
    real = row_update._sorted_tile_add_counted
    monkeypatch.setattr(
        row_update, "_sorted_tile_add_counted",
        lambda *a: handed.add(a[2].shape[1]) or real(*a))
    monkeypatch.setattr(row_update, "MAX_LANES", 512)
    steer_arms(
        push="tile_add", combine="tile_kernel", write_back="tile_assign")
    # (a fresh program: an eager push would reuse one traced for another case)
    got, _ = jax.jit(
        lambda table, *a: store_mod.push_counted(store.spec, table, *a)
    )(store.table, *args)
    assert handed == {width}
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


@pytest.mark.parametrize("backend, shape, dtype, layout, combine, write_back, noted", [
    ("tpu", (602,), jnp.float32, "auto", True, True, 0),   # GloVe's row
    ("tpu", (129,), jnp.float32, "auto", True, True, 0),
    ("tpu", (256,), jnp.float32, "auto", True, True, 0),
    ("cpu", (602,), jnp.float32, "auto", False, False, 0),
    ("tpu", (602,), jnp.bfloat16, "auto", False, False, 2),
    # pinned dense, a wide row keeps XLA's arms: the combine says so
    ("tpu", (602,), jnp.float32, "dense", False, False, 1),
    # as before: a packed row of several to a register, a narrow row's tile
    ("tpu", (36,), jnp.float32, "auto", True, True, 0),
    ("tpu", (3,), jnp.float32, "auto", False, True, 0),
    ("tpu", (128,), jnp.float32, "auto", True, False, 0),
])
def test_a_flat_wide_rule_store_takes_the_tile_kernels_from_its_spec(
        backend, shape, dtype, layout, combine, write_back, noted, monkeypatch):
    from flink_parameter_server_tpu.core import store as store_mod
    from flink_parameter_server_tpu.ops import row_update

    spec = jax.eval_shape(lambda: ShardedParamStore.create(
        1000, shape, dtype=dtype, update=_sticky_rule, layout=layout)).spec
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(store_mod, "_REFUSALS_NOTED", set())
    n0 = row_update.refusal_count()
    width, flat = shape[0], spec.table_shape()[1] > 128
    want = (
        "sort" if width <= 4 else "scatter_add" if not combine
        else "tile_kernel" if flat else "row_kernel",
        "xla_set" if not write_back else "tile_set" if width <= 8
        else "tile_assign" if flat else "row_set")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        assert _rule_arms(spec) == want
    assert row_update.refusal_count() == n0 + noted
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # a refusal is noted once
        assert _rule_arms(spec) == want
    assert row_update.refusal_count() == n0 + noted


@pytest.mark.parametrize("shape, want, lanes", [
    ((602,), "packed", 640), ((129,), "packed", 256), ((256,), "packed", 256),
    ((36,), "packed", 128), ((3,), "dense", 4), ((128,), "dense", 128),
    ((65,), "packed", 128), ((101,), "packed", 128), ((127,), "packed", 128),
    ((2, 300), "dense", None),
])
def test_a_rule_row_resolves_by_its_width_and_its_reload_to_the_same(
        shape, want, lanes):
    from flink_parameter_server_tpu.core.store import _resolve_layout

    assert _resolve_layout("auto", _ema, shape) == want
    store = ShardedParamStore.create(40, shape, update=_ema, layout="auto")
    again = ShardedParamStore.from_values(
        store.values(), update=_ema, layout="auto")
    assert store.spec == again.spec and store.spec.layout == want
    if lanes is not None:
        assert store.table.shape[1] == lanes
    assert np.asarray(again.table).tobytes() == np.asarray(store.table).tobytes()


@pytest.mark.parametrize("masked", [False, True], ids=["all_live", "masked"])
def test_a_turned_push_through_the_tile_kernel_is_the_flat_arms_push(
        masked, monkeypatch, steer_arms):
    """Cell 10's push since PR 65, a combination no cell ran until then: the
    shift ``kernel_by_field`` (handed ``(d, K, B)``) in front of ``tile_add``
    (``ops/row_update``'s tile kernel).  Two 64-lane rows to a physical row,
    26 fields of 256 examples, a field its own rows and one field of three
    rows that every example repeats: the table XLA's flat arms leave (pads
    under a select, ``table.at[].add``), bit for bit, and the kernel's
    counts."""
    from flink_parameter_server_tpu.core import store as store_mod
    from flink_parameter_server_tpu.ops import packed

    width, block = 64, (26, 256)
    cards = np.array([3] + [400] * 25)
    firsts = np.concatenate([[0], np.cumsum(cards)[:-1]])
    cap = int(cards.sum())
    store = ShardedParamStore.from_values(
        jnp.asarray(_init_values(cap, (width,))), layout="auto")
    assert (store.spec.layout, store.spec.pack) == ("packed", 2)
    rng = np.random.default_rng([7, masked])
    ids = (rng.integers(0, cards[:, None], block) + firsts[:, None]).astype(np.int32)
    # dead lanes, dropped in every arm: negative, or past the padded table
    end = store.spec.padded_capacity
    ids[4, :3] = [-1, end, end + 9]
    ids = jnp.asarray(ids)
    deltas = jnp.asarray(rng.normal(size=block + (width,)).astype(np.float32))
    # (an example's mask beside each of its fields, as DLRM's is)
    mask = jnp.asarray(np.broadcast_to(rng.random(256) < 0.7, block)) if masked else None

    def push(*args, turned=False):
        return jax.jit(lambda table, i, d, m: store_mod.push_counted(
            store.spec, table, i, d, m, turned=turned))(store.table, *args)

    want, counted = push(ids.reshape(-1), deltas.reshape(-1, width),
                         None if mask is None else mask.reshape(-1))
    assert counted is None
    assert packed.by_field(*block)
    steer_arms(push="tile_add", shift="kernel_by_field")
    calls = []
    real = packed.lane_shift_kernel
    monkeypatch.setattr(
        packed, "lane_shift_kernel",
        lambda by_lane, ids, d, mask=None: calls.append(
            (by_lane.shape, ids.shape)) or real(by_lane, ids, d, mask))
    got, counted = push(ids, deltas, mask, turned=True)
    assert calls == [((width,) + block, block)], calls
    np.testing.assert_array_equal(
        np.asarray(got).view(np.uint32), np.asarray(want).view(np.uint32))
    assert not np.array_equal(np.asarray(got), np.asarray(store.table))
    # a masked lane keeps its id and adds a row of zeros; a dead one is dropped
    live = np.asarray(ids).reshape(-1)
    live = live[(live >= 0) & (live < end)]
    assert int(counted["ps_push_kernel_lanes"]) == live.size == ids.size - 3
    assert int(counted["ps_push_tile_rows"]) == len(np.unique(live // 2 // 8))


# -- THE CASE TABLE of `core/store.arms` --------------------------------------
# One case a row of the two tables in its docstring, in their order, the
# backend steered to the TPU the tables describe; then the same specs off it.
# (what, value_shape, update, layout, mesh (dp, ps), capacity, pull lanes,
#  push lanes, lanes over workers, the record, refusals noted on the way)
_RULE = "rule"
ARMS_ON_A_TPU = [
    ("dense 1 reg, lanes x 8 > rows", (128,), "add", "auto", None, 40_000,
     65_536, 65_536, False, ("take", "xla_add", "", "", "", False), 0),
    ("dense 1 reg, 1,024+ <= rows / 8", (128,), "add", "auto", None, 40_000,
     5_000, 5_000, False, ("take", "tile_add", "", "", "", False), 0),
    # cell 16 (PR 67): under ONE worker the kernel's push runs on the shards,
    # read as a store of a shard's block is: 5,000 x 8 <= 160,000 / 4 rows
    ("1 reg over ps 4, shard's rows / 8", (128,), "add", "auto", (1, 4),
     160_000, 5_000, 5_000, False, ("take", "tile_add", "", "", "", True), 0),
    ("dense 1 reg, dp 4, shard <= lanes", (128,), "add", "auto", (4, 1), 96,
     256, 256, True, ("take", "worker_reduce", "", "", "", False), 0),
    ("packed k 7, lanes x 8 > rows", (17,), "add", "auto", None, 7_000,
     8_192, 8_192, False,
     ("packed_kernel", "xla_add", "kernel", "", "", False), 0),
    ("the same over ps 4, dp 1", (17,), "add", "auto", (1, 4), 7_000,
     8_192, 8_192, False,
     ("packed_kernel", "xla_add", "kernel", "", "", False), 0),
    # a block of two axes whose logic takes it turned (`FIELDS`): both lane
    # kernels a field at a time where the batch is whole blocks
    ("packed k 7, fields 39", (17,), "add", "auto", None, 7_000,
     39 * 256, 39 * 256, False,
     ("packed_kernel_by_field", "xla_add", "kernel_by_field", "", "", False),
     0),
    ("fields 39 over ps 4, dp 1", (17,), "add", "auto", (1, 4), 7_000,
     39 * 256, 39 * 256, False,
     ("packed_kernel_by_field", "xla_add", "kernel_by_field", "", "", False),
     0),
    ("fields 39, the batch no blocks", (17,), "add", "auto", None, 7_000,
     39 * 250, 39 * 250, False,
     ("packed_kernel", "xla_add", "kernel", "", "", False), 0),
    ("packed k 2, 1,024+ <= rows / 8", (64,), "add", "auto", None, 80_000,
     4_096, 4_096, False,
     ("packed_kernel", "tile_add", "kernel", "", "", False), 0),
    # cell 10 since PR 65: the tile kernel behind the shift a field at a time
    ("k 2, fields 26, 1,024+ <= rows/8", (64,), "add", "auto", None, 120_000,
     26 * 256, 26 * 256, False,
     ("packed_kernel_by_field", "tile_add", "kernel_by_field", "", "", False),
     0),
    ("packed k 7, under a block of ids", (17,), "add", "auto", None, 7_000,
     312, 312, False, ("packed_selects", "xla_add", "selects", "", "", False),
     2),  # the slice and the shift: noted
    ("packed k 1, 5 regs", (2, 300), "add", "auto", None, 61,
     8_192, 8_192, False,
     ("packed_selects", "tile_add", "selects", "", "", False), 0),
    ("5 regs over ps 4, dp 1", (640,), "add", "auto", (1, 4), 61,
     8_192, 8_192, False, ("take", "tile_add", "", "", "", True), 0),
    ("5 regs over ps 2, dp 2", (640,), "add", "auto", (2, 2), 61,
     8_192, 8_192, False, ("take", "xla_add", "", "", "", False),
     1),  # the push on the shards: the batch lies split over dp, noted
    # cell 18 (PR 75): stores a step only READS (`only_read`: no push arm is
    # asked and none noted), one of them int32 scalar rows, XLA's arms
    ("dense 1 reg, only read", (128,), "add", "auto", None, 40_000,
     5_000, 0, False, ("take", "", "", "", "", False), 0),
    ("int32 scalars, k 128, only read", (), "add", "auto", None, 40_000,
     8_192, 0, False, ("packed_selects", "", "", "", "", False), 0),
    # cell 6 since PR 70: rows a sort carries, in one place, are read a
    # DISTINCT row at a time, for the logic and the rule both
    ("3 lanes, held at its tile of 4", (3,), _RULE, "auto", None, 1_000,
     8_192, 8_192, False,
     ("narrow_distinct", "rule", "", "sort", "tile_set", False), 0),
    # (a mesh's table has no tile, and its pull is GSPMD's or the shards')
    ("3 lanes over ps 4, dp 1", (3,), _RULE, "auto", (1, 4), 1_000,
     8_192, 8_192, False, ("take", "rule", "", "sort", "xla_set", True), 0),
    ("6 lanes, held at its tile of 8", (6,), _RULE, "auto", None, 1_000,
     8_192, 8_192, False,
     ("narrow", "rule", "", "row_kernel", "tile_set", False), 0),
    ("(2, 2) lanes: rank 2, no tile", (2, 2), _RULE, "auto", None, 1_000,
     8_192, 8_192, False, ("take", "rule", "", "sort", "xla_set", False),
     1),  # the write-back of a narrow row not held at its tile: noted
    ("packed k 3 (36 lanes)", (36,), _RULE, "auto", None, 3_000,
     8_192, 8_192, False,
     ("packed_kernel", "rule", "", "row_kernel", "row_set", False), 0),
    ("the same over ps 4, dp 1", (36,), _RULE, "auto", (1, 4), 3_000,
     8_192, 8_192, False,
     ("packed_kernel", "rule", "", "row_kernel", "row_set", True), 0),
    ("the same over ps 2, dp 2", (36,), _RULE, "auto", (2, 2), 3_000,
     8_192, 8_192, False,
     ("packed_kernel", "rule", "", "scatter_add", "xla_set", False),
     1),  # the push on the shards: the batch lies split over dp, noted
    ("packed k 1, 1 reg (100 lanes)", (100,), _RULE, "auto", None, 1_000,
     8_192, 8_192, False,
     ("packed_selects", "rule", "", "row_kernel", "row_set", False), 0),
    ("packed k 1, 5 regs (602 lanes)", (602,), _RULE, "auto", None, 1_000,
     8_192, 8_192, False,
     ("packed_selects", "rule", "", "tile_kernel", "tile_assign", False), 0),
    ("dense 1 reg (pinned, 100)", (100,), _RULE, "dense", None, 1_000,
     8_192, 8_192, False,
     ("take", "rule", "", "row_kernel", "xla_set", False), 0),
    # the worker's part of a row (`WORKER_WIDTHS`): the pull arms and the
    # write-backs of the whole row, the combine by the width that is pushed
    ("packed k 3, the worker's 20 / 36", (36,), _RULE, "auto", None, 3_000,
     8_192, 8_192, False,
     ("packed_kernel", "rule", "", "row_kernel", "row_set", False), 0),
    ("the worker's 20 / 36 over ps 4", (36,), _RULE, "auto", (1, 4), 3_000,
     8_192, 8_192, False,
     ("packed_kernel", "rule", "", "row_kernel", "row_set", True), 0),
    ("the worker's 20 / 36, fields 39", (36,), _RULE, "auto", None, 3_000,
     39 * 256, 39 * 256, False,
     ("packed_kernel_by_field", "rule", "", "row_kernel", "row_set", False),
     0),
    ("20 / 36, fields 39, over ps 4", (36,), _RULE, "auto", (1, 4), 3_000,
     39 * 256, 39 * 256, False,
     ("packed_kernel_by_field", "rule", "", "row_kernel", "row_set", True), 0),
    ("5 regs, the worker's 301 / 602", (602,), _RULE, "auto", None, 1_000,
     8_192, 8_192, False,
     ("packed_selects", "rule", "", "tile_kernel", "tile_assign", False), 0),
    ("5 regs, the worker's 100 / 602", (602,), _RULE, "auto", None, 1_000,
     8_192, 8_192, False,
     ("packed_selects", "rule", "", "row_kernel", "tile_assign", False), 0),
    ("5 regs, the worker's 3 / 602", (602,), _RULE, "auto", None, 1_000,
     8_192, 8_192, False,
     ("packed_selects", "rule", "", "sort", "tile_assign", False), 0),
    ("1 reg, the worker's 100 / 101", (101,), _RULE, "auto", None, 1_000,
     8_192, 8_192, False,
     ("packed_selects", "rule", "", "row_kernel", "row_set", False), 0),
    ("1 reg, 100 / 101 over ps 4", (101,), _RULE, "auto", (1, 4), 1_000,
     8_192, 8_192, False,
     ("packed_selects", "rule", "", "row_kernel", "row_set", True), 0),
    # a wide rule row whose worker's part is ONE WHOLE register (cell 15's
    # (w[128], G[128])): the combine is the row kernel's, the write-back the
    # tile kernel's, in one push
    ("2 regs, the worker's 128 / 256", (256,), _RULE, "auto", None, 1_000,
     8_192, 8_192, False,
     ("packed_selects", "rule", "", "row_kernel", "tile_assign", False), 0),
    ("2 regs, 128 / 256 over ps 4", (256,), _RULE, "auto", (1, 4), 1_000,
     8_192, 8_192, False,
     ("packed_selects", "rule", "", "row_kernel", "tile_assign", True), 0),
]
WORKER_WIDTHS = {
    "packed k 3, the worker's 20 / 36": 20, "the worker's 20 / 36 over ps 4": 20,
    "5 regs, the worker's 301 / 602": 301, "5 regs, the worker's 100 / 602": 100,
    "5 regs, the worker's 3 / 602": 3,
    "1 reg, the worker's 100 / 101": 100, "1 reg, 100 / 101 over ps 4": 100,
    "the worker's 20 / 36, fields 39": 20, "20 / 36, fields 39, over ps 4": 20,
    "2 regs, the worker's 128 / 256": 128, "2 regs, 128 / 256 over ps 4": 128,
}
# a table of integers (a graph's adjacency): XLA's arms on a TPU too
DTYPES = {"int32 scalars, k 128, only read": jnp.int32}
# the keys an example of a block of two axes that its logic takes TURNED
FIELDS = {
    "packed k 7, fields 39": 39, "fields 39 over ps 4, dp 1": 39,
    "fields 39, the batch no blocks": 39, "k 2, fields 26, 1,024+ <= rows/8": 26,
    "the worker's 20 / 36, fields 39": 39, "20 / 36, fields 39, over ps 4": 39,
}
# off a TPU: XLA's forms; where the push runs is read from the mesh alone
ARMS_OFF_IT = {
    "dense 1 reg, 1,024+ <= rows / 8": ("take", "xla_add", "", "", "", False),
    "1 reg over ps 4, shard's rows / 8": ("take", "xla_add", "", "", "", False),
    "5 regs over ps 4, dp 1": ("take", "xla_add", "", "", "", False),
    "5 regs over ps 2, dp 2": ("take", "xla_add", "", "", "", False),
    "dense 1 reg, dp 4, shard <= lanes": (
        "take", "worker_reduce", "", "", "", False),
    "packed k 2, 1,024+ <= rows / 8": (
        "packed_selects", "xla_add", "selects", "", "", False),
    "packed k 7, fields 39": (
        "packed_selects", "xla_add", "selects", "", "", False),
    "k 2, fields 26, 1,024+ <= rows/8": (
        "packed_selects", "xla_add", "selects", "", "", False),
    "packed k 1, 5 regs": ("packed_selects", "xla_add", "selects", "", "", False),
    "dense 1 reg, only read": ("take", "", "", "", "", False),
    "int32 scalars, k 128, only read": (
        "packed_selects", "", "", "", "", False),
    "3 lanes, held at its tile of 4": (
        "narrow", "rule", "", "sort", "xla_set", False),
    "packed k 3 (36 lanes)": (
        "packed_selects", "rule", "", "scatter_add", "xla_set", False),
    "packed k 1, 5 regs (602 lanes)": (
        "packed_selects", "rule", "", "scatter_add", "xla_set", False),
    "5 regs, the worker's 301 / 602": (
        "packed_selects", "rule", "", "scatter_add", "xla_set", False),
    "5 regs, the worker's 3 / 602": (
        "packed_selects", "rule", "", "sort", "xla_set", False),
    "1 reg, the worker's 100 / 101": (
        "packed_selects", "rule", "", "scatter_add", "xla_set", False),
    "2 regs, the worker's 128 / 256": (
        "packed_selects", "rule", "", "scatter_add", "xla_set", False),
}


@pytest.mark.parametrize("backend, row", [
    ("tpu", i) for i in range(len(ARMS_ON_A_TPU))
] + [
    ("cpu", i) for i, c in enumerate(ARMS_ON_A_TPU) if c[0] in ARMS_OFF_IT
], ids=lambda v: v if isinstance(v, str) else ARMS_ON_A_TPU[v][0])
def test_the_arms_table(backend, row, mesh_devices, monkeypatch):
    """``core/store.arms`` is the one reader of which form a pull and a push
    take; its docstring's case table is held here row by row."""
    import dataclasses

    from flink_parameter_server_tpu.core import store as store_mod
    from flink_parameter_server_tpu.ops import row_update
    from flink_parameter_server_tpu.parallel.mesh import make_mesh

    (what, shape, update, layout, mesh_shape, capacity, pull_lanes,
     push_lanes, over_workers, want, noted) = ARMS_ON_A_TPU[row]
    if backend == "cpu":
        want = ARMS_OFF_IT[what]
        noted = 0
    mesh = mesh_shape and make_mesh(
        *mesh_shape, devices=mesh_devices[:mesh_shape[0] * mesh_shape[1]])
    rule = _sticky_rule if update == _RULE else "add"
    part = WORKER_WIDTHS.get(what)
    spec = store_mod.StoreSpec(
        capacity, shape, update=rule, mesh=mesh or None,
        layout=store_mod._resolve_layout(layout, rule, shape),
        worker_width=part, dtype=DTYPES.get(what, jnp.float32))
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(store_mod, "_REFUSALS_NOTED", set())
    n0 = row_update.refusal_count()

    def read(**width):
        return dataclasses.astuple(store_mod.arms(
            spec, pull_lanes=pull_lanes, push_lanes=push_lanes,
            lanes_over_workers=over_workers, fields=FIELDS.get(what),
            only_read=what.endswith("only read"), **width))

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        assert read() == want
    assert len(caught) == noted == row_update.refusal_count() - n0
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # a refusal is noted once
        assert read() == want
        if part is not None:
            # a step's push is the worker's part; whole-row deltas (a bare
            # `push`) get the combine of the store that names no part
            assert read(push_width=part) == want
            whole = dataclasses.astuple(store_mod.arms(
                dataclasses.replace(spec, worker_width=None),
                pull_lanes=pull_lanes, push_lanes=push_lanes,
                fields=FIELDS.get(what)))
            assert read(push_width=spec.row_width) == whole
    assert row_update.refusal_count() == n0 + noted


def test_the_arms_table_has_a_case_a_row_of_the_docstring():
    """The docstring of ``arms`` IS the case table: its data rows, in order,
    are the cases above (first column against first column)."""
    from flink_parameter_server_tpu.core import store as store_mod

    # (a row ends with the PRs that priced it; the headers end with "PR")
    rows = [
        line.strip() for line in store_mod.arms.__doc__.splitlines()
        if "  " in line.strip() and line.split()[-1].isdigit()]
    assert len(rows) == len(ARMS_ON_A_TPU)
    for row, case in zip(rows, ARMS_ON_A_TPU):
        assert row.startswith(case[0]), (row, case[0])
        want = case[9]
        cells = row[len(case[0]):].split()
        got = [c for c in cells if c in {
            "take", "narrow", "narrow_distinct", "packed_selects",
            "packed_kernel",
            "packed_kernel_by_field", "kernel_by_field", "xla_add",
            "tile_add", "worker_reduce", "selects", "kernel", "sort",
            "scatter_add", "row_kernel", "tile_kernel", "xla_set", "tile_set",
            "row_set", "tile_assign"}]
        assert got == [w for w in want if w not in ("", "rule", True, False)]


# -- the worker's part of a rule store's row ---------------------------------
# ``StoreSpec.worker_width``: a step pulls and pushes the leading lanes a
# worker reads and writes; the rule still reads and writes whole rows.  The
# lanes left out only ever carried +0.0 that no rule reads, so the table is
# the whole-row step's, bit for bit.
PART_ROWS = [  # (what, row width, the worker's part, layout, physical lanes)
    ("dense", 100, 37, "dense", 100),
    ("packed_k3", 36, 20, "auto", 128),
    ("flat_wide_k1", 260, 131, "auto", 384),
    ("one_register_k1", 101, 100, "auto", 128),  # PBG's row: cell 14
]
PART = {what: part for what, _, part, _, _ in PART_ROWS}


def _part_rule(part):
    """A rule over a row whose first ``part`` lanes are the worker's: it
    reads ``combined`` by lane number over those alone, moves every lane of
    a touched row, and each new lane is ONE float32 operation (two programs
    that fuse it differently still round it alike)."""

    def rule(current, combined):
        g = combined[..., :part]
        return jnp.concatenate(
            [current[..., :part] - g, current[..., part:] + g[..., :1]],
            axis=-1)

    return rule


class _PartLogic:
    """Pushes gradients at the width the rows came: the worker's part, or
    whole rows with zeros for the server's lanes (``models/difacto.py``)."""

    def __init__(self, part):
        self.part = part

    def init_state(self, rng):
        return ()

    def keys(self, batch):
        return batch["ids"]

    def step(self, state, batch, pulled):
        g = pulled[..., :self.part] * batch["x"][..., None]
        # what a dropped lane carries reaches no row, whatever it is
        g = jnp.where(batch["bad"][..., None], batch["poison"], g)
        past = pulled.shape[-1] - self.part
        if past:
            g = jnp.concatenate(
                [g, jnp.zeros(g.shape[:-1] + (past,), g.dtype)], axis=-1)
        out = {"lanes": jnp.asarray(pulled.shape[-1], jnp.int32)}
        return state, PushRequest(batch["ids"], g, batch["mask"]), out


def _part_store(what, cap, mesh, rng, part="own"):
    _, width, own, layout, lanes = next(r for r in PART_ROWS if r[0] == what)
    values = rng.normal(size=(cap, width)).astype(np.float32)
    part = own if part == "own" else part
    store = ShardedParamStore.create(
        cap, (width,), init_fn=lambda ids: jnp.asarray(values)[ids],
        update=_part_rule(own), mesh=mesh, layout=layout, worker_width=part)
    assert store.table.shape[1] == lanes and store.spec.worker_width == part
    return store, values


def _part_batch(rng, cap, poison):
    ids = rng.integers(0, cap, (32, 6)).astype(np.int32)
    ids[:, 0] = 7  # a hot row
    ids[3, 1:4] = [-1, cap + 50, 2 ** 31 - 1]  # dead lanes
    mask = rng.random(ids.shape) > 0.2
    bad = ~mask | (ids < 0) | (ids >= cap)
    return {
        "ids": ids, "x": rng.normal(size=ids.shape).astype(np.float32),
        "mask": mask, "bad": bad, "poison": np.float32(poison)}


@pytest.mark.parametrize("poison", [0.0, np.nan, np.inf])
@pytest.mark.parametrize("place", ["one_place", "ps4", "one_place_kernels"])
@pytest.mark.parametrize("what", [r[0] for r in PART_ROWS])
def test_a_step_with_the_workers_part_is_the_whole_row_step_bit_for_bit(
        what, place, poison, ps_mesh, steer_arms):
    from flink_parameter_server_tpu.core import store as store_mod
    from flink_parameter_server_tpu.core.transform import make_train_step

    seed = [sorted(PART).index(what), place == "ps4"]
    cap = 500
    mesh = ps_mesh if place == "ps4" else None
    part_store, values = _part_store(
        what, cap, mesh, np.random.default_rng(seed))
    whole_store, _ = _part_store(
        what, cap, mesh, np.random.default_rng(seed), part=None)
    assert np.asarray(whole_store.values()).tobytes() == values.tobytes()
    if place == "one_place_kernels":  # interpreted off the TPU
        steer_arms(
            combine=lambda s: "tile_kernel" if (
                s.worker_width or s.row_width) > 128 else "row_kernel",
            write_back=lambda s: "xla_set" if s.layout == "dense" else (
                "tile_assign" if s.row_width > 128 else "row_set"))
    batch = _part_batch(np.random.default_rng(seed + [1]), cap, poison)
    logic = _PartLogic(PART[what])
    tables, lanes = [], []
    for store in (part_store, whole_store):
        table = store.table
        step = jax.jit(make_train_step(logic, store.spec))
        for _ in range(2):  # the second step reads what the first wrote
            table, _, out = step(table, (), batch)
        tables.append(np.asarray(
            ShardedParamStore(store.spec, table).values()))
        lanes.append(int(out["lanes"]))
        counted = {k: int(v) for k, v in out.items() if k.startswith("ps_")}
        if store is part_store:
            assert counted["ps_pull_row_lanes"] == PART[what]
            assert counted["ps_push_row_lanes"] == PART[what]
        else:
            assert "ps_pull_row_lanes" not in counted
            assert "ps_push_row_lanes" not in counted
    width = values.shape[1]
    assert lanes == [PART[what], width]
    assert tables[0].tobytes() == tables[1].tobytes()
    assert np.isfinite(tables[0]).all()
    live = ~batch["bad"]
    hit = np.zeros(cap, bool)
    hit[np.unique(batch["ids"][live])] = True
    assert (tables[0][hit] != values[hit]).all(axis=1).all()  # every lane
    assert tables[0][~hit].tobytes() == values[~hit].tobytes()
    # the public pull keeps whole rows; the step's hands out the part
    probe = jnp.asarray(batch["ids"][:4])
    after = ShardedParamStore(part_store.spec, part_store.table)
    rows = np.asarray(after.pull(probe))
    assert rows.shape == (4, 6, width)
    got = np.asarray(store_mod.pull(
        after.spec, after.table, probe, worker_part=True))
    assert got.tobytes() == rows[..., :PART[what]].tobytes()
    # ... and of a store that names no part, whole rows either way
    assert store_mod.pull(
        whole_store.spec, whole_store.table, probe, worker_part=True
    ).shape == (4, 6, width)


@pytest.mark.parametrize("traffic", TRAFFIC)
@pytest.mark.parametrize("what", [r[0] for r in PART_ROWS])
def test_push_pull_case_table_the_workers_part(what, traffic):
    """The case table's traffic pushed at the worker's width into a rule
    store (``current + combined`` over the part, the server's lanes left as
    they are) against the float64 ``np.add.at``, and against the same
    deltas pushed as whole rows with zero lanes."""
    from flink_parameter_server_tpu.core import store as store_mod

    _, width, part, layout, _ = next(r for r in PART_ROWS if r[0] == what)
    rng = np.random.default_rng([width, TRAFFIC.index(traffic)])
    values = _init_values(CAP, (width,))

    def rule(current, combined):
        return jnp.concatenate(
            [current[..., :part] + combined[..., :part], current[..., part:]],
            axis=-1)

    store = ShardedParamStore.create(
        CAP, (width,), init_fn=lambda ids: jnp.asarray(values)[ids],
        update=rule, layout=layout, worker_width=part)
    ids, deltas, mask = _traffic(traffic, rng, CAP, (part,))
    args = (jnp.asarray(ids), None if mask is None else jnp.asarray(mask))
    pushed = store.push(args[0], jnp.asarray(deltas), args[1])
    got = np.asarray(pushed.values()).astype(np.float64)
    want, mag = _reference(values[:, :part], ids, deltas, mask)
    off = np.abs(got[:, :part] - want)
    assert (off <= 64 * np.finfo(np.float32).eps * mag + 1e-30).all()
    assert got[:, part:].tobytes() == values[:, part:].astype(
        np.float64).tobytes()
    whole = np.zeros(ids.shape + (width,), np.float32)
    whole[..., :part] = deltas
    twin = store.push(args[0], jnp.asarray(whole), args[1])
    assert np.asarray(twin.values()).tobytes() == np.asarray(
        pushed.values()).tobytes()
    pulled = np.asarray(store_mod.pull(
        pushed.spec, pushed.table, jnp.asarray(ids), worker_part=True))
    assert pulled.shape == ids.shape + (part,)
    below = (ids >= 0) & (ids < CAP)
    assert pulled[below].tobytes() == np.asarray(
        pushed.values())[ids[below], :part].tobytes()


def test_a_push_at_neither_width_and_a_part_no_row_has_raise():
    store, _ = _part_store("packed_k3", 50, None, np.random.default_rng(0))
    ids = jnp.arange(4)
    for width in (20, 36):  # the worker's part, the whole row
        store.push(ids, jnp.ones((4, width)))
    for width in (19, 21, 35, 1):
        with pytest.raises(ValueError, match=r"worker's part, \(20,\)"):
            store.push(ids, jnp.ones((4, width)))
    whole, _ = _part_store(
        "packed_k3", 50, None, np.random.default_rng(0), part=None)
    with pytest.raises(ValueError, match=r"does not match ids"):
        whole.push(ids, jnp.ones((4, 20)))  # the spec names no part
    import dataclasses

    for bad in (dict(update="add"), dict(value_shape=(2, 18)),
                dict(worker_width=0), dict(worker_width=37)):
        with pytest.raises(ValueError, match="worker_width"):
            dataclasses.replace(store.spec, **bad)


# -- the third naming rule: `mesh.<what>` on what crosses between chips --------
# Every arm of a step under a mesh that moves data between chips names the
# expression the transfer is made from (`training/tracing.mesh_scope`, inside
# the `ps.*` scope it stands in) and says how many bytes it moves; the step
# hands their sum out as `ps_mesh_kib` (`core/store.step_counts` defines a
# transfer's bytes, the collective's result on ONE chip, and the unit).  A step in one place
# names nothing and keeps its outputs.
class _EchoLogic:
    """Pulls a block of keys, pushes a tenth of each pulled row back; with
    ``example_blocks`` it also sums the rows block by block into its state
    (the association ``core/batched.sums_by_blocks`` writes down)."""

    def __init__(self, example_blocks=None):
        if example_blocks:
            self.example_blocks = example_blocks

    def keys(self, batch):
        return batch["ids"]

    def step(self, state, batch, pulled):
        from flink_parameter_server_tpu.core.batched import sums_by_blocks

        blocks = getattr(self, "example_blocks", 1)
        state = state + sums_by_blocks(
            lambda rows: rows.sum(axis=(0, 1)), blocks, pulled)
        return state, PushRequest(batch["ids"], -0.1 * pulled, None), {
            "seen": jnp.sum(batch["ids"] >= 0)}


B, K = 64, 3  # the test batch: 64 examples of 3 keys
MESH_ARMS = {
    # arm: (mesh (dp, ps), row width, update, blocks, steered arm,
    #       {mesh name: [bytes of each transfer, by hand]})
    "packed_pull_on_shards": ((1, 4), 17, "add", None, {}, {
        "mesh.pull_rows_sum": [B * K * 17 * 4]}),
    "packed_pull_over_dp_x_ps": ((2, 2), 17, "add", None, {}, {
        "mesh.pull_rows_sum": [B * K * 17 * 4 // 2]}),  # a worker's half
    "take_on_shards": ((1, 4), 128, "add", None, {}, {
        "mesh.pull_rows_sum": [B * K * 128 * 4]}),
    "compute_over_servers": ((1, 4), 128, "add", 4, {}, {
        # the scatter leaves a chip its quarter; the deltas are gathered
        # whole; every chip holds the four blocks' sums of the one leaf
        "mesh.pull_rows_sum": [B * K * 128 * 4 // 4],
        "mesh.push_deltas_gather": [B * K * 128 * 4],
        "mesh.block_sums_gather": [4 * 128 * 4]}),
    "rule_on_shards": ((1, 4), 36, "rule", None, {}, {
        "mesh.pull_rows_sum": [B * K * 36 * 4],
        # (six counts, in the first lanes of one whole tile a shard)
        "mesh.push_counts_gather": [4 * 8 * 128 * 4]}),
    "add_on_shards": ((1, 4), 128, "add", None, dict(
        push="tile_add", on_shards=lambda spec: spec.mesh is not None), {
        "mesh.pull_rows_sum": [B * K * 128 * 4],
        "mesh.push_counts_gather": [4 * 8 * 128 * 4]}),  # lanes, tile rows
    "delta_reduce_over_workers": ((4, 1), 128, "add", None, {}, {
        # the one transfer named before the rule: its name stands
        # (a table of 100 rows, padded to 104: whole tile rows of eight)
        "ps.delta_reduce": [104 * 128 * 4]}),
}


def _echo_step(arm, mesh_devices, steer_arms, meshed=True):
    from flink_parameter_server_tpu.parallel.mesh import make_mesh

    (dp, ps), width, update, blocks, steered, moved = MESH_ARMS[arm]
    mesh = make_mesh(dp, ps, devices=mesh_devices[:dp * ps]) if meshed else None
    # (cell 8's arm sums worker by worker where a shard has no more rows
    # than the batch has lanes)
    rows = 100 if arm == "delta_reduce_over_workers" else 4000
    spec = jax.eval_shape(lambda: ShardedParamStore.create(
        rows, (width,), mesh=mesh, layout="auto",
        **({} if update == "add" else {"update": _sticky_rule}))).spec
    if steered and meshed:
        steer_arms(**steered)
    step = make_train_step(_EchoLogic(blocks), spec)
    args = (jax.ShapeDtypeStruct(spec.table_shape(), jnp.float32),
            jax.ShapeDtypeStruct((width,), jnp.float32),
            {"ids": jax.ShapeDtypeStruct((B, K), jnp.int32)})
    return spec, step, args, moved


@pytest.mark.parametrize("arm", sorted(MESH_ARMS))
def test_a_mesh_arm_names_its_transfers_and_the_step_hands_out_their_bytes(
        arm, mesh_devices, steer_arms):
    from flink_parameter_server_tpu.training import tracing

    spec, step, args, moved = _echo_step(arm, mesh_devices, steer_arms)
    with tracing.mesh_tally() as tally:
        text = jax.jit(step).lower(*args).as_text(debug_info=True)
    outs = jax.eval_shape(step, *args)[2]
    # the sites, name by name, against the shapes by hand
    assert {"mesh." + k if k != "delta_reduce" else "ps.delta_reduce": v
            for k, v in tally.items()} == moved
    for name in moved:
        assert f"{name}/" in text, name
    assert ("mesh." in text) == any(n.startswith("mesh.") for n in moved)
    # ... and their sum, a constant of the trace, among the step's outputs
    lowered = jax.jit(lambda *a: step(*a)[2]).lower(*args).as_text()
    total = sum(b for sizes in moved.values() for b in sizes)
    assert outs["ps_mesh_kib"].dtype == jnp.int32
    assert f"dense<{-(-total // 1024)}> : tensor<i32>" in lowered
    assert [k for k in outs if "mesh" in k] == ["ps_mesh_kib"]
    assert "seen" in outs  # beside the logic's own


@pytest.mark.parametrize("arm", sorted(MESH_ARMS))
def test_a_step_in_one_place_names_no_transfer_and_hands_out_no_bytes(
        arm, mesh_devices, steer_arms):
    spec, step, args, _ = _echo_step(arm, mesh_devices, steer_arms, meshed=False)
    text = jax.jit(step).lower(*args).as_text(debug_info=True)
    assert "mesh." not in text and "ps.delta_reduce" not in text
    assert not [k for k in jax.eval_shape(step, *args)[2] if "mesh" in k]


@pytest.mark.parametrize("moved, kib", [
    ({}, None),  # no site spoke: no output
    ({"pull_rows_sum": [1]}, 1),  # rounded UP: a byte is a KiB's worth
    ({"pull_rows_sum": [1024], "push_counts_gather": [1]}, 2),
    # past what an int32 of bytes holds (cell 16 at four times its batch)
    ({"pull_rows_sum": [2**31], "push_deltas_gather": [3 * 2**31 + 5]},
     2**23 + 1),
    ({"pull_rows_sum": [2**40]}, 2**30),  # a TiB a chip a step
])
def test_the_tally_leaves_the_step_in_kib_and_the_gauge_reads_bytes(moved, kib):
    """A constant of the trace must never stop the trace: ``ps_mesh_kib`` is
    an int32 of KiB, which holds 2 TiB a chip a step where bytes held 2 GiB
    (``jnp.asarray(2**31, jnp.int32)`` raises), and ``publish_counts`` turns
    it back into the gauge's bytes on the host."""
    from flink_parameter_server_tpu.core import store as store_mod
    from flink_parameter_server_tpu.telemetry.registry import MetricsRegistry

    spec = jax.eval_shape(
        lambda: ShardedParamStore.create(64, (128,), layout="dense")).spec
    outs = jax.jit(lambda: store_mod.step_counts(
        spec, None, pull_lanes=8, push_lanes=8, crossings=moved))()
    registry = MetricsRegistry()
    store_mod.publish_counts(outs, registry, int, int)
    gauges = {i.name: i.value for i in registry.instruments()}
    if kib is None:
        assert "ps_mesh_kib" not in outs and "store_mesh_bytes" not in gauges
        return
    assert outs["ps_mesh_kib"].dtype == jnp.int32
    assert int(outs["ps_mesh_kib"]) == kib
    total = sum(b for sizes in moved.values() for b in sizes)
    assert 0 <= gauges["store_mesh_bytes"] - total < 1024
    assert not [k for k in gauges if k.startswith("store_mesh_") and
                k != "store_mesh_bytes"]


# -- a narrow rule store pulls a batch's DISTINCT rows once (PR 70) ----------
# `core/store.arms`' pull arm ``narrow_distinct`` (cell 6 on a TPU; steered in
# here): one sort finds the distinct ids, their rows are gathered once, go back
# to the batch's lanes by shifted selects and a sort (`ops/dedup.spread_runs`),
# and the push of the same keys runs its rule on them.  The pulled block is
# ``jnp.take``'s and the table the parent path's, bit for bit.
def _criteo_like(rng, examples, rows):
    # integer fields of one row each, small and large fields of their own
    return np.stack([
        np.full(examples, 0), np.full(examples, 1),
        2 + rng.integers(0, 3, examples), 5 + rng.integers(0, 40, examples),
        45 + rng.integers(0, rows - 45, examples)], axis=1)


def _masked(rng, examples, rows):
    ids = _criteo_like(rng, examples, rows)
    live = rng.random(ids.shape) < 0.7
    ids[5, 4], live[5, 4] = rows - 1, False  # a row no live lane names
    return ids, live


def _bad_ids(rng, examples, rows):
    ids = _criteo_like(rng, examples, rows)
    ids[rng.random(ids.shape) < 0.1] = -1
    ids[rng.random(ids.shape) < 0.1] = rows + 1_000_000
    ids[0, :2] = [-7, np.iinfo(np.int32).max]
    return ids


# (what, (examples, fields), rows, the loop's chunk or None for its own, ids)
DISTINCT_BATCHES = {
    "criteo_like_duplicates": (
        (60, 5), 400, 64, lambda rng: _criteo_like(rng, 60, 400)),
    "all_distinct": (
        (60, 5), 400, 64,
        lambda rng: rng.permutation(400)[:300].reshape(60, 5)),
    "one_id": ((60, 5), 400, 64, lambda rng: np.full((60, 5), 17)),
    "a_run_longer_than_a_chunk": (
        (60, 5), 400, 64, lambda rng: np.where(
            rng.random((60, 5)) < 0.6, 200, rng.integers(0, 400, (60, 5)))),
    "masked_lanes_with_nan_deltas": (
        (60, 5), 400, 64, lambda rng: _masked(rng, 60, 400)),
    "negative_and_out_of_range_ids": (
        (60, 5), 400, 64, lambda rng: _bad_ids(rng, 60, 400)),
    "shorter_than_a_chunk": (
        (9, 5), 400, None, lambda rng: _criteo_like(rng, 9, 400)),
    "a_batch_of_one_lane": ((1, 1), 400, None, lambda rng: np.full((1, 1), 3)),
}


def _ftrl_that_shows_its_rows():
    from flink_parameter_server_tpu.models import logistic_ftrl as lf

    class Logic(lf.LogisticFTRL):
        """FTRL's step, the pulled block among its outputs and a masked
        lane's delta NaN: what a dropped lane holds reaches no row."""

        def step(self, state, batch, pulled):
            state, req, out = super().step(state, batch, pulled)
            deltas = jnp.where(req.mask[..., None], req.deltas, jnp.nan)
            return state, PushRequest(req.ids, deltas, req.mask), {
                **out, "pulled": pulled}

    return Logic()


def _distinct_case(what, monkeypatch, steps):
    """``(spec, table, batches, chunk)`` of a case: a warm FTRL table (both
    branches of the rule's threshold hold rows) and ``steps`` batches of the
    case's keys with fresh values and labels."""
    from flink_parameter_server_tpu.core import store as store_mod
    from flink_parameter_server_tpu.models import logistic_ftrl as lf

    (examples, fields), rows, chunk, make = DISTINCT_BATCHES[what]
    if chunk is not None:
        monkeypatch.setattr(store_mod, "_RULE_CHUNK", chunk)
    rng = np.random.default_rng(70)
    made = make(rng)
    ids, live = made if isinstance(made, tuple) else (made, None)
    spec = lf.make_store(rows).spec
    z = rng.normal(0, 2, spec.table_shape()[0]).astype(np.float32)
    n = rng.uniform(0, 64, z.shape).astype(np.float32)
    values = jnp.stack(
        [lf.FTRLProximal().weights(jnp.asarray(z), jnp.asarray(n)), z, n], 1)
    table = jnp.pad(values, ((0, 0), (0, spec.table_shape()[1] - 3)))
    batches = [{
        "ids": jnp.asarray(ids.astype(np.int32)),
        "values": jnp.asarray(rng.random(ids.shape, np.float32) + 0.5),
        "feat_mask": jnp.asarray(
            np.ones(ids.shape, bool) if live is None else live),
        "label": jnp.asarray(rng.choice([-1.0, 1.0], examples), jnp.float32),
        # (whole examples dropped too, in the case that masks lanes)
        "mask": jnp.asarray(
            (rng.random(examples) < 0.9) | (live is None)),
    } for _ in range(steps)]
    return spec, table, batches


def _bits(x):
    return np.asarray(x).view(np.int32)


@pytest.mark.parametrize("steps", [1, 3])
@pytest.mark.parametrize("what", sorted(DISTINCT_BATCHES))
def test_the_distinct_pull_hands_out_takes_block_and_leaves_the_parents_table(
        what, steps, steer_arms, monkeypatch):
    from flink_parameter_server_tpu.core import store as store_mod

    spec, table, batches = _distinct_case(what, monkeypatch, steps)
    assert store_mod.arms(spec).pull == "narrow"  # the parent path, off a TPU
    step = jax.jit(make_train_step(_ftrl_that_shows_its_rows(), spec))
    want, outs = table, []
    for batch in batches:
        want, _, out = step(want, (), batch)
        outs.append(out)
    steer_arms(pull="narrow_distinct")
    assert store_mod.arms(spec).pull == "narrow_distinct"
    step = jax.jit(make_train_step(_ftrl_that_shows_its_rows(), spec))
    got = table
    for batch, parents in zip(batches, outs):
        clipped = jnp.clip(batch["ids"], 0, spec.padded_capacity - 1)
        block = jnp.take(got[:, :3], clipped, axis=0)
        got, _, out = step(got, (), batch)
        assert np.array_equal(_bits(out["pulled"]), _bits(block))
        assert int(out["ps_pull_distinct_rows"]) == len(
            np.unique(np.asarray(clipped)))
        assert set(out) == set(parents) | {"ps_pull_distinct_rows"}
        for name, value in parents.items():
            assert np.array_equal(_bits(out[name]), _bits(value)), name
    assert np.array_equal(_bits(got), _bits(want))
    assert not np.array_equal(_bits(got), _bits(table))  # something moved


@pytest.mark.parametrize("what", sorted(DISTINCT_BATCHES))
def test_the_gauge_of_the_distinct_pull_counts_what_numpy_unique_counts(
        what, steer_arms, monkeypatch):
    from flink_parameter_server_tpu.core import store as store_mod
    from flink_parameter_server_tpu.telemetry.registry import MetricsRegistry

    spec, table, (batch,) = _distinct_case(what, monkeypatch, 1)
    steer_arms(pull="narrow_distinct")
    step = jax.jit(make_train_step(_ftrl_that_shows_its_rows(), spec))
    _, _, out = step(table, (), batch)
    registry = MetricsRegistry()
    store_mod.publish_counts(out, registry, int, int)
    gauges = {i.name: i.value for i in registry.instruments()}
    clipped = np.clip(np.asarray(batch["ids"]), 0, spec.padded_capacity - 1)
    assert gauges["store_pull_distinct_rows"] == len(np.unique(clipped))
    # ... a pull no push follows stays the gather, bit for bit the same
    rows, left = store_mod.pull_counted(spec, table, batch["ids"])
    assert int(left.count) == len(np.unique(clipped))
    assert np.array_equal(
        _bits(rows), _bits(jnp.take(table[:, :3], jnp.asarray(clipped), axis=0)))
    assert np.array_equal(
        _bits(rows), _bits(store_mod.pull(spec, table, batch["ids"])))
    lowered = jax.jit(lambda t, i: store_mod.pull(spec, t, i)).lower(
        table, batch["ids"]).as_text()
    assert "stablehlo.sort" not in lowered and "stablehlo.gather" in lowered
    # the parent path's step hands out no such count and sets no such gauge
    monkeypatch.undo()
    _, _, out = jax.jit(make_train_step(
        _ftrl_that_shows_its_rows(), spec))(table, (), batch)
    registry = MetricsRegistry()
    store_mod.publish_counts(out, registry, int, int)
    assert "ps_pull_distinct_rows" not in out
    assert "store_pull_distinct_rows" not in {
        i.name for i in registry.instruments()}


@pytest.mark.parametrize("what, shared", [
    ("criteo_like_duplicates", True), ("all_distinct", True),
    ("a_run_longer_than_a_chunk", True), ("shorter_than_a_chunk", True),
    ("masked_lanes_with_nan_deltas", False),
    ("negative_and_out_of_range_ids", False),
])
def test_the_rule_runs_on_the_pulled_rows_where_the_pushs_ids_are_the_pulls(
        what, shared, steer_arms, monkeypatch):
    """Who reads the table when: handed rows that are NOT the table's (each
    lane one more), a push whose distinct ids are the pull's runs its rule
    on THEM and gathers nothing; a push that a masked or clipped lane left
    fewer ids reads the table, as the parent does."""
    from flink_parameter_server_tpu.core import store as store_mod
    from flink_parameter_server_tpu.models import logistic_ftrl as lf

    spec, table, (batch,) = _distinct_case(what, monkeypatch, 1)
    steer_arms(pull="narrow_distinct")
    ids = batch["ids"]
    live = batch["feat_mask"] & batch["mask"][:, None]
    rows, left = store_mod.pull_counted(spec, table, ids)
    _, deltas = lf.example_deltas(
        batch["values"], rows[..., lf.W], batch["label"])
    other = left._replace(rows=left.rows + 1)

    def pushed(pulled, on):
        return np.asarray(jax.jit(lambda t: store_mod.push_counted(
            spec, t, ids, deltas, live, pulled=pulled)[0])(on))

    parents = pushed(None, table)
    assert np.array_equal(_bits(pushed(left, table)), _bits(parents))
    moved = jnp.where(
        jnp.arange(4) < 3, table + 1, table)  # (the tile's pad lane is 0)
    want = parents if not shared else pushed(None, moved)
    touched = np.unique(np.asarray(ids)[np.asarray(live)])
    touched = touched[(touched >= 0) & (touched < spec.padded_capacity)]
    # (two programs may fuse the rule's arithmetic differently: to an ulp)
    got = pushed(other, table)[touched]
    assert np.allclose(got, want[touched], rtol=1e-5, atol=1e-6)
    assert shared != np.allclose(got, parents[touched], rtol=1e-5, atol=1e-6)
    # rows of another batch are not this push's: it reads the table
    short = store_mod.pull_counted(spec, table, ids.reshape(-1)[:-1])[1]
    assert np.array_equal(_bits(pushed(short, table)), _bits(parents))


def test_a_request_of_other_ids_than_the_pulled_keys_keeps_its_own_read(
        steer_arms, monkeypatch):
    """``make_train_step`` hands the pull's rows to the push only where the
    request's ids ARE the pulled keys, the same traced array: a logic that
    pushes to other rows (here: every key's neighbour) must have its rule
    read the rows it names."""
    from flink_parameter_server_tpu.core import store as store_mod

    spec, table, (batch,) = _distinct_case(
        "criteo_like_duplicates", monkeypatch, 1)

    class Neighbours(type(_ftrl_that_shows_its_rows())):
        def step(self, state, batch, pulled):
            state, req, out = super().step(state, batch, pulled)
            return state, PushRequest(req.ids + 1, req.deltas, req.mask), out

    want, _, _ = jax.jit(make_train_step(Neighbours(), spec))(table, (), batch)
    steer_arms(pull="narrow_distinct")
    handed = []
    real = store_mod.push_counted
    monkeypatch.setattr(store_mod, "push_counted", lambda *a, **kw: (
        handed.append(kw["pulled"]), real(*a, **kw))[1])
    got, _, out = jax.jit(make_train_step(Neighbours(), spec))(table, (), batch)
    assert handed == [None] and "ps_pull_distinct_rows" in out
    assert np.array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("n, rows", [
    (1, 5), (2, 1), (3, 2), (7, 3), (64, 9), (65, 64), (1000, 37), (1000, 5000),
])
def test_spread_runs_is_a_gather_by_run_number(n, rows):
    """``ops/dedup.sorted_runs`` and ``spread_runs`` alone: the distinct ids
    as ``numpy.unique`` has them, and every lane its id's row through the
    shifted selects and the sort, NaN, -0.0 and all."""
    from flink_parameter_server_tpu.ops.dedup import sorted_runs, spread_runs

    rng = np.random.default_rng(n * 1009 + rows)
    ids = rng.integers(0, rows, n).astype(np.int32)
    row_ids, count, place, behind = jax.jit(
        lambda i: sorted_runs(i, rows))(jnp.asarray(ids))
    uniq = np.unique(ids)
    assert int(count) == len(uniq)
    assert np.array_equal(np.asarray(row_ids)[:len(uniq)], uniq)
    assert (np.asarray(row_ids)[len(uniq):] == rows).all()
    steps = np.diff(np.asarray(behind))
    assert behind[0] == 0 and ((steps == 0) | (steps == 1)).all()
    table = rng.normal(size=(rows, 3)).astype(np.float32)
    table[rng.random(table.shape) < 0.1] = np.nan
    table[rng.random(table.shape) < 0.1] = -0.0
    compact = np.zeros((n, 3), np.float32)
    compact[:len(uniq)] = table[uniq]
    compact[len(uniq):] = 7.0  # never read
    got = jax.jit(spread_runs)(jnp.asarray(compact), place, behind)
    assert np.array_equal(_bits(got), _bits(table[ids]))


# -- a gather whose ids the code itself put in bounds does not fill (PR 76) ---
@pytest.mark.parametrize("arm", ["take", "narrow", "narrow_distinct"])
def test_a_pull_of_ids_it_has_clipped_reads_the_clipped_ids_rows(
        arm, steer_arms):
    """``_pull`` clips its ids and then gathers with ``mode="clip"``:
    ``jnp.take``'s default would compare every id with the table's bounds
    again and select, over everything it fetched, between the row and a NaN
    that no lane can get.  A dead lane (-1) reads row 0, an id past the
    table its last row, as ever, NaN and -0.0 in the rows and all; and the
    traced pull holds no select."""
    from flink_parameter_server_tpu.core import store as store_mod

    narrow = arm != "take"
    row = (3,) if narrow else (128,)
    values = _init_values(CAP, row)
    values[[0, CAP - 1], 0], values[5, 1] = np.nan, -0.0
    store = ShardedParamStore.create(
        CAP, row, init_fn=lambda ids: jnp.asarray(values)[ids],
        update=(lambda current, combined: current + combined) if narrow
        else "add")
    spec, table = store.spec, store.table
    last = spec.padded_capacity - 1
    ids = jnp.array([[-1, 0, CAP - 1, CAP], [2 ** 31 - 1, -(2 ** 31), 5, 5],
                     [last, last + 1, 7, -1]], jnp.int32)
    if arm == "narrow_distinct":
        steer_arms(pull="narrow_distinct")
    assert store_mod.arms(spec, pull_lanes=ids.size).pull == arm
    want = np.asarray(table)[np.clip(ids, 0, last)][..., :row[0]]
    for pull in (store_mod.pull, store_mod.pull_counted):
        run = jax.jit(lambda t, i: pull(spec, t, i))
        got = run(table, ids)
        rows, left = got if pull is store_mod.pull_counted else (got, None)
        assert np.array_equal(_bits(rows), _bits(want)), pull.__name__
        assert (left is not None) == (
            arm == "narrow_distinct" and pull is store_mod.pull_counted)
        if left is None:  # (the distinct pull spreads its rows by selects)
            assert "stablehlo.select" not in run.lower(table, ids).as_text()


def test_row_add_permutes_its_rows_with_no_fill_to_the_parents_bits(
        monkeypatch):
    """``row_add``'s two permutes take ``mode="clip"`` (``order`` is a
    sort's permutation of the lanes): the table is the one the filling
    permutes gave, kept here, bit for bit; a masked lane, a negative id and
    one past the state sort to the end and their NaN reaches no row."""
    from flink_parameter_server_tpu.ops import row_update

    monkeypatch.setattr(row_update, "BLOCK", 128)
    rng = np.random.default_rng(76)
    rows, n = 32, 100
    ids = rng.integers(0, rows, n).astype(np.int32)
    ids[[3, 40]] = [-1, rows]
    mask = rng.random(n) > 0.2
    dead = ~mask | (ids < 0) | (ids >= rows)
    state = rng.normal(size=(rows, 128)).astype(np.float32)
    deltas = rng.normal(size=(n, 128)).astype(np.float32)
    old = state[np.clip(ids, 0, rows - 1)]
    deltas[dead], old[dead] = np.nan, np.nan

    def parents(st, i, o, d, m):
        sid, order = row_update.sort_by_row(i, m, st.shape[0])
        return row_update.sorted_row_update(
            st, sid, jnp.take(o, order, axis=0), jnp.take(d, order, axis=0),
            interpret=True)

    got, want = jax.jit(lambda *a: (
        row_update.row_add(*a, interpret=True), parents(*a)))(
            state, ids, old, deltas, mask)
    assert np.array_equal(_bits(got), _bits(want))
    assert np.isfinite(np.asarray(got)).all()
    touched = np.unique(ids[~dead])
    assert not np.array_equal(np.asarray(got)[touched], state[touched])
