"""Lane-packed store layout (ops/packed.py + StoreSpec.layout="packed").

The packed layout must be OBSERVATIONALLY IDENTICAL to the dense layout
through the whole store protocol (pull / push / values / checkpoint) —
it is purely a physical-layout change (k narrow rows per 128-lane
physical row) that buys full vector lanes for the reference's narrow
value shapes (MF dim 64, FM dim 17, PA scalars).  Push and pull of the
packed store against a float64 reference: tests/test_store.py's case table.
"""
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from flink_parameter_server_tpu.core import store as store_mod
from flink_parameter_server_tpu.core.store import ShardedParamStore, StoreSpec
from flink_parameter_server_tpu.ops import packed as packed_mod
from flink_parameter_server_tpu.ops.packed import (
    _sub_row_slice,
    lane_shift_deltas,
    lane_shift_kernel,
    lane_unshift,
    pack_k,
    pack_table,
    packed_pull,
    phys_width,
    sub_row_slice_kernel,
    turned_slice_kernel,
    unpack_table,
)


# an HLO line that APPLIES a collective (a use of its result is `%all-reduce,`)
COLLECTIVE_OP = re.compile(
    r" (all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute)"
    r"(-start)?\("
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


def _gathered_rows_with_specials(rng, n, d):
    """``(rows, ids)``: ``n`` gathered physical rows with NaN, +-inf and
    -0.0 in every sub-row, so in the selected one and in its neighbours."""
    rows = rng.normal(0, 1, (n, 128)).astype(np.float32)
    for bad in (np.nan, np.inf, -np.inf, -0.0):
        rows[rng.integers(0, n, n // 2), rng.integers(0, 128, n // 2)] = bad
    return jnp.asarray(rows), jnp.asarray(
        rng.integers(0, 10 ** 6, n).astype(np.int32))


# n against a block of 256 ids: whole blocks, one block and a remainder (of
# whole 128-lane groups; of a ragged 44 lanes), many blocks and a remainder
@pytest.mark.parametrize("n", [256, 1024, 256 + 128, 256 + 44, 256 * 5 + 129])
@pytest.mark.parametrize("d", [1, 4, 17, 64, 100])
def test_the_slice_kernel_equals_the_select_arm_bit_for_bit(d, n):
    """``sub_row_slice_kernel`` (interpreted here) against
    ``_sub_row_slice``: the same bits at every width, NaN, infinities and
    -0.0 included, in the selected sub-row and beside it, whatever ``n``
    leaves of the last block."""
    rows, ids = _gathered_rows_with_specials(np.random.default_rng([d, n]), n, d)
    got = sub_row_slice_kernel(rows, ids, d, block=256)
    want = _sub_row_slice(rows, ids, d)
    assert got.shape == want.shape == (n, d) and got.dtype == want.dtype
    np.testing.assert_array_equal(
        np.asarray(got).view(np.uint32), np.asarray(want).view(np.uint32))
    assert np.isnan(np.asarray(want)).any() or d == 1
    np.testing.assert_array_equal(
        np.asarray(want).view(np.uint32),
        np.asarray(_slice_by_gather(rows, ids, d)).view(np.uint32))


# n against a block of 256 lanes: whole blocks, and a last block that is cut
# (to whole 128-lane groups; to a ragged 44 lanes; after several blocks)
@pytest.mark.parametrize("masked", [False, True], ids=["all_live", "masked"])
@pytest.mark.parametrize("n", [256, 256 + 128, 256 + 44, 256 * 5 + 129])
@pytest.mark.parametrize("d", [1, 16, 17, 36, 63, 64])
def test_the_shift_kernel_equals_the_select_arm_bit_for_bit(d, n, masked):
    """``lane_shift_kernel`` (interpreted here, fed the deltas' transpose)
    against ``lane_shift_deltas``: the same bits at every width packed
    several rows to a physical row, NaN, infinities and -0.0 included,
    zeros (+0.0) in every lane outside the id's window, the pad lanes among
    them, whatever ``n`` leaves of the last block; a masked lane is a row of +0.0, the bits
    of the selects over deltas zeroed first, whatever it held; and
    ``lane_unshift`` gives the deltas back."""
    rng = np.random.default_rng([d, n])
    ids = jnp.asarray(rng.integers(0, 10 ** 6, n).astype(np.int32))
    deltas = rng.normal(0, 1, (n, d)).astype(np.float32)
    for bad in (np.nan, np.inf, -np.inf, -0.0):
        deltas[rng.integers(0, n, n // 4), rng.integers(0, d, n // 4)] = bad
    deltas = jnp.asarray(deltas)
    mask = jnp.asarray(rng.random(n) < 0.7) if masked else None

    def bits(x):
        return np.asarray(x).view(np.uint32)

    got = lane_shift_kernel(deltas.T, ids, d, mask, block=256)
    kept = deltas if mask is None else jnp.where(mask[:, None], deltas, 0)
    want = lane_shift_deltas(kept, ids, d)
    assert got.shape == want.shape == (n, 128) and got.dtype == want.dtype
    np.testing.assert_array_equal(bits(got), bits(want))
    assert np.isnan(np.asarray(want)).any()
    np.testing.assert_array_equal(
        bits(want), bits(_shift_by_gather(kept, ids, d)))
    np.testing.assert_array_equal(bits(lane_unshift(got, ids, d)), bits(kept))
    if masked:  # rows of +0.0, not of what the lane held times zero
        assert not bits(got)[~np.asarray(mask)].any()


# a key block (B, K): K fields on the leading axis of the answer
@pytest.mark.parametrize("batch, fields", [
    (256, 1), (256, 5), (512, 39), (256, 40), (256, 26)])
@pytest.mark.parametrize("d, width", [
    (4, None), (17, None), (36, 20), (64, 1), (64, None)])
def test_the_turned_slice_kernel_is_the_select_arm_turned_bit_for_bit(
        d, width, batch, fields):
    """``turned_slice_kernel`` (interpreted here) reads the rows gathered
    for a two-axis key block in the block's C order and writes them TURNED:
    the bits of ``_sub_row_slice`` on the flat ids, NaN, infinities and -0.0
    included, with the block's axes swapped, and so the flat kernel's.
    ``by_field`` says which block the kernels take so: whole blocks of
    examples, as many fields as were compiled for the chip.  (64 lanes whole,
    two rows to a register, 26 fields: DLRM's, cell 10 since PR 65.)"""
    n = batch * fields
    rows, ids = _gathered_rows_with_specials(
        np.random.default_rng([d, batch, fields]), n, d)
    block = ids.reshape(batch, fields)
    want = jnp.swapaxes(_sub_row_slice(rows, ids, d, width).reshape(
        batch, fields, -1), 0, 1)

    def bits(x):
        return np.asarray(x).view(np.uint32)

    got = turned_slice_kernel(rows, block, d, width)
    assert got.shape == want.shape == (fields, batch, width or d)
    np.testing.assert_array_equal(bits(got), bits(want))
    if pack_k(d) > 1:
        flat = sub_row_slice_kernel(rows, ids, d, width, block=256)
        np.testing.assert_array_equal(bits(got), bits(jnp.swapaxes(
            flat.reshape(batch, fields, -1), 0, 1)))
    assert np.isnan(np.asarray(want)).any() or (width or d) == 1
    assert packed_mod.by_field(fields, batch)
    assert not packed_mod.by_field(fields, batch - 8)
    assert not packed_mod.by_field(packed_mod.TURN_FIELDS + 1, batch)
    assert not packed_mod.by_field(0, batch)


@pytest.mark.parametrize("masked", [False, True], ids=["all_live", "masked"])
@pytest.mark.parametrize("batch, fields", [
    (128, 1), (256, 5), (384, 39), (128, 40), (256, 26)])
@pytest.mark.parametrize("d", [16, 17, 36, 64])
def test_the_shift_kernel_takes_its_deltas_a_field_at_a_time_bit_for_bit(
        d, batch, fields, masked):
    """``lane_shift_kernel`` handed ``(d, K, B)`` deltas with ``(K, B)`` ids
    and mask (interpreted here): the rows of the flat kernel on the same
    batch flattened, ``(K B, 128)`` in the block's C order, and so
    ``lane_shift_deltas``' bits, NaN, infinities and -0.0 included; a
    masked lane a row of +0.0."""
    rng = np.random.default_rng([d, batch, fields])
    n = batch * fields
    ids = jnp.asarray(rng.integers(0, 10 ** 6, (fields, batch)).astype(np.int32))
    deltas = rng.normal(0, 1, (fields, batch, d)).astype(np.float32)
    for bad in (np.nan, np.inf, -np.inf, -0.0):
        deltas[rng.integers(0, fields, n // 4), rng.integers(0, batch, n // 4),
               rng.integers(0, d, n // 4)] = bad
    deltas = jnp.asarray(deltas)
    mask = jnp.asarray(rng.random((fields, batch)) < 0.7) if masked else None

    def bits(x):
        return np.asarray(x).view(np.uint32)

    got = lane_shift_kernel(jnp.moveaxis(deltas, -1, 0), ids, d, mask)
    flat_mask = None if mask is None else mask.reshape(-1)
    flat = lane_shift_kernel(
        deltas.reshape(n, d).T, ids.reshape(-1), d, flat_mask, block=256)
    kept = deltas if mask is None else jnp.where(mask[..., None], deltas, 0)
    want = lane_shift_deltas(kept.reshape(n, d), ids.reshape(-1), d)
    assert got.shape == want.shape == (n, 128) and got.dtype == want.dtype
    np.testing.assert_array_equal(bits(got), bits(flat))
    np.testing.assert_array_equal(bits(got), bits(want))
    assert np.isnan(np.asarray(want)).any()
    if masked:
        assert not bits(got)[~np.asarray(flat_mask)].any()


@pytest.mark.parametrize("n,kernel", [(255, False), (256, True), (300, True)])
def test_a_pull_under_one_block_keeps_the_select_arm(n, kernel, monkeypatch):
    """The arm is read from the backend, the dtype, ``k`` and ``n``
    (``core/store.arms``' ``pull``): on a TPU a float32 pull of a
    block or more of 17-lane rows takes the kernel, a shorter one (an eager
    read-back) traces none and is noted once; here the backend is steered
    and the kernel's call recorded."""
    from flink_parameter_server_tpu.ops import row_update

    monkeypatch.setattr(packed_mod, "SLICE_BLOCK", 256)
    monkeypatch.setattr(store_mod, "_REFUSALS_NOTED", set())
    store = ShardedParamStore.create(
        1000, (17,), init_fn=_rand_init(17), layout="packed")
    ids = jnp.asarray(np.random.default_rng(n).integers(0, 1000, n), jnp.int32)
    want = np.asarray(store.pull(ids))
    assert store_mod.arms(
        store.spec, pull_lanes=n).pull == "packed_selects"  # this is a CPU
    calls = []
    real = packed_mod.sub_row_slice_kernel
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # the backend is steered, the kernel still has to be interpreted
    monkeypatch.setattr(
        packed_mod, "sub_row_slice_kernel",
        lambda rows, ids, d, width=None: calls.append(rows.shape) or real(
            rows, ids, d, width, interpret=True))
    n0 = row_update.refusal_count()
    if kernel:
        got = store.pull(ids)
        assert row_update.refusal_count() == n0
    else:
        with pytest.warns(RuntimeWarning, match="under one block"):
            got = store.pull(ids)
        store.pull(ids[:100])  # once a row shape
        assert row_update.refusal_count() == n0 + 1
    assert calls == ([(n, 128)] if kernel else [])
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("arm", ["select", "kernel", "kernel_by_field"])
def test_the_driver_says_which_arm_sliced_its_pulled_rows(arm, monkeypatch,
        steer_arms):
    """Where an operator reads it: the gauge ``store_packed_slice_kernel``,
    from the scalar the step of a packed store carries among its outputs
    (``ps_slice_kernel``: what its trace read), and beside it, for an
    ``add`` store, ``store_packed_shift_kernel`` (``ps_shift_kernel``: the
    push's arm).  ``store_packed_by_field`` (``ps_lanes_by_field``) says
    whether those kernels moved the batch a field at a time, the logic's
    buffers batch-minor at the other end: 1 only where both did.  A dense
    store's driver has none of the three."""
    from flink_parameter_server_tpu.models import factorization_machine as fmm
    from flink_parameter_server_tpu.telemetry.registry import MetricsRegistry
    from flink_parameter_server_tpu.training.driver import (
        DriverConfig, StreamingDriver)

    examples = 32
    if arm != "select":  # steered: this is a CPU, the kernels are interpreted
        fielded = "_by_field" if arm == "kernel_by_field" else ""
        steer_arms(pull="packed_kernel" + fielded, shift="kernel" + fielded)
        monkeypatch.setattr(packed_mod, "SLICE_BLOCK", 64)
        packed_mod.packed_pull.clear_cache()
        if fielded:  # whole blocks of examples, as `arms` asks of a TPU's
            examples = 256
            assert packed_mod.by_field(5, examples)
    cfg = fmm.FMConfig(num_features=500, dim=16)
    rng = np.random.default_rng(3)
    batches = [{
        "ids": rng.integers(0, 500, (examples, 5)).astype(np.int32),
        "values": rng.random((examples, 5)).astype(np.float32),
        "feat_mask": rng.random((examples, 5)) > 0.2,
        "label": (rng.integers(0, 2, examples) * 2 - 1).astype(np.float32),
        "mask": np.ones(examples, bool),
    } for _ in range(3)]
    seen = {}
    for layout in ("auto", "dense"):
        reg = MetricsRegistry()
        driver = StreamingDriver(
            fmm.FactorizationMachine(cfg), fmm.make_store(cfg, layout=layout),
            config=DriverConfig(dump_model=False), registry=reg)
        driver.run(batches)
        seen[layout] = {k: v[0]["value"] for k, v in reg.snapshot().items()
                        if k.startswith("store_")}
    packed_mod.packed_pull.clear_cache()
    # (`store_compute_parts`, PR 68: the whole minibatch computed in one
    # place; every driver sets it)
    assert seen == {
        "auto": {"store_layout_packed": 1.0, "store_compute_parts": 1.0,
                 "store_packed_slice_kernel": float(arm != "select"),
                 "store_packed_shift_kernel": float(arm != "select"),
                 "store_packed_by_field": float(arm == "kernel_by_field")},
        "dense": {"store_layout_packed": 0.0, "store_compute_parts": 1.0},
    }


def test_a_step_is_by_field_only_where_every_lane_kernel_it_has_is(steer_arms):
    """``core/store.step_counts``' ``ps_lanes_by_field``: the pull's arm and,
    for an ``add`` store, the shift's; a rule store has no shift to ask; a
    store of one row to a physical row hands out none of the three."""
    from flink_parameter_server_tpu.core import store as store_mod

    def read(store, **arms):
        steer_arms(**arms)  # (calls stack: an arm stays as last steered)
        out = store_mod.step_counts(
            store.spec, None, pull_lanes=1280, push_lanes=1280, fields=5)
        return {k: int(v) for k, v in out.items() if k in (
            "ps_slice_kernel", "ps_shift_kernel", "ps_lanes_by_field")}

    add = ShardedParamStore.create(500, (17,), layout="packed")
    assert read(add) == {
        "ps_slice_kernel": 0, "ps_shift_kernel": 0, "ps_lanes_by_field": 0}
    assert read(add, pull="packed_kernel_by_field", shift="kernel") == {
        "ps_slice_kernel": 1, "ps_shift_kernel": 1, "ps_lanes_by_field": 0}
    assert read(add, shift="kernel_by_field") == {
        "ps_slice_kernel": 1, "ps_shift_kernel": 1, "ps_lanes_by_field": 1}
    rule = ShardedParamStore.create(
        500, (36,), update=lambda current, combined: current + combined,
        layout="auto")
    assert rule.spec.pack == 3
    assert read(rule, pull="packed_kernel", shift="selects") == {
        "ps_slice_kernel": 1, "ps_lanes_by_field": 0}
    assert read(rule, pull="packed_kernel_by_field") == {
        "ps_slice_kernel": 1, "ps_lanes_by_field": 1}
    wide = ShardedParamStore.create(61, (300,), layout="packed")
    assert wide.spec.pack == 1 and read(wide) == {}


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
    """A packed table under ``ps > 1`` is packed by ``_pack_block`` wherever
    its values lie (no second, eager way to pack): values in one place are
    packed there, whole, and handed to the shards; values on the mesh are
    packed shard by shard and the whole-table program is never built.
    Either way: rows padded to the shards' aligned blocks, the table
    ``ps``-sharded, the same table as ``pack_table``'s."""
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
    built = []
    for name in ("_pack_rows", "_pack_rows_on_mesh"):
        monkeypatch.setattr(
            store_mod, name,
            lambda spec, name=name, make=getattr(store_mod, name): (
                built.append(name) or make(spec)))
    table = ShardedParamStore._place(spec, values)
    assert built == [
        "_pack_rows_on_mesh" if origin == "on_the_mesh" else "_pack_rows"]
    assert table.shape == spec.table_shape()
    assert table.sharding == spec.sharding()
    np.testing.assert_array_equal(
        np.asarray(table),
        np.asarray(pack_table(
            jnp.asarray(want), spec.rows_per_shard * spec.num_shards)))
    np.testing.assert_array_equal(
        np.asarray(ShardedParamStore(spec, table).values()), want)


def _ps_mesh(mesh_devices, dp):
    from flink_parameter_server_tpu.parallel.mesh import make_mesh

    return make_mesh(dp, 4, devices=mesh_devices[:dp * 4])


def _on_mesh(values, mesh):
    """As the benchmark's values lie: row-sharded over ``ps`` where the
    rows divide, else whole on every device of the mesh."""
    P = jax.sharding.PartitionSpec
    rows = P("ps", None) if values.shape[0] % 4 == 0 else P()
    return jax.device_put(values, jax.sharding.NamedSharding(mesh, rows))


# FM's 17-lane rows, 7 to a physical row, 4 shards of 8-aligned blocks:
# 672 = 3 x (7 x 8 x 4) fills every block exactly; 1000 and 2004 leave each
# packed shard 30 and 3 logical rows longer than a shard of the values (the
# real table: 35), so shard s's block starts s x that many rows into its own
# values and ends in its neighbour's, and their last physical row is partly
# filled (1000 = 142 x 7 + 6, 2004 = 286 x 7 + 2); 100 rows are too few for
# one neighbour to reach (packed whole, then placed); 1001 do not divide by
# 4, so such values cannot lie row-sharded on the mesh.
@pytest.mark.parametrize("dp", [1, 2])
@pytest.mark.parametrize("capacity", [672, 1000, 2004, 100, 1001])
def test_packed_store_under_a_mesh_equals_the_one_shard_store(
        capacity, dp, mesh_devices, monkeypatch):
    """One logical table: pull, push and ``values()`` of the packed store
    sharded over ``ps = 4`` (and ``dp x ps = 2 x 4``) are those of the
    one-shard packed store bit for bit, built from values that lie on the
    mesh; ids with duplicates, negative and out-of-range ids, a lane mask."""
    monkeypatch.setattr(store_mod, "_PACK_CHUNK", 8)
    d = 17
    mesh = _ps_mesh(mesh_devices, dp)
    rng = np.random.default_rng(capacity)
    want = rng.normal(size=(capacity, d)).astype(np.float32)
    spec = StoreSpec(capacity=capacity, value_shape=(d,), layout="packed",
                     mesh=mesh)
    assert store_mod._next_shard_in_reach(spec, capacity) == (
        capacity in (672, 1000, 2004))
    sharded = ShardedParamStore.from_spec_values(spec, _on_mesh(want, mesh))
    single = ShardedParamStore.from_spec_values(
        StoreSpec(capacity=capacity, value_shape=(d,), layout="packed"),
        jnp.asarray(want))
    assert sharded.table.sharding == spec.sharding()
    np.testing.assert_array_equal(np.asarray(sharded.values()), want)

    # (the padding rows between ``capacity`` and each store's own
    # ``padded_capacity`` are addressable, and the two stores pad differently)
    ids = rng.integers(-9, capacity, size=(96, 5)).astype(np.int32)
    ids[:, 0] = ids[0, 0]  # one row on a fifth of the lanes
    ids[:8, 1] = [0, capacity - 1, capacity + 5000, -1, 6, 7, 2 ** 31 - 1,
                  -2 ** 31]
    deltas = rng.normal(size=ids.shape + (d,)).astype(np.float32)
    mask = rng.random(ids.shape) < 0.7
    ids, deltas, mask = map(jnp.asarray, (ids, deltas, mask))
    np.testing.assert_array_equal(
        np.asarray(sharded.pull(ids)), np.asarray(single.pull(ids)))
    pushed = sharded.push(ids, deltas, mask)
    assert pushed.table.sharding.is_equivalent_to(spec.sharding(), 2)
    want_after = np.asarray(single.push(ids, deltas, mask).values())
    assert not np.array_equal(want_after, want)
    np.testing.assert_array_equal(np.asarray(pushed.values()), want_after)
    np.testing.assert_array_equal(
        np.asarray(pushed.pull(ids)),
        np.asarray(single.push(ids, deltas, mask).pull(ids)))


@pytest.mark.parametrize("shards", [None, (1, 4), (2, 4), (4, 1)])
@pytest.mark.parametrize("capacity", [5, 672, 1001])
def test_create_inits_and_packs_chunk_by_chunk_on_every_shard(
        capacity, shards, mesh_devices, monkeypatch):
    """``create`` of a packed store holds ``init_fn`` of EVERY row of its
    table (the padding rows too: initialised, addressable), on one device
    and with each shard of a mesh building its own block, whether the
    blocks divide into chunks or the last chunk overlaps (``core/store.
    _create_packed``); and no collective is needed to build it."""
    from flink_parameter_server_tpu.parallel.mesh import make_mesh

    monkeypatch.setattr(store_mod, "_PACK_CHUNK", 24)
    d = 17
    mesh = shards and make_mesh(
        *shards, devices=mesh_devices[: shards[0] * shards[1]])
    store = ShardedParamStore.create(
        capacity, (d,), init_fn=_rand_init(d), mesh=mesh, layout="auto")
    spec = store.spec
    assert spec.layout == "packed" and store.table.shape == spec.table_shape()
    if mesh is not None:
        assert store.table.sharding.is_equivalent_to(spec.sharding(), 2)
        assert not COLLECTIVE_OP.search(
            store_mod._create_packed(spec, _rand_init(d))
            .lower().compile().as_text())
    want = np.asarray(_rand_init(d)(jnp.arange(spec.padded_capacity)))
    np.testing.assert_array_equal(
        np.asarray(unpack_table(store.table, spec.padded_capacity, d)), want)
    np.testing.assert_array_equal(np.asarray(store.values()), want[:capacity])


@pytest.mark.parametrize("dp", [1, 2])
def test_placing_values_on_the_mesh_never_holds_them_or_the_table_whole(
        dp, mesh_devices, monkeypatch):
    """``_place`` of values that lie on the mesh: one program in which every
    shard packs its own block, whose only collective hands the few rows at
    a block's end over from the right neighbour.  No all-gather, no buffer
    of the table's or the values' whole shape on a device, the result
    ``ps``-sharded (a table sharded because it outgrew a chip cannot be
    built whole; PERF.md section 6, PR 31)."""
    monkeypatch.setattr(store_mod, "_PACK_CHUNK", 8)
    rows, d = 2004, 17
    mesh = _ps_mesh(mesh_devices, dp)
    spec = StoreSpec(capacity=rows, value_shape=(d,), layout="packed",
                     mesh=mesh)
    values = _on_mesh(np.zeros((rows, d), np.float32), mesh)
    lowered = store_mod._pack_rows_on_mesh(spec).lower(values)
    compiled = lowered.compile()
    assert compiled.output_shardings == spec.sharding()
    text = compiled.as_text()
    collectives = [
        line for line in text.splitlines() if COLLECTIVE_OP.search(line)]
    assert len(collectives) == 1, collectives
    # 3 shards x 3 rows ahead: what the last-but-one block takes from its
    # neighbour; the values' 501 rows a shard never travel
    assert "collective-permute" in collectives[0]
    assert f"f32[9,{d}]" in collectives[0]
    whole = (f"f32[{rows},{d}]", "f32[%d,128]" % spec.table_shape()[0])
    assert not any(shape in text for shape in whole), whole
    assert f"f32[{rows // 4},{d}]" in text
    assert f"f32[{spec.rows_per_shard},128]" in text
    mem = compiled.memory_analysis()
    if mem is not None:  # a shard's bytes, not the table's
        assert mem.output_size_in_bytes == spec.rows_per_shard * 128 * 4
        assert mem.argument_size_in_bytes == rows // 4 * d * 4


@pytest.mark.parametrize("dp", [1, 2])
def test_the_sharded_packed_step_holds_one_all_reduce_of_17_lane_rows(
        dp, mesh_devices):
    """FM's step on a packed table under ``ps = 4``: each shard slices the
    physical rows it gathered down to 17 lanes BEFORE the step's one
    all-reduce.  Left to GSPMD the ``jnp.take`` is all-reduced 128 lanes
    wide, 7.5 x the bytes (PERF.md section 6, PR 31).  The scatter-add
    needs no collective."""
    from flink_parameter_server_tpu.core.transform import make_train_step
    from flink_parameter_server_tpu.models import factorization_machine as fmm

    mesh = _ps_mesh(mesh_devices, dp)
    cfg = fmm.FMConfig(num_features=2004, dim=16)
    spec = jax.eval_shape(lambda: fmm.make_store(cfg, mesh=mesh)).spec
    assert spec.layout == "packed"
    P, batch, fields = jax.sharding.PartitionSpec, 64, 5
    lead = jax.sharding.NamedSharding(mesh, P("dp") if dp > 1 else P())

    def shape(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=lead)

    compiled = jax.jit(
        make_train_step(fmm.FactorizationMachine(cfg), spec),
        donate_argnums=(0, 1),
    ).lower(
        jax.ShapeDtypeStruct(
            spec.table_shape(), jnp.float32, sharding=spec.sharding()),
        (),
        {"ids": shape((batch, fields), jnp.int32),
         "values": shape((batch, fields), jnp.float32),
         "feat_mask": shape((batch, fields), jnp.bool_),
         "label": shape((batch,), jnp.float32),
         "mask": shape((batch,), jnp.bool_)},
    ).compile()
    reduces = [
        line for line in compiled.as_text().splitlines()
        if re.search(r" all-reduce(-start)?\(", line)]
    pull = [line for line in reduces if "ps.pull" in line]
    assert len(pull) == 1, reduces
    assert f"f32[{batch // dp * fields},17]" in pull[0]
    assert ",128]" not in pull[0]
    if dp == 1:  # under dp the push sums the workers' deltas, as dense
        assert len(reduces) == 1, reduces


@pytest.mark.parametrize("dp", [1, 2])
def test_values_of_a_packed_sharded_table_come_out_sharded(dp, mesh_devices):
    """The round trip a checkpoint of a sharded deployment makes:
    ``from_spec_values -> push -> values()``.  The logical rows come out
    row-sharded over ``ps``, as they went in: unpacked on the shards with a
    few rows handed to the neighbour, not gathered onto one chip."""
    mesh = _ps_mesh(mesh_devices, dp)
    rows, d = 2012, 17
    rng = np.random.default_rng(dp)
    want = rng.normal(size=(rows, d)).astype(np.float32)
    spec = StoreSpec(capacity=rows, value_shape=(d,), layout="packed",
                     mesh=mesh)
    store = ShardedParamStore.from_spec_values(spec, _on_mesh(want, mesh))
    ids = jnp.asarray(rng.integers(0, rows, 300).astype(np.int32))
    deltas = rng.normal(size=(300, d)).astype(np.float32)
    # a copy: the CPU's device_put may alias `want`, and the pack above
    # reads it asynchronously
    want = want.copy()
    np.add.at(want, np.asarray(ids), deltas)
    values = store.push(ids, jnp.asarray(deltas)).values()
    np.testing.assert_allclose(np.asarray(values), want, rtol=1e-5, atol=1e-6)
    rows_over_ps = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("ps"))
    assert values.sharding.is_equivalent_to(rows_over_ps, 2)
    assert {s.data.shape for s in values.addressable_shards} == {
        (rows // 4, d)}
    text = store_mod._unpack_rows_on_mesh.lower(
        spec, store.table).compile().as_text()
    assert "all-gather" not in text and f"f32[{rows},{d}]" not in text
    again = ShardedParamStore.from_spec_values(spec, values)
    np.testing.assert_array_equal(
        np.asarray(again.values()), np.asarray(values))


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
    # a rule store of 9 to 64 lanes packs too, and either layout may be
    # pinned for one (until PR 47 a rule could not be packed)
    def rule(c, d):
        return c + 2 * d

    s = ShardedParamStore.create(10, (17,), update=rule, layout="auto")
    assert s.spec.layout == "packed" and s.table.shape == (8, 128)
    s = ShardedParamStore.create(10, (17,), update=rule, layout="dense")
    assert s.spec.layout == "dense" and s.table.shape == (16, 17)
    s = ShardedParamStore.create(10, (3,), update=rule, layout="auto")
    assert s.spec.layout == "dense" and s.spec.tile_lanes == 4
    # ... and of 65 to 127 lanes one to a register (PR 61)
    s = ShardedParamStore.create(10, (100,), update=rule, layout="auto")
    assert s.spec.layout == "packed" and s.table.shape == (16, 128)
    s = ShardedParamStore.create(10, (128,), update=rule, layout="auto")
    assert s.spec.layout == "dense"


@pytest.mark.parametrize("shape, k", [
    ((3,), 42), ((17,), 7), ((36,), 3), ((100,), 1), ((2, 150), 1),
])
def test_a_rule_store_pinned_packed_at_any_width_is_the_dense_one(shape, k):
    """``layout="packed"`` pinned for a rule store whatever its row: ``k``
    rows to a physical row, one flat padded row at ``k`` = 1, the push
    through the packed arm; the same rows as the dense store, bit for bit,
    and a round trip through ``values()``."""
    from flink_parameter_server_tpu.core import store as store_mod

    def rule(c, d):
        return 0.5 * c - d + 2.0

    rng = np.random.default_rng(k)
    cap = 200
    values = rng.normal(size=(cap,) + shape).astype(np.float32)
    ids = rng.integers(-2, cap + 2, 150).astype(np.int32)
    ids[:3] = [0, 1, 2]  # neighbours of one physical row where k >= 3
    deltas = rng.normal(size=(150,) + shape).astype(np.float32)
    packed = ShardedParamStore.from_values(
        jnp.asarray(values), update=rule, layout="packed")
    dense = ShardedParamStore.from_values(
        jnp.asarray(values), update=rule, layout="dense")
    assert packed.spec.layout == "packed" and packed.spec.pack == k
    assert packed.table.shape[1] % 128 == 0
    assert np.asarray(packed.values()).tobytes() == values.tobytes()
    got, counted = store_mod.push_counted(
        packed.spec, packed.table, jnp.asarray(ids), jnp.asarray(deltas))
    want = dense.push(jnp.asarray(ids), jnp.asarray(deltas)).values()
    pushed = ShardedParamStore(packed.spec, got)
    assert np.asarray(pushed.values()).tobytes() == np.asarray(want).tobytes()
    kept = np.unique(ids[(ids >= 0) & (ids < packed.spec.padded_capacity)])
    assert int(counted["ps_rule_packed_rows"]) == len(np.unique(kept // k))
    reloaded = ShardedParamStore.from_spec_values(packed.spec, pushed.values())
    assert reloaded.table.shape == got.shape  # (the padding rows start over)
    assert np.asarray(reloaded.values()).tobytes() == (
        np.asarray(pushed.values()).tobytes())


def test_fm_store_packs_on_one_shard_and_under_ps(mesh_devices, recwarn):
    """``make_store`` leaves the layout to the store, which reads the row
    width (17 < 128 lanes) and the update rule, and not the shard count:
    packed with the table on one shard and under ``ps = 4`` alike, with
    nothing said (the refusal of PR 29 went with its reason, PR 31); a
    store and its reload from values resolve to one layout."""
    from flink_parameter_server_tpu.models import factorization_machine as fmm
    from flink_parameter_server_tpu.parallel.mesh import make_mesh

    cfg = fmm.FMConfig(num_features=100, dim=16)
    assert fmm.make_store(cfg).spec.layout == "packed"
    one_shard = make_mesh(4, 1, devices=mesh_devices[:4])
    assert fmm.make_store(cfg, mesh=one_shard).spec.layout == "packed"
    assert fmm.make_store(cfg, layout="dense").spec.layout == "dense"
    ps4 = make_mesh(1, 4, devices=mesh_devices[:4])
    store = fmm.make_store(cfg, mesh=ps4)
    assert store.spec.layout == "packed"
    assert store.table.shape == (32, 128)  # 4 shards x 8 physical rows
    assert store.table.sharding == store.spec.sharding()
    reloaded = ShardedParamStore.from_values(
        store.values(), mesh=ps4, layout="auto")
    assert reloaded.spec == store.spec
    np.testing.assert_array_equal(
        np.asarray(reloaded.values()), np.asarray(store.values()))
    assert not [w for w in recwarn if "layout" in str(w.message)]
    assert not hasattr(store_mod, "packed_refusal_count")
    # where an operator reads it: the driver's gauge
    from flink_parameter_server_tpu.telemetry.registry import MetricsRegistry
    from flink_parameter_server_tpu.training.driver import StreamingDriver

    dense = fmm.make_store(cfg, mesh=ps4, layout="dense")
    seen = {}
    for name, st in (("one", fmm.make_store(cfg)), ("ps4", store),
                     ("ps4_dense", dense)):
        reg = MetricsRegistry()
        StreamingDriver(fmm.FactorizationMachine(cfg), st, registry=reg)
        seen[name] = {k: v[0]["value"] for k, v in reg.snapshot().items()
                      if k.startswith("store_")}
    assert seen == {"one": {"store_layout_packed": 1.0},
                    "ps4": {"store_layout_packed": 1.0},
                    "ps4_dense": {"store_layout_packed": 0.0}}
    # a pinned layout is what it says, and wide rows stay dense under a mesh
    assert fmm.make_store(cfg, mesh=ps4, layout="packed").spec.layout == "packed"
    assert dense.spec.layout == "dense"
    assert ShardedParamStore.create(
        100, (128,), mesh=ps4, layout="auto").spec.layout == "dense"


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
