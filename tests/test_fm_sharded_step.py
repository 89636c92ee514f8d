"""The FM step with its table row-sharded over ``ps = 4``
(``make_mesh(1, 4)``, ``make_store``'s default layout: packed on every shard): against the
plain numpy reference the benchmark's ``correct`` rests on, within the
allowance its four-chip configuration states, and against the one-device step
of the same seed.  One logical table: the result may not depend on the number
of shards (``chipbench/configs/fm-criteo-ps4.json``, ``guarantees``)."""
import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from chipbench import run, spec
from flink_parameter_server_tpu.core.transform import make_train_step
from flink_parameter_server_tpu.parallel.mesh import make_mesh

CFG = spec.resolve(
    spec.load_benchmark(), "fm-criteo-ps4.train-fields-uniform", dry_run=True
)["cfg"]
SEED = 2**31 + 28

# Small tables with the record's hot rows (13 integer-field rows that every
# example of a batch hits, fields of 3, 4, 10 and 14 values).  "aligned"
# splits into four equal 8-aligned blocks; "padded" does not, as the real
# 187,767,412 rows do not (12 padding rows there, 20 here), so
# ``ShardedParamStore._place`` pads a sharded array and the last block ends in
# rows no id addresses.
SIZES = {
    "aligned": CFG["field_cardinalities"],
    "padded": [c - 20 if i == 0 else c
               for i, c in enumerate(CFG["field_cardinalities"])],
}


def _train(cfg, mesh, n=3):
    fam, ref = spec.family(cfg["family"]), spec.reference(cfg)
    logic, store = fam.build(cfg, SEED % (2**31 - 1), mesh)
    batches = fam.host_batches(cfg, {"keys": {"kind": "uniform"}}, SEED, n)
    ids = ref.touched(batches)
    before = fam.rows(store, (), ids)
    step = jax.jit(make_train_step(logic, store.spec))
    table = store.table
    for batch in batches:
        if mesh is not None:  # one global batch, replicated to the chips
            batch = jax.device_put(batch, NamedSharding(mesh, PartitionSpec()))
        table, _, out = step(table, (), batch)
    trained = type(store)(store.spec, table)
    after = fam.rows(trained, (), ids)
    return {
        "want": ref.apply(cfg, before, ids, batches), "before": before,
        # the logical rows: the store packs them 7 to a physical row by
        # itself, on one device and on every shard under ``ps = 4``
        "after": after, "table": np.asarray(trained.values()),
        "prediction": np.asarray(out["prediction"]), "store": store,
    }


@pytest.fixture(scope="module", params=sorted(SIZES))
def runs(request, mesh_devices):
    cards = SIZES[request.param]
    cfg = dict(CFG, field_cardinalities=cards, num_features=13 + sum(cards))
    mesh = make_mesh(1, 4, devices=mesh_devices[:4])
    return cfg, _train(cfg, mesh), _train(cfg, None)


def test_table_is_sharded_in_contiguous_aligned_row_blocks(runs):
    cfg, sharded, _ = runs
    store = sharded["store"]
    assert store.spec.num_shards == 4
    assert store.spec.rows_per_shard % 8 == 0
    assert store.spec.padded_capacity >= cfg["num_features"]
    blocks = sorted(
        (s.index[0].start or 0, s.data.shape[0])
        for s in store.table.addressable_shards
    )
    assert blocks == [
        (i * store.spec.rows_per_shard, store.spec.rows_per_shard)
        for i in range(4)
    ]


def test_sharded_step_is_within_the_configurations_allowance(runs):
    # cell 2's allowances, unloosened: delta_rtol of what the reference moved
    # an element + delta_atol + row_ulps roundings of the row (the
    # configuration's ``reference.why``).  The hot integer rows take 512
    # deltas a step here; their order within the owning shard is the stream's.
    cfg, sharded, _ = runs
    failures, worst = run._check_rows(
        cfg["reference"], sharded["want"], sharded["after"], sharded["before"]
    )
    assert failures == [] and 0.0 < worst["share"] <= 1.0


def test_sharded_step_equals_the_one_device_step_bit_for_bit(runs):
    # Tolerance: none.  The batch is replicated, so every chip computes the
    # same deltas; the sharded pull all-reduces each row with three zeros
    # (exact); the partitioned scatter-add lands a row's deltas on the
    # shard that owns it in the order the one-device scatter adds them (a
    # row's deltas in its own 17 lanes of a physical row, on both), so not
    # a rounding differs.  (On the CPU; on the TPU the benchmark holds the
    # cell to the reference's allowance, which is what users are promised.)
    _, sharded, single = runs
    assert (sharded["store"].spec.layout, single["store"].spec.layout) == (
        "packed", "packed")
    np.testing.assert_array_equal(sharded["before"]["feature"],
                                  single["before"]["feature"])
    np.testing.assert_array_equal(sharded["table"], single["table"])
    np.testing.assert_array_equal(sharded["prediction"], single["prediction"])
    assert not np.array_equal(sharded["after"]["feature"],
                              sharded["before"]["feature"])
