"""``models/fasttext.py``: the logic against the plain reference through
``StreamingDriver``, its tie to ``SkipGramNS``, dead lanes, the store's
layout for a 300-lane row, and the two counts.  (The hashing and the bags
are the stream's, and the benchmark's generator makes them:
``tests/chipbench_tests/test_chipbench_ft.py``.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import run, spec
from flink_parameter_server_tpu import (
    DriverConfig,
    ShardedParamStore,
    StreamingDriver,
)
from flink_parameter_server_tpu.core import store as store_mod
from flink_parameter_server_tpu.core.transform import make_train_step
from flink_parameter_server_tpu.models import fasttext as ftm
from flink_parameter_server_tpu.models import word2vec as w2vm
from flink_parameter_server_tpu.telemetry.registry import MetricsRegistry

CELL = "ft-wiki-en-300.train-pairs-zipf"
DRY = spec.resolve(spec.load_benchmark(), CELL, dry_run=True)
FAM = spec.family("ft")


def _random_store(cfg, seed):
    """The dry-run store with every block away from zero (the output block
    starts at 0, and a bag's rows then first move in the second batch)."""
    rng = np.random.default_rng(seed)
    rows = 2 * cfg["vocab_size"] + cfg["buckets"]
    values = rng.normal(0, 0.05, (rows, cfg["dim"])).astype(np.float32)
    return ShardedParamStore.from_values(jnp.asarray(values), layout="auto")


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_the_logic_is_the_reference_through_the_driver(seed):
    """Three batches through ``StreamingDriver`` on seeded random weights
    at the dry-run size: both slots within every allowance of the
    configuration, and each term of the allowance has something to allow."""
    cfg = DRY["cfg"]
    ref = spec.reference(cfg)
    logic, _ = FAM.build(cfg, 1, None)
    store = _random_store(cfg, seed)
    batches = FAM.host_batches(cfg, DRY["traffic_spec"], seed, 3)
    touched = ref.touched(batches)
    assert (touched["in"] >= 0).all() and (np.diff(touched["in"]) >= 0).all()
    before = FAM.rows(store, (), touched)
    driver = StreamingDriver(logic, store, config=DriverConfig(**cfg["driver"]))
    result = driver.run(iter(batches))
    after = FAM.rows(result.store, (), touched)
    want = ref.apply(cfg, before, touched, batches)
    failures, worst = run._check_rows(cfg["reference"], want, after, before)
    assert failures == [] and 0 < worst["share"] < 1
    for name in ("in", "out"):
        assert (after[name] != before[name]).any(axis=1).mean() > 0.99
        # not bit-equal to the reference: the allowances are in use
        assert np.abs(after[name] - want[0][name]).max() > 0


def test_without_ngrams_it_is_skipgram_with_the_mean_combiner():
    """``maxn`` 0: a bag is the word alone, ``h`` its vector, and the word
    and output rows move as ``SkipGramNS(dedup_scale=True)``'s ``IN`` and
    ``OUT`` slots do from the same start (centres and the others drawn from
    disjoint words: skip-gram counts a word's two slots as one row)."""
    V, K, d, B, N = 64, 8, 16, 96, 5
    rng = np.random.default_rng(9)
    start = rng.normal(0, 0.1, (2 * V + K, d)).astype(np.float32)
    centre = rng.integers(0, V // 2, B).astype(np.int32)
    context = rng.integers(V // 2, V, B).astype(np.int32)
    negatives = rng.integers(V // 2, V, (B, N)).astype(np.int32)
    mask = rng.random(B) > 0.1
    ft = ftm.FastTextSkipGram(0.05, V, K, 1)
    ft_store = ShardedParamStore.from_values(jnp.asarray(start))
    table, _, ft_out = jax.jit(make_train_step(ft, ft_store.spec))(
        ft_store.table, (), {
            "bag": centre[:, None], "context": ft.output_rows(context),
            "negatives": ft.output_rows(negatives), "mask": mask,
        })
    got = np.asarray(ShardedParamStore(ft_store.spec, table).values())
    sg = w2vm.SkipGramNS(0.05, dedup_scale=True, vocab_size=V)
    both = np.stack([start[:V], start[V + K:]], axis=1)  # (V, 2, d)
    sg_store = ShardedParamStore.from_values(jnp.asarray(both))
    table, _, sg_out = jax.jit(make_train_step(sg, sg_store.spec))(
        sg_store.table, (), {
            "center": centre, "context": context, "negatives": negatives,
            "mask": mask,
        })
    want = np.asarray(ShardedParamStore(sg_store.spec, table).values())
    np.testing.assert_allclose(got[:V], want[:, w2vm.IN], rtol=1e-6, atol=1e-9)
    np.testing.assert_allclose(got[V + K:], want[:, w2vm.OUT], rtol=1e-6, atol=1e-9)
    np.testing.assert_array_equal(got[V:V + K], start[V:V + K])  # no bucket named
    np.testing.assert_allclose(ft_out["loss"], sg_out["loss"], rtol=1e-6)
    assert (got[:V] != start[:V]).any() and (got[V + K:] != start[V + K:]).any()


def _one_step(logic, store, batch):
    table, _, out = jax.jit(make_train_step(logic, store.spec))(
        store.table, (), batch
    )
    return np.asarray(ShardedParamStore(store.spec, table).values()), out


def test_dead_lanes_and_dead_pairs_move_nothing_and_count_nothing():
    cfg = {**DRY["cfg"], "batch": 32}
    logic, _ = FAM.build(cfg, 1, None)
    store = _random_store(cfg, 4)
    before = np.asarray(store.values())
    (batch,) = FAM.host_batches(cfg, DRY["traffic_spec"], 12, 1)
    # rows that only a dead lane or a dead pair would name
    spare = np.setdiff1d(
        np.arange(cfg["vocab_size"]),
        np.concatenate([batch["bag"].ravel(), [0]]),
    )[:3].astype(np.int32)
    bag = batch["bag"].copy()
    mask = batch["mask"].copy()
    mask[5] = False  # an all-dead pair...
    bag[5, 0] = spare[0]  # ...naming a row no live lane names
    bag[bag[:, 0] == 0, 0] = spare[1]  # row 0 to the dead lanes alone
    out_base = cfg["vocab_size"] + cfg["buckets"]
    context = batch["context"].copy()
    spare_out = np.setdiff1d(np.arange(cfg["vocab_size"]), np.concatenate(
        [context, batch["negatives"].ravel()]) - out_base)[0]
    context[5] = out_base + spare_out
    live_batch = {**batch, "bag": bag, "mask": mask, "context": context}
    after, out = _one_step(logic, store, live_batch)
    np.testing.assert_array_equal(after[spare[0]], before[spare[0]])
    np.testing.assert_array_equal(
        after[out_base + spare_out], before[out_base + spare_out])
    # row 0 is where the store's pull clips a dead lane's -1: a dead lane
    # neither reads it into the average nor moves it
    assert not (bag[mask] == 0).any() and (bag == -1).any()
    np.testing.assert_array_equal(after[0], before[0])
    keys = np.concatenate([bag, context[:, None], batch["negatives"]], axis=1)
    live = (keys >= 0) & mask[:, None]
    assert int(out["bag_live_keys"]) == live.sum() < keys.size
    assert int(out["bag_padded_keys"]) == keys.size == 32 * 57
    assert float(out["loss"][5]) == 0.0
    touched = np.unique(keys[live])
    untouched = np.setdiff1d(np.arange(before.shape[0]), touched)
    np.testing.assert_array_equal(after[untouched], before[untouched])
    assert (after[touched] != before[touched]).any(axis=1).all()
    # what a dead lane holds does not reach a live one: the same step with
    # the dead pair named otherwise and the dead lanes' rows poisoned
    poisoned = before.copy()
    poisoned[0] = np.nan
    store2 = ShardedParamStore.from_values(jnp.asarray(poisoned), layout="auto")
    again, _ = _one_step(logic, store2, live_batch)
    np.testing.assert_array_equal(again[1:], after[1:])


def test_the_combiners_counts_are_over_live_lanes_of_all_three_key_spaces():
    """One hot bucket in every bag, one hot context: each takes the mean of
    its lanes' deltas, whatever the dead lanes beside them hold."""
    V, K, d, B = 16, 4, 8, 6
    rng = np.random.default_rng(2)
    start = rng.normal(0, 0.1, (2 * V + K, d)).astype(np.float32)
    logic = ftm.FastTextSkipGram(0.05, V, K, 3)
    out_base = V + K
    bag = np.stack([np.arange(B), np.full(B, V + 1), np.full(B, -1)], axis=1).astype(np.int32)
    batch = {
        "bag": bag, "context": np.full(B, out_base + 3, np.int32),
        "negatives": (out_base + 4 + np.arange(B * 5).reshape(B, 5) % 12).astype(np.int32),
        "mask": np.ones(B, bool),
    }
    ref = spec.reference(DRY["cfg"])
    cfg = {"learning_rate": 0.05, "vocab_size": V, "buckets": K}
    ids = ref.touched([batch])
    store = ShardedParamStore.from_values(jnp.asarray(start))
    before = {k: start[ids[k]] for k in ids}
    after, _ = _one_step(logic, store, batch)
    want, moved = ref.apply(cfg, before, ids, [batch])
    np.testing.assert_allclose(after[ids["in"]], want["in"], rtol=1e-5, atol=1e-8)
    np.testing.assert_allclose(after[ids["out"]], want["out"], rtol=1e-5, atol=1e-8)
    # the hot bucket moved by the MEAN of its six bags' gradients, each
    # whole (not divided by the bag's two rows), by hand in float64
    z = start.astype(np.float64)
    outs = np.concatenate([batch["context"][:, None], batch["negatives"]], axis=1)
    total = np.zeros(d)
    for b in range(B):
        h = (z[b] + z[V + 1]) / 2
        e = 1 / (1 + np.exp(-z[outs[b]] @ h))
        e[0] -= 1
        total -= 0.05 * e @ z[outs[b]]
    np.testing.assert_allclose(
        after[V + 1], z[V + 1] + total / B, rtol=1e-5, atol=1e-8)
    assert moved["in"][np.searchsorted(ids["in"], V + 1)].max() > 0


def test_the_step_scales_each_live_lane_by_the_bincount_of_its_row(monkeypatch):
    """The deltas a step pushes on its live lanes are the unscaled ones
    times ``1 / np.bincount`` of the batch's live keys, to the bit: a
    bucket that hundreds of bags share, a context named by every third
    pair, rows named once, dead lanes in every bag (most of the lanes) and
    a masked pair whose lanes name the hot rows and count nothing."""
    V, K, d, B, G, N = 600, 64, 16, 256, 24, 5
    rng = np.random.default_rng(39)
    out_base = V + K
    bag = np.full((B, G), -1, np.int32)
    bag[:, 0] = rng.permutation(V)[:B]  # each word once
    size = rng.integers(1, G // 2, B)  # dead lanes in every bag, over half
    for r in range(B):
        bag[r, 1:size[r]] = V + rng.integers(0, K, size[r] - 1)
    bag[size > 2, 2] = V + 5  # the hot bucket
    context = (out_base + rng.integers(0, V, B)).astype(np.int32)
    context[::3] = out_base + 9
    negatives = (out_base + rng.integers(0, V, (B, N))).astype(np.int32)
    mask = np.arange(B) != 11
    bag[11, :3], context[11] = [bag[0, 0], V + 5, V + 5], out_base + 9
    keys = np.concatenate([bag, context[:, None], negatives], axis=1)
    live = (keys >= 0) & mask[:, None]
    counts = np.bincount(keys[live], minlength=2 * V + K)
    assert counts[V + 5] > 150 and counts[out_base + 9] > 80
    assert (counts == 1).sum() > 200 and live.mean() < 0.5
    pulled = rng.normal(0, 0.2, keys.shape + (d,)).astype(np.float32)
    batch = {k: jnp.asarray(v) for k, v in {
        "bag": bag, "context": context, "negatives": negatives, "mask": mask,
    }.items()}
    logic = ftm.FastTextSkipGram(0.05, V, K, G)

    def pushed():
        _, req, _ = jax.jit(lambda b, p: logic.step((), b, p))(batch, pulled)
        np.testing.assert_array_equal(np.asarray(req.mask), live)
        np.testing.assert_array_equal(
            np.asarray(req.ids), np.where(live, keys, -1))
        return np.asarray(req.deltas)

    got = pushed()
    monkeypatch.setattr(
        ftm, "occurrence_scale", lambda keys, capacity: jnp.ones(keys.shape))
    scale = np.float32(1.0) / counts[np.clip(keys, 0, None)].astype(np.float32)
    want = pushed() * scale[..., None]
    assert (want[live] != 0).any(axis=1).all()
    np.testing.assert_array_equal(
        got[live].view(np.uint32), want[live].view(np.uint32))
    assert (got[~live] == 0).all()


def test_a_300_lane_row_lies_in_three_registers_and_round_trips():
    assert store_mod._resolve_layout("auto", "add", (300,)) == "packed"
    rng = np.random.default_rng(0)
    values = rng.normal(size=(50, 300)).astype(np.float32)
    store = ShardedParamStore.from_values(jnp.asarray(values), layout="auto")
    assert store.spec.layout == "packed" and store.spec.pack == 1
    assert store.table.shape == (56, 384)  # rows aligned to 8, 300 -> 384 lanes
    assert (np.asarray(store.table)[:50, 300:] == 0).all()
    np.testing.assert_array_equal(np.asarray(store.values()), values)
    again = ShardedParamStore.from_values(store.values(), layout="auto")
    np.testing.assert_array_equal(np.asarray(again.table), np.asarray(store.table))
    full = jax.eval_shape(
        lambda: ftm.make_store(2_519_370, 2_000_000, 300)
    ).spec
    assert full.table_shape() == (7_038_744, 384) and full.capacity == 7_038_740


def test_make_store_draws_the_input_block_and_zeroes_the_output_block():
    V, K, d = 40, 24, 20
    store = ftm.make_store(V, K, d, seed=3)
    other = jax.jit(lambda s: ftm.make_store(V, K, d, seed=s))(np.uint32(4))
    values = np.asarray(store.values())
    assert values.shape == (2 * V + K, d)
    assert (values[V + K:] == 0).all()
    inputs = values[:V + K]
    assert (np.abs(inputs) <= 1 / d).all() and inputs.std() > 0.4 / d
    assert not np.array_equal(inputs, np.asarray(other.values())[:V + K])


def test_the_driver_publishes_the_lane_counts_after_the_loop():
    cfg = DRY["cfg"]
    logic, store = FAM.build(cfg, 2, None)
    batches = FAM.host_batches(cfg, DRY["traffic_spec"], 2, 3)
    registry = MetricsRegistry()
    driver = StreamingDriver(
        logic, store, config=DriverConfig(**cfg["driver"]), registry=registry,
    )
    driver.run(iter(batches))
    gauges = registry.snapshot()
    last = batches[-1]
    live = (last["bag"] >= 0).sum() + last["context"].size + last["negatives"].size
    assert gauges["bag_live_keys"][0]["value"] == live
    assert gauges["bag_padded_keys"][0]["value"] == cfg["batch"] * 57
    assert "store_rule_rows" not in gauges


def test_the_scopes_are_in_the_lowered_step():
    logic, store = FAM.build(DRY["cfg"], 1, None)
    (b,) = FAM.host_batches(DRY["cfg"], DRY["traffic_spec"], 1, 1)
    text = jax.jit(make_train_step(logic, store.spec)).lower(
        store.table, (), b
    ).as_text(debug_info=True)
    assert "ps.compute/ps.bag_pool" in text
    assert "ps.compute/ps.delta_build" in text
