"""Aux subsystem tests: checkpoint/resume (incl. shard elasticity),
metrics, data streams, dedup ops (SURVEY.md §5 obligations)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_parameter_server_tpu.core.store import ShardedParamStore, StoreSpec
from flink_parameter_server_tpu.data.streams import microbatches, prefetch
from flink_parameter_server_tpu.ops.dedup import (
    occurrence_counts,
    occurrence_scale,
)
from flink_parameter_server_tpu.training import checkpoint
from flink_parameter_server_tpu.training.metrics import StepMetrics
from flink_parameter_server_tpu.utils.initializers import ranged_random_factor


def test_checkpoint_roundtrip(tmp_path, mesh):
    init = ranged_random_factor(3, (4,))
    store = ShardedParamStore.create(50, (4,), init_fn=init, mesh=mesh)
    state = {"user": jnp.arange(12.0).reshape(3, 4)}
    path = str(tmp_path / "ckpt1")
    checkpoint.save(path, store, state, step=7, extra={"lr": 0.1})
    restored, rstate, meta = checkpoint.restore(path, store.spec)
    np.testing.assert_allclose(
        np.asarray(restored.values()), np.asarray(store.values())
    )
    np.testing.assert_allclose(np.asarray(rstate["user"]), np.asarray(state["user"]))
    assert meta["step"] == 7 and meta["lr"] == pytest.approx(0.1)


def test_checkpoint_shard_elasticity(tmp_path, mesh):
    """Save at ps_parallelism=4, restore unsharded AND at a different
    padded capacity — the M→M' elasticity the reference lacks."""
    init = ranged_random_factor(5, (2,))
    store4 = ShardedParamStore.create(10, (2,), init_fn=init, mesh=mesh)
    path = str(tmp_path / "ckpt2")
    checkpoint.save(path, store4, step=1)

    spec1 = StoreSpec(capacity=10, value_shape=(2,))  # single shard
    restored, _, _ = checkpoint.restore(path, spec1)
    np.testing.assert_allclose(
        np.asarray(restored.values()), np.asarray(store4.values())
    )
    # restored store must be usable (push works at the new layout)
    out = restored.push(jnp.array([0]), jnp.ones((1, 2)))
    assert np.asarray(out.values())[0, 0] == pytest.approx(
        np.asarray(store4.values())[0, 0] + 1.0
    )


def test_checkpoint_load_model(tmp_path):
    store = ShardedParamStore.from_values(jnp.arange(12.0).reshape(6, 2))
    path = str(tmp_path / "ckpt3")
    checkpoint.save(path, store)
    loaded = checkpoint.load_model(path)
    np.testing.assert_allclose(
        np.asarray(loaded.values()), np.asarray(store.values())
    )


def test_step_metrics():
    m = StepMetrics(events_per_step=100)
    for _ in range(5):
        m.step_start()
        m.step_end()
    snap = m.snapshot()
    assert snap["steps"] == 5 and snap["events"] == 500
    assert snap["updates_per_sec"] > 0
    assert snap["dispatch_interval_p50_ms"] >= 0
    line = m.emit()
    assert '"updates_per_sec"' in line


def test_microbatches_padding_and_epochs():
    data = {"x": np.arange(10)}
    batches = list(microbatches(data, 4, epochs=2))
    assert len(batches) == 6  # 3 per epoch (last padded)
    assert batches[2]["mask"].sum() == 2  # 10 = 4+4+2
    assert batches[2]["x"].shape == (4,)


def test_prefetch_preserves_order():
    got = list(prefetch(iter(range(50)), size=4))
    assert got == list(range(50))


def _bincount_counts(ids, capacity, mask):
    """What ``occurrence_counts`` is held to, written with ``np.bincount``:
    a lane counts if its id names a row and its mask is true; every lane
    that counts reads how many such lanes hold its id, every other reads 1."""
    ids = np.asarray(ids, np.int64)
    counts = (ids >= 0) & (ids < capacity)
    if mask is not None:
        counts &= np.asarray(mask, bool)
    distinct, inverse = np.unique(ids[counts], return_inverse=True)
    out = np.ones(ids.shape, np.float32)
    out[counts] = np.bincount(inverse, minlength=len(distinct))[inverse]
    return out


_rng = np.random.default_rng(39)
_OCCURRENCE_CASES = {
    # PR 39 kept this one: duplicates across the rows of a (B, K) batch
    "duplicates": ([[3, 3, 5], [3, 9, 9]], 16, None),
    "a_mask": ([[3, 3, 5], [3, 9, 9]], 16,
               [[True, True, True], [True, False, False]]),
    "ids_of_minus_one": ([7, -1, 7, -1, -1, 0, 7], 8, None),
    "ids_past_the_table": ([15, 16, 15, 400, 16, 2**31 - 1, 0], 16, None),
    "a_batch_of_bags": (
        np.where(_rng.random((64, 57)) < 0.46, -1,
                 _rng.zipf(1.2, (64, 57)) % 500), 500, None),
    "a_batch_of_bags_masked": (
        _rng.zipf(1.3, (96, 7)) % 64, 64,
        np.broadcast_to((np.arange(96) % 5 != 0)[:, None], (96, 7))),
    "one_lane": ([4], 5, None),
    "one_dead_lane": ([-1], 5, None),
    "every_lane_the_same_id": (np.full((33, 3), 11), 12, None),
    "every_lane_distinct": (_rng.permutation(1000)[:257], 1000, None),
    "a_hash_space_of_2_to_the_30": (
        _rng.integers(2**30 - 40, 2**30 + 8, (50, 9)), 2**30, None),
    "every_lane_masked": ([1, 1, 2], 4, [False, False, False]),
    "an_empty_batch": (np.zeros((0, 3)), 4, None),
}


@pytest.mark.parametrize("case", list(_OCCURRENCE_CASES))
@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jitted"])
def test_occurrence_counts_and_scale(case, jitted):
    """The counts are ``np.bincount``'s on every lane that counts and 1 on
    every lane that does not, at any shape, for any capacity (nothing as
    long as ``capacity`` is built: 2**30 would be 4 GB of counters)."""
    ids, capacity, mask = _OCCURRENCE_CASES[case]
    ids = np.asarray(ids, np.int32)
    want = _bincount_counts(ids, capacity, mask)
    args = (jnp.asarray(ids),) + (() if mask is None else (jnp.asarray(mask),))
    count = lambda i, m=None: occurrence_counts(i, capacity, m)
    scale = lambda i, m=None: occurrence_scale(i, capacity, m)
    if jitted:
        count, scale = jax.jit(count), jax.jit(scale)
    got = count(*args)
    assert got.shape == ids.shape and got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(
        np.asarray(scale(*args)), np.float32(1.0) / want
    )
    if case == "duplicates":
        np.testing.assert_array_equal(want, [[3, 3, 1], [3, 2, 2]])
    if case == "a_mask":  # dropping row 1's two 9s leaves 3, 3, 5, 3
        np.testing.assert_array_equal(want, [[3, 3, 1], [3, 1, 1]])
