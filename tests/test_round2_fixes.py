"""Round-2 regression tests: the advisor/verdict findings stay fixed.

Covers (the advisor's findings r1 + VERDICT r1 "weak"):
  * transform_batched must not consume the caller's store/state (donation
    contract now matches transform_dense).
  * JobCheckpointManager.save(force=True) replaces a step without a
    zero-durable-checkpoint window and leaves no trash dir behind.
  * event-backend routing hash is PYTHONHASHSEED-independent.
"""
import jax.numpy as jnp
import numpy as np

from flink_parameter_server_tpu.core.store import ShardedParamStore
from flink_parameter_server_tpu.core.transform import (
    stable_route_hash,
    transform_batched,
)
from flink_parameter_server_tpu.models.matrix_factorization import (
    OnlineMatrixFactorization,
    SGDUpdater,
)
from flink_parameter_server_tpu.training import checkpoint
from flink_parameter_server_tpu.utils.initializers import ranged_random_factor


def test_transform_batched_does_not_consume_inputs():
    """The caller's store must stay readable after the run (the jitted
    step donates its buffers; transform must copy first)."""
    logic = OnlineMatrixFactorization(8, 4, updater=SGDUpdater(0.1))
    store = ShardedParamStore.create(
        16, (4,), init_fn=ranged_random_factor(1, (4,))
    )
    before = np.asarray(store.values()).copy()
    batch = {
        "user": jnp.array([0, 1, 2, 3]),
        "item": jnp.array([1, 2, 3, 4]),
        "rating": jnp.ones(4),
        "mask": jnp.ones(4, bool),
    }
    result = transform_batched([batch, batch], logic, store)
    # input store unchanged and alive; result store differs
    np.testing.assert_allclose(np.asarray(store.values()), before)
    assert not np.allclose(np.asarray(result.store.values()), before)


def test_force_resave_replaces_without_gap(tmp_path):
    import os

    mgr = checkpoint.JobCheckpointManager(str(tmp_path / "mgr"), max_to_keep=2)
    s1 = ShardedParamStore.from_values(jnp.ones((4, 2)))
    s2 = ShardedParamStore.from_values(jnp.full((4, 2), 7.0))
    assert mgr.save(5, s1)
    mgr.wait()
    assert mgr.save(5, s2, force=True)
    restored, _, _ = mgr.restore_latest(s2.spec)
    np.testing.assert_allclose(np.asarray(restored.values()), 7.0)
    # the rename-aside trash dir must be pruned after the commit
    assert not any(
        p.startswith(".replacing") for p in os.listdir(tmp_path / "mgr")
    )
    mgr.close()


def test_force_resave_non_latest_step(tmp_path):
    """Replacing a step BELOW latest must bypass orbax's save-interval
    policy and must never destroy the old copy if the save is rejected."""
    import os

    mgr = checkpoint.JobCheckpointManager(str(tmp_path / "m2"), max_to_keep=4)
    s10 = ShardedParamStore.from_values(jnp.ones((4, 2)))
    s20 = ShardedParamStore.from_values(jnp.full((4, 2), 2.0))
    s10b = ShardedParamStore.from_values(jnp.full((4, 2), 9.0))
    assert mgr.save(10, s10)
    assert mgr.save(20, s20)
    mgr.wait()
    assert mgr.save(10, s10b, force=True)  # below latest_step
    restored, _, _ = checkpoint._payload_to_state(
        mgr._mgr.restore(10), s10b.spec
    )
    np.testing.assert_allclose(np.asarray(restored.values()), 9.0)
    assert not any(
        p.startswith(".replacing") for p in os.listdir(tmp_path / "m2")
    )
    mgr.close()


def test_stable_route_hash_deterministic():
    # ints keep identity semantics (the reference's Int hashCode)
    assert stable_route_hash(42) == 42
    # strings: pinned crc32, not PYTHONHASHSEED-randomised hash()
    import zlib

    assert stable_route_hash("user:9") == zlib.crc32(b"user:9")
    assert stable_route_hash("user:9") == stable_route_hash("user:9")


def test_mf_dedup_scale_means_duplicate_updates():
    """With dedup_scale, k identical (user,item) records in one batch move
    the factors by ONE averaged step, not k summed steps."""
    import jax

    def run(dedup):
        logic = OnlineMatrixFactorization(
            4, 4, updater=SGDUpdater(0.1), dedup_scale=dedup,
            num_items=8 if dedup else None,
        )
        store = ShardedParamStore.create(
            8, (4,), init_fn=ranged_random_factor(1, (4,))
        )
        batch = {
            "user": jnp.zeros(4, jnp.int32),
            "item": jnp.full(4, 3, jnp.int32),
            "rating": jnp.ones(4),
            "mask": jnp.ones(4, bool),
        }
        res = transform_batched([batch], logic, store)
        return (
            np.asarray(res.worker_state),
            np.asarray(res.store.values()),
            store,
        )

    u_sum, i_sum, store0 = run(False)
    u_mean, i_mean, _ = run(True)
    base_i = np.asarray(store0.values())
    logic1 = OnlineMatrixFactorization(4, 4, updater=SGDUpdater(0.1))
    store1 = ShardedParamStore.create(
        8, (4,), init_fn=ranged_random_factor(1, (4,))
    )
    one = {
        "user": jnp.zeros(1, jnp.int32),
        "item": jnp.full(1, 3, jnp.int32),
        "rating": jnp.ones(1),
        "mask": jnp.ones(1, bool),
    }
    res1 = transform_batched([one], logic1, store1)
    # mean-combined quadruplicate == one single-record step
    np.testing.assert_allclose(
        i_mean[3], np.asarray(res1.store.values())[3], rtol=1e-5
    )
    np.testing.assert_allclose(
        u_mean[0], np.asarray(res1.worker_state)[0], rtol=1e-5
    )
    # and the sum path moved 4x as far from the start
    np.testing.assert_allclose(
        i_sum[3] - base_i[3], 4.0 * (i_mean[3] - base_i[3]), rtol=1e-4
    )


def test_pa_event_duplicate_feature_ids():
    """Duplicate feature ids within one example must still complete the
    countdown under the O(1) per-answer waiting index."""
    from flink_parameter_server_tpu.core.transform import transform
    from flink_parameter_server_tpu.models.passive_aggressive import (
        PABinaryWorkerLogic,
    )

    data = [
        (np.array([1, 1, 3]), np.array([1.0, 0.5, 2.0]), 1.0),
        (np.array([3, 4]), np.array([1.0, 1.0]), -1.0),
    ]
    res = transform(
        data,
        lambda: PABinaryWorkerLogic(),
        param_init=lambda pid: np.zeros((), np.float32),
        param_update=lambda cur, delta: cur + delta,
    )
    # every example produced an output (no example stuck pending)
    assert len(res.worker_outputs) == len(data)
