"""StreamingDriver with steps_per_call=K — the production envelope at
dispatch granularity (K>1 amortises host dispatch; cadences round UP to
group boundaries).
"""
import jax
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
    TrainingDiverged,
)
from flink_parameter_server_tpu.utils.initializers import (
    normal_factor,
    ranged_random_factor,
)


def _driver(tmpdir=None, **cfg_kw):
    logic = OnlineMatrixFactorization(64, 4, updater=SGDUpdater(0.05))
    store = ShardedParamStore.create(
        96, (4,), init_fn=ranged_random_factor(0, (4,))
    )
    config = DriverConfig(
        checkpoint_dir=str(tmpdir) if tmpdir else None, prefetch=2, **cfg_kw
    )
    return StreamingDriver(logic, store, config=config)


def _stream(n=20, seed=0):
    data = synthetic_ratings(64, 96, n * 128, rank=3, seed=seed)
    return microbatches(data, 128, shuffle_seed=1)


def test_driver_k4_matches_k1():
    """Grouped dispatch is a pure batching of the same math: final
    table, worker state, cursor, and event totals all match K=1."""
    d1 = _driver(metrics_every=5, steps_per_call=1)
    d1.run(_stream())
    d4 = _driver(metrics_every=5, steps_per_call=4)
    d4.run(_stream())
    assert d4.step_idx == d1.step_idx == 20
    assert d4.metrics.total_steps == d1.metrics.total_steps == 20
    assert d4.metrics.total_events == d1.metrics.total_events
    assert d4.metrics.snapshot()["updates_per_sec"] > 0
    np.testing.assert_allclose(
        np.asarray(d4.store.values()),
        np.asarray(d1.store.values()),
        atol=1e-6,
    )


def test_driver_k4_checkpoint_rounds_to_group_boundary(tmp_path):
    """checkpoint_every=10 with K=4: the step-10 crossing is honored at
    the NEXT dispatch boundary (step 12) — never silently dropped."""
    d = _driver(tmp_path, checkpoint_every=10, steps_per_call=4)
    d.run(_stream())
    assert d._ckpt_mgr.latest_step() == 20  # close-time save
    # the mid-run crossing landed at the group boundary after step 10
    steps = d._ckpt_mgr.all_steps()
    assert 12 in steps, steps


@pytest.mark.parametrize("k", [4, 7])
def test_driver_k_resume_matches_uninterrupted(tmp_path, k):
    """Crash + resume under grouped dispatch reproduces the
    uninterrupted run (k=7 exercises the ragged tail: 20 % 7 != 0)."""
    d_full = _driver(None, steps_per_call=k)
    d_full.run(_stream())
    assert d_full.step_idx == 20

    d_a = _driver(tmp_path, checkpoint_every=4, steps_per_call=k)
    stream = list(_stream())
    d_a.run(iter(stream[:12]))  # crash after 12 batches
    d_b = _driver(tmp_path, steps_per_call=k)
    assert d_b.resume()
    assert d_b.step_idx == 12  # close-time save at the partial end
    d_b.run(iter(stream))  # same stream; cursor fast-forwards
    assert d_b.step_idx == 20
    np.testing.assert_allclose(
        np.asarray(d_b.store.values()),
        np.asarray(d_full.store.values()),
        atol=1e-6,
    )


def test_driver_k4_async_checkpoints_match_sync(tmp_path):
    """Async saves from group boundaries are donation-safe and durable —
    same resume state as sync mode."""
    d_sync = _driver(tmp_path / "sync", checkpoint_every=8,
                     steps_per_call=4)
    d_sync.run(_stream())
    d_async = _driver(tmp_path / "async", checkpoint_every=8,
                      steps_per_call=4, async_checkpoints=True)
    d_async.run(_stream())
    r_sync = _driver(tmp_path / "sync")
    r_async = _driver(tmp_path / "async")
    assert r_sync.resume() and r_async.resume()
    assert r_sync.step_idx == r_async.step_idx == 20
    np.testing.assert_array_equal(
        np.asarray(r_sync.store.values()),
        np.asarray(r_async.store.values()),
    )


def test_driver_k4_request_stop_drains_and_checkpoints(tmp_path):
    """Preemption under grouped dispatch: stop after the next group
    boundary, drain (tail may run as single steps), close-time save."""
    d = _driver(tmp_path, checkpoint_every=100, steps_per_call=4)
    stream = list(_stream())

    def stopping():
        for i, b in enumerate(stream):
            if i == 9:
                d.request_stop()
            yield b
        raise AssertionError("stop was ignored — stream exhausted")

    d.run(stopping())
    # stopped partway: cursor < 20, and the close-time save is durable
    assert 0 < d.step_idx < 20
    assert d._ckpt_mgr.latest_step() == d.step_idx
    # resume + same stream completes the job exactly
    d2 = _driver(tmp_path, steps_per_call=4)
    assert d2.resume()
    d2.run(iter(stream))
    assert d2.step_idx == 20
    d_full = _driver(None, steps_per_call=4)
    d_full.run(iter(stream))
    np.testing.assert_allclose(
        np.asarray(d2.store.values()),
        np.asarray(d_full.store.values()),
        atol=1e-6,
    )


def test_all_knobs_composed_converges(tmp_path):
    """The knob matrix rows are tested pairwise; this is the one
    everything-at-once run: driver envelope (checkpoints + NaN guard +
    metrics) x steps_per_call=16 x layout=packed x dp=4 x ps=2 mesh, at
    ML-100K-ish scale — must train (beat the zero predictor) and match
    the dense one-step-a-dispatch oracle on the same stream."""
    from flink_parameter_server_tpu.parallel.mesh import make_mesh

    num_users, num_items, dim = 960, 1682, 16
    mesh = make_mesh(ps_parallelism=2)
    data = synthetic_ratings(num_users, num_items, 60_000, rank=6, seed=2)

    def run(layout, K):
        logic = OnlineMatrixFactorization(
            num_users, dim, updater=SGDUpdater(0.05), mesh=mesh,
        )
        store = ShardedParamStore.create(
            num_items, (dim,), mesh=mesh,
            init_fn=ranged_random_factor(0, (dim,)), layout=layout,
        )
        cfg = DriverConfig(
            checkpoint_dir=str(tmp_path / f"{layout}_{K}"),
            checkpoint_every=20, nan_check_every=10, metrics_every=20,
            steps_per_call=K,
        )
        d = StreamingDriver(logic, store, config=cfg)
        d.run(microbatches(data, 2048, epochs=2, shuffle_seed=3))
        return d

    d_all = run("packed", 16)
    d_ref = run("dense", 1)

    def rmse(d):
        uf = np.asarray(d._state)
        itf = np.asarray(d.store.values())
        pred = np.einsum(
            "ij,ij->i", uf[data["user"]], itf[data["item"]]
        )
        return float(np.sqrt(np.mean((pred - data["rating"]) ** 2)))

    base = float(np.sqrt(np.mean(data["rating"] ** 2)))
    r_all, r_ref = rmse(d_all), rmse(d_ref)
    assert np.isfinite(np.asarray(d_all.store.values())).all()
    assert r_all < 0.9 * base  # genuinely trained
    # same updates, different layout and dispatch grouping only
    assert abs(r_all - r_ref) < 0.02, (r_all, r_ref)


def test_driver_k4_nan_guard_fires_at_group_boundary(tmp_path):
    """A NaN injected at step 8 (inside the second group) is caught at
    that group's boundary and rolls back to the last durable save."""
    d = _driver(tmp_path, checkpoint_every=4, nan_check_every=1,
                steps_per_call=4)

    def poisoned():
        for i, b in enumerate(_stream()):
            if i >= 7:
                b = dict(b, rating=b["rating"] * np.nan)
            yield b

    with pytest.raises(TrainingDiverged, match="step 8"):
        d.run(poisoned())
    assert d.step_idx == 4  # rolled back to the durable checkpoint
    assert np.isfinite(np.asarray(d.store.values())).all()


# -- transform_batched(steps_per_call=K), below the driver -------------------


@pytest.mark.parametrize("spc", [2, 3])
def test_steps_per_call_matches_single_dispatch(spc):
    """K steps per jitted dispatch (lax.scan) must be per-step identical
    to the one-dispatch-per-batch loop — including a tail shorter than K
    and per-batch worker outputs."""
    data = synthetic_ratings(60, 90, 2_000, rank=4, noise=0.01, seed=4)

    def run(steps_per_call):
        logic = OnlineMatrixFactorization(
            60, 8, updater=SGDUpdater(0.08), seed=0
        )
        store = ShardedParamStore.create(
            90, (8,), init_fn=normal_factor(1, (8,)),
        )
        return transform_batched(
            microbatches(data, 256, epochs=1, shuffle_seed=0),
            logic, store, rng=jax.random.PRNGKey(0),
            steps_per_call=steps_per_call,
        )

    a, b = run(1), run(spc)
    np.testing.assert_allclose(
        np.asarray(a.store.values()), np.asarray(b.store.values()),
        atol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(a.worker_state), np.asarray(b.worker_state), atol=1e-6,
    )
    assert len(a.worker_outputs) == len(b.worker_outputs)
    for oa, ob in zip(a.worker_outputs, b.worker_outputs):
        ja, jb = jax.tree.leaves(oa), jax.tree.leaves(ob)
        for xa, xb in zip(ja, jb):
            np.testing.assert_allclose(
                np.asarray(xa), np.asarray(xb), atol=1e-6
            )


def test_steps_per_call_rejects_state_callback():
    data = synthetic_ratings(60, 90, 500, rank=2, seed=5)
    logic = OnlineMatrixFactorization(60, 4, updater=SGDUpdater(0.05))
    store = ShardedParamStore.create(90, (4,))
    with pytest.raises(ValueError, match="steps_per_call"):
        transform_batched(
            microbatches(data, 128, epochs=1), logic, store,
            steps_per_call=2, state_callback=lambda *a: None,
        )


def test_steps_per_call_sharded_mesh(mesh):
    """The scan path on a dp x ps mesh: dp shard moves to axis 1 of the
    stacked batches; results must match the per-dispatch mesh run."""
    data = synthetic_ratings(64, 96, 2_048, rank=4, noise=0.01, seed=6)

    def run(steps_per_call):
        logic = OnlineMatrixFactorization(
            64, 8, updater=SGDUpdater(0.08), seed=0, mesh=mesh
        )
        store = ShardedParamStore.create(
            96, (8,), init_fn=normal_factor(1, (8,)), mesh=mesh,
        )
        return transform_batched(
            microbatches(data, 256, epochs=1, shuffle_seed=0),
            logic, store, rng=jax.random.PRNGKey(0), mesh=mesh,
            collect_outputs=False, steps_per_call=steps_per_call,
        )

    a, b = run(1), run(4)
    np.testing.assert_allclose(
        np.asarray(a.store.values()), np.asarray(b.store.values()),
        atol=2e-5,
    )
    np.testing.assert_allclose(
        np.asarray(a.worker_state), np.asarray(b.worker_state), atol=2e-5,
    )
