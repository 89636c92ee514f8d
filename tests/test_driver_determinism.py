"""StreamingDriver + determinism-mode tests.

Determinism (SURVEY.md §5 "Race detection"): the reference *embraces*
races (async SGD, JVM); its tests cope by asserting on sets.  The rebuild
does better: with fixed seeds and schedules, runs are bitwise
reproducible — async effects become debuggable.  These tests pin that
property for both backends.
"""
import os

import jax
import numpy as np
import pytest

from flink_parameter_server_tpu.core.store import ShardedParamStore
from flink_parameter_server_tpu.data.movielens import synthetic_ratings
from flink_parameter_server_tpu.data.streams import microbatches
from flink_parameter_server_tpu.models.matrix_factorization import (
    OnlineMatrixFactorization,
    SGDUpdater,
    ps_online_mf,
)
from flink_parameter_server_tpu.training.driver import (
    DriverConfig,
    StreamingDriver,
)
from flink_parameter_server_tpu.utils.initializers import ranged_random_factor


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


def test_driver_runs_with_metrics(tmp_path):
    d = _driver(metrics_every=5)
    res = d.run(_stream())
    assert d.metrics.total_steps == 20
    snap = d.metrics.snapshot()
    assert snap["updates_per_sec"] > 0 and snap["dispatch_interval_p50_ms"] > 0
    ids, vals = res.server_outputs[0]
    assert vals.shape == (96, 4)


def test_driver_checkpoint_and_resume(tmp_path):
    d1 = _driver(tmp_path, checkpoint_every=10)
    d1.run(_stream())
    assert d1._ckpt_mgr.latest_step() == 20  # final durable save

    # Fresh driver resumes from the saved cursor and state.
    d2 = _driver(tmp_path)
    assert d2.resume()
    assert d2.step_idx == 20
    np.testing.assert_allclose(
        np.asarray(d2.store.values()), np.asarray(d1.store.values())
    )
    # feeding a NEW stream: opt out of the cursor fast-forward
    d2.run(_stream(5, seed=3), fast_forward=False)
    assert d2.step_idx == 25


def test_driver_resume_does_not_double_apply(tmp_path):
    """Crash-at-step-K resume: re-feeding the same stream must fast-forward
    past the consumed prefix, reproducing the uninterrupted run exactly."""
    # uninterrupted oracle
    d_full = _driver(None)
    d_full.run(_stream())
    # interrupted run: checkpoint every 10, stop after 10 steps
    d_a = _driver(tmp_path, checkpoint_every=10)
    stream = list(_stream())
    d_a.run(iter(stream[:10]))  # "crash" right at the checkpoint
    d_b = _driver(tmp_path)
    assert d_b.resume() and d_b.step_idx == 10
    d_b.run(iter(stream))  # SAME stream from the start; driver skips 10
    assert d_b.step_idx == 20
    np.testing.assert_allclose(
        np.asarray(d_b.store.values()),
        np.asarray(d_full.store.values()),
        atol=1e-6,
    )


def test_batched_backend_bitwise_deterministic():
    r1 = ps_online_mf(
        _stream(), num_users=64, num_items=96, dim=4, collect_outputs=False
    )
    r2 = ps_online_mf(
        _stream(), num_users=64, num_items=96, dim=4, collect_outputs=False
    )
    np.testing.assert_array_equal(
        np.asarray(r1.store.values()), np.asarray(r2.store.values())
    )
    np.testing.assert_array_equal(
        np.asarray(r1.worker_state), np.asarray(r2.worker_state)
    )


def test_event_backend_schedule_deterministic():
    """Same config + same input order ⇒ identical event schedule,
    including the interleaved (racy) one."""
    from tests.test_transform_local import CountingWorker
    from flink_parameter_server_tpu import transform

    data = [("k", i) for i in range(30)]

    def run():
        return transform(
            list(data),
            CountingWorker,
            param_init=lambda _k: 0,
            param_update=lambda c, d: c + d,
            worker_parallelism=3,
            input_window=5,
        )

    a, b = run(), run()
    assert a.worker_outputs == b.worker_outputs  # same stale-read pattern
    assert a.server_outputs == b.server_outputs


def test_prefetch_propagates_stream_errors():
    """A crashed data iterator must raise, not masquerade as end-of-stream."""
    from flink_parameter_server_tpu.data.streams import prefetch

    def broken():
        yield 1
        yield 2
        raise RuntimeError("stream died")

    it = prefetch(broken(), size=2)
    assert next(it) == 1 and next(it) == 2
    with pytest.raises(RuntimeError, match="stream died"):
        next(it)


def test_driver_usable_after_midrun_crash(tmp_path):
    """If the stream dies mid-run, the driver reloads its last checkpoint
    and stays usable (no deleted-array references)."""
    d = _driver(tmp_path, checkpoint_every=5)

    def dying():
        for i, b in enumerate(_stream()):
            if i == 8:
                raise RuntimeError("boom")
            yield b

    with pytest.raises(RuntimeError, match="boom"):
        d.run(dying())
    # recovered to the step-5 checkpoint; store is readable and training
    # can continue
    assert d.step_idx == 5
    assert np.isfinite(np.asarray(d.store.values())).all()
    d.run(_stream(3), fast_forward=False)


def test_nan_guard_detects_and_rolls_back(tmp_path):
    """Failure detection (SURVEY §5): a diverging stream raises
    TrainingDiverged and the driver rolls back to the last checkpoint."""
    from flink_parameter_server_tpu.training.driver import TrainingDiverged

    d = _driver(tmp_path, checkpoint_every=5, nan_check_every=1)

    def poisoned():
        for i, b in enumerate(_stream()):
            if i >= 7:
                b = dict(b, rating=b["rating"] * np.nan)
            yield b

    with pytest.raises(TrainingDiverged, match="step 8"):
        d.run(poisoned())
    assert d.step_idx == 5  # rolled back to the durable checkpoint
    assert np.isfinite(np.asarray(d.store.values())).all()


def test_nan_guard_blocks_poisoned_checkpoint(tmp_path):
    """A NaN landing exactly on a checkpoint step must be caught BEFORE
    the save (even when the step misses the nan_check_every modulus), so
    the rollback point is never poisoned."""
    from flink_parameter_server_tpu.training.driver import TrainingDiverged

    d = _driver(tmp_path, checkpoint_every=5, nan_check_every=7)

    def poisoned():
        for i, b in enumerate(_stream()):
            if i == 9:  # global step 10 — a checkpoint step, not a 7-multiple
                b = dict(b, rating=b["rating"] * np.inf)
            yield b

    with pytest.raises(TrainingDiverged, match="step 10"):
        d.run(poisoned())
    assert d.step_idx == 5
    assert np.isfinite(np.asarray(d.store.values())).all()


def test_async_checkpoints_match_sync(tmp_path):
    """async_checkpoints=True produces the same checkpoint/resume state as
    the synchronous path (saves drain before any read or rewrite)."""
    d_sync = _driver(tmp_path / "sync", checkpoint_every=7)
    d_sync.run(_stream())
    d_async = _driver(tmp_path / "async", checkpoint_every=7,
                      async_checkpoints=True)
    d_async.run(_stream())

    r_sync = _driver(tmp_path / "sync")
    r_async = _driver(tmp_path / "async", async_checkpoints=True)
    assert r_sync.resume() and r_async.resume()
    assert r_sync.step_idx == r_async.step_idx == 20
    np.testing.assert_allclose(
        np.asarray(r_sync.store.values()), np.asarray(r_async.store.values())
    )
    # mid-run crash recovery also drains correctly
    d2 = _driver(tmp_path / "async", checkpoint_every=5,
                 async_checkpoints=True, nan_check_every=1)
    from flink_parameter_server_tpu.training.driver import TrainingDiverged

    def poisoned():
        for i, b in enumerate(_stream()):
            if i == 8:
                b = dict(b, rating=b["rating"] * np.nan)
            yield b

    with pytest.raises(TrainingDiverged):
        d2.run(poisoned(), fast_forward=False)
    assert np.isfinite(np.asarray(d2.store.values())).all()


def test_preemption_signal_stops_saves_and_resumes(tmp_path):
    """stop_signals (SURVEY.md §5 failure detection; the reference's
    stop-with-savepoint analogue): SIGUSR1 mid-stream stops feeding,
    the driver checkpoints what completed, and a fresh driver resumes
    from the cursor to the same final state as an uninterrupted run."""
    import signal

    # uninterrupted oracle
    d_full = _driver()
    full = d_full.run(_stream())
    _ids, full_vals = full.server_outputs[0]

    # interrupted run: the signal fires while batches are still flowing
    d1 = _driver(tmp_path, stop_signals=(signal.SIGUSR1,))

    def interrupting():
        for n, b in enumerate(_stream()):
            if n == 7:
                os.kill(os.getpid(), signal.SIGUSR1)
            yield b

    d1.run(interrupting())
    assert d1._stop_requested
    # stopped early (some slack for already-yielded batches)
    assert 7 <= d1.step_idx < 20, d1.step_idx
    assert d1._ckpt_mgr.latest_step() == d1.step_idx  # durable save

    # resume + replay the same logical stream to completion
    d2 = _driver(tmp_path)
    assert d2.resume()
    assert d2.step_idx == d1.step_idx
    res = d2.run(_stream())
    assert d2.step_idx == 20
    _ids2, vals2 = res.server_outputs[0]
    # bitwise: resume replays the identical batch sequence through the
    # identical jitted steps (the module's determinism guarantee)
    np.testing.assert_array_equal(np.asarray(vals2), np.asarray(full_vals))


def test_request_stop_programmatic(tmp_path):
    """request_stop() from a step callback stops the run gracefully."""
    d = _driver(tmp_path)

    def stopping():
        for n, b in enumerate(_stream()):
            if n == 5:
                d.request_stop()
            yield b

    d.run(stopping())
    assert 5 <= d.step_idx < 20
    # a fresh run clears the stop flag and completes
    d2 = _driver()
    d2.run(_stream(n=3))
    assert d2.step_idx == 3
