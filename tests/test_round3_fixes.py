"""Round-3 regression tests: the advisor findings (r2) stay fixed.

Covers:
  * topk serving unpacks ANY packed store — including pack == 1 widths
    (65-127), whose physical rows are lane-padded to 128 and would
    shape-mismatch ``queries @ table.T`` raw.
  * StreamingDriver.run() restores signal handlers safely when the prior
    handler was installed from C (signal.getsignal() -> None).
"""
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_parameter_server_tpu.core.store import ShardedParamStore
from flink_parameter_server_tpu.models.topk_recommender import (
    make_mf_topk_step,
    query_topk,
)
from flink_parameter_server_tpu.utils.initializers import normal_factor


@pytest.mark.parametrize("width", [100, 64, 17])
def test_query_topk_packed_any_width(width):
    """Packed stores must serve top-k at every width class: pack == 1
    lane-padded (100), pack > 1 (64, 17)."""
    cap = 50
    store = ShardedParamStore.create(
        cap, (width,), dtype=jnp.float32,
        init_fn=normal_factor(0, (width,)), layout="packed",
    )
    dense = ShardedParamStore.from_values(store.values())  # dense oracle
    q_users = jnp.asarray(np.random.default_rng(0).normal(size=(4, width)),
                          jnp.float32)
    uids = jnp.arange(4, dtype=jnp.int32)
    s_packed, i_packed = query_topk(store, q_users, uids, k=5)
    s_dense, i_dense = query_topk(dense, q_users, uids, k=5)
    np.testing.assert_allclose(
        np.asarray(s_packed), np.asarray(s_dense), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_array_equal(np.asarray(i_packed), np.asarray(i_dense))


def test_mf_topk_step_packed_pack1_width():
    """The fused train+serve step on a pack==1 packed store (the exact
    ADVICE r2 repro: width-100 store -> dot_general shape mismatch)."""
    from flink_parameter_server_tpu.models.matrix_factorization import (
        OnlineMatrixFactorization,
        SGDUpdater,
    )

    width, cap, users, b = 100, 40, 8, 16
    store = ShardedParamStore.create(
        cap, (width,), dtype=jnp.float32,
        init_fn=normal_factor(0, (width,)), layout="packed",
    )
    logic = OnlineMatrixFactorization(users, width, updater=SGDUpdater(0.01))
    state = logic.init_state(jax.random.PRNGKey(0))
    step = jax.jit(make_mf_topk_step(logic, store.spec, k=3))
    rng = np.random.default_rng(1)
    batch = {
        "user": jnp.asarray(rng.integers(0, users, b), jnp.int32),
        "item": jnp.asarray(rng.integers(0, cap, b), jnp.int32),
        "rating": jnp.asarray(rng.normal(size=b), jnp.float32),
        "mask": jnp.ones(b, bool),
        "query_user": jnp.arange(4, dtype=jnp.int32),
    }
    table, state, out = step(store.table, state, batch)
    assert out["topk_ids"].shape == (4, 3)
    assert np.isfinite(np.asarray(out["topk_scores"])).all()


def test_driver_restores_none_signal_handler(monkeypatch):
    """A prior C-installed handler reads back as None; run() must not
    crash restoring it (TypeError at exit of a successful run)."""
    from flink_parameter_server_tpu.models.matrix_factorization import (
        OnlineMatrixFactorization,
        SGDUpdater,
    )
    from flink_parameter_server_tpu.training.driver import (
        DriverConfig,
        StreamingDriver,
    )

    sig = signal.SIGUSR2
    orig = signal.getsignal(sig)
    real_signal = signal.signal

    def fake_signal(s, h):
        r = real_signal(s, h)
        # emulate a C-installed prior handler on first install
        return None if s == sig and h is not orig else r

    monkeypatch.setattr(signal, "signal", fake_signal)
    store = ShardedParamStore.create(
        16, (8,), dtype=jnp.float32, init_fn=normal_factor(0, (8,)),
    )
    logic = OnlineMatrixFactorization(4, 8, updater=SGDUpdater(0.01))
    driver = StreamingDriver(
        logic, store, config=DriverConfig(stop_signals=(sig,)),
    )
    rng = np.random.default_rng(0)
    b = 8
    batches = [{
        "user": jnp.asarray(rng.integers(0, 4, b), jnp.int32),
        "item": jnp.asarray(rng.integers(0, 16, b), jnp.int32),
        "rating": jnp.asarray(rng.normal(size=b), jnp.float32),
        "mask": jnp.ones(b, bool),
    }]
    driver.run(batches)  # must not raise TypeError in the finally block
    # the unrecoverable C handler is mapped to SIG_DFL, not left as the
    # driver's _request_stop closure
    assert signal.getsignal(sig) == signal.SIG_DFL
    real_signal(sig, orig)
