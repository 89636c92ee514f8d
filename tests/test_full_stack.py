"""Full-stack integration: every subsystem in one job.

native C++ loader → StreamingDriver (metrics + checkpoints + NaN guard +
prefetch) → MF on a dp×ps mesh with the pallas scatter store → top-K
serving from the result → checkpoint → load_model → serve again.
The closest analogue of the reference's end-to-end example jobs
(SURVEY.md §4 "integration-style tests dominate").
"""
import io
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_parameter_server_tpu import (
    DriverConfig,
    ShardedParamStore,
    StreamingDriver,
)
from flink_parameter_server_tpu.models.matrix_factorization import (
    OnlineMatrixFactorization,
    SGDUpdater,
)
from flink_parameter_server_tpu.models.topk_recommender import query_topk
from flink_parameter_server_tpu.training import checkpoint
from flink_parameter_server_tpu.utils.initializers import ranged_random_factor

native = pytest.importorskip("flink_parameter_server_tpu.data.native_loader")

try:
    native.get_lib()
    HAVE_NATIVE = True
except native.NativeUnavailable:
    HAVE_NATIVE = False


@pytest.mark.skipif(not HAVE_NATIVE, reason="no C++ toolchain")
def test_full_stack_job(tmp_path, mesh):
    # 1. a ratings file on disk, parsed/batched by the native loader
    rng = np.random.default_rng(0)
    num_users, num_items = 128, 160
    P = rng.normal(0, 0.5, (num_users, 4))
    Q = rng.normal(0, 0.5, (num_items, 4))
    path = str(tmp_path / "ratings.data")
    with open(path, "w") as f:
        for _ in range(8000):
            u = rng.integers(0, num_users)
            i = rng.integers(0, num_items)
            r = float(P[u] @ Q[i]) + rng.normal(0, 0.05)
            f.write(f"{u}\t{i}\t{r:.4f}\t0\n")

    # 2. sharded store + driver with the full envelope
    logic = OnlineMatrixFactorization(
        num_users, 8, updater=SGDUpdater(0.08), mesh=mesh
    )
    store = ShardedParamStore.create(
        num_items, (8,), init_fn=ranged_random_factor(1, (8,)),
        mesh=mesh,
    )
    sink = io.StringIO()
    driver = StreamingDriver(
        logic,
        store,
        config=DriverConfig(
            checkpoint_dir=str(tmp_path / "ckpt"),
            checkpoint_every=20,
            metrics_every=10,
            nan_check_every=5,
            prefetch=2,
        ),
        metrics_sink=sink,
    )
    res = driver.run(
        native.stream_batches(path, 256, epochs=8, shuffle_seed=0)
    )

    # 3. it learned (vs the zero predictor)
    cols = native.load_ratings(path)
    uf = np.asarray(res.worker_state)
    itf = np.asarray(res.store.values())
    pred = np.einsum("ij,ij->i", uf[cols["user"]], itf[cols["item"]])
    rmse = float(np.sqrt(np.mean((pred - cols["rating"]) ** 2)))
    base = float(np.sqrt(np.mean(cols["rating"] ** 2)))
    assert rmse < 0.6 * base, (rmse, base)
    assert len(sink.getvalue().strip().splitlines()) >= 3  # metrics flowed

    # 4. top-K serving straight from the job result
    scores, ids = query_topk(res.store, res.worker_state, jnp.arange(4), k=5)
    assert ids.shape == (4, 5) and (np.asarray(ids) >= 0).all()

    # 5. model-load path: restore the dumped table into a fresh store and
    # serve identically
    loaded = checkpoint.load_model(str(tmp_path / "ckpt"))
    scores2, ids2 = query_topk(loaded, res.worker_state, jnp.arange(4), k=5)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids2))
