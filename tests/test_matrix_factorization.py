"""MF end-to-end: convergence, sharded-vs-single parity, event-API parity.

The integration-test style mirrors the reference (SURVEY.md §4): whole
pipeline on a small in-memory dataset, assert convergence.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_parameter_server_tpu.data.movielens import synthetic_ratings
from flink_parameter_server_tpu.data.streams import microbatches
from flink_parameter_server_tpu.models.matrix_factorization import (
    MFWorkerLogic,
    SGDUpdater,
    ps_online_mf,
)


def _rmse(result, data, num_users):
    user_f = np.asarray(result.worker_state)
    item_f = np.asarray(result.store.values())
    pred = np.einsum("ij,ij->i", user_f[data["user"]], item_f[data["item"]])
    return float(np.sqrt(np.mean((pred - data["rating"]) ** 2)))


def test_mf_converges_single_device():
    data = synthetic_ratings(200, 300, 20_000, rank=4, noise=0.01, seed=1)
    stream = microbatches(data, batch_size=512, epochs=8, shuffle_seed=0)
    res = ps_online_mf(
        stream,
        num_users=200,
        num_items=300,
        dim=8,
        learning_rate=0.08,
        collect_outputs=False,
    )
    rmse = _rmse(res, data, 200)
    base = float(np.sqrt(np.mean(data["rating"] ** 2)))
    assert rmse < 0.5 * base, (rmse, base)


def test_mf_sharded_matches_convergence(mesh):
    from flink_parameter_server_tpu.models.matrix_factorization import (
        OnlineMatrixFactorization,
    )

    data = synthetic_ratings(128, 256, 8_000, rank=4, noise=0.01, seed=2)
    # under the mesh the user factors lie with two keyed workers and the
    # loop routes the stream to them (data/keyed.py): a microbatch is then
    # two lane blocks, one a worker.  One device is given those same
    # microbatches, so that both runs apply the same records in the same
    # bulk-synchronous steps
    keyed = list(
        OnlineMatrixFactorization(128, 8, mesh=mesh).key_router().route(
            microbatches(data, batch_size=256, epochs=6, shuffle_seed=0)
        )
    )
    assert sum(int(b["mask"].sum()) for b in keyed) == 6 * 8_000
    res = ps_online_mf(
        iter(keyed),
        num_users=128,
        num_items=256,
        dim=8,
        learning_rate=0.08,
        mesh=mesh,
        collect_outputs=False,
    )
    rmse = _rmse(res, data, 128)
    base = float(np.sqrt(np.mean(data["rating"] ** 2)))
    assert rmse < 0.6 * base, (rmse, base)
    # sharded run must match the unsharded run bit-for-bit-ish: same math,
    # same init (deterministic per-id), same microbatches; what differs is
    # the device layout and the order in which a row's deltas are summed
    res_single = ps_online_mf(
        iter(keyed),
        num_users=128,
        num_items=256,
        dim=8,
        learning_rate=0.08,
        collect_outputs=False,
    )
    np.testing.assert_allclose(
        np.asarray(res.store.values()),
        np.asarray(res_single.store.values()),
        atol=1e-4,
    )
    np.testing.assert_allclose(
        np.asarray(res.worker_state)[:128],
        np.asarray(res_single.worker_state),
        atol=1e-4,
    )


def test_event_api_mf_agrees_with_batched_math():
    """One rating through the event-API MFWorkerLogic must produce exactly
    the SGDUpdater math (reference §3.2 data path)."""
    from flink_parameter_server_tpu import SimplePSLogic, transform

    updater = SGDUpdater(learning_rate=0.1, regularization=0.0)
    worker = MFWorkerLogic(dim=4, updater=updater, seed=5)
    item_init = np.full(4, 0.1, np.float32)

    logic = SimplePSLogic(
        init=lambda _k: item_init.copy(), update=lambda c, d: c + d
    )
    res = transform([(0, 7, 1.0)], worker, logic)
    (u, i, pred) = res.worker_outputs[0]
    assert (u, i) == (0, 7)
    final_item = dict(res.server_outputs)[7]
    user0 = np.asarray(worker._init(jnp.array([0]))[0])
    expected_pred = float(user0 @ item_init)
    assert pred == pytest.approx(expected_pred, rel=1e-5)
    err = 1.0 - expected_pred
    np.testing.assert_allclose(
        final_item, item_init + 0.1 * err * user0, rtol=1e-5
    )


def test_query_topk_exclusions_exceeding_catalogue():
    """k + |exclude| > catalogue size must not crash lax.top_k; excluded
    and missing candidates come back as id -1 / -inf."""
    import jax
    from flink_parameter_server_tpu.core.store import ShardedParamStore
    from flink_parameter_server_tpu.models.topk_recommender import query_topk

    item_store = ShardedParamStore.from_values(
        jnp.eye(6, 4, dtype=jnp.float32)
    )  # 6 items, dim 4
    user_vectors = jnp.ones((2, 4), jnp.float32)
    exclude = jnp.tile(jnp.array([[0, 1, 2, 3, 4]]), (2, 1))  # ban 5 of 6
    scores, ids = query_topk(
        item_store, user_vectors, jnp.array([0, 1]), k=4, exclude=exclude
    )
    assert ids.shape == (2, 4)
    assert ids[0, 0] == 5  # the only unbanned item wins
    assert (ids[0, 1:] == -1).all()  # rest padded


def test_transform_with_model_load_simple_overload():
    """The (param_init, param_update) overload of model-load must work."""
    from flink_parameter_server_tpu import transform_with_model_load
    from tests.test_transform_local import CountingWorker

    res = transform_with_model_load(
        [("a", 7)],
        [("a", 1)],
        CountingWorker,
        param_init=lambda _k: 0,
        param_update=lambda c, d: c + d,
    )
    assert dict(res.server_outputs)["a"] == 8


def test_make_mf_topk_step_interleaved_queries():
    """The fused train+serve step answers in-stream queries against the
    pre-push table — the reference's interleaved query events."""
    from flink_parameter_server_tpu.core.store import ShardedParamStore
    from flink_parameter_server_tpu.models.matrix_factorization import (
        OnlineMatrixFactorization,
        SGDUpdater,
    )
    from flink_parameter_server_tpu.models.topk_recommender import (
        make_mf_topk_step,
    )
    from flink_parameter_server_tpu.ops.topk import dense_topk
    from flink_parameter_server_tpu.utils.initializers import (
        ranged_random_factor,
    )

    logic = OnlineMatrixFactorization(32, 4, updater=SGDUpdater(0.05))
    store = ShardedParamStore.create(
        48, (4,), init_fn=ranged_random_factor(1, (4,))
    )
    step = jax.jit(make_mf_topk_step(logic, store.spec, k=5))
    state = logic.init_state(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {
        "user": jnp.asarray(rng.integers(0, 32, 64).astype(np.int32)),
        "item": jnp.asarray(rng.integers(0, 48, 64).astype(np.int32)),
        "rating": jnp.asarray(rng.normal(0, 1, 64).astype(np.float32)),
        "mask": jnp.ones(64, bool),
        "query_user": jnp.asarray([0, 5, 9], jnp.int32),
    }
    table2, state2, out = step(store.table, state, batch)
    assert out["topk_ids"].shape == (3, 5)
    # queries were served against the PRE-push table with POST-update
    # user vectors (bounded staleness semantics)
    q = jnp.take(state2, batch["query_user"], axis=0)
    want_scores, want_ids = dense_topk(store.table, q, 5, valid_rows=48)
    np.testing.assert_array_equal(
        np.asarray(out["topk_ids"]), np.asarray(want_ids)
    )
    np.testing.assert_allclose(
        np.asarray(out["topk_scores"]), np.asarray(want_scores), atol=1e-5
    )


def test_query_topk_on_packed_store():
    """Regression: serving must see LOGICAL rows — a packed item store
    fed raw physical rows into the MIPS matmul (shape error at best,
    wrong neighbours at worst).  Packed results must equal dense."""
    import numpy as np

    from flink_parameter_server_tpu.core.store import ShardedParamStore
    from flink_parameter_server_tpu.models.topk_recommender import query_topk

    rng = np.random.default_rng(3)
    vals = jnp.asarray(rng.normal(size=(100, 64)), jnp.float32)
    users = jnp.asarray(rng.normal(size=(16, 64)), jnp.float32)
    uids = jnp.arange(8, dtype=jnp.int32)

    dense = ShardedParamStore.from_values(vals)
    packed = ShardedParamStore.from_values(vals, layout="packed")
    assert packed.spec.pack == 2  # really packed

    sd, idd = query_topk(dense, users, uids, k=5)
    sp, idp = query_topk(packed, users, uids, k=5)
    np.testing.assert_array_equal(np.asarray(idd), np.asarray(idp))
    np.testing.assert_allclose(
        np.asarray(sd), np.asarray(sp), rtol=1e-5, atol=1e-6
    )

    # with exclusions, too
    excl = jnp.asarray(np.asarray(idd[:, :2]))
    sd2, idd2 = query_topk(dense, users, uids, k=5, exclude=excl)
    sp2, idp2 = query_topk(packed, users, uids, k=5, exclude=excl)
    np.testing.assert_array_equal(np.asarray(idd2), np.asarray(idp2))


def test_ps_online_mf_layout_knob_matches_default():
    """The canonical wrapper must reach the store's layout knob without
    changing the math: identical stream -> near-identical factors vs
    default."""
    data = synthetic_ratings(100, 150, 6_000, rank=4, noise=0.01, seed=3)

    def run(**kw):
        stream = microbatches(data, batch_size=256, epochs=2,
                              shuffle_seed=0)
        return ps_online_mf(
            stream, num_users=100, num_items=150, dim=8,
            learning_rate=0.08, seed=0, collect_outputs=False, **kw,
        )

    base = run()
    alt = run(layout="packed")
    np.testing.assert_allclose(
        np.asarray(alt.store.values()),
        np.asarray(base.store.values()),
        rtol=0, atol=5e-5,
    )
    np.testing.assert_allclose(
        np.asarray(alt.worker_state),
        np.asarray(base.worker_state),
        rtol=0, atol=5e-5,
    )
