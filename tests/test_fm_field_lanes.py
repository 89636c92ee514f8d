"""The FM family's lane order (``models/factorization_machine.FieldLanes``):
the copy of ``FactorizationMachine`` and of ``DiFacto`` that a step in one
place traces computes with the field axis leading and the batch axis minor;
held here to the example-major step they replaced (PR 62's bodies, kept below
as the reference), through ``make_train_step`` on the CPU, in one place and
over two data-parallel workers.  The logic itself keeps the base contract,
``pulled`` ``keys.shape + row``, for every other caller of ``step``:
``cluster/driver.ClusterDriver`` runs it to the single-process table."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_parameter_server_tpu.core.batched import PushRequest
from flink_parameter_server_tpu.core.transform import make_train_step
from flink_parameter_server_tpu.models import difacto as df
from flink_parameter_server_tpu.models import factorization_machine as fmm
from flink_parameter_server_tpu.parallel.mesh import make_mesh

B, K, DIM, PER_FIELD = 64, 5, 4, 40
ROWS = K * PER_FIELD


def _example_major_gradients(x, w, v, loss_gradient, l2):
    """``forward_gradients`` as it stood: ``(B, K)``, sums over axis 1."""
    linear = jnp.sum(w * x, axis=-1)
    xv = x[..., None] * v
    s = jnp.sum(xv, axis=1)
    interaction = 0.5 * (jnp.sum(s * s, axis=-1) - jnp.sum(xv * xv, axis=(1, 2)))
    y_hat = linear + interaction
    g, loss = loss_gradient(y_hat)
    dw = g[:, None] * x + l2 * w
    dv = g[:, None, None] * (x[..., None] * (s[:, None, :] - xv)) + l2 * v
    return y_hat, loss, dw, dv


class ExampleMajorFM(fmm.FactorizationMachine):
    """The step as it stood: keys, rows, deltas and mask ``(B, K)``."""

    def for_workers(self, workers):
        return self

    def step(self, state, batch, pulled):
        cfg = self.config
        x = jnp.where(batch["feat_mask"], batch["values"].astype(jnp.float32), 0.0)

        def loss_gradient(y_hat):
            label = batch["label"].astype(jnp.float32)
            return (-label * jax.nn.sigmoid(-label * y_hat),
                    jax.nn.softplus(-label * y_hat))

        y_hat, loss, dw, dv = _example_major_gradients(
            x, pulled[..., 0], pulled[..., 1:], loss_gradient, cfg.l2)
        deltas = jnp.concatenate(
            [-cfg.learning_rate * dw[..., None], -cfg.learning_rate * dv], axis=-1)
        mask = batch["feat_mask"] & batch["mask"][:, None]
        out = {"prediction": y_hat, "loss": loss * batch["mask"]}
        return state, PushRequest(batch["ids"], deltas, mask), out


class ExampleMajorDiFacto(df.DiFacto):
    def for_workers(self, workers):
        return self

    def step(self, state, batch, pulled):
        dim, v_at = self.config.dim, df.STATE_LANES
        mask = batch["feat_mask"] & batch["mask"][:, None]
        x = jnp.where(batch["feat_mask"], batch["values"].astype(jnp.float32), 0.0)
        w = pulled[..., df.W]
        v_live = df.embedding_live(
            w, pulled[..., df.C], self.V_threshold) & batch["feat_mask"]
        v = jnp.where(v_live[..., None], pulled[..., v_at:v_at + dim], 0.0)

        def loss_gradient(y_hat):
            sign = jnp.where(batch["label"] > 0, 1.0, -1.0).astype(y_hat.dtype)
            return (-sign / (1.0 + jnp.exp(sign * y_hat)),
                    jax.nn.softplus(-sign * y_hat))

        y_hat, loss, gw, gv = _example_major_gradients(x, w, v, loss_gradient, 0.0)
        gv = jnp.where(v_live[..., None], gv, 0.0)
        lead = gw.shape
        deltas = jnp.concatenate(
            [gw[..., None], jnp.zeros(lead + (v_at - 1,), gw.dtype), gv], axis=-1)
        out = {
            "prediction": jax.nn.sigmoid(y_hat), "loss": loss * batch["mask"],
            "fm_live_keys": jnp.sum(mask, dtype=jnp.int32),
            "fm_v_live_keys": jnp.sum(v_live & mask, dtype=jnp.int32),
        }
        return state, PushRequest(batch["ids"], deltas, mask), out


def _batches(seed, ids_kind, masked, n=3):
    rng = np.random.default_rng(seed)
    for _ in range(n):
        if ids_kind == "in_field":  # a key space a field, as the cells have it
            ids = rng.integers(0, PER_FIELD, (B, K)) + np.arange(K) * PER_FIELD
            ids[:, 0] = 0  # and a field of ONE row, as their dense fields are
        else:  # every field draws from all the rows
            ids = rng.integers(0, ROWS, (B, K))
        feat = rng.random((B, K)) < 0.8 if masked else np.ones((B, K), bool)
        live = rng.random(B) < 0.8 if masked else np.ones(B, bool)
        yield {
            "ids": np.where(feat, ids, -1).astype(np.int32),  # -1: a dead lane
            "values": rng.uniform(0.1, 1, (B, K)).astype(np.float32),
            "feat_mask": feat, "label": rng.choice([-1.0, 1.0], B).astype(
                np.float32), "mask": live,
        }


def _models(model, mesh):
    rng = np.random.default_rng(5)
    if model == "fm":
        cfg = fmm.FMConfig(num_features=ROWS, dim=DIM, learning_rate=0.05)
        rows = rng.normal(0, 0.1, (ROWS, 1 + DIM)).astype(np.float32)
        store = fmm.make_store(cfg, mesh=mesh)
        return (fmm.FactorizationMachine(cfg), ExampleMajorFM(cfg),
                type(store).from_spec_values(store.spec, jnp.asarray(rows)))
    cfg = df.DiFactoConfig(ROWS, DIM)
    rows = np.zeros((ROWS, cfg.row_lanes), np.float32)
    rows[:, df.Z], rows[:, df.S] = rng.normal(0, 3, ROWS), rng.uniform(0, 4, ROWS)
    rows[:, df.W] = np.asarray(df.DiFactoUpdater().weights(
        jnp.asarray(rows[:, df.Z]), jnp.asarray(rows[:, df.S])))
    rows[:, df.C] = rng.integers(0, 21, ROWS)  # both sides of the threshold
    rows[:, 4:4 + DIM] = rng.normal(0, 0.1, (ROWS, DIM))
    rows[:, 4 + DIM:] = rng.uniform(0, 2, (ROWS, DIM))
    store = df.make_store(
        cfg, init_fn=lambda ids: jnp.asarray(rows)[ids], mesh=mesh)
    return df.DiFacto(cfg), ExampleMajorDiFacto(cfg), store


@pytest.mark.parametrize("placed", ["one_place", "dp_2"])
@pytest.mark.parametrize("ids_kind", ["in_field", "colliding"])
@pytest.mark.parametrize("masked", [False, True], ids=["all_live", "masked"])
@pytest.mark.parametrize("model", ["fm", "difacto"])
def test_three_steps_leave_the_example_major_steps_table(
        model, masked, ids_kind, placed, mesh_devices):
    """Where a row belongs to one field a row's deltas reach the push in the
    order example-major lanes give them: the table is the reference's BIT
    FOR BIT.  Where fields share rows the order differs, the sums' last bits
    with it.  Over two workers the logic keeps example-major lanes (a
    worker's lanes are its examples'): the reference's program."""
    mesh = None if placed == "one_place" else make_mesh(
        2, 2, devices=mesh_devices[:4])
    logic, reference, store = _models(model, mesh)
    traced = logic.for_workers(1 if mesh is None else 2)
    assert traced.pulls_turned == traced.field_major == (mesh is None)
    assert not reference.for_workers(1).pulls_turned
    tables = []
    for side in (logic, reference):
        step = jax.jit(make_train_step(side, store.spec))
        table = store.table
        for batch in _batches(11, ids_kind, masked):
            table, _, out = step(table, (), batch)
            assert out["prediction"].shape == out["loss"].shape == (B,)
        tables.append(np.asarray(type(store)(store.spec, table).values()))
    got, want = tables
    assert np.isfinite(want).all() and not np.array_equal(
        want, np.asarray(store.values()))
    if ids_kind == "in_field" or mesh is not None:
        np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
    else:
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)


@pytest.mark.parametrize("model", ["fm", "difacto"])
def test_the_logic_alone_is_example_major_and_its_copy_turned(model):
    logic, reference, store = _models(model, None)
    batch = next(_batches(3, "colliding", True))
    ids = jnp.clip(batch["ids"], 0, ROWS - 1)
    width = store.spec.worker_width or store.spec.row_width
    rows = jnp.asarray(store.values())[:, :width]
    _, want, ref_out = reference.step((), batch, rows[ids])
    # the logic as any caller of `step` holds it: the base contract
    assert not logic.pulls_turned and logic.for_workers(2) is logic
    assert logic.keys(batch).shape == (B, K)
    _, req, out = logic.step((), batch, rows[ids])
    assert req.ids.shape == req.mask.shape == (B, K)
    np.testing.assert_array_equal(req.ids, want.ids)
    np.testing.assert_array_equal(req.mask, want.mask)
    np.testing.assert_allclose(req.deltas, want.deltas, rtol=1e-5, atol=1e-7)
    # the copy `make_train_step` traces in one place: the same keys, rows
    # and deltas turned, the same numbers
    turned = logic.for_workers(1)
    assert turned is not logic and turned.pulls_turned
    assert turned.for_workers(1) is turned and not logic.pulls_turned
    assert turned.keys(batch).shape == (B, K)
    _, req, out = turned.step((), batch, rows[ids.T])
    assert req.ids.shape == req.mask.shape == (K, B)
    assert req.deltas.shape == (K, B, width)
    np.testing.assert_array_equal(req.ids, np.asarray(want.ids).T)
    np.testing.assert_array_equal(req.mask, np.asarray(want.mask).T)
    np.testing.assert_allclose(
        np.swapaxes(req.deltas, 0, 1), want.deltas, rtol=1e-5, atol=1e-7)
    for name in ref_out:
        np.testing.assert_allclose(out[name], ref_out[name], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("batch", [B, K], ids=["B_64", "B_equals_K"])
def test_the_cluster_driver_runs_the_logic_example_major(batch):
    """``ClusterDriver`` pulls ``keys.shape + row`` itself and calls
    ``logic.step`` with no ``make_train_step`` in between: the logic it is
    given keeps that contract (nothing turned), and a BSP run leaves the
    single-process step's table; with ``B`` = ``K`` too, where a turned
    block would broadcast against the batch's without an error."""
    from flink_parameter_server_tpu.cluster import ClusterConfig, ClusterDriver

    cfg = fmm.FMConfig(num_features=ROWS, dim=DIM, learning_rate=0.05)
    rows = np.random.default_rng(5).normal(
        0, 0.1, (ROWS, 1 + DIM)).astype(np.float32)

    def init(ids):
        return jnp.asarray(rows)[ids]

    batches = [
        {k: v[:batch] for k, v in b.items()}
        for b in _batches(11, "colliding", True)]
    for b in batches:  # (the cluster routes every id: a masked lane names row 0)
        b["ids"] = np.maximum(b["ids"], 0)
    logic = fmm.FactorizationMachine(cfg)
    store = fmm.make_store(cfg)
    store = type(store).from_spec_values(store.spec, jnp.asarray(rows))
    step = jax.jit(make_train_step(logic, store.spec))
    table = store.table
    for b in batches:
        table, _, _ = step(table, (), b)
    want = np.asarray(type(store)(store.spec, table).values())
    driver = ClusterDriver(
        logic, capacity=ROWS, value_shape=(1 + DIM,), init_fn=init,
        config=ClusterConfig(num_shards=2, num_workers=1, staleness_bound=0),
        registry=False,
    )
    with driver:
        result = driver.run(batches)
    assert result.rounds == len(batches)
    assert not np.array_equal(want, rows)
    np.testing.assert_allclose(result.values, want, rtol=2e-5, atol=1e-6)
