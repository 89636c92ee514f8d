"""``models/dlrm.py``: the step against the plain reference of the benchmark
(``chipbench/references/dlrm.py``) and against autodiff, on one device and at
``dp`` = 2; the copy a step in one place traces (``FieldLanes``: the batch
the minor axis) and the triangle put back by a 0/1 product against the
example-major step with its scattered triangle that PR 65's parent ran, kept
here, bit for bit; the dict state through the driver, its gauges and a checkpoint; and
the store a 64-lane add row resolves to (two rows to a physical row) against
a dense one, bit for bit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import run
from chipbench.references import dlrm as reference
from flink_parameter_server_tpu import (
    DriverConfig,
    ShardedParamStore,
    StreamingDriver,
)
from flink_parameter_server_tpu.core.transform import (
    make_train_step,
    transform_batched,
)
from flink_parameter_server_tpu.models import dlrm
from flink_parameter_server_tpu.parallel.mesh import make_mesh

# a 3-row field (every batch repeats its rows), two fields no batch repeats
# much, and a one-row field; widths a fraction of the published ones
CARDS = (40, 3, 1, 500)
CONFIG = dlrm.DLRMConfig(
    CARDS, dense_features=5, dim=8, bottom_mlp=(16, 8), top_mlp=(24, 12, 1),
    learning_rate=0.1,
)
# what the reference reads of a configuration file
CFG = {
    "learning_rate": 0.1, "dim": 8, "bottom_mlp": [16, 8],
    "top_mlp": [24, 12, 1], "dense_fields": 5, "field_cardinalities": CARDS,
    "reference": {"delta_rtol": 4e-5, "relu_ulps": 16},
}
CHECK = {"delta_rtol": 4e-5, "delta_atol": 1e-11, "row_ulps": 8}
FIRSTS = np.concatenate([[0], np.cumsum(CARDS)[:-1]])


def _batches(seed, n, batch=32, masked=(5,)):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        mask = np.ones(batch, bool)
        mask[list(masked)] = False
        out.append({
            "dense": rng.random((batch, 5), np.float32),
            "ids": (rng.integers(0, CARDS, (batch, 4)) + FIRSTS).astype(np.int32),
            "label": rng.integers(0, 2, batch).astype(np.float32),
            "mask": mask,
        })
    return out


def _rows(store, state, ids):
    """The compared rows as the benchmark's family hands them over: the
    touched embedding rows, then the MLPs flat, ``dim`` lanes to a row."""
    from chipbench.families import dlrm as family

    return family.rows(store, state, ids)


def _against_the_reference(logic, store, state, batches, after):
    """``run._check_rows`` of what ``after(store, state, batches)`` leaves."""
    ids = reference.touched(batches)
    before = _rows(store, state, ids)
    want = reference.apply(CFG, before, ids, batches)
    got = _rows(*after(store, state, batches), ids)
    return run._check_rows(CHECK, want, got, before), want, got, before


def _stepped(logic):
    def after(store, state, batches):
        step = jax.jit(make_train_step(logic, store.spec))
        table = store.table
        for b in batches:
            table, state, _ = step(table, state, b)
        return ShardedParamStore(store.spec, table), state

    return after


def test_the_configuration_counts_the_sources_sizes():
    full = dlrm.DLRMConfig((9980333, 36084, 17217, 7378, 20134, 3, 7112, 1442, 61,
                            9758201, 1333352, 313829, 10, 2208, 11156, 122, 4, 970,
                            14, 9994222, 7267859, 9946608, 415421, 12420, 101, 36))
    assert full.num_rows == 49_126_297 and full.fields == 26
    assert full.interaction_terms == 351
    assert full.layers() == {
        "bot0": (13, 512), "bot1": (512, 256), "bot2": (256, 64),
        "top0": (415, 512), "top1": (512, 512), "top2": (512, 256),
        "top3": (256, 1),
    }
    assert full.macs_per_example == 806_720 and full.dense_params == 762_177
    with pytest.raises(ValueError, match="must end at"):
        dlrm.DLRMConfig((3, 4), dim=8, bottom_mlp=(16,), top_mlp=(1,))


def test_init_state_is_the_sources_init_from_rng_and_seed():
    logic = dlrm.DLRM(dlrm.DLRMConfig((3, 4)), seed=7)
    state = logic.init_state(jax.random.PRNGKey(0))
    assert sorted(state) == sorted(
        f"{n}{i}_{p}" for n, d in (("bot", 3), ("top", 4)) for i in range(d)
        for p in "wb"
    )
    assert state["top0_w"].shape == (64 + 3, 512) and state["top3_b"].shape == (1,)
    w, b = np.asarray(state["bot1_w"]), np.asarray(state["top1_b"])
    assert abs(w.std() - np.sqrt(2 / (512 + 256))) < 2e-3 and abs(w.mean()) < 1e-3
    assert abs(b.std() - np.sqrt(1 / 512)) < 6e-3
    again = logic.init_state(jax.random.PRNGKey(0))
    other = dlrm.DLRM(logic.config, seed=8).init_state(jax.random.PRNGKey(0))
    assert np.array_equal(again["bot1_w"], w) and not np.array_equal(other["bot1_w"], w)


def _eager_init(config, seed, rng):
    """``DLRM.init_state`` as it stood until PR 67: op by op, a ``fold_in``,
    a ``split``, two ``normal``s and two multiplies a layer."""
    key = jax.random.fold_in(rng, seed)
    state = {}
    for i, (name, (n, m)) in enumerate(config.layers().items()):
        kw, kb = jax.random.split(jax.random.fold_in(key, i))
        state[f"{name}_w"] = np.sqrt(2.0 / (m + n)) * jax.random.normal(
            kw, (n, m), jnp.float32)
        state[f"{name}_b"] = np.sqrt(1.0 / m) * jax.random.normal(
            kb, (m,), jnp.float32)
    return state


# MLPerf's widths (cell 16) and the test's own; a seed over 31 bits
INIT_CONFIGS = {
    "mlperf": dlrm.DLRMConfig(
        (7, 5) * 13, dim=128, bottom_mlp=(512, 256, 128),
        top_mlp=(1024, 1024, 512, 256, 1)),
    "small": CONFIG,
}


@pytest.mark.parametrize("seed", [0, 7, np.uint32(3_000_000_067)])
@pytest.mark.parametrize("widths", list(INIT_CONFIGS))
def test_init_state_as_one_program_is_the_eager_form_bit_for_bit(widths, seed):
    """The same keys, shapes, scales and BITS as the op-by-op init: the
    jitted form keeps each leaf's scale apart from ``normal``'s own last
    multiply (an ``optimization_barrier``); folded into it, XLA's CPU
    rounds once where the eager form rounded twice, and up to 65 % of a
    leaf's elements differ by one or two ulps (measured while this was
    written).  So a job that is started again draws the weights it drew."""
    config = INIT_CONFIGS[widths]
    rng = jax.random.PRNGKey(3)
    got = dlrm.DLRM(config, seed=seed).init_state(rng)
    want = _eager_init(config, seed, rng)
    assert sorted(got) == sorted(want)
    for leaf, x in want.items():
        assert got[leaf].shape == x.shape and got[leaf].dtype == x.dtype
        assert np.asarray(got[leaf]).tobytes() == np.asarray(x).tobytes(), leaf


def test_init_state_compiles_one_program_where_it_compiled_a_score():
    """What a warm set-up pays for the init: ONE program traced, lowered and
    loaded, whatever the seed (an argument); the eager form was ~28 of them
    (a ``fold_in``, a ``split``, a ``normal`` and a multiply for every new
    shape: 35 compiles in cell 16's set-up on the ledger, PR 66)."""
    from flink_parameter_server_tpu.telemetry import compile_ledger

    compile_ledger.install()
    config = dlrm.DLRMConfig(  # widths no other test compiles an init for
        (3, 4, 5), dim=24, bottom_mlp=(40, 24), top_mlp=(56, 1))

    def compiled(fn):
        before = len(compile_ledger.events())
        jax.block_until_ready(fn())
        return [e["program"] for e in compile_ledger.events()[before:]
                if e["stage"] == "backend"]

    first = compiled(lambda: dlrm.DLRM(config, seed=1).init_state(
        jax.random.PRNGKey(0)))
    assert first == ["_init_layers"]
    assert compiled(lambda: dlrm.DLRM(config, seed=2).init_state(
        jax.random.PRNGKey(5))) == []
    eager = compiled(lambda: _eager_init(config, 1, jax.random.PRNGKey(0)))
    assert len(eager) >= 8  # (more in a process that has compiled nothing)


def test_a_drivers_second_run_under_a_mesh_meets_the_step_it_traced(
        mesh_devices):
    """A state built on the default device beside a table over ``ps`` (the
    MLPs beside cell 16's table): the loop lays it over the mesh before the
    first dispatch, where the step hands it back, so the second ``run`` of
    the driver (the benchmark's window after its checked batches) meets the
    shardings the first traced for and traces, lowers and loads nothing.
    Left on its device, the second run's first dispatch was a program of its
    own: the step once more, ~0.5 s of cell 16's ``warmup`` (PR 67)."""
    from flink_parameter_server_tpu.telemetry import compile_ledger

    compile_ledger.install()
    mesh = make_mesh(1, 4, devices=mesh_devices[:4])
    logic = dlrm.DLRM(CONFIG, seed=3)
    store = dlrm.make_store(CONFIG, seed=3, mesh=mesh)
    one = dlrm.make_store(CONFIG, seed=3)
    batches = _batches(11, 4)
    driver = StreamingDriver(logic, store, config=DriverConfig())

    def steps_built(run):
        before = len(compile_ledger.events())
        result = run()
        return result, [
            (e["stage"], e["program"])
            for e in compile_ledger.events()[before:] if e["program"] == "step"]

    first, built = steps_built(lambda: driver.run(iter(batches[:2])))
    assert [stage for stage, _ in built] == ["trace", "lower", "backend"]
    for leaf in jax.tree.leaves(first.worker_state):
        assert leaf.sharding.is_fully_replicated
        assert leaf.sharding.device_set == set(mesh.devices.flat)
    second, built = steps_built(lambda: driver.run(iter(batches[2:])))
    assert built == []
    # ... and the placement changed no number: one device, the same batches
    alone = StreamingDriver(logic, one, config=DriverConfig())
    alone.run(iter(batches[:2]))
    want = alone.run(iter(batches[2:]))
    for leaf, x in want.worker_state.items():
        np.testing.assert_allclose(
            np.asarray(second.worker_state[leaf]), np.asarray(x),
            rtol=2e-6, atol=1e-7)
    np.testing.assert_allclose(
        np.asarray(second.store.values())[:sum(CARDS)],
        np.asarray(want.store.values())[:sum(CARDS)], rtol=2e-6, atol=1e-7)


def test_make_store_packs_two_rows_and_draws_every_field_in_its_own_range():
    config = dlrm.DLRMConfig(CARDS, dim=64)
    store = dlrm.make_store(config, seed=3)
    assert (store.spec.layout, store.spec.pack, store.spec.update) == ("packed", 2, "add")
    assert store.table.shape == (272, 128) and store.spec.capacity == 544
    values = np.asarray(store.values())
    for first, card in zip(FIRSTS, CARDS):
        rows = values[first:first + card]
        assert np.abs(rows).max() <= np.sqrt(1 / card)
        assert np.abs(rows).max() > 0.8 * np.sqrt(1 / card)
    traced = jax.jit(lambda s: dlrm.make_store(config, seed=s).table)(3)
    assert np.array_equal(np.asarray(traced), np.asarray(store.table))
    assert not np.array_equal(values, np.asarray(dlrm.make_store(config, seed=4).values()))


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 5])
def test_the_step_is_the_plain_reference(seed):
    logic = dlrm.DLRM(CONFIG, seed=seed % 1000)
    store = dlrm.make_store(CONFIG, seed=seed % 1000)
    state = logic.init_state(jax.random.PRNGKey(0))
    batches = _batches(seed, 2)
    # the 3-row field repeats its rows, the one-row field holds every example
    assert len(np.unique(batches[0]["ids"][:, 1])) <= 3
    (failures, worst), (want, moved), got, before = _against_the_reference(
        logic, store, state, batches, _stepped(logic)
    )
    assert failures == [] and 0 < worst["share"] < 0.5, worst
    # the masked example moved nothing: a row only it names is bit for bit
    ids = reference.touched(batches)["embedding"]
    only_masked = np.setdiff1d(
        batches[0]["ids"][5], np.concatenate(
            [np.delete(b["ids"], 5, axis=0).reshape(-1) for b in batches]
            + [batches[1]["ids"][5]]
        ),
    )
    assert only_masked.size  # the 500-row field's, at these seeds
    at = np.searchsorted(ids, only_masked)
    assert np.array_equal(got["parameters"][at], before["parameters"][at])
    assert not moved["parameters"][at].any()
    # ... and the MLPs lie behind the embedding rows, whole
    table, layers = reference.unpack(CFG, got["parameters"], ids.size)
    assert table.shape == (ids.size, 8) and layers["top0"].shape == (8 + 10 + 1, 24)


@pytest.mark.parametrize("form", ["example_major", "field_major"])
def test_the_gradients_are_autodiffs(form):
    logic = dlrm.DLRM(CONFIG, seed=1)
    store = dlrm.make_store(CONFIG, seed=1)
    state = logic.init_state(jax.random.PRNGKey(0))
    (batch,) = _batches(4, 1)
    pulled = store.pull(jnp.asarray(batch["ids"]))

    def loss(state, pulled):
        a = batch["dense"]
        for i in range(2):
            a = jax.nn.relu(a @ state[f"bot{i}_w"] + state[f"bot{i}_b"])
        t = jnp.concatenate([a[:, None], pulled], axis=1)
        li, lj = np.tril_indices(5, -1)
        r = jnp.concatenate([a, jnp.einsum("bid,bjd->bij", t, t)[:, li, lj]], axis=1)
        for i in range(3):
            r = r @ state[f"top{i}_w"] + state[f"top{i}_b"]
            r = jax.nn.relu(r) if i < 2 else r
        bce = jax.nn.softplus(r[:, 0]) - batch["label"] * r[:, 0]
        return jnp.sum(bce * batch["mask"]) / batch["mask"].sum()

    g_state, g_rows = jax.grad(loss, (0, 1))(state, pulled)
    if form == "field_major":
        # the copy a step in one place traces: rows, ids, deltas and mask
        # with the fields in front of the batch
        logic = logic.for_workers(1)
        assert logic.pulls_turned
        new, req, out = logic.step(state, batch, jnp.swapaxes(pulled, 0, 1))
        assert req.deltas.shape == (4, 32, 8)
        req = type(req)(req.ids.T, jnp.swapaxes(req.deltas, 0, 1), req.mask.T)
    else:
        assert not logic.pulls_turned and logic.for_workers(2) is logic
        new, req, out = logic.step(state, batch, pulled)
    for k in state:
        np.testing.assert_allclose(
            (state[k] - new[k]) / 0.1, g_state[k], rtol=1e-4, atol=2e-6
        )
    np.testing.assert_allclose(-req.deltas / 0.1, g_rows, rtol=1e-4, atol=1e-8)
    assert np.array_equal(req.ids, batch["ids"]) and req.mask.shape == (32, 4)
    assert not np.asarray(req.mask)[5].any() and np.asarray(req.mask)[4].all()
    assert float(out["loss"].sum() / 31) == pytest.approx(float(loss(state, pulled)), rel=1e-5)
    assert int(out["dlrm_dense_params"]) == CONFIG.dense_params
    assert float(out["dlrm_dense_flops_per_step"]) == 6.0 * CONFIG.macs_per_example * 32


class _ParentsDLRM(dlrm.DLRM):
    """The logic as PR 65's parent had it: example-major whoever traces it,
    ``T`` ``(B, F + 1, dim)``, the triangle of ``dZ`` put back by a scatter
    (``at[...].set``) and ``dZ + dZ^t`` an add.  Kept to hold the logic to
    its bits."""

    def for_workers(self, workers):
        return self

    def step(self, state, batch, pulled):
        from flink_parameter_server_tpu.core.batched import PushRequest

        cfg, p = self.config, dlrm._PRECISION
        lr, live = cfg.learning_rate, batch["mask"]
        x = batch["dense"].astype(jnp.float32)
        lower_i, lower_j = np.tril_indices(cfg.fields + 1, -1)
        n_bot, n_top = len(cfg.bottom_mlp), len(cfg.top_mlp)
        # (the sums over the examples in the logic's blocks: PR 68)
        blocks = 1 if x.shape[0] % self.example_blocks else self.example_blocks
        bot = dlrm._mlp_forward(state, "bot", x, n_bot, True)
        t = jnp.concatenate([bot[-1][:, None, :], pulled], axis=1)
        z = jnp.einsum("bid,bjd->bij", t, t, precision=p)
        r = jnp.concatenate([bot[-1], z[:, lower_i, lower_j]], axis=1)
        top = dlrm._mlp_forward(state, "top", r, n_top, False)
        logit = top[-1][:, 0]
        sign = jnp.where(batch["label"] > 0, 1.0, -1.0)
        examples = jnp.maximum(jnp.sum(live, dtype=jnp.float32), 1.0)
        d_logit = jnp.where(
            live, -sign / (1.0 + jnp.exp(sign * logit)), 0.0) / examples
        grads, d_r = dlrm._mlp_backward(
            state, "top", top, d_logit[:, None], False, blocks)
        d_z = jnp.zeros_like(z).at[:, lower_i, lower_j].set(d_r[:, cfg.dim:])
        d_t = jnp.einsum(
            "bij,bjd->bid", d_z + d_z.swapaxes(1, 2), t, precision=p)
        bot_grads, _ = dlrm._mlp_backward(
            state, "bot", bot, d_r[:, :cfg.dim] + d_t[:, 0], True, blocks)
        grads.update(bot_grads)
        state = {k: v - lr * grads[k] for k, v in state.items()}
        out = {"prediction": jax.nn.sigmoid(logit),
               "loss": jax.nn.softplus(-sign * logit) * live}
        mask = jnp.broadcast_to(live[:, None], batch["ids"].shape)
        return state, PushRequest(batch["ids"], -lr * d_t[:, 1:], mask), out


def _bits(x):
    x = np.asarray(x)
    return x.view(np.uint32) if x.dtype == np.float32 else x


def _same_bits(got, want):
    """Every leaf of two trees of arrays, bit for bit."""
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(_bits(g), _bits(w))


@pytest.mark.parametrize("dim", [8, 64])
def test_the_step_in_one_place_is_the_parents_example_major_step_bit_for_bit(dim):
    """What ``make_train_step`` traces in one place is the copy that
    ``pulls_turned`` and keeps the batch the minor axis; three steps of it
    leave the table, the MLPs and the outputs that the parent's example-major
    step leaves, masked examples included (every field owns its rows, so a
    row's deltas are summed in the order of the examples either way)."""
    config = dlrm.DLRMConfig(CARDS, dense_features=5, dim=dim,
                             bottom_mlp=(16, dim), top_mlp=(24, 12, 1))
    logic, parents = dlrm.DLRM(config, seed=1), _ParentsDLRM(config, seed=1)
    traced = logic.for_workers(1)
    assert traced is not logic and traced.pulls_turned and traced.field_major
    assert traced.for_workers(1) is traced and not logic.pulls_turned
    store = dlrm.make_store(config, seed=1)
    state = logic.init_state(jax.random.PRNGKey(0))
    got = want = (store.table, state)
    step = jax.jit(make_train_step(logic, store.spec))
    parents_step = jax.jit(make_train_step(parents, store.spec))
    for batch in _batches(12, 3, masked=(5, 9)):
        *got, out = step(*got, batch)
        *want, parents_out = parents_step(*want, batch)
        _same_bits(got, want)
        _same_bits([out[k] for k in ("prediction", "loss")],
                   [parents_out[k] for k in ("prediction", "loss")])
    assert not np.array_equal(np.asarray(got[0]), np.asarray(store.table))


def test_example_major_stays_the_logics_own_form_for_every_other_caller(devices):
    """A caller that hands ``step`` example-major ``pulled`` (rows it pulled
    itself: ``cluster/driver.ClusterDriver``) gets the parent's request and
    state, ``(B, fields[, dim])``, bit for bit; and so does the step over
    ``dp`` = 2 workers, which keeps the logic as it is."""
    logic, parents = dlrm.DLRM(CONFIG, seed=2), _ParentsDLRM(CONFIG, seed=2)
    store = dlrm.make_store(CONFIG, seed=2)
    state = logic.init_state(jax.random.PRNGKey(0))
    batch = _batches(13, 1, masked=(5, 9))[0]
    pulled = store.pull(jnp.asarray(batch["ids"]))
    assert pulled.shape == (32, 4, 8) and not logic.pulls_turned
    got, want = logic.step(state, batch, pulled), parents.step(state, batch, pulled)
    assert got[1].ids.shape == (32, 4) and got[1].deltas.shape == (32, 4, 8)
    _same_bits((got[0], vars(got[1])), (want[0], vars(want[1])))
    mesh = make_mesh(2, 1, devices=devices[:2])
    assert logic.for_workers(2) is logic
    store = dlrm.make_store(CONFIG, seed=2, mesh=mesh)
    got = jax.jit(make_train_step(logic, store.spec))(store.table, state, batch)
    want = jax.jit(make_train_step(parents, store.spec))(store.table, state, batch)
    _same_bits(got[:2], want[:2])


@pytest.mark.parametrize("vectors", [2, 5, 27])
def test_the_triangle_put_back_by_a_product_is_the_scattered_triangle(vectors):
    """``pair_tables``: the pairs below the diagonal row by row (the source's
    ``li``, ``lj``) and ``both``, the 0/1 matrix by which ONE product of the
    pairs' gradients is ``dZ + dZ^t`` of the ``dZ`` that ``at[...].set``
    scatters them into, bit for bit; forward, the gather of the pairs is the
    one whose transpose that scatter is."""
    lower_i, lower_j, both = dlrm.pair_tables(vectors)
    pairs = vectors * (vectors - 1) // 2
    assert np.array_equal((lower_i, lower_j), np.tril_indices(vectors, -1))
    square = both.reshape(pairs, vectors, vectors)
    assert both.dtype == np.float32 and set(np.unique(both)) <= {0.0, 1.0}
    assert np.array_equal(square, square.swapaxes(1, 2))
    assert (square.sum(axis=(1, 2)) == 2).all()  # (i, j) and (j, i), no other
    assert np.array_equal(square[np.arange(pairs), lower_i, lower_j], np.ones(pairs))
    assert not square[:, np.arange(vectors), np.arange(vectors)].any()
    rng = np.random.default_rng(vectors)
    z = jnp.asarray(rng.normal(size=(16, vectors, vectors)).astype(np.float32))
    d_pairs = rng.normal(size=(16, pairs)).astype(np.float32)
    d_pairs[3, 0], d_pairs[4, 0], d_pairs[5, 0] = 0.0, 3e38, 1e-30
    d_pairs = jnp.asarray(d_pairs)
    d_z = jnp.zeros_like(z).at[:, lower_i, lower_j].set(d_pairs)
    # (the values: a diagonal entry is a sum of products by zero, -0.0 where
    # every pair's gradient is negative, which adds nothing to what it meets)
    put_back = np.asarray(dlrm._dot(d_pairs, both).reshape(z.shape))
    np.testing.assert_array_equal(put_back, np.asarray(d_z + d_z.swapaxes(1, 2)))
    _same_bits(put_back[:, lower_i, lower_j], d_pairs)
    _same_bits(put_back[:, lower_j, lower_i], d_pairs)
    _same_bits(z[:, lower_i, lower_j], np.asarray(z)[:, lower_i, lower_j])
    grad = jax.grad(lambda z: jnp.sum(z[:, lower_i, lower_j] * d_pairs))(z)
    _same_bits(grad, d_z)


def test_at_dp_2_the_step_is_the_plain_reference_and_the_mlps_stay_replicated(devices):
    mesh = make_mesh(2, 1, devices=devices[:2])
    logic = dlrm.DLRM(CONFIG, seed=2)
    store = dlrm.make_store(CONFIG, seed=2, mesh=mesh)
    state = logic.init_state(jax.random.PRNGKey(0))
    batches = _batches(6, 2)

    def after(store, state, batches):
        result = transform_batched(
            iter(batches), logic, store, initial_state=state, mesh=mesh,
            dump_model=False, collect_outputs=False,
        )
        return result.store, result.worker_state

    (failures, worst), _, _, _ = _against_the_reference(
        logic, store, state, batches, after
    )
    assert failures == [] and 0 < worst["share"] < 0.5, worst
    text = jax.jit(make_train_step(logic, store.spec)).lower(
        store.table, state, batches[0]
    ).compile().as_text()
    assert "all-reduce" in text  # the partitioner's, nothing names it


def test_the_dict_state_goes_through_the_driver_its_gauges_and_a_checkpoint(tmp_path):
    from flink_parameter_server_tpu.telemetry.registry import MetricsRegistry

    config = dlrm.DLRMConfig(CARDS, dense_features=5, dim=64, bottom_mlp=(16, 64),
                             top_mlp=(24, 1))
    logic = dlrm.DLRM(config, seed=3)
    batches = _batches(8, 3)

    def driver(registry=None):
        return StreamingDriver(
            logic, dlrm.make_store(config, seed=3), registry=registry,
            config=DriverConfig(checkpoint_dir=str(tmp_path / "ckpt"), dump_model=False),
        )

    registry = MetricsRegistry()
    first = driver(registry)
    seen = []
    first.add_group_hook(lambda step, n, table, state, outs: seen.append(sorted(state)))
    first.run(iter(batches[:2]))  # saves on close
    gauges = registry.snapshot()
    assert gauges["dlrm_dense_params"][0]["value"] == config.dense_params
    assert gauges["dlrm_dense_flops_per_step"][0]["value"] == pytest.approx(
        6.0 * config.macs_per_example * 32
    )
    assert gauges["store_layout_packed"][0]["value"] == 1
    assert len(seen) == 2 and seen[0] == sorted(first._state)
    state = {k: np.asarray(v) for k, v in first._state.items()}
    values = np.asarray(first.store.values())
    second = driver()
    assert second.resume() and second.step_idx == 2
    assert second.store.spec.pack == 2
    assert np.array_equal(np.asarray(second.store.values()), values)
    assert sorted(second._state) == sorted(state)
    for k in state:
        assert np.array_equal(np.asarray(second._state[k]), state[k]), k
    # the restored job and the first go on to the same third batch, bit for bit
    got = second.run(iter(batches))  # skips the two it has
    want = first.run(iter(batches[2:]), fast_forward=False)
    assert second.step_idx == first.step_idx == 3
    for k in state:
        assert np.array_equal(
            np.asarray(got.worker_state[k]), np.asarray(want.worker_state[k])
        ), k
        assert not np.array_equal(np.asarray(got.worker_state[k]), state[k]), k
    assert np.array_equal(
        np.asarray(got.store.values()), np.asarray(want.store.values())
    )


@pytest.mark.parametrize("dim, pack", [(64, 2), (128, 1)])
def test_the_step_through_the_one_register_tile_kernel_is_xlas_and_the_driver_says_so(
        dim, pack, monkeypatch, steer_arms):
    """Cell 10's arm since PR 49 (``core/store.arms``' ``push``
    ``"tile_add"``: a TPU, a table eight batches long or more), steered here and
    interpreted: the driver's run leaves the table and the MLPs XLA's arm
    leaves, bit for bit, and sets ``store_push_kernel_lanes`` /
    ``store_push_tile_rows`` from the last dispatch (what
    ``store.push_tile_rows_share`` reads); XLA's arm sets neither."""
    from flink_parameter_server_tpu.core import store as store_mod
    from flink_parameter_server_tpu.telemetry.registry import MetricsRegistry

    config = dlrm.DLRMConfig(CARDS, dense_features=5, dim=dim,
                             bottom_mlp=(16, dim), top_mlp=(24, 1))
    batches = _batches(11, 3, masked=(5, 9))

    def ran():
        registry = MetricsRegistry()
        store = dlrm.make_store(config, seed=3)
        assert store.spec.pack == pack and store.table.shape[1] == 128
        driver = StreamingDriver(
            dlrm.DLRM(config, seed=3), store, registry=registry,
            config=DriverConfig(dump_model=False),
        )
        result = driver.run(iter(batches))
        return result, registry.snapshot()

    want, gauges = ran()
    assert "store_push_tile_rows" not in gauges
    assert "store_push_kernel_lanes" not in gauges
    assert "store_tile_lanes_per_row" not in gauges
    steer_arms(push="tile_add")
    got, gauges = ran()
    assert np.array_equal(
        np.asarray(got.store.table).view(np.uint32),
        np.asarray(want.store.table).view(np.uint32))
    for k, v in want.worker_state.items():
        assert np.array_equal(np.asarray(got.worker_state[k]), np.asarray(v)), k
    # a masked example's lanes keep their ids and add zeros: kept, all of them
    ids = batches[-1]["ids"].reshape(-1)
    assert gauges["store_push_kernel_lanes"][0]["value"] == ids.size
    assert gauges["store_push_tile_rows"][0]["value"] == len(
        np.unique(ids // pack // 8))
    # how many lanes share a tile row's one load and one store (PR 74)
    assert gauges["store_tile_lanes_per_row"][0]["value"] == ids.size / len(
        np.unique(ids // pack // 8))


def test_the_scopes_are_whole_path_components_forward_and_backward():
    from chipbench import program_trace

    logic = dlrm.DLRM(CONFIG)
    store = dlrm.make_store(CONFIG)
    state = logic.init_state(jax.random.PRNGKey(0))
    text = jax.jit(make_train_step(logic, store.spec)).lower(
        store.table, state, _batches(0, 1)[0]
    ).as_text(debug_info=True)
    for scope in ("dense_bottom", "dense_interact", "dense_top", "dense_sgd",
                  "delta_build"):
        assert f"jit(step)/ps.compute/ps.{scope}/" in text, scope
    # nothing of the dense net lies under ps.compute alone or under a
    # transform's name that the benchmark's reduction would pass by
    assert "transpose(jvp(" not in text
    names = set(__import__("re").findall(r'"(jit\(step\)/ps\.compute/[^"]*)"', text))
    dots = [n for n in names if n.endswith("dot_general")]
    # (a layer's three products share one name, whichever block of the
    # examples an `a^t d` is taken over; the interaction has its two batched
    # products and the 0/1 product that puts the triangle back)
    assert len(dots) == 5
    for n in dots:
        assert program_trace.SCOPE.findall(n)[-1] in (
            "ps.dense_bottom", "ps.dense_interact", "ps.dense_top"), n


@pytest.mark.parametrize("capacity", [1000, 1001, 7])
def test_a_store_of_two_rows_to_a_physical_row_is_a_dense_one_bit_for_bit(capacity):
    rng = np.random.default_rng(capacity)
    init = lambda ids: jnp.sin(ids[:, None] * 64.0 + jnp.arange(64.0))  # noqa: E731
    packed = ShardedParamStore.create(capacity, (64,), init_fn=init, layout="auto")
    dense = ShardedParamStore.create(capacity, (64,), init_fn=init, layout="dense")
    assert (packed.spec.layout, packed.spec.pack) == ("packed", 2)
    assert packed.table.shape == (-(-capacity // 16) * 8, 128)
    assert dense.table.shape[1] == 64
    assert np.array_equal(np.asarray(packed.values()), np.asarray(dense.values()))
    assert packed.values().shape == (capacity, 64)
    ids = rng.integers(0, capacity, (50, 3)).astype(np.int32)
    ids[0, 0], ids[1, 1] = capacity - 1, capacity - 1  # the odd table's last row
    ids[2] = -1  # a dead lane
    deltas = rng.normal(size=(50, 3, 64)).astype(np.float32)
    deltas[3, 0] = [np.nan, np.inf, -0.0] + [1.0] * 61
    mask = rng.random((50, 3)) < 0.8
    assert np.array_equal(
        np.asarray(packed.pull(jnp.asarray(ids))), np.asarray(dense.pull(jnp.asarray(ids))),
        equal_nan=True,
    )
    for _ in range(2):
        packed = packed.push(jnp.asarray(ids), jnp.asarray(deltas), jnp.asarray(mask))
        dense = dense.push(jnp.asarray(ids), jnp.asarray(deltas), jnp.asarray(mask))
    got, want = np.asarray(packed.values()), np.asarray(dense.values())
    assert np.array_equal(got, want, equal_nan=True)
    assert np.array_equal(np.signbit(got), np.signbit(want))
    assert np.array_equal(
        np.asarray(packed.pull(jnp.asarray(ids))), np.asarray(dense.pull(jnp.asarray(ids))),
        equal_nan=True,
    )
    # a reload packs the same rows (the rows past the capacity are zeros then)
    again = ShardedParamStore.from_spec_values(packed.spec, packed.values())
    assert again.table.shape == packed.table.shape
    assert np.array_equal(np.asarray(again.values()), got, equal_nan=True)


# -- the compute split over the servers' own axis (PR 68) ---------------------
# Under `make_mesh(1, ps)` a logic that declares `example_blocks` has its
# minibatch's compute split over `ps`; every other logic, and every logic
# without such a mesh, computes the whole minibatch in every place.


def _split_config(dim):
    return dlrm.DLRMConfig(
        CARDS, dense_features=5, dim=dim, bottom_mlp=(16, dim),
        top_mlp=(24, 12, 1), learning_rate=0.1)


def _one_step(logic, store, state, batch):
    table, state, out = jax.jit(make_train_step(logic, store.spec))(
        store.table, state, batch)
    return ShardedParamStore(store.spec, table), state, out


@pytest.mark.parametrize("masked", [(5,), tuple(range(8, 16)) + (3, 30)],
                         ids=["one_dead", "a_quarter_dead"])
@pytest.mark.parametrize("dim, push, ps", [
    (8, "xla", 4), (128, "xla", 4), (128, "tiles", 4), (128, "tiles", 2)])
def test_at_ps_4_the_compute_is_split_and_the_step_is_the_one_place_steps(
        dim, push, ps, masked, devices, steer_arms):
    """``make_mesh(1, 4)``: every chip computes the dense net on ITS quarter
    of the examples (``ps`` = 2: on its half, two blocks a chip).  TWO steps
    leave the one-place step's table AND MLPs bit for bit: the per-example
    arithmetic is untouched, the push takes the gathered deltas in the
    batch's order (XLA's scatter-add at two widths, and cell 16's own push,
    the tile kernel on the shards that own the rows, interpreted), and every
    dense gradient is the sum of four blocks' own sums added in the batch's
    order, wherever the blocks lie (``core/batched.sums_by_blocks``; with
    ONE sum of a chip's share and an all-reduce the second step's rows
    differed in their last bits); the MLPs are equal on the chips bit for
    bit; and the loss is normalised by the WHOLE minibatch's live count
    whichever quarter the dead examples lie in (examples 8-15 are one chip's
    quarter: a local mean would divide that chip's gradients by 1 and the
    others' by 7, 8 and 7)."""
    if push == "tiles":
        steer_arms(push="tile_add")
    config = _split_config(dim)
    logic = dlrm.DLRM(config, seed=2)
    mesh = make_mesh(1, ps, devices=devices[:ps])
    state = logic.init_state(jax.random.PRNGKey(0))
    one = dlrm.make_store(config, seed=2)
    got_store, want_store = dlrm.make_store(config, seed=2, mesh=mesh), one
    got = want = state
    rows = sum(CARDS)
    for batch in _batches(6, 2, masked=masked):
        got_store, got, out = _one_step(logic, got_store, got, batch)
        want_store, want, want_out = _one_step(logic, want_store, want, batch)
        assert int(out["ps_compute_parts"]) == ps
        assert "ps_compute_parts" not in want_out
        if push == "tiles":
            assert int(out["ps_push_kernel_lanes"]) == batch["ids"].size
        _same_bits(np.asarray(got_store.values())[:rows],
                   np.asarray(want_store.values())[:rows])
        for k in ("prediction", "loss"):
            _same_bits(np.asarray(out[k]), np.asarray(want_out[k]))
        for leaf, x in got.items():
            assert x.sharding.is_fully_replicated, leaf
            chips = [np.asarray(s.data) for s in x.addressable_shards]
            assert len(chips) == ps
            for chip in chips[1:]:
                _same_bits(chip, chips[0])
            _same_bits(np.asarray(x), np.asarray(want[leaf]))
            assert not np.array_equal(np.asarray(x), np.asarray(state[leaf]))
    assert not np.array_equal(
        np.asarray(want_store.values())[:rows], np.asarray(one.values())[:rows])


@pytest.mark.parametrize("blocks", [1, 2, 4])
def test_sums_by_blocks_is_the_written_association(blocks, devices):
    """``core/batched.sums_by_blocks``: each block's sums alone, then the
    blocks' added one by one in the batch's order, whatever tree ``fn``
    answers; in one place, and traced under a mesh's description with the
    examples split over its chips (what ``make_train_step`` does), the same
    bits; one block is ``fn`` as it is."""
    from flink_parameter_server_tpu.core.batched import sums_by_blocks

    rng = np.random.default_rng(blocks)
    a = jnp.asarray(rng.normal(size=(64, 5)).astype(np.float32) * 1e3)
    d = jnp.asarray(rng.normal(size=(64, 3)).astype(np.float32))

    def fn(a, d):
        return {"w": a.T @ d, "b": (d.sum(axis=0), a.sum())}

    @jax.jit
    def written_out(a, d):
        done = None
        for a_k, d_k in zip(jnp.split(a, blocks), jnp.split(d, blocks)):
            part = fn(a_k, d_k)
            done = part if done is None else jax.tree.map(jnp.add, done, part)
        return done

    want = written_out(a, d)
    got = jax.jit(lambda a, d: sums_by_blocks(fn, blocks, a, d))(a, d)
    _same_bits(got, want)
    if blocks == 1:
        _same_bits(got, jax.jit(fn)(a, d))
        return
    mesh = make_mesh(1, 2, devices=devices[:2])
    split = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("ps"))

    def on_the_mesh(a, d):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return sums_by_blocks(fn, blocks, a, d)

    placed = jax.jit(on_the_mesh)(
        jax.device_put(a, split), jax.device_put(d, split))
    _same_bits(placed, want)
    assert all(x.sharding.is_fully_replicated for x in jax.tree.leaves(placed))


def test_at_ps_4_the_step_is_the_plain_reference(devices):
    mesh = make_mesh(1, 4, devices=devices[:4])
    logic = dlrm.DLRM(CONFIG, seed=2)
    store = dlrm.make_store(CONFIG, seed=2, mesh=mesh)
    state = logic.init_state(jax.random.PRNGKey(0))
    batches = _batches(6, 2, masked=tuple(range(8, 16)))

    def after(store, state, batches):
        result = transform_batched(
            iter(batches), logic, store, initial_state=state, mesh=mesh,
            dump_model=False, collect_outputs=False,
        )
        return result.store, result.worker_state

    (failures, worst), _, _, _ = _against_the_reference(
        logic, store, state, batches, after
    )
    assert failures == [] and 0 < worst["share"] < 0.5, worst
    text = jax.jit(make_train_step(logic, store.spec)).lower(
        store.table, state, batches[0]).as_text()
    # the three constraints `make_train_step` writes (`pulled` split, the
    # deltas split where they are built and, under the transfer's own name,
    # `mesh.push_deltas_gather`, whole again in front of the push: PR 69)
    # and the logic's own, a layer's two block sums held whole in every
    # place: nothing else names a placement
    assert text.count("sdy.sharding_constraint") + text.count(
        "custom_call @Sharding") == 3 + 2 * len(CONFIG.layers()), text


def _fm_family(model, mesh):
    from flink_parameter_server_tpu.models import difacto as df
    from flink_parameter_server_tpu.models import factorization_machine as fmm

    rng = np.random.default_rng(4)
    batch = {
        "ids": rng.integers(0, 400, (32, 4)).astype(np.int32),
        "values": rng.uniform(0.1, 1, (32, 4)).astype(np.float32),
        "feat_mask": np.ones((32, 4), bool),
        "label": rng.choice([-1.0, 1.0], 32).astype(np.float32),
        "mask": np.ones(32, bool),
    }
    if model == "fm":
        cfg = fmm.FMConfig(num_features=400, dim=8, learning_rate=0.05)
        return fmm.FactorizationMachine(cfg), fmm.make_store(cfg, mesh=mesh), batch
    cfg = df.DiFactoConfig(400, 8)
    return df.DiFacto(cfg), df.make_store(cfg, mesh=mesh), batch


@pytest.mark.parametrize("case", [
    "dlrm_ps_4", "dlrm_ps_2", "dlrm_ps_4_ragged", "dlrm_dp_2_ps_2", "dlrm_dp_2",
    "dlrm_one_place", "fm_ps_4", "difacto_ps_4", "fm_one_place",
    "difacto_one_place"])
def test_the_split_engages_by_the_mesh_and_the_logics_declaration_alone(
        case, devices):
    """The new count, among the step's outputs where it is more than 1 and
    the gauge ``store_compute_parts`` from the driver everywhere: DLRM under
    one worker group and ``ps`` servers reads ``ps`` (a batch that does not
    divide over them, and any ``dp`` > 1, keep the whole minibatch in every
    place); FM and DiFacto do not declare and read 1 under the same mesh,
    their lowered step naming no constraint; every logic without a mesh
    reads 1."""
    from flink_parameter_server_tpu.telemetry.registry import MetricsRegistry

    model, _, placed = case.partition("_")
    shape = {"ps_4": (1, 4), "ps_2": (1, 2), "ps_4_ragged": (1, 4),
             "dp_2_ps_2": (2, 2), "dp_2": (2, 1)}.get(placed)
    mesh = None
    if shape is not None:
        mesh = make_mesh(*shape, devices=devices[:shape[0] * shape[1]])
    if model == "dlrm":
        logic = dlrm.DLRM(CONFIG, seed=1)
        store = dlrm.make_store(CONFIG, seed=1, mesh=mesh)
        batch = _batches(3, 1, batch=30 if placed == "ps_4_ragged" else 32)[0]
        assert logic.example_blocks
    else:
        logic, store, batch = _fm_family(model, mesh)
        assert not getattr(logic, "example_blocks", None)
    want = {"dlrm_ps_4": 4, "dlrm_ps_2": 2}.get(case, 1)
    state = logic.init_state(jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(logic, store.spec))
    lowered = step.lower(store.table, state, batch).as_text()
    constraints = lowered.count("sdy.sharding_constraint") + lowered.count(
        "custom_call @Sharding")
    if shape is None or shape[0] == 1:
        # (`pulled`, the deltas split and gathered, and a DLRM layer's two
        # block sums)
        assert constraints == (
            3 + 2 * len(CONFIG.layers()) if want > 1 else 0)
    out = step(store.table, state, batch)[2]
    assert ("ps_compute_parts" in out) == (want > 1)
    assert int(out.get("ps_compute_parts", 1)) == want
    registry = MetricsRegistry()
    StreamingDriver(logic, store, registry=registry,
                    config=DriverConfig(dump_model=False)).run(iter([batch]))
    assert registry.snapshot()["store_compute_parts"][0]["value"] == want
