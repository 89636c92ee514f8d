"""ops/row_update: one row write per unique id of a sorted batch, against a
plain numpy scatter-add (kernel interpreted on the CPU), and the MF step
that uses it against the XLA arm in stream order."""
import json
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_parameter_server_tpu import ShardedParamStore
from flink_parameter_server_tpu.core.transform import make_train_step
from flink_parameter_server_tpu.models import matrix_factorization as mfm
from flink_parameter_server_tpu.ops import row_update

WIDTH = 128
CONFIG = os.path.join(
    os.path.dirname(__file__), "..", "chipbench", "configs",
    "mf-hugewiki-k128.json",
)


def numpy_scatter_add(state, ids, deltas, mask):
    out = np.array(state, np.float64)
    for k, (i, d) in enumerate(zip(ids, deltas)):
        if (mask is None or mask[k]) and 0 <= i < state.shape[0]:
            out[i] += d
    return out


def _case(name):
    """(rows, ids, mask, poison): the batch of one named case; ``poison``
    lanes carry NaN deltas (they are masked or out of range)."""
    rng = np.random.default_rng(11)
    rows, n = 1024, 1536
    ids = rng.integers(0, rows, n).astype(np.int32)
    mask = None
    poison = np.zeros(n, bool)
    if name == "hit_1_2_1000_times":
        ids = np.arange(n, dtype=np.int32) % 400 + 20  # every row once..
        ids[5] = ids[900] = 3  # ..row 3 twice
        ids[200:1200] = 7  # ..row 7 a thousand times, across blocks
        ids = rng.permutation(ids)
    elif name == "masked_lanes_carry_nan":
        mask = rng.random(n) > 0.3
        poison = ~mask
    elif name == "ids_out_of_range_dropped":
        ids[::5] = -1 - ids[::5]
        ids[1::7] = rows + ids[1::7]
        ids[3] = np.iinfo(np.int32).max
        ids[4] = np.iinfo(np.int32).min
        poison = (ids < 0) | (ids >= rows)
    elif name == "rows_not_a_multiple_of_8":
        rows = 1028
        ids = rng.integers(0, rows, n).astype(np.int32)
        ids[:4] = (1024, 1025, 1026, 1027)
    elif name == "all_masked":
        mask = np.zeros(n, bool)
        poison = ~mask
    elif name == "every_id_equal":
        ids[:] = 513
    elif name == "batch_not_a_multiple_of_block":
        ids = ids[:1000]
        poison = poison[:1000]
    elif name != "uniform_few_duplicates":
        raise AssertionError(name)
    return rows, ids, mask, poison


CASES = [
    "uniform_few_duplicates", "hit_1_2_1000_times", "masked_lanes_carry_nan",
    "ids_out_of_range_dropped", "rows_not_a_multiple_of_8", "all_masked",
    "every_id_equal", "batch_not_a_multiple_of_block",
]


@pytest.mark.parametrize("block", [128, 256])
@pytest.mark.parametrize("name", CASES)
def test_row_add_matches_numpy_scatter_add(name, block, monkeypatch):
    """The caller hands over the rows it has gathered, as the MF step does;
    a dropped lane's row and delta are garbage."""
    monkeypatch.setattr(row_update, "BLOCK", block)
    rows, ids, mask, poison = _case(name)
    rng = np.random.default_rng(5)
    state = rng.normal(size=(rows, WIDTH)).astype(np.float32)
    deltas = rng.normal(size=(ids.shape[0], WIDTH)).astype(np.float32)
    deltas[poison] = np.nan
    old = state[np.clip(ids, 0, rows - 1)]
    old[poison] = np.nan
    got = np.asarray(jax.jit(
        lambda s, i, o, d, m: row_update.row_add(s, i, o, d, m, interpret=True)
    )(state, ids, old, deltas, mask))
    want = numpy_scatter_add(state, ids, deltas, mask)
    assert np.isfinite(got).all()
    # float32 sums of up to 1,000 deltas against float64
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-4)
    kept = np.ones(ids.shape, bool) if mask is None else mask
    touched = np.unique(ids[kept & (ids >= 0) & (ids < rows)])
    untouched = np.setdiff1d(np.arange(rows), touched)
    assert np.array_equal(got[untouched], state[untouched])  # bit for bit


def test_eager_call_leaves_the_callers_state_alone():
    state = jnp.ones((16, WIDTH), jnp.float32)
    out = row_update.row_add(
        state, jnp.array([3, 3, 5]), jnp.ones((3, WIDTH)),
        jnp.ones((3, WIDTH)), interpret=True,
    )
    assert float(state[3, 0]) == 1.0 and float(out[3, 0]) == 3.0
    assert float(out[5, 0]) == 2.0


@pytest.mark.parametrize("bad", [np.nan, np.inf, -np.inf])
def test_a_non_finite_delta_in_a_kept_lane_stays_in_its_row(bad):
    """The XLA scatter confines a bad record to its row; so does the mask
    matmul (0 x NaN would poison the block): the row's element reads
    non-finite, its other elements and every other row are summed as ever,
    across a block boundary too (row 7 fills lanes of two blocks)."""
    rng = np.random.default_rng(3)
    rows, n = 64, 512
    ids = np.sort(rng.integers(0, rows, n)).astype(np.int32)
    ids[200:300] = 7
    ids = rng.permutation(ids)
    state = rng.normal(size=(rows, WIDTH)).astype(np.float32)
    deltas = rng.normal(size=(n, WIDTH)).astype(np.float32)
    lane = int(np.flatnonzero(ids == 7)[40])
    deltas[lane, 5] = bad
    got = np.array(row_update.row_add(
        state, ids, state[ids], deltas, interpret=True))
    assert not np.isfinite(got[7, 5])
    clean = deltas.copy()
    clean[lane, 5] = 0.0
    want = numpy_scatter_add(state, ids, clean, None)
    got[7, 5] = want[7, 5]
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-4)


@pytest.mark.parametrize("width,dtype,lanes,refused", [
    (128, jnp.float32, 256, False), (256, jnp.float32, 256, False),
    (128, jnp.float32, row_update.MAX_LANES, False),
    (64, jnp.float32, 256, True), (128, jnp.bfloat16, 256, True),
    (128, jnp.float32, row_update.MAX_LANES + 256, True),
])
def test_refusal_names_what_the_kernel_cannot_take(
        width, dtype, lanes, refused):
    why = row_update.refusal(width, dtype, lanes)
    assert (why is not None) == refused
    if refused:
        shape = jax.ShapeDtypeStruct  # traced only: nothing this size is made
        with pytest.raises(ValueError, match="sorted_row_update"):
            jax.eval_shape(
                lambda *a: row_update.sorted_row_update(*a, interpret=False),
                shape((8, width), dtype), shape((lanes,), jnp.int32),
                shape((lanes, width), dtype), shape((lanes, width), dtype),
            )


# -- the MF step --------------------------------------------------------------
def _mf_step_run(arm, cfg, batches):
    dry = cfg["dry_run"]
    logic = mfm.OnlineMatrixFactorization(
        dry["num_users"], cfg["dim"],
        updater=mfm.SGDUpdater(float(cfg["learning_rate"])),
        init_low=-cfg["init_scale"], init_high=cfg["init_scale"],
        state_scatter=arm,
    )
    store = ShardedParamStore.from_values(
        jnp.asarray(np.random.default_rng(1).normal(
            size=(dry["num_items"], cfg["dim"])) * cfg["init_scale"],
            jnp.float32)
    )
    step = jax.jit(make_train_step(logic, store.spec))
    table, state = store.table, logic.init_state(jax.random.PRNGKey(0))
    first = (np.asarray(table), np.asarray(state))
    outs = []
    for batch in batches:
        table, state, out = step(table, state, batch)
        outs.append({k: np.asarray(v) for k, v in out.items()})
    return first, (np.asarray(table), np.asarray(state)), outs


def test_mf_step_sorted_rows_matches_xla_in_stream_order():
    """The new arm against ``state_scatter="xla"`` through make_train_step at
    the configuration's dry-run sizes: table, state and both per-record
    outputs (stream order), within the configuration's own allowance."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    dry, ref = cfg["dry_run"], cfg["reference"]
    rng = np.random.default_rng(2)
    batches = []
    for _ in range(ref["batches"]):
        users = rng.integers(0, dry["num_users"], dry["batch"])
        users[:40] = users[40]  # a user hit 41 times
        mask = np.ones(dry["batch"], bool)
        mask[-17:] = False
        batches.append({
            "user": jnp.asarray(users, jnp.int32),
            "item": jnp.asarray(
                (rng.zipf(1.2, dry["batch"]) - 1) % dry["num_items"], jnp.int32),
            "rating": jnp.asarray(rng.normal(size=dry["batch"]), jnp.float32),
            "mask": jnp.asarray(mask),
        })
    first, (t_x, s_x), o_x = _mf_step_run("xla", cfg, batches)
    _, (t_r, s_r), o_r = _mf_step_run("sorted_rows", cfg, batches)
    assert np.abs(s_x - first[1]).max() > 0  # the batches did change rows
    for got, want, was in ((t_r, t_x, first[0]), (s_r, s_x, first[1])):
        # a bound on the reference's summed |delta| an element: its net
        # change is no larger, so this allowance is no looser than the
        # configuration's
        allowed = (
            ref["delta_rtol"] * np.abs(want - was) + ref["delta_atol"]
            + ref["row_ulps"] * 2.0 ** -23 * np.abs(want)
        )
        assert (np.abs(got - want) <= allowed).all()
    for a, b in zip(o_r, o_x):
        np.testing.assert_allclose(a["prediction"], b["prediction"],
                                   rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(a["error"], b["error"], rtol=1e-5, atol=1e-6)
        assert (a["error"][-17:] == 0).all()


def _arm(monkeypatch, backend, dim, dtype=jnp.float32, mesh=None, lanes=256,
         **kw):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    logic = mfm.OnlineMatrixFactorization(64, dim, dtype=dtype, mesh=mesh, **kw)
    return logic, logic.state_update_arm(
        jax.ShapeDtypeStruct((64, dim), dtype), lanes)


@pytest.mark.parametrize("backend,dim,dtype,pinned,want", [
    ("tpu", 128, jnp.float32, None, "sorted_rows"),
    ("tpu", 256, jnp.float32, None, "sorted_rows"),
    ("cpu", 128, jnp.float32, None, "xla"),
    ("tpu", 128, jnp.float32, "xla", "xla"),
    ("cpu", 64, jnp.float32, "sorted_rows", "sorted_rows"),
])
def test_state_update_arm_is_read_from_what_the_step_sees(
        monkeypatch, backend, dim, dtype, pinned, want):
    n0 = row_update.refusal_count()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        _, arm = _arm(monkeypatch, backend, dim, dtype, state_scatter=pinned)
    assert arm == want
    assert row_update.refusal_count() == n0


@pytest.mark.parametrize("dim,dtype,lanes,reason", [
    (64, jnp.float32, 256, "multiple of 128"),
    (128, jnp.bfloat16, 256, "bfloat16"),
    (128, jnp.float32, 131_072, "131072 lanes"),
])
def test_refused_state_shape_on_tpu_warns_once_and_counts(
        monkeypatch, dim, dtype, lanes, reason):
    n0 = row_update.refusal_count()
    with pytest.warns(RuntimeWarning, match="falling back") as caught:
        logic, arm = _arm(monkeypatch, "tpu", dim, dtype, lanes=lanes)
    assert arm == "xla" and reason in str(caught[0].message)
    assert row_update.refusal_count() == n0 + 1
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # the second trace is silent
        assert logic.state_update_arm(
            jax.ShapeDtypeStruct((64, dim), dtype), lanes) == "xla"
    assert row_update.refusal_count() == n0 + 1


def test_mesh_keeps_the_xla_arm_silently(monkeypatch):
    from flink_parameter_server_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(worker_parallelism=2, ps_parallelism=2,
                     devices=jax.devices()[:4])
    n0 = row_update.refusal_count()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        _, arm = _arm(monkeypatch, "tpu", 128, mesh=mesh)
    assert arm == "xla" and row_update.refusal_count() == n0


@pytest.mark.parametrize("backend,dim,pinned,started", [
    ("tpu", 128, None, 1), ("cpu", 128, None, 0), ("tpu", 64, None, 0),
    ("tpu", 128, "xla", 0),
])
def test_a_logic_that_will_trace_the_kernel_starts_the_pallas_import(
        monkeypatch, backend, dim, pinned, started):
    calls = []
    monkeypatch.setattr(row_update, "preload", lambda: calls.append(1))
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    mfm.OnlineMatrixFactorization(64, dim, state_scatter=pinned)
    assert len(calls) == started


def test_preload_imports_pallas_off_the_calling_thread():
    import sys
    import threading

    row_update.preload()
    for t in threading.enumerate():
        if t.name == "pallas-import":
            t.join(60)
    assert "jax.experimental.pallas.tpu" in sys.modules


def test_unknown_state_scatter_is_refused():
    with pytest.raises(ValueError, match="state_scatter"):
        mfm.OnlineMatrixFactorization(8, 8, state_scatter="pallas")
