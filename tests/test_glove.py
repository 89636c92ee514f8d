"""GloVe with AdaGrad on the server (``models/glove.py``): the logic and the
rule through ``make_train_step`` against the plain reference
(``chipbench/references/glove.py``), parameters and accumulators."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import run, spec
from flink_parameter_server_tpu import ShardedParamStore
from flink_parameter_server_tpu.core import store as store_mod
from flink_parameter_server_tpu.core.transform import make_train_step
from flink_parameter_server_tpu.models import glove as gl

VOCAB, DIM = 96, 150  # 302 lanes: flat in three registers
MODEL = gl.GloVeConfig(VOCAB, DIM)
CFG = {
    "dim": DIM, "eta": 0.05, "x_max": 100.0, "alpha": 0.75,
    "reference": {"delta_rtol": 4e-5, "delta_atol": 1e-12, "row_ulps": 8},
}
REF = spec.reference({"reference": {"file": "chipbench/references/glove.py"}})


def _batches(seed, n=3, size=64, hot=True):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        word = rng.integers(0, VOCAB, size).astype(np.int32)
        context = rng.integers(0, VOCAB, size).astype(np.int32)
        if hot:
            word[: size // 4] = 7      # a row named 16 times a batch
            context[5: size // 8] = 7  # the same WORD as a context: another row
        out.append({
            "word": word, "context": context,
            "count": np.exp(rng.uniform(0, 9, size)).astype(np.float32),
            "mask": rng.random(size) < 0.9,
        })
    return out


def _rows(store, ids):
    return {
        side: np.asarray(store.pull(jnp.asarray(ids[side] + first)), np.float32)
        for side, first in (("word", 0), ("context", VOCAB))
    }


def _checked(seed, logic=None, update=None, layout="auto"):
    store = gl.make_store(MODEL, gl.GloVeAdaGrad(0.05), seed=seed, layout=layout)
    if update is not None:
        store = ShardedParamStore(
            dataclasses.replace(store.spec, update=update), store.table)
    batches = _batches(seed)
    ids = REF.touched(batches)
    before = _rows(store, ids)
    step = jax.jit(make_train_step(logic or gl.GloVe(MODEL), store.spec))
    table = store.table
    for b in batches:
        table, _, outs = step(table, (), b)
    after = ShardedParamStore(store.spec, table)
    failures, worst = run._check_rows(
        CFG["reference"], REF.apply(CFG, before, ids, batches),
        _rows(after, ids), before)
    return failures, worst, store, after, outs


@pytest.mark.parametrize("layout", ["auto", "dense"])
@pytest.mark.parametrize("seed", [1, 5, 2**31 + 3])
def test_the_step_is_the_reference_on_parameters_and_accumulators(seed, layout):
    failures, worst, store, after, outs = _checked(seed % 2**31, layout=layout)
    assert failures == [] and worst["share"] < 0.5, worst
    assert store.spec.layout == ("packed" if layout == "auto" else "dense")
    before, now = np.asarray(store.values()), np.asarray(after.values())
    named = np.zeros(2 * VOCAB, bool)
    for b in _batches(seed % 2**31):
        named[b["word"][b["mask"]]] = True
        named[VOCAB + b["context"][b["mask"]]] = True
    # a row no record names is left bit-equal; a named one moved
    assert now[~named].tobytes() == before[~named].tobytes()
    assert (now[named] != before[named]).any(axis=1).all()
    p = MODEL.params
    # an accumulator never falls, and starts at 1
    assert (before[:, p:] == 1).all() and (now[:, p:] >= before[:, p:]).all()
    assert int(outs["ps_rule_keys"]) == 2 * int(outs["cooc_live_records"])
    assert float(jnp.sum(outs["cost"])) > 0


@pytest.mark.parametrize("arm", ["xla", "tile_kernels"])
def test_a_masked_record_changes_nothing(arm, monkeypatch, steer_arms):
    """... whatever its gradient holds: the push hands a masked lane's delta
    on as it is (no pass zeroes the pushed block since PR 57), so a masked
    record of count 0 (``ln 0``: a row of -inf and NaN gradients) must reach
    no kept row, in the scatter-add's sums and in the tile kernel's."""
    if arm == "tile_kernels":
        steer_arms(combine="tile_kernel", write_back="tile_assign")
    store = gl.make_store(MODEL, seed=3)
    (b,) = _batches(3, n=1)
    step = jax.jit(make_train_step(gl.GloVe(MODEL), store.spec))
    kept = {**b, "mask": b["mask"] & (np.arange(64) % 3 != 0)}
    dropped = {k: v[kept["mask"]] for k, v in kept.items()}
    kept["count"] = np.where(kept["mask"], b["count"], 0.0).astype(np.float32)
    got, _, _ = step(store.table, (), kept)
    assert np.isfinite(np.asarray(got)).all()
    want, _, _ = jax.jit(make_train_step(gl.GloVe(MODEL), store.spec))(
        store.table, (), dropped)
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    none, _, outs = step(store.table, (), {**b, "mask": np.zeros(64, bool)})
    assert np.asarray(none).tobytes() == np.asarray(store.table).tobytes()
    assert int(outs["ps_rule_rows"]) == 0 and float(outs["cost"].sum()) == 0


def test_the_rule_is_glove_c_s_adaptive_update_on_one_row():
    rng = np.random.default_rng(0)
    p = MODEL.params
    row = np.concatenate([rng.normal(size=p), 1 + rng.random(p)]).astype(np.float32)
    grad = np.concatenate([rng.normal(size=p), np.zeros(p)]).astype(np.float32)
    new = np.asarray(gl.GloVeAdaGrad(0.05)(row, grad))
    u = np.float32(0.05) * grad[:p]
    assert np.allclose(new[:p], row[:p] - u / np.sqrt(row[p:]), rtol=1e-6)
    assert np.allclose(new[p:], row[p:] + u * u, rtol=1e-6)
    # vectorised over any leading axes
    many = np.asarray(gl.GloVeAdaGrad(0.05)(
        np.tile(row, (2, 3, 1)), np.tile(grad, (2, 3, 1))))
    assert many.shape == (2, 3, 2 * p) and np.array_equal(many[1, 2], new)


def test_bfloat16_gradients_fail_the_check():
    class Rounded(gl.GloVe):
        def step(self, state, batch, pulled):
            state, req, out = super().step(state, batch, pulled)
            req.deltas = req.deltas.astype(jnp.bfloat16).astype(jnp.float32)
            return state, req, out

    failures, worst, *_ = _checked(5, logic=Rounded(MODEL))
    assert failures and worst["share"] > 20, worst


@pytest.mark.parametrize("fault", [
    "no_square", "rule_twice_on_a_duplicated_row", "accumulator_read_after",
    "bfloat16_accumulators",
])
def test_a_wrong_rule_fails_the_check(fault):
    eta = np.float32(0.05)

    def rule(current, combined):
        p = current.shape[-1] // 2
        param, g = current[..., :p], current[..., p:]
        u = eta * combined[..., :p]
        if fault == "no_square":
            return jnp.concatenate([param - u / jnp.sqrt(g), g], axis=-1)
        if fault == "accumulator_read_after":
            g = g + u * u
            return jnp.concatenate([param - u / jnp.sqrt(g), g], axis=-1)
        if fault == "bfloat16_accumulators":
            g2 = (g + u * u).astype(jnp.bfloat16).astype(jnp.float32)
            return jnp.concatenate([param - u / jnp.sqrt(g), g2], axis=-1)
        # the rule run once a LANE of the row's run: half the sum, twice
        half = gl.GloVeAdaGrad(0.05)(current, 0.5 * combined)
        return gl.GloVeAdaGrad(0.05)(half, 0.5 * combined)

    failures, worst, *_ = _checked(5, update=rule)
    assert failures and worst["share"] > 3, worst


def test_make_store_is_a_rule_store_flat_in_whole_registers():
    store = jax.jit(lambda s: gl.make_store(MODEL, seed=s))(np.uint32(9))
    assert store.spec.layout == "packed" and store.spec.pack == 1
    assert store.table.shape == (2 * VOCAB, 384)  # 302 lanes in three registers
    assert store_mod.arms(store.spec).push == "rule"
    values = np.asarray(store.values())
    p = MODEL.params
    assert values.shape == (2 * VOCAB, 2 * p)
    assert (np.abs(values[:, :p]) <= 0.5 / DIM).all() and values[:, :p].std() > 0
    assert (values[:, p:] == 1).all()
    assert not np.asarray(store.table)[:, 2 * p:].any()  # the pad lanes
    # a row is a function of the seed and its id alone, whatever the layout
    dense = gl.make_store(MODEL, seed=9, layout="dense")
    assert np.asarray(dense.values()).tobytes() == values.tobytes()
    other = gl.make_store(MODEL, seed=10)
    assert not np.array_equal(np.asarray(other.values()), values)


def test_the_logics_scope_is_in_the_lowered_step_inside_compute():
    store = gl.make_store(MODEL, seed=1)
    (b,) = _batches(1, n=1)
    text = jax.jit(make_train_step(gl.GloVe(MODEL), store.spec)).lower(
        store.table, (), b).as_text(debug_info=True)
    for scope in ("ps.pull", "ps.compute/ps.cooc_grad_rows", "ps.push/ps.combine",
                  "ps.rule"):
        assert scope in text, scope


def test_the_driver_sets_the_rule_s_gauges_after_the_loop():
    from flink_parameter_server_tpu import DriverConfig, StreamingDriver
    from flink_parameter_server_tpu.telemetry.registry import MetricsRegistry

    registry = MetricsRegistry()
    driver = StreamingDriver(
        gl.GloVe(MODEL), gl.make_store(MODEL, seed=2),
        config=DriverConfig(steps_per_call=1, dump_model=False),
        registry=registry,
    )
    batches = _batches(2)
    driver.run(iter(batches))
    gauges = registry.snapshot()
    last = batches[-1]
    keys = np.concatenate([
        last["word"][last["mask"]], VOCAB + last["context"][last["mask"]]])
    assert gauges["store_rule_keys"][0]["value"] == len(keys)
    assert gauges["store_rule_rows"][0]["value"] == len(np.unique(keys))
    assert gauges["store_rule_packed_rows"][0]["value"] == len(np.unique(keys))
    assert gauges["store_combine_kernel_lanes"][0]["value"] == 0  # a CPU
    # the worker's part of a row crossed, the vector and its bias
    assert gauges["store_pull_row_lanes"][0]["value"] == MODEL.params
    assert gauges["store_push_row_lanes"][0]["value"] == MODEL.params
