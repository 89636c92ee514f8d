"""PyTorch-BigGraph's ComplEx trainer (``models/kge.py``): the logic and the
row-wise AdaGrad rule through ``make_train_step`` against the plain reference
(``chipbench/references/kge.py``): rows, accumulators and operators."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import run, spec
from flink_parameter_server_tpu import ShardedParamStore
from flink_parameter_server_tpu.core import store as store_mod
from flink_parameter_server_tpu.core.transform import make_train_step
from flink_parameter_server_tpu.models import kge

ENTITIES, RELATIONS, DIM = 600, 24, 100  # 101 lanes: one register a row
CHUNKS, CHUNK, UNIFORM = 4, 10, 6
MODEL = kge.KGEConfig(ENTITIES, RELATIONS, DIM)
RULE = kge.RowAdaGrad(0.1, 1e-10)
CFG = {
    "dim": DIM, "lr": 0.1, "lr_rel": 0.01, "eps": 1e-10,
    "reference": {"delta_rtol": 1e-5, "delta_atol": 1e-18, "row_ulps": 8},
}
REF = spec.reference({"reference": {"file": "chipbench/references/kge.py"}})
FAM = spec.family("kge")


def _batches(seed, n=3, hot=True):
    rng = np.random.default_rng(seed)
    half = ENTITIES // 2
    out = []
    for _ in range(n):
        b = {
            "source": rng.integers(0, half, (CHUNKS, CHUNK)),
            "destination": half + rng.integers(0, half, (CHUNKS, CHUNK)),
            "relation": rng.integers(0, RELATIONS - 2, (CHUNKS, CHUNK)),
            "source_negatives": rng.integers(0, half, (CHUNKS, UNIFORM)),
            "destination_negatives": half + rng.integers(
                0, half, (CHUNKS, UNIFORM)),
        }
        if hot:
            b["source"][:, :3] = 7  # a row named 12 times a batch, and
            b["source_negatives"][0, 0] = 7  # as a negative of its own chunk
            b["destination"][1, 2:5] = half + 9
        out.append({k: v.astype(np.int32) for k, v in b.items()})
    return out


def _checked(seed, logic=None, update=None, layout="auto"):
    store = kge.make_store(MODEL, RULE, seed=seed, layout=layout)
    if update is not None:
        store = ShardedParamStore(
            dataclasses.replace(store.spec, update=update), store.table)
    logic = logic or kge.ComplExNegatives(MODEL)
    batches = _batches(seed)
    ids = REF.touched(batches)
    state = logic.init_state(jax.random.PRNGKey(0))
    before = FAM.rows(store, state, ids)
    step = jax.jit(make_train_step(logic, store.spec))
    table = store.table
    for b in batches:
        table, state, outs = step(table, state, b)
    after = ShardedParamStore(store.spec, table)
    failures, worst = run._check_rows(
        CFG["reference"], REF.apply(CFG, before, ids, batches),
        FAM.rows(after, state, ids), before)
    return failures, worst, store, after, state, outs


@pytest.mark.parametrize("layout", ["auto", "dense"])
@pytest.mark.parametrize("seed", [1, 5, 2**31 + 3])
def test_the_step_is_the_reference_on_rows_accumulators_and_operators(
        seed, layout):
    failures, worst, store, after, state, outs = _checked(
        seed % 2**31, layout=layout)
    assert failures == [] and 0 < worst["share"] < 0.5, worst
    assert store.spec.layout == ("packed" if layout == "auto" else "dense")
    before, now = np.asarray(store.values()), np.asarray(after.values())
    named, relations = np.zeros(ENTITIES, bool), np.zeros(RELATIONS, bool)
    for b in _batches(seed % 2**31):
        for k in REF.KEYS:
            named[b[k]] = True
        relations[b["relation"]] = True
    # a row no edge names is left bit-equal; a named one moved
    assert now[~named].tobytes() == before[~named].tobytes()
    assert (now[named, :DIM] != before[named, :DIM]).any(axis=1).all()
    # an accumulator never falls, and starts at 0
    assert (before[:, DIM] == 0).all() and (now[named, DIM] > 0).all()
    # ... and so the operators: the identity, bit-equal where no edge names
    fresh = kge.ComplExNegatives(MODEL).init_state(jax.random.PRNGKey(0))
    ops, acc = np.asarray(state["operators"]), np.asarray(state["operator_acc"])
    assert not relations.all()
    assert ops[~relations].tobytes() == np.asarray(
        fresh["operators"])[~relations].tobytes()
    assert not acc[~relations].any() and (acc[relations] > 0).all()
    # (a lane may step back onto its start: AdaGrad's first step is +-lr_rel)
    moved = ops[relations] != np.asarray(fresh["operators"])[relations]
    assert moved.mean() > 0.95 and moved.any(axis=(1, 2)).all()
    assert int(outs["ps_rule_keys"]) == CHUNKS * 2 * (CHUNK + UNIFORM)
    assert int(outs["kge_relations_live"]) == len(
        np.unique(_batches(seed % 2**31)[-1]["relation"]))
    assert outs["loss"].shape == (CHUNKS, CHUNK) and float(outs["loss"].sum()) > 0


def test_the_written_out_backward_pass_is_autodiffs():
    rng = np.random.default_rng(3)
    (b,) = _batches(3, n=1)
    pulled = jnp.asarray(rng.normal(size=(CHUNKS, 2 * (CHUNK + UNIFORM), DIM)),
                         jnp.float32)
    ops = jnp.asarray(rng.normal(size=(RELATIONS, 2, DIM)), jnp.float32)
    logic = kge.ComplExNegatives(MODEL)

    def loss(pulled, ops):
        state = {"operators": ops, "operator_acc": jnp.zeros_like(ops)}
        return jnp.sum(logic.step(state, b, pulled)[2]["loss"])

    d_rows, d_ops = jax.grad(loss, (0, 1))(pulled, ops)
    state, req, _ = logic.step(
        {"operators": ops, "operator_acc": jnp.zeros_like(ops)}, b, pulled)
    assert np.allclose(req.deltas, d_rows, rtol=1e-4, atol=1e-4)
    # AdaGrad from a zero accumulator: S' is the gradient squared
    assert np.allclose(
        np.sqrt(state["operator_acc"]), np.abs(d_ops), rtol=1e-4, atol=1e-4)


def test_the_rule_is_row_adagrad_on_one_row():
    rng = np.random.default_rng(0)
    row = np.append(rng.normal(size=DIM), 0.3).astype(np.float32)
    grad = rng.normal(size=DIM).astype(np.float32)
    new = np.asarray(RULE(row, grad))
    acc = np.float32(0.3) + np.mean(grad * grad)
    assert np.allclose(new[DIM], acc, rtol=1e-6)
    assert np.allclose(
        new[:DIM], row[:DIM] - 0.1 * grad / (np.sqrt(acc) + 1e-10), rtol=1e-6)
    # whole-row sums carry a zero past the embedding: the same row
    assert np.array_equal(np.asarray(RULE(row, np.append(grad, 0.0))), new)
    # vectorised over any leading axes
    many = np.asarray(RULE(np.tile(row, (2, 3, 1)), np.tile(grad, (2, 3, 1))))
    assert many.shape == (2, 3, DIM + 1) and np.array_equal(many[1, 2], new)


def _faulty(name):
    class Rounded(kge.ComplExNegatives):
        def step(self, state, batch, pulled):
            state, req, out = super().step(state, batch, pulled)
            req.deltas = req.deltas.astype(jnp.bfloat16).astype(jnp.float32)
            return state, req, out

    class NoInChunkNegative(kge.ComplExNegatives):
        # what a destination takes as the negative of its chunk's OTHER
        # edges (and of its own: `dD[:n]`), lost
        def step(self, state, batch, pulled):
            lost = _as_negative(state, batch, pulled)
            state, req, out = super().step(state, batch, pulled)
            req.deltas = req.deltas.at[:, CHUNK:2 * CHUNK].add(-lost)
            return state, req, out

    class ReverseForForward(kge.ComplExNegatives):
        def step(self, state, batch, pulled):
            swapped = {**state, "operators": state["operators"][:, ::-1]}
            new, req, out = super().step(swapped, batch, pulled)
            return {k: v[:, ::-1] for k, v in new.items()}, req, out

    return {"bfloat16_gradients": Rounded,
            "in_chunk_negatives_gradient_dropped": NoInChunkNegative,
            "reverse_operator_for_the_forward": ReverseForForward}[name]


def _as_negative(state, batch, pulled):
    """``dD[:n]``: what a chunk's destinations take from the scores of the
    chunk's edges against them."""
    n = CHUNK
    theta = pulled[..., :DIM]
    ops = jnp.take(state["operators"], batch["relation"], axis=0)
    dst_all = jnp.concatenate([theta[:, n:2 * n], theta[:, 2 * n + UNIFORM:]], 1)
    return kge._side(ops[:, :, 0], theta[:, :n], dst_all)[3][:, :n]


@pytest.mark.parametrize("fault", [
    "bfloat16_gradients", "in_chunk_negatives_gradient_dropped",
    "reverse_operator_for_the_forward",
])
def test_a_wrong_step_fails_the_check(fault):
    if fault == "reverse_operator_for_the_forward":
        # a relation's two operators differ only once trained: start apart
        class Apart:
            def init_state(self, rng):
                state = super().init_state(rng)
                tilt = jnp.linspace(0.5, 1.5, DIM)[None, None, :] * jnp.asarray(
                    [1.0, -1.0])[None, :, None]
                return {**state, "operators": state["operators"] * tilt}

        right = type("Right", (Apart, kge.ComplExNegatives), {})(MODEL)
        assert _checked(5, logic=right)[0] == []
        wrong = type("Wrong", (Apart, _faulty(fault)), {})(MODEL)
        failures, worst, *_ = _checked(5, logic=wrong)
    else:
        failures, worst, *_ = _checked(5, logic=_faulty(fault)(MODEL))
    assert failures and worst["share"] > 3, worst


@pytest.mark.parametrize("fault", [
    "accumulator_read_before", "mean_taken_as_a_sum",
    "rule_twice_on_a_duplicated_row",
])
def test_a_wrong_rule_fails_the_check(fault):
    def rule(current, combined):
        theta, acc = current[..., :DIM], current[..., DIM:]
        g = combined[..., :DIM]
        if fault == "accumulator_read_before":
            # GloVe's order: divide by the accumulator as it stood
            grown = acc + jnp.mean(g * g, axis=-1, keepdims=True)
            return jnp.concatenate(
                [theta - 0.1 * g / (jnp.sqrt(acc) + 1e-10), grown], axis=-1)
        if fault == "mean_taken_as_a_sum":
            grown = acc + jnp.sum(g * g, axis=-1, keepdims=True)
            return jnp.concatenate(
                [theta - 0.1 * g / (jnp.sqrt(grown) + 1e-10), grown], axis=-1)
        # the rule run once a LANE of the row's run: half the sum, twice
        return RULE(RULE(current, 0.5 * combined), 0.5 * combined)

    failures, worst, *_ = _checked(5, update=rule)
    assert failures and worst["share"] > 3, worst


def test_default_precision_products_fail_the_check(monkeypatch):
    # one bfloat16 pass, the TPU's default, stood in for on the CPU: the
    # products' operands rounded to bfloat16
    real = jnp.einsum

    def coarse(eq, a, b, **kw):
        return real(eq, a.astype(jnp.bfloat16).astype(jnp.float32),
                    b.astype(jnp.bfloat16).astype(jnp.float32), **kw)

    monkeypatch.setattr(kge.jnp, "einsum", coarse)
    failures, worst, *_ = _checked(5)
    assert failures and worst["share"] > 3, worst


def test_make_store_is_a_rule_store_of_one_register_a_row():
    store = jax.jit(lambda s: kge.make_store(MODEL, seed=s))(np.uint32(9))
    spec_ = store.spec
    assert spec_.layout == "packed" and spec_.pack == 1
    assert spec_.value_shape == (DIM + 1,) and spec_.worker_width == DIM
    assert store.table.shape == (ENTITIES, 128)
    assert store_mod.arms(spec_).push == "rule"
    values = np.asarray(store.values())
    assert values.shape == (ENTITIES, DIM + 1)
    assert 0.5e-3 < values[:, :DIM].std() < 2e-3 and not values[:, DIM].any()
    assert not np.asarray(store.table)[:, DIM + 1:].any()  # the pad lanes
    # a row is a function of the seed and its id alone, whatever the layout
    dense = kge.make_store(MODEL, seed=9, layout="dense")
    assert np.asarray(dense.values()).tobytes() == values.tobytes()
    other = kge.make_store(MODEL, seed=10)
    assert not np.array_equal(np.asarray(other.values()), values)
    # a step's pull is the worker's part, a bare pull the whole row
    ids = jnp.asarray([3, 0, 599])
    part = store_mod.pull(spec_, store.table, ids, worker_part=True)
    assert part.shape == (3, DIM)
    assert np.asarray(part).tobytes() == values[[3, 0, 599], :DIM].tobytes()
    assert np.asarray(store.pull(ids)).tobytes() == values[[3, 0, 599]].tobytes()


def test_a_store_that_names_no_part_trains_the_same_table():
    # whole rows pulled (a `from_values` reload names no worker's part): the
    # step answers with whole rows, a zero for the accumulator's lane
    store = kge.make_store(MODEL, RULE, seed=4)
    whole = dataclasses.replace(store.spec, worker_width=None)
    logic = kge.ComplExNegatives(MODEL)
    (b,) = _batches(4, n=1)
    tables = []
    for spec_ in (store.spec, whole):
        table, _, outs = jax.jit(make_train_step(logic, spec_))(
            store.table, logic.init_state(jax.random.PRNGKey(0)), b)
        tables.append(np.asarray(table))
        assert ("ps_pull_row_lanes" in outs) == (spec_ is store.spec)
    assert tables[0].tobytes() == tables[1].tobytes()


def test_the_logics_scopes_are_in_the_lowered_step_inside_compute():
    store = kge.make_store(MODEL, seed=1)
    (b,) = _batches(1, n=1)
    logic = kge.ComplExNegatives(MODEL)
    text = jax.jit(make_train_step(logic, store.spec)).lower(
        store.table, logic.init_state(jax.random.PRNGKey(0)), b,
    ).as_text(debug_info=True)
    for scope in ("ps.pull", "ps.compute/ps.kge_operator",
                  "ps.compute/ps.kge_score", "ps.compute/ps.kge_score_grad",
                  "ps.compute/ps.kge_operator_update", "ps.push/ps.combine",
                  "ps.rule"):
        assert scope in text, scope
    assert "transpose(jvp(" not in text  # the backward pass is written out


def test_the_driver_sets_the_rule_s_gauges_and_the_logic_s_after_the_loop():
    from flink_parameter_server_tpu import DriverConfig, StreamingDriver
    from flink_parameter_server_tpu.telemetry.registry import MetricsRegistry

    registry = MetricsRegistry()
    driver = StreamingDriver(
        kge.ComplExNegatives(MODEL), kge.make_store(MODEL, seed=2),
        config=DriverConfig(steps_per_call=1, dump_model=False),
        registry=registry,
    )
    batches = _batches(2)
    driver.run(iter(batches))
    gauges = registry.snapshot()
    last = batches[-1]
    keys = np.concatenate([last[k].reshape(-1) for k in REF.KEYS])
    assert gauges["store_rule_keys"][0]["value"] == len(keys)
    assert gauges["store_rule_rows"][0]["value"] == len(np.unique(keys))
    assert gauges["store_rule_packed_rows"][0]["value"] == len(np.unique(keys))
    assert gauges["kge_relations_live"][0]["value"] == len(
        np.unique(last["relation"]))
    # the worker's part of a row crossed: the embedding, no accumulator
    assert gauges["store_pull_row_lanes"][0]["value"] == DIM
    assert gauges["store_push_row_lanes"][0]["value"] == DIM


@pytest.mark.parametrize("arm", ["xla", "row_kernel"])
def test_the_driver_publishes_the_copies_the_combine_started(arm, steer_arms):
    """``store_combine_kernel_writes`` beside ``store_combine_kernel_lanes``
    for the one-register rule row: with the row kernel steered on
    (interpreted here) the sums of a batch whose keys are nearly all
    distinct leave as ONE copy a block of 256 sorted lanes (the dense plan
    of PR 62; a DMA a distinct row until then), and the step's table is the
    scatter-add arm's within the rounding of a blocked sum; both gauges 0
    where XLA's scatter-add summed the rows."""
    from flink_parameter_server_tpu import DriverConfig, StreamingDriver
    from flink_parameter_server_tpu.telemetry.registry import MetricsRegistry

    if arm == "row_kernel":
        steer_arms(combine="row_kernel")
    rng = np.random.default_rng(62)
    half, chunks, chunk, uniform = ENTITIES // 2, 8, 40, 24
    batch = {
        "source": rng.integers(0, half, (chunks, chunk)),
        "destination": half + rng.integers(0, half, (chunks, chunk)),
        "relation": rng.integers(0, RELATIONS, (chunks, chunk)),
        "source_negatives": rng.integers(0, half, (chunks, uniform)),
        "destination_negatives": half + rng.integers(0, half, (chunks, uniform)),
    }
    batch = {k: v.astype(np.int32) for k, v in batch.items()}
    registry = MetricsRegistry()
    driver = StreamingDriver(
        kge.ComplExNegatives(MODEL), kge.make_store(MODEL, seed=3),
        config=DriverConfig(steps_per_call=1, dump_model=False),
        registry=registry,
    )
    result = driver.run(iter([batch]))
    gauges = registry.snapshot()
    lanes = gauges["store_combine_kernel_lanes"][0]["value"]
    writes = gauges["store_combine_kernel_writes"][0]["value"]
    if arm == "xla":
        assert lanes == 0 == writes
        return
    keys = np.concatenate([batch[k].reshape(-1) for k in REF.KEYS])
    rows = gauges["store_rule_rows"][0]["value"]
    assert lanes == len(keys) == 1024 and rows == len(np.unique(keys)) > 300
    assert writes == 4 == lanes // 256  # a copy a block, not a DMA a row
    want = StreamingDriver(
        kge.ComplExNegatives(MODEL), kge.make_store(MODEL, seed=3),
        config=DriverConfig(steps_per_call=1, dump_model=False),
    )
    steer_arms(combine="scatter_add")
    want = np.asarray(want.run(iter([batch])).store.table)
    np.testing.assert_allclose(
        np.asarray(result.store.table), want, rtol=2e-6, atol=1e-7)
