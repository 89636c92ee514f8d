"""DLRM-DCNv2 (``models/dlrm_dcnv2.py``): the written-out backward pass
against autodiff of the written-down loss, the server's rule against
``torch.optim.Adagrad``'s formula, the step through ``make_train_step``
against the benchmark's plain reference, hot one-row tables."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import run
from chipbench.families import dlrm_dcnv2 as fam
from chipbench.references import dlrm_dcnv2 as plain
from flink_parameter_server_tpu.core.transform import make_train_step
from flink_parameter_server_tpu.models import dlrm_dcnv2 as dcn

BAGS, DIM, DENSE, BATCH = (3, 1, 2), 8, 5, 32


def _cfg(field_rows=(50, 1, 7), published=(1000, 3, 200)):
    """A configuration file's keys at the test's sizes: bags of (3, 1, 2),
    rank 4."""
    return {
        "field_cardinalities": list(field_rows),
        "source_sizes": {"num_embeddings_per_feature": list(published)},
        "multi_hot_sizes": list(BAGS), "dense_fields": DENSE, "dim": DIM,
        "bottom_mlp": [16, DIM], "cross_layers": 3, "cross_rank": 4,
        "over_mlp": [16, 8, 1], "learning_rate": 0.004, "eps": 1e-8,
        "dtype": "float32", "batch": BATCH,
        "reference": {"batches": 1, "delta_rtol": 1e-5, "delta_atol": 1e-18,
                      "row_ulps": 8, "relu_ulps": 16},
    }


def _model(cfg):
    return dcn.DCNv2Config(
        tuple(cfg["field_cardinalities"]), BAGS,
        tuple(cfg["source_sizes"]["num_embeddings_per_feature"]),
        dense_features=DENSE, dim=DIM, bottom_mlp=(16, DIM), cross_layers=3,
        cross_rank=4, over_mlp=(16, 8, 1))


def _loss(model, params, rows, batch):
    """The loss as the source writes it down, for autodiff."""
    h = batch["dense"]
    for i in range(len(model.bottom_mlp)):
        h = jnp.maximum(h @ params[f"bot{i}_w"] + params[f"bot{i}_b"], 0)
    ends = np.cumsum(model.bag_sizes)
    pooled = [rows[:, e - s:e].sum(1) for e, s in zip(ends, model.bag_sizes)]
    x0 = x = jnp.concatenate([h] + pooled, axis=1)
    for l in range(model.cross_layers):
        x = x0 * ((x @ params[f"cross{l}v_w"]) @ params[f"cross{l}w_w"]
                  + params[f"cross{l}w_b"]) + x
    for i in range(len(model.over_mlp)):
        x = x @ params[f"over{i}_w"] + params[f"over{i}_b"]
        if i < len(model.over_mlp) - 1:
            x = jnp.maximum(x, 0)
    logit, y = x[:, 0], batch["label"]
    return jnp.mean(jax.nn.softplus(logit) - y * logit)


def test_the_configuration_counts_what_the_source_publishes():
    sizes = [40000000, 39060, 17295, 7424, 20265, 3, 7122, 1543, 63, 40000000,
             3067956, 405282, 10, 2209, 11938, 155, 4, 976, 14, 40000000,
             40000000, 40000000, 590152, 12973, 108, 36]
    bags = [3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1, 12, 100,
            27, 10, 3, 1, 1]
    model = dcn.DCNv2Config(tuple(-(-n // 32) for n in sizes), tuple(bags),
                            tuple(sizes))
    assert (model.num_rows, model.lookups, model.width) == (6_380_781, 214, 3456)
    assert model.dense_params == 16_044_545
    assert model.macs_per_example == 16_030_464
    with pytest.raises(ValueError):
        dcn.DCNv2Config((5, 5), (1, 1), bottom_mlp=(16, 4), dim=8)
    with pytest.raises(ValueError):
        dcn.DCNv2Config((5, 5), (1,))


def test_the_backward_pass_is_autodiff_of_the_written_down_loss():
    cfg = _cfg()
    model, (batch,) = _model(cfg), fam.host_batches(
        cfg, {"keys": {"kind": "uniform"}}, 5, 1)
    logic = dcn.DLRMDCNv2(model, seed=3)
    state = logic.init_state(jax.random.PRNGKey(0))
    store = dcn.make_store(model, seed=1)
    rows = store.pull(jnp.asarray(batch["ids"]))[..., :DIM]
    new, req, out = logic.step(state, batch, rows)
    params = {k: v for k, v in state.items() if not k.endswith("_acc")}
    with jax.default_matmul_precision("highest"):
        want_p, want_r = jax.grad(
            lambda p, r: _loss(model, p, r, batch), argnums=(0, 1))(params, rows)
    # RAW gradients, one a pulled row, at the worker's width
    assert req.deltas.shape == (BATCH, sum(BAGS), DIM)
    np.testing.assert_allclose(req.deltas, want_r, rtol=2e-5, atol=1e-9)
    assert np.array_equal(req.ids, batch["ids"]) and np.asarray(req.mask).all()
    # Adagrad on every dense leaf, from accumulators at zero
    assert sorted(new) == sorted(state) and len(new) == 2 * len(params)
    for k, g in want_p.items():
        scale = float(jnp.abs(g).max())
        np.testing.assert_allclose(
            jnp.sqrt(new[f"{k}_acc"]), jnp.abs(g), atol=2e-5 * scale)
        sure = np.abs(g) > 1e-4 * scale  # (a step's sign where g is not noise)
        np.testing.assert_allclose(
            np.asarray(new[k])[sure], np.asarray(
                state[k] - 0.004 * g / (jnp.abs(g) + 1e-8))[sure], atol=1e-6)
    np.testing.assert_allclose(
        out["loss"].mean(), _loss(model, params, rows, batch), rtol=1e-5)
    assert sorted(out) == ["loss", "prediction"]


def test_the_rule_is_torch_optim_adagrad_s_step_by_hand():
    rng = np.random.default_rng(0)
    rule = dcn.Adagrad(lr=0.004, eps=1e-8)
    row = np.concatenate([rng.normal(size=(6, DIM)), np.zeros((6, DIM))], 1)
    row = row.astype(np.float32)
    w, acc = row[:, :DIM].astype(np.float64), row[:, DIM:].astype(np.float64)
    for _ in range(3):
        g = rng.normal(size=(6, DIM)).astype(np.float32) * 1e-3
        g[0] = 0.0  # a row's lanes with no gradient stay bit for bit
        new = np.asarray(rule(jnp.asarray(row), jnp.asarray(g)))
        # state_sum.addcmul_(g, g); std = state_sum.sqrt().add_(eps);
        # param.addcdiv_(g, std, value=-lr): the accumulator read AFTER the add
        acc = acc + g.astype(np.float64) ** 2
        w = w - 0.004 * g / (np.sqrt(acc) + 1e-8)
        np.testing.assert_allclose(new[:, :DIM], w, rtol=1e-6, atol=1e-9)
        np.testing.assert_allclose(new[:, DIM:], acc, rtol=1e-6)
        assert np.array_equal(new[0], row[0])
        row = new
    # the first step from zero is a SIGN step, whatever the gradient's size
    first = np.asarray(rule(jnp.zeros((1, 2 * DIM)), jnp.full((1, DIM), 1e-5)))
    np.testing.assert_allclose(first[0, :DIM], -0.004, rtol=2e-3)
    # read BEFORE the add (GloVe's order) the same step divides by eps
    assert dcn.Adagrad().lr == 0.004 and dcn.Adagrad().eps == 1e-8


def test_the_store_is_one_array_of_weights_and_zeroed_accumulators():
    cfg = _cfg()
    model = _model(cfg)
    store = dcn.make_store(model, seed=7)
    assert store.spec.value_shape == (2 * DIM,) and store.spec.worker_width == DIM
    assert store.spec.update == dcn.Adagrad(0.004, 1e-8)
    values = np.asarray(store.values())
    assert values.shape == (58, 2 * DIM) and not values[:, DIM:].any()
    # a table starts by its PUBLISHED row count, not by the rows held
    firsts = [0, 50, 51]
    for first, rows, n in zip(firsts, (50, 1, 7), (1000, 3, 200)):
        block = values[first:first + rows, :DIM]
        assert np.abs(block).max() <= np.sqrt(1 / n)
        assert np.abs(block).max() > 0.5 * np.sqrt(1 / n)
    again = np.asarray(dcn.make_store(model, seed=7).values())
    assert np.array_equal(values, again)
    assert not np.array_equal(values, np.asarray(dcn.make_store(model, seed=8).values()))


def _three_steps(cfg, seed, keys):
    """Three batches through ``make_train_step``; each step is held to the
    plain reference FROM THE STATE THE SYSTEM ITSELF STOOD IN before it (a
    stale read, a rule run twice or an accumulator not written back shows in
    the step after), at the configuration's own limits."""
    model = _model(cfg)
    logic, store = dcn.DLRMDCNv2(model, seed=seed), dcn.make_store(model, seed=seed)
    batches = fam.host_batches(cfg, {"keys": keys}, seed, 3)
    state = logic.init_state(jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(logic, store.spec))
    table, shares = store.table, []
    for b in batches:
        ids = plain.touched([b])
        before = fam.rows(type(store)(store.spec, table), state, ids)
        table, state, out = step(table, state, b)
        got = fam.rows(type(store)(store.spec, table), state, ids)
        failures, worst = run._check_rows(
            cfg["reference"], plain.apply(cfg, before, ids, [b]), got, before)
        assert failures == [], (failures, worst)
        shares.append(worst["share"])
        assert int(out["ps_rule_keys"]) == BATCH * sum(BAGS)
        assert int(out["ps_rule_rows"]) == np.unique(b["ids"]).size
        assert int(out["ps_pull_row_lanes"]) == int(out["ps_push_row_lanes"]) == DIM
    return shares


@pytest.mark.parametrize("keys", [{"kind": "uniform"}, {"kind": "zipf", "a": 1.2}],
                         ids=["uniform", "zipf"])
def test_three_steps_through_make_train_step_are_the_plain_reference_s(keys):
    shares = _three_steps(_cfg(), 11, keys)
    assert all(0 < s <= 1.0 for s in shares), shares


@pytest.mark.parametrize("field_rows", [(1, 1, 1), (50, 1, 1), (1, 40, 7)],
                         ids=str)
def test_hot_one_row_tables_take_one_rule_step_on_the_sum(field_rows):
    # a table of ONE held row takes its field's whole batch on that row:
    # every lane of the field is a duplicate, the rule runs once on the sum
    cfg = _cfg(field_rows=field_rows)
    shares = _three_steps(cfg, 5, {"kind": "uniform"})
    assert all(0 < s <= 1.0 for s in shares), shares
    # ... and by hand: the hot row's accumulator is the SQUARE OF THE SUM of
    # its lanes' gradients, not the sum of their squares
    model = _model(cfg)
    logic, store = dcn.DLRMDCNv2(model, seed=5), dcn.make_store(model, seed=5)
    (b,) = fam.host_batches(cfg, {"keys": {"kind": "uniform"}}, 5, 1)
    state = logic.init_state(jax.random.PRNGKey(0))
    rows = store.pull(jnp.asarray(b["ids"]))[..., :DIM]
    _, req, _ = logic.step(state, b, rows)
    hot = int(np.cumsum((0,) + field_rows)[field_rows.index(1)])
    lanes = np.asarray(b["ids"]) == hot
    assert lanes.sum() >= BATCH
    g = np.asarray(req.deltas, np.float64)[lanes].sum(axis=0)
    table, _, _ = jax.jit(make_train_step(logic, store.spec))(store.table, state, b)
    after = np.asarray(type(store)(store.spec, table).values())[hot]
    np.testing.assert_allclose(after[DIM:], g * g, rtol=1e-4, atol=1e-20)
