"""Sparse logistic regression with FTRL-Proximal on the server
(``models/logistic_ftrl.py``): the rule, the batched logic and store against
the plain numpy reference, the batch form against Algorithm 1 run example by
example, the event API against the batched path, and the counters a rule
store's push hands to the driver."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import spec as bench_spec
from flink_parameter_server_tpu import (
    DriverConfig,
    ShardedParamStore,
    StreamingDriver,
)
from flink_parameter_server_tpu.core.api import SimplePSLogic
from flink_parameter_server_tpu.core.transform import (
    make_train_step,
    transform,
)
from flink_parameter_server_tpu.models import logistic_ftrl as lf
from flink_parameter_server_tpu.telemetry.registry import MetricsRegistry

CFG = {"alpha": 0.1, "beta": 1.0, "l1": 1.0, "l2": 1.0, "reference": {
    "delta_rtol": 4e-5, "delta_atol": 1e-12, "row_ulps": 8,
}}
RULE = lf.FTRLProximal(**{k: CFG[k] for k in ("alpha", "beta", "l1", "l2")})
REF = bench_spec.reference({"reference": {"file": "chipbench/references/lr.py"}})


def _warm_rows(rng, n):
    z = rng.normal(0, 2.0, n).astype(np.float32)
    acc = (64 * rng.random(n)).astype(np.float32)
    w = np.asarray(RULE.weights(jnp.asarray(z), jnp.asarray(acc)))
    return np.stack([w, z, acc], axis=-1)


def _batches(rng, features, batch, fields, count, hot=3):
    """Heavy duplicates: ``hot`` rows every example hits (values in [0, 1)),
    a few dozen-valued fields, a wide one; some features and examples masked,
    some ids out of range."""
    out = []
    for _ in range(count):
        ids = np.concatenate([
            np.broadcast_to(np.arange(hot), (batch, hot)),
            rng.integers(hot, hot + 12, (batch, 2)),
            rng.integers(0, features, (batch, fields - hot - 2)),
        ], axis=1).astype(np.int32)
        values = np.ones((batch, fields), np.float32)
        values[:, :hot] = rng.random((batch, hot), np.float32)
        out.append({
            "ids": ids, "values": values,
            "feat_mask": rng.random((batch, fields)) < 0.9,
            "label": rng.choice(np.array([-1.0, 1.0], np.float32), batch),
            "mask": rng.random(batch) < 0.95,
        })
    return out


def test_the_rule_is_data_and_a_store_update():
    assert RULE == lf.FTRLProximal(0.1, 1.0, 1.0, 1.0) and hash(RULE) == hash(
        lf.FTRLProximal()
    )
    assert lf.FTRLProximal(alpha=0.2) != RULE
    store = lf.make_store(40, RULE)
    assert store.spec.update is RULE and store.spec.value_shape == (3,)
    assert store.spec.layout == "dense"
    assert not np.asarray(store.values()).any()
    assert np.array_equal(RULE.init(7), np.zeros(3, np.float32))


@pytest.mark.parametrize("z, n, want", [
    (0.5, 9.0, 0.0),                 # inside the L1 ball
    (-1.0, 9.0, 0.0),                # on it
    (3.0, 9.0, -2.0 / 41.0),         # (beta + 3) / alpha + l2 = 41
    (-3.0, 0.0, 2.0 / 11.0),
])
def test_weights_is_algorithm_1_s_closed_form(z, n, want):
    got = float(RULE.weights(jnp.float32(z), jnp.float32(n)))
    assert got == pytest.approx(want, rel=1e-6, abs=0)


def test_the_rule_vectorises_over_leading_axes_and_takes_numpy():
    rng = np.random.default_rng(0)
    rows = _warm_rows(rng, 24)
    combined = np.stack(
        [rng.normal(size=24), np.zeros(24), rng.random(24)], axis=-1
    ).astype(np.float32)
    flat = np.asarray(RULE(rows, combined))
    boxed = np.asarray(RULE(rows.reshape(4, 6, 3), combined.reshape(4, 6, 3)))
    one = np.asarray(RULE(rows[5], combined[5]))
    assert np.array_equal(flat.reshape(4, 6, 3), boxed)
    assert np.array_equal(flat[5], one) and flat.dtype == np.float32
    # n' = n + S; an untouched accumulator and a zero gradient leave z alone
    assert np.array_equal(flat[:, lf.N], rows[:, lf.N] + combined[:, lf.N])
    still = np.asarray(RULE(rows, np.zeros_like(rows)))
    assert np.array_equal(still[:, 1:], rows[:, 1:])


def _algorithm_1(rows, batch, cfg):
    """float64, example by example in stream order, every weight read as it
    stood at the start of the step (the batched path's staleness): Algorithm
    1's per-coordinate lines as the paper writes them."""
    z, n = rows[:, 1].astype(np.float64), rows[:, 2].astype(np.float64)
    w0 = rows[:, 0].astype(np.float64)
    seen = np.zeros(len(rows), bool)
    for ids, x, fm, y, m in zip(batch["ids"], batch["values"],
                                batch["feat_mask"], batch["label"], batch["mask"]):
        if not m:
            continue
        x = np.where(fm, x, 0).astype(np.float64)
        p = 1 / (1 + np.exp(-(w0[ids] * x).sum()))
        for i, xi, on in zip(ids, x, fm):
            if not on:
                continue
            g = (p - (y > 0)) * xi
            sigma = (np.sqrt(n[i] + g * g) - np.sqrt(n[i])) / cfg["alpha"]
            z[i] += g - sigma * w0[i]
            n[i] += g * g
            seen[i] = True
    scale = (cfg["beta"] + np.sqrt(n)) / cfg["alpha"] + cfg["l2"]
    w = np.where(np.abs(z) <= cfg["l1"], 0, -(z - np.sign(z) * cfg["l1"]) / scale)
    return np.where(seen[:, None], np.stack([w, z, n], -1), rows), seen


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_batch_form_is_algorithm_1_example_by_example(seed):
    # the per-example sigma telescope: summed deltas and ONE rule a touched
    # row equal the paper's loop to float64 rounding
    rng = np.random.default_rng(seed)
    rows = _warm_rows(rng, 60).astype(np.float64)
    (batch,) = _batches(rng, 60, 96, 8, 1)
    want, seen = _algorithm_1(rows, batch, CFG)
    live = batch["feat_mask"] & batch["mask"][:, None]
    x = np.where(batch["feat_mask"], batch["values"], 0).astype(np.float64)
    p = 1 / (1 + np.exp(-(rows[batch["ids"], 0] * x).sum(-1)))
    g = np.where(live, (p - (batch["label"] > 0))[:, None] * x, 0)
    big_g, big_s = np.zeros(60), np.zeros(60)
    np.add.at(big_g, batch["ids"].ravel(), g.ravel())
    np.add.at(big_s, batch["ids"].ravel(), (g * g).ravel())
    with jax.enable_x64():
        got = np.asarray(RULE(
            jnp.asarray(rows), jnp.stack(
                [jnp.asarray(big_g), jnp.zeros(60), jnp.asarray(big_s)], -1
            )
        ))
    assert got.dtype == np.float64 and seen.sum() > 30
    assert np.allclose(got[seen], want[seen], rtol=1e-12, atol=1e-13)
    # both branches of the threshold among the touched rows
    assert (want[seen, 0] == 0).any() and (want[seen, 0] != 0).any()


def _run_batches(store, batches):
    step = jax.jit(make_train_step(lf.LogisticFTRL(), store.spec))
    table, outs = store.table, []
    for b in batches:
        table, _, out = step(table, (), b)
        outs.append(out)
    return ShardedParamStore(store.spec, table), outs


@pytest.mark.parametrize("seed, features, batch, fields", [
    (3, 300, 128, 9), (4, 2000, 512, 12), (5, 97, 64, 7),
])
def test_logic_and_store_against_the_plain_reference(seed, features, batch, fields):
    rng = np.random.default_rng(seed)
    rows = _warm_rows(rng, features)
    batches = _batches(rng, features, batch, fields, 3)
    # ids no row has, negative and past the end: the pull clips them (their
    # value is 0 here, so the margin takes nothing from the row they clip
    # to) and the push drops them
    batches[1]["ids"][::7, -1] = -3
    batches[1]["values"][::7, -1] = 0
    batches[2]["ids"][::5, -2] = features + 211  # past the padding rows too
    batches[2]["values"][::5, -2] = 0
    store = ShardedParamStore.from_values(jnp.asarray(rows), update=RULE)
    after, outs = _run_batches(store, batches)
    got = np.asarray(after.values())
    in_range = [
        {**b, "feat_mask": b["feat_mask"] & (b["ids"] >= 0) & (b["ids"] < features),
         "ids": np.clip(b["ids"], 0, features - 1)} for b in batches
    ]
    ids = {"feature": np.arange(features, dtype=np.int32)}
    (want,), (moved,) = (
        list(t.values()) for t in REF.apply(CFG, {"feature": rows}, ids, in_range)
    )
    touched = moved[:, lf.N] > 0
    assert 0.3 < touched.mean() <= 1.0
    # rows nobody pushed to are left bit for bit
    assert np.array_equal(got[~touched], rows[~touched])
    allowed = 4e-5 * moved + 8 * np.finfo(np.float32).eps * np.maximum(
        np.abs(want), np.abs(rows)
    )
    assert (np.abs(got - want) <= allowed).all()
    assert (want[touched, lf.W] == 0).any() and (want[touched, lf.W] != 0).any()
    # a masked example and an out-of-range id push nothing
    keys = [int(o["ps_rule_keys"]) for o in outs]
    assert keys[0] == (batches[0]["feat_mask"] & batches[0]["mask"][:, None]).sum()
    assert keys[2] == (in_range[2]["feat_mask"] & in_range[2]["mask"][:, None]).sum()
    for o, b in zip(outs, in_range):
        live = b["feat_mask"] & b["mask"][:, None]
        assert int(o["ps_rule_rows"]) == len(np.unique(b["ids"][live]))
        assert o["prediction"].shape == o["loss"].shape == (batch,)
        assert np.isfinite(np.asarray(o["loss"])).all()
        assert (np.asarray(o["loss"])[~b["mask"]] == 0).all()


@pytest.mark.parametrize("margin", [-40.0, -20.0, 20.0, 40.0])
def test_the_loss_of_a_saturated_margin_is_finite(margin):
    rows = jnp.tile(jnp.array([[margin, 0.0, 4.0]], jnp.float32), (4, 1))
    batch = {
        "ids": np.arange(4, dtype=np.int32).reshape(4, 1),
        "values": np.ones((4, 1), np.float32),
        "feat_mask": np.ones((4, 1), bool),
        "label": np.array([1, -1, 1, -1], np.float32),
        "mask": np.ones(4, bool),
    }
    _, req, out = lf.LogisticFTRL().step((), batch, rows[batch["ids"]])
    loss = np.asarray(out["loss"])
    assert np.isfinite(loss).all() and np.isfinite(np.asarray(req.deltas)).all()
    wrong = (np.sign(margin) != batch["label"])
    assert np.allclose(loss[wrong], abs(margin), rtol=1e-6)
    assert (loss[~wrong] < 1e-8).all()


def test_the_event_api_runs_the_same_rule_as_the_batched_path():
    # one example a step: SimplePSLogic(init, update=rule) against
    # transform_batched at batch size 1, same stream
    rng = np.random.default_rng(11)
    features, fields = 30, 4
    stream = []
    for _ in range(40):
        ids = rng.choice(features, fields, replace=False).astype(np.int32)
        stream.append((ids, rng.random(fields).astype(np.float32) + 0.5,
                       float(rng.choice([-1.0, 1.0]))))
    strong = lf.FTRLProximal(alpha=0.5, beta=1.0, l1=0.05, l2=0.1)
    # the event runtime admits the next record while this one's answers are
    # in flight (the reference's interleaving): one transform a record keeps
    # the stream sequential, the server logic and its store carried across
    server, probs = SimplePSLogic(strong.init, strong), []
    for ids, values, label in stream:
        done = transform(
            [(list(map(int, ids)), list(map(float, values)), label)],
            lf.LogisticFTRLWorkerLogic(), server,
        )
        probs += [p for _, p in done.worker_outputs]
    model = server.store
    batched = lf.train_logistic_ftrl(
        [{"ids": i[None], "values": v[None], "feat_mask": np.ones((1, fields), bool),
          "label": np.array([y], np.float32), "mask": np.ones(1, bool)}
         for i, v, y in stream],
        num_features=features, rule=strong,
    )
    table = np.asarray(batched.store.values())
    assert len(model) > 20 and (table[:, lf.W] != 0).sum() > 5
    for fid, row in model.items():
        assert np.allclose(np.asarray(row), table[fid], rtol=2e-5, atol=1e-7)
    untouched = np.setdiff1d(np.arange(features), list(model))
    assert not table[untouched].any()
    want = [float(o["prediction"][0]) for o in batched.worker_outputs]
    assert np.allclose(probs, want, rtol=1e-5, atol=1e-7)


def test_the_driver_publishes_what_the_push_counted():
    rng = np.random.default_rng(2)
    registry = MetricsRegistry()
    driver = StreamingDriver(
        lf.LogisticFTRL(), lf.make_store(200),
        config=DriverConfig(dump_model=False), registry=registry,
    )
    batches = _batches(rng, 200, 64, 6, 4)
    result = driver.run(batches)
    last = batches[-1]
    live = last["feat_mask"] & last["mask"][:, None]
    gauges = registry.snapshot()
    assert gauges["store_rule_keys"][0]["value"] == live.sum()
    assert gauges["store_rule_rows"][0]["value"] == len(np.unique(last["ids"][live]))
    assert gauges["store_rule_tiles"][0]["value"] == 0  # XLA wrote the rows
    assert np.isfinite(np.asarray(result.store.values())).all()
    # an add store's step counts nothing: its outputs gain no `ps_rule_*`
    # key (a packed one's names the arms that sliced its pulled rows and
    # shifted its pushed ones: here, off the TPU, XLA's selects)
    from flink_parameter_server_tpu.models import factorization_machine as fmm

    fm = fmm.FMConfig(num_features=200, dim=4)
    table, _, out = jax.jit(make_train_step(
        fmm.FactorizationMachine(fm), fmm.make_store(fm).spec
    ))(fmm.make_store(fm).table, (), batches[0])
    assert set(out) == {
        "prediction", "loss", "ps_slice_kernel", "ps_shift_kernel",
        "ps_lanes_by_field"}
    assert int(out["ps_slice_kernel"]) == int(out["ps_shift_kernel"]) == 0
    assert int(out["ps_lanes_by_field"]) == 0


@pytest.mark.parametrize("arm", ["xla", "set_kernel"])
def test_the_store_holds_w_z_n_at_four_lanes_and_the_step_is_the_same(
        arm, monkeypatch, steer_arms):
    """``make_store``'s physical row is four lanes (the fourth zero, never
    read by the rule, stripped by ``pull`` and ``values()``); a checkpoint
    of it holds logical rows and restores onto the same table; the step
    through the kernel's arm (steered, interpreted) leaves the bits XLA's
    row ``set`` leaves and counts the tiles it moved."""
    from flink_parameter_server_tpu.core import store as store_mod
    from flink_parameter_server_tpu.training import checkpoint

    rng = np.random.default_rng(6)
    features = 700
    rows = _warm_rows(rng, features)
    store = ShardedParamStore.from_values(jnp.asarray(rows), update=RULE)
    assert store.table.shape == (768, 4) and store.spec.tile_lanes == 4
    assert lf.make_store(features).table.shape == (768, 4)
    batches = _batches(rng, features, 256, 9, 2)
    want, want_outs = _run_batches(store, batches)
    assert all(int(o["ps_rule_tiles"]) == 0 for o in want_outs)
    if arm == "set_kernel":
        steer_arms(write_back="tile_set")
        got, outs = _run_batches(store, batches)
        np.testing.assert_array_equal(
            np.asarray(got.table), np.asarray(want.table))
        for o, b in zip(outs, batches):
            live = b["feat_mask"] & b["mask"][:, None]
            assert int(o["ps_rule_tiles"]) == len(
                np.unique(b["ids"][live] // 128))
            assert int(o["ps_rule_rows"]) == len(np.unique(b["ids"][live]))
    else:
        got = want
    table = np.asarray(got.table)
    assert not table[:, 3].any() and not table[features:].any()
    values = np.asarray(got.values())
    assert values.shape == (features, 3)
    assert np.array_equal(values, table[:features, :3])
    assert not np.array_equal(values, rows)
    ids = np.array([0, 5, features - 1], np.int32)
    assert np.array_equal(np.asarray(got.pull(jnp.asarray(ids))), values[ids])
    payload = checkpoint._make_payload(got, (), 3, None)
    assert payload["table"].shape == (features, 3)
    back, _, _ = checkpoint._payload_to_state(
        {**payload, "table": np.asarray(payload["table"])}, got.spec
    )
    np.testing.assert_array_equal(np.asarray(back.table), table)


def test_a_scanned_dispatch_sums_its_steps_counts():
    rng = np.random.default_rng(4)
    registry = MetricsRegistry()
    driver = StreamingDriver(
        lf.LogisticFTRL(), lf.make_store(120),
        config=DriverConfig(dump_model=False, steps_per_call=2),
        registry=registry,
    )
    batches = _batches(rng, 120, 32, 5, 4)
    driver.run(batches)
    want = sum(
        (b["feat_mask"] & b["mask"][:, None]).sum() for b in batches[-2:]
    )
    assert registry.snapshot()["store_rule_keys"][0]["value"] == want
