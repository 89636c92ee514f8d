"""SEVERAL stores in one train step (``core/store.StoreGroup`` /
``GroupSpec``, ``core/transform.make_train_step`` over a group) and the model
that needs it, ``models/wide_deep.py``: two key spaces, two row widths, two
rules, the wide keys hashed inside the step."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_parameter_server_tpu import (
    BatchedWorkerLogic, DriverConfig, GroupSpec, PushRequest, StoreGroup,
    StreamingDriver, transform_batched)
from flink_parameter_server_tpu.core import store as store_mod
from flink_parameter_server_tpu.core.transform import make_train_step
from flink_parameter_server_tpu.models import dlrm_dcnv2 as dcn
from flink_parameter_server_tpu.models import logistic_ftrl as lf
from flink_parameter_server_tpu.models import wide_deep as wd
from flink_parameter_server_tpu.ops.hashing import pair_key
from flink_parameter_server_tpu.telemetry.registry import MetricsRegistry

CONFIG = wd.WideDeepConfig(
    (50, 7, 300, 3), dense_features=5, dim=8, hidden=(32, 16),
    cross_buckets=64)
WARM = {"z_max": 3.0, "n_max": 8.0, "acc_span": 0.4}
FIRSTS = np.concatenate([[0], np.cumsum(CONFIG.field_rows)[:-1]])


def _stores(seed=3, **warm):
    return wd.make_stores(CONFIG, seed=seed, **(warm or WARM))


def _batch(rng, n=16, masked=()):
    ids = np.stack(
        [rng.integers(0, c, n) for c in CONFIG.field_rows], 1) + FIRSTS
    mask = np.ones(n, bool)
    mask[list(masked)] = False
    return {
        "ids": ids.astype(np.int32),
        "dense": rng.random((n, CONFIG.dense_features), np.float32),
        "label": rng.choice(np.array([0.0, 1.0], np.float32), n),
        "mask": mask,
    }


def _batches(seed=0, n=3):
    rng = np.random.default_rng(seed)
    return [_batch(rng, masked=(2,) if i == 1 else ()) for i in range(n)]


def _tables(store):
    return {k: np.asarray(v) for k, v in store.values().items()}


# -- the group itself ---------------------------------------------------------
def test_a_group_is_built_and_rebuilt_as_a_store_is():
    stores = _stores()
    spec = stores.spec
    assert isinstance(spec, GroupSpec) and list(spec) == ["wide", "deep"]
    assert spec["wide"].narrow_rule and spec["wide"].worker_width is None
    assert spec["deep"].layout == "packed" and spec["deep"].worker_width == 8
    assert spec.capacity == {
        "wide": CONFIG.wide_rows, "deep": CONFIG.num_rows}
    assert spec.mesh is None and hash(spec) == hash(stores.spec)
    # from a spec and a table, like a ShardedParamStore: what a loop, a
    # driver and a test's own `type(store)(store.spec, table)` all do
    again = type(stores)(spec, stores.table)
    assert again.spec == spec and again["deep"].table is stores["deep"].table
    assert spec.store(None).table is None
    assert spec.store(stores.table).table.keys() == {"wide", "deep"}
    assert [labels for labels, _ in spec.named()] == [
        {"store": "wide"}, {"store": "deep"}]
    assert [labels for labels, _ in spec["deep"].named()] == [{}]
    # a pytree of its tables: jit hands it in and out
    leaves = jax.tree.leaves(stores)
    assert len(leaves) == 2
    out = jax.jit(lambda s: s)(stores)
    assert isinstance(out, StoreGroup) and out.spec == spec
    dumped = stores.dump()
    assert dumped["wide"][1].shape == (CONFIG.wide_rows, 3)
    assert dumped["deep"][0].shape == (CONFIG.num_rows,)


@pytest.mark.parametrize("names", [(), ("a", "a"), ("a b",), ("a@b",)])
def test_a_group_s_names_are_distinct_identifiers(names):
    spec = store_mod.StoreSpec(8, (2,))
    with pytest.raises(ValueError, match="distinct identifiers"):
        GroupSpec(tuple((n, spec) for n in names))


def test_a_group_of_stores_lies_on_one_mesh_with_one_worker_group():
    from flink_parameter_server_tpu.parallel.mesh import make_mesh

    if jax.device_count() < 4:
        pytest.skip("needs four devices")
    one = store_mod.StoreSpec(64, (4,))
    over = store_mod.StoreSpec(
        64, (4,), mesh=make_mesh(1, 2, devices=jax.devices()[:2]))
    with pytest.raises(ValueError, match="ONE mesh"):
        GroupSpec((("a", one), ("b", over)))
    dp = store_mod.StoreSpec(
        64, (4,), mesh=make_mesh(2, 2, devices=jax.devices()[:4]))
    with pytest.raises(ValueError, match="ONE worker group"):
        make_train_step(wd.WideAndDeep(CONFIG), GroupSpec((("a", dp),)))


class _OneStoreInAGroup(BatchedWorkerLogic):
    """A single-store logic told as a group of one."""

    def __init__(self, logic, ids_none):
        self.logic, self.ids_none = logic, ids_none

    def init_state(self, rng):
        return self.logic.init_state(rng)

    def keys(self, batch):
        return {"only": self.logic.keys(batch)}

    def step(self, state, batch, pulled):
        state, req, out = self.logic.step(state, batch, pulled["only"])
        if self.ids_none:
            req = PushRequest(None, req.deltas, req.mask)
        return state, {"only": req}, out


@pytest.mark.parametrize("ids_none", [False, True])
def test_a_group_of_one_store_trains_the_single_store_s_bits(ids_none):
    cfg = dcn.DCNv2Config(
        (50, 7), (2, 1), dense_features=3, dim=8, bottom_mlp=(16, 8),
        cross_layers=1, cross_rank=4, over_mlp=(8, 1))
    rng = np.random.default_rng(1)
    batches = [{
        "ids": rng.integers(0, 50, (16, 3)).astype(np.int32),
        "dense": rng.random((16, 3), np.float32),
        "label": rng.choice(np.array([0.0, 1.0], np.float32), 16),
        "mask": np.ones(16, bool),
    } for _ in range(3)]
    logic = dcn.DLRMDCNv2(cfg)
    alone = transform_batched(batches, logic, dcn.make_store(cfg, seed=2))
    group = transform_batched(
        batches, _OneStoreInAGroup(logic, ids_none),
        StoreGroup.of({"only": dcn.make_store(cfg, seed=2)}))
    assert np.array_equal(
        np.asarray(alone.store.values()), _tables(group.store)["only"])
    for k, v in alone.worker_state.items():
        assert np.array_equal(np.asarray(v), np.asarray(group.worker_state[k]))
    # the group's counts carry the store's name, the single store's none
    assert "ps_rule_rows" in alone.worker_outputs[0]
    assert "ps_rule_rows@only" in group.worker_outputs[0]
    assert not [k for k in alone.worker_outputs[0] if "@" in k]
    # the model flush is a table a name
    ids, values = group.server_outputs[0]["only"]
    assert np.array_equal(values, np.asarray(alone.server_outputs[0][1]))


# -- a step over one store is the program it was ------------------------------
# sha256 of the lowered step's text at the parent commit (159d4b6), from
# `git archive` of it, this jax: models/logistic_ftrl (a narrow rule store)
# and models/dlrm_dcnv2 (a packed rule store with a worker's part)
PARENT_JAX = "0.9.0"
PARENT_TEXT = {
    # (PR 76: `_narrow_pull` gathers with `mode="clip"`, no fill behind ids
    # its callers have bounded; 3373b1a5464e62bc... until then.  The other
    # five trace neither it nor the arm `take` and did not move)
    "lr": "2b520208de72c39478840bae21c6a682016dd412ef3127e60d0c50edb010ea0c",
    "dcn": "7a042098f1a40890e201b67955b5688bdcff4177b88d2f18b4befde0790c0396",
    # PR 72 (the set kernel copies spans; `_push_rule` carries its counts):
    # the stores whose write-back is NOT `tile_set`, at PR 72's parent
    # (90cbcf4): a packed rule store of three rows to a register (DiFacto),
    # a flat wide one (GloVe), a one-register one (PBG's ComplEx) and a
    # packed add store (FM); "lr" and "dcn" above did not move either (off
    # the TPU a narrow rule store's write-back is XLA's row set)
    "difacto": "c579045f24c4f8b1fa76549a721deb641669a84f3e0856e934bb403585e9d731",
    "glove": "bc4031dbf2f9494a63b47637fec09fd106ce751ee14aca266d1151fcba93ad95",
    "kge": "c40ebcf65f60c7dd85bfb16b21bc1b0fe1cd01afd62c76ed141162b0a36151f9",
    "fm": "c071e25106d07d67f60c68c8e51cf28d85d42b90feceb97f2ece726de34116f8",
}


def _lowered(logic, store, batch, **how):
    state = logic.init_state(jax.random.PRNGKey(0))
    return jax.jit(
        make_train_step(logic, store.spec), donate_argnums=(0, 1)
    ).lower(store.table, state, batch).as_text(**how)


def _other_store(which):
    """A small model of a cell whose write-back is not the set kernel's."""
    from flink_parameter_server_tpu.models import difacto as df
    from flink_parameter_server_tpu.models import factorization_machine as fmm
    from flink_parameter_server_tpu.models import glove as gl
    from flink_parameter_server_tpu.models import kge

    ids = np.zeros((32, 6), np.int32)
    clicks = {
        "ids": ids, "values": np.ones(ids.shape, np.float32),
        "feat_mask": np.ones(ids.shape, bool),
        "label": np.ones(32, np.float32), "mask": np.ones(32, bool)}
    if which == "difacto":
        cfg = df.DiFactoConfig(200, 16)
        return df.DiFacto(cfg), df.make_store(cfg), clicks
    if which == "glove":
        model = gl.GloVeConfig(96, 150)
        return gl.GloVe(model), gl.make_store(model, seed=3), {
            "word": np.zeros(64, np.int32), "context": np.zeros(64, np.int32),
            "count": np.ones(64, np.float32), "mask": np.ones(64, bool)}
    if which == "kge":
        graph = kge.KGEConfig(600, 24, 100)
        return kge.ComplExNegatives(graph), kge.make_store(graph, seed=3), {
            k: np.zeros((4, n), np.int32) for k, n in (
                ("source", 10), ("destination", 10), ("relation", 10),
                ("source_negatives", 6), ("destination_negatives", 6))}
    cfg = fmm.FMConfig(num_features=200, dim=16, learning_rate=0.05)
    return fmm.FactorizationMachine(cfg), fmm.make_store(cfg), clicks


@pytest.mark.parametrize(
    "which", ["lr", "dcn", "difacto", "glove", "kge", "fm"])
def test_a_single_store_s_lowered_step_is_the_parent_s_to_the_letter(which):
    if jax.__version__ != PARENT_JAX:
        pytest.skip(f"the pins are jax {PARENT_JAX}'s text")
    if which in ("difacto", "glove", "kge", "fm"):
        text = _lowered(*_other_store(which))
        assert "ps_rule_descriptors" not in text
    elif which == "lr":
        text = _lowered(lf.LogisticFTRL(), lf.make_store(1000), {
            "ids": np.zeros((64, 5), np.int32),
            "values": np.ones((64, 5), np.float32),
            "feat_mask": np.ones((64, 5), bool),
            "label": np.ones(64, np.float32), "mask": np.ones(64, bool)})
    else:
        cfg = dcn.DCNv2Config(
            (50, 7), (2, 1), dense_features=3, dim=8, bottom_mlp=(16, 8),
            cross_layers=1, cross_rank=4, over_mlp=(8, 1))
        text = _lowered(dcn.DLRMDCNv2(cfg), dcn.make_store(cfg), {
            "ids": np.zeros((16, 3), np.int32),
            "dense": np.ones((16, 3), np.float32),
            "label": np.ones(16, np.float32), "mask": np.ones(16, bool)})
    assert "store." not in text
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_TEXT[which]


# -- Wide & Deep ---------------------------------------------------------------
def test_the_step_hashes_its_wide_keys_and_labels_each_store_s_ops():
    logic, stores = wd.WideAndDeep(CONFIG), _stores()
    batch = _batches()[0]
    keys = logic.keys(batch)
    assert keys["deep"] is batch["ids"]
    ids = batch["ids"]
    want = np.arange(4) * 64 + np.asarray(pair_key(
        jnp.asarray(ids), jnp.asarray(np.roll(ids, -1, axis=1)), 64))
    assert np.array_equal(np.asarray(keys["wide"]), want)
    assert (want // 64 == np.arange(4)).all()  # a cross's own buckets
    text = _lowered(logic, stores, batch, debug_info=True)
    assert text.count("ps.cross_hash") > 5
    for phase in ("pull", "push"):
        for store in ("wide", "deep"):
            assert f"ps.{phase}/store.{store}" in text, (phase, store)
    # one program: both tables donated with the state
    assert text.count("tf.aliasing_output") >= 2 + len(
        logic.init_state(jax.random.PRNGKey(0)))


def test_wide_and_deep_takes_one_rule_step_a_distinct_row_in_each_store():
    """Against the rules run by hand on this batch's sums: duplicates within
    a batch (a 3-row field names a row ~5 times; the same example twice names
    a cross twice) take ONE step on their sum, a masked example none."""
    logic, stores = wd.WideAndDeep(CONFIG, seed=3), _stores()
    rng = np.random.default_rng(5)
    batch = _batch(rng, masked=(4,))
    for k in ("ids", "dense", "label"):
        batch[k][1] = batch[k][0]  # an example twice: its crosses twice
    before = _tables(stores)
    state = logic.init_state(jax.random.PRNGKey(0))
    keys = {k: np.asarray(v) for k, v in logic.keys(batch).items()}
    result = transform_batched(
        [batch], logic, stores, initial_state=state, dump_model=False)
    after = _tables(result.store)
    # the gradients, by autodiff of the same loss
    dim, live = CONFIG.dim, batch["mask"]

    def loss(rows, weights, leaves):
        x = jnp.concatenate(
            [rows.reshape(16, -1), jnp.asarray(batch["dense"])], axis=1)
        for i in range(3):
            x = x @ leaves[f"deep{i}_w"] + leaves[f"deep{i}_b"]
            x = jnp.maximum(x, 0.0) if i < 2 else x
        logit = x[:, 0] + weights.sum(axis=1) + leaves["bias"][0]
        y = jnp.asarray(batch["label"])
        bce = jnp.maximum(logit, 0) - logit * y + jnp.log1p(
            jnp.exp(-jnp.abs(logit)))
        return jnp.sum(jnp.where(live, bce, 0.0)) / live.sum()

    leaves = {k: v for k, v in state.items() if not k.endswith("_acc")}
    g_rows, g_w, g_leaves = jax.grad(loss, argnums=(0, 1, 2))(
        jnp.asarray(before["deep"][keys["deep"]][..., :dim]),
        jnp.asarray(before["wide"][keys["wide"]][..., 0]), leaves)
    # deep: AdaGrad once a distinct row, on the sum over the live lanes
    lanes = np.where(live[:, None], keys["deep"], -1).reshape(-1)
    want = before["deep"].copy()
    for row in np.unique(lanes[lanes >= 0]):
        g = np.asarray(g_rows).reshape(-1, dim)[lanes == row].sum(axis=0)
        acc = before["deep"][row, dim:] + g * g
        want[row] = np.concatenate([
            before["deep"][row, :dim] - CONFIG.learning_rate * g / (
                np.sqrt(acc) + CONFIG.eps), acc])
    np.testing.assert_allclose(after["deep"], want, rtol=2e-5, atol=1e-7)
    untouched = np.setdiff1d(np.arange(CONFIG.num_rows), lanes)
    assert np.array_equal(after["deep"][untouched], before["deep"][untouched])
    # wide: FTRL once a distinct bucket, on (sum g, sum g^2)
    lanes = np.where(live[:, None], keys["wide"], -1).reshape(-1)
    assert len(np.unique(lanes[lanes >= 0])) < (lanes >= 0).sum()
    want = before["wide"].copy()
    for row in np.unique(lanes[lanes >= 0]):
        g = np.asarray(g_w).reshape(-1)[lanes == row]
        want[row] = np.asarray(CONFIG.ftrl(
            before["wide"][row], np.array([g.sum(), 0.0, (g * g).sum()])))
    np.testing.assert_allclose(after["wide"], want, rtol=2e-5, atol=1e-7)
    untouched = np.setdiff1d(np.arange(CONFIG.wide_rows), lanes)
    assert np.array_equal(after["wide"][untouched], before["wide"][untouched])
    assert not np.array_equal(after["wide"], before["wide"])
    # the worker: AdaGrad on every leaf and on bias, accumulators from acc0
    for k, g in g_leaves.items():
        acc = CONFIG.acc0 + np.asarray(g) ** 2
        np.testing.assert_allclose(
            np.asarray(result.worker_state[f"{k}_acc"]), acc, rtol=2e-5)
        np.testing.assert_allclose(
            np.asarray(result.worker_state[k]),
            np.asarray(leaves[k]) - CONFIG.learning_rate * np.asarray(g) / (
                np.sqrt(acc) + CONFIG.eps), rtol=2e-4, atol=1e-7)


def test_the_pull_s_distinct_rows_reach_the_push_of_the_hashed_keys(
        monkeypatch):
    """The wide keys are COMPUTED in the step, and the push is handed the
    very array the pull read (``PushRequest.ids`` ``None``): a narrow rule
    store that pulls a batch's distinct rows once (a TPU's arm, steered
    here) leaves them for its rule, as it does for staged keys."""
    batches = _batches(seed=7)
    plain = transform_batched(batches, wd.WideAndDeep(CONFIG), _stores())
    # (`steer_arms` steers EVERY store's pull; here the narrow one's alone)
    import dataclasses

    real = store_mod.arms

    def steered(spec, **lanes):
        arm = real(spec, **lanes)
        if arm.pull == "narrow":
            arm = dataclasses.replace(arm, pull="narrow_distinct")
        return arm

    monkeypatch.setattr(store_mod, "arms", steered)
    shared = transform_batched(batches, wd.WideAndDeep(CONFIG), _stores())
    out = shared.worker_outputs[-1]
    assert "ps_pull_distinct_rows@wide" in out
    assert "ps_pull_distinct_rows@wide" not in plain.worker_outputs[-1]
    # every lane of the last batch live: the rows pulled are the rows ruled
    assert int(out["ps_pull_distinct_rows@wide"]) == int(
        out["ps_rule_rows@wide"])
    for k, v in _tables(plain.store).items():
        assert np.array_equal(v, _tables(shared.store)[k]), k


@pytest.mark.parametrize("steps_per_call", [1, 2])
def test_the_driver_carries_two_tables_and_labels_each_store_s_gauges(
        steps_per_call):
    registry = MetricsRegistry()
    batches = _batches(n=4)
    driver = StreamingDriver(
        wd.WideAndDeep(CONFIG), _stores(), registry=registry,
        config=DriverConfig(dump_model=False, steps_per_call=steps_per_call))
    result = driver.run(iter(batches))
    alone = transform_batched(
        batches, wd.WideAndDeep(CONFIG), _stores(), dump_model=False)
    for k, v in _tables(alone.store).items():
        assert np.array_equal(v, _tables(result.store)[k]), k
    assert driver.store is result.store
    gauges = registry.snapshot()

    def by_store(name):
        return {e["labels"]["store"]: e["value"] for e in gauges[name]}

    assert by_store("store_layout_packed") == {"wide": 0, "deep": 1}
    # the newest dispatch: the last batch (or the last two of a scanned one)
    last = batches[-steps_per_call:]
    logic = wd.WideAndDeep(CONFIG)
    want = {
        name: sum(len(np.unique(np.asarray(logic.keys(b)[name])))
                  for b in last)
        for name in ("wide", "deep")}
    assert by_store("store_rule_rows") == want
    assert by_store("store_rule_keys") == {
        "wide": 64 * steps_per_call, "deep": 64 * steps_per_call}
    assert by_store("store_pull_row_lanes") == {"deep": 8}
    assert "store_pull_row_lanes" in gauges and not [
        e for e in gauges["store_compute_parts"] if "store" not in e["labels"]]


def test_save_kill_resume_round_trips_two_tables_bit_for_bit(tmp_path):
    batches = _batches(n=4)
    config = DriverConfig(dump_model=False, checkpoint_dir=str(tmp_path))
    first = StreamingDriver(wd.WideAndDeep(CONFIG), _stores(), config=config)
    first.run(iter(batches[:2]))  # its close-time save is the checkpoint
    held = _tables(first.store)
    state = jax.tree.map(np.asarray, first._state)
    whole = first.run(iter(batches), fast_forward=False)  # batches 3 and 4
    del first  # "kill": nothing of the first driver is left but its files
    # a new process: fresh stores of ANOTHER seed, then the checkpoint
    second = StreamingDriver(
        wd.WideAndDeep(CONFIG), _stores(seed=11), config=DriverConfig(
            dump_model=False, checkpoint_dir=str(tmp_path / "other")))
    assert not second.resume()  # nothing saved there
    second = StreamingDriver(
        wd.WideAndDeep(CONFIG), _stores(seed=11), config=config)
    # (the first driver saved once more after its second run: step 6)
    assert second.resume() and second.step_idx == 6
    del second
    # the state after TWO batches, from a directory that holds it alone
    two = tmp_path / "two"
    a = StreamingDriver(
        wd.WideAndDeep(CONFIG), _stores(), config=DriverConfig(
            dump_model=False, checkpoint_dir=str(two)))
    a.run(iter(batches[:2]))
    del a
    b = StreamingDriver(
        wd.WideAndDeep(CONFIG), _stores(seed=11), config=DriverConfig(
            dump_model=False, checkpoint_dir=str(two)))
    assert b.resume() and b.step_idx == 2
    assert isinstance(b.store, StoreGroup)
    assert b.store.spec == _stores().spec
    for k, v in held.items():
        assert np.array_equal(v, _tables(b.store)[k]), k
    for k, v in state.items():
        assert np.array_equal(v, np.asarray(b._state[k])), k
    # ... and the stream goes on from the cursor to the same bits
    resumed = b.run(iter(batches))  # skips the two it has consumed
    straight = transform_batched(
        batches, wd.WideAndDeep(CONFIG), _stores(), dump_model=False)
    for k, v in _tables(straight.store).items():
        assert np.array_equal(v, _tables(resumed.store)[k]), k
    assert whole.store.spec == resumed.store.spec


def test_publish_counts_sets_a_group_s_gauges_store_by_store():
    registry = MetricsRegistry()
    outs = {
        "loss": 1.0, "ps_rule_keys@wide": 5, "ps_rule_rows@wide": 4,
        "ps_rule_tiles@wide": 1, "ps_pull_distinct_rows@wide": 4,
        "ps_rule_keys@deep": 7, "ps_rule_rows@deep": 6,
        "ps_rule_tiles@deep": 0, "ps_rule_packed_rows@deep": 3,
    }
    store_mod.publish_counts(outs, registry, float, float)
    snap = registry.snapshot()
    assert {e["labels"]["store"]: e["value"]
            for e in snap["store_rule_rows"]} == {"wide": 4, "deep": 6}
    assert [e["labels"]["store"] for e in snap["store_pull_distinct_rows"]
            ] == ["wide"]
    assert [e["labels"]["store"] for e in snap["store_rule_packed_rows"]
            ] == ["deep"]
    # a single store's outputs keep their unlabelled gauges
    registry = MetricsRegistry()
    store_mod.publish_counts(
        {"ps_rule_keys": 5, "ps_rule_rows": 4, "ps_rule_tiles": 1},
        registry, float, float)
    assert "store" not in registry.snapshot()["store_rule_rows"][0]["labels"]
