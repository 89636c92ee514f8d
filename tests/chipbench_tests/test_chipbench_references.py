"""Both plain references against ``models/`` at tiny sizes on the CPU: the
same batches from the same rows give the same rows."""
import os

import jax
import numpy as np
import pytest

from chipbench import run, spec
from flink_parameter_server_tpu.core.transform import make_train_step

BENCH = spec.load_benchmark()
KEYS = [{"kind": "zipf", "a": 1.2}, {"kind": "uniform"}]


def _train(cfg, traffic, seed, n):
    fam, ref = spec.family(cfg["family"]), spec.reference(cfg)
    logic, store = fam.build(cfg, seed, None)
    batches = fam.host_batches(cfg, traffic, seed, n)
    ids = ref.touched(batches)
    state = logic.init_state(jax.random.PRNGKey(0))
    before = fam.rows(store, state, ids)
    step = jax.jit(make_train_step(logic, store.spec))
    table = store.table
    for b in batches:
        table, state, _ = step(table, state, b)
    after = fam.rows(type(store)(store.spec, table), state, ids)
    return ref.apply(cfg, before, ids, batches), after, before


def _cfg(config):
    cell = next(w for w in BENCH["workloads"] if w["config"] == config)
    return spec.resolve(BENCH, cell["name"], dry_run=True)["cfg"]


@pytest.mark.parametrize("keys", KEYS, ids=["zipf", "uniform"])
@pytest.mark.parametrize(
    "config", [c["name"] for c in BENCH["configs"]]
)
def test_reference_matches_the_model(config, keys):
    cfg = _cfg(config)
    want, got, before = _train(cfg, {"keys": keys}, seed=2**31 + 5, n=3)
    failures, worst = run._check_rows(cfg["reference"], want, got, before)
    assert failures == [] and 0.0 < worst["share"] <= 1.0


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_a_bfloat16_delta_fails_the_check(config):
    # the system's own rows pass; the same rows with every CHANGE rounded to
    # bfloat16 (what a bfloat16 delta or accumulator would leave) do not
    import jax.numpy as jnp

    cfg = _cfg(config)
    want, got, before = _train(cfg, {"keys": KEYS[0]}, seed=1, n=3)
    assert run._check_rows(cfg["reference"], want, got, before)[0] == []
    coarse = {
        name: before[name] + np.asarray(
            jnp.asarray(got[name] - before[name]).astype(jnp.bfloat16),
            np.float32,
        )
        for name in got
    }
    failures, worst = run._check_rows(cfg["reference"], want, coarse, before)
    assert len(failures) == len(want[0]) and worst["share"] > 3.0


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_most_touched_elements_move_past_their_allowance(config):
    # the check has teeth only where the reference's change is well above
    # what it lets pass: true of nearly every element the batches touch
    cfg = _cfg(config)
    check = cfg["reference"]
    (want, moved), _, before = _train(
        cfg, {"keys": KEYS[0]}, seed=3, n=int(check["batches"])
    )
    ulp = check["row_ulps"] * float(np.finfo(np.float32).eps)
    for name in want:
        net = np.abs(want[name] - before[name])
        allowed = check["delta_rtol"] * moved[name] + check["delta_atol"] + ulp * np.maximum(
            np.abs(want[name]), np.abs(before[name])
        )
        assert np.median(net / allowed) > 20 and np.mean(net > 5 * allowed) > 0.8, name


def test_a_dropped_update_and_a_stale_read_fail_the_check():
    cfg = _cfg(BENCH["configs"][0]["name"])
    want, got, before = _train(cfg, {"keys": KEYS[0]}, seed=4, n=2)
    assert run._check_rows(cfg["reference"], want, got, before)[0] == []
    for name in got:
        dropped = {**got, name: got[name].copy()}
        row = int(np.argmax(np.abs(got[name] - before[name]).sum(axis=1)))
        dropped[name][row] = before[name][row]
        assert run._check_rows(cfg["reference"], want, dropped, before)[0]
    assert len(run._check_rows(cfg["reference"], want, before, before)[0]) >= 2 * len(got)


def test_click_records_keep_every_field_to_its_own_rows():
    cfg = _cfg(next(c["name"] for c in BENCH["configs"] if "fm" in c["name"]))
    full = spec.load_json(os.path.join(
        spec.ROOT, next(c["file"] for c in BENCH["configs"] if "fm" in c["name"])
    ))
    for c in (cfg, full):
        assert c["num_features"] == c["dense_fields"] + sum(c["field_cardinalities"])
        assert c["fields"] == c["dense_fields"] + len(c["field_cardinalities"])
    assert full["field_cardinalities"] == full["source_sizes"]["field_cardinalities"]
    fam = spec.family("fm")
    (b,) = fam.host_batches(cfg, {"keys": {"kind": "uniform"}}, 2**31 + 7, 1)
    dense, cards = cfg["dense_fields"], np.asarray(cfg["field_cardinalities"])
    first = dense + np.concatenate([[0], np.cumsum(cards)[:-1]])
    assert (b["ids"][:, :dense] == np.arange(dense)).all()
    assert (b["ids"][:, dense:] >= first).all()
    assert (b["ids"][:, dense:] < first + cards).all()
    assert (b["values"][:, dense:] == 1.0).all()
    assert ((b["values"][:, :dense] >= 0) & (b["values"][:, :dense] < 1)).all()
    assert set(np.unique(b["label"])) == {-1.0, 1.0}
    assert b["ids"].dtype == np.int32 and b["ids"].max() < cfg["num_features"]


def test_topk_reference_accepts_the_top_and_rejects_the_rest():
    ref = spec.reference(_cfg(BENCH["configs"][0]["name"]))
    rng = np.random.default_rng(0)
    table = rng.normal(size=(200, 16)).astype(np.float32)
    vec = rng.normal(size=16).astype(np.float32)
    scores = table @ vec
    ids = np.argsort(-scores)[:10]
    assert ref.topk_holds(vec, table, ids, scores[ids], rtol=1e-5, atol=1e-6)
    worse = ids.copy()
    worse[-1] = np.argsort(-scores)[50]
    assert not ref.topk_holds(vec, table, worse, scores[worse], rtol=1e-5, atol=1e-6)
    assert not ref.topk_holds(vec, table, ids, scores[ids] + 1.0, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("family", ["mf", "fm"])
def test_touched_rows_have_one_shape_whatever_the_seed(family):
    # a shape that moved with the seed would compile inside every new run
    cell = next(
        w for w in BENCH["workloads"]
        if spec.resolve(BENCH, w["name"], dry_run=True)["cfg"]["family"] == family
    )
    cfg = spec.resolve(BENCH, cell["name"], dry_run=True)["cfg"]
    fam, ref = spec.family(family), spec.reference(cfg)
    shapes = set()
    for seed in (1, 2, 2**31 + 3):
        ids = ref.touched(fam.host_batches(cfg, {"keys": KEYS[0]}, seed, 2))
        for name, arr in ids.items():
            assert (np.diff(arr) >= 0).all()
            shapes.add((name, arr.shape))
    assert len(shapes) == len(ids)
