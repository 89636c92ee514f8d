"""``w2v-googlenews-300``: its pair stream against the closed-form laws, the
slot a pair leaves alone, the bytes a step must move, its two readers, and
the cell's dry run."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import lint, program_trace, run, spec
from flink_parameter_server_tpu.core.transform import make_train_step

CELL = "w2v-googlenews-300.train-pairs-zipf"
BENCH = spec.load_benchmark()
FULL = spec.resolve(BENCH, CELL, dry_run=False)
DRY = spec.resolve(BENCH, CELL, dry_run=True)
FAM = spec.family("w2v")


@pytest.fixture(scope="module")
def full_laws():
    return FAM.laws(FULL["cfg"], FULL["traffic_spec"]["keys"])


def test_the_configuration_is_the_released_table_uncut():
    cfg = FULL["cfg"]
    assert cfg["reduced"] == [] and cfg["family"] == "w2v"
    for key, value in cfg["source_sizes"].items():
        assert cfg.get(key, value) == value
    assert (cfg["vocab_size"], cfg["dim"], cfg["dtype"]) == (3_000_000, 300, "float32")
    assert cfg["vocab_size"] * 2 * cfg["dim"] * 4 == 7_200_000_000
    assert FAM.hbm_bytes_per_step(cfg) == 412_876_800
    assert len(cfg["source"]) <= 200 and lint.problems(spec.ROOT) == []


@pytest.mark.parametrize("law, ids, want", [
    ("unigram", slice(0, 1), 0.25681),   # the hottest word of the corpus
    ("pairs", slice(0, 1), 0.010382),    # 170 of a batch's 16,384 centres
    ("noise", slice(0, 1), 0.053619),    # 4,392 of its 81,920 negatives
    ("pairs", slice(0, 1000), 0.37139),
    ("noise", slice(0, 1000), 0.43517),
])
def test_the_laws_at_full_size_are_the_closed_form(full_laws, law, ids, want):
    assert full_laws[law].sum() == pytest.approx(1.0, abs=1e-12)
    assert full_laws[law][ids].sum() == pytest.approx(want, rel=2e-4)
    assert (np.diff(full_laws[law]) <= 0).all()  # id = frequency rank - 1


def test_a_step_touches_the_rows_the_traffic_file_says(full_laws):
    batch = FULL["cfg"]["batch"]
    untouched = (1 - full_laws["pairs"]) ** (2 * batch) * (
        1 - full_laws["noise"]
    ) ** (FULL["cfg"]["negatives"] * batch)
    assert (1 - untouched).sum() == pytest.approx(48_619, rel=1e-3)
    pool = 1 - untouched ** FULL["cfg"]["pool_batches"]
    assert pool.sum() == pytest.approx(1_081_488, rel=1e-3)


@pytest.mark.parametrize("keys", [{"kind": "zipf", "a": 1.3}, {"kind": "uniform"}],
                         ids=["zipf", "uniform"])
def test_the_three_streams_follow_their_laws(keys):
    # the dry run's vocabulary, a batch large enough to count the hot words
    cfg = {**DRY["cfg"], "batch": 40_000}
    law = FAM.laws(cfg, keys)
    (b,) = FAM.host_batches(cfg, {"keys": keys}, 2**31 + 11, 1)
    assert b["center"].shape == b["context"].shape == b["mask"].shape == (40_000,)
    assert b["negatives"].shape == (40_000, cfg["negatives"])
    assert b["mask"].all()
    for name, which in (("center", "pairs"), ("context", "pairs"), ("negatives", "noise")):
        ids = b[name].reshape(-1)
        assert ids.dtype == np.int32 and ids.min() >= 0 and ids.max() < cfg["vocab_size"]
        share = np.bincount(ids, minlength=cfg["vocab_size"]) / ids.size
        sigma = np.sqrt(law[which] * (1 - law[which]) / ids.size)
        assert (np.abs(share - law[which]) <= 5 * sigma + 1e-12).all(), name
    if keys["kind"] == "zipf":
        # subsampling flattens the centres, the 3/4 power the negatives less
        assert law["unigram"][0] > law["noise"][0] > law["pairs"][0]
    else:
        assert np.allclose(law["pairs"], 1 / cfg["vocab_size"])


def test_the_stream_is_a_function_of_the_seed_alone():
    cfg, traffic = DRY["cfg"], DRY["traffic_spec"]
    one = FAM.host_batches(cfg, traffic, 2**31 + 3, 3)
    again = FAM.host_batches(cfg, traffic, 2**31 + 3, 2)
    other = FAM.host_batches(cfg, traffic, 2**31 + 4, 2)
    for a, b in zip(one, again):
        assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(one[0]["center"], other[0]["center"])
    assert not np.array_equal(one[0]["center"], one[1]["center"])
    with pytest.raises(ValueError, match="unknown key distribution"):
        FAM.host_batches(cfg, {"keys": {"kind": "pareto"}}, 1, 1)


def test_build_is_one_program_whatever_the_seed_and_make_stores_own():
    from flink_parameter_server_tpu.models.word2vec import IN, OUT, SkipGramNS

    cfg = DRY["cfg"]
    logic, store = FAM.build(cfg, 5, None)
    _, other = FAM.build(cfg, 2**31 + 6, None)
    assert isinstance(logic, SkipGramNS) and logic.dedup_scale
    assert logic.vocab_size == cfg["vocab_size"] and logic.learning_rate == 0.025
    values = np.asarray(store.values())
    assert values.shape == (cfg["vocab_size"], 2, cfg["dim"])
    assert (values[:, OUT] == 0).all()
    bound = 0.5 / cfg["dim"]
    assert (np.abs(values[:, IN]) <= bound).all() and values[:, IN].std() > bound / 3
    assert not np.array_equal(values, np.asarray(other.values()))
    # a (2, dim) row lies flat, padded to whole 128-lane registers
    assert store.spec.layout == "packed"
    assert store.table.shape[1] == -(-2 * cfg["dim"] // 128) * 128


def test_the_slot_a_pair_does_not_address_comes_back_bit_equal():
    from flink_parameter_server_tpu.models.word2vec import IN, OUT

    cfg = {**DRY["cfg"], "vocab_size": 4096}  # most words on one side only
    logic, store = FAM.build(cfg, 7, None)
    # output vectors away from 0, so that a centre's vector moves at once
    store = store.push(
        jnp.arange(4096), jnp.full((4096, 2, cfg["dim"]), 1e-3, jnp.float32)
    )
    batches = FAM.host_batches(cfg, {"keys": {"kind": "uniform"}}, 9, 2)
    before = np.asarray(store.values())
    step = jax.jit(make_train_step(logic, store.spec))
    table, state = store.table, logic.init_state(jax.random.PRNGKey(0))
    for b in batches:
        table, state, _ = step(table, state, b)
    after = np.asarray(type(store)(store.spec, table).values())
    centres = np.unique(np.concatenate([b["center"] for b in batches]))
    outs = np.unique(np.concatenate(
        [np.concatenate([b["context"], b["negatives"].reshape(-1)]) for b in batches]
    ))
    not_centre = np.setdiff1d(np.arange(4096), centres)
    not_out = np.setdiff1d(np.arange(4096), outs)
    assert len(not_centre) > 100 and len(not_out) > 100
    assert np.array_equal(after[not_centre, IN], before[not_centre, IN])
    assert np.array_equal(after[not_out, OUT], before[not_out, OUT])
    assert (after[centres, IN] != before[centres, IN]).any(axis=1).all()
    assert (after[outs, OUT] != before[outs, OUT]).any(axis=1).all()


def test_rows_and_touched_name_the_two_slots_apart():
    cfg = DRY["cfg"]
    ref = spec.reference(cfg)
    _, store = FAM.build(cfg, 3, None)
    batches = FAM.host_batches(cfg, DRY["traffic_spec"], 3, 2)
    ids = ref.touched(batches)
    assert set(ids) == {"in", "out"}
    assert ids["in"].shape == (2 * cfg["batch"],)
    assert ids["out"].shape == (2 * cfg["batch"] * (cfg["negatives"] + 1),)
    got = FAM.rows(store, (), ids)
    values = np.asarray(store.values())
    assert np.array_equal(got["in"], values[ids["in"], 0])
    assert np.array_equal(got["out"], values[ids["out"], 1])


def _per_lane_mean(cfg, rows, batch):
    """float64, lane by lane: every pulled row's (2, dim) delta (zeros in
    the slot the lane leaves), then the mean per word over ALL its lanes."""
    lr, k = cfg["learning_rate"], cfg["negatives"]
    total = np.zeros_like(rows, dtype=np.float64)
    lanes = np.zeros(len(rows))
    for c, o, negs in zip(batch["center"], batch["context"], batch["negatives"]):
        v, outs = rows[c, 0].astype(np.float64), [o, *negs]
        g = np.array([1 / (1 + np.exp(-v @ rows[w, 1])) for w in outs])
        g[0] -= 1
        total[c, 0] -= lr * sum(gj * rows[w, 1] for gj, w in zip(g, outs))
        lanes[c] += 1
        for gj, w in zip(g, outs):
            total[w, 1] -= lr * gj * v
            lanes[w] += 1
    assert lanes.sum() == len(batch["center"]) * (k + 2)
    return rows + total / np.maximum(lanes, 1)[:, None, None]


def test_the_reference_gives_a_word_the_mean_of_its_lanes_deltas():
    cfg = {**DRY["cfg"], "vocab_size": 24, "batch": 64, "dim": 8}
    rng = np.random.default_rng(5)
    rows = rng.normal(size=(24, 2, 8)).astype(np.float32) / 4
    (batch,) = FAM.host_batches(cfg, {"keys": {"kind": "zipf", "a": 1.3}}, 8, 1)
    ref = spec.reference(cfg)
    ids = ref.touched([batch])
    before = {"in": rows[ids["in"], 0], "out": rows[ids["out"], 1]}
    want, moved = ref.apply(cfg, before, ids, [batch])
    by_lane = _per_lane_mean(cfg, rows, batch)
    assert np.allclose(want["in"], by_lane[ids["in"], 0], rtol=0, atol=2e-6)
    assert np.allclose(want["out"], by_lane[ids["out"], 1], rtol=0, atol=2e-6)
    # the hottest word is named many times and still takes one step of its
    # mean delta, far under the sum of them
    hot = np.bincount(np.concatenate([batch["context"], batch["negatives"].reshape(-1)])).argmax()
    at = np.searchsorted(ids["out"], hot)
    assert np.abs(want["out"][at] - before["out"][at]).max() < moved["out"][at].max()
    assert (moved["in"] >= np.abs(want["in"] - before["in"]) - 1e-7).all()


def test_summed_deltas_fail_the_check():
    # the combiner is part of the result: the same program with the deltas
    # of a word's lanes SUMMED (dedup_scale=False) is not this deployment
    from flink_parameter_server_tpu.models.word2vec import SkipGramNS

    cfg = DRY["cfg"]
    ref = spec.reference(cfg)
    _, store = FAM.build(cfg, 6, None)
    batches = FAM.host_batches(cfg, DRY["traffic_spec"], 6, cfg["reference"]["batches"])
    ids = ref.touched(batches)
    before = FAM.rows(store, (), ids)
    step = jax.jit(make_train_step(SkipGramNS(cfg["learning_rate"]), store.spec))
    table = store.table
    for b in batches:
        table, _, _ = step(table, (), b)
    got = FAM.rows(type(store)(store.spec, table), (), ids)
    failures, worst = run._check_rows(
        cfg["reference"], ref.apply(cfg, before, ids, batches), got, before
    )
    assert len(failures) == 2 and worst["share"] > 100


def _ctx(**over):
    return {
        "cfg": FULL["cfg"], "traffic": FULL["traffic_spec"], "chips": 1,
        "trace": None, "peaks": None, "spans": [],
        "counters": {"peak_hbm_bytes": 0}, **over,
    }


def test_peak_over_table_counts_tables():
    reader = spec.metric_reader("store.peak_over_table")
    assert reader.__doc__ and reader.read(_ctx()) is None
    one = reader.read(_ctx(counters={"peak_hbm_bytes": 8_400_000_000}))
    assert one == pytest.approx(8.4 / 7.2)
    two = reader.read(_ctx(counters={"peak_hbm_bytes": 7_680_000_000 * 2}))
    assert one < 1.95 < 2.13 <= two
    # a configuration that states no such table reports nothing
    mf = spec.resolve(BENCH, "mf-hugewiki-k128.train-zipf", dry_run=False)["cfg"]
    assert reader.read(_ctx(cfg=mf, counters={"peak_hbm_bytes": 5e9})) is None


def test_delta_build_reads_its_scope_and_nothing_without_it(monkeypatch):
    reader = spec.metric_reader("step.delta_build_device_ms")
    assert reader.__doc__ and reader.read(_ctx()) is None
    where = os.path.join(run.OUT_DIR, "trace", CELL)
    reduced = {"scope_ms": {"ps.pull": 5.0, "ps.delta_build": 2.5, "ps.compute": 1.0}}
    monkeypatch.setitem(program_trace._RUNS, where, reduced)
    traced = _ctx(trace={"step_device_ms": 20.0})
    assert reader.read(traced) == pytest.approx(2.5)
    assert spec.metric_reader("step.compute_device_ms").read(traced) == pytest.approx(1.0)
    # the parent's program has no such scope: the line leaves the metric out
    monkeypatch.setitem(program_trace._RUNS, where, {"scope_ms": {"ps.pull": 5.0}})
    assert reader.read(traced) is None


def test_the_scope_is_in_the_lowered_step():
    logic, store = FAM.build(DRY["cfg"], 1, None)
    (b,) = FAM.host_batches(DRY["cfg"], DRY["traffic_spec"], 1, 1)
    text = jax.jit(make_train_step(logic, store.spec)).lower(
        store.table, (), b
    ).as_text(debug_info=True)
    assert program_trace.SCOPE.findall("jit(step)/ps.compute/ps.delta_build/scatter")[-1] == "ps.delta_build"
    assert "ps.compute/ps.delta_build" in text


def test_the_cells_entries_and_its_dry_run():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and BENCH["workloads"][-1] == cell
    per_layer = {m["name"] for m in spec.metrics_of(BENCH, "per_layer", CELL)}
    assert {"step.delta_build_device_ms", "store.peak_over_table",
            "store.gather_scatter_roofline", "step.device_ms"} <= per_layer
    assert "step.state_update_device_ms" not in per_layer
    assert {m["name"] for m in spec.metrics_of(BENCH, "end_to_end", CELL)} == {
        "updates_per_s_chip", "setup_s",
    }
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.ROOT)
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL, "--seed",
         str(2**31 + 12), "--seconds", "0.5", "--trace", "1", "--cpu-dry-run"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] and last["failed"] == 0 and last["failures"] == []
    assert "metrics" not in last and "driver.dispatch_ms" in last["metric_names"]
