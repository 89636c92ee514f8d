"""``ft-wiki-en-300``: the configuration's sizes, its vocabulary and bags from
the seed, the traffic's pinned numbers, the bytes a step must move, the
reference check's teeth, the two readers, and the cell's dry run."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import lint, program_trace, run, spec
from flink_parameter_server_tpu.core.batched import PushRequest
from flink_parameter_server_tpu.core.transform import make_train_step
from flink_parameter_server_tpu.models import fasttext as ftm

CELL = "ft-wiki-en-300.train-pairs-zipf"
BENCH = spec.load_benchmark()
FULL = spec.resolve(BENCH, CELL, dry_run=False)
DRY = spec.resolve(BENCH, CELL, dry_run=True)
FAM = spec.family("ft")


def _named(entries, name):
    (entry,) = [e for e in entries if e["name"] == name]
    return entry


def test_the_configuration_is_the_released_model_uncut():
    cfg = FULL["cfg"]
    assert cfg["reduced"] == [] and cfg["family"] == "ft"
    for key, value in cfg["source_sizes"].items():
        assert cfg.get(key, value) == value
    assert (cfg["vocab_size"], cfg["buckets"], cfg["dim"], cfg["dtype"]) == (
        2_519_370, 2_000_000, 300, "float32")
    assert (cfg["minn"], cfg["maxn"], cfg["negatives"]) == (3, 6, 5)
    assert (cfg["noise_power"], cfg["subsample_t"], cfg["learning_rate"]) == (
        0.5, 1e-4, 0.05)
    rows = 2 * cfg["vocab_size"] + cfg["buckets"]
    assert rows == 7_038_740 and rows * cfg["dim"] * 4 == 8_446_488_000
    # on the chip: 300 lanes flat in three registers, rows aligned to 8
    assert -(-rows // 8) * 8 * 384 * 4 == 10_811_510_784
    assert cfg["batch"] == 4096 and FAM._max_bag(cfg) == 51
    assert len(cfg["letter_freq"]) == 26
    assert sum(cfg["letter_freq"].values()) == pytest.approx(100, abs=0.1)
    for key in ("source_sizes", "vocabulary", "batch", "combiner", "bag_in_the_stream"):
        assert cfg["assumed"][key]
    assert any("dead lane" in g for g in cfg["guarantees"])
    assert len(cfg["source"]) <= 200 and lint.problems(spec.ROOT) == []


def test_the_entries_are_found_by_name_and_are_the_issues():
    entry = _named(BENCH["configs"], "ft-wiki-en-300")
    assert entry["file"] == "chipbench/configs/ft-wiki-en-300.json"
    assert entry["reduced"] == [] and entry["source"] == FULL["cfg"]["source"]
    cell = _named(BENCH["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ft-wiki-en-300", "train-pairs-zipf", 1)
    # cell 5's traffic file, letter for letter: one file, two cells
    w2v = _named(BENCH["workloads"], "w2v-googlenews-300.train-pairs-zipf")
    assert w2v["traffic"] == cell["traffic"]
    for name, source in (("step.bag_pool_device_ms", "device_trace"),
                         ("step.bag_live_share", "program_counter")):
        m = _named(BENCH["per_layer"], name)
        assert m["workloads"] == [CELL] and m["source"] == source
        assert (m["layer"], m["moves"]) == ("worker step", "updates_per_s_chip")
        assert spec.metric_reader(name).__doc__
    per_layer = {m["name"] for m in spec.metrics_of(BENCH, "per_layer", CELL)}
    assert {"step.bag_pool_device_ms", "step.bag_live_share", "step.device_ms",
            "store.gather_scatter_roofline", "store.pull_device_ms",
            "store.push_device_ms", "step.compute_device_ms"} <= per_layer
    # lists of other cells' names are theirs until a benchmark PR appends
    assert not {"step.delta_build_device_ms", "store.peak_over_table",
                "setup.kernel_import_s"} & per_layer
    assert {m["name"] for m in spec.metrics_of(BENCH, "end_to_end", CELL)} == {
        "updates_per_s_chip", "setup_s",
    }


@pytest.fixture(scope="module")
def full_pool():
    """Eight batches of the full-size stream at a fixed seed (~2 s)."""
    return FAM.host_batches(FULL["cfg"], FULL["traffic_spec"], 77, 8)


def _keys(b):
    return np.concatenate([b["bag"], b["context"][:, None], b["negatives"]], axis=1)


def _fnv1a(text: bytes) -> int:
    """The plain hash, the test's own: fastText's ``Dictionary::hash``."""
    h = 2166136261
    for byte in text:
        h = ((h ^ byte) * 16777619) & 0xFFFFFFFF
    return h


def _plain_bag(word_id, spelt: str, vocab, buckets, minn=3, maxn=6):
    """A word's bag letter by letter: fastText's ``computeSubwords`` order
    (by start, then by length), duplicates kept."""
    text = "<" + spelt + ">"
    return [word_id] + [
        vocab + _fnv1a(text[s:s + n].encode()) % buckets
        for s in range(len(text)) for n in range(max(minn, 1), maxn + 1)
        if s + n <= len(text)
    ]


@pytest.mark.parametrize("text,want", [
    (b"", 2166136261), (b"a", 0xE40C292C), (b"foobar", 0xBF9CF968),
])
def test_fnv1a_test_vectors(text, want):
    """The published vectors, on the test's plain hash and on the
    generator's vectorised one."""
    assert _fnv1a(text) == want
    chars = np.frombuffer(text, np.uint8).reshape(1, -1)
    hashed = FAM.fnv1a(np.repeat(chars, 3, axis=0))
    assert hashed.dtype == np.uint32 and hashed.tolist() == [want] * 3


def _bag_of(word: str, vocab=1000, buckets=2_000_000, minn=3, maxn=6, width=14):
    letters = np.zeros((1, width), np.uint8)
    letters[0, :len(word)] = np.frombuffer(word.encode(), np.uint8)
    return FAM.subword_bags(
        np.array([7]), letters, np.array([len(word)]), vocab_size=vocab,
        buckets=buckets, minn=minn, maxn=maxn,
    )[0]


def test_a_hand_counted_bag():
    """``<where>`` has 5 + 4 + 3 + 2 = 14 n-grams of 3 to 6 characters, by
    start and then by length, each at its FNV-1a bucket past the words."""
    bag = _bag_of("where")
    assert bag.shape == (51,) and bag.dtype == np.int32
    assert FAM.max_bag(14, 3, 6) == 51
    grams = [
        "<where>"[s:s + n] for s in range(7) for n in range(3, 7) if s + n <= 7
    ]
    assert len(grams) == 14 and grams[:5] == ["<wh", "<whe", "<wher", "<where", "whe"]
    want = [7] + [1000 + _fnv1a(g.encode()) % 2_000_000 for g in grams]
    assert want == _plain_bag(7, "where", 1000, 2_000_000)
    assert bag[:15].tolist() == want and (bag[15:] == -1).all()


@pytest.mark.parametrize("word,grams", [
    ("a", 1), ("of", 3), ("abcdefghijklmn", 50), ("aaaa", 10),
])
def test_bag_sizes_and_kept_duplicates(word, grams):
    bag = _bag_of(word)
    assert (bag >= 0).sum() == 1 + grams
    assert (bag[:1 + grams] >= 0).all()  # the live lanes come first
    assert bag[:1 + grams].tolist() == _plain_bag(7, word, 1000, 2_000_000)
    if word == "aaaa":  # "aaa" twice: fastText keeps both
        live = bag[1:1 + grams]
        assert len(set(live.tolist())) == grams - 1


def test_no_ngrams_leave_the_word_alone():
    assert _bag_of("where", maxn=0).tolist() == [7]


def test_the_full_size_bags_are_fasttexts_by_a_plain_hash(full_pool):
    """The stream's bags at the published sizes, re-derived letter by
    letter with the test's own hash from the seed's spellings: every pair
    of the first batch and a sample of the rest (what the program is fed
    is what fastText's dictionary would feed it)."""
    cfg = FULL["cfg"]
    V, K = cfg["vocab_size"], cfg["buckets"]
    letters, lengths = FAM.vocabulary(cfg, 77)
    rows = [full_pool[0]["bag"]] + [b["bag"][::64] for b in full_pool[1:]]
    seen = set()
    for bag in np.concatenate(rows):
        word = int(bag[0])
        if word in seen:
            continue
        seen.add(word)
        spelt = bytes(letters[word, :lengths[word]]).decode()
        want = _plain_bag(word, spelt, V, K, cfg["minn"], cfg["maxn"])
        assert bag[:len(want)].tolist() == want and (bag[len(want):] == -1).all()
    assert len(seen) > 1500


def test_the_traffic_at_full_size_is_what_the_configuration_states(full_pool):
    cfg = FULL["cfg"]
    V, K = cfg["vocab_size"], cfg["buckets"]
    for b in full_pool:
        assert b["bag"].shape == (4096, 51) and b["bag"].dtype == np.int32
        assert b["context"].dtype == b["negatives"].dtype == np.int32
        assert ((b["bag"][:, 0] >= 0) & (b["bag"][:, 0] < V)).all()
        grams = b["bag"][:, 1:]
        assert ((grams == -1) | ((grams >= V) & (grams < V + K))).all()
        for name in ("context", "negatives"):
            assert (b[name] >= V + K).all() and (b[name] < 2 * V + K).all()
        live = b["bag"] >= 0  # the live lanes first, then the dead
        assert (live[:, :-1] >= live[:, 1:]).all()
    bag_sizes = np.concatenate([(b["bag"] >= 0).sum(axis=1) for b in full_pool])
    assert bag_sizes.min() >= 2 and bag_sizes.max() <= 51
    assert bag_sizes.mean() - 1 == pytest.approx(23.97, rel=0.02)
    assert np.median(bag_sizes) - 1 == pytest.approx(22, abs=1)
    keys = _keys(full_pool[0])
    assert keys.shape == (4096, 57) and keys.size == 233_472
    live = keys[keys >= 0]
    assert live.size / keys.size == pytest.approx(0.54, abs=0.01)
    rows, counts = np.unique(live, return_counts=True)
    assert rows.size == pytest.approx(62_500, rel=0.03)
    assert 80 <= counts.max() <= 200  # the most-named row of a batch
    centres = np.concatenate([b["bag"][:, 0] for b in full_pool])
    assert (centres == 0).mean() == pytest.approx(0.0195, rel=0.15)  # the hottest word
    buckets = np.concatenate([b["bag"][:, 1:].ravel() for b in full_pool[:1]])
    buckets = buckets[buckets >= 0]
    assert buckets.size == pytest.approx(98_000, rel=0.03)
    _, per_bucket = np.unique(buckets, return_counts=True)
    assert per_bucket.size == pytest.approx(39_000, rel=0.04)
    assert 60 <= per_bucket.max() <= 160  # the hottest bucket of a batch


def test_the_bytes_a_step_must_move_are_the_live_keys(full_pool):
    cfg = FULL["cfg"]
    # the expected live keys a pair: the word, its n-grams (the length's
    # law in closed form), the context, the negatives
    from scipy.stats import poisson

    lens = np.minimum(1 + np.arange(200), cfg["max_word_len"])
    grams = sum(np.maximum(0, lens + 2 - n + 1) for n in range(3, 7))
    expected = 1 + (poisson.pmf(np.arange(200), cfg["word_len_poisson"]) * grams).sum() + 6
    assert cfg["live_keys_per_pair"] == pytest.approx(expected, abs=0.005)
    drawn = np.mean([(_keys(b) >= 0).sum() / 4096 for b in full_pool])
    assert drawn == pytest.approx(cfg["live_keys_per_pair"], rel=0.02)
    assert FAM.hbm_bytes_per_step(cfg) == pytest.approx(
        3 * 4096 * cfg["live_keys_per_pair"] * 300 * 4)
    assert 0.53 < cfg["live_keys_per_pair"] / 57 < 0.55


def test_the_vocabulary_and_the_stream_are_functions_of_the_seed_alone():
    cfg, traffic = DRY["cfg"], DRY["traffic_spec"]
    letters, lengths = FAM.vocabulary(cfg, 2**31 + 3)
    again = FAM.vocabulary(cfg, 2**31 + 3)
    assert letters.shape == (512, 14) and letters.dtype == np.uint8
    assert np.array_equal(letters, again[0]) and np.array_equal(lengths, again[1])
    assert lengths.min() >= 1 and lengths.max() <= 14
    assert ((letters >= ord("a")) & (letters <= ord("z"))).all()
    assert not np.array_equal(letters, FAM.vocabulary(cfg, 2**31 + 4)[0])
    one = FAM.host_batches(cfg, traffic, 2**31 + 3, 3)
    same = FAM.host_batches(cfg, traffic, 2**31 + 3, 2)
    for a, b in zip(one, same):  # a word's bag does not depend on the pool
        assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(one[0]["bag"], one[1]["bag"])
    # a bag is its word's, hashed by fastText's own function
    word = int(one[0]["bag"][0, 0])
    spelt = bytes(letters[word, :lengths[word]]).decode()
    want = _plain_bag(word, spelt, 512, 256)
    assert one[0]["bag"][0, :len(want)].tolist() == want
    with pytest.raises(ValueError, match="unknown key distribution"):
        FAM.host_batches(cfg, {"keys": {"kind": "pareto"}}, 1, 1)


def test_the_letters_follow_the_files_frequencies():
    cfg = {**DRY["cfg"], "vocab_size": 40_000}
    letters, lengths = FAM.vocabulary(cfg, 5)
    share = np.bincount(letters.ravel(), minlength=128)[ord("a"):ord("z") + 1] / letters.size
    want = np.array([cfg["letter_freq"][chr(c)] for c in range(ord("a"), ord("z") + 1)])
    assert np.abs(share - want / want.sum()).max() < 2e-3
    assert lengths.mean() == pytest.approx(7.49, abs=0.05)


def test_build_is_one_program_whatever_the_seed_and_make_stores_own():
    cfg = DRY["cfg"]
    logic, store = FAM.build(cfg, 5, None)
    _, other = FAM.build(cfg, 2**31 + 6, None)
    assert isinstance(logic, ftm.FastTextSkipGram)
    assert (logic.vocab_size, logic.buckets, logic.max_bag) == (512, 256, 51)
    assert logic.learning_rate == 0.05 and logic.capacity == store.spec.capacity
    values = np.asarray(store.values())
    assert values.shape == (2 * 512 + 256, cfg["dim"])
    assert (values[512 + 256:] == 0).all()
    assert (np.abs(values[:768]) <= 1 / cfg["dim"]).all()
    assert not np.array_equal(values, np.asarray(other.values()))
    assert store.spec.layout == "packed" and store.table.shape[1] == 384


def _checked(logic, seed):
    """The configuration's check at the dry-run size on a seeded store."""
    cfg = DRY["cfg"]
    ref = spec.reference(cfg)
    _, store = FAM.build(cfg, seed, None)
    batches = FAM.host_batches(
        cfg, DRY["traffic_spec"], seed, cfg["reference"]["batches"])
    ids = ref.touched(batches)
    before = FAM.rows(store, (), ids)
    step = jax.jit(make_train_step(logic, store.spec))
    table = store.table
    for b in batches:
        table, _, _ = step(table, (), b)
    got = FAM.rows(type(store)(store.spec, table), (), ids)
    return run._check_rows(
        cfg["reference"], ref.apply(cfg, before, ids, batches), got, before
    )


class _Bf16Deltas(ftm.FastTextSkipGram):
    def step(self, state, batch, pulled):
        state, req, out = super().step(state, batch, pulled)
        rounded = jax.lax.reduce_precision(req.deltas, 8, 7)
        return state, PushRequest(req.ids, rounded, req.mask), out


class _Summed(ftm.FastTextSkipGram):
    def step(self, state, batch, pulled, _scale=ftm.occurrence_scale):
        ftm.occurrence_scale = lambda keys, capacity: jnp.ones(keys.shape)
        try:
            return super().step(state, batch, pulled)
        finally:
            ftm.occurrence_scale = _scale


@pytest.mark.parametrize("seed", [6, 2**31 + 7])
def test_the_check_passes_the_program_and_has_teeth(seed):
    logic, _ = FAM.build(DRY["cfg"], seed, None)
    args = (logic.learning_rate, logic.vocab_size, logic.buckets, logic.max_bag)
    failures, worst = _checked(logic, seed)
    assert failures == [] and worst["share"] < 0.5
    # every change rounded to bfloat16 is a different result
    failures, worst = _checked(_Bf16Deltas(*args), seed)
    assert len(failures) == 2 and worst["share"] > 10
    # the combiner is part of the result: the deltas of a row's lanes SUMMED
    failures, worst = _checked(_Summed(*args), seed)
    assert len(failures) == 2 and worst["share"] > 100


def _ctx(**over):
    return {
        "cfg": FULL["cfg"], "traffic": FULL["traffic_spec"], "chips": 1,
        "trace": None, "peaks": None, "spans": [],
        "counters": {"peak_hbm_bytes": 0}, **over,
    }


def test_bag_pool_reads_its_scope_and_nothing_without_it(monkeypatch):
    reader = spec.metric_reader("step.bag_pool_device_ms")
    assert reader.read(_ctx()) is None
    where = os.path.join(run.OUT_DIR, "trace", CELL)
    reduced = {"scope_ms": {"ps.pull": 4.0, "ps.bag_pool": 0.6, "ps.compute": 0.3}}
    monkeypatch.setitem(program_trace._RUNS, where, reduced)
    traced = _ctx(trace={"step_device_ms": 17.0})
    assert reader.read(traced) == pytest.approx(0.6)
    assert spec.metric_reader("step.compute_device_ms").read(traced) == pytest.approx(0.3)
    assert program_trace.SCOPE.findall(
        "jit(step)/ps.compute/ps.bag_pool/reduce_sum")[-1] == "ps.bag_pool"
    # the parent's program has no such scope: the line leaves the metric out
    monkeypatch.setitem(program_trace._RUNS, where, {"scope_ms": {"ps.pull": 4.0}})
    assert reader.read(traced) is None


def test_bag_live_share_reads_the_gauges_and_nothing_without_them():
    from flink_parameter_server_tpu.telemetry import registry as registry_mod

    reader = spec.metric_reader("step.bag_live_share")
    real = registry_mod.get_registry
    fresh = registry_mod.MetricsRegistry()
    registry_mod.get_registry = lambda: fresh
    try:
        assert reader.read(_ctx()) is None  # the parent, every other logic
        fresh.gauge("bag_live_keys", component="train").set(126_449.0)
        fresh.gauge("bag_padded_keys", component="train").set(233_472.0)
        assert reader.read(_ctx()) == pytest.approx(54.16, abs=0.01)
    finally:
        registry_mod.get_registry = real


def test_the_cells_dry_run():
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
    assert "metrics" not in last
    assert {"step.bag_live_share", "driver.dispatch_ms"} <= set(last["metric_names"])
    info = json.loads(
        [ln for ln in done.stderr.splitlines() if ln.startswith("[chipbench] {")][-1]
        [len("[chipbench] "):]
    )
    assert 50 < info["metrics"]["step.bag_live_share"] < 58
