"""``mf-hugewiki-k128.train-uniform``: cell 1 under uniform item keys, the
control PERF.md section 7 row 1 asked for.  Files only: a traffic file and a
``workloads`` entry; configuration, family, reference and readers are cell
1's."""
import json
import os
import subprocess
import sys

import numpy as np

from chipbench import lint, run, spec

CELL = "mf-hugewiki-k128.train-uniform"
ZIPF = "mf-hugewiki-k128.train-zipf"
BENCH = spec.load_benchmark()
FULL = spec.resolve(BENCH, CELL, dry_run=False)
DRY = spec.resolve(BENCH, CELL, dry_run=True)
FAM = spec.family("mf")


def test_the_traffic_is_cell_1s_with_uniform_item_keys_and_nothing_else():
    mine, theirs = FULL["traffic_spec"], spec.resolve(BENCH, ZIPF, dry_run=False)["traffic_spec"]
    assert mine["name"] == "train-uniform" and mine["keys"] == {"kind": "uniform"}
    differ = {k for k in mine if mine[k] != theirs.get(k)}
    assert differ == {"name", "keys", "keys_source"} and set(mine) == set(theirs)
    assert "control" in mine["keys_source"] and "PERF.md section 7" in mine["keys_source"]
    assert "queries" not in mine
    # the configuration is cell 1's own file, untouched
    assert FULL["cfg"] == spec.resolve(BENCH, ZIPF, dry_run=False)["cfg"]
    assert (FULL["cfg"]["num_items"], FULL["cfg"]["batch"]) == (39_780, 65_536)


def test_items_are_uniform_and_users_and_shapes_are_cell_1s():
    cfg, seed = FULL["cfg"], 2**31 + 9
    (flat,) = FAM.host_batches(cfg, FULL["traffic_spec"], seed, 1)
    (skew,) = FAM.host_batches(
        cfg, spec.resolve(BENCH, ZIPF, dry_run=False)["traffic_spec"], seed, 1
    )
    assert np.array_equal(flat["user"], skew["user"])  # drawn before the items
    assert {k: (v.shape, v.dtype) for k, v in flat.items()} == {
        k: (v.shape, v.dtype) for k, v in skew.items()}
    counts = np.bincount(flat["item"], minlength=cfg["num_items"])
    hot = np.bincount(skew["item"], minlength=cfg["num_items"])
    # 65,536 draws over 39,780 items: 1.65 a row, no row hot; Zipf(1.2)'s
    # first row takes a sixth of the batch
    assert counts.max() <= 12 and hot.max() > 10_000
    assert 0.78 < (counts > 0).mean() < 0.83  # 1 - exp(-1.647)
    assert flat["item"].min() >= 0 and flat["item"].max() < cfg["num_items"]
    assert abs(flat["item"].mean() / cfg["num_items"] - 0.5) < 0.01


def test_the_reference_holds_the_model_under_these_keys_with_teeth():
    import jax

    from flink_parameter_server_tpu.core.transform import make_train_step

    cfg = DRY["cfg"]
    ref, check = spec.reference(cfg), cfg["reference"]
    logic, store = FAM.build(cfg, 11, None)
    batches = FAM.host_batches(cfg, DRY["traffic_spec"], 11, check["batches"])
    ids = ref.touched(batches)
    state = logic.init_state(jax.random.PRNGKey(0))
    before = FAM.rows(store, state, ids)
    step = jax.jit(make_train_step(logic, store.spec))
    table = store.table
    for b in batches:
        table, state, _ = step(table, state, b)
    got = FAM.rows(type(store)(store.spec, table), state, ids)
    want, moved = ref.apply(cfg, before, ids, batches)
    failures, worst = run._check_rows(check, (want, moved), got, before)
    assert failures == [] and 0 < worst["share"] < 0.5, worst
    ulp = check["row_ulps"] * float(np.finfo(np.float32).eps)
    for name in want:
        net = np.abs(want[name] - before[name])
        allowed = check["delta_rtol"] * moved[name] + check["delta_atol"] + (
            ulp * np.maximum(np.abs(want[name]), np.abs(before[name])))
        assert np.mean(net > 5 * allowed) > 0.8, name


def test_the_cells_entries_by_name_and_its_dry_run():
    # by name, never by place: later cells are appended after this one
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == "mf-hugewiki-k128"
    assert cell["traffic"] == "train-uniform" and len(cell["why"]) <= 200
    assert sum(w["config"] == "mf-hugewiki-k128" for w in BENCH["workloads"]) == 3
    assert {m["name"] for m in spec.metrics_of(BENCH, "end_to_end", CELL)} == {
        "updates_per_s_chip", "setup_s",
    }
    per_layer = {m["name"] for m in spec.metrics_of(BENCH, "per_layer", CELL)}
    assert {
        "store.pull_device_ms", "store.push_device_ms", "step.compute_device_ms",
        "store.gather_scatter_roofline", "step.device_ms", "device.idle_share",
    } <= per_layer
    # the lists of cell 1's own metrics were not edited to name this cell
    assert not {"step.state_update_device_ms", "serving.publish_ms",
                "step.dense_device_ms"} & per_layer
    for m in BENCH["end_to_end"]:
        assert CELL not in m.get("workloads", [])
    assert lint.problems(spec.ROOT) == []
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
    assert {"driver.dispatch_ms", "setup.compiles"} <= set(last["metric_names"])
