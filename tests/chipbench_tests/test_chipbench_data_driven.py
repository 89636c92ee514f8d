"""The harness is driven by data: a cell, a configuration, a traffic mix and
a layer metric added as NEW FILES (plus entries appended to BENCHMARK.json)
are found by name and run — no file that was there is edited.  And a run
without a TPU exits nonzero with no result line."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import lint, spec


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(cwd), spec.ROOT]))
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "-m", "chipbench.run", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


@pytest.fixture()
def copy(tmp_path):
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(
        os.path.join(spec.ROOT, "chipbench"), tmp_path / "chipbench",
        ignore=shutil.ignore_patterns("out", "__pycache__", "fixtures"),
    )
    os.makedirs(tmp_path / "tests" / "chipbench_tests")
    return tmp_path


def test_new_cell_config_traffic_and_metric_are_files_only(copy):
    bench = json.loads((copy / "BENCHMARK.json").read_text())
    before = {
        p: p.read_bytes() for p in (copy / "chipbench").rglob("*") if p.is_file()
    }
    cfg = json.loads(
        (copy / "chipbench/configs/mf-hugewiki-k128.json").read_text()
    )
    cfg.update(name="mf-added", source="a test's own deployment", reduced=[])
    (copy / "chipbench/configs/mf-added.json").write_text(json.dumps(cfg))
    (copy / "chipbench/traffic/train-uniform.json").write_text(json.dumps({
        "name": "train-uniform", "keys": {"kind": "uniform"},
        "keys_source": "a test's own: no skew at all",
        "warmup_dispatches": 4, "trace_after_s": 0.05, "trace_seconds": 0.2,
    }))
    (copy / "chipbench/layer_metrics/ingest.batches.py").write_text(
        "def read(ctx):\n    return float(len(ctx['counters']['ingest_ms']))\n"
    )
    bench["configs"].append({
        "name": "mf-added", "source": "a test's own deployment",
        "file": "chipbench/configs/mf-added.json", "reduced": [],
        "why": "added by files alone",
    })
    bench["workloads"].append({
        "name": "mf-added.train-uniform", "config": "mf-added",
        "traffic": "train-uniform", "chips": 1, "why": "added by files alone",
    })
    bench["per_layer"].append({
        "name": "ingest.batches", "unit": "batches", "better": "higher",
        "source": "program_counter", "layer": "ingest",
        "moves": "updates_per_s_chip", "workloads": ["mf-added.train-uniform"],
    })
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))
    assert lint.problems(str(copy)) == []

    done = _run(copy, "--workload", "mf-added.train-uniform", "--seed", "5",
                "--seconds", "0.5", "--trace", "1", "--cpu-dry-run")
    assert done.returncode == 0, done.stderr[-3000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] and "dry_run" in last and "metrics" not in last
    assert "ingest.batches" in last["metric_names"]
    assert "ingest.batch_ms" in last["metric_names"]
    for path, content in before.items():
        assert path.read_bytes() == content, f"{path} was edited"


def test_a_sharded_four_chip_cell_is_files_only_too(copy):
    # PERF.md section 7, row 1: the sharded table comes back as a
    # configuration file, an entry and a reader, with no edit to the harness
    bench = json.loads((copy / "BENCHMARK.json").read_text())
    cfg = json.loads((copy / "chipbench/configs/fm-criteo.json").read_text())
    cards = [11] + [400] * 25  # 13 + 10,011 rows: four equal shards
    cfg.update(name="fm-added-ps4", source="a test's own deployment",
               mesh={"dp": 1, "ps": 4})
    cfg["dry_run"].update(field_cardinalities=cards, num_features=13 + sum(cards))
    (copy / "chipbench/configs/fm-added-ps4.json").write_text(json.dumps(cfg))
    (copy / "chipbench/layer_metrics/collectives.device_ms.py").write_text(
        "def read(ctx):\n"
        "    return ctx['trace'] and ctx['trace']['collective_ms_per_step']\n"
    )
    bench["configs"].append({
        "name": "fm-added-ps4", "source": "a test's own deployment",
        "file": "chipbench/configs/fm-added-ps4.json", "reduced": [],
        "why": "added by files alone",
    })
    cell = "fm-added-ps4.train-fields-uniform"
    bench["workloads"].append({
        "name": cell, "config": "fm-added-ps4",
        "traffic": "train-fields-uniform", "chips": 4, "why": "added by files alone",
    })
    bench["end_to_end"][1]["workloads"].append(cell)
    bench["per_layer"].append({
        "name": "collectives.device_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "collectives",
        "moves": "updates_per_s_chip", "workloads": [cell],
    })
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))
    assert lint.problems(str(copy)) == []
    done = _run(copy, "--workload", cell, "--seed", "6", "--seconds", "0.5",
                "--trace", "0", "--cpu-dry-run")
    assert done.returncode == 0, done.stderr[-3000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["correct"] and last["failed"] == 0
    assert "pull_push_p50_ms" in last["metric_names"]
    assert '"mesh": {"dp": 1, "ps": 4}' in done.stderr


def test_without_a_tpu_there_is_no_result_line(copy):
    done = _run(copy, "--workload", "mf-hugewiki-k128.train-zipf", "--seed",
                "5", "--seconds", "1", "--trace", "0")
    assert done.returncode == 2
    assert done.stdout == ""
    assert "TPU" in done.stderr


def test_dry_run_of_the_serving_cell_counts_and_never_prints_a_result(copy):
    done = _run(copy, "--workload", "mf-hugewiki-k128.train-zipf-serve-topk",
                "--seed", str(2**31 + 99), "--seconds", "1", "--cpu-dry-run")
    assert done.returncode == 0, done.stderr[-3000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(last) == {
        "dry_run", "correct", "attempted", "failed", "metric_names", "failures",
    }
    assert last["correct"] and last["failed"] == 0 and last["attempted"] > 100
    assert set(last["metric_names"]) == {
        "updates_per_s_chip", "query_p95_ms", "setup_s",
    }
