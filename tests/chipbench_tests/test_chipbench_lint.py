"""BENCHMARK.json and the benchmark's data files keep to the contract's
formats (chipbench/lint.py), and the lint itself catches what it should."""
import copy
import json
import os

import pytest

from chipbench import lint, spec

BENCH = spec.load_benchmark()


def test_tree_is_clean():
    assert lint.problems(spec.ROOT) == []


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_to_files_and_metrics(cell):
    got = spec.resolve(BENCH, cell, dry_run=False)
    assert spec.family(got["cfg"]["family"]).STEP_PROGRAM
    assert spec.reference(got["cfg"]).apply
    e2e = {m["name"] for m in spec.metrics_of(BENCH, "end_to_end", cell)}
    assert "setup_s" in e2e and "updates_per_s_chip" in e2e
    for m in spec.metrics_of(BENCH, "per_layer", cell):
        assert m["moves"] in e2e
        assert spec.metric_reader(m["name"]) is not None, m["name"]


def test_end_to_end_metrics_are_the_issues_four():
    assert [m["name"] for m in BENCH["end_to_end"]] == [
        "updates_per_s_chip", "pull_push_p50_ms", "query_p95_ms", "setup_s",
    ]
    assert "serving.query_p50_ms" in {m["name"] for m in BENCH["per_layer"]}
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= 1


def _broken(mutate):
    bench = copy.deepcopy(BENCH)
    mutate(bench)
    return lint.check_benchmark(bench, spec.ROOT)


@pytest.mark.parametrize("mutate, expect", [
    (lambda b: b["end_to_end"][0].update(name="has space"), "malformed"),
    (lambda b: b["end_to_end"][0].update(unit="tokens per second"), "unit"),
    (lambda b: b["end_to_end"][0].update(unit="x" * 17), "unit"),
    (lambda b: b["end_to_end"][0].update(bound=0.2), "bound"),
    (lambda b: b["end_to_end"][0].update(why="no"), "keys"),
    (lambda b: b["workloads"][0].update(config="nope"), "unknown config"),
    (lambda b: b["workloads"][0].update(traffic="nope"), "no traffic file"),
    (lambda b: b["workloads"][0].update(chips=2), "chips"),
    (lambda b: [w.update(chips=4) for w in b["workloads"][:2]], "four chips"),
    (lambda b: b["configs"][0].update(source="x" * 201), "1-200"),
    (lambda b: b["configs"][0].update(file="README.md"), "outside paths"),
    (lambda b: b["configs"][0].update(reduced=["dim"]), "a width"),
    (lambda b: b["per_layer"][5].update(moves="pull_push_p50_ms"), "does not report"),
    (lambda b: b["per_layer"][0].update(source="guess"), "source"),
    (lambda b: b.update(run_seconds=52), "run_seconds"),
    (lambda b: b["command"].append("/etc/passwd"), "leaves the repo"),
    (lambda b: b.update(extra=1), "top-level keys"),
])
def test_lint_catches(mutate, expect):
    found = _broken(mutate)
    assert any(expect in line for line in found), found


def test_a_traffic_file_says_where_its_skew_comes_from(tmp_path):
    import shutil

    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(
        os.path.join(spec.ROOT, "chipbench"), tmp_path / "chipbench",
        ignore=shutil.ignore_patterns("out", "__pycache__", "fixtures"),
    )
    os.makedirs(tmp_path / "tests" / "chipbench_tests")
    assert lint.problems(str(tmp_path)) == []
    mix = tmp_path / "chipbench/traffic/train-zipf.json"
    data = json.loads(mix.read_text())
    del data["keys_source"]
    mix.write_text(json.dumps(data))
    assert any("keys_source" in line for line in lint.problems(str(tmp_path)))


def test_config_files_declare_reference_and_guarantees():
    for entry in BENCH["configs"]:
        cfg = spec.load_json(os.path.join(spec.ROOT, entry["file"]))
        assert cfg["guarantees"] and cfg["assumed"]
        assert cfg["reference"]["file"].startswith("chipbench/references/")
        bad = dict(cfg)
        del bad["reference"]
        assert lint.check_config_file(entry, bad, spec.ROOT)
    assert len(json.dumps(BENCH)) < 64 * 1024
