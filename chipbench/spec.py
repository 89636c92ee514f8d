"""Everything a cell needs, found by the names in ``BENCHMARK.json``.

No list of names lives in code: a cell names its configuration and traffic,
the configuration's entry names its file, the file names its family and the
plain reference it is compared with, and a metric's reader is the file that
carries the metric's name.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
from typing import List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def _named(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json names no {what} {name!r}")


def resolve(bench: dict, workload: str, *, dry_run: bool, root: str = ROOT) -> dict:
    """The cell's entry with its configuration and traffic files loaded;
    a dry run lays the configuration's ``dry_run`` sizes over the real."""
    cell = dict(_named(bench["workloads"], workload, "workload"))
    entry = _named(bench["configs"], cell["config"], "config")
    def sized(data: dict) -> dict:
        return {**data, **data.get("dry_run", {})} if dry_run else data

    cell.update(
        cfg=sized(load_json(os.path.join(root, entry["file"]))),
        traffic_spec=sized(load_json(
            os.path.join(BENCH_DIR, "traffic", cell["traffic"] + ".json")
        )),
    )
    return cell


def family(name: str):
    return importlib.import_module(f"chipbench.families.{name}")


def _module_at(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reference(cfg: dict, root: str = ROOT):
    """The plain reference the configuration file declares
    (``reference.file``, a path from the repo's root): what ``correct``
    compares the system with."""
    path = cfg["reference"]["file"]
    return _module_at(
        os.path.join(root, path),
        "chipbench_reference_" + "".join(c if c.isalnum() else "_" for c in path),
    )


def metric_reader(name: str):
    """The module ``chipbench/layer_metrics/<metric name>.py`` (metric names
    carry dots, so it is loaded by path), or ``None`` if there is none."""
    path = os.path.join(BENCH_DIR, "layer_metrics", name + ".py")
    if not os.path.exists(path):
        return None
    return _module_at(
        path, "chipbench_metric_" + name.replace(".", "_").replace("-", "_")
    )


def metrics_of(bench: dict, kind: str, workload: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [
        m for m in bench[kind]
        if "workloads" not in m or workload in m["workloads"]
    ]
