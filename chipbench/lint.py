"""Lint of ``BENCHMARK.json`` and the benchmark's data files against the
contract's formats.  ``problems(root)`` returns what is wrong, one line
each; the tests require none."""
from __future__ import annotations

import json
import os
import re
from typing import Dict, List

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
TOP_KEYS = {
    "command", "paths", "run_seconds", "configs", "workloads", "end_to_end",
    "per_layer",
}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
DATA_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")
# a width may never be listed as reduced
WIDTH = re.compile(r"(_dim|_rank)$|^(dim|fields|dtype|batch)$")


def _line(text, what: str, out: List[str]) -> None:
    if not isinstance(text, str) or not 1 <= len(text) <= 200 or (
        "\n" in text or "\t" in text
    ):
        out.append(f"{what}: not 1-200 characters on one line")


def check_benchmark(bench: dict, root: str) -> List[str]:
    out: List[str] = []
    if set(bench) != TOP_KEYS:
        out.append(f"top-level keys {sorted(bench)} != {sorted(TOP_KEYS)}")
        return out
    if not (isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51):
        out.append("run_seconds not a whole number in 1..51")
    if not 1 <= len(bench["command"]) <= 32:
        out.append("command not 1..32 words")
    for word in bench["command"]:
        _line(word, f"command word {word!r}", out)
        if word.startswith("/") or ".." in word.split("/"):
            out.append(f"command word {word!r} leaves the repo")
    paths = bench["paths"]
    if not 1 <= len(paths) <= 16:
        out.append("paths not 1..16 directories")
    for p in paths:
        if not PATH.match(p) or p.startswith("/") or ".." in p.split("/"):
            out.append(f"path {p!r} malformed")
        elif not os.path.isdir(os.path.join(root, p)):
            out.append(f"path {p!r} is no directory")

    def under_paths(f: str) -> bool:
        return any(f.startswith(p.rstrip("/") + "/") for p in paths)

    names: Dict[str, set] = {}

    def unique(kind: str, name) -> None:
        if not isinstance(name, str) or not NAME.match(name):
            out.append(f"{kind} name {name!r} malformed")
        if name in names.setdefault(kind, set()):
            out.append(f"{kind} name {name!r} appears twice")
        names[kind].add(name)

    files = set()
    for c in bench["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            out.append(f"config {c.get('name')}: keys {sorted(c)}")
            continue
        unique("config", c["name"])
        _line(c["source"], f"config {c['name']} source", out)
        _line(c["why"], f"config {c['name']} why", out)
        if not under_paths(c["file"]) or not os.path.isfile(os.path.join(root, c["file"])):
            out.append(f"config {c['name']}: file {c['file']!r} missing or outside paths")
        if c["file"] in files:
            out.append(f"config file {c['file']!r} used twice")
        files.add(c["file"])
        if len(c["reduced"]) > 16:
            out.append(f"config {c['name']}: more than 16 reduced keys")
        for key in c["reduced"]:
            if not NAME.match(key) or WIDTH.search(key):
                out.append(f"config {c['name']}: reduced key {key!r} malformed or a width")
    if not 1 <= len(bench["configs"]) <= 24:
        out.append("configs not 1..24")

    cells = bench["workloads"]
    if not 1 <= len(cells) <= 24:
        out.append("workloads not 1..24")
    pairs = set()
    for w in cells:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            out.append(f"workload {w.get('name')}: keys {sorted(w)}")
            continue
        unique("workload", w["name"])
        _line(w["why"], f"workload {w['name']} why", out)
        if w["config"] not in names.get("config", ()):
            out.append(f"workload {w['name']}: unknown config {w['config']!r}")
        if not NAME.match(w["traffic"]):
            out.append(f"workload {w['name']}: traffic name malformed")
        if w["chips"] not in (1, 4):
            out.append(f"workload {w['name']}: chips {w['chips']!r}")
        if (w["config"], w["traffic"]) in pairs:
            out.append(f"workload {w['name']}: pair appears twice")
        pairs.add((w["config"], w["traffic"]))
        traffic = [
            os.path.join(root, p, "traffic", w["traffic"] + s)
            for p in paths for s in DATA_SUFFIXES
        ]
        found = [t for t in traffic if os.path.isfile(t)]
        if not found:
            out.append(f"workload {w['name']}: no traffic file {w['traffic']!r}")
        elif found[0].endswith(".json"):
            with open(found[0]) as f:
                mix = json.load(f)
            if "keys" in mix and not mix.get("keys_source"):
                out.append(
                    f"traffic {w['traffic']!r}: keys without keys_source "
                    "(where its skew comes from)"
                )
    used = {w["config"] for w in cells if "config" in w}
    for c in names.get("config", ()):
        if c not in used:
            out.append(f"config {c!r} is used by no cell")
    four = sum(1 for w in cells if w.get("chips") == 4)
    if four > max(1, len(cells) // 4):
        out.append(f"{four} of {len(cells)} cells ask for four chips")

    cell_names = names.get("workload", set())
    reported: Dict[str, set] = {w: set() for w in cell_names}
    for kind, keys in (
        ("end_to_end", {"name", "unit", "better", "bound", "source"}),
        ("per_layer", {"name", "unit", "better", "source", "layer", "moves"}),
    ):
        entries = bench[kind]
        if not 1 <= len(entries) <= (16 if kind == "end_to_end" else 128):
            out.append(f"{kind}: count {len(entries)}")
        for m in entries:
            if set(m) - {"workloads"} != keys:
                out.append(f"{kind} {m.get('name')}: keys {sorted(m)}")
                continue
            unique("metric", m["name"])
            if not UNIT.match(m["unit"]):
                out.append(f"metric {m['name']}: unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                out.append(f"metric {m['name']}: better {m['better']!r}")
            if m["source"] not in SOURCES:
                out.append(f"metric {m['name']}: source {m['source']!r}")
            where = set(m.get("workloads", cell_names))
            if not where or where - cell_names:
                out.append(f"metric {m['name']}: workloads {sorted(where - cell_names)} unknown or empty")
            if kind == "end_to_end":
                if m["source"] not in ("host_clock", "device_trace"):
                    out.append(f"end-to-end {m['name']}: source {m['source']!r}")
                if not (isinstance(m["bound"], float) and 0.01 <= m["bound"] <= 0.1):
                    out.append(f"end-to-end {m['name']}: bound {m['bound']!r}")
                for w in where & cell_names:
                    reported[w].add(m["name"])
            else:
                _line(m["layer"], f"metric {m['name']} layer", out)
                for w in where & cell_names:
                    if m["moves"] not in reported[w]:
                        out.append(
                            f"layer metric {m['name']} moves {m['moves']!r}, "
                            f"which cell {w} does not report"
                        )
    for w, have in reported.items():
        if "setup_s" not in have or len(have) < 2:
            out.append(f"cell {w}: reports {sorted(have)}; needs setup_s and one more")
        if not any(
            "workloads" not in m or w in m["workloads"] for m in bench["per_layer"]
        ):
            out.append(f"cell {w}: no per-layer metric")
    return out


def check_config_file(entry: dict, cfg: dict, root: str) -> List[str]:
    """A configuration file states its source, cuts, assumptions, guarantees
    and the plain reference its ``correct`` rests on."""
    out = []
    name = entry["name"]
    for key in ("family", "source", "reduced", "assumed", "guarantees", "reference", "driver", "dry_run"):
        if key not in cfg:
            out.append(f"{name}: configuration file lacks {key!r}")
    if cfg.get("name") != name:
        out.append(f"{name}: file names itself {cfg.get('name')!r}")
    if cfg.get("source") != entry["source"] or cfg.get("reduced") != entry["reduced"]:
        out.append(f"{name}: source/reduced differ between file and BENCHMARK.json")
    ref = cfg.get("reference", {})
    if not os.path.isfile(os.path.join(root, ref.get("file", "?"))):
        out.append(f"{name}: reference file {ref.get('file')!r} missing")
    for key in ("batches", "delta_rtol", "delta_atol", "row_ulps", "why"):
        if key not in ref:
            out.append(f"{name}: reference lacks {key!r}")
    for key in cfg.get("reduced", []):
        if key not in cfg.get("source_sizes", {}) and key not in cfg:
            out.append(f"{name}: reduced key {key!r} is no key of the file")
    return out


def problems(root: str) -> List[str]:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        text = f.read()
    out = []
    if len(text.encode()) > 64 * 1024:
        out.append("BENCHMARK.json over 64 KiB")
    bench = json.loads(text)
    out += check_benchmark(bench, root)
    for entry in bench.get("configs", []):
        path = os.path.join(root, entry.get("file", "?"))
        if os.path.isfile(path):
            with open(path) as f:
                out += check_config_file(entry, json.load(f), root)
    for p in bench.get("paths", []):
        for dirpath, _, filenames in os.walk(os.path.join(root, p)):
            if "/out" in dirpath or "__pycache__" in dirpath:
                continue
            for fn in filenames:
                rel = os.path.relpath(os.path.join(dirpath, fn), root)
                if not PATH.match(rel):
                    out.append(f"file name {rel!r} has characters outside a name's")
    return out


if __name__ == "__main__":
    import sys

    found = problems(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    print("\n".join(found) or "clean")
    sys.exit(1 if found else 0)
