#!/usr/bin/env python
"""nemesis_battery — replay the fixed-seed fault-injection corpus.

Runs every committed schedule (``flink_parameter_server_tpu/nemesis/
corpus/``) through the scenario runner: ≥ 8 survivable scenarios
(partitions one-way/two-way, an asymmetric partition splitting a live
migration, kill-primary-under-partition, promote-while-client-
partitioned, bandwidth drip under scale-out, a straggler storm under
SSP, mid-frame RSTs both directions, a half-open accept) plus the
deliberately seeded corruption the checkers must CATCH.

Reports scenarios run/passed, faults injected per class, the invariant
verdict table, and the corpus-replay result (every scenario matched
its recorded expectation), and writes
``results/<platform>/nemesis.{md,json}`` — the artifact any
robustness claim should cite (docs/resilience.md "Fault-model
matrix").  ``FPS_BENCH_NEMESIS=1 python bench.py`` emits the same
numbers as a guarded metric line; the JSON shape folds into
``tools/bench_history.py``.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time
from typing import Dict

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def run_nemesis_bench(*, artifact_failures: bool = False) -> Dict:
    """Replay the corpus; returns the roll-up dict (no I/O)."""
    from flink_parameter_server_tpu.nemesis.runner import (
        load_corpus,
        run_scenario,
    )

    t0 = time.perf_counter()
    wal_root = tempfile.mkdtemp(prefix="nemesis-bench-")
    artifact_dir = (
        tempfile.mkdtemp(prefix="nemesis-artifacts-")
        if artifact_failures else None
    )
    scenarios = load_corpus()
    reports = []
    for s in scenarios:
        reports.append(run_scenario(
            s, wal_root=wal_root, artifact_dir=artifact_dir,
            witness=(s.name == "two_way_partition_heal"),
        ))
    faults: Dict[str, int] = {}
    for r in reports:
        for kind, n in r.faults.items():
            faults[kind] = faults.get(kind, 0) + n
    passing = [r for r in reports if r.scenario.expect == "pass"]
    violations = [r for r in reports if r.scenario.expect == "violation"]
    import jax

    return {
        "scenarios_run": len(reports),
        "scenarios_passing_expected": len(passing),
        "scenarios_passed": sum(1 for r in passing if r.ok),
        "violations_seeded": len(violations),
        "violations_caught": sum(1 for r in violations if not r.ok),
        "corpus_replay_ok": all(r.as_expected for r in reports),
        "faults_injected": dict(sorted(faults.items())),
        "fault_classes": len(faults),
        "scenarios": [r.as_dict() for r in reports],
        "wall_s": round(time.perf_counter() - t0, 2),
        "platform": jax.default_backend(),
    }


def _render_md(r: Dict) -> str:
    ts = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    lines = [
        f"# nemesis scenario battery — {r['platform']}, {ts}",
        f"# corpus replay: {r['scenarios_run']} schedules, "
        f"{r['fault_classes']} fault classes, wall {r['wall_s']}s",
        "",
        "| scenarios | passed | violations seeded | caught | "
        "corpus replay |",
        "|---|---|---|---|---|",
        f"| {r['scenarios_passing_expected']} | {r['scenarios_passed']} "
        f"| {r['violations_seeded']} | {r['violations_caught']} "
        f"| {'ok' if r['corpus_replay_ok'] else 'MISMATCH'} |",
        "",
        "## Faults injected per class",
        "",
        "| class | count |",
        "|---|---|",
    ]
    for kind, n in r["faults_injected"].items():
        lines.append(f"| {kind} | {n} |")
    lines += [
        "",
        "## Per-scenario verdicts",
        "",
        "| scenario | expect | outcome | invariants | faults |",
        "|---|---|---|---|---|",
    ]
    for s in r["scenarios"]:
        verdicts = " ".join(
            ("✓" if v["ok"] else "✗") + v["name"].split("_")[0]
            for v in s["verdicts"]
        )
        fstr = ",".join(f"{k}:{v}" for k, v in s["faults"].items()) or "-"
        lines.append(
            f"| {s['name']} | {s['expect']} "
            f"| {'ok' if s['ok'] else 'violated'}"
            f"{' (as expected)' if s['as_expected'] else ' (MISMATCH)'} "
            f"| {verdicts} | {fstr} |"
        )
    lines += [
        "",
        "Every failing run is reproducible from its (seed, schedule)",
        "pair — the canonical schedule JSONs live in",
        "flink_parameter_server_tpu/nemesis/corpus/ and replay in",
        "tier-1 (tests/test_nemesis.py).  See docs/resilience.md",
        '"Fault-model matrix".',
        "",
    ]
    return "\n".join(lines)


def main() -> None:
    from flink_parameter_server_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    r = run_nemesis_bench()
    out_dir = os.path.join(REPO, "results", r["platform"])
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "nemesis.json"), "w") as f:
        json.dump({
            "captured_at": time.time(),
            "payload": {
                "metric": "nemesis scenario battery "
                          "(fixed-seed fault injection)",
                "value": r["scenarios_passed"],
                "unit": "scenarios passed",
                "extra": r,
            },
        }, f, indent=1)
        f.write("\n")
    with open(os.path.join(out_dir, "nemesis.md"), "w") as f:
        f.write(_render_md(r))
    print(json.dumps({
        "scenarios_run": r["scenarios_run"],
        "scenarios_passed": r["scenarios_passed"],
        "violations_caught": r["violations_caught"],
        "corpus_replay_ok": r["corpus_replay_ok"],
        "wall_s": r["wall_s"],
    }))


if __name__ == "__main__":
    main()
