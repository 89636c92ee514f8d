"""Workload battery — the ROADMAP-5 acceptance artifact.

Two parts, one committed artifact
(``results/<platform>/workload_battery.{md,json}`` — docs/workloads.md):

  1. **full-stack scenarios** — for each non-MF registered workload
     (the PA classifier and the count-min sketch layer), replay its
     train-while-serve-while-resize-while-faulted corpus scenario
     (``nemesis/corpus/{pa,sketch}_full_stack.json``: scale_out +
     kill→promote + partition composed over the workload) and record
     the full verdict table — exactly-once ledger, parity vs the
     workload's own oracle (BITWISE for PA, INTEGER-EXACT for the
     sketch, with ``wire_format="q8"`` requested and bypassed by the
     increment carve-out), serving error budget, staleness bound,
     thread ledger;
  2. **the q8/aggregation soak arms** — short open-loop soaks through
     ``loadgen.SoakRunner`` with ``wire_format="q8"`` and
     ``+ push_aggregate`` on the train-push path (the PR-14 follow-on
     arms; the minutes-long headline A/B lives in
     ``benchmarks/soak_capacity.py`` and its committed artifact),
     recording goodput, push bytes saved, combined pushes and the
     invariant verdicts.

Usage::

    JAX_PLATFORMS=cpu python benchmarks/workload_battery.py \
        [--soak-seconds 8] [--out results/cpu/workload_battery.md]

Prints one JSON metric line (bench.py shape; ``FPS_BENCH_WORKLOADS=1``
emits the same line from bench.py, both code paths).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from typing import Dict, List

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

WORKLOAD_SCENARIOS = ("pa_full_stack", "sketch_full_stack")
SOAK_ARMS = (
    ("q8", {"wire_format": "q8"}),
    ("q8_agg", {"wire_format": "q8", "push_aggregate": True}),
)


def run_workload_battery(*, soak_seconds: float = 8.0,
                         seed: int = 0) -> dict:
    """Run both parts; returns the result dict (import-time
    side-effect free — bench.py imports this)."""
    import jax

    from flink_parameter_server_tpu.loadgen.soak import (
        SoakConfig,
        run_soak,
    )
    from flink_parameter_server_tpu.nemesis.runner import run_scenario
    from flink_parameter_server_tpu.nemesis.scenarios import (
        BUILTIN_SCENARIOS,
    )
    from flink_parameter_server_tpu.workloads import create_workload

    by_name = {s.name: s for s in BUILTIN_SCENARIOS}
    wal_root = tempfile.mkdtemp(prefix="workload-battery-")

    scenarios: List[Dict[str, object]] = []
    for name in WORKLOAD_SCENARIOS:
        s = by_name[name]
        wl = create_workload(s.workload)
        report = run_scenario(s, wal_root=wal_root)
        scenarios.append({
            "scenario": name,
            "workload": s.workload,
            "push_semantics": wl.push_semantics,
            "parity_mode": wl.parity,
            "wire_format_requested": s.wire_format,
            "ok": report.ok,
            "rounds": report.rounds,
            "wall_s": round(report.wall_s, 3),
            "ops_executed": report.ops_executed,
            "faults": dict(sorted(report.faults.items())),
            "verdicts": [v.as_dict() for v in report.verdicts],
        })

    soak_arms: Dict[str, dict] = {}
    for arm, overrides in SOAK_ARMS:
        cfg = SoakConfig(
            duration_s=float(soak_seconds),
            offered_rps=120.0,
            generators=4,
            num_users=256,
            num_items=1024,
            dim=8,
            warmup_requests=48,
            link_delay_ms=0.0,
            seed=seed,
            **overrides,
        )
        rep = run_soak(cfg)
        soak_arms[arm] = {
            **{k: rep.summary[k] for k in (
                "arrivals", "ok", "late", "shed", "error",
                "goodput_rps", "p50_ms", "p99_ms", "latency_anchor",
            )},
            "invariants_ok": rep.ok,
            "verdicts": [v.as_dict() for v in rep.verdicts],
            "wire_format": rep.overload.get("wire_format"),
            "push_aggregate": rep.overload.get("push_aggregate"),
            "compression_bytes_saved": rep.overload.get(
                "compression_bytes_saved", 0
            ),
            "combined_pushes": rep.overload.get("combined_pushes", 0),
            "combined_rows_saved": rep.overload.get(
                "combined_rows_saved", 0
            ),
        }

    return {
        "scenarios": scenarios,
        "scenarios_passed": sum(1 for s in scenarios if s["ok"]),
        "soak_arms": soak_arms,
        "soak_seconds": float(soak_seconds),
        "platform": jax.default_backend(),
    }


def battery_artifact(r: dict) -> dict:
    from flink_parameter_server_tpu.telemetry.registry import (
        default_run_id,
    )

    return {
        "ts": round(time.time(), 3),
        "run_id": default_run_id(),
        "captured_at": time.time(),
        "payload": {
            "metric": (
                "workload battery (PA + sketch full-stack scenarios)"
            ),
            "value": r["scenarios_passed"],
            "unit": "scenarios passed",
            "extra": {
                "scenarios": [
                    {k: s[k] for k in ("scenario", "workload", "ok",
                                       "parity_mode", "wall_s")}
                    for s in r["scenarios"]
                ],
                "soak_q8_goodput_rps":
                    r["soak_arms"]["q8"]["goodput_rps"],
                "soak_q8_bytes_saved":
                    r["soak_arms"]["q8"]["compression_bytes_saved"],
                "soak_q8_agg_combined_pushes":
                    r["soak_arms"]["q8_agg"]["combined_pushes"],
                "platform": r["platform"],
            },
        },
        "workloads": r,
    }


def _render_md(r: dict, stamp: str) -> str:
    lines = [
        f"# workload battery — {r['platform']}, {stamp}",
        "# the ROADMAP-5 acceptance: both non-MF workloads through "
        "train-while-serve-while-resize-while-faulted "
        "(scale_out + kill→promote + partition; docs/workloads.md)",
        "",
        "## Full-stack scenarios",
        "",
        "| scenario | workload | parity mode | wire req | ok | "
        "rounds | ops | wall s |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for s in r["scenarios"]:
        lines.append(
            f"| {s['scenario']} | {s['workload']} | "
            f"{s['parity_mode']} | {s['wire_format_requested']} | "
            f"{'PASS' if s['ok'] else 'FAIL'} | {s['rounds']} | "
            f"{s['ops_executed']} | {s['wall_s']} |"
        )
    lines.append("")
    for s in r["scenarios"]:
        for v in s["verdicts"]:
            lines.append(
                f"- `{s['scenario']}` / {v['name']}: "
                f"{'✓' if v['ok'] else '✗'} {v['detail']}"
            )
    lines += [
        "",
        f"## q8 / aggregation soak arms "
        f"({r['soak_seconds']:.0f} s open-loop each; the 60 s "
        f"headline arms live in results/cpu/soak_capacity.md)",
        "",
        "| arm | wire | agg | goodput req/s | p50 ms | p99 ms | "
        "push bytes saved | combined pushes | invariants |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for arm, a in r["soak_arms"].items():
        lines.append(
            f"| {arm} | {a['wire_format']} | "
            f"{'yes' if a['push_aggregate'] else '—'} | "
            f"{a['goodput_rps']} | {a['p50_ms']} | {a['p99_ms']} | "
            f"{a['compression_bytes_saved']} | "
            f"{a['combined_pushes']} | "
            f"{'ALL PASS' if a['invariants_ok'] else 'VIOLATED'} |"
        )
    return "\n".join(lines) + "\n"


def main():
    from flink_parameter_server_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--soak-seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    r = run_workload_battery(
        soak_seconds=args.soak_seconds, seed=args.seed
    )
    doc = battery_artifact(r)
    print(json.dumps(doc["payload"]))

    out = args.out or os.path.join(
        REPO, "results", r["platform"], "workload_battery.md"
    )
    stamp = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        f.write(_render_md(r, stamp))
    with open(os.path.splitext(out)[0] + ".json", "w") as f:
        json.dump(doc, f, indent=1)
    print(f"# wrote {out}", file=sys.stderr)


if __name__ == "__main__":
    main()
