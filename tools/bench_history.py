#!/usr/bin/env python
"""bench_history — fold every benchmark artifact into one perf ledger.

The repo accumulates perf evidence in two shapes: the driver's
round-stamped ``BENCH_r0*.json`` captures at the repo root (``{"n":
<round>, "parsed": {"metric", "value", "unit", ...}}``) and the
benchmark suites' ``results/<platform>/*.json`` artifacts
(``{"captured_at": ..., "payload": {"metric", "value", "unit", ...}}``
— cluster_scaling, elastic_scaling, recovery_time, serving_qps,
failover_time, nemesis, tierstore_soak, ...; tierstore_soak's
pull-latency ratio is a ``x slowdown`` unit so the worse direction is
upward).
Until this tool, comparing a metric across rounds meant opening each
file by hand — so regressions slid by unless someone remembered the
old number.  This folds them all into one metric × round table and
**flags >10% regressions with a nonzero exit**, so CI can gate on the
ledger instead of on vigilance.

Direction is inferred from the unit string: rates (``.../sec``) are
higher-is-better; durations (``seconds``, ``ms``) and ``% slowdown``
are lower-is-better.  A regression is a worse-direction change beyond
``--threshold`` (default 0.10) between the LAST two observations of a
metric.  Metrics seen only once are listed, never flagged.

Usage::

    python tools/bench_history.py [--repo PATH] [--threshold 0.10]
        [--json] [--out results/perf_ledger.md]

Exit 0 = no regression, 1 = at least one flagged, 2 = usage/IO error.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

# round label for the results/<platform>/ artifacts (no round stamp —
# they reflect the working tree's latest run)
CURRENT = "current"


def normalize_metric(name: str) -> str:
    """Strip volatile decorations so the same metric lines up across
    rounds: bracketed suffixes (``[note: ...]``) and redundant
    whitespace."""
    name = re.sub(r"\s*\[[^\]]*\]", "", str(name))
    return " ".join(name.split())


def higher_is_better(unit: str) -> bool:
    u = str(unit).lower()
    if "/sec" in u or "per sec" in u:
        return True
    if "slowdown" in u or "second" in u or re.search(r"\bms\b", u):
        return False
    # bytes-on-wire metrics (bytes/round, bytes/request — the
    # compression ledger, docs/compression.md) regress UPWARD; a rate
    # like bytes/sec was already claimed by the "/sec" branch above
    if "byte" in u:
        return False
    return True


def _entry(payload: Any) -> Optional[Tuple[str, float, str]]:
    """(metric, value, unit) from one artifact payload, or None when
    the file is not a metric-shaped artifact (run reports, raw sweep
    tables, ... — skipped, not errors)."""
    if not isinstance(payload, dict):
        return None
    metric, value = payload.get("metric"), payload.get("value")
    if not isinstance(metric, str) or not isinstance(
        value, (int, float)
    ) or isinstance(value, bool):
        return None
    return (
        normalize_metric(metric), float(value),
        str(payload.get("unit", "")),
    )


def load_ledger(repo: str) -> Dict[str, Dict[str, Tuple[float, str]]]:
    """``{metric: {round_label: (value, unit)}}`` over every readable
    artifact.  Round labels: ``r<n>`` from ``BENCH_r0*.json``'s ``n``
    field, ``current`` from ``results/*/*.json``."""
    ledger: Dict[str, Dict[str, Tuple[float, str]]] = {}

    def note(metric: str, rnd: str, value: float, unit: str) -> None:
        ledger.setdefault(metric, {})[rnd] = (value, unit)

    for path in sorted(glob.glob(os.path.join(repo, "BENCH_r*.json"))):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(doc, dict) or doc.get("rc") not in (0, None):
            continue  # a failed capture is not a datapoint
        ent = _entry(doc.get("parsed"))
        if ent is not None and isinstance(doc.get("n"), int):
            note(ent[0], f"r{doc['n']:02d}", ent[1], ent[2])
    for path in sorted(glob.glob(os.path.join(repo, "results", "*",
                                              "*.json"))):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(doc, dict):
            continue
        ent = _entry(doc.get("payload", doc))
        if ent is not None:
            note(ent[0], CURRENT, ent[1], ent[2])
        # A/B artifacts carry SEVERAL metric-shaped payloads (e.g.
        # results/cpu/transport_ab.json: one per arm + the headline
        # shares; results/cpu/mesh_backend_ab.json: rate + pull/push
        # p50 per backend arm) — fold each so regressions in either
        # arm, or in the speedup itself, flag in the worse direction
        payloads = doc.get("payloads")
        if isinstance(payloads, list):
            for p in payloads:
                ent = _entry(p)
                if ent is not None:
                    note(ent[0], CURRENT, ent[1], ent[2])
    return ledger


def _round_order(rounds) -> List[str]:
    stamped = sorted(
        (r for r in rounds if r != CURRENT),
        key=lambda r: (len(r), r),
    )
    return stamped + ([CURRENT] if CURRENT in rounds else [])


def detect_regressions(
    ledger: Dict[str, Dict[str, Tuple[float, str]]],
    threshold: float = 0.10,
) -> List[Dict[str, Any]]:
    """Worse-direction changes beyond ``threshold`` between the last
    two observations of each metric, most severe first."""
    out: List[Dict[str, Any]] = []
    for metric, by_round in ledger.items():
        order = _round_order(by_round)
        if len(order) < 2:
            continue
        prev_r, last_r = order[-2], order[-1]
        prev_v, unit = by_round[prev_r]
        last_v, _ = by_round[last_r]
        if prev_v == 0:
            continue
        change = (last_v - prev_v) / abs(prev_v)
        worse = -change if higher_is_better(unit) else change
        if worse > threshold:
            out.append({
                "metric": metric,
                "unit": unit,
                "from_round": prev_r,
                "to_round": last_r,
                "from": prev_v,
                "to": last_v,
                "change_pct": round(change * 100.0, 1),
                "worse_pct": round(worse * 100.0, 1),
            })
    return sorted(out, key=lambda r: -r["worse_pct"])


def render_markdown(
    ledger: Dict[str, Dict[str, Tuple[float, str]]],
    regressions: List[Dict[str, Any]],
    threshold: float,
) -> str:
    rounds = _round_order(
        {r for by in ledger.values() for r in by}
    )
    flagged = {r["metric"] for r in regressions}
    lines = [
        "# Perf ledger (metric × round)",
        "",
        f"Folded from `BENCH_r0*.json` + `results/*/*.json` by "
        f"`tools/bench_history.py`; regression bar "
        f"{round(threshold * 100)}% on the last two observations.",
        "",
        "| metric | unit | " + " | ".join(rounds) + " | Δ last | |",
        "|---|---|" + "---|" * len(rounds) + "---|---|",
    ]
    for metric in sorted(ledger):
        by_round = ledger[metric]
        unit = next(iter(by_round.values()))[1]
        cells = [
            f"{by_round[r][0]:g}" if r in by_round else "—"
            for r in rounds
        ]
        order = _round_order(by_round)
        delta = "—"
        if len(order) >= 2:
            a, b = by_round[order[-2]][0], by_round[order[-1]][0]
            if a:
                delta = f"{(b - a) / abs(a) * 100.0:+.1f}%"
        flag = "**REGRESSION**" if metric in flagged else ""
        lines.append(
            f"| {metric} | {unit} | " + " | ".join(cells)
            + f" | {delta} | {flag} |"
        )
    if regressions:
        lines += ["", "## Flagged regressions", ""]
        for r in regressions:
            lines.append(
                f"- **{r['metric']}**: {r['from']:g} → {r['to']:g} "
                f"{r['unit']} ({r['change_pct']:+.1f}% between "
                f"{r['from_round']} and {r['to_round']})"
            )
    return "\n".join(lines) + "\n"


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(prog="bench_history", description=__doc__)
    p.add_argument(
        "--repo",
        default=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    p.add_argument("--threshold", type=float, default=0.10)
    p.add_argument("--json", action="store_true",
                   help="emit the ledger + flags as JSON")
    p.add_argument("--out", default=None,
                   help="also write the markdown table here")
    args = p.parse_args(argv)
    ledger = load_ledger(args.repo)
    if not ledger:
        print(f"bench_history: no artifacts found under {args.repo}",
              file=sys.stderr)
        return 2
    regs = detect_regressions(ledger, args.threshold)
    if args.json:
        print(json.dumps({
            "ledger": {
                m: {r: {"value": v, "unit": u}
                    for r, (v, u) in by.items()}
                for m, by in ledger.items()
            },
            "regressions": regs,
            "threshold": args.threshold,
        }, indent=2))
    else:
        print(render_markdown(ledger, regs, args.threshold), end="")
    if args.out:
        with open(args.out, "w") as f:
            f.write(render_markdown(ledger, regs, args.threshold))
    return 1 if regs else 0


if __name__ == "__main__":
    sys.exit(main())
