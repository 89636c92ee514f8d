#!/usr/bin/env python
"""Lint for metric-line streams (the JSON-lines sink contract).

Every emitter in the repo — StepMetrics, ServingMetrics, the stall
watchdog, the recovery supervisor, the registry itself — writes ONE
valid single-line JSON object per sample, stamped with the shared
``ts``/``run_id`` fields (telemetry/registry.py ``json_line``).  This
tool enforces that contract over captured logs, so a malformed line is
caught in CI (tests/test_telemetry.py invokes it over a live example
run) instead of by a downstream parser at 3 a.m.

Usage::

    python tools/check_metric_lines.py run.log [more.log ...]
    some_job 2>&1 | python tools/check_metric_lines.py -

Lines that are empty or start with ``#`` (bench commentary) are
skipped; everything else must ``json.loads`` to a dict carrying ``ts``
(number) and ``run_id`` (string).  ``--allow-missing-ids`` relaxes the
ts/run_id requirement (pre-telemetry logs).  Exit 0 = clean, 1 = at
least one malformed line (each is reported with file:line and reason).

Registry samples (``"kind": "registry"``) additionally have every
``component=`` label checked against the known component set — a
typo'd component silently forks a dashboard's series, so it fails the
lint instead.

Four further artifact shapes from the observability plane lint here
too (docs/observability.md):

    python tools/check_metric_lines.py --trace merged_trace.json
    python tools/check_metric_lines.py --flightrec flightrec_stall.json
    python tools/check_metric_lines.py --budget budget.json
    python tools/check_metric_lines.py --timeline timeline.json

``--trace`` checks a Chrome trace-event JSON array (the
``TraceCollector`` merge format): every ``X`` event carries ``pid``,
numeric non-negative ``ts``, and a ``trace_id`` key in ``args``
(``null`` allowed — the key records the decision); ``X`` events are
timestamp-monotone.  ``--flightrec`` checks a flight-recorder dump:
a JSON object with ``reason``/``pid``/``run_id``/``events``, every
event carrying a numeric ``ts`` and ``kind``.  ``--budget`` checks a
latency-budget artifact (telemetry/profiler.py
``write_budget_artifact``): ts/run_id stamped, every budget carries a
non-empty phase list with numeric ``p50_ms``/``pct``, and for any
verb with full coverage the phase percentages sum to 100 ± 10 — the
additivity contract the profiler's decomposition promises.
``--timeline`` checks a metric-timeline artifact
(telemetry/timeline.py ``TimelineRecorder.payload()``, possibly nested
under ``arms``/``timelines``): every series' timestamps are monotone
non-decreasing, the sampling cadence holds (median inter-point gap
within 3x the declared ``interval_s`` — a jittering sampler quietly
voids rate math), and every anomaly record cross-references a metric
the artifact actually carries a series for.  A mode flag applies
to the paths that follow it.
"""
from __future__ import annotations

import json
import sys
from typing import Any, Iterable, List, Tuple

# every component label the repo's emitters stamp (docs/observability.md
# instrument catalog + docs/cluster.md): new planes register here so
# their lines lint instead of linting AROUND them.  serving_dispatch is
# the HealthMonitor heartbeat component (resilience/health.py SERVING).
KNOWN_COMPONENTS = frozenset(
    {"train", "serving", "ingest", "recovery", "cluster",
     "serving_dispatch", "elastic", "slo", "profiler", "net",
     "replication", "nemesis", "hotcache", "loadgen", "compression",
     "workloads", "shmem", "meshstore", "timeline", "adaptive",
     "tierstore", "compile", "setup"}
)


def _unknown_components(obj: dict) -> List[str]:
    """Component label values outside KNOWN_COMPONENTS in a registry
    sample (empty list = clean)."""
    bad = []
    metrics = obj.get("metrics")
    if not isinstance(metrics, dict):
        return bad
    for series in metrics.values():
        if not isinstance(series, list):
            continue
        for inst in series:
            labels = inst.get("labels") if isinstance(inst, dict) else None
            comp = labels.get("component") if isinstance(labels, dict) else None
            if comp is not None and comp not in KNOWN_COMPONENTS:
                bad.append(str(comp))
    return bad


def check_lines(
    lines: Iterable[str], *, require_ids: bool = True
) -> List[Tuple[int, str, str]]:
    """Return ``[(lineno, reason, line), ...]`` for malformed lines
    (1-based line numbers; empty list = clean)."""
    bad = []
    for i, raw in enumerate(lines, 1):
        line = raw.rstrip("\n")
        stripped = line.strip()
        if not stripped or stripped.startswith("#"):
            continue
        try:
            obj = json.loads(stripped)
        except ValueError as e:
            bad.append((i, f"not valid JSON: {e}", line))
            continue
        if not isinstance(obj, dict):
            bad.append((i, f"not a JSON object (got {type(obj).__name__})",
                        line))
            continue
        if "\n" in stripped:  # unreachable via splitlines; belt+braces
            bad.append((i, "spans multiple lines", line))
            continue
        if require_ids:
            ts = obj.get("ts")
            if not isinstance(ts, (int, float)):
                bad.append((i, "missing/non-numeric 'ts'", line))
                continue
            if not isinstance(obj.get("run_id"), str):
                bad.append((i, "missing/non-string 'run_id'", line))
                continue
        if obj.get("kind") == "registry":
            unknown = _unknown_components(obj)
            if unknown:
                bad.append((
                    i,
                    f"unknown component label(s) {sorted(set(unknown))} "
                    f"(known: {sorted(KNOWN_COMPONENTS)})",
                    line,
                ))
    return bad


def check_trace_events(doc: Any) -> List[str]:
    """Lint a merged Chrome trace (``TraceCollector`` format); returns
    human-readable problems (empty = clean)."""
    bad: List[str] = []
    if not isinstance(doc, list):
        return [f"trace document is {type(doc).__name__}, expected a "
                f"JSON array of events"]
    last_ts = None
    for i, ev in enumerate(doc):
        if not isinstance(ev, dict):
            bad.append(f"event {i}: not an object")
            continue
        if "pid" not in ev:
            bad.append(f"event {i} ({ev.get('name')!r}): missing 'pid'")
        if ev.get("ph") != "X":
            continue  # metadata events carry no timeline
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)) or ts < 0:
            bad.append(
                f"event {i} ({ev.get('name')!r}): missing/negative 'ts'"
            )
            continue
        if last_ts is not None and ts < last_ts:
            bad.append(
                f"event {i} ({ev.get('name')!r}): ts {ts} < previous "
                f"{last_ts} — X events must be timestamp-monotone"
            )
        last_ts = ts
        args = ev.get("args")
        if not isinstance(args, dict) or "trace_id" not in args:
            bad.append(
                f"event {i} ({ev.get('name')!r}): args must carry a "
                f"'trace_id' key (null for untraced spans)"
            )
    return bad


def check_flightrec(doc: Any) -> List[str]:
    """Lint a flight-recorder dump (telemetry/flightrec.py format)."""
    bad: List[str] = []
    if not isinstance(doc, dict):
        return [f"flightrec document is {type(doc).__name__}, expected "
                f"a JSON object"]
    if not isinstance(doc.get("reason"), str) or not doc.get("reason"):
        bad.append("missing/empty 'reason'")
    if not isinstance(doc.get("pid"), int):
        bad.append("missing/non-integer 'pid'")
    if not isinstance(doc.get("run_id"), str):
        bad.append("missing/non-string 'run_id'")
    events = doc.get("events")
    if not isinstance(events, list):
        bad.append("missing/non-list 'events'")
        return bad
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            bad.append(f"event {i}: not an object")
            continue
        if not isinstance(ev.get("ts"), (int, float)):
            bad.append(f"event {i}: missing/non-numeric 'ts'")
        if not isinstance(ev.get("kind"), str):
            bad.append(f"event {i}: missing/non-string 'kind'")
    return bad


# the canonical phase vocabulary of one cluster round — kept in
# LOCKSTEP with telemetry/profiler.PHASES (a test pins the pair, same
# idiom as the nemesis corpus pin) so a transport rework that renames
# or adds a phase must update the lint AND the docs together.  The
# binary transport (utils/frames.py) reuses these names — the phases
# are transport-generic costs (frame encode IS client_serialize), and
# one vocabulary is what keeps a line-vs-binary A/B directly
# comparable.
KNOWN_BUDGET_PHASES = frozenset({
    "client_serialize",
    "wire",
    "server_queue_wait",
    "server_parse",
    "wal_append",
    "scatter_apply",
    "response_serialize",
    "server_other",
    "client_parse",
})


def check_budget(doc: Any) -> List[str]:
    """Lint a latency-budget artifact (telemetry/profiler.py
    ``write_budget_artifact`` format)."""
    bad: List[str] = []
    if not isinstance(doc, dict):
        return [f"budget document is {type(doc).__name__}, expected a "
                f"JSON object"]
    if not isinstance(doc.get("ts"), (int, float)):
        bad.append("missing/non-numeric 'ts'")
    if not isinstance(doc.get("run_id"), str):
        bad.append("missing/non-string 'run_id'")
    budgets = doc.get("budgets")
    if not isinstance(budgets, dict) or not budgets:
        bad.append("missing/empty 'budgets' object")
        return bad
    for verb, b in budgets.items():
        if not isinstance(b, dict):
            bad.append(f"budget {verb!r}: not an object")
            continue
        phases = b.get("phases")
        if not isinstance(phases, list) or not phases:
            bad.append(f"budget {verb!r}: missing/empty 'phases'")
            continue
        for p in phases:
            if not isinstance(p, dict) or not isinstance(
                p.get("phase"), str
            ):
                bad.append(f"budget {verb!r}: phase without a name")
                continue
            if p["phase"] not in KNOWN_BUDGET_PHASES:
                bad.append(
                    f"budget {verb!r}: unknown phase {p['phase']!r} "
                    f"(not in the canonical vocabulary — update "
                    f"KNOWN_BUDGET_PHASES + telemetry/profiler.PHASES "
                    f"together)"
                )
            for field in ("p50_ms", "pct"):
                v = p.get(field)
                if not isinstance(v, (int, float)) or v < 0:
                    bad.append(
                        f"budget {verb!r} phase {p.get('phase')!r}: "
                        f"missing/negative {field!r}"
                    )
        # additivity: with both endpoints instrumented the phase
        # percentages must close the books on the round
        if b.get("coverage") == "full" and b.get("round_ms"):
            total = sum(
                p.get("pct", 0) for p in phases
                if isinstance(p.get("pct"), (int, float))
            )
            if not 90.0 <= total <= 110.0:
                bad.append(
                    f"budget {verb!r}: phase percentages sum to "
                    f"{round(total, 1)} (full coverage requires "
                    f"100 ± 10)"
                )
    return bad


def _find_timeline_payloads(doc: Any) -> List[Tuple[str, dict]]:
    """Locate TimelineRecorder payloads in a document: the document
    itself when it carries a ``series`` list, else any value of an
    ``arms``/``timelines``/``timeline`` mapping that does."""
    found: List[Tuple[str, dict]] = []
    if not isinstance(doc, dict):
        return found
    if isinstance(doc.get("series"), list):
        return [("<root>", doc)]
    for key in ("timeline", "metric_timeline"):
        sub = doc.get(key)
        if isinstance(sub, dict) and isinstance(sub.get("series"), list):
            found.append((key, sub))
    for key in ("arms", "timelines"):
        group = doc.get(key)
        if isinstance(group, dict):
            for name, sub in group.items():
                found.extend(
                    (f"{key}.{name}{'' if w == '<root>' else '.' + w}", p)
                    for w, p in _find_timeline_payloads(sub)
                )
    return found


def _check_one_timeline(where: str, tl: dict) -> List[str]:
    bad: List[str] = []
    interval = tl.get("interval_s")
    if not isinstance(interval, (int, float)) or interval <= 0:
        bad.append(f"{where}: missing/non-positive 'interval_s'")
        interval = None
    metrics_present = set()
    for i, series in enumerate(tl.get("series", [])):
        if not isinstance(series, dict):
            bad.append(f"{where}: series[{i}] is not an object")
            continue
        metric = series.get("metric")
        if isinstance(metric, str):
            metrics_present.add(metric)
        label = f"{where}: series[{i}] ({metric!r})"
        points = series.get("points")
        if not isinstance(points, list):
            bad.append(f"{label}: missing/non-list 'points'")
            continue
        ts_prev = None
        gaps: List[float] = []
        for j, pt in enumerate(points):
            if (not isinstance(pt, (list, tuple)) or len(pt) != 2
                    or not isinstance(pt[0], (int, float))
                    or not isinstance(pt[1], (int, float))):
                bad.append(f"{label}: points[{j}] is not a numeric "
                           f"[ts, value] pair")
                continue
            ts = float(pt[0])
            if ts_prev is not None:
                if ts < ts_prev:
                    bad.append(
                        f"{label}: timestamps regress at points[{j}] "
                        f"({ts} < {ts_prev})"
                    )
                gaps.append(ts - ts_prev)
            ts_prev = ts
        # cadence: the MEDIAN gap must honour the declared interval —
        # tolerant of a few legitimate long gaps (process pauses, gauge
        # probes returning None) but not of a sampler that drifted
        if interval is not None and len(gaps) >= 3:
            gaps.sort()
            median_gap = gaps[len(gaps) // 2]
            if median_gap > 3.0 * interval:
                bad.append(
                    f"{label}: cadence jitter — median inter-point gap "
                    f"{median_gap:.4f}s exceeds 3x interval_s "
                    f"({interval}s)"
                )
    for i, rec in enumerate(tl.get("anomalies", [])):
        if not isinstance(rec, dict):
            bad.append(f"{where}: anomalies[{i}] is not an object")
            continue
        if not isinstance(rec.get("ts"), (int, float)):
            bad.append(f"{where}: anomalies[{i}] missing numeric 'ts'")
        metric = rec.get("metric")
        if metric not in metrics_present:
            bad.append(
                f"{where}: anomalies[{i}] references metric {metric!r} "
                f"but the artifact carries no series for it — an "
                f"anomaly without its evidence is unfalsifiable"
            )
    for i, mark in enumerate(tl.get("marks", [])):
        if not isinstance(mark, dict) or not isinstance(
            mark.get("ts"), (int, float)
        ):
            bad.append(f"{where}: marks[{i}] missing numeric 'ts'")
    return bad


def check_timeline(doc: Any) -> List[str]:
    """Lint a metric-timeline artifact (telemetry/timeline.py
    ``TimelineRecorder.payload()`` shape, docs/observability.md) —
    standalone or embedded under ``arms``/``timelines``."""
    if not isinstance(doc, dict):
        return [f"timeline document is {type(doc).__name__}, expected "
                f"a JSON object"]
    payloads = _find_timeline_payloads(doc)
    if not payloads:
        return ["no timeline payload found (need a 'series' list at "
                "the root or under 'arms'/'timelines')"]
    bad: List[str] = []
    for where, tl in payloads:
        bad.extend(_check_one_timeline(where, tl))
    return bad


def _check_json_artifact(path: str, checker) -> List[str]:
    try:
        with open(path) as f:
            doc = json.load(f)
    except ValueError as e:
        return [f"not valid JSON: {e}"]
    return checker(doc)


def main(argv: List[str]) -> int:
    require_ids = True
    mode = "lines"
    jobs: List[Tuple[str, str]] = []  # (mode, path)
    for a in argv:
        if a == "--allow-missing-ids":
            require_ids = False
        elif a == "--trace":
            mode = "trace"
        elif a == "--flightrec":
            mode = "flightrec"
        elif a == "--budget":
            mode = "budget"
        elif a == "--timeline":
            mode = "timeline"
        elif a == "--lines":
            mode = "lines"
        elif a in ("-h", "--help"):
            print(__doc__)
            return 0
        else:
            jobs.append((mode, a))
    if not jobs:
        print("usage: check_metric_lines.py [--allow-missing-ids] "
              "[--trace|--flightrec|--budget|--timeline|--lines] "
              "<file|-> ...",
              file=sys.stderr)
        return 2
    failed = False
    for mode, path in jobs:
        if mode in ("trace", "flightrec", "budget", "timeline"):
            checker = {
                "trace": check_trace_events,
                "flightrec": check_flightrec,
                "budget": check_budget,
                "timeline": check_timeline,
            }[mode]
            problems = _check_json_artifact(path, checker)
            for reason in problems:
                failed = True
                print(f"{path}: {reason}", file=sys.stderr)
            print(f"{path}: {mode} artifact, {len(problems)} problems")
            continue
        if path == "-":
            lines = sys.stdin.read().splitlines()
            name = "<stdin>"
        else:
            with open(path) as f:
                lines = f.read().splitlines()
            name = path
        bad = check_lines(lines, require_ids=require_ids)
        for lineno, reason, line in bad:
            failed = True
            shown = line if len(line) <= 120 else line[:117] + "..."
            print(f"{name}:{lineno}: {reason}: {shown}", file=sys.stderr)
        print(f"{name}: {len(lines)} lines, {len(bad)} malformed")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
