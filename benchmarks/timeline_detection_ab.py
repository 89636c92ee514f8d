"""Timeline detection A/B: does the timeline plane NAME the straggler?

An observability plane that cannot be falsified is decoration.  This
benchmark runs the committed ``straggler-storm-SSP`` nemesis schedule
(nemesis/corpus/straggler_storm_ssp.json: a 10 ms both-ways delay
seeded onto shard 0 at round 3, cleared at round 8) TWICE with an
attached :class:`~telemetry.timeline.TimelineRecorder`:

  * **fault arm** — the schedule as committed.  The skew tracker and
    online detectors watch the per-shard RTT series
    (``cluster_shard_rtt_seconds{shard,worker}``, p99 field) and must
    ATTRIBUTE the slowdown to shard 0 within **3 sample windows** of
    the delay op's ``mark()`` on the timeline — detection latency is
    the measured number, not a vibe.
  * **oracle arm** — the same scenario with the ops stripped
    (``Scenario.with_ops(())``): identical workload, identical seeds,
    zero faults.  The detectors must stay SILENT — a single anomaly
    firing here is a false positive and fails the run.

Attribution counts from whichever speaks first: a flagged
:class:`~telemetry.timeline.SkewTracker` verdict naming shard 0 (the
entities are each other's control group, so no pre-fault baseline is
needed — critical here, because the schedule gives the detectors only
~3 quiet rounds of warmup) or a detector anomaly on a
shard-0-labelled series.

Artifacts: ``results/<platform>/soak_timeline.{md,json}`` — the JSON
carries both arms' timeline payloads (series filtered to the metrics
under test so the committed file stays reviewable), self-linted by
``tools/check_metric_lines.py --timeline`` before anything is
written, plus a ``payloads`` list ``tools/bench_history.py`` folds
into the perf ledger (detection latency in seconds — lower is
better).

Usage::

    JAX_PLATFORMS=cpu python benchmarks/timeline_detection_ab.py \
        [--interval 0.05] [--out results/cpu]
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

METRIC = "cluster_shard_rtt_seconds"
# metrics worth committing in the artifact: the series under test,
# the attribution gauges, and the anomaly counter
KEEP_METRICS = (METRIC, "skew_ratio", "timeline_anomalies_total")
CORPUS = os.path.join(
    REPO, "flink_parameter_server_tpu", "nemesis", "corpus",
    "straggler_storm_ssp.json",
)


def _build_timeline(registry, interval_s: float):
    from flink_parameter_server_tpu.telemetry.detectors import (
        EWMADriftDetector,
        RollingMADDetector,
    )
    from flink_parameter_server_tpu.telemetry.timeline import (
        SkewTracker,
        TimelineRecorder,
    )

    # window=4: the schedule's post-onset evidence budget is 3 sample
    # windows, so a per-entity median over a long window would still be
    # dominated by pre-fault points when the deadline passes.
    # ratio_threshold=1.7: with only TWO entities the baseline
    # (median-of-medians) averages the straggler in, bounding the
    # max/baseline ratio below 2 — so 1.7 sits between the oracle
    # arm's measured noise ceiling (~1.5) and the fault arm's ~1.9.
    # warmup_evals=6: the first windows price connection setup, not
    # steady-state service time, and with 2 shards the asymmetry
    # transiently mimics skew.
    skew = SkewTracker(
        METRIC, entity_label="shard", field="p99",
        window=4, min_points=2, ratio_threshold=1.7,
        warmup_evals=6,
    )
    detectors = [
        EWMADriftDetector(METRIC, field="p99", k=6.0, warmup=8),
        RollingMADDetector(METRIC, field="p99", window=16, k=8.0,
                           warmup=12),
    ]
    return TimelineRecorder(
        registry, interval_s=interval_s, detectors=detectors,
        skew=[skew],
    ), skew


def run_arm(name: str, scenario, *, interval_s: float) -> dict:
    from flink_parameter_server_tpu.nemesis.runner import run_scenario
    from flink_parameter_server_tpu.telemetry.registry import (
        MetricsRegistry,
    )

    reg = MetricsRegistry()
    tl, skew = _build_timeline(reg, interval_s)
    wal_root = tempfile.mkdtemp(prefix=f"timeline-ab-{name}-")
    try:
        report = run_scenario(
            scenario, wal_root=wal_root, registry=reg, timeline=tl,
        )
    finally:
        shutil.rmtree(wal_root, ignore_errors=True)
    payload = tl.payload()
    payload["series"] = [
        s for s in payload["series"] if s["metric"] in KEEP_METRICS
    ]
    return {
        "arm": name,
        "ok": report.ok,
        "rounds": report.rounds,
        "wall_s": report.wall_s,
        "timeline": payload,
        "skew_history": [dict(v) for v in skew.history],
        "anomalies": list(tl.anomalies()),
        "marks": list(tl._marks),
    }


def _fault_onset_ts(arm: dict):
    for mark in arm["marks"]:
        if mark.get("label") == "nemesis_op" and (
            mark.get("action") == "delay"
        ):
            return mark["ts"], str(mark.get("shard"))
    return None, None


def attribute(arm: dict, *, interval_s: float) -> dict:
    """Detection verdict for the fault arm: the first timeline signal
    naming the seeded shard at/after fault onset, in seconds and in
    sample windows."""
    onset, shard = _fault_onset_ts(arm)
    if onset is None:
        return {"detected": False, "reason": "no delay op marked"}
    candidates = []
    for v in arm["skew_history"]:
        if v.get("flagged") and v.get("entity") == shard and (
            v["ts"] >= onset
        ):
            candidates.append(("skew", v["ts"], v.get("ratio")))
            break
    for a in arm["anomalies"]:
        if a.get("ts", 0.0) >= onset and (
            str((a.get("labels") or {}).get("shard")) == shard
        ):
            candidates.append((a.get("kind", "anomaly"), a["ts"],
                               a.get("score")))
            break
    if not candidates:
        return {
            "detected": False, "shard": shard, "onset_ts": onset,
            "reason": "no signal named the seeded shard",
        }
    via, ts, strength = min(candidates, key=lambda c: c[1])
    latency = ts - onset
    return {
        "detected": True,
        "shard": shard,
        "onset_ts": onset,
        "detect_ts": ts,
        "via": via,
        "strength": strength,
        "latency_s": round(latency, 4),
        "windows": math.ceil(latency / interval_s),
    }


def run_detection_ab(*, interval_s: float = 0.05) -> dict:
    from flink_parameter_server_tpu.nemesis.scenarios import Scenario

    with open(CORPUS) as f:
        scenario = Scenario.from_json(f.read())
    oracle_scenario = scenario.with_ops(())

    fault = run_arm("fault", scenario, interval_s=interval_s)
    oracle = run_arm("oracle", oracle_scenario, interval_s=interval_s)

    detection = attribute(fault, interval_s=interval_s)
    oracle_flagged = [
        v for v in oracle["skew_history"] if v.get("flagged")
    ]
    return {
        "interval_s": interval_s,
        "scenario": scenario.name,
        "arms": {"fault": fault, "oracle": oracle},
        "detection": detection,
        "oracle_anomalies": len(oracle["anomalies"]),
        "oracle_skew_flags": len(oracle_flagged),
        "passed": bool(
            detection.get("detected")
            and detection.get("windows", 99) <= 3
            and len(oracle["anomalies"]) == 0
            and not oracle_flagged
        ),
    }


def write_artifacts(r: dict, out_dir: str) -> None:
    from flink_parameter_server_tpu.telemetry.registry import (
        default_run_id,
    )
    from tools.check_metric_lines import check_timeline

    det = r["detection"]
    doc = {
        "ts": round(time.time(), 3),
        "run_id": default_run_id(),
        "kind": "timeline_detection_ab",
        "scenario": r["scenario"],
        "interval_s": r["interval_s"],
        "detection": det,
        "oracle_anomalies": r["oracle_anomalies"],
        "oracle_skew_flags": r["oracle_skew_flags"],
        "passed": r["passed"],
        "arms": {
            name: {
                "ok": arm["ok"],
                "rounds": arm["rounds"],
                "wall_s": arm["wall_s"],
                "anomaly_count": len(arm["anomalies"]),
                "timeline": arm["timeline"],
            }
            for name, arm in r["arms"].items()
        },
        "payloads": [
            {"metric": "straggler detection latency",
             "value": det.get("latency_s", -1.0), "unit": "seconds"},
            {"metric": "straggler detection windows",
             "value": float(det.get("windows", -1)),
             "unit": "sample windows"},
            {"metric": "oracle false-positive anomalies",
             "value": float(r["oracle_anomalies"]),
             "unit": "firings"},
        ],
        "host": {"cpus": os.cpu_count()},
    }
    bad = check_timeline(doc)
    if bad:
        raise SystemExit(
            f"timeline_detection_ab: artifact failed its own lint: "
            f"{bad}"
        )
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "soak_timeline.json"), "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")

    fault, oracle = r["arms"]["fault"], r["arms"]["oracle"]
    top = r["arms"]["fault"]["skew_history"]
    peak = max((v.get("ratio", 0.0) for v in top), default=0.0)
    md = f"""# Timeline detection A/B — {r['scenario']}

The committed straggler schedule (10 ms both-ways delay on shard 0,
rounds 3–8) run twice with a live `TimelineRecorder`
({r['interval_s']}s cadence) watching
`cluster_shard_rtt_seconds{{shard,worker}}` p99: once as committed,
once with the ops stripped (the fault-free oracle — same workload,
same seeds, zero faults).  Attribution = the first flagged
`SkewTracker` verdict naming the seeded shard, or the first detector
anomaly on a shard-0 series, whichever speaks first.

| arm | rounds | invariants ok | anomaly firings | verdict |
|---|---|---|---|---|
| fault | {fault['rounds']} | {fault['ok']} | \
{len(fault['anomalies'])} | named shard {det.get('shard')} via \
{det.get('via')} in {det.get('latency_s')}s \
({det.get('windows')} windows) |
| oracle | {oracle['rounds']} | {oracle['ok']} | \
{len(oracle['anomalies'])} | silent \
({r['oracle_skew_flags']} skew flags) |

**Detection: {"PASS" if r['passed'] else "FAIL"}** — the seeded shard
was named within {det.get('windows')} sample window(s) of the delay
op's timeline mark (bar: 3), and the oracle arm fired
{r['oracle_anomalies']} anomalies (bar: 0).  Peak skew ratio on the
fault arm: {peak:.2f}x the fleet median (flag threshold 1.7x — with
only two shards the median-of-medians baseline averages the
straggler in, so ~2x is the ceiling; the first 6 verdicts are
warmup-suppressed because connection setup transiently mimics skew).  The skew tracker speaks first
by construction here: the schedule leaves the drift detectors only
~3 quiet rounds of warmup, while the entities-as-control-group
comparison needs no baseline at all.

Produced by `benchmarks/timeline_detection_ab.py`; linted by
`tools/check_metric_lines.py --timeline`; folded into the perf
ledger by `tools/bench_history.py` (payloads list); pinned by
tests/test_timeline.py (committed-artifact lint).
"""
    with open(os.path.join(out_dir, "soak_timeline.md"), "w") as f:
        f.write(md)


def main() -> int:
    from flink_parameter_server_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--interval", type=float, default=0.05)
    p.add_argument("--out", default=os.path.join(REPO, "results", "cpu"))
    args = p.parse_args()
    r = run_detection_ab(interval_s=args.interval)
    # the md needs skew_history; write before trimming nothing — the
    # artifact writer reads r["arms"][...]["skew_history"] directly
    write_artifacts(r, args.out)
    det = r["detection"]
    print(json.dumps({
        "metric": "timeline straggler detection latency",
        "value": det.get("latency_s"),
        "unit": "seconds",
        "extra": {
            "windows": det.get("windows"),
            "via": det.get("via"),
            "shard": det.get("shard"),
            "oracle_anomalies": r["oracle_anomalies"],
            "oracle_skew_flags": r["oracle_skew_flags"],
            "passed": r["passed"],
        },
    }))
    return 0 if r["passed"] else 1


if __name__ == "__main__":
    sys.exit(main())
