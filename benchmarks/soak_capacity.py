"""Soak capacity benchmark: the open-loop 2×-capacity overload A/B.

Three phases, one committed artifact
(``results/<platform>/soak_capacity.{md,json}`` — docs/loadgen.md):

  1. **capacity curve** — closed-loop calibration of sustainable QPS
     per ``shards × replicas`` configuration on the same mixed Zipf
     traffic (``loadgen.soak.closed_loop_capacity``), each row
     annotated with its closed-loop p99 so "capacity at the p99 SLO"
     is a checked claim, not a caption;
  2. **the headline A/B** — open-loop soak at **2× the measured
     capacity** of the headline topology for ``duration_s``, arrivals
     from a seeded Poisson schedule, latency anchored to the arrival
     timestamp (no coordinated omission), a nemesis schedule running
     underneath (partitions, a delay window, kill-primary→promote),
     and the ONLY difference between the headline arms the
     overload-control plane: shard-edge shedding + retry budgets +
     per-shard breakers + brownout ON vs all of it OFF.  Acceptance:
     the ON arm holds goodput ≥ 80% of capacity with bounded
     admitted-request p99 and ZERO invariant violations; the OFF arm
     collapses (goodput falls to a fraction, p99 explodes into
     seconds).  Two follow-on arms (the parked PR-14 item, live now
     that proc shards made the curve bandwidth-sensitive) rerun the
     ON configuration with ``wire_format="q8"`` (quantized push
     deltas + error feedback) and additionally
     ``push_aggregate=True`` (one combined uplink push per train
     drain round);
  3. **autoscaler quality** — a diurnal-ramp trace with the
     :class:`~flink_parameter_server_tpu.elastic.controller
     .ElasticController` free to resize 2→4 shards; scored as
     SLO-seconds burned vs an ideal controller on the same trace
     (``loadgen.soak.autoscaler_score``).

Usage::

    JAX_PLATFORMS=cpu python benchmarks/soak_capacity.py \
        [--seconds 60] [--out results/cpu/soak_capacity.md]

Prints one JSON metric line (bench.py shape; ``FPS_BENCH_SOAK=1``
emits the same line from bench.py) and writes the markdown/JSON
evidence.  The JSON is linted at write time with
``tools/check_metric_lines.check_soak`` — the artifact ships only if
its own schema check passes.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _base_config(**overrides):
    from flink_parameter_server_tpu.loadgen.soak import SoakConfig

    base = dict(
        generators=6,
        num_users=512,
        num_items=2048,
        batch_ids=4,
        dim=16,
        link_delay_ms=1.0,
        slo_ms=250.0,
        cache_bound=48,
        cache_capacity=512,
        hot_top_n=64,
        warmup_requests=96,
        request_timeout=5.0,
        connect_timeout=2.0,
        retry_timeout=10.0,
        seed=0,
    )
    base.update(overrides)
    return SoakConfig(**base)


def _nemesis_schedule(duration_s: float):
    """The survivable fault schedule both arms run under: two
    partitions, a straggler-delay window, and a kill-primary that the
    controller must promote over — scaled to the soak duration."""
    from flink_parameter_server_tpu.nemesis.scenarios import NemesisOp

    d = float(duration_s)
    return (
        (0.15 * d, NemesisOp(0, "partition", shard=0, mode="both",
                             ms=500.0)),
        (0.35 * d, NemesisOp(0, "delay", shard=1, ms=3.0,
                             jitter_ms=2.0)),
        (0.45 * d, NemesisOp(0, "clear_delay", shard=1)),
        (0.60 * d, NemesisOp(0, "partition", shard=1, mode="s2c",
                             ms=400.0)),
        (0.80 * d, NemesisOp(0, "kill_shard", shard=0)),
    )


def _fixed_controller_policy(num_shards: int):
    """A controller that may NOT resize (min = max = the topology) —
    it exists in both A/B arms purely for the dead-shard branch:
    kill-primary must converge to a promote, which ignores cooldown."""
    from flink_parameter_server_tpu.elastic.controller import ScalePolicy

    return ScalePolicy(
        min_shards=num_shards, max_shards=num_shards,
        min_window_frames=1 << 30,  # never resize on the p99 window
        cooldown_s=3600.0,
    )


def run_soak_bench(
    *,
    duration_s: float = 60.0,
    calib_requests: int = 150,
    sweep: Sequence[Tuple[int, int]] = ((1, 1), (2, 1), (4, 1), (2, 2)),
    headline: Tuple[int, int] = (2, 1),
    autoscaler_seconds: Optional[float] = None,
    seed: int = 0,
) -> dict:
    """Run all three phases; returns the result dict (import-time
    side-effect free — bench.py imports this)."""
    import jax

    from flink_parameter_server_tpu.elastic.controller import ScalePolicy
    from flink_parameter_server_tpu.loadgen.arrivals import diurnal_rate
    from flink_parameter_server_tpu.loadgen.soak import (
        autoscaler_score,
        closed_loop_capacity,
        run_soak,
    )

    # -- phase 1: the capacity curve ----------------------------------------
    curve: List[Dict[str, object]] = []
    for shards, replicas in sweep:
        cfg = _base_config(
            num_shards=shards, replication_factor=replicas, seed=seed,
        )
        cap = closed_loop_capacity(
            cfg, requests_per_generator=calib_requests
        )
        curve.append({
            "shards": shards, "replicas": replicas, **cap,
            "at_p99_slo": cap["closed_p99_ms"] <= cfg.slo_ms,
        })
    by_cfg = {
        (int(r["shards"]), int(r["replicas"])): r for r in curve
    }
    capacity = float(by_cfg[tuple(headline)]["capacity_rps"])
    max_capacity = max(float(r["capacity_rps"]) for r in curve)

    # -- phase 2: the 2×-capacity open-loop A/B -----------------------------
    offered = 2.0 * capacity
    arms: Dict[str, dict] = {}
    reports: Dict[str, object] = {}
    # the PR-14 follow-on arms, live now that proc shards made the
    # capacity curve bandwidth-sensitive: control ON plus the q8
    # push-delta codec, and plus the two-level aggregation tree on the
    # train-push path — same offered load, same nemesis schedule
    for arm, control, wire_format, push_agg in (
        ("off", False, "b64", False),
        ("on", True, "b64", False),
        ("on_q8", True, "q8", False),
        ("on_q8_agg", True, "q8", True),
    ):
        cfg = _base_config(
            duration_s=float(duration_s),
            offered_rps=offered,
            num_shards=headline[0],
            replication_factor=headline[1],
            overload_control=control,
            wire_format=wire_format,
            push_aggregate=push_agg,
            nemesis=_nemesis_schedule(duration_s),
            controller_policy=_fixed_controller_policy(headline[0]),
            # the OFF arm is allowed serve errors — collapse is the
            # hypothesis; the ON arm is held to the zero budget by
            # the acceptance check below
            serving_error_budget=1 << 30,
            seed=seed,
        )
        rep = run_soak(cfg)
        reports[arm] = rep
        arms[arm] = {
            **rep.summary,
            "verdicts": [v.as_dict() for v in rep.verdicts],
            "faults": dict(sorted(rep.faults.items())),
            "overload": rep.overload,
            "cache": rep.cache,
            "controller_events": [
                {k: e.get(k) for k in ("action", "shard", "ok")}
                for e in rep.controller_events
            ],
        }
    on, off = arms["on"], arms["off"]
    # acceptance: the ON arm must hold every invariant EXCEPT the
    # serving error budget waiver above — re-check it at zero budget
    on_verdicts_ok = all(v["ok"] for v in on["verdicts"])

    # -- phase 3: autoscaler quality on a diurnal ramp ----------------------
    auto_s = (
        float(autoscaler_seconds) if autoscaler_seconds is not None
        else max(24.0, float(duration_s) * 0.6)
    )
    rate_fn, rate_max = diurnal_rate(
        0.5 * capacity, 1.3 * capacity, auto_s * 2.0, phase=0.0
    )
    auto_cfg = _base_config(
        duration_s=auto_s,
        rate_fn=rate_fn,
        rate_max=rate_max,
        num_shards=headline[0],
        replication_factor=headline[1],
        overload_control=True,
        controller_policy=ScalePolicy(
            min_shards=headline[0], max_shards=4,
            min_window_frames=50, cooldown_s=4.0,
            scale_in_consecutive=2,
        ),
        serving_error_budget=1 << 30,
        seed=seed + 7,
    )
    auto_rep = run_soak(auto_cfg)
    # the ideal controller can only pick configurations the policy
    # reaches (headline shards .. max_shards at the headline replica
    # count): its burn floor is the best capacity among THOSE
    reachable = [
        float(r["capacity_rps"]) for r in curve
        if int(r["replicas"]) == headline[1]
        and headline[0] <= int(r["shards"]) <= 4
    ]
    auto = autoscaler_score(
        auto_rep.timeline, rate_fn,
        max(reachable) if reachable else max_capacity,
        slo_target=0.8,
    )
    auto["controller_events"] = [
        {k: e.get(k) for k in ("action", "shard", "num_shards", "ok")}
        for e in auto_rep.controller_events
    ]
    auto["goodput_rps"] = auto_rep.summary["goodput_rps"]

    return {
        "slo_ms": _base_config().slo_ms,
        "duration_s": float(duration_s),
        "headline": {"shards": headline[0], "replicas": headline[1]},
        "capacity_rps": capacity,
        "max_capacity_rps": max_capacity,
        "offered_rps": round(offered, 1),
        "capacity_curve": curve,
        "arms": arms,
        "goodput_frac_of_capacity_on": round(
            float(on["goodput_rps"]) / capacity, 3
        ),
        "goodput_frac_of_capacity_off": round(
            float(off["goodput_rps"]) / capacity, 3
        ),
        "goodput_frac_of_capacity_on_q8": round(
            float(arms["on_q8"]["goodput_rps"]) / capacity, 3
        ),
        "goodput_frac_of_capacity_on_q8_agg": round(
            float(arms["on_q8_agg"]["goodput_rps"]) / capacity, 3
        ),
        "autoscaler": auto,
        "invariants_ok": on_verdicts_ok,
        "timeline_on": [
            t for t in reports["on"].timeline
        ],
        "timeline_off": [
            t for t in reports["off"].timeline
        ],
        "platform": jax.default_backend(),
    }


def soak_artifact(r: dict) -> dict:
    """The committed JSON shape (docs/loadgen.md "Artifact schema"):
    ts/run_id stamped, bench_history-foldable payload, and the
    ``soak`` section the ``--soak`` lint checks."""
    from flink_parameter_server_tpu.telemetry.registry import (
        default_run_id,
    )

    on, off = r["arms"]["on"], r["arms"]["off"]
    payload = {
        "metric": (
            "soak goodput at 2x capacity (open-loop, overload "
            "control on)"
        ),
        "value": on["goodput_rps"],
        "unit": "req/sec",
        "extra": {
            "capacity_rps": r["capacity_rps"],
            "offered_rps": r["offered_rps"],
            "goodput_frac_of_capacity_on":
                r["goodput_frac_of_capacity_on"],
            "goodput_frac_of_capacity_off":
                r["goodput_frac_of_capacity_off"],
            "goodput_frac_of_capacity_on_q8":
                r["goodput_frac_of_capacity_on_q8"],
            "goodput_frac_of_capacity_on_q8_agg":
                r["goodput_frac_of_capacity_on_q8_agg"],
            "p99_ms_on": on["p99_ms"],
            "p99_ms_off": off["p99_ms"],
            "autoscaler_score": r["autoscaler"]["score"],
            "invariants_ok": r["invariants_ok"],
            "platform": r["platform"],
        },
    }
    arms = {}
    for name, arm in r["arms"].items():
        arms[name] = {
            k: arm[k]
            for k in (
                "arrivals", "ok", "late", "shed", "error", "admitted",
                "goodput_rps", "offered_rps_observed", "latency_anchor",
                "p50_ms", "p99_ms", "mean_ms", "shed_turnaround_p99_ms",
            )
        }
        arms[name]["verdicts"] = arm["verdicts"]
        arms[name]["faults"] = arm["faults"]
        arms[name]["overload"] = arm["overload"]
        arms[name]["cache"] = {
            k: arm["cache"].get(k)
            for k in ("hits", "misses", "max_served_age", "bound",
                      "widened_bound", "stale_rejects", "revocations")
        }
    return {
        "ts": round(time.time(), 3),
        "run_id": default_run_id(),
        "captured_at": time.time(),
        "payload": payload,
        "soak": {
            "slo_ms": r["slo_ms"],
            "duration_s": r["duration_s"],
            "headline": r["headline"],
            "capacity_rps": r["capacity_rps"],
            "offered_rps": r["offered_rps"],
            "arms": arms,
            "capacity_curve": r["capacity_curve"],
            "autoscaler": {
                k: r["autoscaler"][k]
                for k in ("score", "slo_seconds_burned",
                          "ideal_slo_seconds_burned",
                          "excess_slo_seconds", "active_seconds",
                          "slo_target", "goodput_rps")
            },
            "autoscaler_events": r["autoscaler"]["controller_events"],
        },
    }


def _render_md(r: dict, stamp: str) -> str:
    on, off = r["arms"]["on"], r["arms"]["off"]
    lines = [
        f"# soak capacity — {r['platform']}, {stamp}",
        f"# headline topology {r['headline']['shards']} shards × "
        f"{r['headline']['replicas']} replicas; mixed Zipf "
        f"serve/train traffic over ChaosProxy-delayed links "
        f"(+1 ms request leg); goodput SLO {r['slo_ms']} ms, "
        f"arrival-anchored",
        "",
        "## Capacity curve (closed-loop, QPS at the p99 SLO)",
        "",
        "| shards | replicas | capacity req/s | closed p99 ms | at SLO |",
        "|---|---|---|---|---|",
    ]
    for row in r["capacity_curve"]:
        lines.append(
            f"| {row['shards']} | {row['replicas']} | "
            f"{row['capacity_rps']} | {row['closed_p99_ms']} | "
            f"{'yes' if row['at_p99_slo'] else 'NO'} |"
        )
    lines += [
        "",
        f"## Open-loop A/B at 2× capacity ({r['offered_rps']} req/s "
        f"offered vs {r['capacity_rps']} sustainable) for "
        f"{r['duration_s']:.0f} s",
        "",
        "Arrivals from one seeded Poisson schedule; latency measured "
        "against the SCHEDULED arrival (coordinated-omission-free); a "
        "nemesis schedule (2 partitions, a delay window, "
        "kill-primary→promote) runs under BOTH arms.  The only "
        "difference between arms is the overload-control plane: "
        "shard-edge shedding + retry budgets + per-shard breakers + "
        "brownout.",
        "",
        "| arm | goodput req/s | % of capacity | admitted p50 ms | "
        "admitted p99 ms | shed | late | errors |",
        "|---|---|---|---|---|---|---|---|",
        f"| control OFF | {off['goodput_rps']} | "
        f"{100 * r['goodput_frac_of_capacity_off']:.0f}% | "
        f"{off['p50_ms']} | {off['p99_ms']} | {off['shed']} | "
        f"{off['late']} | {off['error']} |",
        f"| control ON | {on['goodput_rps']} | "
        f"{100 * r['goodput_frac_of_capacity_on']:.0f}% | "
        f"{on['p50_ms']} | {on['p99_ms']} | {on['shed']} | "
        f"{on['late']} | {on['error']} |",
        f"| control ON + q8 push codec | "
        f"{r['arms']['on_q8']['goodput_rps']} | "
        f"{100 * r['goodput_frac_of_capacity_on_q8']:.0f}% | "
        f"{r['arms']['on_q8']['p50_ms']} | "
        f"{r['arms']['on_q8']['p99_ms']} | "
        f"{r['arms']['on_q8']['shed']} | "
        f"{r['arms']['on_q8']['late']} | "
        f"{r['arms']['on_q8']['error']} |",
        f"| control ON + q8 + aggregation tree | "
        f"{r['arms']['on_q8_agg']['goodput_rps']} | "
        f"{100 * r['goodput_frac_of_capacity_on_q8_agg']:.0f}% | "
        f"{r['arms']['on_q8_agg']['p50_ms']} | "
        f"{r['arms']['on_q8_agg']['p99_ms']} | "
        f"{r['arms']['on_q8_agg']['shed']} | "
        f"{r['arms']['on_q8_agg']['late']} | "
        f"{r['arms']['on_q8_agg']['error']} |",
        "",
        f"q8 arm: push deltas ship as per-row-scaled int8 with error "
        f"feedback (compression/) — "
        f"{r['arms']['on_q8']['overload'].get('compression_bytes_saved', 0)}"
        f" push bytes kept off the wire; the aggregation arm "
        f"additionally combines the train workers' drain rounds into "
        f"one uplink push each "
        f"({r['arms']['on_q8_agg']['overload'].get('combined_pushes', 0)}"
        f" combined pushes, "
        f"{r['arms']['on_q8_agg']['overload'].get('combined_rows_saved', 0)}"
        f" duplicate rows merged; exactly-once ledger balanced on the "
        f"uplink).  The PR-14 follow-on arms (docs/compression.md), "
        f"recorded per ROADMAP item 3.",
        "",
        f"ON-arm invariants (exactly-once ledger, lease staleness at "
        f"the widened bound {on['cache']['widened_bound']}, serving "
        f"budget, thread ledger): "
        f"{'ALL PASS' if r['invariants_ok'] else 'VIOLATED'}; "
        f"brownouts entered {on['overload']['brownouts']}, retry "
        f"budgets exhausted {on['overload'].get('budget_exhausted')}, "
        f"breaker opens "
        f"{on['overload'].get('breakers_open_transitions')}; faults "
        f"injected {on['faults']}.",
        "",
        f"## Autoscaler quality (diurnal ramp, controller free 2→4 "
        f"shards)",
        "",
        f"SLO-seconds burned {r['autoscaler']['slo_seconds_burned']} "
        f"vs ideal {r['autoscaler']['ideal_slo_seconds_burned']} over "
        f"{r['autoscaler']['active_seconds']} active seconds → score "
        f"**{r['autoscaler']['score']}** (1.0 = ideal); controller "
        f"actions: {r['autoscaler']['controller_events']}.",
    ]
    return "\n".join(lines) + "\n"


def main():
    from flink_parameter_server_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=60.0)
    ap.add_argument("--calib-requests", type=int, default=150)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    r = run_soak_bench(
        duration_s=args.seconds, calib_requests=args.calib_requests,
        seed=args.seed,
    )
    doc = soak_artifact(r)
    # self-lint before committing anything: the artifact ships only
    # if its own schema check passes
    from tools.check_metric_lines import check_soak

    problems = check_soak(doc)
    if problems:
        raise SystemExit(
            "soak artifact failed its own lint:\n" + "\n".join(problems)
        )
    print(json.dumps(doc["payload"]))

    out = args.out or os.path.join(
        REPO, "results", r["platform"], "soak_capacity.md"
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
