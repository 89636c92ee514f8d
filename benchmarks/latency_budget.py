"""Latency-budget benchmark: decompose a cluster round, phase by phase.

Runs a 2-shard, 1-worker online-MF cluster job with the latency-budget
profiler (telemetry/profiler.py) and the span tracer on, then:

  * assembles the per-verb phase budget (client serialize → wire →
    server queue-wait → WAL append → scatter/apply → response
    serialize → client parse);
  * checks the budget's pull round against the SPAN-TRACE ORACLE — the
    p50 of the client ring's ``pull_batch`` spans, measured completely
    independently of the phase timers — and reports the coverage error
    (the acceptance bar is ≤10%);
  * reports wire byte/frame totals (utils/net.py accounting) — the
    bytes-on-wire baseline ROADMAP item 4 is judged against.

The phases land in the process registry, so a subsequent
``build_run_report()`` (``benchmarks/telemetry_overhead.py`` main runs
this bench before writing the report) carries the latency-budget
section docs/perf_status.md cites for the ROADMAP item 2 transport
rework.

Usage::

    JAX_PLATFORMS=cpu python benchmarks/latency_budget.py \
        [--rounds 60] [--batch 512] [--shards 2]

Prints one JSON metric line (bench.py shape).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Optional

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def run_budget_bench(
    *,
    rounds: int = 60,
    batch: int = 512,
    num_shards: int = 2,
    num_items: int = 2_048,
    num_users: int = 512,
    dim: int = 16,
    seed: int = 0,
    wal_dir: Optional[str] = None,
    wire_proto: str = "auto",
    wire_format: str = "b64",
) -> dict:
    """One profiled cluster run; returns the budget + oracle verdict.
    Import-time side-effect free — tests call this with tiny shapes.
    Phases accumulate in the CURRENT process registry/profiler (the
    run-report section reads them from there)."""
    from flink_parameter_server_tpu.cluster.driver import (
        ClusterConfig,
        ClusterDriver,
    )
    from flink_parameter_server_tpu.models.matrix_factorization import (
        OnlineMatrixFactorization,
        SGDUpdater,
    )
    from flink_parameter_server_tpu.telemetry.profiler import get_profiler
    from flink_parameter_server_tpu.utils.initializers import normal_factor

    rng = np.random.default_rng(seed)
    batches = [
        {
            "user": rng.integers(0, num_users, batch).astype(np.int32),
            "item": ((rng.zipf(1.2, batch) - 1) % num_items).astype(
                np.int32
            ),
            "rating": rng.normal(0, 1, batch).astype(np.float32),
        }
        for _ in range(rounds)
    ]
    logic = OnlineMatrixFactorization(
        num_users, dim, updater=SGDUpdater(0.01)
    )
    cfg = ClusterConfig(
        num_shards=num_shards, num_workers=1, staleness_bound=0,
        trace=True, profile=True, wal_dir=wal_dir,
        wire_proto=wire_proto, wire_format=wire_format,
    )
    driver = ClusterDriver(
        logic, capacity=num_items, value_shape=(dim,),
        init_fn=normal_factor(1, (dim,)), config=cfg,
    )
    with driver:
        # warmup: the first rounds pay jit compiles (client step fn,
        # shard scatter buckets) that belong to no steady-state phase
        driver.run(batches[: min(5, rounds)])
        result = driver.run(batches)
        prof = get_profiler()
        budget = prof.budget_report()
        # the span-trace oracle: p50 of the client's per-shard
        # `pull.shard<k>` spans — one wall measurement covering
        # serialize → wire → parse, timed by the tracer, completely
        # independent of the phase timers the budget sums.  (batch ≤
        # chunk keeps one frame per span, so per-frame phases and
        # per-span walls describe the same window.)
        pulls = sorted(
            s["dur"] for s in driver.client_tracer.spans()
            if s["name"].startswith("pull.shard")
        )
    oracle_p50_ms = (
        round(pulls[len(pulls) // 2] * 1e3, 4) if pulls else None
    )
    pull_budget = budget.get("pull", {})
    round_ms = pull_budget.get("round_ms")
    coverage_err = (
        round(abs(round_ms - oracle_p50_ms) / oracle_p50_ms, 4)
        if round_ms and oracle_p50_ms else None
    )
    return {
        "budget": budget,
        "oracle_pull_p50_ms": oracle_p50_ms,
        "budget_round_ms": round_ms,
        "coverage_error": coverage_err,
        "coverage_ok": (
            coverage_err is not None and coverage_err <= 0.10
        ),
        "top_phase": pull_budget.get("top_phase"),
        "top_pct": pull_budget.get("top_pct"),
        "updates_per_sec": round(result.updates_per_sec, 1),
        "rounds": rounds,
        "batch": batch,
        "num_shards": num_shards,
        "wire_proto": wire_proto,
        "wire_format": wire_format,
    }


def main() -> None:
    from flink_parameter_server_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--rounds", type=int, default=60)
    p.add_argument("--batch", type=int, default=512)
    p.add_argument("--shards", type=int, default=2)
    args = p.parse_args()
    r = run_budget_bench(
        rounds=args.rounds, batch=args.batch, num_shards=args.shards
    )
    print(json.dumps({
        "metric": "latency budget (per-phase cost attribution, "
                  f"{args.shards}-shard cluster round)",
        "value": r["top_pct"],
        "unit": f"% of pull round in top phase ({r['top_phase']})",
        "extra": {
            k: v for k, v in r.items() if k != "budget"
        },
    }))
    for verb, b in sorted(r["budget"].items()):
        print(f"# {verb}: round p50 {b['round_ms']} ms, top "
              f"{b['top_phase']} ({b['top_pct']}%)", file=sys.stderr)


if __name__ == "__main__":
    main()
