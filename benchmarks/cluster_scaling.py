"""Cluster scaling benchmark: 1 → 2 → 4 shards, same MF job.

The cluster runtime's reason to exist is scaling the store past one
owner — so the evidence is a shard sweep: the SAME online-MF stream
(synthetic MovieLens-shaped ratings, Zipf-hot items) trained through
:class:`~flink_parameter_server_tpu.cluster.ClusterDriver` at 1, 2 and
4 shards, reporting per arm:

  * updates/sec (masked rating events / wall),
  * pull RTT p50/p99 from the client-side
    ``cluster_pull_rtt_seconds`` histogram (the tail-latency column —
    stragglers live in the p99),
  * coalescing counters (duplicate pulls/pushes saved from the wire),
  * staleness + block counts from the clock (BSP arms should read 0
    momentary staleness at the end and real block counts).

On one host the arms share cores, so updates/sec is NOT expected to
rise linearly — the honest claims this file supports are (a) the wire
protocol + coalescing + pipelining overhead per shard count, and (b)
pull-p99 behaviour as the key space spreads.  Cross-host scaling needs
real NICs; docs/perf_status.md says exactly which claims this artifact
can back.

Usage::

    JAX_PLATFORMS=cpu python benchmarks/cluster_scaling.py \
        [--rounds 30] [--batch 2048] [--workers 2] \
        [--out results/cpu/cluster_scaling.md]

Prints one JSON line (bench.py's metric-line shape) and writes the
markdown/JSON evidence next to the other off-chip results.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def run_cluster_bench(
    *,
    shard_counts=(1, 2, 4),
    num_users: int = 2_000,
    num_items: int = 8_192,
    dim: int = 16,
    batch: int = 2_048,
    rounds: int = 30,
    num_workers: int = 2,
    staleness_bound: int = 0,
    window: int = 8,
    chunk: int = 1_024,
    seed: int = 0,
    shard_procs: bool = False,
) -> dict:
    """Run the shard sweep; returns {"arms": [...], config...}.

    Import-time side-effect free (bench.py imports and calls this) —
    jax is imported lazily here.
    """
    import jax

    from flink_parameter_server_tpu.cluster import (
        ClusterConfig,
        ClusterDriver,
    )
    from flink_parameter_server_tpu.data.movielens import synthetic_ratings
    from flink_parameter_server_tpu.data.streams import microbatches
    from flink_parameter_server_tpu.models.matrix_factorization import (
        OnlineMatrixFactorization,
        SGDUpdater,
    )
    from flink_parameter_server_tpu.telemetry.registry import MetricsRegistry
    from flink_parameter_server_tpu.utils.initializers import (
        ranged_random_factor,
    )

    cols = synthetic_ratings(
        num_users, num_items, rounds * batch, seed=seed
    )
    batches = list(microbatches(cols, batch))
    # proc arms need a PICKLABLE init spec (cluster/procs.py); the
    # thread arms keep the historical jax init so the pre-existing
    # curve stays comparable round over round
    proc_init = {"kind": "hashed_uniform", "scale": 0.1, "seed": seed}
    init = (
        None if shard_procs else ranged_random_factor(seed + 1, (dim,))
    )

    arms = []
    for n_shards in shard_counts:
        # per-arm registry: the RTT histogram must not mix arms
        reg = MetricsRegistry()
        logic = OnlineMatrixFactorization(
            num_users, dim, updater=SGDUpdater(0.01), seed=seed
        )
        driver = ClusterDriver(
            logic,
            capacity=num_items,
            value_shape=(dim,),
            init_fn=init,
            config=ClusterConfig(
                num_shards=n_shards,
                num_workers=num_workers,
                staleness_bound=staleness_bound,
                window=window,
                chunk=chunk,
                shard_procs=shard_procs,
                proc_init=proc_init if shard_procs else None,
            ),
            registry=reg,
        )
        with driver:
            # warm-up round outside the timed window (jit compile +
            # connection setup); run() walks the full list, so time a
            # fresh run after a 1-batch warm-up
            driver.run(batches[:1])
            result = driver.run(batches)
        rtt = None
        for inst in reg.instruments():
            if inst.name == "cluster_pull_rtt_seconds":
                rtt = inst
                break
        coalesced_pulls = sum(
            c.pulls_coalesced for c in driver._clients
        ) if driver._clients else 0
        arms.append({
            "num_shards": n_shards,
            "updates_per_sec": round(result.updates_per_sec, 1),
            "events": result.events,
            "rounds": result.rounds,
            "wall_s": round(result.wall_s, 3),
            "pull_p50_ms": (
                round(rtt.percentile(50) * 1e3, 3) if rtt else None
            ),
            "pull_p99_ms": (
                round(rtt.percentile(99) * 1e3, 3) if rtt else None
            ),
            "pull_frames": rtt.count if rtt else 0,
            "staleness_final": result.clock["staleness"],
            "block_counts": result.clock["block_counts"],
            "shard_pushes": [s["pushes"] for s in result.shard_stats],
        })
    return {
        "arms": arms,
        "num_users": num_users,
        "num_items": num_items,
        "dim": dim,
        "batch": batch,
        "rounds": rounds,
        "num_workers": num_workers,
        "staleness_bound": staleness_bound,
        "window": window,
        "chunk": chunk,
        "shard_procs": shard_procs,
        "cpus": os.cpu_count(),
        "platform": jax.default_backend(),
    }


def main():
    from flink_parameter_server_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--batch", type=int, default=2_048)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--num-items", type=int, default=8_192)
    ap.add_argument("--dim", type=int, default=16)
    ap.add_argument("--bound", type=int, default=0)
    ap.add_argument("--threads-only", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    common = dict(
        rounds=args.rounds, batch=args.batch, num_workers=args.workers,
        num_items=args.num_items, dim=args.dim,
        staleness_bound=args.bound,
    )
    threads = run_cluster_bench(shard_procs=False, **common)
    procs = (
        None if args.threads_only
        else run_cluster_bench(shard_procs=True, **common)
    )

    def ratio(i):
        if procs is None:
            return None
        t = threads["arms"][i]["updates_per_sec"]
        p = procs["arms"][i]["updates_per_sec"]
        return round(p / t, 2) if t else None

    headline = (procs or threads)["arms"]
    best = max(a["updates_per_sec"] for a in headline)
    payload = {
        # the canonical ledger metric name (bench.py emits the same):
        # renaming it would orphan the r01..r05 history in
        # tools/bench_history.py — the best arm is now the proc sweep's
        "metric": "cluster scaling (multi-shard PS, online MF)",
        "value": best,
        "unit": "updates/sec (best arm)",
        "extra": {
            "threads": threads,
            "procs": procs,
            "proc_over_thread": (
                [ratio(i) for i in range(len(threads["arms"]))]
                if procs else None
            ),
        },
    }
    print(json.dumps(payload))

    out = args.out or os.path.join(
        REPO, "results", threads["platform"], "cluster_scaling.md"
    )
    stamp = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    cpus = threads["cpus"]
    lines = [
        f"# cluster scaling (1/2/4 shards) — {threads['platform']}, "
        f"{stamp}",
        f"# items={threads['num_items']} dim={threads['dim']} "
        f"batch={threads['batch']} rounds={threads['rounds']} "
        f"workers={threads['num_workers']} "
        f"bound={threads['staleness_bound']} window={threads['window']} "
        f"cpus={cpus}",
        "# thread shards share ONE GIL (the flat-to-inverted curve); "
        "proc shards",
        "# (cluster/procs.py, binary transport) are the GIL escape — "
        "on a host with",
        "# cores >= shards the proc curve rises; on this "
        f"{cpus}-CPU container the",
        "# processes time-share one core, so the honest evidence is "
        "the per-arm",
        "# proc/thread ratio and the collapse -> gentle-slope shape "
        "change.",
        "",
        "| shards | threads upd/s | procs upd/s | procs/threads | "
        "threads p99 ms | procs p99 ms |",
        "|---|---|---|---|---|---|",
    ]
    for i, a in enumerate(threads["arms"]):
        p = procs["arms"][i] if procs else None
        lines.append(
            f"| {a['num_shards']} | {a['updates_per_sec']} "
            f"| {p['updates_per_sec'] if p else '-'} "
            f"| {ratio(i) if p else '-'} "
            f"| {a['pull_p99_ms']} "
            f"| {p['pull_p99_ms'] if p else '-'} |"
        )
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        f.write("\n".join(lines) + "\n")
    with open(os.path.splitext(out)[0] + ".json", "w") as f:
        json.dump({"captured_at": time.time(), "payload": payload}, f,
                  indent=1)
    print(f"# wrote {out}", file=sys.stderr)


if __name__ == "__main__":
    main()
