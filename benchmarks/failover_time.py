"""Failover-time benchmark: kill a replicated primary mid-train-while-
serve, measure the follower flip against a full WAL rebuild.

The replica-chain claim (docs/elastic.md) is quantitative: promotion
completes in **O(lag)** — the records the follower had not yet applied
plus the dead primary's unshipped tail — while ``replace_shard``
rebuilds **O(log)** (deterministic init + full replay) and stalls every
read for the range meanwhile.  This harness measures both on the same
log length, on the real stack:

  * train online MF on a 2-shard replicated cluster
    (``ReplicatedClusterDriver``, 1 follower per primary) while a
    serving reader pulls through the chains
    (``FollowerLookupService``);
  * kill shard 0's primary mid-stream, promote its follower
    (``promote_shard`` — fence, catch-up, salvage, one epoch flip),
    and report:

      - ``failover_seconds`` — kill → membership publish (reads route
        to the promoted primary from here),
      - ``reads_served_during_failover`` / ``read_errors`` — the
        serving window's zero-error claim, measured not asserted,
      - ``lag_records_at_promote`` / salvage + catch-up counts,
      - ``promoted_bitwise_equal`` — the post-flip audit: the promoted
        table vs a scratch replay of its own log;

  * after the run, kill shard 1 (whose WAL saw the same traffic shape)
    and time ``replace_shard`` — the O(log) yardstick
    (``replace_seconds``, ``replace_records_replayed``).

Usage::

    JAX_PLATFORMS=cpu python benchmarks/failover_time.py \
        [--rounds 192] [--batch 128] [--out results/cpu/failover_time.md]

Prints one JSON line (bench.py metric-line shape) and writes md/json
evidence under results/<platform>/ (folded into the perf ledger by
tools/bench_history.py).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def run_failover_bench(
    *,
    num_users: int = 256,
    num_items: int = 2_048,
    dim: int = 16,
    batch: int = 128,
    rounds: int = 192,
    num_workers: int = 2,
    replication_factor: int = 1,
    kill_after_rounds: int = 32,
    seed: int = 0,
    workdir: str = None,
) -> dict:
    """Run the kill/promote/replace experiment; returns the metrics
    dict.  Import-time side-effect free (bench.py imports this)."""
    import shutil
    import tempfile

    import jax

    from flink_parameter_server_tpu.data.movielens import synthetic_ratings
    from flink_parameter_server_tpu.data.streams import microbatches
    from flink_parameter_server_tpu.models.matrix_factorization import (
        OnlineMatrixFactorization,
        SGDUpdater,
    )
    from flink_parameter_server_tpu.replication import (
        ReplicatedClusterConfig,
        ReplicatedClusterDriver,
    )
    from flink_parameter_server_tpu.replication.failover import (
        verify_against_log,
    )
    from flink_parameter_server_tpu.serving.follower import (
        FollowerLookupService,
    )
    from flink_parameter_server_tpu.telemetry.registry import MetricsRegistry
    from flink_parameter_server_tpu.utils.initializers import (
        ranged_random_factor,
    )

    cols = synthetic_ratings(num_users, num_items, rounds * batch,
                             seed=seed)
    batches = list(microbatches(cols, batch))
    init = ranged_random_factor(3, (dim,))
    reg = MetricsRegistry()
    tmp = workdir or tempfile.mkdtemp(prefix="fps_failover_bench_")
    made_tmp = workdir is None
    try:
        logic = OnlineMatrixFactorization(
            num_users, dim, updater=SGDUpdater(0.01), seed=1
        )
        driver = ReplicatedClusterDriver(
            logic, capacity=num_items, value_shape=(dim,), init_fn=init,
            config=ReplicatedClusterConfig(
                num_shards=2, num_workers=num_workers,
                wal_dir=os.path.join(tmp, "wal"),
                replication_factor=replication_factor,
                follower_staleness_bound=None,  # serving reads keep
                # flowing at any lag during the incident window
            ),
            registry=reg,
        )
        driver.start()
        serve = FollowerLookupService(
            driver.membership, (dim,), registry=reg
        )
        read_errors = []
        reads = []  # timestamps of successful lookups
        stop_reader = threading.Event()

        def reader():
            ids = np.arange(0, min(64, num_items))
            while not stop_reader.is_set():
                try:
                    serve.lookup(ids)
                    reads.append(time.perf_counter())
                except Exception as e:  # noqa: BLE001 — measured, not raised
                    read_errors.append(f"{type(e).__name__}: {e}")
                time.sleep(0.001)

        rounds_c = reg.counter(
            "cluster_worker_rounds_total", component="cluster"
        )
        timeline = {}
        promote_report = []

        def control():
            deadline = time.monotonic() + 120
            while (
                rounds_c.value < kill_after_rounds * num_workers
                and time.monotonic() < deadline
            ):
                time.sleep(0.002)
            timeline["killed_at"] = time.perf_counter()
            driver.kill_shard(0)
            promote_report.append(driver.promote_shard(0))
            timeline["promoted_at"] = time.perf_counter()

        reader_t = threading.Thread(target=reader, daemon=True)
        control_t = threading.Thread(target=control, daemon=True)
        reader_t.start()
        control_t.start()
        result = driver.run(batches, timeout=300)
        control_t.join(timeout=60)
        stop_reader.set()
        reader_t.join(timeout=10)
        serve.close()
        if not promote_report:
            raise RuntimeError("the failover never ran")
        rep = promote_report[0]
        window = (timeline["killed_at"], timeline["promoted_at"])
        reads_during = sum(1 for t in reads if window[0] <= t <= window[1])
        bitwise = verify_against_log(driver.shards[0])

        # the O(log) yardstick: rebuild shard 1 from its full WAL (the
        # same traffic shape and log length as the promoted shard saw)
        shard1_records = driver.shards[1].stats()["wal_records"]
        driver.kill_shard(1)
        t0 = time.perf_counter()
        replayed = driver.replace_shard(1)
        replace_seconds = time.perf_counter() - t0
        driver.stop()
        return {
            "failover_seconds": round(rep.failover_seconds, 4),
            "replace_seconds": round(replace_seconds, 4),
            "speedup_vs_replace": round(
                replace_seconds / max(rep.failover_seconds, 1e-9), 1
            ),
            "reads_served_during_failover": reads_during,
            "reads_served_total": len(reads),
            "read_errors": len(read_errors),
            "read_error_samples": read_errors[:3],
            "lag_records_at_promote": rep.lag_records_at_promote,
            "records_caught_up": rep.records_caught_up,
            "records_salvaged": rep.records_salvaged,
            "promoted_bitwise_equal": bool(bitwise),
            "replace_records_replayed": replayed,
            "wal_records_at_replace": shard1_records,
            "rounds": rounds,
            "batch": batch,
            "num_items": num_items,
            "dim": dim,
            "num_workers": num_workers,
            "replication_factor": replication_factor,
            "updates_per_sec": round(result.updates_per_sec, 1),
            "platform": jax.default_backend(),
        }
    finally:
        if made_tmp:
            shutil.rmtree(tmp, ignore_errors=True)


def main():
    from flink_parameter_server_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=192)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--num-items", type=int, default=2_048)
    ap.add_argument("--dim", type=int, default=16)
    ap.add_argument("--kill-after", type=int, default=32)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    r = run_failover_bench(
        rounds=args.rounds, batch=args.batch, num_items=args.num_items,
        dim=args.dim, kill_after_rounds=args.kill_after,
    )
    payload = {
        "metric": "replica-chain failover (kill primary mid-train-while-serve)",
        "value": r["failover_seconds"],
        "unit": "seconds",
        "extra": r,
    }
    print(json.dumps(payload))

    out = args.out or os.path.join(
        REPO, "results", r["platform"], "failover_time.md"
    )
    stamp = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    lines = [
        f"# replica-chain failover — {r['platform']}, {stamp}",
        f"# items={r['num_items']} dim={r['dim']} batch={r['batch']} "
        f"rounds={r['rounds']} workers={r['num_workers']} "
        f"factor={r['replication_factor']}",
        "",
        "| failover_s | replace_s (full WAL rebuild) | speedup | "
        "reads during failover | read errors | lag at promote | "
        "salvaged | bitwise |",
        "|---|---|---|---|---|---|---|---|",
        f"| {r['failover_seconds']} | {r['replace_seconds']} "
        f"| {r['speedup_vs_replace']}x "
        f"| {r['reads_served_during_failover']} | {r['read_errors']} "
        f"| {r['lag_records_at_promote']} | {r['records_salvaged']} "
        f"| {r['promoted_bitwise_equal']} |",
    ]
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        f.write("\n".join(lines) + "\n")
    with open(os.path.splitext(out)[0] + ".json", "w") as f:
        json.dump({"captured_at": time.time(), "payload": payload}, f,
                  indent=1)
    print(f"# wrote {out}", file=sys.stderr)


if __name__ == "__main__":
    main()
