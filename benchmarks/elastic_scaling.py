"""Elastic scaling benchmark: 1 → 2 → 4 shards, MID-TRAINING.

The static sweep (benchmarks/cluster_scaling.py) measures shard counts
in isolation; this one measures the thing elasticity actually sells —
resizing WHILE the job runs.  One online-MF stream trains through
:class:`~flink_parameter_server_tpu.elastic.ElasticClusterDriver`; a
control thread fires ``scale_out`` twice (1→2 at ~⅓ of the stream,
2→4 at ~⅔), and the report answers the three questions that decide
whether live resize is usable:

  * **throughput** — updates/sec BEFORE the first resize, DURING the
    resize windows, and AFTER the last one (a resize should dent, not
    crater, the rate);
  * **stall** — the ``elastic_migration_stall_seconds`` p50/p99: how
    long writes to MOVING keys were frozen (non-moving keys never
    block; with per-shard WALs the freeze covers only the log-tail
    catch-up, not the bulk transfer);
  * **hedging** — backup-pull win rate under the same load (how often
    the budgeted second connection beat a straggling primary).

Plus the exactly-once audit: unique delta rows acked by the clients
vs rows applied across every shard ever live — equal or the run is
broken.

On one host the shards share cores, so rising updates/sec is NOT the
claim (see docs/perf_status.md); the honest claims are the stall
ceiling, the reject/retry overhead visible as the during-window dip,
and zero lost/duplicated updates.

Usage::

    JAX_PLATFORMS=cpu python benchmarks/elastic_scaling.py \
        [--rounds 48] [--batch 2048] [--workers 2] \
        [--out results/cpu/elastic_scaling.md]
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


def run_elastic_bench(
    *,
    num_users: int = 2_000,
    num_items: int = 8_192,
    dim: int = 16,
    batch: int = 2_048,
    rounds: int = 256,
    num_workers: int = 2,
    window: int = 8,
    chunk: int = 1_024,
    hedge_after_s: float = 0.02,
    seed: int = 0,
) -> dict:  # rounds default gives the post-resize phase real runway
    """Run the mid-training 1→2→4 scale-out; returns the phase rates,
    stall percentiles, hedging stats and the exactly-once audit.
    Import-time side-effect free (bench.py imports and calls this)."""
    import jax

    from flink_parameter_server_tpu.data.movielens import synthetic_ratings
    from flink_parameter_server_tpu.data.streams import microbatches
    from flink_parameter_server_tpu.elastic import (
        ElasticClusterConfig,
        ElasticClusterDriver,
    )
    from flink_parameter_server_tpu.models.matrix_factorization import (
        OnlineMatrixFactorization,
        SGDUpdater,
    )
    from flink_parameter_server_tpu.telemetry.registry import MetricsRegistry
    from flink_parameter_server_tpu.utils.initializers import (
        ranged_random_factor,
    )

    cols = synthetic_ratings(num_users, num_items, rounds * batch, seed=seed)
    batches = list(microbatches(cols, batch))
    init = ranged_random_factor(seed + 1, (dim,))
    reg = MetricsRegistry()
    logic = OnlineMatrixFactorization(
        num_users, dim, updater=SGDUpdater(0.01), seed=seed
    )
    import tempfile

    with tempfile.TemporaryDirectory(prefix="fps-elastic-bench-") as wal:
        driver = ElasticClusterDriver(
            logic,
            capacity=num_items,
            value_shape=(dim,),
            init_fn=init,
            config=ElasticClusterConfig(
                num_shards=1,
                num_workers=num_workers,
                staleness_bound=0,
                window=window,
                chunk=chunk,
                wal_dir=wal,
                hedge_after_s=hedge_after_s,
            ),
            registry=reg,
        )
        driver.start()
        c_rounds = reg.counter(
            "cluster_worker_rounds_total", component="cluster"
        )
        resize_windows = []  # (t_start, t_end, shards_after)
        stop_poll = threading.Event()
        samples = []  # (t, worker_rounds)

        def poller():
            while not stop_poll.wait(0.01):
                samples.append((time.monotonic(), c_rounds.value))

        def controller():
            # fire 1→2 at ~⅓ of the stream; fire 2→4 a couple of
            # rounds after the first resize LANDS (a fixed second
            # round index could fall past the end of a fast stream —
            # the dent a resize makes is what we're here to measure,
            # so both must actually fire)
            target = rounds * num_workers // 5
            for add in (1, 2):
                while c_rounds.value < target and not stop_poll.is_set():
                    time.sleep(0.005)
                if stop_poll.is_set():
                    return
                t0 = time.monotonic()
                driver.scale_out(add)
                resize_windows.append(
                    (t0, time.monotonic(), driver.partitioner.num_shards)
                )
                target = c_rounds.value + 2 * num_workers

        threads = [
            threading.Thread(target=poller, daemon=True),
            threading.Thread(target=controller, daemon=True),
        ]
        for t in threads:
            t.start()
        result = driver.run(batches, timeout=600.0)
        stop_poll.set()
        for t in threads:
            t.join(timeout=30)

        # the exactly-once audit: unique rows acked == rows applied
        rows_acked = sum(c.rows_pushed for c in driver._clients)
        rows_applied = sum(sh.rows_applied for sh in driver.all_shards)
        hedged = sum(
            i.value for i in reg.instruments()
            if i.name == "elastic_hedged_pulls_total"
        )
        hedges_won = sum(
            i.value for i in reg.instruments()
            if i.name == "elastic_hedges_won_total"
        )
        stall = None
        for i in reg.instruments():
            if i.name == "elastic_migration_stall_seconds" and i.count:
                stall = i
        rows_migrated = sum(
            i.value for i in reg.instruments()
            if i.name == "elastic_rows_migrated_total"
        )
        final_epoch = driver.membership.current().epoch
        driver.stop()

    def rate_between(t_lo, t_hi):
        """updates/sec from the sampled worker-rounds counter (each
        worker-round processes ~batch/num_workers masked events)."""
        inside = [(t, r) for t, r in samples if t_lo <= t <= t_hi]
        if len(inside) < 2:
            return None
        dt = inside[-1][0] - inside[0][0]
        dr = inside[-1][1] - inside[0][1]
        if dt <= 0:
            return None
        return dr * (batch / num_workers) / dt

    t_run0 = samples[0][0] if samples else 0.0
    t_run1 = samples[-1][0] if samples else 0.0
    if resize_windows:
        before = rate_between(t_run0, resize_windows[0][0])
        during = rate_between(
            resize_windows[0][0], resize_windows[-1][1]
        )
        after = rate_between(resize_windows[-1][1], t_run1)
    else:  # no resize fired (stream too short): whole-run rate
        before = during = after = rate_between(t_run0, t_run1)

    return {
        "updates_per_sec_before": (
            round(before, 1) if before is not None else None
        ),
        "updates_per_sec_during": (
            round(during, 1) if during is not None else None
        ),
        "updates_per_sec_after": (
            round(after, 1) if after is not None else None
        ),
        "updates_per_sec_overall": round(result.updates_per_sec, 1),
        "resizes": [
            {
                "wall_s": round(t1 - t0, 3),
                "shards_after": n,
            }
            for t0, t1, n in resize_windows
        ],
        "migration_stall_p50_ms": (
            round(stall.percentile(50) * 1e3, 3) if stall else None
        ),
        "migration_stall_p99_ms": (
            round(stall.percentile(99) * 1e3, 3) if stall else None
        ),
        "rows_migrated": int(rows_migrated),
        "hedged_pulls": int(hedged),
        "hedges_won": int(hedges_won),
        "hedge_win_rate": (
            round(hedges_won / hedged, 3) if hedged else None
        ),
        "final_epoch": int(final_epoch),
        "final_shards": (
            resize_windows[-1][2] if resize_windows else 1
        ),
        "rows_acked": int(rows_acked),
        "rows_applied": int(rows_applied),
        "exactly_once": bool(rows_acked == rows_applied),
        "events": result.events,
        "rounds": rounds,
        "batch": batch,
        "num_workers": num_workers,
        "num_items": num_items,
        "dim": dim,
        "hedge_after_s": hedge_after_s,
        "platform": jax.default_backend(),
    }


def main():
    from flink_parameter_server_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=256)
    ap.add_argument("--batch", type=int, default=2_048)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--num-items", type=int, default=8_192)
    ap.add_argument("--dim", type=int, default=16)
    ap.add_argument("--hedge-after-ms", type=float, default=20.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    r = run_elastic_bench(
        rounds=args.rounds, batch=args.batch, num_workers=args.workers,
        num_items=args.num_items, dim=args.dim,
        hedge_after_s=args.hedge_after_ms / 1e3,
    )
    payload = {
        "metric": "elastic scaling (mid-training 1→2→4 scale-out)",
        "value": r["updates_per_sec_after"],
        "unit": "updates/sec (post-resize)",
        "extra": r,
    }
    print(json.dumps(payload))

    out = args.out or os.path.join(
        REPO, "results", r["platform"], "elastic_scaling.md"
    )
    stamp = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    lines = [
        f"# elastic scaling (mid-training 1→2→4) — {r['platform']}, "
        f"{stamp}",
        f"# items={r['num_items']} dim={r['dim']} batch={r['batch']} "
        f"rounds={r['rounds']} workers={r['num_workers']} bound=0 "
        f"hedge_after={r['hedge_after_s'] * 1e3:.0f}ms",
        "# thread-backed shards on ONE host: arms share cores — the",
        "# claims this artifact backs are the stall ceiling, the",
        "# during-resize dip, and the exactly-once audit (see",
        "# docs/perf_status.md)",
        "",
        "| phase | updates/sec |",
        "|---|---|",
        f"| before (1 shard) | {r['updates_per_sec_before']} |",
        f"| during resizes | {r['updates_per_sec_during']} |",
        f"| after (4 shards) | {r['updates_per_sec_after']} |",
        "",
        f"- migration stall p50/p99: {r['migration_stall_p50_ms']} / "
        f"{r['migration_stall_p99_ms']} ms over {r['rows_migrated']} "
        f"migrated rows, epochs 0→{r['final_epoch']}",
        f"- hedged pulls: {r['hedged_pulls']} issued, "
        f"{r['hedges_won']} won "
        f"(win rate {r['hedge_win_rate']})",
        f"- exactly-once audit: {r['rows_acked']} rows acked == "
        f"{r['rows_applied']} applied → {r['exactly_once']}",
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
