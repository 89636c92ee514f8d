"""Serving-path benchmark: top-K QPS + latency percentiles + snapshot
staleness, measured TRAIN-WHILE-SERVE (the subsystem's whole point:
queries answered while the trainer keeps pushing).

Harness shape: a StreamingDriver trains online MF on a synthetic
Zipf-skewed rating stream with ``serve_with`` attached; ``concurrency``
client threads hammer ``topk`` queries through the in-process
:class:`ServingClient` (the admission batcher coalesces them into
bucketed microbatches) for ``duration_s`` seconds.  Reported:

  * achieved QPS (completed queries / wall time),
  * request latency p50/p90/p99 (admission → answer),
  * snapshot staleness (steps behind the trainer) per answer —
    mean/max over the run — plus the publish cadence that bought it,
  * batch-fill ratio and rejection count (admission-queue health),
  * trainer updates/sec alongside, so the serve path's cost to the
    train path is visible in one row.

Usage::

    JAX_PLATFORMS=cpu python benchmarks/serving_qps.py \
        [--duration 5] [--concurrency 8] [--out results/cpu/serving_qps.md]

Prints one JSON line (same shape as bench.py's metric lines) and writes
the markdown/JSON evidence next to the other off-chip results.
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


def run_serving_bench(
    *,
    num_users: int = 2_000,
    num_items: int = 8_192,
    dim: int = 32,
    batch: int = 4_096,
    k: int = 10,
    duration_s: float = 5.0,
    concurrency: int = 8,
    publish_every: int = 4,
    max_batch: int = 64,
    max_delay_ms: float = 2.0,
    max_queue: int = 512,
    seed: int = 0,
) -> dict:
    """Run the train-while-serve load test; returns the metrics dict.

    Import-time side-effect free (bench.py imports and calls this) —
    jax is imported lazily here.
    """
    import jax

    from flink_parameter_server_tpu.core.store import ShardedParamStore
    from flink_parameter_server_tpu.data.movielens import synthetic_ratings
    from flink_parameter_server_tpu.data.streams import microbatches
    from flink_parameter_server_tpu.models.matrix_factorization import (
        OnlineMatrixFactorization,
        SGDUpdater,
    )
    from flink_parameter_server_tpu.training.driver import (
        DriverConfig,
        StreamingDriver,
    )
    from flink_parameter_server_tpu.serving import QueueFull
    from flink_parameter_server_tpu.utils.initializers import normal_factor

    logic = OnlineMatrixFactorization(
        num_users, dim, updater=SGDUpdater(0.01)
    )
    store = ShardedParamStore.create(
        num_items, (dim,), init_fn=normal_factor(1, (dim,))
    )
    driver = StreamingDriver(
        logic, store, config=DriverConfig(dump_model=False)
    )
    service = driver.serve_with(
        publish_every=publish_every, max_batch=max_batch,
        max_delay_ms=max_delay_ms, max_queue=max_queue,
    )
    client = service.client()

    # enough epochs to outlast the load window; request_stop() ends it
    cols = synthetic_ratings(num_users, num_items, 50 * batch, seed=seed)
    stream = microbatches(cols, batch, epochs=10_000, shuffle_seed=seed)
    trainer = threading.Thread(
        target=lambda: driver.run(stream, collect_outputs=False),
        daemon=True,
    )
    trainer.start()
    # warm-up gate: version 2 = the first snapshot carrying worker state
    if not service.wait_for_snapshot(60, min_version=2):
        driver.request_stop()
        raise RuntimeError("trainer never published a serving snapshot")
    # compile the query kernels outside the timed window (one bucket
    # shape per occupancy bucket; the load loop reuses them)
    client.top_k(0, k=k)

    stop = threading.Event()
    completed = []
    staleness = []
    rejected = [0]
    lock = threading.Lock()

    def load(worker_idx: int):
        rng = np.random.default_rng(seed + worker_idx)
        while not stop.is_set():
            user = int(rng.integers(0, num_users))
            try:
                res = client.top_k(user, k=k)
            except QueueFull:
                with lock:
                    rejected[0] += 1
                time.sleep(0.001)  # back off, as a real client would
                continue
            except RuntimeError:
                return  # service shut down under us
            with lock:
                completed.append(time.perf_counter())
                staleness.append(res.staleness)

    threads = [
        threading.Thread(target=load, args=(i,), daemon=True)
        for i in range(concurrency)
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(duration_s)
    stop.set()
    for t in threads:
        t.join(timeout=10)
    elapsed = time.perf_counter() - t0
    driver.request_stop()
    trainer.join(timeout=120)

    lat = service.metrics.latency_percentiles()
    n = len(completed)
    out = {
        "serving_qps": round(n / elapsed, 1),
        "requests_completed": n,
        "requests_rejected": rejected[0] + service.metrics.total_rejected,
        "p50_ms": round(lat["p50"] * 1e3, 3),
        "p90_ms": round(lat["p90"] * 1e3, 3),
        "p99_ms": round(lat["p99"] * 1e3, 3),
        "staleness_mean_steps": (
            round(float(np.mean(staleness)), 2) if staleness else None
        ),
        "staleness_max_steps": (
            int(np.max(staleness)) if staleness else None
        ),
        "publish_every": publish_every,
        "batch_fill": round(service.metrics.batch_fill(), 3),
        "concurrency": concurrency,
        "k": k,
        "duration_s": round(elapsed, 2),
        "train_steps_during_load": driver.step_idx,
        "train_updates_per_sec": (
            round(driver.metrics.updates_per_sec(), 1)
            if driver.metrics is not None
            else None
        ),
        "num_items": num_items,
        "dim": dim,
        "platform": jax.default_backend(),
    }
    service.stop()
    return out


def main():
    from flink_parameter_server_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--duration", type=float, default=5.0)
    ap.add_argument("--concurrency", type=int, default=8)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--publish-every", type=int, default=4)
    ap.add_argument("--num-items", type=int, default=8_192)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    r = run_serving_bench(
        duration_s=args.duration, concurrency=args.concurrency, k=args.k,
        publish_every=args.publish_every, num_items=args.num_items,
        dim=args.dim,
    )
    payload = {
        "metric": "serving top-K QPS (train-while-serve, online MF)",
        "value": r["serving_qps"],
        "unit": "queries/sec",
        "extra": r,
    }
    print(json.dumps(payload))

    out = args.out or os.path.join(
        REPO, "results", r["platform"], "serving_qps.md"
    )
    stamp = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    lines = [
        f"# serving QPS (train-while-serve) — {r['platform']}, {stamp}",
        f"# items={r['num_items']} dim={r['dim']} k={r['k']} "
        f"concurrency={r['concurrency']} publish_every="
        f"{r['publish_every']}",
        "",
        "| qps | p50_ms | p99_ms | staleness mean/max | fill | rejected |"
        " train steps |",
        "|---|---|---|---|---|---|---|",
        f"| {r['serving_qps']} | {r['p50_ms']} | {r['p99_ms']} "
        f"| {r['staleness_mean_steps']}/{r['staleness_max_steps']} "
        f"| {r['batch_fill']} | {r['requests_rejected']} "
        f"| {r['train_steps_during_load']} |",
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
