"""Hot-key storm benchmark: the lease-cache tier ON vs OFF.

The workload the ROADMAP's millions-of-readers story is judged by:
**1% of the keys take 90% of the requests** (a celebrity head on a
uniform tail).  A live 2-shard cluster serves read batches while a
writer client keeps pushing — invalidations flow — and the same
request stream runs through two arms:

  * **off** — every read crosses the wire (the PR-7 baseline: wire is
    60.9% of a pull round);
  * **on** — a :class:`~flink_parameter_server_tpu.hotcache.HotRowCache`
    fronts the reader, lease grants driven by the live PR-6 sketches
    (``hot_keys`` shard sketches → :class:`LeasePolicy`), so hot rows
    are served at the edge for up to ``bound`` ticks.

Reported per arm: request p50/p99 (ms), wire bytes/request (client
side of the ``NetMeter`` ledger, utils/net.py — the committed
bytes-on-wire accounting), plus the on-arm's cache hit rate and lease
counts.  The acceptance deltas are ``p99_off / p99_on`` and
``bytes_off / bytes_on``.

The run also replays the committed ``partition_client_mid_lease``
nemesis schedule (nemesis/corpus/) and records whether the
``lease_staleness`` checker held — the correctness half of the
evidence next to the speed half.

Usage::

    JAX_PLATFORMS=cpu python benchmarks/hotcache_storm.py \
        [--requests 600] [--out results/cpu/hotcache_storm.md]

Prints one JSON line (bench.py metric-line shape) and writes the
markdown/JSON evidence under ``results/<platform>/``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from typing import Optional

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _client_wire_bytes() -> float:
    """Total client-role bytes on the wire, both directions, from the
    process registry (utils/net.py NetMeter)."""
    from flink_parameter_server_tpu.telemetry.registry import get_registry

    total = 0.0
    for inst in get_registry().instruments():
        if inst.name != "net_bytes_total":
            continue
        if inst.labels.get("role") == "client":
            total += float(inst.value or 0.0)
    return total


def _request_stream(
    rng, n_requests, batch_ids, hot_ids, num_items, hot_share
):
    """Per-request id batches: each id is hot with prob ``hot_share``
    (uniform over the hot set), else uniform over the full table."""
    out = []
    for _ in range(n_requests):
        hot_mask = rng.random(batch_ids) < hot_share
        ids = np.where(
            hot_mask,
            rng.choice(hot_ids, size=batch_ids),
            rng.integers(0, num_items, size=batch_ids),
        )
        out.append(ids.astype(np.int64))
    return out


def run_hotcache_bench(
    *,
    num_items: int = 4_096,
    dim: int = 32,
    num_shards: int = 2,
    requests: int = 600,
    # serving-shaped lookups: a handful of rows per request (a user's
    # feature rows), not a training microbatch — which is also what
    # lets a hot request be served ENTIRELY at the edge
    batch_ids: int = 4,
    # closed-loop readers; default 1 keeps the p50/p99 comparison
    # scheduler-clean on small boxes (every reader, shard handler and
    # the writer timeshare the same cores here) — raise it to measure
    # contention relief instead of per-request latency
    concurrency: int = 1,
    hot_frac: float = 0.01,
    hot_share: float = 0.9,
    # serving staleness bound, in ticks (= requests here): a serving
    # read already tolerates snapshot staleness by contract, so the
    # window is an operator dial, not a parity constraint
    bound: int = 64,
    # per-direction wire delay injected by a ChaosProxy on every shard
    # link (nemesis/proxy.py): models a LAN RTT so the wire costs what
    # it costs in production — localhost RTT is ~50 µs, which
    # underprices the round trip this tier exists to delete, and makes
    # both arms CPU-bound instead of wire-bound on small boxes
    link_delay_ms: float = 1.0,
    # warmup must put every hot key's sketch count safely past the
    # policy's min_count before measurement (n_hot keys share
    # warmup × batch_ids × hot_share observations)
    warmup: int = 250,
    # arms run interleaved (off,on,off,on,...) and pool: single-arm
    # p99 on a shared box is scheduler-noise-bound, and interleaving
    # cancels slow-machine windows out of the comparison
    passes: int = 2,
    seed: int = 0,
    run_nemesis: bool = True,
) -> dict:
    """Run both arms over the same storm stream; returns the metrics
    dict.  Import-time side-effect free (bench.py imports this)."""
    import jax

    from flink_parameter_server_tpu.cluster.driver import (
        ClusterConfig,
        ClusterDriver,
    )
    from flink_parameter_server_tpu.hotcache import (
        HotRowCache,
        LeasePolicy,
    )
    from flink_parameter_server_tpu.models.matrix_factorization import (
        OnlineMatrixFactorization,
        SGDUpdater,
    )
    from flink_parameter_server_tpu.telemetry.hotkeys import get_aggregator
    from flink_parameter_server_tpu.utils.initializers import (
        ranged_random_factor,
    )

    rng = np.random.default_rng(seed)
    n_hot = max(1, int(num_items * hot_frac))
    hot_ids = rng.choice(num_items, size=n_hot, replace=False).astype(
        np.int64
    )
    # per-reader request streams (identical across arms: same seeds)
    streams = [
        _request_stream(
            np.random.default_rng(seed + 10 + t), warmup + requests,
            batch_ids, hot_ids, num_items, hot_share,
        )
        for t in range(concurrency)
    ]

    def run_arm(arm: str, rate: Optional[float] = None) -> dict:
        """One arm, one topology.  ``rate=None`` runs CLOSED loop (the
        capacity calibration); a rate runs OPEN loop — arrivals on a
        fixed schedule, latency = completion − scheduled arrival — so
        a saturated arm shows its backlog instead of silently
        self-throttling (coordinated omission, the ROADMAP item-4
        honesty rule)."""
        logic = OnlineMatrixFactorization(
            64, dim, updater=SGDUpdater(0.05), seed=1
        )
        driver = ClusterDriver(
            logic,
            capacity=num_items,
            value_shape=(dim,),
            init_fn=ranged_random_factor(7, (dim,)),
            config=ClusterConfig(
                num_shards=num_shards, num_workers=1,
                # async clock: the readers are serving clients, not
                # BSP workers — the tier's home turf (carve-out table,
                # docs/hotcache.md)
                staleness_bound=None,
                hot_keys=True,
                # space-saving capacity must clear the hot set with
                # room for tail churn, or the tail transiently
                # displaces real hot keys from the candidate set
                hot_key_k=128,
            ),
        )
        driver.start()
        proxies = []
        if link_delay_ms > 0:
            from flink_parameter_server_tpu.nemesis.proxy import (
                ChaosProxy,
            )

            for i, srv in enumerate(driver.servers):
                p = ChaosProxy(
                    srv.host, srv.port,
                    name=f"nemesis-storm-{arm}-{i}", registry=False,
                ).start()
                # request leg only: one delay per request burst
                # regardless of how many frames it pipelines (the s2c
                # leg would charge per response frame, which is a
                # store-and-forward artifact, not an RTT)
                p.set_delay(link_delay_ms, 0.0, "c2s")
                proxies.append(p)
            addrs = [(p.host, p.port) for p in proxies]
        else:
            addrs = [(srv.host, srv.port) for srv in driver.servers]

        def make_client(worker):
            from flink_parameter_server_tpu.cluster.client import (
                ClusterClient,
            )

            return ClusterClient(
                addrs, driver.partitioner, (dim,),
                registry=False, worker=worker,
            )

        writer = make_client(f"storm-writer-{arm}")
        # min_count filters the uniform tail out of the lease set: a
        # tail key's count-min estimate stays ~ε·N while a real hot
        # key's count is ~hot_share·N/n_hot — orders apart, so the
        # threshold needs no tuning finer than "tens"
        policy = (
            LeasePolicy(
                get_aggregator(), top_n=max(64, 2 * n_hot),
                min_count=10, refresh_s=0.05,
            )
            if arm == "on" else None
        )
        readers, caches = [], []
        for t in range(concurrency):
            reader = make_client(f"storm-{arm}-{t}")
            if policy is not None:
                cache = HotRowCache(
                    bound, capacity=max(64, 2 * n_hot),
                    worker=f"storm-{arm}-{t}",
                )
                reader.attach_hotcache(
                    cache, policy, lease_ttl=2 * bound
                )
                caches.append(cache)
            readers.append(reader)
        lat = [np.empty(requests) for _ in range(concurrency)]
        errors: list = []
        try:
            # warmup: connections, host mirrors, sketch counts (the
            # policy needs observed traffic before anything is "hot")
            for t, reader in enumerate(readers):
                for ids in streams[t][:warmup]:
                    reader.pull_batch(ids)
            if policy is not None:
                policy.refresh()
            bytes0 = _client_wire_bytes()
            writes = [0]
            stop_writer = threading.Event()

            def writer_loop() -> None:
                # concurrent pushes to hot keys: the invalidation
                # plane stays live in both arms (symmetry).  Cadence is
                # read-heavy (a celebrity-key storm is reads ≫ writes):
                # ~20 hot-key writes/sec against hundreds of reads/sec
                wrng = np.random.default_rng(seed + 1)
                while not stop_writer.is_set():
                    wids = wrng.choice(hot_ids, size=2, replace=False)
                    writer.push_batch(
                        wids, np.ones((2, dim), np.float32) * 1e-3
                    )
                    writes[0] += 1
                    stop_writer.wait(0.05)

            t_start = time.perf_counter() + 0.02

            def reader_loop(t: int) -> None:
                try:
                    for i, ids in enumerate(streams[t][warmup:]):
                        if rate is None:
                            t0 = time.perf_counter()
                            readers[t].pull_batch(ids)
                            lat[t][i] = time.perf_counter() - t0
                        else:
                            # open loop: reader t owns arrival slots
                            # t, t+K, t+2K, ... of the global schedule
                            target = t_start + (
                                i * concurrency + t
                            ) / rate
                            now = time.perf_counter()
                            if target > now:
                                time.sleep(target - now)
                            readers[t].pull_batch(ids)
                            lat[t][i] = time.perf_counter() - target
                except BaseException as e:  # noqa: BLE001 — re-raised
                    errors.append(e)

            wt = threading.Thread(
                target=writer_loop, name="cluster-storm-writer",
                daemon=True,
            )
            wt.start()
            threads = [
                threading.Thread(
                    target=reader_loop, args=(t,),
                    name=f"cluster-storm-reader-{t}", daemon=True,
                )
                for t in range(concurrency)
            ]
            t_arm = time.perf_counter()
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            arm_wall = time.perf_counter() - t_arm
            stop_writer.set()
            wt.join(timeout=10)
            if errors:
                raise errors[0]
            wire_bytes = _client_wire_bytes() - bytes0
            out = {
                "latencies": np.concatenate(lat),
                "wall_s": arm_wall,
                "wire_bytes": wire_bytes,
                "writer_pushes": writes[0],
            }
            if caches:
                agg = {
                    k: sum(c.stats()[k] for c in caches)
                    for k in ("hits", "misses", "fills", "revocations",
                              "stale_rejects", "evictions", "entries")
                }
                agg["max_served_age"] = max(
                    c.stats()["max_served_age"] for c in caches
                )
                out["cache"] = agg
                out["leases_acquired"] = sum(
                    r.leases_acquired for r in readers
                )
            return out
        finally:
            for reader in readers:
                reader.close()
            writer.close()
            for p in proxies:
                p.stop()
            driver.stop()

    total = requests * concurrency
    # throwaway warm pass: the first topology in a process pays every
    # cold path (jax dispatch caches, allocator growth, import tails)
    # and would corrupt the calibration below
    run_arm("off")
    # phase 1 — closed-loop calibration: each arm's sustainable
    # capacity (and its bytes-on-wire footprint) with arrivals coupled
    # to completions
    calib = {arm: run_arm(arm) for arm in ("off", "on")}
    capacity = {
        arm: total / calib[arm]["wall_s"] for arm in ("off", "on")
    }
    # phase 2 — open-loop storm at ONE offered rate both arms face: a
    # load 20% beyond what the UNCACHED path just sustained.  Latency
    # is measured against the arrival schedule, so the losing arm's
    # backlog is visible instead of silently self-throttled.
    offered = 1.2 * capacity["off"]
    pooled: dict = {"off": [], "on": []}
    for _ in range(max(1, int(passes))):
        for arm in ("off", "on"):
            pooled[arm].append(run_arm(arm, rate=offered))
    arms = {}
    for arm, runs in pooled.items():
        lats = np.concatenate([p["latencies"] for p in runs])
        wall = sum(p["wall_s"] for p in runs)
        wire_bytes = sum(p["wire_bytes"] for p in runs)
        n = total * len(runs)
        arms[arm] = {
            "p50_ms": round(float(np.percentile(lats, 50)) * 1e3, 4),
            "p99_ms": round(float(np.percentile(lats, 99)) * 1e3, 4),
            "mean_ms": round(float(lats.mean()) * 1e3, 4),
            "requests_per_sec": round(n / wall, 1),
            "capacity_rps": round(capacity[arm], 1),
            "wire_bytes_per_request": round(wire_bytes / n, 1),
            "writer_pushes": sum(p["writer_pushes"] for p in runs),
            "passes": len(runs),
        }
        if "cache" in runs[0]:
            agg = {
                k: sum(p["cache"][k] for p in runs)
                for k in ("hits", "misses", "fills", "revocations",
                          "stale_rejects", "evictions", "entries")
            }
            agg["max_served_age"] = max(
                p["cache"]["max_served_age"] for p in runs
            )
            agg["bound"] = bound
            served = agg["hits"] + agg["misses"]
            agg["hit_rate"] = (
                round(agg["hits"] / served, 4) if served else None
            )
            arms[arm]["cache"] = agg
            arms[arm]["leases_acquired"] = sum(
                p["leases_acquired"] for p in runs
            )

    off, on = arms["off"], arms["on"]
    result = {
        "num_items": num_items,
        "dim": dim,
        "num_shards": num_shards,
        "requests": requests,
        "batch_ids": batch_ids,
        "concurrency": concurrency,
        "hot_keys": int(n_hot),
        "hot_frac": hot_frac,
        "hot_share": hot_share,
        "bound": bound,
        "link_delay_ms": link_delay_ms,
        "offered_rps": round(offered, 1),
        "off": off,
        "on": on,
        "p99_speedup": round(off["p99_ms"] / on["p99_ms"], 2)
        if on["p99_ms"] else None,
        "p50_speedup": round(off["p50_ms"] / on["p50_ms"], 2)
        if on["p50_ms"] else None,
        "wire_bytes_ratio": round(
            off["wire_bytes_per_request"]
            / max(1.0, on["wire_bytes_per_request"]), 2
        ),
        "cache_hit_rate": on["cache"]["hit_rate"],
        "platform": jax.default_backend(),
    }
    if run_nemesis:
        result["nemesis_mid_lease"] = _replay_mid_lease()
    return result


def _replay_mid_lease() -> dict:
    """Replay the committed partition-client-mid-lease schedule and
    report the lease_staleness verdict — the correctness half of the
    storm evidence."""
    import tempfile

    from flink_parameter_server_tpu.nemesis.runner import (
        load_corpus,
        run_scenario,
    )

    scenario = next(
        (s for s in load_corpus()
         if s.name == "partition_client_mid_lease"),
        None,
    )
    if scenario is None:
        return {"ok": False, "detail": "schedule missing from corpus"}
    with tempfile.TemporaryDirectory() as wal:
        report = run_scenario(scenario, wal_root=wal)
    lease = next(
        (v for v in report.verdicts if v.name == "lease_staleness"), None
    )
    return {
        "ok": report.ok,
        "lease_staleness_ok": lease.ok if lease else None,
        "lease_staleness_detail": lease.detail if lease else None,
        "faults": report.faults,
    }


def main():
    from flink_parameter_server_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=600)
    ap.add_argument("--num-items", type=int, default=4_096)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--bound", type=int, default=64)
    ap.add_argument("--concurrency", type=int, default=1)
    ap.add_argument("--no-nemesis", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    r = run_hotcache_bench(
        requests=args.requests, num_items=args.num_items, dim=args.dim,
        bound=args.bound, concurrency=args.concurrency,
        run_nemesis=not args.no_nemesis,
    )
    payload = {
        "metric": "hotcache storm serving p99 (1% keys = 90% reads, tier on)",
        "value": r["on"]["p99_ms"],
        "unit": "ms",
        "extra": r,
    }
    print(json.dumps(payload))

    out = args.out or os.path.join(
        REPO, "results", r["platform"], "hotcache_storm.md"
    )
    stamp = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    off, on = r["off"], r["on"]
    nem = r.get("nemesis_mid_lease", {})
    lines = [
        f"# hotcache storm — {r['platform']}, {stamp}",
        f"# items={r['num_items']} dim={r['dim']} shards="
        f"{r['num_shards']} readers={r['concurrency']}×{r['requests']}"
        f" reqs of {r['batch_ids']} ids; {r['hot_keys']} hot keys "
        f"({r['hot_frac']:.0%}) take {r['hot_share']:.0%} of reads; "
        f"bound={r['bound']} ticks",
        "",
        f"open-loop at a common offered load of {r['offered_rps']} "
        f"req/s — 20% beyond the uncached arm's measured closed-loop "
        f"capacity — over ChaosProxy-delayed shard links "
        f"(+{r['link_delay_ms']} ms request leg, a LAN RTT model); "
        f"latency vs the arrival schedule, so backlog is visible (no "
        f"coordinated omission):",
        "",
        "| arm | capacity req/s | p50 ms | p99 ms | wire B/req |",
        "|---|---|---|---|---|",
        f"| tier off | {off['capacity_rps']} | {off['p50_ms']} "
        f"| {off['p99_ms']} | {off['wire_bytes_per_request']} |",
        f"| tier on | {on['capacity_rps']} | {on['p50_ms']} "
        f"| {on['p99_ms']} | {on['wire_bytes_per_request']} |",
        "",
        f"p99 speedup ×{r['p99_speedup']}, p50 speedup "
        f"×{r['p50_speedup']}, wire bytes/request ÷"
        f"{r['wire_bytes_ratio']} (NetMeter client ledger), cache hit "
        f"rate {r['cache_hit_rate']}, "
        f"{on['cache']['revocations']} revocations / "
        f"{on['cache']['stale_rejects']} stale rejects "
        f"(worst served age {on['cache']['max_served_age']} ≤ bound "
        f"{r['bound']}).",
    ]
    if nem:
        lines += [
            "",
            f"nemesis partition_client_mid_lease: "
            f"{'PASS' if nem.get('ok') else 'FAIL'} — "
            f"{nem.get('lease_staleness_detail')}",
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
