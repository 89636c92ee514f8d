"""Compression A/B: quantized delta pushes + aggregation tree vs fp32.

The bytes-down-at-equal-RMSE claim (ROADMAP item 3,
docs/compression.md) is quantitative, so this harness measures all
four of its legs on the real stack:

  1. **push codec A/B** — the same seeded Zipf-hot delta stream pushed
     through 2 shard servers behind bandwidth-capped
     (:class:`~flink_parameter_server_tpu.nemesis.proxy.ChaosProxy`
     drip) links, ``wire_format="b64"`` (negotiates binary fp32) vs
     ``"q8"`` (per-row-scaled int8 + error-feedback residuals):
     bytes/round, push p50/p99 (per ``push_batch`` wall), and the
     final-table RMSE of EACH arm against the ideal fp32 accumulation
     oracle — "equal RMSE" is measured, not asserted by hope;
  2. **aggregation tree A/B** — the same BSP MF workload with 4
     workers, ``push_aggregate`` off vs on: push bytes and frames per
     round (the tree's fan-in is the frames ÷);
  3. **replication legs on the same log** — one primary WAL shipped to
     a follower through a dripped link, ``enc="f32"`` vs ``"q8"``:
     catch-up seconds, repl bytes, max follower error;
  4. **BSP parity pin** — a bound-0 driver configured ``"q8"`` lands
     BITWISE identical to the ``"b64"`` run (the carve-out in
     ``ClusterDriver._make_client`` downgrades bound-0 workers to
     exact fp32).

Usage::

    JAX_PLATFORMS=cpu python benchmarks/compression_ab.py \
        [--rounds 40] [--out results/cpu/compression_ab.md]

Prints one JSON metric line (bench.py shape) and writes md/json
evidence under results/<platform>/ — the json carries a ``payloads``
list so tools/bench_history.py folds every arm's number into the perf
ledger (bytes units regress upward there).
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


def _net_bytes(reg, verb: str, direction: str, role: str = "client") -> int:
    total = 0
    for inst in reg.snapshot().get("net_bytes_total", []):
        lb = inst["labels"]
        if (
            lb.get("verb") == verb
            and lb.get("direction") == direction
            and lb.get("role") == role
        ):
            total += int(inst["value"] or 0)
    return total


def _net_frames(reg, verb: str, direction: str, role: str = "client") -> int:
    total = 0
    for inst in reg.snapshot().get("net_frames_total", []):
        lb = inst["labels"]
        if (
            lb.get("verb") == verb
            and lb.get("direction") == direction
            and lb.get("role") == role
        ):
            total += int(inst["value"] or 0)
    return total


def _delta_stream(rounds, rows, capacity, dim, seed):
    """Seeded Zipf-hot (ids, deltas) rounds — the same stream for both
    arms, materialized once."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(rounds):
        # Zipf-ish skew: half the rows hammer the hot 5% of keys
        hot = rng.integers(0, max(1, capacity // 20), rows // 2)
        cold = rng.integers(0, capacity, rows - rows // 2)
        ids = np.concatenate([hot, cold]).astype(np.int64)
        deltas = rng.normal(0.0, 0.01, (rows, dim)).astype(np.float32)
        out.append((ids, deltas))
    return out


def _run_push_arm(
    wire_format, stream, capacity, dim, *, num_shards, drip_bps, seed
):
    from flink_parameter_server_tpu.cluster.client import ClusterClient
    from flink_parameter_server_tpu.cluster.partition import (
        RangePartitioner,
    )
    from flink_parameter_server_tpu.cluster.shard import (
        ParamShard,
        ShardServer,
    )
    from flink_parameter_server_tpu.nemesis.proxy import ChaosProxy
    from flink_parameter_server_tpu.ops.dedup import aggregate_deltas
    from flink_parameter_server_tpu.telemetry.registry import (
        MetricsRegistry,
        set_registry,
    )

    reg = MetricsRegistry()
    set_registry(reg)
    part = RangePartitioner(capacity, num_shards)
    shards = [
        ParamShard(i, part, (dim,), registry=False)
        for i in range(num_shards)
    ]
    servers = [ShardServer(s).start() for s in shards]
    proxies = []
    for i, srv in enumerate(servers):
        p = ChaosProxy(
            srv.host, srv.port, name=f"comp-{wire_format}-{i}",
            seed=seed + i, registry=False,
        ).start()
        p.set_drip(drip_bps, "both")
        proxies.append(p)
    client = ClusterClient(
        [(p.host, p.port) for p in proxies], part, (dim,),
        wire_format=wire_format, registry=reg,
    )
    push_s = []
    try:
        # numpy-store oracle of EXACTLY what was delivered: each round
        # aggregated (the client's combine semantics) then accumulated
        # fp32 — the ideal table both arms are scored against
        oracle = np.zeros((capacity, dim), np.float32)
        for ids, deltas in stream:
            uq, summed = aggregate_deltas(ids, deltas)
            np.add.at(oracle, uq, summed.astype(np.float32))
            t0 = time.perf_counter()
            client.push_batch(ids, deltas)
            push_s.append(time.perf_counter() - t0)
        table = client.pull_batch(np.arange(capacity, dtype=np.int64))
        rmse = float(np.sqrt(np.mean((table - oracle) ** 2)))
        rel_rmse = rmse / max(1e-12, float(
            np.sqrt(np.mean(oracle ** 2))
        ))
        push_out = _net_bytes(reg, "push", "out")
        saved = 0
        for inst in reg.snapshot().get(
            "compression_bytes_saved_total", []
        ):
            saved += int(inst["value"] or 0)
        return {
            "wire_format": wire_format,
            "push_bytes_per_round": push_out / max(1, len(stream)),
            "push_bytes_total": push_out,
            "push_frames": _net_frames(reg, "push", "out"),
            "push_p50_ms": float(np.percentile(push_s, 50) * 1e3),
            "push_p99_ms": float(np.percentile(push_s, 99) * 1e3),
            "bytes_saved_counter": saved,
            "rmse_vs_oracle": rmse,
            "rel_rmse_vs_oracle": rel_rmse,
            "negotiated_encs": sorted(
                next(iter(client._conns.values())).encs
            ) if client._conns else [],
        }
    finally:
        client.close()
        for p in proxies:
            p.stop()
        for srv in servers:
            srv.stop()
        set_registry(None)


def _mf_workload(rounds, batch, num_users, num_items, dim):
    from flink_parameter_server_tpu.data.movielens import synthetic_ratings
    from flink_parameter_server_tpu.data.streams import microbatches
    from flink_parameter_server_tpu.models.matrix_factorization import (
        OnlineMatrixFactorization,
        SGDUpdater,
    )
    from flink_parameter_server_tpu.utils.initializers import (
        ranged_random_factor,
    )

    cols = synthetic_ratings(num_users, num_items, rounds * batch, seed=3)
    batches = list(microbatches(cols, batch))
    logic = OnlineMatrixFactorization(
        num_users, dim, updater=SGDUpdater(0.05), seed=1
    )
    return batches, logic, ranged_random_factor(7, (dim,))


def _run_driver_arm(
    *, wire_format, push_aggregate, rounds, batch, num_users, num_items,
    dim, num_workers,
):
    from flink_parameter_server_tpu.cluster.driver import (
        ClusterConfig,
        ClusterDriver,
    )
    from flink_parameter_server_tpu.telemetry.registry import (
        MetricsRegistry,
        set_registry,
    )

    reg = MetricsRegistry()
    set_registry(reg)
    batches, logic, init = _mf_workload(
        rounds, batch, num_users, num_items, dim
    )
    driver = ClusterDriver(
        logic, capacity=num_items, value_shape=(dim,), init_fn=init,
        config=ClusterConfig(
            num_shards=2, num_workers=num_workers, staleness_bound=0,
            wire_format=wire_format, push_aggregate=push_aggregate,
        ),
        registry=reg,
    )
    try:
        with driver:
            values = driver.run(batches).values
            # ledger audit while the topology is still up: rows acked
            # by every pushing client (workers, or the tree's uplink)
            # vs rows the shards applied
            acked = sum(c.rows_pushed for c in driver._clients)
            agg = getattr(driver, "last_push_aggregator", None)
            if agg is not None:
                acked += agg.client.rows_pushed
            applied = sum(sh.rows_applied for sh in driver.shards)
        return {
            "values": values,
            "push_bytes_per_round": (
                _net_bytes(reg, "push", "out") / max(1, rounds)
            ),
            "push_frames": _net_frames(reg, "push", "out"),
            "rows_acked": acked,
            "rows_applied": applied,
        }
    finally:
        set_registry(None)


def _run_repl_arm(enc, stream, capacity, dim, *, drip_bps, workdir, seed):
    import shutil

    from flink_parameter_server_tpu.cluster.partition import (
        RangePartitioner,
    )
    from flink_parameter_server_tpu.cluster.shard import (
        ParamShard,
        ShardServer,
    )
    from flink_parameter_server_tpu.nemesis.proxy import ChaosProxy
    from flink_parameter_server_tpu.replication.follower import ReplicaShard
    from flink_parameter_server_tpu.replication.shipper import (
        ReplHub,
        WALShipper,
    )
    from flink_parameter_server_tpu.telemetry.registry import (
        MetricsRegistry,
        set_registry,
    )

    reg = MetricsRegistry()
    set_registry(reg)
    arm_dir = os.path.join(workdir, f"repl-{enc}")
    part = RangePartitioner(capacity, 1)
    primary = ParamShard(
        0, part, (dim,), wal_dir=os.path.join(arm_dir, "primary"),
        registry=False,
    )
    # build the log first — the SAME log for both arms' shape (same
    # stream, fresh dirs): shipping starts only once the log is whole,
    # so the arm measures pure catch-up on a bandwidth-capped link
    for ids, deltas in stream:
        from flink_parameter_server_tpu.ops.dedup import aggregate_deltas

        uq, summed = aggregate_deltas(ids, deltas)
        primary.push(uq, summed.astype(np.float32))
    follower = ReplicaShard(
        0, part, (dim,), wal_dir=os.path.join(arm_dir, "follower"),
        registry=False,
    )
    srv = ShardServer(follower).start()
    proxy = ChaosProxy(
        srv.host, srv.port, name=f"repl-{enc}", seed=seed,
        registry=False,
    ).start()
    proxy.set_drip(drip_bps, "both")
    hub = ReplHub()
    ship = WALShipper(
        primary, (proxy.host, proxy.port), hub.subscribe(),
        registry=False, enc=("q8" if enc == "q8" else "f32"),
    )
    t0 = time.perf_counter()
    ship.start()
    head = primary.head_seq()
    try:
        deadline = time.monotonic() + 120
        while ship.acked_seq < head and time.monotonic() < deadline:
            time.sleep(0.005)
        catch_up_s = time.perf_counter() - t0
        # wait for the async applier too, then compare tables
        deadline = time.monotonic() + 30
        while follower.apply_lag() > 0 and time.monotonic() < deadline:
            time.sleep(0.005)
        err = float(np.abs(
            follower.values() - primary.values()
        ).max())
        repl_bytes = _net_bytes(reg, "repl", "out")
        return {
            "enc": enc,
            "records": head,
            "catch_up_s": round(catch_up_s, 3),
            "repl_bytes": repl_bytes,
            "repl_bytes_saved": ship.repl_bytes_saved,
            "max_follower_err": err,
            "final_lag": ship.lag(),
        }
    finally:
        ship.stop()
        proxy.stop()
        srv.stop()
        follower.close()
        primary.close()
        set_registry(None)
        shutil.rmtree(arm_dir, ignore_errors=True)


def run_compression_bench(
    *,
    rounds: int = 40,
    rows_per_round: int = 768,
    capacity: int = 2_048,
    dim: int = 32,
    num_shards: int = 2,
    drip_bps: float = 4_000_000.0,
    mf_rounds: int = 10,
    mf_batch: int = 96,
    mf_workers: int = 4,
    repl_records: int = 160,
    repl_rows: int = 256,
    seed: int = 5,
    workdir: str = None,
) -> dict:
    """Run all four A/B legs; returns the metrics dict (import-time
    side-effect free — bench.py imports this)."""
    import tempfile

    import jax

    platform = jax.default_backend()
    own_dir = workdir is None
    if own_dir:
        workdir = tempfile.mkdtemp(prefix="compression-ab-")

    stream = _delta_stream(rounds, rows_per_round, capacity, dim, seed)
    f32 = _run_push_arm(
        "b64", stream, capacity, dim, num_shards=num_shards,
        drip_bps=drip_bps, seed=seed,
    )
    q8 = _run_push_arm(
        "q8", stream, capacity, dim, num_shards=num_shards,
        drip_bps=drip_bps, seed=seed,
    )
    bytes_ratio = (
        f32["push_bytes_per_round"] / max(1.0, q8["push_bytes_per_round"])
    )

    # aggregation tree A/B (BSP MF, 4 workers)
    flat = _run_driver_arm(
        wire_format="b64", push_aggregate=False, rounds=mf_rounds,
        batch=mf_batch, num_users=48, num_items=64, dim=4,
        num_workers=mf_workers,
    )
    tree = _run_driver_arm(
        wire_format="b64", push_aggregate=True, rounds=mf_rounds,
        batch=mf_batch, num_users=48, num_items=64, dim=4,
        num_workers=mf_workers,
    )
    tree_ledger_ok = tree["rows_acked"] == tree["rows_applied"]

    # BSP carve-out pin: bound-0 with "q8" is bitwise the "b64" run.
    # One worker — the pin is about the CODEC carve-out, and a single
    # pusher keeps the fp32 scatter order deterministic (concurrent
    # workers reorder fp32 adds, which is why BSP parity elsewhere is
    # allclose, never bitwise).
    bsp_q8 = _run_driver_arm(
        wire_format="q8", push_aggregate=False, rounds=mf_rounds,
        batch=mf_batch, num_users=48, num_items=64, dim=4,
        num_workers=1,
    )
    bsp_f32 = _run_driver_arm(
        wire_format="b64", push_aggregate=False, rounds=mf_rounds,
        batch=mf_batch, num_users=48, num_items=64, dim=4,
        num_workers=1,
    )
    bsp_bitwise = bool(
        np.array_equal(bsp_q8["values"], bsp_f32["values"])
    )

    repl_stream = _delta_stream(
        repl_records, repl_rows, capacity, dim, seed + 1
    )
    repl_f32 = _run_repl_arm(
        "f32", repl_stream, capacity, dim, drip_bps=drip_bps,
        workdir=workdir, seed=seed,
    )
    repl_q8 = _run_repl_arm(
        "q8", repl_stream, capacity, dim, drip_bps=drip_bps,
        workdir=workdir, seed=seed,
    )

    if own_dir:
        import shutil

        shutil.rmtree(workdir, ignore_errors=True)
    return {
        "platform": platform,
        "rounds": rounds,
        "rows_per_round": rows_per_round,
        "capacity": capacity,
        "dim": dim,
        "num_shards": num_shards,
        "drip_bytes_per_sec": drip_bps,
        "push": {"f32": f32, "q8": q8},
        "push_bytes_ratio": round(bytes_ratio, 3),
        "push_p99_ratio": round(
            f32["push_p99_ms"] / max(1e-9, q8["push_p99_ms"]), 3
        ),
        "aggregation": {
            "flat": {k: v for k, v in flat.items() if k != "values"},
            "tree": {k: v for k, v in tree.items() if k != "values"},
            "frames_ratio": round(
                flat["push_frames"] / max(1, tree["push_frames"]), 3
            ),
            "bytes_ratio": round(
                flat["push_bytes_per_round"]
                / max(1.0, tree["push_bytes_per_round"]), 3
            ),
            "tree_parity_allclose": bool(np.allclose(
                flat["values"], tree["values"], atol=1e-4, rtol=1e-4
            )),
            "tree_exactly_once": tree_ledger_ok,
            "mf_workers": mf_workers,
        },
        "bsp_bitwise": bsp_bitwise,
        "replication": {
            "f32": repl_f32,
            "q8": repl_q8,
            "catch_up_ratio": round(
                repl_f32["catch_up_s"]
                / max(1e-9, repl_q8["catch_up_s"]), 3
            ),
            "bytes_ratio": round(
                repl_f32["repl_bytes"]
                / max(1.0, repl_q8["repl_bytes"]), 3
            ),
        },
    }


def main() -> None:
    from flink_parameter_server_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rounds", type=int, default=40)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    r = run_compression_bench(rounds=args.rounds)
    q8, f32 = r["push"]["q8"], r["push"]["f32"]
    payload = {
        "metric": "compression push bytes ratio (fp32/q8, equal RMSE)",
        "value": r["push_bytes_ratio"],
        "unit": "x (higher is better)",
        "extra": r,
    }
    print(json.dumps(payload))

    out = args.out or os.path.join(
        REPO, "results", r["platform"], "compression_ab.md"
    )
    stamp = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    agg, rep = r["aggregation"], r["replication"]
    lines = [
        f"# compression A/B — {r['platform']}, {stamp}",
        f"# capacity={r['capacity']} dim={r['dim']} "
        f"rounds={r['rounds']}×{r['rows_per_round']} rows, "
        f"{r['num_shards']} shards behind "
        f"{r['drip_bytes_per_sec'] / 1e6:g} MB/s dripped links",
        "",
        "## Push codec (wire_format b64-fp32 vs q8)",
        "",
        "| arm | bytes/round | push p50 ms | push p99 ms | "
        "RMSE vs oracle | rel RMSE |",
        "|---|---|---|---|---|---|",
        f"| fp32 | {f32['push_bytes_per_round']:,.0f} "
        f"| {f32['push_p50_ms']:.2f} | {f32['push_p99_ms']:.2f} "
        f"| {f32['rmse_vs_oracle']:.3g} "
        f"| {f32['rel_rmse_vs_oracle']:.3g} |",
        f"| q8 | {q8['push_bytes_per_round']:,.0f} "
        f"| {q8['push_p50_ms']:.2f} | {q8['push_p99_ms']:.2f} "
        f"| {q8['rmse_vs_oracle']:.3g} "
        f"| {q8['rel_rmse_vs_oracle']:.3g} |",
        "",
        f"**bytes/round ÷{r['push_bytes_ratio']}**, push p99 "
        f"÷{r['push_p99_ratio']} at equal final-table RMSE (both arms' "
        f"relative RMSE vs the fp32 accumulation oracle above; the q8 "
        f"arm's error is bounded by one quantization granule per id — "
        f"error feedback re-injects the rest).",
        "",
        "## Aggregation tree (4 BSP workers, flat vs combined)",
        "",
        "| arm | push bytes/round | push frames | parity | "
        "exactly-once |",
        "|---|---|---|---|---|",
        f"| flat | {agg['flat']['push_bytes_per_round']:,.0f} "
        f"| {agg['flat']['push_frames']} | — | — |",
        f"| tree | {agg['tree']['push_bytes_per_round']:,.0f} "
        f"| {agg['tree']['push_frames']} "
        f"| {agg['tree_parity_allclose']} "
        f"| {agg['tree_exactly_once']} |",
        "",
        f"frames ÷{agg['frames_ratio']}, bytes ÷{agg['bytes_ratio']} — "
        f"one combined push per shard per round "
        f"(uplink ledger: {agg['tree']['rows_acked']} rows acked == "
        f"{agg['tree']['rows_applied']} applied).",
        "",
        "## Replication legs (same log, dripped link)",
        "",
        "| enc | records | catch-up s | repl bytes | max follower err |",
        "|---|---|---|---|---|",
        f"| f32 | {rep['f32']['records']} | {rep['f32']['catch_up_s']} "
        f"| {rep['f32']['repl_bytes']:,} "
        f"| {rep['f32']['max_follower_err']:.3g} |",
        f"| q8 | {rep['q8']['records']} | {rep['q8']['catch_up_s']} "
        f"| {rep['q8']['repl_bytes']:,} "
        f"| {rep['q8']['max_follower_err']:.3g} |",
        "",
        f"catch-up ÷{rep['catch_up_ratio']}, repl bytes "
        f"÷{rep['bytes_ratio']} on the same log — replication lag "
        f"drains that much faster on a bandwidth-constrained leg.",
        "",
        "## BSP carve-out",
        "",
        f"bound-0 driver configured `wire_format=\"q8\"` is "
        f"**bitwise identical** to the `\"b64\"` run: "
        f"{r['bsp_bitwise']} (workers downgrade to exact fp32 — "
        f"docs/compression.md).",
    ]
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        f.write("\n".join(lines) + "\n")
    payloads = [
        payload,
        {"metric": "compression push bytes/round (q8 arm)",
         "value": round(q8["push_bytes_per_round"], 1),
         "unit": "bytes/round"},
        {"metric": "compression push bytes/round (fp32 arm)",
         "value": round(f32["push_bytes_per_round"], 1),
         "unit": "bytes/round"},
        {"metric": "compression push p99 (q8 arm)",
         "value": round(q8["push_p99_ms"], 3), "unit": "ms"},
        {"metric": "compression repl catch-up (q8 arm)",
         "value": rep["q8"]["catch_up_s"], "unit": "seconds"},
        {"metric": "compression aggregation push frames ratio",
         "value": agg["frames_ratio"], "unit": "x (higher is better)"},
    ]
    with open(os.path.splitext(out)[0] + ".json", "w") as f:
        json.dump({
            "captured_at": time.time(),
            "payload": payload,
            "payloads": payloads,
        }, f, indent=1)
    print(f"# wrote {out}", file=sys.stderr)


if __name__ == "__main__":
    main()
