"""Two-tier store soak: Criteo-scale rows at a bounded resident set.

The tiered backend (tierstore/, docs/tierstore.md) exists for ONE
claim: a 2^24+-row shard slice can serve a Zipf-skewed mix at a
bounded peak RSS, with the hot path staying within a small factor of
the all-RAM store it replaces.  This benchmark prices that claim and
refuses to report it without the recovery planes that make it safe:

  * **perf arms** (each in its OWN child process so ``ru_maxrss`` is
    that arm's honest peak): a 2^24-row x dim-16 slice under the same
    seeded Zipf-like mix — ``dense`` materialises the full table the
    way a dense ParamShard slice does (1 GiB of fp32 at this shape);
    ``tiered`` runs :class:`TieredStore` with a 2^20-row hot tier
    (1/16th of the id space).  Both arms run the same untimed warmup
    rounds first so the percentiles price steady state, not the cold
    ramp (the warmup references still land in the recorded ledger).
    Recorded per arm: peak RSS, pull/push p50/p99, and (tiered) the
    hit/miss ledger.  The bars, both self-linted before anything is
    written: ``tiered_peak_rss_bytes <= rss_bound_bytes`` (the bound
    is RECORDED in the artifact — a soak that never wrote down its
    own bound proves nothing) and ``pull_p50_ratio <=
    pull_overhead_limit`` (2x).
  * **correctness legs** (parent process, 2^12 rows, real per-id
    init, deliberately tiny hot tiers so every leg crosses demoted
    cold rows): bitwise tiered-vs-dense shard parity, kill→promote
    over a replica chain (the ``kill_promote_cold_tier`` nemesis
    scenario, tier-residency invariant included), WAL replay through
    cold rows (``crash()``/``restart()`` bitwise), and elastic
    migration (``plan_moves``/``execute_moves`` between tiered
    shards, bitwise at handoff).  A red leg fails the run — the RSS
    and latency numbers only count on a commit whose recovery planes
    pass.

The Zipf mix is the log-uniform rank draw (``id = floor(n^u) - 1``,
u ~ U[0,1) — the s≈1 Zipf inverse CDF): the top 2^17 ranks carry
~17/24 of the references, the same shape the r2 trace measured on the
MF workload, with a heavy tail that keeps the eviction scan honest.

Artifacts: ``results/cpu/tierstore_soak.{md,json}`` — linted by
``tools/check_metric_lines.py --tier``, folded into the perf ledger
by ``tools/bench_history.py`` (the pull ratio travels as an
``x slowdown`` unit so upward drift flags).  ``FPS_BENCH_TIER=1
python bench.py`` re-emits the last stdout line as a guarded metric
line.

Usage::

    JAX_PLATFORMS=cpu python benchmarks/tierstore_soak.py \
        [--rows 16777216] [--dim 16] [--hot 1048576] [--rounds 400] \
        [--warmup 100] [--batch 8192] [--out results/cpu]
"""
from __future__ import annotations

import argparse
import json
import os
import resource
import subprocess
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

RSS_BOUND_BYTES = 768 * (1 << 20)
PULL_OVERHEAD_LIMIT = 2.0
ZIPF_S = 1.0  # the log-uniform draw is the s=1 bounded-Zipf inverse CDF


def _zipf_batch(rng: np.random.Generator, n: int, batch: int) -> np.ndarray:
    u = rng.random(batch)
    return np.minimum(
        np.exp(u * np.log(n)).astype(np.int64), n - 1
    )


def _peak_rss_bytes() -> int:
    # linux ru_maxrss is KiB
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def _pctl(samples, q) -> float:
    return round(float(np.percentile(np.asarray(samples), q)) * 1e3, 4)


# ---------------------------------------------------------------------------
# child arms (one process each — ru_maxrss must be per-arm)
# ---------------------------------------------------------------------------


def run_arm(arm: str, *, rows: int, dim: int, hot: int, rounds: int,
            batch: int, warmup: int = 0, seed: int = 0) -> dict:
    """The perf loop, identical per arm: per round, one gather and one
    scatter-add push over the same seeded Zipf mix.  The first
    ``warmup`` rounds are untimed (cold-ramp promote storm / first
    page faults excluded from the percentiles, NOT from the ledger or
    the RSS peak).  Prints nothing — returns the measurement dict
    (the child's ``main`` JSON-prints it)."""
    rng = np.random.default_rng(seed)
    drng = np.random.default_rng(seed + 1)
    if arm == "dense":
        # the all-RAM baseline: a dense ParamShard slice materialises
        # its whole table, so the arm does too (np.zeros alone maps
        # lazy pages and would understate the RSS a dense deployment
        # actually pays)
        table = np.zeros((rows, dim), np.float32)
        table.fill(0.0)
        store = None
    else:
        from flink_parameter_server_tpu.tierstore.store import TieredStore

        store = TieredStore(rows, (dim,), row_init=None, hot_rows=hot)
        table = None
    pulls, pushes = [], []
    for i in range(warmup + rounds):
        ids = _zipf_batch(rng, rows, batch)
        deltas = drng.normal(size=(batch, dim)).astype(np.float32)
        t = time.perf_counter()
        if store is None:
            _ = table[ids]
        else:
            _ = store.gather(ids)
        dt_pull = time.perf_counter() - t
        t = time.perf_counter()
        if store is None:
            np.add.at(table, ids, deltas)
        else:
            store.push(ids, deltas)
        if i >= warmup:
            pulls.append(dt_pull)
            pushes.append(time.perf_counter() - t)
    out = {
        "arm": arm,
        "rows": rows, "dim": dim, "rounds": rounds, "batch": batch,
        "warmup": warmup,
        "pull_p50_ms": _pctl(pulls, 50),
        "pull_p99_ms": _pctl(pulls, 99),
        "push_p50_ms": _pctl(pushes, 50),
        "push_p99_ms": _pctl(pushes, 99),
        "peak_rss_bytes": _peak_rss_bytes(),
    }
    if store is not None:
        st = store.stats()
        # one gather + one push reference per lane, warmup included
        # (the store saw those references; hiding them would skew the
        # recorded hit rate)
        refs = 2 * (warmup + rounds) * batch
        out["hot_rows"] = hot
        out["stats"] = st
        out["ledger"] = {
            "hits": int(st["hits"]),
            "misses": int(st["misses"]),
            "references": refs,
        }
        out["hit_rate"] = round(st["hits"] / refs, 4)
        store.close()
    return out


def _spawn_arm(arm: str, args) -> dict:
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--arm", arm,
         "--rows", str(args.rows), "--dim", str(args.dim),
         "--hot", str(args.hot), "--rounds", str(args.rounds),
         "--warmup", str(args.warmup), "--batch", str(args.batch)],
        capture_output=True, text=True, timeout=480,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"{arm} arm failed (rc={proc.returncode}): "
            f"{proc.stderr.strip()[-400:]}"
        )
    return json.loads(proc.stdout.splitlines()[-1])


# ---------------------------------------------------------------------------
# correctness legs (parent process, small shapes, real init)
# ---------------------------------------------------------------------------

LEG_ROWS = 1 << 12
LEG_DIM = 4


def leg_parity_bitwise() -> bool:
    """Tiered vs numpy ParamShard, same pushes (duplicates included),
    a 64-row hot tier over 2^12 rows: every pull and the final
    ``values()`` must be BITWISE equal — misses recompute the
    deterministic init bitwise and scatter-adds share apply order."""
    from flink_parameter_server_tpu.cluster import RangePartitioner
    from flink_parameter_server_tpu.cluster.shard import ParamShard
    from flink_parameter_server_tpu.utils.initializers import (
        ranged_random_factor,
    )

    part = RangePartitioner(LEG_ROWS, 1)
    init = ranged_random_factor(11, (LEG_DIM,))
    tiered = ParamShard(
        0, part, (LEG_DIM,), init_fn=init, registry=False,
        store_backend="tiered", tier_hot_rows=64,
    )
    dense = ParamShard(
        0, part, (LEG_DIM,), init_fn=init, registry=False,
        store_backend="numpy",
    )
    try:
        rng = np.random.default_rng(3)
        ok = True
        for _ in range(40):
            ids = _zipf_batch(rng, LEG_ROWS, 256)
            ok &= np.array_equal(tiered.pull(ids), dense.pull(ids))
            deltas = rng.normal(size=(256, LEG_DIM)).astype(np.float32)
            tiered.push(ids, deltas)
            dense.push(ids, deltas)
        ok &= np.array_equal(tiered.values(), dense.values())
        return bool(ok)
    finally:
        tiered.close()
        dense.close()


def leg_wal_replay() -> bool:
    """Kill→restart over a mostly-demoted tier: WAL replay rebuilds
    the table bitwise THROUGH the cold tier (the replayed pushes
    re-promote/demote as they go), and a fresh shard over the same
    wal_dir lands identically."""
    from flink_parameter_server_tpu.cluster import RangePartitioner
    from flink_parameter_server_tpu.cluster.shard import ParamShard
    from flink_parameter_server_tpu.utils.initializers import (
        ranged_random_factor,
    )

    part = RangePartitioner(LEG_ROWS, 1)
    init = ranged_random_factor(5, (LEG_DIM,))
    with tempfile.TemporaryDirectory(prefix="tier-soak-wal-") as tmp:
        wal = os.path.join(tmp, "wal")
        shard = ParamShard(
            0, part, (LEG_DIM,), init_fn=init, wal_dir=wal,
            registry=False, store_backend="tiered", tier_hot_rows=48,
        )
        try:
            rng = np.random.default_rng(9)
            for _ in range(30):
                ids = _zipf_batch(rng, LEG_ROWS, 128)
                shard.push(
                    ids, rng.normal(size=(128, LEG_DIM)).astype(np.float32)
                )
            before = shard.values().copy()
            shard.crash()
            replayed = shard.restart()
            ok = replayed == 30
            ok &= bool(np.array_equal(shard.values(), before))
        finally:
            shard.close()
        reborn = ParamShard(
            0, part, (LEG_DIM,), init_fn=init, wal_dir=wal,
            registry=False, store_backend="tiered", tier_hot_rows=48,
        )
        try:
            ok &= bool(np.array_equal(reborn.values(), before))
        finally:
            reborn.close()
    return bool(ok)


def leg_kill_promote() -> dict:
    """The committed ``kill_promote_cold_tier`` nemesis scenario:
    kill the tiered primary mid-run, promote its follower (also
    tiered — chains inherit the tier), finish the workload.  Green =
    every invariant verdict passes, tier residency included."""
    from flink_parameter_server_tpu.nemesis.runner import run_scenario
    from flink_parameter_server_tpu.nemesis.scenarios import (
        BUILTIN_SCENARIOS,
    )

    (scenario,) = [
        s for s in BUILTIN_SCENARIOS if s.name == "kill_promote_cold_tier"
    ]
    with tempfile.TemporaryDirectory(prefix="tier-soak-nem-") as wal_root:
        report = run_scenario(scenario, wal_root=wal_root)
    return {
        "ok": bool(report.ok),
        "verdicts": {v.name: bool(v.ok) for v in report.verdicts},
    }


def leg_migration() -> bool:
    """Elastic handoff between TIERED shards: donor export crosses
    hot + slab + never-touched rows, receiver load lands bitwise
    (verified pre-flip by ``execute_moves``), and the moved rows
    read back bitwise on the destination tier."""
    from flink_parameter_server_tpu.cluster import (
        ConsistentHashPartitioner,
        ShardServer,
    )
    from flink_parameter_server_tpu.cluster.shard import ParamShard
    from flink_parameter_server_tpu.elastic import (
        execute_moves,
        plan_moves,
    )
    from flink_parameter_server_tpu.utils.initializers import (
        ranged_random_factor,
    )

    old = ConsistentHashPartitioner(LEG_ROWS, 1, seed=2)
    new = old.grown(2)
    init = ranged_random_factor(3, (LEG_DIM,))
    src = ParamShard(
        0, old, (LEG_DIM,), init_fn=init, registry=False,
        store_backend="tiered", tier_hot_rows=64,
    )
    dst = ParamShard(
        1, new, (LEG_DIM,), init_fn=init, registry=False,
        store_backend="tiered", tier_hot_rows=64,
    )
    servers = [
        ShardServer(src, supervised=False).start(),
        ShardServer(dst, supervised=False).start(),
    ]
    try:
        rng = np.random.default_rng(1)
        for _ in range(10):
            ids = _zipf_batch(rng, LEG_ROWS, 256)
            src.push(
                ids, rng.normal(size=(256, LEG_DIM)).astype(np.float32)
            )
        moves = plan_moves(old, new)
        pre = {mv.dst: src.snapshot_rows(mv.ids)[0] for mv in moves}
        report = execute_moves(
            moves, {0: src, 1: dst},
            {0: (servers[0].host, servers[0].port),
             1: (servers[1].host, servers[1].port)},
            (LEG_DIM,), verify=True, registry=False,
        )
        ok = bool(report.verified) and report.mismatches == 0
        ok &= report.rows_moved == sum(len(m.ids) for m in moves)
        for mv in moves:
            ok &= bool(np.array_equal(dst.peek_rows(mv.ids), pre[mv.dst]))
        return bool(ok)
    finally:
        for s in servers:
            s.stop()
        src.close()
        dst.close()


# ---------------------------------------------------------------------------
# artifacts
# ---------------------------------------------------------------------------


def write_artifacts(doc: dict, md: str, out_dir: str) -> None:
    from tools.check_metric_lines import check_tier

    bad = check_tier(doc)
    if bad:
        raise SystemExit(
            f"tierstore_soak: artifact failed its own lint: {bad}"
        )
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "tierstore_soak.json"), "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    with open(os.path.join(out_dir, "tierstore_soak.md"), "w") as f:
        f.write(md)


def _fmt_mb(b) -> str:
    return f"{b / (1 << 20):.0f} MiB"


def main() -> int:
    from flink_parameter_server_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--arm", choices=("tiered", "dense"), default=None,
                   help="internal: run ONE perf arm and print its JSON")
    p.add_argument("--rows", type=int, default=1 << 24)
    p.add_argument("--dim", type=int, default=16)
    p.add_argument("--hot", type=int, default=1 << 20)
    p.add_argument("--rounds", type=int, default=400)
    p.add_argument("--warmup", type=int, default=100)
    p.add_argument("--batch", type=int, default=8192)
    p.add_argument("--out", default=os.path.join(REPO, "results", "cpu"))
    args = p.parse_args()
    if args.arm:
        print(json.dumps(run_arm(
            args.arm, rows=args.rows, dim=args.dim, hot=args.hot,
            rounds=args.rounds, batch=args.batch, warmup=args.warmup,
        )))
        return 0

    tiered = _spawn_arm("tiered", args)
    dense = _spawn_arm("dense", args)
    legs_detail = {
        "parity_bitwise": leg_parity_bitwise(),
        "wal_replay": leg_wal_replay(),
        "migration": leg_migration(),
    }
    kp = leg_kill_promote()
    legs_detail["kill_promote"] = kp["ok"]
    legs = {k: bool(v) for k, v in legs_detail.items()}

    ratio = (
        round(tiered["pull_p50_ms"] / dense["pull_p50_ms"], 3)
        if dense["pull_p50_ms"] else float("inf")
    )
    from flink_parameter_server_tpu.telemetry.registry import (
        default_run_id,
    )

    tier = {
        "rows": args.rows, "dim": args.dim, "hot_rows": args.hot,
        "rounds": args.rounds, "warmup_rounds": args.warmup,
        "batch": args.batch,
        "zipf_s": ZIPF_S,
        "rss_bound_bytes": RSS_BOUND_BYTES,
        "tiered_peak_rss_bytes": tiered["peak_rss_bytes"],
        "dense_peak_rss_bytes": dense["peak_rss_bytes"],
        "pull_p50_ratio": ratio,
        "pull_overhead_limit": PULL_OVERHEAD_LIMIT,
        "hit_rate": tiered["hit_rate"],
        "ledger": tiered["ledger"],
        "legs": legs,
        "arms": {
            "tiered": {k: tiered[k] for k in (
                "pull_p50_ms", "pull_p99_ms", "push_p50_ms",
                "push_p99_ms", "peak_rss_bytes",
            )},
            "dense": {k: dense[k] for k in (
                "pull_p50_ms", "pull_p99_ms", "push_p50_ms",
                "push_p99_ms", "peak_rss_bytes",
            )},
        },
        "tiered_stats": tiered["stats"],
        "kill_promote_verdicts": kp["verdicts"],
    }
    doc = {
        "ts": round(time.time(), 3),
        "run_id": default_run_id(),
        "kind": "tierstore_soak",
        "metric": "tierstore pull latency ratio at bounded RSS",
        "value": ratio,
        "unit": "x slowdown (tiered / all-RAM pull p50)",
        "tier": tier,
        "payloads": [
            {"metric": "tierstore pull p50 (tiered)",
             "value": tiered["pull_p50_ms"], "unit": "ms"},
            {"metric": "tierstore pull p50 (all-RAM)",
             "value": dense["pull_p50_ms"], "unit": "ms"},
            {"metric": "tierstore push p50 (tiered)",
             "value": tiered["push_p50_ms"], "unit": "ms"},
            {"metric": "tierstore peak RSS (tiered)",
             "value": tiered["peak_rss_bytes"], "unit": "bytes resident"},
            {"metric": "tierstore peak RSS (all-RAM)",
             "value": dense["peak_rss_bytes"], "unit": "bytes resident"},
        ],
        "host": {"cpus": os.cpu_count()},
    }
    st = tiered["stats"]
    md = f"""# Two-tier store soak — 2^24 rows at a bounded resident set

Same seeded Zipf mix (log-uniform rank draw, s≈1) over a
{args.rows:,}-row x dim-{args.dim} fp32 slice, {args.rounds} timed
rounds x {args.batch} lanes (one gather + one scatter-add push per
round) after {args.warmup} untimed warmup rounds — the percentiles
price steady state, the ledger and RSS peak still cover the ramp —
each arm in its own process so peak RSS is that arm's honest number.
The dense arm materialises the full table the way a dense ParamShard
slice does; the tiered arm (tierstore/, docs/tierstore.md) runs a
{args.hot:,}-row hot tier over the mmap cold slab.

| arm | peak RSS | pull p50 | pull p99 | push p50 | push p99 |
|---|---|---|---|---|---|
| tiered | {_fmt_mb(tiered['peak_rss_bytes'])} | \
{tiered['pull_p50_ms']} ms | {tiered['pull_p99_ms']} ms | \
{tiered['push_p50_ms']} ms | {tiered['push_p99_ms']} ms |
| all-RAM | {_fmt_mb(dense['peak_rss_bytes'])} | \
{dense['pull_p50_ms']} ms | {dense['pull_p99_ms']} ms | \
{dense['push_p50_ms']} ms | {dense['push_p99_ms']} ms |

**RSS bound: {_fmt_mb(tiered['peak_rss_bytes'])} recorded against a
{_fmt_mb(RSS_BOUND_BYTES)} bound** (the dense arm peaked at
{_fmt_mb(dense['peak_rss_bytes'])} — the cost the tier deletes).
**Pull p50 overhead: {ratio}x** against the {PULL_OVERHEAD_LIMIT}x
bar.  Hit rate {tier['hit_rate']:.3f} over
{tier['ledger']['references']:,} references
({tier['ledger']['hits']:,} hot, {tier['ledger']['misses']:,}
slab/init); {st['promotes']:,} promotes, {st['demotes']:,} demotes
({st['demote_writes']:,} dirty slab writes), {st['spills']:,}
spills, {st['evict_scans']} eviction scans, {st['decays']} sketch
decays, final slab {st['slab_rows']:,} rows /
{_fmt_mb(st['slab_bytes'])}.

## Correctness legs (2^12 rows, real per-id init, tiny hot tiers)

| leg | verdict |
|---|---|
| tiered vs dense shard parity (pulls + final table, BITWISE) | \
{'green' if legs['parity_bitwise'] else 'RED'} |
| kill→promote over a tiered replica chain \
(`kill_promote_cold_tier` nemesis scenario, tier-residency invariant \
included) | {'green' if legs['kill_promote'] else 'RED'} |
| WAL replay through cold rows (crash/restart + fresh-process, \
BITWISE) | {'green' if legs['wal_replay'] else 'RED'} |
| elastic migration between tiered shards (verify-then-flip, \
BITWISE at handoff) | {'green' if legs['migration'] else 'RED'} |

A red leg fails the run before any artifact is written: the RSS and
latency numbers only count on a commit whose recovery planes pass.

Produced by `benchmarks/tierstore_soak.py` on a {os.cpu_count()}-CPU
host; linted by `tools/check_metric_lines.py --tier`; folded into the
perf ledger by `tools/bench_history.py` (the ratio is an
`x slowdown` unit — upward drift flags); re-emitted as a guarded
metric line by `FPS_BENCH_TIER=1 python bench.py`.
"""
    write_artifacts(doc, md, args.out)
    print(json.dumps(doc))
    return 0 if all(legs.values()) and ratio <= PULL_OVERHEAD_LIMIT and (
        tiered["peak_rss_bytes"] <= RSS_BOUND_BYTES
    ) else 1


if __name__ == "__main__":
    sys.exit(main())
