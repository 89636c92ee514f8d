"""Benchmark: MF-SGD updates/sec/chip (BASELINE.md headline metric).

Runs the compiled PS training step (pull → SGD → push) on the platform
JAX finds over a synthetic MovieLens-like rating stream (Zipf-skewed
items — the hard case for sharded scatter-add), and compares against a
single-node per-record CPU baseline emulating the reference's execution
model (one record per callback, hash-routed store ops — SURVEY.md §3.2;
the Scala original cannot run here, so the baseline reproduces its
per-record semantics in numpy).

Prints ONE JSON line per requested metric; the headline is
  {"metric": ..., "value": N, "unit": "updates/sec/chip", "vs_baseline": N,
   "platform": ..., "device_kind": ..., "device_count": N, "extra": {...}}
— extra carries the pull→push p50 (the second north-star metric) and
the baseline rate.  Every line names the platform it ran on; nothing is
relabelled, replayed or re-run elsewhere.  Exits nonzero if any
requested line failed.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
import traceback

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))


def tpu_updates_per_sec(
    num_users=100_000,
    num_items=131_072,
    dim=None,
    batch=None,
    warmup_steps=3,
    bench_steps=30,
    dtype=None,
):
    import jax
    import jax.numpy as jnp

    from flink_parameter_server_tpu.core.store import ShardedParamStore
    from flink_parameter_server_tpu.core.transform import make_train_step
    from flink_parameter_server_tpu.models.matrix_factorization import (
        OnlineMatrixFactorization,
        SGDUpdater,
    )
    from flink_parameter_server_tpu.utils.initializers import normal_factor

    if batch is None:
        # one TPU chip sustains much larger microbatches before going
        # compute-bound (tables are ~30 MB; batch arrays are trivial);
        # the CPU backend stays small to keep a CPU run short.
        default_batch = 65_536 if jax.default_backend() == "tpu" else 16_384
        raw = os.environ.get("FPS_BENCH_BATCH", str(default_batch))
        try:
            batch = int(raw)
        except ValueError:
            raise SystemExit(
                f"FPS_BENCH_BATCH={raw!r}: expected a positive integer"
            ) from None
        if batch <= 0:
            raise SystemExit(f"FPS_BENCH_BATCH={batch}: must be positive")
    if dtype is None:
        # bfloat16 is the TPU-native table dtype (halves HBM gather/
        # scatter bytes) but is *emulated* (≈10× slower) on the CPU
        # backend — default by platform; FPS_BENCH_DTYPE overrides.
        default = "bfloat16" if jax.default_backend() == "tpu" else "float32"
        name = os.environ.get("FPS_BENCH_DTYPE", default)
        valid = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
        if name not in valid:
            raise SystemExit(
                f"FPS_BENCH_DTYPE={name!r} not supported; use one of "
                f"{sorted(valid)}"
            )
        dtype = valid[name]
    if dim is None:
        raw = os.environ.get("FPS_BENCH_DIM", "64")  # the reference's shape
        try:
            dim = int(raw)
        except ValueError:
            raise SystemExit(
                f"FPS_BENCH_DIM={raw!r}: expected a positive integer"
            ) from None
        if dim <= 0:
            raise SystemExit(f"FPS_BENCH_DIM={dim}: must be positive")
    # validate BEFORE any use — an invalid value must exit with the clean
    # one-liner, not a _resolve_layout traceback
    layout = os.environ.get("FPS_BENCH_LAYOUT", "dense")
    if layout not in ("dense", "packed", "auto"):
        raise SystemExit(f"FPS_BENCH_LAYOUT={layout!r}: dense|packed|auto")
    # validated up front with the other knobs: a typo must exit in
    # milliseconds, not after compile + warmup
    raw_reps = os.environ.get("FPS_BENCH_REPS", "3")
    try:
        reps = int(raw_reps)
    except ValueError:
        raise SystemExit(
            f"FPS_BENCH_REPS={raw_reps!r}: expected a positive integer"
        ) from None
    if reps <= 0:
        raise SystemExit(f"FPS_BENCH_REPS={reps}: must be positive")
    # Multi-chip TPU: shard over a dp × ps mesh and report PER-CHIP rate.
    # (Only on real TPUs — virtual CPU meshes on this 1-core host trip
    # XLA's collective-rendezvous watchdog at bench-scale steps.)
    mesh = None
    n_chips = 1
    if (
        jax.default_backend() == "tpu"
        and len(jax.devices()) > 1
        and jax.process_count() == 1  # single-process only: device_put to
        # non-addressable devices would crash on multi-host slices
    ):
        from flink_parameter_server_tpu.parallel.mesh import make_mesh

        n_chips = len(jax.devices())
        ps = next((c for c in (4, 2) if n_chips % c == 0), 1)
        mesh = make_mesh(ps_parallelism=ps)  # dp absorbs the rest
        batch = batch * mesh.shape["dp"]  # scale work with dp

    # lr matches cpu_per_record_baseline (both sides numerically stable).
    logic = OnlineMatrixFactorization(
        num_users, dim, updater=SGDUpdater(0.01), dtype=dtype, mesh=mesh,
    )
    store = ShardedParamStore.create(
        num_items, (dim,), dtype=dtype,
        init_fn=normal_factor(1, (dim,), dtype=dtype), mesh=mesh,
        layout=layout,
    )
    state = logic.init_state(jax.random.PRNGKey(0))

    rng = np.random.default_rng(0)
    items = ((rng.zipf(1.2, batch) - 1) % num_items).astype(np.int32)
    data = {
        "user": jnp.asarray(rng.integers(0, num_users, batch).astype(np.int32)),
        "item": jnp.asarray(items),
        "rating": jnp.asarray(rng.normal(0, 1, batch).astype(np.float32)),
        "mask": jnp.ones(batch, bool),
    }

    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        sh = NamedSharding(mesh, PartitionSpec("dp"))
        data = {k: jax.device_put(v, sh) for k, v in data.items()}

    raw_step = make_train_step(logic, store.spec)
    step = jax.jit(raw_step, donate_argnums=(0, 1))
    table = store.table
    for _ in range(warmup_steps):
        table, state, out = step(table, state, data)
    jax.block_until_ready(table)

    # throughput: free-running (pipelined) steps, >=3 reps — a
    # single-shot number is not evidence; report the median + spread.
    rep_rates = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(bench_steps):
            table, state, out = step(table, state, data)
        jax.block_until_ready(table)
        rep_rates.append(bench_steps * batch / (time.perf_counter() - t0))
    updates_per_sec = float(np.median(rep_rates))
    dt = bench_steps * batch / updates_per_sec  # median step-time basis

    # pull→push latency, e2e: synchronous per-step round trips (host
    # dispatch included).
    lats = []
    for _ in range(10):
        t1 = time.perf_counter()
        table, state, out = step(table, state, data)
        jax.block_until_ready(table)
        lats.append(time.perf_counter() - t1)
    p50_ms = float(np.percentile(np.array(lats), 50) * 1e3)

    # pull→push latency, DEVICE-side: K steps inside ONE jitted
    # lax.scan, so host dispatch amortizes to 1/K and the per-step
    # quotient is the device latency the kernels actually set.  K
    # defaults by platform (64 on TPU; off-TPU a small K just confirms
    # the scan path).  0 disables the scan entirely (profiler jobs do
    # this: 6xK extra steps inside a trace window would bury the 10
    # steady-state steps it wants).
    default_k = "64" if jax.default_backend() == "tpu" else "8"
    raw_k = os.environ.get("FPS_BENCH_DEVICE_P50_STEPS", default_k)
    try:
        scan_k = int(raw_k)
    except ValueError:
        raise SystemExit(
            f"FPS_BENCH_DEVICE_P50_STEPS={raw_k!r}: expected a "
            f"non-negative integer (0 disables the device-p50 scan)"
        ) from None
    if scan_k < 0:
        raise SystemExit(
            f"FPS_BENCH_DEVICE_P50_STEPS={scan_k}: must be >= 0"
        )

    p50_device_ms = None
    if scan_k:
        def _scan_steps(table, state):
            def body(carry, _):
                t, s = carry
                t, s, _out = raw_step(t, s, data)
                return (t, s), None

            carry, _ = jax.lax.scan(
                body, (table, state), None, length=scan_k
            )
            return carry

        scan_fn = jax.jit(_scan_steps, donate_argnums=(0, 1))
        table, state = scan_fn(table, state)  # compile + warm
        jax.block_until_ready(table)
        dev_lats = []
        for _ in range(5):
            t2 = time.perf_counter()
            table, state = scan_fn(table, state)
            jax.block_until_ready(table)
            dev_lats.append((time.perf_counter() - t2) / scan_k)
        p50_device_ms = float(np.percentile(np.array(dev_lats), 50) * 1e3)

    # HBM traffic model for the gather/scatter-bound MF step (the honest
    # perf yardstick for a bandwidth-bound workload): each side (user state
    # table, item store) does a batch-row gather (1 read) and a batch-row
    # scatter RMW (1 read + 1 write) → 6 row-traversals.
    el = jnp.dtype(dtype).itemsize
    # the packed layout moves full physical rows (128 lanes) per
    # pull/push regardless of the logical dim
    if store.spec.layout == "packed":
        from flink_parameter_server_tpu.ops.packed import phys_width

        row_lanes = phys_width(dim)
    else:
        row_lanes = dim
    hbm_bytes_per_step = 3 * batch * (row_lanes + dim) * el
    step_time = dt / bench_steps
    from flink_parameter_server_tpu.utils.device_peaks import device_peaks

    peaks = device_peaks()  # None off the chip; unknown TPU kinds raise
    bandwidth_util = (
        (hbm_bytes_per_step / n_chips) / step_time / peaks.hbm_bytes_per_sec
        if peaks else None
    )
    return {
        "updates_per_sec_per_chip": updates_per_sec / n_chips,
        "p50_ms": p50_ms,
        "p50_device_ms": p50_device_ms,
        "table_dtype": jnp.dtype(dtype).name,
        "batch": batch,
        "hbm_bytes_per_step": hbm_bytes_per_step,
        "bandwidth_util": bandwidth_util,
        "dim": dim,
        "layout": layout,
        "reps": reps,
        "rate_min": float(np.min(rep_rates)) / n_chips,
        "rate_max": float(np.max(rep_rates)) / n_chips,
    }


def cpu_per_record_baseline(num_ratings=20_000, dim=64, lr=0.01):
    """Single-node per-record PS loop: the reference's execution model
    (per-record callback, keyed store lookup, vector SGD, keyed store
    update) without JVM/Flink overheads — a *favourable* stand-in for the
    Scala original.

    lr=0.01 keeps plain SGD numerically stable on N(0,1) ratings (at 0.05
    the factor norms blow up and the yardstick computes inf/NaN math —
    round-1 verdict finding).  Finiteness is returned alongside the rate;
    main() refuses to publish a vs_baseline ratio against a diverged
    baseline."""
    rng = np.random.default_rng(0)
    users = rng.integers(0, 5000, num_ratings)
    items = (rng.zipf(1.2, num_ratings) - 1) % 10_000
    ratings = rng.normal(0, 1, num_ratings).astype(np.float32)
    user_store: dict = {}
    item_store: dict = {}

    def get(store, k):
        v = store.get(k)
        if v is None:
            v = rng.normal(0, 0.01, dim).astype(np.float32)
            store[k] = v
        return v

    t0 = time.perf_counter()
    for n in range(num_ratings):
        u, i, r = users[n], items[n], ratings[n]
        p = get(user_store, u)  # worker-local state lookup
        q = get(item_store, i)  # ps.pull(i)
        err = r - float(p @ q)
        p += lr * err * q  # local user update
        item_store[i] = q + lr * err * p  # ps.push(i, delta)
    dt = time.perf_counter() - t0
    finite = all(
        np.isfinite(v).all() for v in user_store.values()
    ) and all(np.isfinite(v).all() for v in item_store.values())
    return num_ratings / dt, finite


def _switch(name: str, default: str = "0") -> bool:
    """Strict 0|1 env switch: junk values die loudly."""
    raw = os.environ.get(name, default)
    if raw not in ("0", "1"):
        raise SystemExit(f"{name}={raw!r}: 0|1")
    return raw == "1"


def _guarded(metric: str, unit: str, produce) -> bool:
    """Print ``produce()``'s payload as one metric line and return True.

    A failing line must not take down the lines after it, so this is
    the one boundary that catches: the traceback goes to stderr, a
    value-None line carrying the error goes to stdout, and the False
    returned makes ``main`` exit nonzero."""
    try:
        payload = produce()
    except Exception as e:  # noqa: BLE001 — reported, counted, exit != 0
        traceback.print_exc()
        print(json.dumps({
            "metric": metric,
            "value": None,
            "unit": unit,
            "error": f"{type(e).__name__}: {e}",
        }))
        return False
    print(json.dumps({"metric": metric, **payload}))
    return True


def _child_benchmark(script: str) -> dict:
    """Run a ``benchmarks/`` script that drives JAX itself in a child
    and return its last stdout line's payload.  This process has touched
    JAX and holds the chip, so the child is pinned to the CPU in the
    environment it is handed — which is what these four are built for
    (virtual CPU devices, host-side stores)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", script)],
        capture_output=True, text=True, timeout=570,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    if not lines:
        raise RuntimeError(
            f"no output (rc={proc.returncode}): "
            f"{proc.stderr.strip()[-200:]}"
        )
    payload = json.loads(lines[-1])
    payload.pop("metric", None)
    if payload.get("value") is None:
        raise RuntimeError(f"{script} reported no value: {payload}")
    return payload


def _emit_serving_metric(platform: str) -> bool:
    """Second metric line: the serve path (serving_qps + p99_ms).  The
    load is kept small (short window, modest store) so the line costs
    seconds.  FPS_BENCH_SERVING_SECONDS=0 opts out."""
    raw = os.environ.get("FPS_BENCH_SERVING_SECONDS", "3")
    try:
        duration = float(raw)
    except ValueError:
        raise SystemExit(
            f"FPS_BENCH_SERVING_SECONDS={raw!r}: expected a number"
        ) from None
    if duration <= 0:  # explicit opt-out of the serving line
        return True

    def produce():
        from benchmarks.serving_qps import run_serving_bench

        r = run_serving_bench(
            duration_s=duration,
            concurrency=4,
            num_items=8_192,
            dim=32,
            batch=4_096,
        )
        return {
            "value": r["serving_qps"],
            "unit": "queries/sec",
            "extra": {
                "serving_qps": r["serving_qps"],
                "p50_ms": r["p50_ms"],
                "p99_ms": r["p99_ms"],
                "snapshot_staleness_mean_steps": r["staleness_mean_steps"],
                "snapshot_staleness_max_steps": r["staleness_max_steps"],
                "publish_every": r["publish_every"],
                "batch_fill": r["batch_fill"],
                "requests_rejected": r["requests_rejected"],
                "concurrency": r["concurrency"],
                "k": r["k"],
                "platform": r["platform"],
            },
        }

    return _guarded(
        "serving top-K QPS (train-while-serve, online MF)",
        "queries/sec", produce,
    )


def _emit_recovery_metric(platform: str) -> bool:
    """Third metric line: the recovery path (recovery_seconds +
    updates_lost).  FPS_BENCH_RECOVERY=0 opts out; the load is small
    (tens of small-batch steps) so the line costs seconds."""
    if not _switch("FPS_BENCH_RECOVERY", "1"):
        return True

    def produce():
        from benchmarks.recovery_time import run_recovery_bench

        r = run_recovery_bench(
            steps=20,
            crash_at=13,
            checkpoint_every=6,
            batch=1_024,
            num_items=2_048,
            dim=16,
        )
        return {
            "value": r["recovery_seconds"],
            "unit": "seconds",
            "extra": {
                "recovery_seconds": r["recovery_seconds"],
                "updates_lost": r["updates_lost"],
                "tables_bitwise_equal": r["tables_bitwise_equal"],
                "replayed_steps": r["replayed_steps"],
                "restarts": r["restarts"],
                "checkpoint_every": r["checkpoint_every"],
                "crash_at_step": r["crash_at_step"],
                "wal_bytes_peak": r["wal_bytes_peak"],
                "platform": r["platform"],
            },
        }

    return _guarded(
        "crash recovery (checkpoint + WAL replay, online MF)",
        "seconds", produce,
    )


def _emit_telemetry_summary(platform: str) -> bool:
    """Fourth (opt-in) metric line: the unified-registry roll-up.

    FPS_BENCH_TELEMETRY=1 builds the cross-component run report from
    the process-wide MetricsRegistry — which the serving and recovery
    bench lines populated through their driver/serving runs — prints it
    as one JSON line, and writes ``results/<platform>/run_report.{md,
    json}``."""
    if not _switch("FPS_BENCH_TELEMETRY"):
        return True

    def produce():
        from flink_parameter_server_tpu.telemetry import (
            build_run_report,
            write_run_report,
        )

        report = build_run_report()
        paths = write_run_report(report, platform=platform)
        return {
            "value": report["train"]["steps"],
            "unit": "train steps observed",
            "extra": {
                "run_id": report["run_id"],
                "train": report["train"],
                "serving": report["serving"],
                "ingest": report["ingest"],
                "recovery": report["recovery"],
                "run_report_json": os.path.relpath(paths["json"], REPO),
            },
        }

    return _guarded(
        "telemetry summary (unified registry roll-up)",
        "train steps observed", produce,
    )


def _emit_cluster_metric(platform: str) -> bool:
    """Fifth (opt-in) metric line: the multi-shard cluster runtime.

    FPS_BENCH_CLUSTER=1 runs the 1/2/4-shard scaling sweep
    (benchmarks/cluster_scaling.py, thread-backed shards over real TCP)
    and writes ``results/<platform>/cluster_scaling.{md,json}``."""
    if not _switch("FPS_BENCH_CLUSTER"):
        return True

    def produce():
        from benchmarks.cluster_scaling import run_cluster_bench

        r = run_cluster_bench(
            rounds=12,
            batch=1_024,
            num_items=4_096,
            dim=16,
            num_workers=2,
        )
        arms = r["arms"]
        return {
            "value": max(a["updates_per_sec"] for a in arms),
            "unit": "updates/sec (best arm)",
            "extra": {
                "arms": [
                    {
                        "num_shards": a["num_shards"],
                        "updates_per_sec": a["updates_per_sec"],
                        "pull_p50_ms": a["pull_p50_ms"],
                        "pull_p99_ms": a["pull_p99_ms"],
                    }
                    for a in arms
                ],
                "num_workers": r["num_workers"],
                "staleness_bound": r["staleness_bound"],
                "batch": r["batch"],
                "rounds": r["rounds"],
                "platform": r["platform"],
            },
        }

    return _guarded(
        "cluster scaling (multi-shard PS, online MF)",
        "updates/sec (best arm)", produce,
    )


def _emit_elastic_metric(platform: str) -> bool:
    """Sixth (opt-in) metric line: the elastic resize path.

    FPS_BENCH_ELASTIC=1 runs the mid-training 1→2→4 scale-out
    (benchmarks/elastic_scaling.py: live resharding over thread-backed
    shards, migration stall percentiles, hedging win rate, the
    exactly-once audit) and writes
    ``results/<platform>/elastic_scaling.{md,json}``."""
    if not _switch("FPS_BENCH_ELASTIC"):
        return True

    def produce():
        from benchmarks.elastic_scaling import run_elastic_bench

        # the module defaults (rounds=256, batch=2048, items=8192):
        # shorter streams end before the second resize lands, starving
        # the post-resize phase — the same configuration as the
        # committed results/<platform>/elastic_scaling.json artifact
        r = run_elastic_bench()
        return {
            "value": r["updates_per_sec_after"],
            "unit": "updates/sec (post-resize)",
            "extra": {
                "updates_per_sec_before": r["updates_per_sec_before"],
                "updates_per_sec_during": r["updates_per_sec_during"],
                "updates_per_sec_after": r["updates_per_sec_after"],
                "migration_stall_p50_ms": r["migration_stall_p50_ms"],
                "migration_stall_p99_ms": r["migration_stall_p99_ms"],
                "rows_migrated": r["rows_migrated"],
                "hedged_pulls": r["hedged_pulls"],
                "hedges_won": r["hedges_won"],
                "hedge_win_rate": r["hedge_win_rate"],
                "final_epoch": r["final_epoch"],
                "exactly_once": r["exactly_once"],
                "num_workers": r["num_workers"],
                "batch": r["batch"],
                "rounds": r["rounds"],
                "platform": r["platform"],
            },
        }

    return _guarded(
        "elastic scaling (mid-training 1→2→4 scale-out)",
        "updates/sec (post-resize)", produce,
    )


def _emit_failover_metric(platform: str) -> bool:
    """Seventh (opt-in) metric line: replica-chain failover.

    FPS_BENCH_FAILOVER=1 runs the kill-primary-mid-train-while-serve
    experiment (benchmarks/failover_time.py: promote the follower,
    measure kill→publish against a full WAL-rebuild replace_shard on
    the same log length, count serving reads through the window) and
    writes ``results/<platform>/failover_time.{md,json}``."""
    if not _switch("FPS_BENCH_FAILOVER"):
        return True

    def produce():
        from benchmarks.failover_time import run_failover_bench

        r = run_failover_bench()
        return {
            "value": r["failover_seconds"],
            "unit": "seconds",
            "extra": {
                "failover_seconds": r["failover_seconds"],
                "replace_seconds": r["replace_seconds"],
                "speedup_vs_replace": r["speedup_vs_replace"],
                "reads_served_during_failover":
                    r["reads_served_during_failover"],
                "read_errors": r["read_errors"],
                "lag_records_at_promote": r["lag_records_at_promote"],
                "records_salvaged": r["records_salvaged"],
                "promoted_bitwise_equal": r["promoted_bitwise_equal"],
                "replication_factor": r["replication_factor"],
                "rounds": r["rounds"],
                "batch": r["batch"],
                "platform": r["platform"],
            },
        }

    return _guarded(
        "replica-chain failover (kill primary mid-train-while-serve)",
        "seconds", produce,
    )


def _emit_nemesis_metric(platform: str) -> bool:
    """Eighth (opt-in) metric line: the nemesis fault-injection battery.

    FPS_BENCH_NEMESIS=1 replays the committed fixed-seed scenario
    corpus (benchmarks/nemesis_battery.py: chaos-proxied cluster,
    composed network+cluster faults, invariant checkers) and writes
    ``results/<platform>/nemesis.{md,json}``."""
    if not _switch("FPS_BENCH_NEMESIS"):
        return True

    def produce():
        from benchmarks.nemesis_battery import run_nemesis_bench

        r = run_nemesis_bench()
        return {
            "value": r["scenarios_passed"],
            "unit": "scenarios passed",
            "extra": {
                "scenarios_run": r["scenarios_run"],
                "scenarios_passing_expected":
                    r["scenarios_passing_expected"],
                "scenarios_passed": r["scenarios_passed"],
                "violations_seeded": r["violations_seeded"],
                "violations_caught": r["violations_caught"],
                "corpus_replay_ok": r["corpus_replay_ok"],
                "fault_classes": r["fault_classes"],
                "faults_injected": r["faults_injected"],
                "wall_s": r["wall_s"],
                "platform": r["platform"],
            },
        }

    return _guarded(
        "nemesis scenario battery (fixed-seed fault injection)",
        "scenarios passed", produce,
    )


def _emit_hotcache_metric(platform: str) -> bool:
    """Ninth (opt-in) metric line: the hot-key lease cache tier.

    FPS_BENCH_HOTCACHE=1 runs the hot-key storm A/B
    (benchmarks/hotcache_storm.py: 1% of keys take 90% of reads,
    open-loop at a load beyond the uncached arm's capacity over
    ChaosProxy-delayed links, tier on vs off) and writes
    ``results/<platform>/hotcache_storm.{md,json}``."""
    if not _switch("FPS_BENCH_HOTCACHE"):
        return True

    def produce():
        from benchmarks.hotcache_storm import run_hotcache_bench

        r = run_hotcache_bench()
        return {
            "value": r["on"]["p99_ms"],
            "unit": "ms",
            "extra": {
                "p99_ms_off": r["off"]["p99_ms"],
                "p99_ms_on": r["on"]["p99_ms"],
                "p50_ms_off": r["off"]["p50_ms"],
                "p50_ms_on": r["on"]["p50_ms"],
                "p99_speedup": r["p99_speedup"],
                "p50_speedup": r["p50_speedup"],
                "offered_rps": r["offered_rps"],
                "capacity_rps_off": r["off"]["capacity_rps"],
                "capacity_rps_on": r["on"]["capacity_rps"],
                "wire_bytes_per_request_off":
                    r["off"]["wire_bytes_per_request"],
                "wire_bytes_per_request_on":
                    r["on"]["wire_bytes_per_request"],
                "wire_bytes_ratio": r["wire_bytes_ratio"],
                "cache_hit_rate": r["cache_hit_rate"],
                "nemesis_mid_lease_ok":
                    r.get("nemesis_mid_lease", {}).get("ok"),
                "platform": r["platform"],
            },
        }

    return _guarded(
        "hotcache storm serving p99 (1% keys = 90% reads, tier on)",
        "ms", produce,
    )


def _emit_soak_metric(platform: str) -> bool:
    """Tenth (opt-in) metric line: the open-loop soak + overload A/B.

    FPS_BENCH_SOAK=1 runs benchmarks/soak_capacity.py — a capacity
    sweep (QPS vs shards×replicas at the p99 SLO), a 2×-capacity
    open-loop A/B (overload-control plane on vs off, nemesis schedule
    underneath) and an autoscaler-quality trace — and writes
    ``results/<platform>/soak_capacity.{md,json}``.
    FPS_BENCH_SOAK_SECONDS shortens the A/B arms (default 60)."""
    if not _switch("FPS_BENCH_SOAK"):
        return True

    def produce():
        from benchmarks.soak_capacity import run_soak_bench

        r = run_soak_bench(
            duration_s=float(os.environ.get("FPS_BENCH_SOAK_SECONDS", "60"))
        )
        on, off = r["arms"]["on"], r["arms"]["off"]
        return {
            "value": on["goodput_rps"],
            "unit": "req/sec",
            "extra": {
                "capacity_rps": r["capacity_rps"],
                "offered_rps": r["offered_rps"],
                "goodput_frac_of_capacity_on":
                    r["goodput_frac_of_capacity_on"],
                "goodput_frac_of_capacity_off":
                    r["goodput_frac_of_capacity_off"],
                "p99_ms_on": on["p99_ms"],
                "p99_ms_off": off["p99_ms"],
                "shed_on": on["shed"],
                "shed_off": off["shed"],
                "autoscaler_score": r["autoscaler"]["score"],
                "invariants_ok": r["invariants_ok"],
                "platform": r["platform"],
            },
        }

    return _guarded(
        "soak goodput at 2x capacity (open-loop, overload control on)",
        "req/sec", produce,
    )


def _emit_compression_metric(platform: str) -> bool:
    """Eleventh (opt-in) metric line: the quantized push path A/B.

    FPS_BENCH_COMPRESSION=1 runs benchmarks/compression_ab.py — the
    fp32-vs-q8 push codec A/B over bandwidth-capped links, the
    aggregation-tree A/B, the replication-leg catch-up on the same
    log, and the BSP bitwise carve-out pin — and writes
    ``results/<platform>/compression_ab.{md,json}``."""
    if not _switch("FPS_BENCH_COMPRESSION"):
        return True

    def produce():
        from benchmarks.compression_ab import run_compression_bench

        r = run_compression_bench()
        q8, f32 = r["push"]["q8"], r["push"]["f32"]
        return {
            "value": r["push_bytes_ratio"],
            "unit": "x (higher is better)",
            "extra": {
                "push_bytes_per_round_f32": f32["push_bytes_per_round"],
                "push_bytes_per_round_q8": q8["push_bytes_per_round"],
                "push_p99_ms_f32": f32["push_p99_ms"],
                "push_p99_ms_q8": q8["push_p99_ms"],
                "rel_rmse_q8": q8["rel_rmse_vs_oracle"],
                "rel_rmse_f32": f32["rel_rmse_vs_oracle"],
                "bsp_bitwise": r["bsp_bitwise"],
                "aggregation_frames_ratio":
                    r["aggregation"]["frames_ratio"],
                "repl_catch_up_ratio":
                    r["replication"]["catch_up_ratio"],
                "repl_bytes_ratio": r["replication"]["bytes_ratio"],
                "platform": r["platform"],
            },
        }

    return _guarded(
        "compression push bytes ratio (fp32/q8, equal RMSE)",
        "x (higher is better)", produce,
    )


def _emit_workloads_metric(platform: str) -> bool:
    """Twelfth (opt-in) metric line: the workload-generic runtime.

    FPS_BENCH_WORKLOADS=1 runs benchmarks/workload_battery.py — the
    PA-classifier and count-min-sketch full-stack scenarios
    (train-while-serve-while-resize-while-faulted, parity bitwise /
    integer-exact) plus the short q8/aggregation soak arms — and
    writes ``results/<platform>/workload_battery.{md,json}``
    (docs/workloads.md).  FPS_BENCH_WORKLOADS_SECONDS sizes the soak
    arms (default 8)."""
    if not _switch("FPS_BENCH_WORKLOADS"):
        return True

    def produce():
        from benchmarks.workload_battery import run_workload_battery

        r = run_workload_battery(
            soak_seconds=float(os.environ.get(
                "FPS_BENCH_WORKLOADS_SECONDS", "8"
            ))
        )
        return {
            "value": r["scenarios_passed"],
            "unit": "scenarios passed",
            "extra": {
                "scenarios": [
                    {k: s[k] for k in ("scenario", "workload", "ok",
                                       "parity_mode")}
                    for s in r["scenarios"]
                ],
                "soak_q8_goodput_rps":
                    r["soak_arms"]["q8"]["goodput_rps"],
                "soak_q8_bytes_saved":
                    r["soak_arms"]["q8"]["compression_bytes_saved"],
                "soak_q8_agg_combined_pushes":
                    r["soak_arms"]["q8_agg"]["combined_pushes"],
                "platform": r["platform"],
            },
        }

    return _guarded(
        "workload battery (PA + sketch full-stack scenarios)",
        "scenarios passed", produce,
    )


def _emit_mesh_metric(platform: str) -> bool:
    """Thirteenth (opt-in) metric line: the device-mesh store backend.

    FPS_BENCH_MESH=1 runs benchmarks/mesh_backend_ab.py — PA through
    ``store_backend="mesh"`` vs the proc-shard socket path at equal
    worker count (updates/sec + pull/push p50/p99 + parity verdict) —
    and writes ``results/cpu/mesh_backend_ab.{md,json}``, the artifact
    linted by ``tools/check_metric_lines.py --mesh-ab``
    (docs/meshstore.md).  Runs as a CPU child: the mesh arm needs
    ``--xla_force_host_platform_device_count=8`` applied before jax's
    backend initializes, which this process's backend is already past."""
    if not _switch("FPS_BENCH_MESH"):
        return True
    return _guarded(
        "mesh backend A/B (on-device vs proc-shard sockets)",
        "x updates/sec speedup",
        lambda: _child_benchmark("mesh_backend_ab.py"),
    )


def _emit_timeline_metric(platform: str) -> bool:
    """Fourteenth (opt-in) metric line: the timeline detection A/B.

    FPS_BENCH_TIMELINE=1 runs benchmarks/timeline_detection_ab.py —
    the committed straggler-storm-SSP schedule twice (as committed +
    fault-free oracle) with a live ``TimelineRecorder``; the metric is
    how fast the skew tracker / detectors NAME the seeded slow shard
    (bar: 3 sample windows, with zero oracle-arm firings) — and
    writes ``results/cpu/soak_timeline.{md,json}``, the artifact
    linted by ``tools/check_metric_lines.py --timeline``
    (docs/observability.md)."""
    if not _switch("FPS_BENCH_TIMELINE"):
        return True
    return _guarded(
        "timeline straggler detection latency", "seconds",
        lambda: _child_benchmark("timeline_detection_ab.py"),
    )


def _emit_straggler_metric(platform: str) -> bool:
    """Fifteenth (opt-in) metric line: the straggler goodput A/B.

    FPS_BENCH_STRAGGLER=1 runs benchmarks/straggler_ab.py — worker 0's
    links through an 8 ms delay proxy, the same deadline-bounded job
    under stock SSP vs the adaptive runtime (docs/adaptive.md), both
    MF and PA; the metric is the worst-workload goodput ratio
    (bar: >= 2x at equal final-table RMSE, bound envelope green) —
    and writes ``results/cpu/straggler_ab.{md,json}``, the artifact
    linted by ``tools/check_metric_lines.py --straggler-ab``."""
    if not _switch("FPS_BENCH_STRAGGLER"):
        return True
    return _guarded(
        "straggler adaptive goodput ratio",
        "x (adaptive / fixed-bound, worst workload)",
        lambda: _child_benchmark("straggler_ab.py"),
    )


def _emit_tier_metric(platform: str) -> bool:
    """Sixteenth (opt-in) metric line: the two-tier store soak.

    FPS_BENCH_TIER=1 runs benchmarks/tierstore_soak.py — the Criteo-
    scale arms (2^24 rows) under a Zipf mix, tiered vs all-RAM, plus
    the correctness legs (bitwise parity, kill→promote, WAL replay
    through cold rows, elastic migration; docs/tierstore.md); the
    metric is the hot-path pull-latency ratio (bar: <= 2x at a
    recorded peak-RSS bound) — and writes
    ``results/cpu/tierstore_soak.{md,json}``, the artifact linted by
    ``tools/check_metric_lines.py --tier``."""
    if not _switch("FPS_BENCH_TIER"):
        return True
    return _guarded(
        "tierstore pull latency ratio at bounded RSS",
        "x slowdown (tiered / all-RAM pull p50)",
        lambda: _child_benchmark("tierstore_soak.py"),
    )


_EMITTERS = (
    _emit_serving_metric,
    _emit_recovery_metric,
    _emit_telemetry_summary,
    _emit_cluster_metric,
    _emit_elastic_metric,
    _emit_failover_metric,
    _emit_nemesis_metric,
    _emit_hotcache_metric,
    _emit_soak_metric,
    _emit_compression_metric,
    _emit_workloads_metric,
    _emit_mesh_metric,
    _emit_timeline_metric,
    _emit_straggler_metric,
    _emit_tier_metric,
)


def _headline(device) -> dict:
    r = tpu_updates_per_sec()
    cpu_rate, baseline_finite = cpu_per_record_baseline(dim=r["dim"])
    util = r["bandwidth_util"]
    return {
        "value": round(r["updates_per_sec_per_chip"], 1),
        "unit": "updates/sec/chip",
        # a diverged (non-finite) baseline is not a yardstick
        "vs_baseline": (
            round(r["updates_per_sec_per_chip"] / cpu_rate, 2)
            if baseline_finite
            else None
        ),
        **device,
        "extra": {
            # e2e includes host dispatch; device is the scan-amortized
            # per-step latency
            "pull_push_p50_ms": round(r["p50_ms"], 3),
            "p50_e2e_ms": round(r["p50_ms"], 3),
            "p50_device_ms": (
                round(r["p50_device_ms"], 3)
                if r["p50_device_ms"] is not None else None
            ),
            "batch": r["batch"],
            "per_record_baseline_updates_per_sec": round(cpu_rate, 1),
            "baseline_finite": baseline_finite,
            "platform": device["platform"],
            "table_dtype": r["table_dtype"],
            "hbm_bytes_per_step": r["hbm_bytes_per_step"],
            "bandwidth_util": round(util, 4) if util else None,
            "dim": r["dim"],
            "layout": r["layout"],
            "reps": r["reps"],
            "rate_min": round(r["rate_min"], 1),
            "rate_max": round(r["rate_max"], 1),
        },
    }


def main() -> int:
    sys.path.insert(0, REPO)
    import jax

    from flink_parameter_server_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }
    ok = _guarded(
        "MF-SGD updates/sec/chip (synthetic MovieLens-like, Zipf items)",
        "updates/sec/chip", lambda: _headline(device),
    )
    for emit in _EMITTERS:
        ok = emit(device["platform"]) and ok
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
