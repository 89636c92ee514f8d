"""Straggler goodput A/B: the adaptive runtime vs a fixed SSP bound.

The paper's flexible-consistency claim, priced.  One worker's links to
every shard run through a delay proxy (a per-WORKER straggler — the
other workers' links are direct, so the skew is between workers, not
shards), and the same time-bounded training job runs twice per
workload:

  * **fixed arm** — stock SSP at ``staleness_bound=2``.  The gate
    caps every healthy worker at ``straggler + 2`` rounds, so the
    fleet's steady-state rate IS the straggler's rate: the lagged
    links tax all four workers.
  * **adaptive arm** — same topology, same chaos, same deadline, with
    the closed loop live (``ClusterConfig(adaptive=True)`` +
    :class:`~flink_parameter_server_tpu.adaptive.AdaptiveRuntime`
    fed by a :class:`~...telemetry.timeline.TimelineRecorder` watching
    per-worker pull RTT): the straggler's allowance widens toward the
    ceiling (immediate slack), its pushes hedge, and — once the skew
    persists — its row groups re-route to healthy workers at future
    round boundaries, after which its rounds are wire-free and the
    fleet runs at memory speed.

Both arms run under ``driver.run(deadline_s=...)``: under a fixed
wall budget the work completed is the metric (on a fixed workload the
wall clock is floored by the straggler in every arm, which is exactly
the number the adaptive loop exists to change).  Goodput is masked
training events per measured second.  Quality is final-table RMSE
against the fault-free full-stream oracle — the adaptive arm's extra
throughput must not come at the model's expense, so the bar is
``adaptive_rmse <= fixed_rmse`` (within 10%): consistency relaxed
only where the evidence says it is free.

The bound envelope is sampled live
(:class:`~...nemesis.invariants.AdaptiveBoundSampler` at 2 ms) and
audited by ``check_adaptive_bound`` — a goodput win that escaped
``[bound, ceiling]`` would be a correctness trade, not an
optimization, and fails the run.  Every mechanism's firings are
counted in the artifact (a "win" with zero widenings/hedges/moves
means the chaos never bit).

Artifacts: ``results/cpu/straggler_ab.{md,json}``, self-linted by
``tools/check_metric_lines.py --straggler-ab`` before anything is
written; the ``payloads`` list folds into ``tools/bench_history.py``.

Usage::

    JAX_PLATFORMS=cpu python benchmarks/straggler_ab.py \
        [--deadline 4.0] [--lag-ms 25] [--out results/cpu]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

METRIC = "cluster_pull_rtt_seconds"
WORKERS = 4
SHARDS = 2
BOUND = 2          # the correctness bound both arms declare
SUBGROUPS = 8      # row groups per worker (adaptive/rebalance.py)
WORKLOADS = ("mf", "pa")


def _params(workload: str):
    from flink_parameter_server_tpu.workloads import WorkloadParams

    # rounds sized so no arm exhausts the stream inside the deadline
    # (a stream-bounded "goodput" number would cap the fast arm);
    # small batches keep per-round wire cost realistic at CPU scale
    return WorkloadParams(
        rounds=4000, batch=16, num_users=64, num_items=96, dim=8,
        seed=3, num_workers=WORKERS,
    )


def _warm_jit(workload_name: str) -> None:
    """Compile the shard-side scatter/gather kernels for every push and
    pull size the run can produce, on a throwaway no-lag topology.

    The shard store's push/pull executables are shape-keyed and the
    compile cache is process-wide: without this sweep the FIRST arm to
    run eats one ~25 ms XLA compile per novel unique-id count inside
    its measured window (≈0.5 s of a 2 s deadline) and the second arm
    rides warm — a cache asymmetry, not a scheduling effect.  Zero
    deltas keep the warmup value-neutral (both workloads are
    ``push_semantics="delta"``)."""
    import numpy as np

    from flink_parameter_server_tpu.workloads import (
        build_cluster_driver,
        create_workload,
    )

    params = _params(workload_name)
    wl = create_workload(workload_name, params)
    driver = build_cluster_driver(
        wl, config=None, num_shards=SHARDS, num_workers=1,
        staleness_bound=BOUND, partition="hash",
    )
    with driver:
        driver.start()
        client = driver._clients[0]
        cap = driver.capacity
        shape = tuple(driver.value_shape)
        for k in range(1, params.batch + 1):
            ids = np.arange(k, dtype=np.int64)
            client.push_batch(ids, np.zeros((k,) + shape, np.float32))
            client.pull_batch(ids)
        # ids spread across the table exercise the 2-shard split path
        wide = np.linspace(0, cap - 1, params.batch).astype(np.int64)
        client.push_batch(
            np.unique(wide),
            np.zeros((np.unique(wide).size,) + shape, np.float32),
        )


class _LaggedMembership:
    """The straggler worker's view of the cluster: every shard address
    remapped to its delay proxy.  Epochs, partitioner and everything
    else delegate to the real service — only the addresses lie."""

    def __init__(self, inner, addresses):
        self._inner = inner
        self._addresses = tuple(tuple(a) for a in addresses)

    def current(self):
        return dataclasses.replace(
            self._inner.current(),
            addresses=self._addresses, replicas=(),
        )

    def __getattr__(self, name):
        return getattr(self._inner, name)


def _make_driver_class(lag_ms: float):
    from flink_parameter_server_tpu.elastic.controller import (
        ElasticClusterDriver,
    )
    from flink_parameter_server_tpu.nemesis.proxy import ChaosProxy

    class LaggedWorkerDriver(ElasticClusterDriver):
        """Elastic cluster where worker 0's client reaches every shard
        through a ChaosProxy with a symmetric per-request delay — the
        per-worker link straggler both arms train under."""

        lag_worker = "0"

        def __init__(self, logic, **kwargs):
            self.lag_proxies = []
            super().__init__(logic, **kwargs)

        def _make_client(self, worker=None):
            if worker != self.lag_worker:
                return super()._make_client(worker)
            real = self.membership
            proxied = []
            for host, port in real.current().addresses:
                p = ChaosProxy(
                    host, port, name=f"lag-{port}", seed=11,
                    registry=False,
                ).start()
                p.set_delay(lag_ms, 0.0, "both")
                self.lag_proxies.append(p)
                proxied.append((p.host, p.port))
            # the facade only scopes to THIS client's construction —
            # the healthy workers and the control planes keep the
            # direct addresses
            self.membership = _LaggedMembership(real, proxied)
            try:
                return super()._make_client(worker)
            finally:
                self.membership = real

        def stop(self):
            super().stop()
            for p in self.lag_proxies:
                p.stop()
            self.lag_proxies = []

    return LaggedWorkerDriver


def _rmse(values, oracle) -> float:
    import numpy as np

    v = np.asarray(values, np.float64)
    o = np.asarray(oracle, np.float64)
    return float(np.sqrt(np.mean((v - o) ** 2)))


def run_arm(
    workload_name: str, *, adaptive: bool, deadline_s: float,
    lag_ms: float, oracle,
) -> dict:
    from flink_parameter_server_tpu.adaptive import (
        AdaptiveRuntime,
        RebalancePolicy,
        WorkRouter,
    )
    from flink_parameter_server_tpu.elastic.controller import (
        ElasticClusterConfig,
    )
    from flink_parameter_server_tpu.nemesis.invariants import (
        AdaptiveBoundSampler,
        check_adaptive_bound,
    )
    from flink_parameter_server_tpu.telemetry.registry import (
        MetricsRegistry,
    )
    from flink_parameter_server_tpu.telemetry.timeline import (
        SkewTracker,
        TimelineRecorder,
    )
    from flink_parameter_server_tpu.workloads import (
        build_cluster_driver,
        create_workload,
    )

    reg = MetricsRegistry()
    wl = create_workload(workload_name, _params(workload_name))
    cfg = ElasticClusterConfig(
        num_shards=SHARDS, num_workers=WORKERS,
        staleness_bound=BOUND, partition="hash",
        adaptive=adaptive,
        adaptive_push_hedge_after_s=0.01 if adaptive else None,
    )
    driver = build_cluster_driver(
        wl, config=cfg, driver_cls=_make_driver_class(lag_ms),
        registry=reg,
    )
    batches = list(wl.batches())
    tl = rt = None
    bound_samples = []
    with driver:
        # one unmeasured round before anything attaches: compiles this
        # driver's jitted step and dials every connection (including
        # worker 0's through the proxies), so the deadline window
        # measures steady-state rounds in BOTH arms
        driver.run(batches[:1])
        if adaptive:
            tl = TimelineRecorder(
                reg, interval_s=0.04,
                include=lambda n: n == METRIC,
                skew=[SkewTracker(
                    METRIC, entity_label="worker", field="p50",
                    min_points=2, warmup_evals=2,
                )],
            ).start()
            router = WorkRouter(WORKERS, subgroups=SUBGROUPS)
            driver.work_router = router
            rt = AdaptiveRuntime(
                driver, tl, interval_s=0.04, registry=reg,
                rebalance=RebalancePolicy(
                    router, persist_evals=2, cooldown_s=0.1,
                    max_moves=SUBGROUPS, groups_per_move=4,
                    round_delay=2,
                ),
            ).start()
        try:
            with AdaptiveBoundSampler(driver) as sampler:
                result = driver.run(batches, deadline_s=deadline_s)
            bound_samples = list(sampler.samples)
        finally:
            if rt is not None:
                rt.stop()
            if tl is not None:
                tl.stop()
        payload = rt.payload() if rt is not None else None

    arm = {
        "events": int(result.events),
        "rounds": int(result.rounds),
        "wall_s": round(result.wall_s, 4),
        "goodput_eps": round(result.updates_per_sec, 2),
        "rmse": round(_rmse(result.values, oracle), 6),
    }
    if adaptive:
        ceiling = 2 * BOUND + 1  # _make_clock's default, mirrored
        verdict = check_adaptive_bound(bound_samples, BOUND, ceiling)
        nonempty = [row for row in bound_samples if row]
        arm["mechanisms"] = {
            "widenings": int(payload["counts"]["widenings"]),
            "narrowings": int(payload["counts"]["narrowings"]),
            "hedged_pushes": int(payload["hedge"]["issued"]),
            "push_hedges_won": int(payload["hedge"]["won"]),
            "rebalances": int(payload["rebalance"]["moves"]),
        }
        arm["bound_envelope"] = {
            "bound": BOUND,
            "ceiling": ceiling,
            "samples": len(bound_samples),
            "low": min((min(r) for r in nonempty), default=BOUND),
            "high": max((max(r) for r in nonempty), default=BOUND),
            "ok": bool(verdict.ok),
            "detail": verdict.detail,
        }
        arm["rebalance_assignments"] = payload["rebalance"]["assignments"]
        arm["decisions"] = len(payload["decisions"])
    return arm


def run_straggler_ab(
    *, deadline_s: float = 4.0, lag_ms: float = 25.0,
) -> dict:
    from flink_parameter_server_tpu.workloads import create_workload

    workloads = {}
    for name in WORKLOADS:
        _warm_jit(name)
        # fault-free full-stream reference table, computed once per
        # workload — both arms' RMSE measure distance to the SAME
        # converged target
        oracle = create_workload(name, _params(name)).oracle_values()
        fixed = run_arm(
            name, adaptive=False, deadline_s=deadline_s,
            lag_ms=lag_ms, oracle=oracle,
        )
        adaptive = run_arm(
            name, adaptive=True, deadline_s=deadline_s,
            lag_ms=lag_ms, oracle=oracle,
        )
        ratio = (
            adaptive["goodput_eps"] / fixed["goodput_eps"]
            if fixed["goodput_eps"] > 0 else float("inf")
        )
        rmse_ok = adaptive["rmse"] <= fixed["rmse"] * 1.10
        workloads[name] = {
            "arms": {"fixed": fixed, "adaptive": adaptive},
            "goodput_ratio": round(ratio, 3),
            "rmse_ok": rmse_ok,
            "passed": bool(
                ratio >= 2.0 and rmse_ok
                and adaptive["bound_envelope"]["ok"]
            ),
        }
    return {
        "deadline_s": deadline_s,
        "lag_ms": lag_ms,
        "workers": WORKERS,
        "shards": SHARDS,
        "bound": BOUND,
        "workloads": workloads,
        "passed": all(w["passed"] for w in workloads.values()),
    }


def write_artifacts(r: dict, out_dir: str) -> None:
    from flink_parameter_server_tpu.telemetry.registry import (
        default_run_id,
    )
    from tools.check_metric_lines import check_straggler_ab

    doc = {
        "ts": round(time.time(), 3),
        "run_id": default_run_id(),
        "kind": "straggler_ab",
        "straggler_ab": r,
        "payloads": [
            {
                "metric": f"straggler goodput ratio ({name})",
                "value": w["goodput_ratio"],
                "unit": "x (adaptive / fixed-bound)",
            }
            for name, w in r["workloads"].items()
        ] + [
            {
                "metric": f"straggler adaptive goodput ({name})",
                "value": w["arms"]["adaptive"]["goodput_eps"],
                "unit": "events/sec",
            }
            for name, w in r["workloads"].items()
        ],
        "host": {"cpus": os.cpu_count()},
    }
    bad = check_straggler_ab(doc)
    if bad:
        raise SystemExit(
            f"straggler_ab: artifact failed its own lint: {bad}"
        )
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "straggler_ab.json"), "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")

    rows = []
    for name, w in r["workloads"].items():
        for arm_name in ("fixed", "adaptive"):
            a = w["arms"][arm_name]
            mech = a.get("mechanisms", {})
            rows.append(
                f"| {name} | {arm_name} | {a['goodput_eps']:.0f} | "
                f"{a['events']} | {a['rmse']:.4f} | "
                f"{mech.get('widenings', '—')} | "
                f"{mech.get('hedged_pushes', '—')} | "
                f"{mech.get('rebalances', '—')} |"
            )
    envs = {
        name: w["arms"]["adaptive"]["bound_envelope"]
        for name, w in r["workloads"].items()
    }
    env_lines = "\n".join(
        f"* {name}: effective bounds stayed in "
        f"[{e['low']}, {e['high']}] vs declared "
        f"[{e['bound']}, {e['ceiling']}] over {e['samples']} samples "
        f"— {'OK' if e['ok'] else 'VIOLATED'}"
        for name, e in envs.items()
    )
    ratio_lines = "\n".join(
        f"* **{name}**: {w['goodput_ratio']:.2f}× goodput "
        f"(bar ≥ 2×), adaptive RMSE {w['arms']['adaptive']['rmse']:.4f}"
        f" vs fixed {w['arms']['fixed']['rmse']:.4f} "
        f"(bar: no worse within 10%) — "
        f"{'PASS' if w['passed'] else 'FAIL'}"
        for name, w in r["workloads"].items()
    )
    md = f"""# Straggler A/B — adaptive runtime vs fixed SSP bound

Worker 0's links to both shards run through a {r['lag_ms']} ms
symmetric delay proxy (a per-worker straggler; the other
{r['workers'] - 1} workers' links are direct).  The same training job
runs time-bounded (`driver.run(deadline_s={r['deadline_s']})`) twice
per workload: stock SSP at bound {r['bound']} (the gate caps the
fleet at the straggler's pace) vs the adaptive runtime
(docs/adaptive.md: per-worker bound widening to ceiling
{2 * r['bound'] + 1}, push hedging, row-group re-routing).  Goodput =
masked training events / measured second; RMSE = final-table distance
to the fault-free full-stream oracle (both arms, same target).

| workload | arm | goodput (events/s) | events | RMSE | widenings | hedged pushes | rebalances |
|---|---|---|---|---|---|---|---|
{chr(10).join(rows)}

{ratio_lines}

Bound-envelope invariant (`check_adaptive_bound`, 2 ms live
sampling):

{env_lines}

**Overall: {"PASS" if r['passed'] else "FAIL"}.**  The fixed arm
prices the consistency tax: every worker is gated to the straggler's
round rate, so the lagged links cost the whole fleet.  The adaptive
arm's widened allowance buys immediate slack (the healthy workers
run ahead inside the audited envelope), hedged pushes cut the
straggler's own round time where a duplicate leg wins, and the
re-balancer's row-group moves make the steady state: once the
straggler owns no rows its rounds are wire-free, and the fleet runs
at memory speed while the model keeps training on every row —
quality held at equal-or-better final RMSE because the relaxation
never exceeded the declared ceiling.

Produced by `benchmarks/straggler_ab.py` (`FPS_BENCH_STRAGGLER=1
python bench.py`); linted by `tools/check_metric_lines.py
--straggler-ab`; folded into the perf ledger by
`tools/bench_history.py` (payloads list); pinned by
tests/test_adaptive.py (committed-artifact lint).
"""
    with open(os.path.join(out_dir, "straggler_ab.md"), "w") as f:
        f.write(md)


def main() -> int:
    from flink_parameter_server_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--deadline", type=float, default=4.0)
    p.add_argument("--lag-ms", type=float, default=25.0)
    p.add_argument("--out", default=os.path.join(REPO, "results", "cpu"))
    args = p.parse_args()
    r = run_straggler_ab(deadline_s=args.deadline, lag_ms=args.lag_ms)
    write_artifacts(r, args.out)
    ratios = {
        name: w["goodput_ratio"] for name, w in r["workloads"].items()
    }
    print(json.dumps({
        "metric": "straggler adaptive goodput ratio",
        "value": min(ratios.values()),
        "unit": "x (adaptive / fixed-bound, worst workload)",
        "extra": {
            "ratios": ratios,
            "deadline_s": r["deadline_s"],
            "lag_ms": r["lag_ms"],
            "passed": r["passed"],
        },
    }))
    return 0 if r["passed"] else 1


if __name__ == "__main__":
    sys.exit(main())
