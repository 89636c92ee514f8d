"""Telemetry-overhead benchmark: what does the unified plane cost?

The acceptance bar for the telemetry PR (ISSUE 3) is quantitative:
steps/sec with the observability plane enabled must sit within 3% of
disabled on the CPU microbench.  ISSUE 6 widened the plane, so the ON
arm carries the registry + span tracer (``DriverConfig.telemetry``), a
hot-key sketch observing every microbatch's item ids on the ingest
path (telemetry/hotkeys.py), and an SLO engine sampling the registry
on its own poll thread (telemetry/slo.py).  ISSUE 7 widened it again:
the ON arm now ALSO runs the sampling stack profiler
(telemetry/profiler.py ``StackSampler``, default 100 ms interval) for
the whole measured window.  ISSUE 18 adds the timeline plane: a
``TimelineRecorder`` polling every instrument into ring series at the
same 100 ms cadence, with both online detectors (EWMA drift +
rolling-MAD) scoring the training series on every tick.  The OFF arm
runs none of it.  Same logic, same store
shapes, same stream; the result folds into
``results/<platform>/run_report.{md,json}`` (the page
docs/perf_status.md says future bench deltas must cite).  ``main()``
additionally runs the latency-budget cluster round
(``benchmarks/latency_budget.py`` — phase timers + wire byte
accounting on a real TCP topology, the paths the driver microbench
cannot exercise) before writing the report, so the committed
run_report carries the budget section.

Methodology: interleaved reps (on, off, on, off, ...) so drift in the
shared CPU hits both arms equally; per-arm rate = median of reps; the
reported ratio is median(on)/median(off).  The first rep of each arm
is a throwaway (jit compilation).

Usage::

    JAX_PLATFORMS=cpu python benchmarks/telemetry_overhead.py \
        [--steps 200] [--reps 3] [--batch 1024]

Prints one JSON line (bench.py metric-line shape) and writes the run
report under results/<platform>/.
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


def _one_run(*, telemetry: bool, steps: int, batch: int, num_users: int,
             num_items: int, dim: int, seed: int) -> float:
    """One driver run; returns steps/sec (dispatch loop only).  With
    ``telemetry`` on, the FULL observability plane rides along:
    registry + spans (driver config), a hot-key sketch on the ingest
    path, a polling SLO engine, and the sampling stack profiler."""
    from flink_parameter_server_tpu.core.store import ShardedParamStore
    from flink_parameter_server_tpu.data.streams import microbatches
    from flink_parameter_server_tpu.models.matrix_factorization import (
        OnlineMatrixFactorization,
        SGDUpdater,
    )
    from flink_parameter_server_tpu.telemetry.detectors import (
        EWMADriftDetector,
        RollingMADDetector,
    )
    from flink_parameter_server_tpu.telemetry.hotkeys import HotKeySketch
    from flink_parameter_server_tpu.telemetry.profiler import StackSampler
    from flink_parameter_server_tpu.telemetry.slo import (
        SLOEngine,
        pull_latency_slo,
        serving_latency_slo,
    )
    from flink_parameter_server_tpu.telemetry.timeline import (
        TimelineRecorder,
    )
    from flink_parameter_server_tpu.training.driver import (
        DriverConfig,
        StreamingDriver,
    )
    from flink_parameter_server_tpu.utils.initializers import normal_factor

    rng = np.random.default_rng(seed)
    data = {
        "user": rng.integers(0, num_users, steps * batch).astype(np.int32),
        "item": ((rng.zipf(1.2, steps * batch) - 1) % num_items).astype(
            np.int32
        ),
        "rating": rng.normal(0, 1, steps * batch).astype(np.float32),
    }
    logic = OnlineMatrixFactorization(
        num_users, dim, updater=SGDUpdater(0.01)
    )
    store = ShardedParamStore.create(
        num_items, (dim,), init_fn=normal_factor(1, (dim,))
    )
    driver = StreamingDriver(
        logic, store,
        config=DriverConfig(dump_model=False, telemetry=telemetry),
    )
    stream = microbatches(data, batch, epochs=1)
    slo_engine = None
    sampler = None
    if telemetry:
        sketch = HotKeySketch(32)

        def observed(batches):
            # sketch cost lands INSIDE the measured window, on the
            # ingest path — where the cluster shards pay it
            for b in batches:
                sketch.observe(b["item"])
                yield b

        stream = observed(stream)
        slo_engine = SLOEngine(
            [pull_latency_slo(), serving_latency_slo()],
            windows=(1.0, 5.0), register_gauges=False,
        ).start(interval_s=0.02)
        # the sampling stack profiler walks every live thread's frames
        # at its default interval — its cost (tick + GIL preemption
        # tax) is paid INSIDE the measured window
        sampler = StackSampler().start()
        # the timeline plane rides too: the recorder polls EVERY
        # instrument at the StackSampler's cadence and both online
        # detectors score the training series on each tick
        timeline = TimelineRecorder(
            interval_s=0.1,
            detectors=[
                EWMADriftDetector("pull_push_latency_seconds",
                                  field="p99"),
                RollingMADDetector("train_events_total",
                                   field="rate"),
            ],
        ).start()
        # stashed (never installed as the process default here — tests
        # call this as a library and must not inherit a global); main()
        # installs the final ON rep's recorder for the report section
        global _LAST_ON_TIMELINE
        _LAST_ON_TIMELINE = timeline
    else:
        timeline = None
    t0 = time.perf_counter()
    try:
        driver.run(stream)
    finally:
        if slo_engine is not None:
            slo_engine.stop()
        if sampler is not None:
            sampler.stop()
        if timeline is not None:
            timeline.stop()
    dt = time.perf_counter() - t0
    return driver.step_idx / dt


# the final ON rep's (stopped) recorder — main() installs it as the
# process default just long enough for the run report's timeline section
_LAST_ON_TIMELINE = None


def run_overhead_bench(
    *,
    steps: int = 200,
    reps: int = 3,
    batch: int = 1_024,
    num_users: int = 2_000,
    num_items: int = 8_192,
    dim: int = 32,
    seed: int = 0,
) -> dict:
    """Interleaved on/off A/B; returns the metrics dict (import-time
    side-effect free — tests import and call this with tiny shapes)."""
    import jax

    from flink_parameter_server_tpu import telemetry as tm

    # a fresh registry/tracer per bench: the A/B must not inherit a
    # prior run's instruments (cost is per-update, but hygiene is free)
    tm.set_registry(tm.MetricsRegistry())
    tm.set_tracer(tm.SpanTracer())

    on_rates, off_rates = [], []
    # throwaway rep 0 (compilation) per arm, then interleave
    _one_run(telemetry=True, steps=steps, batch=batch,
             num_users=num_users, num_items=num_items, dim=dim, seed=seed)
    _one_run(telemetry=False, steps=steps, batch=batch,
             num_users=num_users, num_items=num_items, dim=dim, seed=seed)
    for r in range(reps):
        on_rates.append(_one_run(
            telemetry=True, steps=steps, batch=batch, num_users=num_users,
            num_items=num_items, dim=dim, seed=seed + r,
        ))
        off_rates.append(_one_run(
            telemetry=False, steps=steps, batch=batch,
            num_users=num_users, num_items=num_items, dim=dim,
            seed=seed + r,
        ))
    on_med = float(np.median(on_rates))
    off_med = float(np.median(off_rates))
    return {
        "steps_per_sec_telemetry_on": round(on_med, 2),
        "steps_per_sec_telemetry_off": round(off_med, 2),
        "overhead_ratio": round(on_med / off_med, 4),
        "overhead_pct": round((1.0 - on_med / off_med) * 100.0, 2),
        "steps": steps,
        "batch": batch,
        "reps": reps,
        "on_rates": [round(r, 2) for r in on_rates],
        "off_rates": [round(r, 2) for r in off_rates],
        "platform": jax.default_backend(),
    }


def main() -> None:
    from flink_parameter_server_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--reps", type=int, default=3)
    p.add_argument("--batch", type=int, default=1_024)
    args = p.parse_args()

    from flink_parameter_server_tpu import telemetry as tm

    r = run_overhead_bench(
        steps=args.steps, reps=args.reps, batch=args.batch
    )
    print(json.dumps({
        "metric": "telemetry overhead (registry+spans+hot-key sketch"
                  "+SLO engine+stack sampler+timeline recorder on vs "
                  "off, CPU driver microbench)",
        "value": r["overhead_pct"],
        "unit": "% slowdown (negative = within noise, faster)",
        "extra": r,
    }))
    # the latency-budget cluster round: phase timers + byte accounting
    # on a real TCP topology (the paths the driver microbench cannot
    # exercise) — its phases land in the same registry the report reads
    from benchmarks.latency_budget import run_budget_bench

    b = run_budget_bench()
    # the A/B left the ON arm's numbers in the default registry — the
    # run report rolls them up with the overhead verdict attached
    from flink_parameter_server_tpu.telemetry.timeline import set_timeline

    set_timeline(_LAST_ON_TIMELINE)
    report = tm.build_run_report(extra={
        "telemetry_overhead_pct": r["overhead_pct"],
        "telemetry_overhead_ratio": r["overhead_ratio"],
        "steps_per_sec_telemetry_on": r["steps_per_sec_telemetry_on"],
        "steps_per_sec_telemetry_off": r["steps_per_sec_telemetry_off"],
        "overhead_bench": (
            f"{args.steps} steps x batch {args.batch}, "
            f"{args.reps} interleaved reps, platform {r['platform']}"
        ),
        "budget_oracle_pull_p50_ms": b["oracle_pull_p50_ms"],
        "budget_round_ms": b["budget_round_ms"],
        "budget_coverage_error": b["coverage_error"],
        "budget_top_phase": (
            f"{b['top_phase']} ({b['top_pct']}% of pull round)"
        ),
    })
    paths = tm.write_run_report(report, platform=r["platform"])
    set_timeline(None)
    print(f"# wrote {paths['md']} and {paths['json']}", file=sys.stderr)


if __name__ == "__main__":
    main()
