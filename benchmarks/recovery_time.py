"""Recovery-time benchmark: crash mid-training, measure the cost of
coming back.

The resilience claim (docs/resilience.md) is quantitative: recovery =
restore latest checkpoint + replay WAL tail, with NOTHING lost.  This
harness measures both halves on the real stack:

  * train online MF with periodic checkpoints + the update WAL,
  * inject a crash at a chaos-scheduled step (``FaultPlan.crash_at`` —
    the dispatch-boundary hook, i.e. after updates were applied and
    before that boundary's checkpoint),
  * let the :class:`~flink_parameter_server_tpu.resilience.RecoveringDriver`
    supervise the restart, and report:

      - ``recovery_seconds`` — wall time from the crash surfacing to the
        driver training on FRESH input again (restore + WAL replay +
        cursor fast-forward; the backoff sleep is excluded — it is a
        policy knob, not recovery work — and reported separately),
      - ``updates_lost`` — events the recovered run never applied
        relative to the uninterrupted oracle (0 is the claim: the WAL
        closes the checkpoint window); measured, not asserted, and
        cross-checked with a bitwise table comparison,
      - ``replayed_steps`` / ``wal_bytes`` — how much tail the WAL
        carried.

Usage::

    JAX_PLATFORMS=cpu python benchmarks/recovery_time.py \
        [--steps 40] [--crash-at 25] [--checkpoint-every 8] \
        [--out results/cpu/recovery_time.md]

Prints one JSON line (bench.py metric-line shape) and writes md/json
evidence under results/<platform>/.
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


def run_recovery_bench(
    *,
    num_users: int = 2_000,
    num_items: int = 8_192,
    dim: int = 32,
    batch: int = 4_096,
    steps: int = 40,
    crash_at: int = 25,
    checkpoint_every: int = 8,
    seed: int = 0,
    workdir: str = None,
) -> dict:
    """Run the crash/recover experiment; returns the metrics dict.
    Import-time side-effect free (bench.py imports and calls this)."""
    import shutil
    import tempfile

    import jax

    from flink_parameter_server_tpu.core.store import ShardedParamStore
    from flink_parameter_server_tpu.data.movielens import synthetic_ratings
    from flink_parameter_server_tpu.data.streams import microbatches
    from flink_parameter_server_tpu.models.matrix_factorization import (
        OnlineMatrixFactorization,
        SGDUpdater,
    )
    from flink_parameter_server_tpu.resilience import (
        FaultPlan,
        RecoveringDriver,
        RestartPolicy,
    )
    from flink_parameter_server_tpu.training.driver import (
        DriverConfig,
        StreamingDriver,
    )
    from flink_parameter_server_tpu.utils.initializers import normal_factor

    cols = synthetic_ratings(num_users, num_items, steps * batch, seed=seed)

    def make_parts():
        logic = OnlineMatrixFactorization(
            num_users, dim, updater=SGDUpdater(0.01)
        )
        store = ShardedParamStore.create(
            num_items, (dim,), init_fn=normal_factor(1, (dim,))
        )
        return logic, store

    def stream():
        return microbatches(cols, batch, epochs=1, shuffle_seed=seed)

    # -- oracle: the uninterrupted run (also the warm-up/compile pass) --
    logic, store = make_parts()
    oracle_driver = StreamingDriver(
        logic, store, config=DriverConfig(dump_model=False)
    )
    t0 = time.perf_counter()
    oracle = oracle_driver.run(stream(), collect_outputs=False)
    uninterrupted_s = time.perf_counter() - t0
    oracle_table = np.asarray(oracle.store.values())

    # -- chaos run: checkpoints + WAL + a scheduled crash ---------------
    tmp = workdir or tempfile.mkdtemp(prefix="fps_recovery_bench_")
    made_tmp = workdir is None
    try:
        logic2, store2 = make_parts()
        cfg = DriverConfig(
            dump_model=False,
            checkpoint_every=checkpoint_every,
            checkpoint_dir=os.path.join(tmp, "ckpt"),
            wal_dir=os.path.join(tmp, "wal"),
        )
        driver = StreamingDriver(logic2, store2, config=cfg)
        plan = FaultPlan(seed=seed).crash_at(crash_at)
        driver.add_group_hook(plan.driver_hook())

        timeline = {}

        def timing_hook(global_step, n_steps, table, state, outs):
            # first dispatch AFTER the recovery run resumed fresh input
            # (replay_target is set once _recover finishes; dispatches
            # before that are the WAL replay itself)
            if "replay_target" in timeline and "recovered_at" not in timeline:
                if global_step > timeline["replay_target"]:
                    timeline["recovered_at"] = time.perf_counter()

        driver.add_group_hook(timing_hook)

        class _TimingRecoverer(RecoveringDriver):
            def _recover(self, fc, exc, event):
                timeline.setdefault("crashed_at", time.perf_counter())
                super()._recover(fc, exc, event)
                timeline["replay_target"] = self.driver.step_idx
                timeline["recover_done_at"] = time.perf_counter()

        rec = _TimingRecoverer(
            driver, stream,
            policy=RestartPolicy(
                max_restarts=2, jitter=0.0, backoff_base_s=0.0, seed=seed
            ),
        )
        wal_bytes_peak = [0]

        def wal_watch(global_step, n_steps, table, state, outs):
            if driver.wal is not None:
                wal_bytes_peak[0] = max(
                    wal_bytes_peak[0], driver.wal.total_bytes
                )

        driver.add_group_hook(wal_watch)
        t1 = time.perf_counter()
        result = rec.run(collect_outputs=False)
        recovered_s = time.perf_counter() - t1

        got_table = np.asarray(result.store.values())
        tables_equal = bool(np.array_equal(oracle_table, got_table))
        # events the recovered run applied vs the oracle: both runs see
        # steps * batch events unless recovery dropped some
        updates_lost = int(
            (steps - driver.step_idx) * batch
        )
        recovery_seconds = None
        if "crashed_at" in timeline and "recover_done_at" in timeline:
            recovery_seconds = (
                timeline["recover_done_at"] - timeline["crashed_at"]
            )
        return {
            "recovery_seconds": (
                round(recovery_seconds, 3)
                if recovery_seconds is not None else None
            ),
            "updates_lost": updates_lost,
            "tables_bitwise_equal": tables_equal,
            "restarts": rec.restarts,
            "replayed_steps": rec.steps_replayed,
            "dropped_steps": rec.steps_dropped,
            "crash_at_step": crash_at,
            "checkpoint_every": checkpoint_every,
            "steps": steps,
            "batch": batch,
            "num_items": num_items,
            "dim": dim,
            "wal_bytes_peak": wal_bytes_peak[0],
            "uninterrupted_s": round(uninterrupted_s, 3),
            "run_with_crash_s": round(recovered_s, 3),
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
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--crash-at", type=int, default=25)
    ap.add_argument("--checkpoint-every", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4_096)
    ap.add_argument("--num-items", type=int, default=8_192)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    r = run_recovery_bench(
        steps=args.steps, crash_at=args.crash_at,
        checkpoint_every=args.checkpoint_every, batch=args.batch,
        num_items=args.num_items, dim=args.dim,
    )
    payload = {
        "metric": "crash recovery (checkpoint + WAL replay, online MF)",
        "value": r["recovery_seconds"],
        "unit": "seconds",
        "extra": r,
    }
    print(json.dumps(payload))

    out = args.out or os.path.join(
        REPO, "results", r["platform"], "recovery_time.md"
    )
    stamp = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    lines = [
        f"# crash recovery — {r['platform']}, {stamp}",
        f"# items={r['num_items']} dim={r['dim']} batch={r['batch']} "
        f"steps={r['steps']} crash_at={r['crash_at_step']} "
        f"checkpoint_every={r['checkpoint_every']}",
        "",
        "| recovery_s | updates_lost | bitwise equal | replayed steps |"
        " wal peak bytes | uninterrupted_s | with-crash_s |",
        "|---|---|---|---|---|---|---|",
        f"| {r['recovery_seconds']} | {r['updates_lost']} "
        f"| {r['tables_bitwise_equal']} | {r['replayed_steps']} "
        f"| {r['wal_bytes_peak']} | {r['uninterrupted_s']} "
        f"| {r['run_with_crash_s']} |",
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
