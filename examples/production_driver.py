"""Production-envelope job: StreamingDriver around the MF loop.

The reference gets its operational envelope from Flink (web-UI metrics,
checkpointing — which famously does NOT cover iterative streams — and
job lifecycle; SURVEY.md §1 L1, §5).  This example is that envelope
here, PS-aware: periodic orbax checkpoints, step metrics, the NaN guard,
preemption-safe shutdown, and crash→resume — demonstrated by actually
"crashing" the stream mid-run and resuming from the durable checkpoint.

Usage (ParameterTool-style args — utils/config.py):
    python examples/production_driver.py [--dim 16] [--batch 2048]
        [--steps-per-call 8] [--checkpoint-every 16] [--ckpt-dir DIR]

``--steps-per-call K`` runs the envelope at dispatch granularity (one
host round trip per K microbatches — it amortises host dispatch and has
not been measured on the chip, ROADMAP S3); checkpoint/metrics/NaN
cadences round up to dispatch boundaries.
"""
import os
import shutil
import sys
import tempfile

import numpy as np

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
from flink_parameter_server_tpu.utils.config import Parameters
from flink_parameter_server_tpu.utils.initializers import ranged_random_factor


class SimulatedPreemption(Exception):
    """Dedicated crash sentinel: a plain RuntimeError would be
    indistinguishable from the driver's own TrainingDiverged (a
    RuntimeError subclass), and masking real divergence as the demo
    crash would be exactly the observability bug this example warns
    against."""


def main():
    from flink_parameter_server_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    params = Parameters.from_env().merged_with(
        Parameters.from_args(sys.argv[1:])
    )
    num_users, num_items = 2000, 3000
    dim = params.get_int("dim", 16)
    batch = params.get_int("batch", 2048)
    n_batches = params.get_int("batches", 48)
    ckpt_every = params.get_int("checkpoint-every", 16)
    K = params.get_int("steps-per-call", 8)
    data = synthetic_ratings(
        num_users, num_items, n_batches * batch, rank=8, seed=0
    )

    def fresh_driver(ckpt_dir):
        logic = OnlineMatrixFactorization(
            num_users, dim, updater=SGDUpdater(0.05)
        )
        store = ShardedParamStore.create(
            num_items, (dim,), init_fn=ranged_random_factor(0, (dim,))
        )
        cfg = DriverConfig(
            checkpoint_dir=ckpt_dir,
            checkpoint_every=ckpt_every,
            metrics_every=16,
            nan_check_every=8,
            steps_per_call=K,
        )
        return StreamingDriver(
            logic, store, config=cfg, metrics_sink=sys.stdout
        )

    ckpt_dir = params.get("ckpt-dir")
    own_tmpdir = ckpt_dir is None
    if own_tmpdir:
        ckpt_dir = tempfile.mkdtemp(prefix="fps_ckpt_")
    elif os.path.isdir(ckpt_dir) and os.listdir(ckpt_dir):
        # stale checkpoints would make BOTH runs resume a prior run's
        # final state and the demo would silently train on nothing
        raise SystemExit(
            f"--ckpt-dir {ckpt_dir} is not empty; point at a fresh "
            f"directory (this demo exercises crash->resume from its "
            f"own checkpoints)"
        )
    stream = list(microbatches(data, batch, shuffle_seed=0))

    # --- run 1: "crash" partway through (the stream iterator dies),
    # but only after at least one durable checkpoint exists: cadences
    # round UP to dispatch boundaries, so the first durable save lands
    # at ceil(checkpoint_every / K) * K steps
    first_durable = -(-ckpt_every // K) * K
    crash_at = max((2 * len(stream)) // 3, first_durable + 1)
    if crash_at >= len(stream):
        raise SystemExit(
            f"--batches {n_batches} is too short to crash after the "
            f"first durable checkpoint (step {first_durable}); raise "
            f"--batches or lower --checkpoint-every/--steps-per-call"
        )
    driver = fresh_driver(ckpt_dir)

    def dying():
        for i, b in enumerate(stream):
            if i == crash_at:
                raise SimulatedPreemption()
            yield b

    try:
        driver.run(dying())
    except SimulatedPreemption:
        print(f"crashed at batch {crash_at}; driver rolled back to "
              f"durable step {driver.step_idx}")

    # --- run 2: fresh process/driver resumes from the checkpoint ------
    driver2 = fresh_driver(ckpt_dir)
    assert driver2.resume(), "no durable checkpoint found"
    print(f"resumed at step {driver2.step_idx}; re-feeding the same "
          f"stream (cursor fast-forwards)")
    res = driver2.run(iter(stream))
    assert driver2.step_idx == len(stream), driver2.step_idx

    uf = np.asarray(res.worker_state)
    itf = np.asarray(res.store.values())
    pred = np.einsum("ij,ij->i", uf[data["user"]], itf[data["item"]])
    rmse = float(np.sqrt(np.mean((pred - data["rating"]) ** 2)))
    base = float(np.sqrt(np.mean(data["rating"] ** 2)))
    print(f"resumed-run RMSE {rmse:.4f} (zero-predictor {base:.4f})")
    from flink_parameter_server_tpu.training.checkpoint import (
        JobCheckpointManager,
    )

    print(f"durable checkpoints: {JobCheckpointManager(ckpt_dir).all_steps()}")
    if own_tmpdir:
        shutil.rmtree(ckpt_dir, ignore_errors=True)


if __name__ == "__main__":
    main()
