"""Online matrix factorization — the framework's canonical example.

Mirrors the reference's ``PSOnlineMatrixFactorization`` demo job
(SURVEY.md §2 #7): stream ratings, keep user factors in worker state and
item factors on the sharded PS, train with async-style SGD.

Usage (ParameterTool-style args — utils/config.py):
    python examples/online_mf_movielens.py [--path ratings-file]
        [--socket host:port] [--num-users N] [--num-items M]
        [--dim 32] [--lr 0.05] [--epochs 3] [--batch 4096]
        [--layout dense|packed|auto] [--steps-per-call 1] [--chaos SEED]
        [--telemetry-port P]

``--telemetry-port P`` serves the unified metrics plane live while the
job trains (``telemetry/``, docs/observability.md): ``curl
http://127.0.0.1:P/metrics`` answers Prometheus text (step counters,
pull→push latency histogram, heartbeat ages), ``/healthz`` the
heartbeat view.  ``P=0`` binds an ephemeral port (printed at start).

``--chaos SEED`` demonstrates the resilience layer end to end: a
seeded FaultPlan crashes the job mid-training, and a RecoveringDriver
(checkpoints + update WAL under a temp workdir) restores, replays the
WAL tail and finishes the run — the printed factors match a
crash-free run bitwise.  See docs/resilience.md.

Without ``--path`` a synthetic Zipf-skewed MovieLens-like stream is used.
``--socket host:port`` instead trains from a LIVE newline-delimited
"user,item,rating" TCP stream until the producer closes — the
reference's canonical unbounded-source (socketTextStream) demo shape;
id spaces then come from --num-users/--num-items (the stream is
unbounded, so they cannot be inferred; combining --socket with the
bounded-file options --path/--epochs is an error).  On a multi-device mesh,
--num-users must be divisible by the dp size (worker state is
dp-sharded).
Runs on whatever devices are available (CPU mesh works:
``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8``).
"""
import sys

import numpy as np

from flink_parameter_server_tpu import make_mesh
from flink_parameter_server_tpu.data.movielens import (
    load_movielens,
    synthetic_ratings,
)
from flink_parameter_server_tpu.data.streams import microbatches
from flink_parameter_server_tpu.models.matrix_factorization import ps_online_mf
from flink_parameter_server_tpu.utils.config import Parameters


def _run_with_chaos(params, make_stream, *, num_users, num_items, mesh):
    """The --chaos path: same MF job, but supervised — a seeded fault
    plan crashes it mid-training and the RecoveringDriver brings it
    back via checkpoint + WAL replay (resilience/)."""
    import tempfile

    from flink_parameter_server_tpu.core.store import ShardedParamStore
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
    from flink_parameter_server_tpu.utils.initializers import (
        ranged_random_factor,
    )

    seed = params.get_int("chaos", 0)
    logic = OnlineMatrixFactorization(
        num_users,
        params.get_int("dim", 32),
        updater=SGDUpdater(params.get_float("lr", 0.05)),
        mesh=mesh,
    )
    store = ShardedParamStore.create(
        num_items,
        (params.get_int("dim", 32),),
        init_fn=ranged_random_factor(1, (params.get_int("dim", 32),)),
        mesh=mesh,
        layout=params.get("layout", "dense"),
    )
    workdir = tempfile.mkdtemp(prefix="fps_chaos_demo_")
    driver = StreamingDriver(
        logic, store,
        config=DriverConfig(
            dump_model=False,
            checkpoint_every=params.get_int("checkpoint-every", 10),
            checkpoint_dir=f"{workdir}/ckpt",
            wal_dir=f"{workdir}/wal",
            steps_per_call=params.get_int("steps-per-call", 1),
        ),
    )
    plan = FaultPlan.from_seed(
        seed, horizon=params.get_int("chaos-horizon", 40)
    )
    driver.add_group_hook(plan.driver_hook())
    rec = RecoveringDriver(
        driver,
        lambda: plan.wrap_source(make_stream()),
        policy=RestartPolicy(seed=seed),
        metrics_sink=sys.stderr,
    )
    print(f"chaos seed {seed}: plan {plan.faults} (workdir {workdir})")
    res = rec.run(collect_outputs=False)
    print(
        f"chaos run survived: {rec.restarts} restart(s), "
        f"{rec.steps_replayed} WAL step(s) replayed, "
        f"{rec.steps_dropped} step(s) dropped"
    )
    return res


def _run_with_driver(params, stream, *, num_users, num_items, mesh):
    """The --telemetry-port path: same MF job, run under the
    StreamingDriver envelope so the unified plane is live (step/event
    counters, pull→push latency histogram, ingest counters, host-side
    spans — all scrapeable on /metrics while this trains)."""
    from flink_parameter_server_tpu.core.store import ShardedParamStore
    from flink_parameter_server_tpu.models.matrix_factorization import (
        OnlineMatrixFactorization,
        SGDUpdater,
    )
    from flink_parameter_server_tpu.training.driver import (
        DriverConfig,
        StreamingDriver,
    )
    from flink_parameter_server_tpu.utils.initializers import (
        ranged_random_factor,
    )

    dim = params.get_int("dim", 32)
    logic = OnlineMatrixFactorization(
        num_users, dim,
        updater=SGDUpdater(params.get_float("lr", 0.05)),
        mesh=mesh,
    )
    store = ShardedParamStore.create(
        num_items, (dim,),
        init_fn=ranged_random_factor(1, (dim,)),
        mesh=mesh,
        layout=params.get("layout", "dense"),
    )
    driver = StreamingDriver(
        logic, store,
        config=DriverConfig(
            dump_model=False,
            steps_per_call=params.get_int("steps-per-call", 1),
        ),
    )
    return driver.run(stream)


def main():
    from flink_parameter_server_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    params = Parameters.from_env().merged_with(
        Parameters.from_args(sys.argv[1:])
    )
    path = params.get("path")
    sock = params.get("socket")
    data = None
    if sock:
        # the socket branch never reads --path/--epochs; silently
        # ignoring them would train on different data/passes than the
        # user asked for — refuse the contradictory combination
        clash = [
            f"--{key}" for key in ("path", "epochs") if key in params
        ]
        if clash:
            raise SystemExit(
                f"--socket streams unbounded live data and is "
                f"incompatible with {', '.join(clash)} (bounded-file "
                f"options); drop one side"
            )
        num_users = params.get_int("num-users", 2000)
        num_items = params.get_int("num-items", 3000)
    else:
        if path:
            data = load_movielens(path)
        else:
            data = synthetic_ratings(2000, 3000, 200_000, rank=8, seed=0)
        num_users = int(data["user"].max()) + 1
        num_items = int(data["item"].max()) + 1

    import jax

    mesh = None
    if len(jax.devices()) > 1:
        mesh = make_mesh()  # all devices on dp; ps=1

    telemetry_server = None
    if "telemetry-port" in params:
        from flink_parameter_server_tpu.telemetry import TelemetryServer

        telemetry_server = TelemetryServer(
            port=params.get_int("telemetry-port", 0)
        ).start()
        print(
            f"telemetry live: http://{telemetry_server.host}:"
            f"{telemetry_server.port}/metrics (and /healthz)"
        )

    if sock:
        from flink_parameter_server_tpu.data.socket import (
            batches_from_records,
            socket_text_stream,
        )

        host, port = sock.rsplit(":", 1)

        def parse(line):
            u, i, r = line.split(",")
            u, i = int(u), int(i)
            if not (0 <= u < num_users and 0 <= i < num_items):
                # out-of-range ids would clamp (gather) / drop (scatter)
                # SILENTLY inside the jitted step — surface them on the
                # dropped counter like any other malformed record
                return None
            return {
                "user": np.int32(u),
                "item": np.int32(i),
                "rating": np.float32(r),
            }

        def make_stream():
            # a fresh dial per (re)start — socket_text_stream itself
            # reconnects through transient drops (data/socket.py)
            return batches_from_records(
                socket_text_stream(host, int(port)),
                params.get_int("batch", 4096),
                parse,
            )

        stream = make_stream()
    else:
        def make_stream():
            return microbatches(
                data,
                params.get_int("batch", 4096),
                epochs=params.get_int("epochs", 3),
                shuffle_seed=0,
            )

        stream = make_stream()

    if "chaos" in params:
        res = _run_with_chaos(
            params, make_stream, num_users=num_users, num_items=num_items,
            mesh=mesh,
        )
    elif telemetry_server is not None:
        # the telemetry demo runs through the StreamingDriver — the
        # plane's instruments (step counters, pull→push histogram,
        # ingest counters, spans) live on the driver envelope, which
        # the bare ps_online_mf/transform_batched loop bypasses
        res = _run_with_driver(
            params, stream, num_users=num_users, num_items=num_items,
            mesh=mesh,
        )
    else:
        res = ps_online_mf(
            stream,
            num_users=num_users,
            num_items=num_items,
            dim=params.get_int("dim", 32),
            learning_rate=params.get_float("lr", 0.05),
            mesh=mesh,
            collect_outputs=False,
            layout=params.get("layout", "dense"),
            steps_per_call=params.get_int("steps-per-call", 1),
        )
    uf = np.asarray(res.worker_state)
    itf = np.asarray(res.store.values())
    if data is not None:
        pred = np.einsum("ij,ij->i", uf[data["user"]], itf[data["item"]])
        rmse = float(np.sqrt(np.mean((pred - data["rating"]) ** 2)))
        base = float(np.sqrt(np.mean(data["rating"] ** 2)))
        print(f"train RMSE {rmse:.4f} (zero-predictor {base:.4f})")
    else:
        # unbounded socket stream: no held dataset to score against —
        # report the trained shapes + the dropped-record count instead
        print(f"socket stream ended; malformed records dropped: "
              f"{stream.dropped}")
    print(f"user factors {uf.shape}, item factors {itf.shape}")
    if telemetry_server is not None:
        telemetry_server.stop()


if __name__ == "__main__":
    main()
