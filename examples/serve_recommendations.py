"""Train-while-serve: online MF with a live top-K recommendation server.

The serving subsystem's canonical demo (docs/serving.md): a
StreamingDriver trains online matrix factorization while the attached
serving service answers top-K queries from versioned table snapshots —
in-process through a :class:`ServingClient`, and over TCP through the
line-protocol :class:`ServingServer` (the serve-side mirror of the
ingest socket).

Usage (ParameterTool-style args — utils/config.py)::

    python examples/serve_recommendations.py
        [--num-users 2000] [--num-items 5000] [--dim 32]
        [--ratings 300000] [--batch 4096] [--epochs 3] [--k 10]
        [--publish-every 4] [--port 0]      # 0 = ephemeral
        [--queries 32]                      # in-process demo queries

Runs on any backend (CPU works: ``JAX_PLATFORMS=cpu``).
"""
import sys
import threading

import numpy as np

from flink_parameter_server_tpu import (
    DriverConfig,
    ShardedParamStore,
    StreamingDriver,
)
from flink_parameter_server_tpu.data.movielens import synthetic_ratings
from flink_parameter_server_tpu.data.streams import microbatches
from flink_parameter_server_tpu.models.matrix_factorization import (
    OnlineMatrixFactorization,
    SGDUpdater,
)
from flink_parameter_server_tpu.serving import ServingServer
from flink_parameter_server_tpu.serving.server import tcp_request
from flink_parameter_server_tpu.utils.config import Parameters
from flink_parameter_server_tpu.utils.initializers import (
    ranged_random_factor,
)


def main():
    from flink_parameter_server_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    params = Parameters.from_env().merged_with(
        Parameters.from_args(sys.argv[1:])
    )
    num_users = params.get_int("num-users", 2000)
    num_items = params.get_int("num-items", 5000)
    dim = params.get_int("dim", 32)
    k = params.get_int("k", 10)

    logic = OnlineMatrixFactorization(
        num_users, dim, updater=SGDUpdater(0.05)
    )
    store = ShardedParamStore.create(
        num_items, (dim,), init_fn=ranged_random_factor(1, (dim,))
    )
    driver = StreamingDriver(
        logic, store, config=DriverConfig(dump_model=False)
    )
    service = driver.serve_with(
        publish_every=params.get_int("publish-every", 4)
    )
    client = service.client()

    data = synthetic_ratings(
        num_users, num_items, params.get_int("ratings", 300_000),
        rank=8, seed=0,
    )
    batches = microbatches(
        data,
        params.get_int("batch", 4096),
        epochs=params.get_int("epochs", 3),
        shuffle_seed=0,
    )
    trainer = threading.Thread(
        target=lambda: driver.run(batches, collect_outputs=False),
        daemon=True,
    )
    trainer.start()

    # -- queries WHILE training ------------------------------------------
    service.wait_for_snapshot(120, min_version=2)
    rng = np.random.default_rng(0)
    for _ in range(params.get_int("queries", 32)):
        user = int(rng.integers(0, num_users))
        # exclude the user's already-rated items (first 16 shown here)
        seen = data["item"][data["user"] == user][:16].tolist()
        res = client.top_k(user, k=k, exclude=seen)
        print(
            f"user {user:5d}  top-{k} {res.item_ids.tolist()}  "
            f"(snapshot v{res.version}, {res.staleness} steps stale)"
        )
    trainer.join()

    # -- and over TCP, from the FINAL model -------------------------------
    server = ServingServer(
        service, port=params.get_int("port", 0)
    ).start()
    print(f"serving on {server.host}:{server.port}")
    resp = tcp_request(server.host, server.port, f"topk 0 {k}")
    print(f"tcp answer: {resp}")
    print(service.metrics.emit())
    server.stop()
    service.stop()


if __name__ == "__main__":
    main()
