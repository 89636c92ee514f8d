"""Migration story: an unmodified event-API logic on the device store.

Step 1 of a reference migration is usually "keep my WorkerLogic, move the
parameters": ``transform_hybrid`` runs the exact callback class you wrote
for the event backend against a ``ShardedParamStore`` — per chunk, every
pull becomes one deduped sharded gather and every push one scatter-add.

Usage:
    python examples/hybrid_migration.py [--chunk 512] [--epochs 5]
"""
import sys

import numpy as np

from flink_parameter_server_tpu import (
    ShardedParamStore,
    make_mesh,
    transform_hybrid,
)
from flink_parameter_server_tpu.data.movielens import synthetic_ratings
from flink_parameter_server_tpu.models.matrix_factorization import (
    MFWorkerLogic,
    SGDUpdater,
)
from flink_parameter_server_tpu.utils.config import Parameters
from flink_parameter_server_tpu.utils.initializers import ranged_random_factor


def main():
    from flink_parameter_server_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    params = Parameters.from_args(sys.argv[1:])
    chunk = params.get_int("chunk", 512)
    epochs = params.get_int("epochs", 5)

    num_users, num_items = 300, 400
    data = synthetic_ratings(num_users, num_items, 30_000, rank=4,
                             noise=0.05, seed=0)
    ratings = list(
        zip(data["user"].tolist(), data["item"].tolist(),
            data["rating"].tolist())
    )

    import jax

    # every device beyond the first becomes a ps shard: the point of the
    # demo is the SHARDED parameter plane under unchanged worker code
    mesh = make_mesh(1) if len(jax.devices()) > 1 else None

    # the SAME class that runs on the event backend — zero changes
    worker = MFWorkerLogic(dim=8, updater=SGDUpdater(0.1), seed=0)
    store = ShardedParamStore.create(
        num_items, (8,), init_fn=ranged_random_factor(1, (8,)), mesh=mesh
    )
    res = transform_hybrid(ratings * epochs, worker, store, chunk_size=chunk)

    item_f = np.asarray(res.store.values())
    user_f = np.zeros((num_users, 8), np.float32)
    for u, v in worker.user_vectors.items():
        user_f[u] = v
    pred = np.einsum(
        "ij,ij->i", user_f[data["user"]], item_f[data["item"]]
    )
    rmse = float(np.sqrt(np.mean((pred - data["rating"]) ** 2)))
    base = float(np.sqrt(np.mean(data["rating"] ** 2)))
    shards = mesh.shape["ps"] if mesh is not None else 1
    print(f"unmodified MFWorkerLogic on a {shards}-shard device store "
          f"(chunk={chunk}): rmse {rmse:.3f} vs zero-pred {base:.3f}")


if __name__ == "__main__":
    main()
