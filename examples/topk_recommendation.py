"""MF training + top-K recommendation serving.

Mirrors the reference's ``PSOnlineMatrixFactorizationAndTopK``
(SURVEY.md §2 #8): train online MF, then answer top-K item queries per
user — LEMP pruning replaced by exact MXU-matmul MIPS (`ops/topk.py`).
"""
import numpy as np
import jax.numpy as jnp

from flink_parameter_server_tpu.data.movielens import synthetic_ratings
from flink_parameter_server_tpu.data.streams import microbatches
from flink_parameter_server_tpu.models.matrix_factorization import ps_online_mf
from flink_parameter_server_tpu.models.topk_recommender import query_topk


def main():
    from flink_parameter_server_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    data = synthetic_ratings(500, 800, 60_000, rank=8, noise=0.02, seed=1)
    res = ps_online_mf(
        microbatches(data, 2048, epochs=4, shuffle_seed=0),
        num_users=500, num_items=800, dim=16, learning_rate=0.06,
        collect_outputs=False,
    )

    users = jnp.arange(5)
    # exclude each user's already-rated items (first 8 shown here)
    seen = np.full((5, 8), -1, np.int32)
    for u in range(5):
        items_u = data["item"][data["user"] == u][:8]
        seen[u, : len(items_u)] = items_u
    scores, ids = query_topk(
        res.store, res.worker_state, users, k=10, exclude=jnp.asarray(seen)
    )
    for u in range(5):
        print(f"user {u}: top-10 items {np.asarray(ids[u]).tolist()}")


if __name__ == "__main__":
    main()
