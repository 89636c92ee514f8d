"""Criteo-shaped wide-sparse-table stress (SURVEY.md §7 "hard parts").

A factorization-machine job against a >=10M-row bf16 store with Zipf-hot
ids and the Pallas sorted-run scatter — the configuration the reference
serves with its per-subtask HashMap sharding and that decides whether the
TPU store design holds at scale.  Records:

  * store HBM footprint (model bytes + device memory_stats when available)
  * sustained examples/sec and lane-updates/sec over the run
  * numeric health of the bf16 table (finite fraction, sampled)

    python benchmarks/criteo_stress.py [--rows 16777216] [--steps 50]

One JSON line on stdout; progress on stderr.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=16_777_216)  # 2^24
    ap.add_argument("--dim", type=int, default=16)
    ap.add_argument("--batch", type=int, default=32_768)
    ap.add_argument("--feats", type=int, default=39)  # Criteo fields
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--zipf", type=float, default=1.2)
    ap.add_argument(
        "--layout", default="packed", choices=["packed", "dense"],
        help="packed = k narrow rows per 128-lane physical row "
        "(ops/packed.py)",
    )
    ap.add_argument(
        "--cpu-scale", action="store_true",
        help="shrink shapes for the 1-core dev host (harness proof only)",
    )
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from flink_parameter_server_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    platform = jax.devices()[0].platform

    from flink_parameter_server_tpu.core.store import ShardedParamStore
    from flink_parameter_server_tpu.core.transform import make_train_step
    from flink_parameter_server_tpu.models.factorization_machine import (
        FMConfig,
        FactorizationMachine,
    )
    from flink_parameter_server_tpu.utils.initializers import normal_factor

    if args.cpu_scale:
        args.rows, args.batch, args.steps = 1_048_576, 4_096, 10
    F, K, B, dim = args.rows, args.feats, args.batch, args.dim
    dtype = jnp.bfloat16

    # (1 + dim) per row: linear weight + embedding, bf16 (halves the HBM
    # footprint AND the gather/scatter bytes vs fp32)
    vinit = normal_factor(0, (dim,), stddev=0.01, dtype=dtype)

    def init(ids):
        v = vinit(ids)
        return jnp.concatenate(
            [jnp.zeros(ids.shape + (1,), v.dtype), v], axis=-1
        )

    t0 = time.perf_counter()
    store = ShardedParamStore.create(
        F, (1 + dim,), dtype=dtype, init_fn=init,
        layout=args.layout,
    )
    jax.block_until_ready(store.table)
    t_init = time.perf_counter() - t0
    table_bytes = store.table.nbytes
    print(
        f"# table {F:,} x {1+dim} bf16 = {table_bytes/2**30:.2f} GiB "
        f"({args.layout} layout, phys {store.table.shape}), "
        f"init {t_init:.1f}s", file=sys.stderr,
    )

    cfg = FMConfig(num_features=F, dim=dim, learning_rate=0.01)
    logic = FactorizationMachine(cfg)
    rng = np.random.default_rng(0)
    batch = {
        "ids": jnp.asarray(
            ((rng.zipf(args.zipf, (B, K)) - 1) % F).astype(np.int32)
        ),
        "values": jnp.asarray(rng.normal(0, 1, (B, K)).astype(np.float32)),
        "feat_mask": jnp.ones((B, K), bool),
        "label": jnp.asarray(rng.choice([-1.0, 1.0], B).astype(np.float32)),
        "mask": jnp.ones(B, bool),
    }
    uniq = len(np.unique(np.asarray(batch["ids"])))

    step = jax.jit(make_train_step(logic, store.spec), donate_argnums=(0, 1))
    table, state = store.table, ()
    for _ in range(3):
        table, state, out = step(table, state, batch)
    jax.block_until_ready(table)

    mem = {}
    try:
        stats = jax.devices()[0].memory_stats() or {}
        mem = {
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        }
    except Exception:
        pass

    t0 = time.perf_counter()
    for _ in range(args.steps):
        table, state, out = step(table, state, batch)
    jax.block_until_ready(table)
    dt = time.perf_counter() - t0

    # numeric health: the Zipf head rows take the most updates — sample
    # the head and a random slice, all must be finite in bf16.  Sample
    # through pull() (LOGICAL ids): raw physical-table indexing would
    # clamp most logical ids under the packed layout and silently
    # re-check one row.
    end_store = ShardedParamStore(store.spec, table)
    head_ix = jnp.arange(4096, dtype=jnp.int32)
    tail_ix = jnp.asarray(rng.integers(0, F, 4096).astype(np.int32))
    head = np.asarray(end_store.pull(head_ix).astype(jnp.float32))
    tail = np.asarray(end_store.pull(tail_ix).astype(jnp.float32))
    finite_frac = float(
        np.mean(np.isfinite(head)) * 0.5 + np.mean(np.isfinite(tail)) * 0.5
    )

    print(
        json.dumps(
            {
                "config": "criteo-stress-fm",
                "platform": platform,
                "table_rows": F,
                "table_gib": round(table_bytes / 2**30, 3),
                "table_dtype": "bfloat16",
                "batch": B,
                "features_per_example": K,
                "unique_ids_per_batch": uniq,
                "examples_per_sec": round(B * args.steps / dt, 1),
                "lane_updates_per_sec": round(B * K * args.steps / dt, 1),
                "init_secs": round(t_init, 2),
                "device_memory": mem,
                "finite_fraction_sampled": finite_frac,
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
