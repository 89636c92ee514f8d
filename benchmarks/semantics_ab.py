"""Staleness-vs-quality A/B at MovieLens-100K scale (SURVEY.md §7).

The reference trains fully async (unbounded staleness, per-record
callbacks).  The TPU rebuild is synchronous within a microbatch: staleness
is bounded by the batch size.  This harness quantifies what that costs on
ML-100K-shaped data (943 users x 1682 items x 100k ratings):

  A  per-record event backend (the faithful reference execution model) on
     a subsampled stream — the quality yardstick;
  B  the batched TPU path on the full stream at batch in {256, 4096,
     65536} — staleness growing three orders of magnitude.

Prints one JSON line per run; the table lives in docs/migration.md.

    python benchmarks/semantics_ab.py [--epochs N] [--event-records M]
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np


def _rmse(user_f, item_f, data) -> float:
    pred = np.einsum("ij,ij->i", user_f[data["user"]], item_f[data["item"]])
    return float(np.sqrt(np.mean((pred - data["rating"]) ** 2)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument(
        "--event-records", type=int, default=25_000,
        help="subsample for the per-record event backend (python-speed)",
    )
    ap.add_argument("--dim", type=int, default=16)
    ap.add_argument("--lr", type=float, default=0.02)
    args = ap.parse_args()

    import os
    import sys as _sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    _sys.path.insert(0, repo)
    import jax
    import jax.numpy as jnp

    from flink_parameter_server_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    platform = jax.devices()[0].platform
    print(f"# platform: {platform}", file=sys.stderr)

    from flink_parameter_server_tpu import SimplePSLogic, transform
    from flink_parameter_server_tpu.data.movielens import synthetic_ratings
    from flink_parameter_server_tpu.data.streams import microbatches
    from flink_parameter_server_tpu.models.matrix_factorization import (
        MFWorkerLogic,
        SGDUpdater,
        ps_online_mf,
    )
    from flink_parameter_server_tpu.utils.initializers import (
        ranged_random_factor,
    )

    NUM_USERS, NUM_ITEMS, N = 943, 1682, 100_000  # the ML-100K shape
    data = synthetic_ratings(
        NUM_USERS, NUM_ITEMS, N, rank=8, noise=0.1, seed=11
    )
    base = float(np.sqrt(np.mean(data["rating"] ** 2)))
    print(f"# zero-predictor RMSE {base:.4f}", file=sys.stderr)

    # -- A: per-record event backend (subsampled) -------------------------
    sub = {k: v[: args.event_records] for k, v in data.items()}
    worker = MFWorkerLogic(dim=args.dim, updater=SGDUpdater(args.lr), seed=0)
    item_init = ranged_random_factor(1, (args.dim,))

    def init_item(i):
        return np.asarray(item_init(jnp.array([i]))[0])

    records = (
        list(zip(sub["user"], sub["item"], sub["rating"])) * args.epochs
    )
    t0 = time.perf_counter()
    res_a = transform(
        records,
        worker,
        SimplePSLogic(init=init_item, update=lambda c, d: c + np.asarray(d)),
    )
    dt_a = time.perf_counter() - t0
    item_f = np.zeros((NUM_ITEMS, args.dim), np.float32)
    for i, v in res_a.server_outputs:
        item_f[i] = v
    user_f = np.zeros((NUM_USERS, args.dim), np.float32)
    for u, v in worker.user_vectors.items():
        user_f[u] = v
    rmse_a = _rmse(user_f, item_f, sub)
    print(
        json.dumps(
            {
                "run": "A-event-per-record",
                "records": args.event_records,
                "epochs": args.epochs,
                "rmse": round(rmse_a, 4),
                "vs_zero_predictor": round(rmse_a / base, 4),
                "secs": round(dt_a, 1),
            }
        ),
        flush=True,
    )

    # -- B: batched path ---------------------------------------------------
    def run_b(tag, ds, n_records, batch, *, dedup=False, eval_ds=None):
        t0 = time.perf_counter()
        res_b = ps_online_mf(
            microbatches(ds, batch, epochs=args.epochs),
            num_users=NUM_USERS,
            num_items=NUM_ITEMS,
            dim=args.dim,
            learning_rate=args.lr,
            dedup_scale=dedup,
            collect_outputs=False,
        )
        dt_b = time.perf_counter() - t0
        rmse_b = _rmse(
            np.asarray(res_b.worker_state),
            np.asarray(res_b.store.values()),
            eval_ds if eval_ds is not None else ds,
        )
        print(
            json.dumps(
                {
                    "run": tag,
                    "batch": batch,
                    "records": n_records,
                    "epochs": args.epochs,
                    "dedup_scale": dedup,
                    "rmse": round(rmse_b, 4),
                    "vs_zero_predictor": round(rmse_b / base, 4),
                    "delta_vs_event": round(rmse_b - rmse_a, 4),
                    "secs": round(dt_b, 1),
                }
            ),
            flush=True,
        )

    # apples-to-apples with A: the same subsampled stream
    run_b(
        "B-batched-256-same-stream", sub, args.event_records, 256,
        eval_ds=sub,
    )
    # staleness sweep on the full 100k stream; at 64k records/step the
    # duplicate-sum path is expected to diverge — the dedup (mean) variant
    # is the framework's mitigation and must stay stable
    for batch in (256, 4096, 65536):
        run_b(f"B-batched-{batch}", data, N, batch)
    run_b("B-batched-65536-dedup", data, N, 65536, dedup=True)


if __name__ == "__main__":
    main()
