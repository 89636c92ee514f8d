"""Micro-benchmarks for the framework's hot ops.

Not the driver-facing bench (that's /bench.py — one JSON line); this
script times individual components for tuning, on whatever backend is
alive:

    python benchmarks/microbench.py [scatter|topk|ring|mf] ...

Each section prints `name value unit` lines.
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def _timeit(fn, *args, warmup=2, iters=10):
    import jax

    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def bench_scatter(capacity=131_072, dims=(17, 64, 128), batch=16_384):
    """XLA scatter-add vs the dedup arms under skew — the A/B grid the
    scatter_impl default hangs on (VERDICT r3 next #1a): skew
    {uniform, zipf 1.05, 1.2, 1.3} x dims {17, 64, 128} x {fp32, bf16},
    xla vs xla_sorted everywhere; on TPU the Pallas kernel's chunk sweep
    runs at its dense-eligible dim 128 (narrow dims take the packed
    layout, A/B'd by the battery's bench variants instead)."""
    import jax
    import jax.numpy as jnp

    from flink_parameter_server_tpu.ops.pallas_scatter import scatter_add
    from flink_parameter_server_tpu.ops.sorted_scatter import (
        sorted_dedup_scatter_add,
    )

    rng = np.random.default_rng(0)
    skews = ("uniform", 1.05, 1.2, 1.3)
    for dtype in (jnp.float32, jnp.bfloat16):
        dname = jnp.dtype(dtype).name
        for dim in dims:
            table = jnp.zeros((capacity, dim), dtype)
            # ONE jit per (dtype, dim) per impl, shared across every
            # skew (same shapes -> same program): a fresh jit per skew
            # would recompile identical programs
            xla = jax.jit(
                lambda t, i, d: t.at[i].add(d.astype(t.dtype))
            )
            srt = jax.jit(
                lambda t, i, d: sorted_dedup_scatter_add(t, i, d)
            )
            pallas_jits = {}
            if jax.default_backend() == "tpu" and dim == 128:
                pallas_jits = {
                    chunk: jax.jit(
                        lambda t, i, d, c=chunk: scatter_add(
                            t, i, d, chunk=c, interpret=False
                        )
                    )
                    for chunk in (256, 512, 1024, 2048)
                }
            for zipf in skews:
                if zipf == "uniform":
                    ids_np = rng.integers(0, capacity, batch)
                else:
                    ids_np = (rng.zipf(zipf, batch) - 1) % capacity
                ids = jnp.asarray(ids_np.astype(np.int32))
                deltas = jnp.asarray(
                    rng.normal(0, 1, (batch, dim)).astype(np.float32)
                )
                uniq = len(np.unique(np.asarray(ids)))
                tag = f"{dname},d{dim},zipf={zipf}"

                t_xla = _timeit(xla, table, ids, deltas)
                print(
                    f"scatter_xla[{tag}] {t_xla*1e3:.3f} ms/op "
                    f"(unique {uniq}/{batch})"
                )

                t_srt = _timeit(srt, table, ids, deltas)
                print(
                    f"scatter_xla_sorted[{tag}] {t_srt*1e3:.3f} ms/op "
                    f"(vs_xla {t_xla/t_srt:.2f}x)"
                )

                for chunk, pl in pallas_jits.items():
                    t_pl = _timeit(pl, table, ids, deltas)
                    print(
                        f"scatter_pallas[{tag},chunk={chunk}] "
                        f"{t_pl*1e3:.3f} ms/op (vs_xla {t_xla/t_pl:.2f}x)"
                    )
    if jax.default_backend() != "tpu":
        print("scatter_pallas skipped (no TPU)")


def bench_topk(rows=131_072, dim=64, batch=64, k=100):
    """Exact MXU top-k, plus (on TPU, >=1M rows) the approx-top-k unit
    A/B: throughput AND measured recall vs the exact oracle — off-TPU
    ``approx_max_k`` computes exactly, so recall there is vacuous.
    SELF-CONTAINED: the public ``approx_recall`` parameter was removed in
    round 5 (unproven after three windowless rounds — ops/topk.py
    decision note), so the A/B calls ``jax.lax.approx_max_k`` directly;
    a measured win here is the evidence for reinstating the parameter."""
    import jax
    import jax.numpy as jnp

    from flink_parameter_server_tpu.ops.topk import dense_topk

    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(0, 1, (rows, dim)).astype(np.float32))
    q = jnp.asarray(rng.normal(0, 1, (batch, dim)).astype(np.float32))
    f = jax.jit(lambda t, q: dense_topk(t, q, k))
    t = _timeit(f, table, q)
    print(f"dense_topk {t*1e3:.3f} ms/{batch}q ({rows} items)")

    if jax.default_backend() != "tpu":
        print("approx_topk A/B skipped (no TPU: approx_max_k is exact)")
        return
    rows_m, batch_m = 1_048_576, 256
    table_m = jnp.asarray(
        rng.normal(0, 1, (rows_m, dim)).astype(np.float32)
    )
    q_m = jnp.asarray(rng.normal(0, 1, (batch_m, dim)).astype(np.float32))
    exact = jax.jit(lambda t, q: dense_topk(t, q, k))
    t_exact = _timeit(exact, table_m, q_m, iters=5)
    _, ids_exact = exact(table_m, q_m)
    for target in (0.95, 0.99):
        apx = jax.jit(
            lambda t, q, r=target: jax.lax.approx_max_k(
                q @ t.T, k, recall_target=r
            )
        )
        t_apx = _timeit(apx, table_m, q_m, iters=5)
        _, ids_apx = apx(table_m, q_m)
        # measured recall: |approx ∩ exact| / k per query, averaged
        ex = np.asarray(ids_exact)
        ap = np.asarray(ids_apx)
        recall = float(np.mean([
            len(np.intersect1d(ex[i], ap[i])) / ex.shape[1]
            for i in range(ex.shape[0])
        ]))
        print(
            f"approx_topk[target={target}] {t_apx*1e3:.3f} ms/{batch_m}q "
            f"({rows_m} items)  recall {recall:.4f}  "
            f"speedup_vs_exact {t_exact/t_apx:.2f}x"
        )
    print(
        f"exact_topk {t_exact*1e3:.3f} ms/{batch_m}q ({rows_m} items)"
    )


def bench_ring(B=4, T=4096, H=8, D=64):
    import jax
    import jax.numpy as jnp

    from flink_parameter_server_tpu.parallel.mesh import make_mesh
    from flink_parameter_server_tpu.parallel.ring_attention import (
        reference_attention,
        ring_attention,
    )

    n = len(jax.devices())
    sp = min(n, 4)
    mesh = make_mesh(n // sp, sp, axis_names=("dp", "sp"))
    rng = np.random.default_rng(0)
    mk = lambda: jnp.asarray(rng.normal(0, 1, (B, T, H, D)).astype(np.float32))
    q, k, v = mk(), mk(), mk()
    ring = jax.jit(lambda a, b, c: ring_attention(a, b, c, mesh=mesh))
    t_ring = _timeit(ring, q, k, v, iters=5)
    print(f"ring_attention sp={sp} {t_ring*1e3:.2f} ms (B{B} T{T} H{H} D{D})")
    dense = jax.jit(reference_attention)
    t_dense = _timeit(dense, q, k, v, iters=5)
    print(f"dense_attention {t_dense*1e3:.2f} ms")


def bench_mf(batch=16_384, dim=64):
    import os

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from bench import tpu_updates_per_sec

    r = tpu_updates_per_sec(batch=batch, dim=dim)
    print(
        f"mf_updates_per_sec {r['updates_per_sec_per_chip']:,.0f}  "
        f"p50 {r['p50_ms']:.3f} ms  dtype {r['table_dtype']}  "
        f"batch {r['batch']}"
    )


def bench_mf_fused(capacity=131_072, num_users=100_000, dim=128,
                   batch=16_384, zipf=1.2):
    """Fused pull+SGD+push kernel vs the unfused XLA step (TPU only —
    interpret mode is not a perf number)."""
    import jax
    import jax.numpy as jnp

    from flink_parameter_server_tpu.core.store import ShardedParamStore
    from flink_parameter_server_tpu.core.transform import make_train_step
    from flink_parameter_server_tpu.models.matrix_factorization import (
        OnlineMatrixFactorization,
        SGDUpdater,
    )
    from flink_parameter_server_tpu.ops.pallas_mf import (
        make_fused_mf_train_step,
    )
    from flink_parameter_server_tpu.utils.initializers import normal_factor

    if jax.default_backend() != "tpu":
        print("mf_fused skipped (no TPU)")
        return
    rng = np.random.default_rng(0)
    logic = OnlineMatrixFactorization(
        num_users, dim, updater=SGDUpdater(0.01)
    )
    store = ShardedParamStore.create(
        capacity, (dim,), init_fn=normal_factor(1, (dim,))
    )
    users0 = logic.init_state(jax.random.PRNGKey(0))
    batch_d = {
        "user": jnp.asarray(
            rng.integers(0, num_users, batch).astype(np.int32)
        ),
        "item": jnp.asarray(
            ((rng.zipf(zipf, batch) - 1) % capacity).astype(np.int32)
        ),
        "rating": jnp.asarray(rng.normal(0, 1, batch).astype(np.float32)),
        "mask": jnp.ones(batch, bool),
    }
    unfused = jax.jit(make_train_step(logic, store.spec))
    t_u = _timeit(unfused, store.table, users0, batch_d)
    print(f"mf_step_unfused {t_u*1e3:.3f} ms/step (batch {batch})")
    for chunk in (512, 1024, 2048):
        fused = jax.jit(
            make_fused_mf_train_step(
                learning_rate=0.01, chunk=chunk, interpret=False
            )
        )
        t_f = _timeit(fused, store.table, users0, batch_d)
        print(f"mf_step_fused[chunk={chunk}] {t_f*1e3:.3f} ms/step")


SECTIONS = {
    "scatter": bench_scatter,
    "topk": bench_topk,
    "ring": bench_ring,
    "mf": bench_mf,
    "mf_fused": bench_mf_fused,
}

if __name__ == "__main__":
    from flink_parameter_server_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    which = sys.argv[1:] or list(SECTIONS)
    for name in which:
        print(f"--- {name} ---")
        SECTIONS[name]()
