"""Throughput for ALL five BASELINE.md configs, single chip.

BASELINE.md lists five reference configs; /bench.py covers only #1 (MF).
This harness gives each of the others one honest number (updates/sec for
the sparse-PS models, tokens/sec + MFU for the dense transformer):

    python benchmarks/baseline_configs.py [mf|pa|w2v|fm|lm|all]

Each config prints one JSON line.  Shapes scale by platform: TPU gets
the BASELINE-shaped sizes, the CPU backend gets miniatures that prove
the harness, not perf.  It runs on the platform JAX finds and says which.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def _is_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def _store_opts() -> dict:
    """Store construction knob for the sparse-PS configs (2/3/4):
    FPS_CFG_LAYOUT=dense|packed|auto."""
    layout = os.environ.get("FPS_CFG_LAYOUT", "dense")
    if layout not in ("dense", "packed", "auto"):
        raise SystemExit(f"FPS_CFG_LAYOUT={layout!r}: dense|packed|auto")
    return {"layout": layout}


def _resolved(store) -> dict:
    """What actually ran (layout='auto' resolves at store creation)."""
    return {"layout": store.spec.layout}


def _moved_lanes(store) -> int:
    """Lanes moved per row-touch: the packed layout moves full physical
    rows (128 lanes) per pull/push regardless of the logical width —
    same accounting convention as bench.py's HBM traffic model."""
    if store.spec.layout == "packed":
        from flink_parameter_server_tpu.ops.packed import phys_width

        return phys_width(store.spec.row_width)
    return store.spec.row_width


def _roofline(store, row_touches: int, dt: float) -> dict:
    """HBM traffic model for a gather+scatter-RMW sparse step: each
    touched row costs 1 read (pull) + 1 read + 1 write (scatter RMW) =
    3 row traversals.  Returns bytes/step + utilization vs the chip's
    HBM peak (None off-TPU — r2 verdict: configs 2-4 need the same
    bytes-moved context as config 1 to be judgeable)."""
    import jax.numpy as jnp

    from flink_parameter_server_tpu.utils.device_peaks import device_peaks

    el = jnp.dtype(store.spec.dtype).itemsize
    hbm_bytes = 3 * row_touches * _moved_lanes(store) * el
    peaks = device_peaks()
    return {
        "hbm_bytes_per_step": hbm_bytes,
        "bandwidth_util": (
            round(hbm_bytes / dt / peaks.hbm_bytes_per_sec, 4)
            if peaks else None
        ),
    }


def _row(config: str, value: float, unit: str, **extra) -> None:
    print(
        json.dumps(
            {"config": config, "value": round(value, 1), "unit": unit,
             "extra": extra},
        ),
        flush=True,
    )


def _time_steps(step, carry, batch, *, warmup=3, iters=20):
    """Free-running step loop; returns secs/step.  ``step`` returns
    ``(*new_carry, per_step_output)``."""
    import jax

    carry = list(carry)
    for _ in range(warmup):
        *carry, _out = step(*carry, batch)
    jax.block_until_ready(carry[0])
    t0 = time.perf_counter()
    for _ in range(iters):
        *carry, _out = step(*carry, batch)
    jax.block_until_ready(carry[0])
    return (time.perf_counter() - t0) / iters


# -- config 2: online passive-aggressive binary (streaming linear) -------


def bench_pa():
    import jax
    import jax.numpy as jnp

    from flink_parameter_server_tpu.core.store import ShardedParamStore
    from flink_parameter_server_tpu.core.store import zeros_init
    from flink_parameter_server_tpu.core.transform import make_train_step
    from flink_parameter_server_tpu.models.passive_aggressive import (
        PassiveAggressiveBinary,
    )

    tpu = _is_tpu()
    B = 65_536 if tpu else 8_192  # examples per microbatch
    K = 32  # active features per example
    F = 2_000_000 if tpu else 100_000  # feature space

    opts = _store_opts()
    store = ShardedParamStore.create(F, (), **opts)
    logic = PassiveAggressiveBinary()
    rng = np.random.default_rng(0)
    batch = {
        "ids": jnp.asarray(
            ((rng.zipf(1.3, (B, K)) - 1) % F).astype(np.int32)
        ),
        "values": jnp.asarray(rng.normal(0, 1, (B, K)).astype(np.float32)),
        "feat_mask": jnp.ones((B, K), bool),
        "label": jnp.asarray(rng.choice([-1.0, 1.0], B).astype(np.float32)),
        "mask": jnp.ones(B, bool),
    }
    step = jax.jit(make_train_step(logic, store.spec), donate_argnums=(0, 1))
    dt = _time_steps(step, (store.table, ()), batch)
    _row(
        "2-passive-aggressive-binary", B / dt, "examples/sec",
        batch=B, active_features=K, feature_space=F,
        lane_updates_per_sec=round(B * K / dt, 1),
        **_resolved(store), **_roofline(store, B * K, dt),
    )


# -- config 3: word2vec skip-gram with negative sampling ------------------


def bench_w2v():
    import jax
    import jax.numpy as jnp

    from flink_parameter_server_tpu.core.transform import make_train_step
    from flink_parameter_server_tpu.models import word2vec

    tpu = _is_tpu()
    B = 32_768 if tpu else 4_096  # (center, context) pairs per microbatch
    N = 5  # negatives per pair
    V = 1_000_000 if tpu else 50_000
    dim = 128 if tpu else 64

    opts = _store_opts()
    store = word2vec.make_store(V, dim, **opts)
    logic = word2vec.SkipGramNS(0.025)
    rng = np.random.default_rng(0)
    batch = {
        "center": jnp.asarray(((rng.zipf(1.3, B) - 1) % V).astype(np.int32)),
        "context": jnp.asarray(((rng.zipf(1.3, B) - 1) % V).astype(np.int32)),
        "negatives": jnp.asarray(
            rng.integers(0, V, (B, N)).astype(np.int32)
        ),
        "mask": jnp.ones(B, bool),
    }
    step = jax.jit(make_train_step(logic, store.spec), donate_argnums=(0, 1))
    dt = _time_steps(step, (store.table, ()), batch)
    _row(
        "3-word2vec-sgns", B / dt, "pairs/sec",
        batch=B, negatives=N, vocab=V, dim=dim, **_resolved(store),
        # rows touched per pair: center + context + N negatives, each
        # pulled and scatter-updated
        **_roofline(store, B * (2 + N), dt),
    )


# -- config 4: factorization machine (Criteo-shaped wide sparse table) ----


def bench_fm(stress: bool = False):
    import jax
    import jax.numpy as jnp

    from flink_parameter_server_tpu.core.transform import make_train_step
    from flink_parameter_server_tpu.models import factorization_machine as fm

    tpu = _is_tpu()
    B = 32_768 if tpu else 4_096
    K = 39  # Criteo: 39 features per example
    F = (
        33_554_432 if (tpu and stress)  # 2^25 rows — the ≥10M-row case
        else (4_194_304 if tpu else 200_000)
    )
    dim = 16

    cfg = fm.FMConfig(num_features=F, dim=dim, learning_rate=0.01)
    opts = _store_opts()
    store = fm.make_store(cfg, **opts)
    logic = fm.FactorizationMachine(cfg)
    rng = np.random.default_rng(0)
    batch = {
        "ids": jnp.asarray(((rng.zipf(1.2, (B, K)) - 1) % F).astype(np.int32)),
        "values": jnp.asarray(
            rng.normal(0, 1, (B, K)).astype(np.float32)
        ),
        "feat_mask": jnp.ones((B, K), bool),
        "label": jnp.asarray(rng.choice([-1.0, 1.0], B).astype(np.float32)),
        "mask": jnp.ones(B, bool),
    }
    step = jax.jit(make_train_step(logic, store.spec), donate_argnums=(0, 1))
    dt = _time_steps(step, (store.table, ()), batch)
    table_gb = F * (1 + dim) * np.dtype(np.float32).itemsize / 2**30
    _row(
        "4-factorization-machine", B / dt, "examples/sec",
        batch=B, features_per_example=K, table_rows=F,
        table_gib=round(table_gb, 2), dim=dim, **_resolved(store),
        **_roofline(store, B * K, dt),
    )


# -- config 5: transformer-base LM, dense data-parallel -------------------


def bench_lm():
    import jax
    import jax.numpy as jnp
    import optax

    from flink_parameter_server_tpu.core.dense import make_dense_train_step
    from flink_parameter_server_tpu.models.transformer import (
        TransformerConfig,
        init_params,
        lm_loss,
    )

    tpu = _is_tpu()
    # transformer-base-ish on TPU; a miniature on the 1-core CPU host.
    # FPS_LM_BATCH / FPS_LM_SEQ / FPS_LM_FLASH (auto|on|off) sweep the
    # MFU levers (workload per step; splash-vs-reference attention);
    # FPS_LM_DMODEL / FPS_LM_LAYERS / FPS_LM_HEADS / FPS_LM_DFF scale
    # the model (MXU saturation needs wider matmuls than base-512).
    B = int(os.environ.get("FPS_LM_BATCH", 16 if tpu else 4))
    T = int(os.environ.get("FPS_LM_SEQ", 512 if tpu else 64))
    flash = os.environ.get("FPS_LM_FLASH", "auto")
    d_model = int(os.environ.get("FPS_LM_DMODEL", 512 if tpu else 64))
    cfg = TransformerConfig(
        vocab_size=32_000 if tpu else 1_000,
        d_model=d_model,
        n_layers=int(os.environ.get("FPS_LM_LAYERS", 6 if tpu else 2)),
        n_heads=int(os.environ.get("FPS_LM_HEADS", 8 if tpu else 4)),
        d_ff=int(os.environ.get("FPS_LM_DFF",
                                4 * d_model if tpu else 128)),
        max_seq=T,
        dtype=jnp.bfloat16 if tpu else jnp.float32,
        flash_attention=flash,
    )

    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = optax.adamw(3e-4)
    opt_state = opt.init(params)
    step = jax.jit(
        make_dense_train_step(lambda p, b: lm_loss(p, b, cfg), opt),
        donate_argnums=(0, 1),
    )
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (B, T)).astype(np.int32)
        ),
    }
    dt = _time_steps(step, (params, opt_state), batch, warmup=2, iters=10)
    tokens_per_sec = B * T / dt
    n_params = sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(params)
    )
    flops_per_step = 6 * n_params * B * T  # fwd+bwd dense-matmul estimate
    from flink_parameter_server_tpu.utils.device_peaks import device_peaks

    peaks = device_peaks()
    mfu = (
        flops_per_step / dt / peaks.bf16_flops_per_sec if peaks else None
    )
    # record which attention path actually ran, not the raw knob —
    # 'auto' can resolve either way (same principle as _resolved()).
    # Mirror the model's dispatch (meshless OR dp-only flash); this
    # bench is meshless, so eligible() decides and eligible_dp() is
    # vacuously False — but keep both so a future dp-mesh bench arm
    # cannot silently mislabel.
    from flink_parameter_server_tpu.ops.flash_attention import (
        eligible as flash_eligible,
        eligible_dp as flash_eligible_dp,
    )

    flash_ran = flash != "off" and (
        flash_eligible(T, cfg.head_dim)
        or flash_eligible_dp(T, cfg.head_dim, B, None)
    )
    _row(
        "5-transformer-lm-dense", tokens_per_sec, "tokens/sec",
        batch=B, seq=T, n_params=n_params,
        d_model=cfg.d_model, n_layers=cfg.n_layers,
        mfu=round(mfu, 4) if mfu else None,
        flash_attention="on" if flash_ran else "off",
    )


def bench_mf():
    import bench as headline

    r = headline.tpu_updates_per_sec()
    _row(
        "1-matrix-factorization", r["updates_per_sec_per_chip"],
        "updates/sec/chip", batch=r["batch"],
        pull_push_p50_ms=round(r["p50_ms"], 3),
        table_dtype=r["table_dtype"],
        hbm_bytes_per_step=r["hbm_bytes_per_step"],
        bandwidth_util=(
            round(r["bandwidth_util"], 4) if r["bandwidth_util"] else None
        ),
    )


BENCHES = {
    "mf": bench_mf,
    "pa": bench_pa,
    "w2v": bench_w2v,
    "fm": bench_fm,
    "lm": bench_lm,
}


def main():
    which = sys.argv[1:] or ["all"]
    bad = [w for w in which if w != "all" and w not in BENCHES]
    if bad:
        raise SystemExit(f"unknown config(s) {bad}; use {list(BENCHES)}")
    import jax

    from flink_parameter_server_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    d = jax.devices()
    print(
        f"# platform: {d[0].platform} device_kind: {d[0].device_kind} "
        f"devices: {len(d)}",
        file=sys.stderr,
    )
    names = list(BENCHES) if "all" in which else which
    for name in names:
        BENCHES[name]()


if __name__ == "__main__":
    main()
