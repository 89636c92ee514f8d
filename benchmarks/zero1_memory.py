"""Measure per-device parameter/optimizer memory: replicated vs ZeRO-1
vs FSDP (VERDICT r3 next #6 — the features' entire point, quantified).

The dense PS path claims 1/dp scaling for Adam's m/v (ZeRO-1,
core/dense.shard_opt_state_constraint) and for params+opt (FSDP,
core/dense.fsdp_place).  This script builds the transformer-base LM
config (BASELINE config #5 shapes) on a dp mesh and records LIVE
per-device bytes — summed over the actual array shards resident on one
device — before and after a real jitted train step, so the numbers
reflect what survives a step, not just placement.

Usage (8-way virtual CPU mesh):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python benchmarks/zero1_memory.py [--json out.json]

On a real multi-chip TPU mesh the same script reports HBM bytes.
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def live_bytes_per_device(tree, device):
    """Bytes of ``tree``'s array shards resident on ``device`` — a
    replicated leaf contributes its FULL size (one copy per device), a
    dp-sharded leaf 1/dp of it."""
    import jax

    total = 0
    for leaf in jax.tree.leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if shards is None:
            continue
        for sh in shards:
            if sh.device == device:
                total += sh.data.nbytes
    return total


def main(argv=None):
    from flink_parameter_server_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from flink_parameter_server_tpu.core.dense import (
        fsdp_place,
        make_dense_train_step,
        opt_state_zero1_specs,
    )
    from flink_parameter_server_tpu.models.transformer import (
        TransformerConfig,
        init_params,
        lm_loss,
    )

    argv = sys.argv[1:] if argv is None else argv
    json_path = None
    if "--json" in argv:
        json_path = argv[argv.index("--json") + 1]

    devices = jax.devices()
    n = len(devices)
    mesh = Mesh(np.array(devices), ("dp",))
    dev0 = devices[0]
    repl = NamedSharding(mesh, P())

    # BASELINE config #5 shapes (transformer-base-ish); fp32 on CPU so
    # the byte table is exact powers of the param count
    cfg = TransformerConfig(
        vocab_size=int(os.environ.get("FPS_LM_VOCAB", 32_000)),
        d_model=int(os.environ.get("FPS_LM_DMODEL", 512)),
        n_layers=int(os.environ.get("FPS_LM_LAYERS", 6)),
        n_heads=int(os.environ.get("FPS_LM_HEADS", 8)),
        d_ff=int(os.environ.get("FPS_LM_DFF", 2048)),
        max_seq=int(os.environ.get("FPS_LM_SEQ", 128)),
        dtype=jnp.float32,
        flash_attention="off",
    )
    opt = optax.adamw(3e-4)
    B, T = 8, cfg.max_seq
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jax.device_put(
            jnp.asarray(
                rng.integers(0, cfg.vocab_size, (B, T)).astype(np.int32)
            ),
            NamedSharding(mesh, P("dp")),
        ),
    }
    loss_fn = lambda p, b: lm_loss(p, b, cfg)

    base_params = init_params(jax.random.PRNGKey(0), cfg)
    n_params = sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(base_params)
    )

    rows = []

    def measure(regime, params, opt_state, step):
        before = (
            live_bytes_per_device(params, dev0),
            live_bytes_per_device(opt_state, dev0),
        )
        params, opt_state, loss = jax.block_until_ready(
            step(params, opt_state, batch)
        )
        after = (
            live_bytes_per_device(params, dev0),
            live_bytes_per_device(opt_state, dev0),
        )
        rows.append({
            "regime": regime,
            "params_bytes_per_dev": after[0],
            "opt_bytes_per_dev": after[1],
            "total_bytes_per_dev": after[0] + after[1],
            "params_bytes_before_step": before[0],
            "opt_bytes_before_step": before[1],
            "loss": float(loss),
        })
        print(
            f"{regime:<12} params/dev {after[0]/2**20:9.1f} MiB   "
            f"opt/dev {after[1]/2**20:9.1f} MiB   "
            f"total {(after[0]+after[1])/2**20:9.1f} MiB   "
            f"loss {float(loss):.3f}"
        )
        del params, opt_state

    # 1. replicated (the no-ZeRO baseline)
    params = jax.device_put(base_params, repl)
    opt_state = jax.jit(opt.init, out_shardings=repl)(params)
    step = jax.jit(make_dense_train_step(loss_fn, opt))
    measure("replicated", params, opt_state, step)

    # 2. ZeRO-1: params replicated, optimizer state dp-sharded
    params = jax.device_put(base_params, repl)
    opt_state = jax.jit(opt.init, out_shardings=repl)(params)
    specs = opt_state_zero1_specs(opt_state, mesh)
    opt_state = jax.tree.map(
        lambda x, s: jax.device_put(x, s) if s is not None else x,
        opt_state, specs,
    )
    step = jax.jit(make_dense_train_step(
        loss_fn, opt, mesh=mesh, shard_opt_state=True, opt_specs=specs,
    ))
    measure("zero1", params, opt_state, step)

    # 3. FSDP: params AND optimizer state dp-sharded
    params = fsdp_place(jax.device_put(base_params, repl), mesh)
    opt_state = opt.init(params)  # zeros_like inherits the dp layout
    step = jax.jit(make_dense_train_step(loss_fn, opt))
    measure("fsdp", params, opt_state, step)

    repl_total = rows[0]["total_bytes_per_dev"]
    for r in rows:
        r["vs_replicated"] = round(r["total_bytes_per_dev"] / repl_total, 4)
    payload = {
        "n_devices": n,
        "n_params": n_params,
        "platform": devices[0].platform,
        "config": {
            "vocab": cfg.vocab_size, "d_model": cfg.d_model,
            "n_layers": cfg.n_layers, "d_ff": cfg.d_ff,
        },
        "rows": rows,
    }
    print(f"n_params {n_params:,}  devices {n}")
    if json_path:
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=1)
    return payload


if __name__ == "__main__":
    main()
