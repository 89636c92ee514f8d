"""Data-parallel Transformer LM through the dense PS (BASELINE config #5),
showcasing every parallelism axis the framework supports.

Usage (ParameterTool-style args):
    python examples/transformer_lm.py [--mode sp|pp|ep|single]
        [--steps 80] [--remat]

Modes (with ≥8 devices):
    sp     dp×sp×tp mesh, ring attention          (default)
    pp     dp×pp mesh, GPipe pipelined layer stack
    ep     dp×ep mesh, switch-MoE expert parallelism
    single one device, dense

Run on the 8-device CPU mesh:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/transformer_lm.py --mode ep
"""
import sys

import numpy as np
import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from flink_parameter_server_tpu.core.dense import (
    DenseParameterServer,
    transform_dense,
)
from flink_parameter_server_tpu.models.transformer import (
    TransformerConfig,
    forward_pipelined,
    init_params,
    lm_loss,
    next_token_xent,
)
from flink_parameter_server_tpu.utils.config import Parameters


def bigram_batches(n, B, T, vocab, seed=0):
    rng = np.random.default_rng(seed)
    perm = rng.permutation(vocab)
    for _ in range(n):
        toks = np.empty((B, T), np.int32)
        toks[:, 0] = rng.integers(0, vocab, B)
        for t in range(1, T):
            toks[:, t] = perm[toks[:, t - 1]]
        yield {"tokens": toks}


def main():
    from flink_parameter_server_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    params = Parameters.from_env().merged_with(
        Parameters.from_args(sys.argv[1:])
    )
    mode = params.get("mode", "sp")
    if mode not in ("sp", "pp", "ep", "single"):
        raise SystemExit(f"--mode {mode!r}: use one of sp, pp, ep, single")
    steps = params.get_int("steps", 80)
    remat = params.get_bool("remat")
    devices = jax.devices()
    if len(devices) < 8 and mode != "single":
        print(f"only {len(devices)} devices; falling back to --mode single")
        mode = "single"

    base = dict(
        vocab_size=256, d_model=128, n_heads=8, n_layers=4, d_ff=512,
        max_seq=64, dtype=jnp.float32, remat=remat,
    )
    mesh = None
    batch_sharding = None
    loss_fn = None

    if mode == "sp":
        mesh = Mesh(np.array(devices[:8]).reshape(2, 2, 2), ("dp", "sp", "tp"))
        cfg = TransformerConfig(
            **base, use_ring_attention=True, sp_axis="sp", tp_axis="tp"
        )
        batch_sharding = NamedSharding(mesh, P("dp", "sp"))
        loss_fn = lambda p, b: lm_loss(p, b, cfg, mesh=mesh)  # noqa: E731
    elif mode == "pp":
        mesh = Mesh(np.array(devices[:8]).reshape(2, 4), ("dp", "pp"))
        cfg = TransformerConfig(**base, pp_axis="pp")
        batch_sharding = NamedSharding(mesh, P("dp"))

        def loss_fn(p, b):
            logits = forward_pipelined(
                p, b["tokens"], cfg, mesh=mesh, num_microbatches=2
            )
            return next_token_xent(logits, b["tokens"])

    elif mode == "ep":
        mesh = Mesh(np.array(devices[:8]).reshape(2, 4), ("dp", "ep"))
        cfg = TransformerConfig(
            **base, num_experts=8, ep_axis="ep", moe_capacity=256
        )
        batch_sharding = NamedSharding(mesh, P("dp"))
        loss_fn = lambda p, b: lm_loss(p, b, cfg, mesh=mesh)  # noqa: E731
    else:  # "single" (validated above)
        cfg = TransformerConfig(**base)
        loss_fn = lambda p, b: lm_loss(p, b, cfg)  # noqa: E731

    model_params = init_params(jax.random.PRNGKey(0), cfg, mesh)
    server = DenseParameterServer(model_params, optax.adamw(3e-3))
    losses = []
    transform_dense(
        bigram_batches(steps, B=8, T=64, vocab=256),
        loss_fn,
        server,
        batch_sharding=batch_sharding,
        on_step=lambda i, l: losses.append(float(l)),
    )
    mesh_desc = dict(mesh.shape) if mesh is not None else "single device"
    print(f"mode={mode} mesh={mesh_desc} remat={remat}")
    print(f"loss {losses[0]:.3f} -> {np.mean(losses[-5:]):.3f} "
          f"(random = {np.log(256):.3f})")


if __name__ == "__main__":
    main()
