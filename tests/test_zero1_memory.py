"""Measured memory scaling for ZeRO-1 / FSDP (VERDICT r3 next #6).

The fixture records live per-device shard bytes — summed over the array
shards resident on one device — before and after a real jitted step of
a small LM on a dp mesh, so the numbers reflect what survives a step and
not just placement; the tests pin the RATIOS, so the claimed 1/dp
scaling is asserted, not narrated:

  * ZeRO-1: optimizer state ~1/8 of replicated, params unchanged.
  * FSDP: params + optimizer state both ~1/8.
"""
import numpy as np
import pytest

pytestmark = pytest.mark.slow


def _live_bytes_on(tree, device):
    """Bytes of ``tree``'s array shards resident on ``device`` — a
    replicated leaf contributes its FULL size (one copy per device), a
    dp-sharded leaf 1/dp of it."""
    import jax

    return sum(
        sh.data.nbytes
        for leaf in jax.tree.leaves(tree)
        for sh in getattr(leaf, "addressable_shards", ())
        if sh.device == device
    )


@pytest.fixture(scope="module")
def payload(devices):
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

    mesh = Mesh(np.array(devices), ("dp",))
    dev0 = devices[0]
    repl = NamedSharding(mesh, P())
    # small dp-divisible config: keep the 3 jitted LM steps cheap; fp32
    # so the byte table is exact multiples of the param count
    cfg = TransformerConfig(
        vocab_size=1024, d_model=64, n_layers=2, n_heads=4, d_ff=128,
        max_seq=32, dtype=jnp.float32, flash_attention="off",
    )
    opt = optax.adamw(3e-4)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, (8, cfg.max_seq))
    batch = {
        "tokens": jax.device_put(
            jnp.asarray(tokens.astype(np.int32)),
            NamedSharding(mesh, P("dp")),
        ),
    }
    loss_fn = lambda p, b: lm_loss(p, b, cfg)
    base_params = init_params(jax.random.PRNGKey(0), cfg)
    rows = []

    def measure(regime, params, opt_state, step):
        before = (
            _live_bytes_on(params, dev0), _live_bytes_on(opt_state, dev0)
        )
        params, opt_state, loss = jax.block_until_ready(
            step(params, opt_state, batch)
        )
        after = (
            _live_bytes_on(params, dev0), _live_bytes_on(opt_state, dev0)
        )
        rows.append({
            "regime": regime,
            "params_bytes_per_dev": after[0],
            "opt_bytes_per_dev": after[1],
            "total_bytes_per_dev": after[0] + after[1],
            "params_bytes_before_step": before[0],
            "loss": float(loss),
        })

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

    return {"n_devices": len(devices), "rows": rows}


def _row(payload, regime):
    return next(r for r in payload["rows"] if r["regime"] == regime)


def test_zero1_opt_state_is_one_over_dp(payload):
    repl = _row(payload, "replicated")
    z1 = _row(payload, "zero1")
    n = payload["n_devices"]
    # params START replicated under ZeRO-1 (placement as configured)...
    assert (
        z1["params_bytes_before_step"] == repl["params_bytes_per_dev"]
    )
    # ...and m/v shard to ~1/dp (scalars like adam's count replicated)
    ratio = z1["opt_bytes_per_dev"] / repl["opt_bytes_per_dev"]
    assert 1 / n * 0.9 < ratio < 1 / n * 1.5, ratio
    # Measured on the virtual CPU mesh: GSPMD propagates the
    # opt-state constraint through apply_updates to the params OUTPUT,
    # so post-step params may come back dp-sharded too — the memory win
    # is AT LEAST the m/v shard, not more than replicated.
    assert (
        z1["params_bytes_per_dev"] <= repl["params_bytes_per_dev"]
    )
    assert z1["total_bytes_per_dev"] <= repl["total_bytes_per_dev"] * 0.5


def test_fsdp_params_and_opt_are_one_over_dp(payload):
    repl = _row(payload, "replicated")
    fs = _row(payload, "fsdp")
    n = payload["n_devices"]
    ratio = fs["total_bytes_per_dev"] / repl["total_bytes_per_dev"]
    assert 1 / n * 0.9 < ratio < 1 / n * 1.8, ratio


def test_all_regimes_trained(payload):
    # each regime ran a REAL step (loss finite) — placement that dies on
    # first use would be a vacuous memory table
    import math

    for r in payload["rows"]:
        assert math.isfinite(r["loss"]), r
