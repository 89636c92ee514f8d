"""Splash flash-attention integration (ops/flash_attention.py).

Interpret mode on CPU proves kernel-call plumbing and numerics; the
compiled path is exercised by chip_smoke.py on the chip.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_parameter_server_tpu.ops.flash_attention import (
    flash_mha,
    supports_shape,
)
from flink_parameter_server_tpu.parallel.ring_attention import (
    reference_attention,
)


def _qkv(rng, B, T, H, D, dtype):
    mk = lambda: jnp.asarray(rng.normal(size=(B, T, H, D)) * 0.5, dtype)
    return mk(), mk(), mk()


@pytest.mark.parametrize(
    "T,D,dtype,tol",
    [(128, 64, jnp.float32, 1e-5), (128, 128, jnp.bfloat16, 0.02)],
)
def test_forward_parity(rng, T, D, dtype, tol):
    q, k, v = _qkv(rng, 2, T, 4, D, dtype)
    got = flash_mha(q, k, v, interpret=True)
    want = reference_attention(q, k, v)
    assert got.shape == want.shape and got.dtype == v.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=tol,
    )


def test_grad_parity(rng):
    q, k, v = _qkv(rng, 1, 128, 2, 64, jnp.float32)

    def loss_flash(q, k, v):
        return flash_mha(q, k, v, interpret=True).sum()

    def loss_ref(q, k, v):
        return reference_attention(q, k, v).sum()

    g = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4
        )


def test_shape_gate():
    assert supports_shape(128, 64) and supports_shape(2048, 128)
    assert not supports_shape(100, 64)  # T not 128-aligned
    assert not supports_shape(128, 65)  # D not lane-aligned
    q = jnp.zeros((1, 100, 2, 64), jnp.float32)
    with pytest.raises(ValueError, match="T % 128"):
        flash_mha(q, q, q, interpret=True)


def test_model_level_parity(rng, monkeypatch):
    """forward() through the flash path == the reference path on a tiny
    LM (the auto-gating wiring in _unsharded_attention, RoPE and
    residuals included).  TPU eligibility is emulated by patching the
    backend probe and routing flash_mha through interpret mode."""
    import dataclasses

    import flink_parameter_server_tpu.models.transformer as tr
    import flink_parameter_server_tpu.ops.flash_attention as fa
    from flink_parameter_server_tpu.models.transformer import (
        TransformerConfig,
        forward,
        init_params,
    )

    cfg_off = TransformerConfig(
        vocab_size=64, d_model=128, n_heads=2, n_layers=1, d_ff=128,
        max_seq=128, dtype=jnp.float32, flash_attention="off",
    )
    params = init_params(jax.random.PRNGKey(0), cfg_off)
    tokens = jnp.asarray(rng.integers(0, 64, (1, 128)), jnp.int32)
    logits_off = forward(params, tokens, cfg_off)

    calls = []
    orig = fa.flash_mha

    def interpreted(q, k, v, **kw):
        calls.append(1)
        return orig(q, k, v, interpret=True)

    monkeypatch.setattr(fa, "flash_mha", interpreted)
    monkeypatch.setattr(tr.jax, "default_backend", lambda: "tpu")
    cfg_auto = dataclasses.replace(cfg_off, flash_attention="auto")
    logits_auto = forward(params, tokens, cfg_auto)
    assert calls, "auto gating did not take the flash path"
    np.testing.assert_allclose(
        np.asarray(logits_auto), np.asarray(logits_off), atol=2e-4
    )


def test_flash_on_requires_tpu(rng):
    """flash_attention='on' must raise off-TPU rather than silently run
    the interpret-mode kernel (an effective hang at model sizes)."""
    import dataclasses

    from flink_parameter_server_tpu.models.transformer import (
        TransformerConfig,
        forward,
        init_params,
    )

    cfg = TransformerConfig(
        vocab_size=64, d_model=128, n_heads=2, n_layers=1, d_ff=128,
        max_seq=128, dtype=jnp.float32, flash_attention="on",
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(rng.integers(0, 64, (1, 128)), jnp.int32)
    with pytest.raises(ValueError, match="ineligible"):
        forward(params, tokens, cfg)


def test_config_validation():
    from flink_parameter_server_tpu.models.transformer import (
        TransformerConfig,
    )

    with pytest.raises(ValueError, match="flash_attention"):
        TransformerConfig(flash_attention="always")


def test_kernel_cache_safe_when_first_use_is_jitted(rng):
    """Regression: the kernel cache must hold concrete objects even when
    the first call at a shape happens inside a jit trace — a cached
    tracer-carrying kernel poisons every later trace
    (UnexpectedTracerError on the next grad/jit at that shape)."""
    from flink_parameter_server_tpu.ops.flash_attention import _make_kernel

    _make_kernel.cache_clear()
    T, D = 256, 64  # a shape no other test uses
    q, k, v = _qkv(rng, 1, T, 2, D, jnp.float32)
    out = jax.jit(
        lambda a, b, c: flash_mha(a, b, c, interpret=True)
    )(q, k, v)
    # second, different trace at the same shape reuses the cache
    g = jax.jit(jax.grad(
        lambda a: flash_mha(a, k, v, interpret=True).sum()
    ))(q)
    assert out.shape == q.shape and g.shape == q.shape


def test_flash_mha_dp_parity(rng):
    """flash under shard_map over a dp-only mesh == the reference on the
    full batch (attention never mixes batch rows)."""
    from jax.sharding import Mesh

    from flink_parameter_server_tpu.ops.flash_attention import (
        eligible_dp,
        flash_mha_dp,
    )

    devs = np.array(jax.devices()[:2]).reshape(2, 1)
    mesh = Mesh(devs, ("dp", "ps"))
    q, k, v = _qkv(rng, 4, 128, 2, 64, jnp.float32)
    got = flash_mha_dp(q, k, v, mesh=mesh, interpret=True)
    want = reference_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=1e-5
    )
    # gating: dp-only requirement and batch divisibility (backend check
    # is False on CPU regardless — assert the structural parts)
    assert not eligible_dp(128, 64, 3, mesh)  # 3 % 2 != 0
    sp_mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "sp"))
    assert not eligible_dp(128, 64, 4, sp_mesh)  # sp axis > 1


def test_model_level_dp_flash_gating(rng, monkeypatch):
    """forward() on a dp-only mesh routes through flash_mha_dp when
    'auto' resolves eligible (emulated TPU), matching the reference."""
    import dataclasses

    from jax.sharding import Mesh

    import flink_parameter_server_tpu.models.transformer as tr
    import flink_parameter_server_tpu.ops.flash_attention as fa
    from flink_parameter_server_tpu.models.transformer import (
        TransformerConfig,
        forward,
        init_params,
    )

    cfg_off = TransformerConfig(
        vocab_size=64, d_model=128, n_heads=2, n_layers=1, d_ff=128,
        max_seq=128, dtype=jnp.float32, flash_attention="off",
    )
    params = init_params(jax.random.PRNGKey(0), cfg_off)
    tokens = jnp.asarray(rng.integers(0, 64, (2, 128)), jnp.int32)
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1), ("dp", "ps"))

    logits_off = forward(params, tokens, cfg_off, mesh=mesh)

    calls = []
    orig = fa.flash_mha_dp

    def interpreted(q, k, v, **kw):
        calls.append(1)
        kw["interpret"] = True
        return orig(q, k, v, **kw)

    monkeypatch.setattr(fa, "flash_mha_dp", interpreted)
    monkeypatch.setattr(tr.jax, "default_backend", lambda: "tpu")
    cfg_auto = dataclasses.replace(cfg_off, flash_attention="auto")
    logits_auto = forward(params, tokens, cfg_auto, mesh=mesh)
    assert calls, "dp auto gating did not take the flash path"
    np.testing.assert_allclose(
        np.asarray(logits_auto), np.asarray(logits_off), atol=2e-4
    )


def test_pipelined_rejects_flash_on(rng):
    """forward_pipelined must raise for flash_attention='on' (the 'on'
    contract is kernel-or-error; stages silently pin flash off)."""
    import dataclasses

    from jax.sharding import Mesh

    from flink_parameter_server_tpu.models.transformer import (
        TransformerConfig,
        forward_pipelined,
        init_params,
    )

    cfg = TransformerConfig(
        vocab_size=64, d_model=128, n_heads=2, n_layers=2, d_ff=128,
        max_seq=128, dtype=jnp.float32, pp_axis="pp",
        flash_attention="on",
    )
    params = init_params(
        jax.random.PRNGKey(0), dataclasses.replace(cfg, flash_attention="off")
    )
    tokens = jnp.asarray(rng.integers(0, 64, (2, 128)), jnp.int32)
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "pp"))
    with pytest.raises(ValueError, match="not supported in forward_pipelined"):
        forward_pipelined(params, tokens, cfg, mesh=mesh)
