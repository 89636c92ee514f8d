"""Transformer LM + ring attention + dense PS tests (BASELINE config 5)."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from flink_parameter_server_tpu.core.dense import (
    DenseParameterServer,
    transform_dense,
)
from flink_parameter_server_tpu.models.transformer import (
    TransformerConfig,
    forward,
    init_params,
    lm_loss,
)
from jax.sharding import Mesh

from flink_parameter_server_tpu.parallel.mesh import make_mesh
from flink_parameter_server_tpu.parallel.ring_attention import (
    reference_attention,
    ring_attention,
)


@pytest.fixture(scope="module")
def sp_mesh():
    return make_mesh(2, 4, axis_names=("dp", "sp"))


class TestRingAttention:
    def _qkv(self, B=2, T=32, H=4, D=8, seed=0):
        rng = np.random.default_rng(seed)
        mk = lambda: jnp.asarray(rng.normal(0, 1, (B, T, H, D)).astype(np.float32))
        return mk(), mk(), mk()

    def test_matches_reference_causal(self, sp_mesh):
        q, k, v = self._qkv()
        want = reference_attention(q, k, v, causal=True)
        got = ring_attention(q, k, v, mesh=sp_mesh)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

    def test_matches_reference_noncausal(self, sp_mesh):
        q, k, v = self._qkv(seed=1)
        want = reference_attention(q, k, v, causal=False)
        got = ring_attention(q, k, v, mesh=sp_mesh, causal=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

    def test_under_jit_with_grad(self, sp_mesh):
        q, k, v = self._qkv(T=16, seed=2)

        def f(q, k, v):
            return jnp.sum(ring_attention(q, k, v, mesh=sp_mesh) ** 2)

        def f_ref(q, k, v):
            return jnp.sum(reference_attention(q, k, v) ** 2)

        g = jax.jit(jax.grad(f))(q, k, v)
        g_ref = jax.grad(f_ref)(q, k, v)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=5e-4)


TINY = TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
    max_seq=32, dtype=jnp.float32,
)


def _bigram_task_batches(n_batches, B=8, T=16, vocab=64, seed=0):
    """Markov chains under a fixed random permutation: next = perm[cur].
    Tied embeddings can't solve this at init — it must be learned."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(vocab)
    for _ in range(n_batches):
        toks = np.empty((B, T), np.int32)
        toks[:, 0] = rng.integers(0, vocab, B)
        for t in range(1, T):
            toks[:, t] = perm[toks[:, t - 1]]
        yield {"tokens": toks}


def test_transformer_learns_bigram_task():
    params = init_params(jax.random.PRNGKey(0), TINY)
    server = DenseParameterServer(params, optax.adam(1e-2))
    losses = []
    res = transform_dense(
        _bigram_task_batches(60),
        lambda p, b: lm_loss(p, b, TINY),
        server,
        on_step=lambda i, l: losses.append(float(l)),
    )
    assert np.mean(losses[-5:]) < 0.25 * np.mean(losses[:3]), (
        losses[:3], losses[-5:]
    )
    # the dump is the model pytree
    assert "embed" in res.server_outputs[0]


def test_tp_sharded_matches_single_device():
    import dataclasses

    cfg = dataclasses.replace(TINY, tp_axis="ps")
    mesh = make_mesh(2, 4)  # dp x ps(=tp)
    params_s = init_params(jax.random.PRNGKey(1), cfg, mesh)
    params_1 = init_params(jax.random.PRNGKey(1), TINY)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, (4, 16)).astype(np.int32)
    )
    logits_s = jax.jit(lambda p, t: forward(p, t, cfg, mesh=mesh))(params_s, tokens)
    logits_1 = forward(params_1, tokens, TINY)
    np.testing.assert_allclose(
        np.asarray(logits_s), np.asarray(logits_1), atol=2e-4
    )


def test_sp_ring_transformer_matches_dense(sp_mesh):
    import dataclasses

    mesh = sp_mesh
    cfg = dataclasses.replace(
        TINY, sp_axis="sp", use_ring_attention=True
    )
    params = init_params(jax.random.PRNGKey(2), TINY)
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, 64, (4, 32)).astype(np.int32)
    )
    from jax.sharding import NamedSharding, PartitionSpec as P

    tok_sharded = jax.device_put(tokens, NamedSharding(mesh, P("dp", "sp")))
    logits_ring = jax.jit(lambda p, t: forward(p, t, cfg, mesh=mesh))(
        params, tok_sharded
    )
    logits_dense = forward(params, tokens, TINY)
    np.testing.assert_allclose(
        np.asarray(logits_ring), np.asarray(logits_dense), atol=3e-4
    )


@pytest.mark.slow
def test_ring_attention_bf16_fp32_accumulators(sp_mesh):
    """bf16 inputs must accumulate in fp32: result within bf16 resolution
    of the fp32 reference."""
    rng = np.random.default_rng(5)
    mk = lambda: rng.normal(0, 1, (2, 32, 4, 8)).astype(np.float32)
    qf, kf, vf = mk(), mk(), mk()
    want = reference_attention(jnp.asarray(qf), jnp.asarray(kf), jnp.asarray(vf))
    got = ring_attention(
        jnp.asarray(qf).astype(jnp.bfloat16),
        jnp.asarray(kf).astype(jnp.bfloat16),
        jnp.asarray(vf).astype(jnp.bfloat16),
        mesh=sp_mesh,
    )
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got.astype(jnp.float32)), np.asarray(want), atol=0.03
    )


def test_transform_dense_preserves_input_server():
    """transform_dense's donation must not destroy the caller's server."""
    params = init_params(jax.random.PRNGKey(0), TINY)
    server = DenseParameterServer(params, optax.sgd(0.1))
    transform_dense(
        _bigram_task_batches(2), lambda p, b: lm_loss(p, b, TINY), server
    )
    # still alive and usable
    assert bool(jnp.isfinite(server.pull()["embed"]).all())
    transform_dense(
        _bigram_task_batches(2), lambda p, b: lm_loss(p, b, TINY), server
    )


def test_lm_loss_row_mask():
    params = init_params(jax.random.PRNGKey(0), TINY)
    toks = np.random.default_rng(0).integers(0, 64, (4, 8)).astype(np.int32)
    full = float(lm_loss(params, {"tokens": jnp.asarray(toks)}, TINY))
    masked = float(
        lm_loss(
            params,
            {"tokens": jnp.asarray(toks), "mask": jnp.array([1, 1, 0, 0], jnp.float32)},
            TINY,
        )
    )
    assert np.isfinite(masked) and masked != full


def test_remat_matches_non_remat_gradients():
    """jax.checkpoint rematerialisation must not change values or grads."""
    import dataclasses

    cfg_r = dataclasses.replace(TINY, remat=True)
    params = init_params(jax.random.PRNGKey(3), TINY)
    toks = jnp.asarray(
        np.random.default_rng(2).integers(0, 64, (2, 16)).astype(np.int32)
    )
    loss_plain, grads_plain = jax.value_and_grad(
        lambda p: lm_loss(p, {"tokens": toks}, TINY)
    )(params)
    loss_remat, grads_remat = jax.value_and_grad(
        lambda p: lm_loss(p, {"tokens": toks}, cfg_r)
    )(params)
    assert float(loss_plain) == pytest.approx(float(loss_remat), rel=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5
        ),
        grads_plain,
        grads_remat,
    )


class TestPipelineParallel:
    def _setup(self, pp=4):
        from flink_parameter_server_tpu.models.transformer import (
            forward_pipelined,
        )
        import dataclasses

        mesh = make_mesh(8 // pp, pp, axis_names=("dp", "pp"))
        cfg = dataclasses.replace(TINY, pp_axis="pp", n_layers=4)
        params = init_params(jax.random.PRNGKey(4), cfg)
        tokens = jnp.asarray(
            np.random.default_rng(3).integers(0, 64, (8, 16)).astype(np.int32)
        )
        return forward_pipelined, mesh, cfg, params, tokens

    def test_pipelined_forward_matches_dense(self):
        forward_pipelined, mesh, cfg, params, tokens = self._setup()
        logits_pp = jax.jit(
            lambda p, t: forward_pipelined(p, t, cfg, mesh=mesh,
                                           num_microbatches=4)
        )(params, tokens)
        logits_dense = forward(params, tokens, cfg)
        np.testing.assert_allclose(
            np.asarray(logits_pp), np.asarray(logits_dense), atol=3e-4
        )

    def test_pipelined_gradients_match(self):
        forward_pipelined, mesh, cfg, params, tokens = self._setup(pp=2)

        def loss_pp(p):
            # dp=4 here: per-dp batch is 2, so 2 microbatches
            lg = forward_pipelined(p, tokens, cfg, mesh=mesh,
                                   num_microbatches=2)
            return jnp.mean(jax.nn.log_softmax(lg)[..., 0])

        def loss_dense(p):
            lg = forward(p, tokens, cfg)
            return jnp.mean(jax.nn.log_softmax(lg)[..., 0])

        g_pp = jax.jit(jax.grad(loss_pp))(params)
        g_dense = jax.grad(loss_dense)(params)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-5
            ),
            g_pp,
            g_dense,
        )

    def test_microbatch_divisibility_asserted(self):
        forward_pipelined, mesh, cfg, params, tokens = self._setup()
        with pytest.raises(AssertionError):
            forward_pipelined(params, tokens, cfg, mesh=mesh,
                              num_microbatches=3)  # 8 % 3 != 0


def test_pipelined_ring_attention_composition():
    """PP × SP: pipelined stages with sp-sharded sequence + ring
    attention inside each stage match the dense oracle."""
    import dataclasses

    from flink_parameter_server_tpu.models.transformer import (
        forward_pipelined,
    )

    mesh = Mesh(
        np.array(jax.devices()).reshape(2, 2, 2), ("dp", "pp", "sp")
    )
    cfg = dataclasses.replace(
        TINY, n_layers=4, pp_axis="pp", sp_axis="sp",
        use_ring_attention=True,
    )
    params = init_params(jax.random.PRNGKey(6), cfg)
    tokens = jnp.asarray(
        np.random.default_rng(7).integers(0, 64, (8, 16)).astype(np.int32)
    )
    logits_pp_sp = jax.jit(
        lambda p, t: forward_pipelined(p, t, cfg, mesh=mesh,
                                       num_microbatches=2)
    )(params, tokens)
    dense_cfg = dataclasses.replace(
        cfg, pp_axis=None, sp_axis=None, use_ring_attention=False
    )
    logits_dense = forward(params, tokens, dense_cfg)
    np.testing.assert_allclose(
        np.asarray(logits_pp_sp), np.asarray(logits_dense), atol=3e-4
    )


@pytest.mark.slow
def test_pipelined_ring_attention_gradients():
    """PP × SP gradients (ppermute inside scan inside the pipeline
    shard_map) match the dense oracle."""
    import dataclasses

    from flink_parameter_server_tpu.models.transformer import (
        forward_pipelined,
    )

    mesh = Mesh(
        np.array(jax.devices()).reshape(2, 2, 2), ("dp", "pp", "sp")
    )
    cfg = dataclasses.replace(
        TINY, n_layers=2, pp_axis="pp", sp_axis="sp",
        use_ring_attention=True,
    )
    params = init_params(jax.random.PRNGKey(8), cfg)
    tokens = jnp.asarray(
        np.random.default_rng(9).integers(0, 64, (4, 16)).astype(np.int32)
    )

    def loss_pp(p):
        lg = forward_pipelined(p, tokens, cfg, mesh=mesh, num_microbatches=2)
        return jnp.mean(jax.nn.log_softmax(lg)[..., 0])

    dense_cfg = dataclasses.replace(
        cfg, pp_axis=None, sp_axis=None, use_ring_attention=False
    )

    def loss_dense(p):
        lg = forward(p, tokens, dense_cfg)
        return jnp.mean(jax.nn.log_softmax(lg)[..., 0])

    g_pp = jax.jit(jax.grad(loss_pp))(params)
    g_dense = jax.grad(loss_dense)(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5
        ),
        g_pp,
        g_dense,
    )


@pytest.mark.parametrize("spc", [2, 3])
def test_transform_dense_steps_per_call_matches(spc):
    """K dense steps per jitted dispatch (lax.scan) must match the
    per-dispatch loop per step — losses, final params, tail included."""
    import numpy as _np

    from flink_parameter_server_tpu.core.dense import transform_dense

    rng = _np.random.default_rng(2)
    batches = [
        {"x": jnp.asarray(rng.normal(size=(32, 16)), jnp.float32),
         "y": jnp.asarray(rng.normal(size=(32, 4)), jnp.float32)}
        for _ in range(5)  # 5 % spc != 0 -> exercises the tail
    ]

    import optax

    from flink_parameter_server_tpu.core.dense import DenseParameterServer

    def run(steps_per_call):
        prng = _np.random.default_rng(0)
        params = {
            "w1": jnp.asarray(prng.normal(0, 0.1, (16, 32)), jnp.float32),
            "b1": jnp.asarray(_np.zeros(32), jnp.float32),
            "w2": jnp.asarray(prng.normal(0, 0.1, (32, 4)), jnp.float32),
        }
        server = DenseParameterServer(params, optax.adam(1e-2))

        def loss_fn(p, batch):
            h = jnp.tanh(batch["x"] @ p["w1"] + p["b1"])
            return jnp.mean(((h @ p["w2"]) - batch["y"]) ** 2)

        return transform_dense(
            batches, loss_fn, server, steps_per_call=steps_per_call
        )

    a, b = run(1), run(spc)
    assert len(a.worker_outputs) == len(b.worker_outputs) == 5
    for la, lb in zip(a.worker_outputs, b.worker_outputs):
        np.testing.assert_allclose(float(la), float(lb), atol=1e-6)
    for xa, xb in zip(
        jax.tree.leaves(a.server_outputs[0]),
        jax.tree.leaves(b.server_outputs[0]),
    ):
        np.testing.assert_allclose(
            np.asarray(xa), np.asarray(xb), atol=1e-6
        )
