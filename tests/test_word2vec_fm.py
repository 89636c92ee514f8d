"""word2vec SGNS and factorization-machine tests (BASELINE configs 3, 4)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_parameter_server_tpu.data.text import (
    skipgram_batches,
    synthetic_corpus,
)
from flink_parameter_server_tpu.models.factorization_machine import (
    FMConfig,
    train_fm,
)
from flink_parameter_server_tpu.models.word2vec import (
    IN,
    train_skipgram,
    sample_negatives,
)


def test_sgns_loss_decreases():
    vocab = 300
    tokens = synthetic_corpus(vocab, 20_000, num_topics=6, seed=0)
    losses = []

    res = train_skipgram(
        skipgram_batches(tokens, vocab, batch_size=512, epochs=2, seed=0),
        vocab_size=vocab,
        dim=16,
        learning_rate=0.05,
        on_step=lambda i, out: losses.append(float(jnp.mean(out["loss"]))),
        collect_outputs=False,
    )
    first = np.mean(losses[:5])
    last = np.mean(losses[-5:])
    assert last < 0.8 * first, (first, last)
    emb = np.asarray(res.store.values())
    assert emb.shape == (vocab, 2, 16)


def test_sgns_topical_structure():
    """Words from the same planted topic should embed closer than words
    from different topics."""
    vocab, topics = 200, 4
    tokens = synthetic_corpus(
        vocab, 60_000, num_topics=topics, topic_stickiness=0.995, seed=1
    )
    res = train_skipgram(
        skipgram_batches(tokens, vocab, batch_size=512, window=3, epochs=3, seed=1),
        vocab_size=vocab,
        dim=16,
        learning_rate=0.05,
        collect_outputs=False,
    )
    emb = np.asarray(res.store.values())[:, IN]
    emb = emb / (np.linalg.norm(emb, axis=1, keepdims=True) + 1e-9)
    wpt = vocab // topics
    # frequent words (low rank within topic) carry the signal
    same, diff = [], []
    for t in range(topics):
        a, b = t * wpt, t * wpt + 1
        same.append(float(emb[a] @ emb[b]))
        other = ((t + 1) % topics) * wpt
        diff.append(float(emb[a] @ emb[other]))
    assert np.mean(same) > np.mean(diff) + 0.2, (same, diff)


def test_sample_negatives_follows_cdf():
    probs = np.array([0.5, 0.25, 0.125, 0.125])
    cdf = jnp.asarray(np.cumsum(probs))
    s = sample_negatives(jax.random.PRNGKey(0), cdf, (20_000,))
    freq = np.bincount(np.asarray(s), minlength=4) / 20_000
    np.testing.assert_allclose(freq, probs, atol=0.02)


def _fm_batches(rng, n, num_feats, k, w, V, batch=256, epochs=1):
    for _ in range(epochs):
        for s in range(0, n, batch):
            B = batch
            ids = rng.integers(0, num_feats, (B, k)).astype(np.int32)
            vals = np.ones((B, k), np.float32)
            fm = np.ones((B, k), bool)
            lin = w[ids].sum(1)
            inter = np.zeros(B)
            for b in range(B):
                vv = V[ids[b]]
                s_ = vv.sum(0)
                inter[b] = 0.5 * ((s_ @ s_) - (vv * vv).sum())
            y = np.sign(lin + inter + 1e-9)
            yield {
                "ids": ids,
                "values": vals,
                "feat_mask": fm,
                "label": y.astype(np.float32),
                "mask": np.ones(B, bool),
            }


def test_fm_learns_synthetic_interactions():
    rng = np.random.default_rng(3)
    F, k = 60, 5
    w_true = rng.normal(0, 1, F)
    V_true = rng.normal(0, 0.5, (F, 4))
    cfg = FMConfig(num_features=F, dim=4, learning_rate=0.05)
    res = train_fm(
        _fm_batches(rng, 6 * 2048, F, k, w_true, V_true, epochs=1),
        cfg,
        collect_outputs=False,
    )
    rng2 = np.random.default_rng(3)
    # regenerate a fresh eval batch from the same ground truth
    eval_batch = next(_fm_batches(rng2, 2048, F, k, w_true, V_true))
    model = np.asarray(res.store.values())
    w, V = model[:, 0], model[:, 1:]
    ids = eval_batch["ids"]
    lin = w[ids].sum(1)
    inter = np.array(
        [0.5 * ((V[i].sum(0) @ V[i].sum(0)) - (V[i] * V[i]).sum()) for i in ids]
    )
    acc = np.mean(np.sign(lin + inter) == eval_batch["label"])
    assert acc > 0.75, acc


def test_fm_squared_loss_gradient_check():
    """FM step gradient vs jax.grad of the same objective (squared loss)."""
    from flink_parameter_server_tpu.models.factorization_machine import (
        FactorizationMachine,
    )

    cfg = FMConfig(num_features=10, dim=3, learning_rate=1.0, loss="squared")
    logic = FactorizationMachine(cfg)
    rng = np.random.default_rng(0)
    pulled = jnp.asarray(rng.normal(0, 0.5, (2, 4, 4)).astype(np.float32))
    batch = {
        "ids": jnp.asarray(rng.integers(0, 10, (2, 4)).astype(np.int32)),
        "values": jnp.asarray(rng.normal(0, 1, (2, 4)).astype(np.float32)),
        "feat_mask": jnp.ones((2, 4), bool),
        "label": jnp.asarray([0.3, -0.7], jnp.float32),
        "mask": jnp.ones(2, bool),
    }

    def objective(p):
        x = batch["values"]
        w, v = p[..., 0], p[..., 1:]
        lin = jnp.sum(w * x, -1)
        xv = x[..., None] * v
        s = xv.sum(1)
        inter = 0.5 * (jnp.sum(s * s, -1) - jnp.sum(xv * xv, (1, 2)))
        return jnp.sum(0.5 * (lin + inter - batch["label"]) ** 2)

    want = -jax.grad(objective)(pulled)  # lr = 1, delta = -grad
    _, req, _ = logic.step((), batch, pulled)
    np.testing.assert_allclose(np.asarray(req.deltas), np.asarray(want), rtol=2e-4, atol=2e-5)
    # the copy a step in one place traces takes its rows turned, fields
    # leading, (K, B, d), and pushes so: the same numbers
    turned = logic.for_workers(1)
    assert turned.pulls_turned and not logic.pulls_turned
    assert turned.keys(batch).shape == (2, 4) and logic.for_workers(2) is logic
    _, req, out = turned.step((), batch, jnp.swapaxes(pulled, 0, 1))
    assert out["prediction"].shape == out["loss"].shape == (2,)
    np.testing.assert_allclose(
        np.swapaxes(np.asarray(req.deltas), 0, 1), np.asarray(want),
        rtol=2e-4, atol=2e-5)


def test_sgns_dedup_scale_stabilizes_high_lr():
    """Summed duplicate deltas diverge at lr=0.1 on a Zipf corpus; the
    occurrence-mean combiner (the combination-sender analogue) keeps the
    same lr stable."""
    vocab = 300
    tokens = synthetic_corpus(vocab, 20_000, num_topics=6, seed=0)
    from flink_parameter_server_tpu.models.word2vec import SkipGramNS, make_store
    from flink_parameter_server_tpu.core.transform import transform_batched

    losses = []
    # lr=0.1 with summed duplicates diverges (see ops/dedup.py docstring);
    # with mean-combining even lr=1.0 is stable and converges fast.
    logic = SkipGramNS(1.0, dedup_scale=True, vocab_size=vocab)
    transform_batched(
        skipgram_batches(tokens, vocab, batch_size=512, epochs=2, seed=0),
        logic,
        make_store(vocab, 16, seed=0),
        on_step=lambda i, o: losses.append(float(jnp.mean(o["loss"]))),
        collect_outputs=False,
        dump_model=False,
    )
    assert max(losses) < 10.0, max(losses)  # no explosion
    assert np.mean(losses[-5:]) < 0.8 * np.mean(losses[:5])


def _sgns_reference(values, batches, lr):
    """Plain numpy float64, independent of ``models/``: one bulk-synchronous
    step a batch (all rows read as they stood before it, the deltas of
    words that share a row summed), ``values`` of shape (vocab, 2, dim)."""
    from flink_parameter_server_tpu.models.word2vec import OUT

    values = values.astype(np.float64)
    for b in batches:
        v = values[b["center"], IN]
        outs = np.concatenate([b["context"][:, None], b["negatives"]], axis=1)
        u = values[outs, OUT]
        g = 1.0 / (1.0 + np.exp(-np.einsum("bd,bnd->bn", v, u)))
        g[:, 0] -= 1.0  # the context is the positive
        dv = -lr * np.einsum("bn,bnd->bd", g, u)
        du = -lr * g[..., None] * v[:, None, :]
        np.add.at(values[:, IN], b["center"], dv)
        np.add.at(values[:, OUT], outs.reshape(-1), du.reshape(-1, v.shape[1]))
    return values


@pytest.mark.parametrize("dim, lanes", [(300, 640), (64, 128), (8, 128)])
def test_sgns_step_on_the_stores_own_layout_is_the_plain_arithmetic(dim, lanes):
    """``SkipGramNS`` through ``make_train_step`` on the table as
    ``make_store`` lays it by itself (a ``(2, dim)`` row flat in whole
    128-lane registers, or several to one), against numpy: the touched
    slots move as the reference's, the slot a batch does not address comes
    back bit-equal.  The table is read as the array it is, not through
    ``pull``."""
    from flink_parameter_server_tpu.core.transform import make_train_step
    from flink_parameter_server_tpu.models.word2vec import (
        OUT, SkipGramNS, make_store,
    )

    vocab, batch, lr = 512, 256, 0.025
    store = make_store(vocab, dim, seed=3)
    assert store.spec.layout == "packed" and store.table.shape[1] == lanes
    # output vectors away from 0, so that a centre's vector moves at once
    store = store.push(
        jnp.arange(vocab), jnp.full((vocab, 2, dim), 1e-2, jnp.float32)
    )

    def logical(table):  # the physical table's rows, by a path of its own
        k, width = store.spec.pack, 2 * dim
        flat = np.asarray(table)[:, : k * width].reshape(-1, width)
        return flat[:vocab].reshape(vocab, 2, dim)

    before = logical(store.table)
    np.testing.assert_array_equal(before, np.asarray(store.values()))
    rng = np.random.default_rng(dim)
    hot = rng.zipf(1.3, (3, batch, 7)) % vocab  # duplicates within a batch
    cold = rng.integers(0, vocab, (3, batch, 7))
    ids = np.where(rng.random((3, batch, 7)) < 0.5, hot, cold).astype(np.int32)
    batches = [{
        "center": i[:, 0] // 2, "context": i[:, 1], "negatives": i[:, 2:],
        "mask": np.ones(batch, bool),
    } for i in ids]  # centres from the lower half: the upper stays idle
    step = jax.jit(make_train_step(SkipGramNS(lr), store.spec))
    table, state = store.table, ()
    for b in batches:
        table, state, _ = step(table, state, b)
    got, want = logical(table), _sgns_reference(before, batches, lr)
    moved = np.abs(want - before)
    assert (moved[: vocab // 2, IN] > 0).any() and (moved[:, OUT] > 0).any()
    assert np.abs(got - want).max() <= 2e-5 * moved.max()
    np.testing.assert_allclose(got - before, want - before, rtol=2e-3, atol=1e-7)
    idle = np.setdiff1d(np.arange(vocab), np.concatenate([b["center"] for b in batches]))
    assert len(idle) >= vocab // 2
    np.testing.assert_array_equal(got[idle, IN], before[idle, IN])
    if k_pad := lanes - store.spec.pack * 2 * dim:
        assert (np.asarray(table)[:, -k_pad:] == 0).all()  # the padding lanes


def _sgns_plain_deltas(logic, batch, pulled):
    """``SkipGramNS.step``'s gradients, then the deltas assembled the plain
    way: a zeroed block, the three groups set into their slots, the whole
    block times the combiner's scale."""
    from flink_parameter_server_tpu.models.word2vec import OUT
    from flink_parameter_server_tpu.ops.dedup import occurrence_scale

    lr = logic.learning_rate
    v, u_pos, u_neg = pulled[:, 0, IN], pulled[:, 1, OUT], pulled[:, 2:, OUT]
    g_pos = jax.nn.sigmoid(jnp.sum(v * u_pos, axis=-1)) - 1.0
    g_neg = jax.nn.sigmoid(jnp.einsum("bd,bnd->bn", v, u_neg))
    d_v = g_pos[:, None] * u_pos + jnp.einsum("bn,bnd->bd", g_neg, u_neg)
    d_upos = g_pos[:, None] * v
    d_uneg = g_neg[..., None] * v[:, None, :]
    deltas = jnp.zeros(pulled.shape, v.dtype)
    deltas = deltas.at[:, 0, IN].set(-lr * d_v)
    deltas = deltas.at[:, 1, OUT].set(-lr * d_upos)
    deltas = deltas.at[:, 2:, OUT].set(-lr * d_uneg)
    if logic.dedup_scale:
        mask = batch.get("mask")
        keys = logic.keys(batch)
        if mask is not None:
            mask = jnp.broadcast_to(mask[:, None], keys.shape)
        deltas = deltas * occurrence_scale(keys, logic.vocab_size, mask)[..., None, None]
    return deltas


@pytest.mark.parametrize("dim", [300, 8])
@pytest.mark.parametrize("with_mask", [True, False])
@pytest.mark.parametrize("dedup_scale", [True, False])
def test_sgns_step_assembles_the_plain_deltas_bit_for_bit(
        dedup_scale, with_mask, dim):
    """The deltas a step pushes are the plain assembly's to the bit, the
    signs of the zeros in the slot a key leaves included: duplicate keys in
    the batch (so the mean combiner's scale is not 1), a masked pair, a
    gradient that is -0.0 (a centre whose pulled output rows are +0.0)."""
    from flink_parameter_server_tpu.models.word2vec import OUT, SkipGramNS

    vocab, batch, negs = 64, 96, 5
    rng = np.random.default_rng(dim + 2 * with_mask + dedup_scale)
    ids = (rng.zipf(1.3, (batch, negs + 2)) % vocab).astype(np.int32)
    b = {"center": ids[:, 0], "context": ids[:, 1], "negatives": ids[:, 2:]}
    if with_mask:
        b["mask"] = np.arange(batch) != 7
    pulled = rng.normal(0.0, 0.3, (batch, negs + 2, 2, dim)).astype(np.float32)
    pulled[3, 1:] = 0.0  # d_v of pair 3 is (-0.5) * 0 + 0.5 * 0: -lr * it is -0.0
    b = {k: jnp.asarray(x) for k, x in b.items()}
    logic = SkipGramNS(0.025, dedup_scale=dedup_scale, vocab_size=vocab)
    _, req, _ = jax.jit(lambda b, p: logic.step((), b, p))(b, pulled)
    want = jax.jit(lambda b, p: _sgns_plain_deltas(logic, b, p))(b, pulled)
    assert req.deltas.shape == (batch, negs + 2, 2, dim)
    assert req.deltas.dtype == jnp.float32 and req.ids.shape == (batch, negs + 2)
    assert (req.mask is None) == (not with_mask)
    got, want = np.asarray(req.deltas), np.asarray(want)
    assert np.signbit(got[3, 0, IN]).all() and not np.signbit(got[3, 0, OUT]).any()
    if dedup_scale:  # the scale did scale: a word named twice and more
        assert len(np.unique(ids)) < ids.size
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
    # and called eagerly, op by op
    _, req, _ = logic.step((), b, jnp.asarray(pulled))
    np.testing.assert_array_equal(
        np.asarray(req.deltas).view(np.uint32),
        np.asarray(_sgns_plain_deltas(logic, b, jnp.asarray(pulled))).view(np.uint32),
    )


def test_sgns_step_scales_each_live_lane_by_the_bincount_of_its_word():
    """The mean combiner's scale is ``1 / np.bincount`` of the batch's
    unmasked keys, to the bit, on every lane the push keeps: a word the
    batch names hundreds of times (as a negative, a context and a centre),
    words named once, and a masked pair that names the hot word too and
    counts nothing (``ops/dedup.occurrence_counts`` counts from a sort of
    the keys; the integers are ``np.bincount``'s, so ``1 / count`` and the
    scaled deltas are the same float32)."""
    from flink_parameter_server_tpu.models.word2vec import SkipGramNS

    vocab, batch, negs, dim, hot = 4096, 512, 5, 24, 17
    rng = np.random.default_rng(39)
    ids = rng.integers(0, vocab, (batch, negs + 2)).astype(np.int32)
    ids[rng.random(ids.shape) < 0.2] = hot
    ids[7] = hot  # the masked pair names it seven times more
    mask = np.arange(batch) != 7
    counts = np.bincount(ids[mask].ravel(), minlength=vocab)
    assert counts[hot] > 500 and (counts == 1).sum() > 100
    pulled = rng.normal(0.0, 0.3, (batch, negs + 2, 2, dim)).astype(np.float32)
    b = {"center": ids[:, 0], "context": ids[:, 1], "negatives": ids[:, 2:],
         "mask": mask}
    b = {k: jnp.asarray(x) for k, x in b.items()}

    def pushed(dedup_scale):
        logic = SkipGramNS(0.025, dedup_scale=dedup_scale, vocab_size=vocab)
        _, req, _ = jax.jit(lambda b, p: logic.step((), b, p))(b, pulled)
        np.testing.assert_array_equal(np.asarray(req.ids), ids)
        return np.asarray(req.deltas)

    scale = np.float32(1.0) / counts[ids].astype(np.float32)
    want = pushed(False) * scale[..., None, None]
    got = pushed(True)
    assert (want[mask] != 0).any(axis=(1, 2, 3)).all()
    np.testing.assert_array_equal(
        got[mask].view(np.uint32), want[mask].view(np.uint32)
    )


def test_make_store_takes_a_dtype_and_a_traced_seed():
    from flink_parameter_server_tpu.models.word2vec import OUT, make_store

    half = make_store(64, 8, seed=1, dtype=jnp.bfloat16)
    assert half.table.dtype == jnp.bfloat16 and half.spec.value_shape == (2, 8)
    build = jax.jit(lambda seed: make_store(64, 8, seed=seed))
    one, other = build(np.uint32(1)), build(np.uint32(2))  # one program
    assert build._cache_size() == 1
    np.testing.assert_array_equal(
        np.asarray(one.values()), np.asarray(make_store(64, 8, seed=1).values())
    )
    assert not np.array_equal(np.asarray(one.values()), np.asarray(other.values()))
    assert (np.asarray(one.values())[:, OUT] == 0).all()
