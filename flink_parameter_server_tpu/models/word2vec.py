"""Word2vec skip-gram with negative sampling (SGNS) on the PS.

Reference parity: BASELINE.json config #3 — "word2vec skip-gram w/
negative sampling (async sparse push)".  The classic PS formulation keeps
*both* embedding matrices on the server, keyed by word id; workers stream
(center, context) pairs, pull the touched rows, compute the SGNS gradient
and push sparse deltas (the reference's async-sparse-push pattern,
SURVEY.md §2 "Asynchrony").

TPU-first: one store row per word holds ``(2, dim)`` — slot 0 the input
("in") embedding, slot 1 the output ("out") embedding — so one sharded
gather fetches everything a pair needs.  A microbatch of B pairs with N
negatives pulls ``(B, N+2)`` rows, computes the loss/gradients as fused
batched matvecs, and pushes one ``(B, N+2, 2, dim)`` scatter-add (zeros in
the untouched slot: the step selects each key's one live slot against
zeros as it writes the row, it fills no zeroed block).  Negative sampling
happens host-side in the data stream (unigram^0.75), or on-device via
``sample_negatives``.

How the rows lie on the chip is the store's to decide
(``core/store._resolve_layout``, which ``make_store`` asks by default): a
``(2, dim)`` row is held flat and zero-padded to whole 128-lane registers,
one logical row to a physical row (``(2, 300)``: 640 lanes), because a
table left as ``(vocab, 2, dim)`` is copied whole twice a step on the TPU.
The logic sees ``(2, dim)`` rows either way.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.batched import BatchedWorkerLogic, PushRequest
from ..core.store import ShardedParamStore
from ..core.transform import transform_batched
from ..training.tracing import scope
from ..utils.initializers import ranged_random_factor

Array = jax.Array

IN, OUT = 0, 1  # slots in the (2, dim) store row


def sgns_gradients(v: Array, u_pos: Array, u_neg: Array):
    """The SGNS arithmetic of a microbatch, shared by every logic whose
    pairs meet one hidden vector with one positive and ``N`` negative
    output vectors (``SkipGramNS``: the centre's input vector;
    ``models/fasttext.FastTextSkipGram``: the average of a bag's).

    ``v`` (B, d), ``u_pos`` (B, d), ``u_neg`` (B, N, d) ->
    ``(pos_logit, neg_logit, d_v, d_upos, d_uneg)``: the logits and the
    loss's gradients by ``v`` and by each output vector (maximise
    ``log sigmoid(pos) + sum log sigmoid(-neg)``)."""
    pos_logit = jnp.sum(v * u_pos, axis=-1)  # (B,)
    neg_logit = jnp.einsum("bd,bnd->bn", v, u_neg)  # (B, N)
    # SGNS: maximize log σ(pos) + Σ log σ(-neg)
    g_pos = jax.nn.sigmoid(pos_logit) - 1.0  # dL/d(pos_logit)
    g_neg = jax.nn.sigmoid(neg_logit)  # dL/d(neg_logit)

    d_v = g_pos[:, None] * u_pos + jnp.einsum("bn,bnd->bd", g_neg, u_neg)
    d_upos = g_pos[:, None] * v
    d_uneg = g_neg[..., None] * v[:, None, :]  # (B, N, d)
    return pos_logit, neg_logit, d_v, d_upos, d_uneg


def sgns_loss(pos_logit: Array, neg_logit: Array, mask=None) -> Array:
    """Per-pair SGNS loss (B,), zero where ``mask`` is false."""
    loss = -(
        jax.nn.log_sigmoid(pos_logit)
        + jnp.sum(jax.nn.log_sigmoid(-neg_logit), axis=-1)
    )
    if mask is not None:
        loss = loss * mask
    return loss


class SkipGramNS(BatchedWorkerLogic):
    """Batch: ``center`` (B,), ``context`` (B,), ``negatives`` (B, N),
    ``mask`` (B,) — produces per-pair SGNS loss and sparse pushes.

    ``dedup_scale`` (requires ``vocab_size``): scale each lane's delta by
    1/count(id-in-batch) so Zipf-hot words take one *averaged* step per
    microbatch instead of count× summed steps — keeps high learning rates
    stable under skew (see :mod:`..ops.dedup`)."""

    def __init__(
        self,
        learning_rate: float = 0.025,
        *,
        dedup_scale: bool = False,
        vocab_size: Optional[int] = None,
    ):
        self.learning_rate = learning_rate
        self.dedup_scale = dedup_scale
        self.vocab_size = vocab_size
        if dedup_scale and vocab_size is None:
            raise ValueError("dedup_scale=True requires vocab_size")

    def init_state(self, rng: Array):
        return ()  # the whole model lives on the PS

    def keys(self, batch: Dict[str, Array]) -> Array:
        return jnp.concatenate(
            [
                batch["center"][:, None],
                batch["context"][:, None],
                batch["negatives"],
            ],
            axis=1,
        )  # (B, N+2)

    def step(self, state, batch: Dict[str, Array], pulled: Array):
        # pulled: (B, N+2, 2, dim)
        lr = self.learning_rate
        v = pulled[:, 0, IN]  # (B, d) center input embedding
        u_pos = pulled[:, 1, OUT]  # (B, d) context output embedding
        u_neg = pulled[:, 2:, OUT]  # (B, N, d)
        pos_logit, neg_logit, d_v, d_upos, d_uneg = sgns_gradients(
            v, u_pos, u_neg
        )

        B, d = v.shape
        N = u_neg.shape[1]
        mask = batch.get("mask")
        lane_mask = None
        if mask is not None:
            lane_mask = jnp.broadcast_to(mask[:, None], (B, N + 2))

        with scope("ps.delta_build"):
            # one (2, d) delta a pulled row, written once.  A key has one
            # live slot (IN the centre's, OUT every other's), so the
            # gradients lie side by side in one compact (B, N+2, d) array,
            # take the combiner's scale there, and only then meet their
            # zeros.  (A zeroed block with the gradients set into it is
            # the same values in four strided passes on the TPU.)
            grads = jnp.concatenate(
                [(-lr * d_v)[:, None], (-lr * d_upos)[:, None], -lr * d_uneg],
                axis=1,
            )
            if self.dedup_scale:
                from ..ops.dedup import occurrence_scale

                keys = self.keys(batch)
                scale = occurrence_scale(keys, self.vocab_size, lane_mask)
                grads = grads * scale[..., None]
            is_centre = (jnp.arange(N + 2) == 0)[None, :, None]
            zeros = jnp.zeros_like(grads)
            deltas = jnp.concatenate(
                [
                    jnp.where(is_centre, grads, zeros),  # IN
                    jnp.where(is_centre, zeros, grads),  # OUT
                ],
                axis=-1,
            ).reshape(B, N + 2, 2, d)

        out = {"loss": sgns_loss(pos_logit, neg_logit, mask)}
        return state, PushRequest(self.keys(batch), deltas, lane_mask), out


def make_store(
    vocab_size: int,
    dim: int,
    *,
    seed: int = 0,
    mesh=None,
    init_scale: float = 0.5,
    dtype=jnp.float32,
    layout: str = "auto",
) -> ShardedParamStore:
    """(vocab, 2, dim) store; input slot random-uniform (the word2vec
    convention: U(-0.5/dim, 0.5/dim)), output slot zero.  ``seed`` may be
    traced (``jax.jit(lambda seed: make_store(..., seed=seed))``: one
    program whatever the seed).  ``layout="auto"`` leaves the rows' place
    on the chip to ``core/store._resolve_layout``."""
    base = ranged_random_factor(
        seed, (dim,), low=-init_scale / dim, high=init_scale / dim,
        dtype=dtype,
    )

    def init(ids: Array) -> Array:
        in_emb = base(ids)
        return jnp.stack([in_emb, jnp.zeros_like(in_emb)], axis=1)

    return ShardedParamStore.create(
        vocab_size, (2, dim), dtype=dtype, init_fn=init, mesh=mesh,
        layout=layout,
    )


def sample_negatives(
    rng: Array, probs_cdf: Array, shape: Tuple[int, ...]
) -> Array:
    """Device-side unigram^0.75 sampling by inverse-CDF binary search —
    branch-free and jit-friendly."""
    u = jax.random.uniform(rng, shape)
    return jnp.searchsorted(probs_cdf, u).astype(jnp.int32)


def train_skipgram(
    pairs,
    *,
    vocab_size: int,
    dim: int = 64,
    learning_rate: float = 0.025,
    dedup_scale: bool = False,
    seed: int = 0,
    mesh=None,
    **kwargs,
):
    """End-to-end SGNS training over an iterable of pair microbatches.
    ``result.store.values()`` is the (vocab, 2, dim) embedding table."""
    logic = SkipGramNS(
        learning_rate, dedup_scale=dedup_scale, vocab_size=vocab_size
    )
    store = make_store(vocab_size, dim, seed=seed, mesh=mesh)
    # the store built here has no other owner: the loop takes it, no copy
    kwargs.setdefault("owns_inputs", True)
    return transform_batched(
        pairs, logic, store, rng=jax.random.PRNGKey(seed), mesh=mesh, **kwargs
    )


__all__ = [
    "SkipGramNS", "make_store", "sample_negatives", "sgns_gradients",
    "sgns_loss", "train_skipgram", "IN", "OUT",
]
