"""fastText skip-gram: negative sampling over BAGS of subword rows, on the PS.

Bojanowski, Grave, Joulin, Mikolov, "Enriching Word Vectors with Subword
Information" (TACL 2017): a word's input vector is the AVERAGE of its own
vector and the vectors of its character n-grams (3 to 6 characters of
``<word>``), the n-grams hashed by FNV-1a into a fixed number of buckets.
It is :mod:`.word2vec`'s job with the one thing word2vec does not have: a
key bag of VARIABLE length per example, rows shared between examples that
share no word, and one gradient that an example sends to every row of its
bag alike.

Key spaces, in ONE store of ``(dim,)`` rows, each a contiguous block (as
FM's fields own theirs): word ``w`` -> row ``w`` (``0 <= w < V``), n-gram
bucket ``b`` -> row ``V + b`` (``0 <= b < K``), output vector of word ``w``
-> row ``V + K + w``.  For centre ``c`` with bag ``G(c)`` of ``m`` rows
(the word's own row first, duplicates kept), context ``o`` and negatives
``n_1..n_N``::

    h       = (1 / m) sum_{q in G(c)} z_q         (fasttext's computeHidden)
    s_j     = sigmoid(<h, u_{t_j}>),  t_0 = o (y = 1), t_j = n_j (y = 0)
    dz_q    = -lr sum_j (s_j - y_j) u_{t_j}       the SAME vector for every
                                                  q in G(c), NOT divided by m
    du_{t_j} = -lr (s_j - y_j) h

The stream carries the bag, as PA's stream carries its ``ids`` and
``feat_mask``: fastText's workers hold the dictionary, the server does not.
``bag`` is ``(B, max_bag)`` int32 STORE rows (the word's id, then ``V +
fnv1a(g) mod K`` for each n-gram, as fasttext's ``Dictionary::getSubwords``
gives them: hashing is the stream's side, and the benchmark's generator
does it in ``chipbench/families/ft.subword_bags``) with **-1 in the dead
lanes**;
``context`` and ``negatives`` are store rows of the output block
(:meth:`FastTextSkipGram.output_rows`).

A dead lane (a bag shorter than ``max_bag``, every lane of a masked pair)
carries id -1 into the push, which drops it (``core/store.push``); its
pulled row (``pull`` clips the id) is masked out of the average, and the
mean combiner's counts (``ops/dedup.occurrence_scale``: a row that the
batch's LIVE lanes name ``n`` times, as a word, a bucket, a context or a
negative, takes the mean of its ``n`` deltas) skip it: they are the run
lengths of the batch's keys sorted, one key space for all three blocks,
the dead lanes keyed past every row (no counter a row of the store).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from ..core.batched import BatchedWorkerLogic, PushRequest
from ..core.store import ShardedParamStore
from ..ops.dedup import occurrence_scale
from ..training.tracing import scope
from ..utils.initializers import ranged_random_factor
from .word2vec import sgns_gradients, sgns_loss

Array = jax.Array

class FastTextSkipGram(BatchedWorkerLogic):
    """Batch: ``bag`` (B, max_bag) int32 store rows, -1 in dead lanes,
    lane 0 the word itself; ``context`` (B,) and ``negatives`` (B, N) store
    rows of the output block; ``mask`` (B,).  Produces the per-pair SGNS
    loss, the sparse pushes of the module docstring under the MEAN combiner,
    and two counts of its lanes (``bag_live_keys`` / ``bag_padded_keys``:
    the live lanes of the step's keys and all of them)."""

    def __init__(
        self, learning_rate: float, vocab_size: int, buckets: int,
        max_bag: int,
    ):
        self.learning_rate = learning_rate
        self.vocab_size = int(vocab_size)
        self.buckets = int(buckets)
        self.max_bag = int(max_bag)

    @property
    def capacity(self) -> int:
        """Rows of the store: words, buckets, output vectors."""
        return 2 * self.vocab_size + self.buckets

    def output_rows(self, words):
        """Store rows of the words' output vectors."""
        return words + (self.vocab_size + self.buckets)

    def init_state(self, rng: Array):
        return ()  # the whole model lives on the PS

    def keys(self, batch: Dict[str, Array]) -> Array:
        return jnp.concatenate(
            [batch["bag"], batch["context"][:, None], batch["negatives"]],
            axis=1,
        )  # (B, max_bag + 1 + N)

    def step(self, state, batch: Dict[str, Array], pulled: Array):
        # pulled: (B, max_bag + 1 + N, dim)
        lr = self.learning_rate
        G = self.max_bag
        # the lanes that name a row (a bag's dead lanes carry -1) in a pair
        # that counts; every other lane is dead, whatever id it holds
        keys = self.keys(batch)
        live = keys >= 0
        if batch.get("mask") is not None:
            live = live & batch["mask"][:, None]
        u_pos = pulled[:, G]  # (B, d)
        u_neg = pulled[:, G + 1:]  # (B, N, d)
        with scope("ps.bag_pool"):
            # the masked average: a dead lane's clipped pull is not read
            in_bag = live[:, :G]
            m = jnp.maximum(jnp.sum(in_bag, axis=1, dtype=jnp.int32), 1)
            h = jnp.sum(
                jnp.where(in_bag[..., None], pulled[:, :G], 0.0), axis=1
            ) * (1.0 / m.astype(pulled.dtype))[:, None]

        pos_logit, neg_logit, d_h, d_upos, d_uneg = sgns_gradients(
            h, u_pos, u_neg
        )

        with scope("ps.delta_build"):
            # one (d,) delta a pulled row: the bag's one gradient on every
            # lane of the bag, whole (skip-gram: normalizeGradient is
            # false), then the context's and the negatives'.  The mean
            # combiner's scale is 0 in a dead lane, so the lane carries a
            # zero beside its id of -1.
            B, d = h.shape
            grads = jnp.concatenate(
                [
                    jnp.broadcast_to((-lr * d_h)[:, None], (B, G, d)),
                    (-lr * d_upos)[:, None],
                    -lr * d_uneg,
                ],
                axis=1,
            )
            keys = jnp.where(live, keys, -1)
            # (the counts drop an id of -1 by themselves)
            scale = jnp.where(
                live, occurrence_scale(keys, self.capacity), 0.0
            )
            deltas = grads * scale[..., None]

        out = {
            "loss": sgns_loss(pos_logit, neg_logit, batch.get("mask")),
            "bag_live_keys": jnp.sum(live, dtype=jnp.int32),
            "bag_padded_keys": jnp.full((), live.size, jnp.int32),
        }
        return state, PushRequest(keys, deltas, live), out

    def publish_counts(self, outs, registry, total, peak) -> None:
        # the live lanes of the dispatch's keys and all of them
        registry.gauge("bag_live_keys", component="train").set(
            total(outs["bag_live_keys"]))
        registry.gauge("bag_padded_keys", component="train").set(
            total(outs["bag_padded_keys"]))


def make_store(
    vocab_size: int,
    buckets: int,
    dim: int,
    *,
    seed: int = 0,
    mesh=None,
    dtype=jnp.float32,
) -> ShardedParamStore:
    """ONE store of ``2 vocab_size + buckets`` rows of ``(dim,)``: the
    words' and the buckets' input vectors U(-1/dim, 1/dim) (fasttext's
    ``wi_->uniform(1.0 / dim)``), the output block 0.  ``seed`` may be
    traced (``jax.jit(lambda seed: make_store(..., seed=seed))``: one
    program whatever the seed).  The rows' place on the chip is
    ``core/store._resolve_layout("auto")``'s to choose (300 lanes: flat in
    384, three registers a physical row)."""
    base = ranged_random_factor(
        seed, (dim,), low=-1.0 / dim, high=1.0 / dim, dtype=dtype
    )
    inputs = vocab_size + buckets

    def init(ids: Array) -> Array:
        rows = base(ids)
        return jnp.where((ids < inputs)[:, None], rows, jnp.zeros_like(rows))

    return ShardedParamStore.create(
        inputs + vocab_size, (dim,), dtype=dtype, init_fn=init, mesh=mesh,
        layout="auto",
    )


__all__ = ["FastTextSkipGram", "make_store"]
