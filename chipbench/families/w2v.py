"""Skip-gram with negative sampling: ``SkipGramNS`` with the program's
mean combiner (``dedup_scale=True``: a word that a batch names ``n`` times
takes the MEAN of its ``n`` deltas, ``ops/dedup.occurrence_scale``) +
``models/word2vec.make_store`` (both matrices on the server, a word's input
and output vector in one ``(2, dim)`` store row, the layout that function's
default), and the pair stream: three key streams under one unigram law."""
from __future__ import annotations

from typing import Dict, List

import numpy as np

STEP_PROGRAM = "jit_step"


def build(cfg: dict, seed: int, mesh):
    """The store is ``make_store``'s own, built on the device in one jitted
    call that takes the seed as an ARGUMENT (input vectors U(-0.5/dim,
    0.5/dim) per word id, output vectors 0: ``make_store``'s distribution,
    word2vec.c's).  Called with a Python seed it bakes the seed into the
    program as a constant, and every new ``--seed`` would compile the
    table's init again (``families/fm.py``).  The table is initialised in
    place, block by block (``core/store.create_table``): at 7.68 GB no
    second copy of it fits beside it."""
    import jax
    import jax.numpy as jnp

    from flink_parameter_server_tpu.models.word2vec import (
        SkipGramNS,
        make_store,
    )

    dtype = jnp.dtype(cfg["dtype"])
    store = jax.jit(lambda s: make_store(
        cfg["vocab_size"], cfg["dim"], seed=s, mesh=mesh, dtype=dtype
    ))(np.uint32(seed % 2**32))
    logic = SkipGramNS(
        float(cfg["learning_rate"]), dedup_scale=True,
        vocab_size=int(cfg["vocab_size"]),
    )
    return logic, store


def laws(cfg: dict, keys: dict) -> Dict[str, np.ndarray]:
    """The three laws over word ids ``0..V-1`` (id = frequency rank - 1),
    float64, each summing to 1.  ``unigram``: ``f(r) = r^-a`` normalised
    over the vocabulary (``{"kind": "zipf", "a": a}``) or ``1 / V``
    (``uniform``).  ``pairs``: a corpus position survives subsampling with
    probability ``min(1, sqrt(t / f) + t / f)`` (word2vec.c's keep rule for
    Mikolov et al.'s threshold ``t``), so centres and contexts follow ``f x
    keep``, renormalised.  ``noise``: ``f^noise_power``, renormalised."""
    size = int(cfg["vocab_size"])
    if keys["kind"] == "zipf":
        f = np.arange(1, size + 1, dtype=np.float64) ** -float(keys["a"])
    elif keys["kind"] == "uniform":
        f = np.ones(size, np.float64)
    else:
        raise ValueError(f"unknown key distribution {keys['kind']!r}")
    f /= f.sum()
    t = float(cfg["subsample_t"])
    pairs = f * np.minimum(1.0, np.sqrt(t / f) + t / f)
    noise = f ** float(cfg["noise_power"])
    return {
        "unigram": f, "pairs": pairs / pairs.sum(), "noise": noise / noise.sum()
    }


def host_batches(cfg: dict, traffic: dict, seed: int, n: int) -> List[Dict]:
    """``n`` full microbatches of (centre, context, negatives): centres and
    contexts drawn independently from the ``pairs`` law, ``negatives`` from
    the ``noise`` law, all by inverse CDF; batch ``i`` draws from its own
    generator, so the stream is a function of the seed alone."""
    law = laws(cfg, traffic["keys"])
    size, batch, k = int(cfg["vocab_size"]), int(cfg["batch"]), int(cfg["negatives"])
    pairs_cdf, noise_cdf = np.cumsum(law["pairs"]), np.cumsum(law["noise"])

    def draw(rng, cdf, shape):
        # rounding may leave cdf[-1] a hair under 1: keep the id in range
        return np.minimum(
            np.searchsorted(cdf, rng.random(shape)), size - 1
        ).astype(np.int32)

    mask = np.ones(batch, bool)
    out = []
    for i in range(n):
        rng = np.random.default_rng([seed, i + 1])
        out.append({
            "center": draw(rng, pairs_cdf, batch),
            "context": draw(rng, pairs_cdf, batch),
            "negatives": draw(rng, noise_cdf, (batch, k)),
            "mask": mask,
        })
    return out


def rows(store, state, ids: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """The touched vectors as float32 numpy through the store's own pull:
    the input slot of the centres' rows, the output slot of the contexts'
    and negatives'."""
    import jax.numpy as jnp

    from flink_parameter_server_tpu.models.word2vec import IN, OUT

    return {
        name: np.asarray(store.pull(jnp.asarray(ids[name]))[:, slot], np.float32)
        for name, slot in (("in", IN), ("out", OUT))
    }


def hbm_bytes_per_step(cfg: dict) -> float:
    """One ``dim``-wide slot read for the gather, one read and one write
    for the scatter-add, for each of a pair's ``negatives + 2`` keys: the
    slot the pair uses.  The row's other slot, which the program pulls
    unread and pushes as zeros, and the lane padding of the 600-lane row are
    waste, not need."""
    el = np.dtype(cfg["dtype"]).itemsize
    return 3.0 * cfg["batch"] * (cfg["negatives"] + 2) * cfg["dim"] * el
