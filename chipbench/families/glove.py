"""GloVe with AdaGrad as the server's rule: ``models/glove.GloVe`` +
``make_store`` with that function's default layout (a 602-lane rule row flat
in five registers), and the record stream: nonzeros ``(i, j, X)`` of a
co-occurrence matrix drawn from a closed-form law (:func:`law`)."""
from __future__ import annotations

from typing import Dict, List

import numpy as np

STEP_PROGRAM = "jit_step"
# ranks below this are a bin each; above it bins grow by BIN_RATIO
SINGLE_RANKS = 128
BIN_RATIO = 1.025


def build(cfg: dict, seed: int, mesh):
    """The store is ``make_store``'s own, built on the device in one jitted
    call that takes the seed as an ARGUMENT (a seed baked into the program
    would compile the init again for every ``--seed``: ``families/fm.py``)
    and initialised IN PLACE, chunk by chunk (``core/store.create_table``):
    at 11.24 GB no second copy of the table fits beside it."""
    import jax
    import jax.numpy as jnp

    from flink_parameter_server_tpu.models import glove as gl

    model = gl.GloVeConfig(
        int(cfg["vocab_size"]), int(cfg["dim"]), float(cfg["x_max"]),
        float(cfg["alpha"]),
    )
    rule = gl.GloVeAdaGrad(float(cfg["eta"]))
    store = jax.jit(lambda s: gl.make_store(
        model, rule, seed=s, mesh=mesh, dtype=jnp.dtype(cfg["dtype"])
    ))(np.uint32(seed % 2**32))
    return gl.GloVe(model), store


def unigram(cfg: dict, keys: dict) -> np.ndarray:
    """``p`` over word ids ``0..V-1`` (id = frequency rank - 1), float64:
    ``r^-a`` normalised over the vocabulary (``{"kind": "zipf", "a": a}``)
    or ``1 / V`` (``uniform``)."""
    size = int(cfg["vocab_size"])
    if keys["kind"] == "zipf":
        p = np.arange(1, size + 1, dtype=np.float64) ** -float(keys["a"])
    elif keys["kind"] == "uniform":
        p = np.ones(size, np.float64)
    else:
        raise ValueError(f"unknown key distribution {keys['kind']!r}")
    return p / p.sum()


def pair_mass(cfg: dict) -> float:
    """``N``: a corpus of ``C`` tokens under a symmetric window of ``w``
    weighted ``1 / d`` (the paper's section 4.2) gives the pair ``(i, j)`` an
    expected count ``N p_i p_j``, ``N = 2 C sum_{d=1..w} 1/d``."""
    return 2.0 * float(cfg["corpus_tokens"]) * sum(
        1.0 / d for d in range(1, int(cfg["window"]) + 1)
    )


def law(cfg: dict, keys: dict) -> dict:
    """The law of a record, closed-form.  A pair is a NONZERO of the matrix
    with probability ``1 - exp(-N p_i p_j)`` and a record is drawn uniformly
    from the nonzeros (``glove.c`` reads a shuffled file of them, each once
    an epoch), with ``X = max(1, N p_i p_j)``.  The probability is taken
    constant over rank BINS (a bin a rank up to ``SINGLE_RANKS``, then
    growing by ``BIN_RATIO``: ``p`` varies by 3.3 % inside one), at the
    bin's geometric-mean ``p``: ``weight[a, b] = n_a n_b (1 - exp(-N p_a
    p_b))`` is the expected nonzeros of the cell, a record picks its cell
    by that and its two ranks uniformly inside it.  Returns the bins'
    ``first`` ranks and ``size``, the cells' ``cdf`` (row-major), the
    expected ``nonzeros``, each word's ``marginal`` share of the records (by
    bin: the share of ONE word of the bin) and ``p``, ``N``."""
    p, big_n = unigram(cfg, keys), pair_mass(cfg)
    size = p.shape[0]
    edges = [float(r) for r in range(min(SINGLE_RANKS, size))]
    while edges[-1] * BIN_RATIO + 1 < size:
        edges.append(max(edges[-1] + 1, np.floor(edges[-1] * BIN_RATIO + 1)))
    first = np.asarray(edges, np.int64)
    count = np.diff(np.append(first, size))
    logp = np.log(p)
    mean_p = np.exp(np.add.reduceat(logp, first) / count)
    weight = np.outer(count, count) * -np.expm1(
        -big_n * np.outer(mean_p, mean_p))
    total = float(weight.sum())
    return {
        "first": first, "size": count, "cdf": np.cumsum(weight.ravel()) / total,
        "nonzeros": total, "marginal": weight.sum(axis=1) / count / total,
        "p": p, "N": big_n,
    }


def law_numbers(cfg: dict, keys: dict) -> Dict[str, float]:
    """What the traffic file's ``keys_source`` states of the law, computed:
    the nonzeros, the hottest word's share of the records on its side, the
    shares of the top 1,000 and 100,000 words, the distinct rows a batch
    touches on one side and the hottest row's expected lanes a batch."""
    found = law(cfg, keys)
    share = np.repeat(found["marginal"], found["size"])  # per word id
    batch = int(cfg["batch"])
    return {
        "nonzeros": found["nonzeros"],
        "hottest_share": float(share[0]),
        "top_1000_share": float(share[:1000].sum()),
        "top_100000_share": float(share[:100000].sum()),
        "distinct_a_side": float(-np.expm1(batch * np.log1p(-share)).sum()),
        "hottest_lanes_a_batch": float(batch * share[0]),
    }


def host_batches(cfg: dict, traffic: dict, seed: int, n: int) -> List[Dict]:
    """``n`` full microbatches of ``(word, context, count)``, by inverse CDF
    over the law's cells; batch ``i`` draws from its own generator, so the
    stream is a function of the seed alone."""
    found = law(cfg, traffic["keys"])
    first, size, p = found["first"], found["size"], found["p"]
    bins, batch = first.shape[0], int(cfg["batch"])
    mask = np.ones(batch, bool)
    out = []
    for i in range(n):
        rng = np.random.default_rng([seed, i + 1])
        # rounding may leave cdf[-1] a hair under 1: keep the cell in range
        cell = np.minimum(
            np.searchsorted(found["cdf"], rng.random(batch)), bins * bins - 1)
        a, b = cell // bins, cell % bins
        word = first[a] + (rng.random(batch) * size[a]).astype(np.int64)
        context = first[b] + (rng.random(batch) * size[b]).astype(np.int64)
        out.append({
            "word": word.astype(np.int32), "context": context.astype(np.int32),
            "count": np.maximum(
                1.0, found["N"] * p[word] * p[context]).astype(np.float32),
            "mask": mask,
        })
    return out


def rows(store, state, ids: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """The touched rows as float32 numpy through the store's own pull: the
    words' rows, and the context words' rows ``vocab_size`` further on."""
    import jax.numpy as jnp

    vocab = store.spec.capacity // 2
    return {
        name: np.asarray(store.pull(jnp.asarray(ids[name] + first)), np.float32)
        for name, first in (("word", 0), ("context", vocab))
    }


def distinct_rows_per_step(cfg: dict, keys: dict) -> float:
    """Expected distinct rows a batch touches, both sides, in closed form
    from the law: of a word whose share of the records is ``m``, ``1 - (1 -
    m)^batch``."""
    return 2.0 * law_numbers(cfg, keys)["distinct_a_side"]


def _traffic_keys(cfg: dict) -> dict:
    """The key law of the configuration's one traffic mix (a byte count is
    asked with the configuration alone)."""
    from chipbench import spec

    return spec.load_json(
        f"{spec.BENCH_DIR}/traffic/{cfg['traffic']}.json")["keys"]


def rule_path_bytes_per_step(cfg: dict) -> float:
    """What the SERVER side of a step (``ps.combine`` + ``ps.rule`` +
    ``ps.push``) must move, whatever implements it: the batch's gradients
    read once at the ``dim + 1`` lanes a key that can be other than zero
    (the accumulators' lanes carry nothing), and every DISTINCT row the
    batch touches read once and written once at its whole ``2 (dim + 1)``
    lanes (the rule runs once a row).  No id, no sort, no padding of a lane
    or a tile: a lower bound, so its share of the roofline cannot pass
    100 %, and a later kernel is held to the same work."""
    el = np.dtype(cfg["dtype"]).itemsize
    live = int(cfg["dim"]) + 1
    return el * (
        2 * cfg["batch"] * live
        + 2 * 2 * live * distinct_rows_per_step(cfg, _traffic_keys(cfg))
    )


def hbm_bytes_per_step(cfg: dict) -> float:
    """What the whole step MUST move: for the pull the ``dim + 1`` lanes a
    key that a worker reads (the vector and its bias; it needs no
    accumulator), and the server side's bytes
    (:func:`rule_path_bytes_per_step`).  Padding to five registers and a
    row's duplicates are waste, not need."""
    el = np.dtype(cfg["dtype"]).itemsize
    return (
        el * 2 * cfg["batch"] * (int(cfg["dim"]) + 1)
        + rule_path_bytes_per_step(cfg)
    )
