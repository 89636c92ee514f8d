"""fastText skip-gram with subword bags: ``FastTextSkipGram`` (the program's
mean combiner, as cell 5's ``SkipGramNS(dedup_scale=True)``) +
``models/fasttext.make_store`` (words, n-gram buckets and output vectors in
ONE store of ``(dim,)`` rows, the layout that function's default), and the
pair stream of ``families/w2v.py`` with the centre replaced by its BAG: the
word's own row and the rows of its hashed character n-grams, -1 in the lanes
a short bag leaves dead.  The workers hold the dictionary, as fastText's
do: the vocabulary's spellings are made here, from the seed, and hashed
here (``fnv1a``, ``subword_bags``: fastText's ``Dictionary::hash`` and
``computeSubwords``); the program is handed store rows and never hashes."""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from chipbench.families import w2v

STEP_PROGRAM = "jit_step"
# cell 5's three laws, with this configuration's power and threshold
laws = w2v.laws


FNV_OFFSET, FNV_PRIME = 2166136261, 16777619


def fnv1a(chars: np.ndarray) -> np.ndarray:
    """32-bit FNV-1a of each row of ``chars`` (n, size) byte codes, (n,)
    uint32 (fasttext's ``Dictionary::hash`` on ASCII: ``""`` -> 2166136261,
    ``"a"`` -> 0xE40C292C, ``"foobar"`` -> 0xBF9CF968): one pass of uint32
    arithmetic a byte, over all rows at once."""
    h = np.full(len(chars), FNV_OFFSET, np.uint32)
    for at in range(chars.shape[1]):
        h = (h ^ chars[:, at].astype(np.uint32)) * np.uint32(FNV_PRIME)
    return h


def max_bag(max_word_len: int, minn: int, maxn: int) -> int:
    """Lanes of a bag: the word and every n-gram of ``<`` + a word of
    ``max_word_len`` letters + ``>`` (14 letters, 3 to 6: 1 + 50)."""
    chars = max_word_len + 2
    return 1 + sum(max(0, chars - n + 1) for n in range(minn, maxn + 1))


def subword_bags(
    word_ids: np.ndarray, letters: np.ndarray, lengths: np.ndarray, *,
    vocab_size: int, buckets: int, minn: int, maxn: int,
) -> np.ndarray:
    """``(n, max_bag)`` int32 store rows of the words' bags, -1 in the dead
    lanes: lane 0 the word's own row, then row ``vocab_size + fnv1a(g) mod
    buckets`` of every n-gram ``g`` of ``<word>`` with ``minn <= |g| <=
    maxn``, by start and then by length (fasttext's ``computeSubwords``),
    duplicates kept.  ``letters`` (n, L) uint8 ASCII codes, ``lengths`` (n,)
    how many of them spell the word.  Vectorised over the words."""
    n, width = letters.shape
    lengths = np.asarray(lengths, np.int64)
    chars = np.full((n, width + 2), ord(">"), np.uint32)
    chars[:, 0] = ord("<")
    chars[:, 1:width + 1] = letters
    # past a word's letters every char is ">": its own closes the word
    chars[np.arange(width + 2)[None, :] > lengths[:, None]] = ord(">")
    ends = lengths + 2  # chars of "<word>"
    grams, live = [], []
    for start in range(width + 2):
        for size in range(max(minn, 1), maxn + 1):
            if start + size > width + 2:
                break
            h = fnv1a(chars[:, start:start + size])
            grams.append(vocab_size + (h % np.uint32(buckets)).astype(np.int64))
            live.append(start + size <= ends)
    lanes = max_bag(width, minn, maxn)
    bag = np.full((n, lanes), -1, np.int32)
    bag[:, 0] = word_ids
    if grams:
        grams, live = np.stack(grams, axis=1), np.stack(live, axis=1)
        # the live n-grams to the front of their bag, in their order
        order = np.argsort(~live, axis=1, kind="stable")
        grams = np.take_along_axis(np.where(live, grams, -1), order, axis=1)
        bag[:, 1:1 + grams.shape[1]] = grams
    return bag


def _max_bag(cfg: dict) -> int:
    return max_bag(int(cfg["max_word_len"]), int(cfg["minn"]), int(cfg["maxn"]))


def build(cfg: dict, seed: int, mesh):
    """The store is ``make_store``'s own, built on the device in one jitted
    call that takes the seed as an ARGUMENT (``families/w2v.build``'s
    pattern: a Python seed would be a constant of the program, and every new
    ``--seed`` would compile the table's init again), initialised in place
    block by block (``core/store.create_table``): at 10.81 GB no second copy
    of the table fits beside it."""
    import jax
    import jax.numpy as jnp

    from flink_parameter_server_tpu.models.fasttext import (
        FastTextSkipGram,
        make_store,
    )

    dtype = jnp.dtype(cfg["dtype"])
    store = jax.jit(lambda s: make_store(
        cfg["vocab_size"], cfg["buckets"], cfg["dim"], seed=s, mesh=mesh,
        dtype=dtype,
    ))(np.uint32(seed % 2**32))
    logic = FastTextSkipGram(
        float(cfg["learning_rate"]), int(cfg["vocab_size"]),
        int(cfg["buckets"]), _max_bag(cfg),
    )
    return logic, store


def vocabulary(cfg: dict, seed: int) -> tuple:
    """``(letters, lengths)``: word ``r``'s spelling, ``lengths[r]`` =
    ``min(1 + Poisson(word_len_poisson), max_word_len)`` ASCII letters
    ``letters[r, :lengths[r]]``, each drawn independently from the
    configuration's letter frequencies, all ``vocab_size`` words from ONE
    generator of the seed (a word's spelling does not depend on which
    words a pool happens to draw)."""
    size, width = int(cfg["vocab_size"]), int(cfg["max_word_len"])
    rng = np.random.default_rng([seed, 0])  # batch i draws from [seed, i + 1]
    lengths = np.minimum(
        1 + rng.poisson(float(cfg["word_len_poisson"]), size), width
    ).astype(np.int8)
    freq = np.array([cfg["letter_freq"][c] for c in sorted(cfg["letter_freq"])])
    codes = np.array([ord(c) for c in sorted(cfg["letter_freq"])], np.uint8)
    # inverse CDF by a table of 2^16 entries: a letter a 16-bit draw
    cdf = np.cumsum(freq / freq.sum())
    table = codes[np.minimum(
        np.searchsorted(cdf, (np.arange(2**16) + 0.5) / 2**16), len(codes) - 1
    )]
    letters = table[rng.integers(0, 2**16, (size, width), dtype=np.uint16)]
    return letters, lengths


def host_batches(cfg: dict, traffic: dict, seed: int, n: int) -> List[Dict]:
    """``n`` full microbatches of (bag, context, negatives), all ids the
    store's: ``families/w2v.host_batches``' pairs, draw for draw (centres
    and contexts independently from the ``pairs`` law, negatives from the
    ``noise`` law), with the contexts and negatives moved to the output
    block and each centre replaced by its bag, hashed once a DISTINCT centre
    of the ``n`` batches (set-up spells 2.5 M words and hashes ~0.1 M)."""
    pairs = w2v.host_batches(cfg, traffic, seed, n)
    size = int(cfg["vocab_size"])
    out_base = np.int32(size + int(cfg["buckets"]))
    words = np.unique(np.concatenate([b["center"] for b in pairs]))
    letters, lengths = vocabulary(cfg, seed)
    bags = subword_bags(
        words, letters[words], lengths[words], vocab_size=size,
        buckets=int(cfg["buckets"]), minn=int(cfg["minn"]),
        maxn=int(cfg["maxn"]),
    )
    return [
        {
            "bag": bags[np.searchsorted(words, b["center"])],
            "context": b["context"] + out_base,
            "negatives": b["negatives"] + out_base,
            "mask": b["mask"],
        }
        for b in pairs
    ]


def rows(store, state, ids: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """The touched vectors as float32 numpy through the store's own pull
    (ids are the store's: the bags' rows, the contexts' and negatives')."""
    import jax.numpy as jnp

    return {
        name: np.asarray(store.pull(jnp.asarray(ids[name])), np.float32)
        for name in ("in", "out")
    }


def hbm_bytes_per_step(cfg: dict) -> float:
    """One ``dim``-wide row read for the gather, one read and one write for
    the scatter-add, for each LIVE key of a batch (the expected number, from
    the configuration, pinned to the generator by a test): a bag's dead
    lanes, which the program pulls and pushes all the same, and the lane
    padding of the 300-lane row are waste, not need."""
    el = np.dtype(cfg["dtype"]).itemsize
    return 3.0 * cfg["batch"] * cfg["live_keys_per_pair"] * cfg["dim"] * el
