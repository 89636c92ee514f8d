"""Word2vec skip-gram with negative sampling on the PS.

BASELINE.json config #3: both embedding matrices live on the sharded
store; workers stream pairs and push sparse deltas.  The dedup combiner
keeps high learning rates stable on Zipf-hot vocabularies.
"""
import numpy as np

from flink_parameter_server_tpu.data.text import (
    skipgram_batches,
    synthetic_corpus,
)
from flink_parameter_server_tpu.models.word2vec import IN, train_skipgram


def main():
    from flink_parameter_server_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    vocab = 2000
    tokens = synthetic_corpus(
        vocab, 150_000, num_topics=10, topic_stickiness=0.995, seed=0
    )
    res = train_skipgram(
        skipgram_batches(tokens, vocab, batch_size=1024, window=4,
                         num_negatives=5, epochs=2, seed=0),
        vocab_size=vocab,
        dim=32,
        learning_rate=1.0,
        dedup_scale=True,
        collect_outputs=False,
    )
    emb = np.asarray(res.store.values())[:, IN]
    emb = emb / (np.linalg.norm(emb, axis=1, keepdims=True) + 1e-9)

    # nearest neighbours of a few topic-head words
    for w in [0, 200, 400]:
        sims = emb @ emb[w]
        nn = np.argsort(-sims)[1:6]
        print(f"word {w}: neighbours {nn.tolist()} "
              f"(same topic: {[int(x // 200 == w // 200) for x in nn]})")


if __name__ == "__main__":
    main()
