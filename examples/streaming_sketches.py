"""PS-backed streaming sketches over a token stream — through the
workload registry.

Mirrors the reference's sketch package (SURVEY.md §2 #10).  The
count-min layer is the registered "sketch" workload
(``workloads/registry.py``), so the same object runs single-process,
on a live multi-shard cluster (``--cluster``, counts checked
INTEGER-EXACT against the pure-numpy ground truth — increments, not
fp32 deltas), and behind the ``query``/``topk`` serving verbs
(``--serve``).  The classic single-process tour (bloom co-occurrence
similarity, tug-of-war F2, time decay) still runs below it.
"""
import argparse

import numpy as np


def main():
    from flink_parameter_server_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--vocab", type=int, default=400)
    ap.add_argument("--rounds", type=int, default=64)
    ap.add_argument("--batch", type=int, default=1024)
    ap.add_argument("--cluster", action="store_true",
                    help="run the count-min layer on a 2-shard PS "
                         "cluster and verify integer-exact counts")
    ap.add_argument("--serve", action="store_true",
                    help="also open the TCP query/topk endpoint "
                         "(implies --cluster)")
    args = ap.parse_args()
    if args.serve:
        args.cluster = True

    from flink_parameter_server_tpu.workloads import (
        WorkloadParams,
        build_cluster_driver,
        create_workload,
    )

    params = WorkloadParams(
        rounds=args.rounds, batch=args.batch, num_items=args.vocab,
        seed=3,
    )
    wl = create_workload("sketch", params)
    tokens = wl._tokens()
    true = np.bincount(tokens, minlength=args.vocab)
    hot = np.argsort(true)[-3:]

    # ground-truth sketch table (pure numpy — integers)
    table = wl.oracle_values()
    est = table.reshape(-1)[wl.cells_np(hot)].min(axis=1)
    print("count-min hottest words:",
          dict(zip(hot.tolist(), est.astype(int).tolist())),
          "true:", true[hot].tolist())

    if args.cluster:
        from flink_parameter_server_tpu.cluster.driver import (
            ClusterConfig,
        )

        driver = build_cluster_driver(
            wl,
            config=ClusterConfig(
                num_shards=2, num_workers=2, staleness_bound=0,
            ),
        )
        with driver:
            result = driver.run(wl.batches())
            exact = bool(np.array_equal(result.values, table))
            print(f"cluster run: {result.events} increments over "
                  f"{result.rounds} rounds on 2 shards; "
                  f"integer-exact vs ground truth: {exact}")
            if not exact:
                raise SystemExit("sketch counts diverged from truth")
            if args.serve:
                from flink_parameter_server_tpu.workloads import (
                    WorkloadServingClient,
                    serve_workload,
                )

                client = driver._make_client(worker="serve")
                server = serve_workload(wl, client)
                try:
                    sc = WorkloadServingClient(
                        server.host, server.port
                    )
                    print("served query:", dict(zip(
                        hot.tolist(), sc.query(hot.tolist())
                    )))
                    print("served top-4:", sc.topk(4))
                finally:
                    server.stop()
                    client.close()

    # -- the classic single-process tour -------------------------------------
    import jax.numpy as jnp

    from flink_parameter_server_tpu.core.transform import (
        transform_batched,
    )
    from flink_parameter_server_tpu.data.text import cooccurrence_pairs
    from flink_parameter_server_tpu.models.sketches import (
        BloomCooccurrence,
        CountMinConfig,
        CountMinSketch,
        TugOfWarConfig,
        TugOfWarSketch,
        decay,
    )

    def key_batches(keys, batch=1024):
        for s in range(0, len(keys) - batch + 1, batch):
            yield {"key": keys[s: s + batch],
                   "mask": np.ones(batch, bool)}

    cms = CountMinSketch(CountMinConfig(width=8192, depth=4, seed=0))
    words = transform_batched(key_batches(tokens), cms,
                              cms.make_store(), collect_outputs=False)

    bloom = BloomCooccurrence(
        CountMinConfig(width=1 << 15, depth=4, seed=1)
    )
    pairs = transform_batched(
        cooccurrence_pairs(tokens, window=2), bloom,
        bloom.make_store(), collect_outputs=False,
    )
    wpt = args.vocab // 4  # words per topic (workload topics = 4)
    a = jnp.asarray([0, 0])
    b = jnp.asarray([1, wpt])  # same-topic vs cross-topic neighbour
    sims = bloom.similarity(pairs.store, words.store, cms, a, b)
    print(f"similarity(word0, word1 same-topic)={float(sims[0]):.3f}  "
          f"(word0, word{wpt} cross-topic)={float(sims[1]):.3f}")

    tow = TugOfWarSketch(TugOfWarConfig(groups=8, per_group=32, seed=2))
    f2 = transform_batched(key_batches(tokens), tow, tow.make_store(),
                           collect_outputs=False)
    print(f"F2 estimate {float(tow.estimate_f2(f2.store)):.3g} "
          f"true {float((true.astype(np.float64) ** 2).sum()):.3g}")

    decayed = decay(words.store, 0.5)
    print("after decay(0.5), hottest estimate:",
          float(cms.query(decayed, jnp.asarray([int(hot[-1])]))[0]))


if __name__ == "__main__":
    main()
