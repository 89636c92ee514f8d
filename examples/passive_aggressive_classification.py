"""Online passive-aggressive binary classification — through the
workload registry.

Mirrors the reference's ``PassiveAggressiveParameterServer
.transformBinary`` (SURVEY.md §2 #9): sparse examples, pull only the
present feature ids, PA-I updates, prediction stream out.  The
workload is resolved from ``workloads/registry.py`` ("pa"), so the
exact same object can run three ways:

  * default — the single-process StreamingDriver path;
  * ``--cluster`` — a 2-shard BSP parameter-server cluster (real TCP),
    whose final weight vector is checked BITWISE against the
    single-process run (the workload's parity contract);
  * ``--serve`` (implies ``--cluster``) — a live ``predict`` serving
    endpoint (workloads/serving.py) answering sparse-margin queries
    over TCP while the table sits on the shards.
"""
import argparse

import numpy as np


def main():
    from flink_parameter_server_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--features", type=int, default=100)
    ap.add_argument("--rounds", type=int, default=24)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--cluster", action="store_true",
                    help="run on a 2-shard PS cluster and verify "
                         "bitwise parity vs the streaming run")
    ap.add_argument("--serve", action="store_true",
                    help="also open the TCP predict endpoint "
                         "(implies --cluster)")
    args = ap.parse_args()
    if args.serve:
        args.cluster = True

    from flink_parameter_server_tpu.workloads import (
        WorkloadParams,
        build_cluster_driver,
        create_workload,
    )
    from flink_parameter_server_tpu.workloads.pa import _pa_stream

    params = WorkloadParams(
        rounds=args.rounds, batch=args.batch,
        num_items=args.features, seed=0,
    )
    wl = create_workload("pa", params)
    X, y = _pa_stream(params)

    # the single-process run (the StreamingDriver oracle)
    w = np.asarray(wl.oracle_values())
    margins = X @ w
    acc = float(np.mean(np.sign(margins) == y))
    loss = float(np.mean(np.maximum(0.0, 1.0 - y * margins)))
    print(f"final hinge loss {loss:.3f}; train accuracy {acc:.3%}")

    if not args.cluster:
        return

    from flink_parameter_server_tpu.cluster.driver import ClusterConfig

    driver = build_cluster_driver(
        wl,
        config=ClusterConfig(
            num_shards=2, num_workers=1, staleness_bound=0,
        ),
    )
    with driver:
        result = driver.run(wl.batches())
        bitwise = bool(np.array_equal(result.values, w))
        print(f"cluster run: {result.events} events over "
              f"{result.rounds} rounds on 2 shards; "
              f"bitwise parity vs streaming: {bitwise}")
        if not bitwise:
            raise SystemExit("cluster/streaming parity violated")
        if args.serve:
            from flink_parameter_server_tpu.workloads import (
                WorkloadServingClient,
                serve_workload,
            )

            client = driver._make_client(worker="serve")
            server = serve_workload(wl, client)
            try:
                sc = WorkloadServingClient(server.host, server.port)
                # serve two live examples from the training stream
                ex = []
                for i in range(2):
                    nz = np.nonzero(X[i])[0][:6]
                    ex.append([(int(f), float(X[i, f])) for f in nz])
                served = sc.predict(ex)
                print("served margins:",
                      [f"{m:.4f}" for m in served],
                      f"(labels {y[:2].astype(int).tolist()})")
            finally:
                server.stop()
                client.close()


if __name__ == "__main__":
    main()
