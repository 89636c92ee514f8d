"""workloads/ — the workload-generic runtime (docs/workloads.md).

What is pinned here, and why it is the right oracle:

  * **registry** — any learner by name, the operational property every
    other harness (nemesis, soak, psctl) rides;
  * **PA bitwise parity** — a BSP cluster run (sockets, WAL, retries)
    equals the StreamingDriver oracle BIT FOR BIT: the on-device dense
    combine (DenseCombineLogic) leaves exactly one fp32 row per id per
    round on both arms, so any mismatch is a real routing/apply bug,
    not float noise;
  * **sketch integer-exactness** — counts are integers and integer
    adds commute, so the cluster table must equal a pure-numpy
    bincount of the hashed stream with NO tolerance, even with two
    interleaving workers and even when the config REQUESTS the q8
    codec (the increment carve-out bypasses it);
  * **the q8/error-feedback rule is PA-compatible** — the delta
    semantics PA shares with MF keeps the compression plane's
    ≤1-granule-per-id property on scalar rows;
  * **serving verbs** — predict/query/topk over live TCP against the
    cluster table, margins/counts checked against manual math;
  * **chaos** — mid-frame RST + kill→promote over the sketch workload
    replays integer-exact (the satellite scenario, run directly here
    with a shorter schedule than the corpus one);
  * **psctl workloads** — the live rate table over a real
    TelemetryServer scrape.
"""
import json

import numpy as np
import pytest

from flink_parameter_server_tpu.cluster.driver import (
    ClusterConfig,
    ClusterDriver,
)
from flink_parameter_server_tpu.workloads import (
    DenseCombineLogic,
    WorkloadParams,
    build_cluster_driver,
    create_workload,
    serve_workload,
    workload_names,
    workload_table,
)

pytestmark = pytest.mark.workloads

SMALL = WorkloadParams(
    rounds=6, batch=48, num_users=24, num_items=32, dim=4, seed=3
)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_builtin_names(self):
        assert {"mf", "pa", "sketch"} <= set(workload_names())

    def test_unknown_name_is_loud(self):
        with pytest.raises(KeyError, match="unknown workload"):
            create_workload("word2vec")

    def test_describe_contract(self):
        pa = create_workload("pa", SMALL)
        d = pa.describe()
        assert d["push_semantics"] == "delta"
        assert d["parity"] == "bitwise"
        assert d["serving_verbs"] == ["predict"]
        sk = create_workload("sketch", SMALL)
        d = sk.describe()
        assert d["push_semantics"] == "increment"
        assert d["parity"] == "exact_int"
        assert set(d["serving_verbs"]) == {"query", "topk"}

    def test_mf_workload_matches_legacy_stream(self):
        """The registry-packaged MF stream is the exact stream the
        nemesis battery always trained (seed 3 synthetic ratings) —
        the corpus replay's oracle cache rides on this."""
        from flink_parameter_server_tpu.data.movielens import (
            synthetic_ratings,
        )
        from flink_parameter_server_tpu.data.streams import microbatches

        mf = create_workload("mf", SMALL)
        got = mf.batches()
        cols = synthetic_ratings(
            SMALL.num_users, SMALL.num_items,
            SMALL.rounds * SMALL.batch, seed=3,
        )
        want = list(microbatches(cols, SMALL.batch))
        assert len(got) == len(want)
        for g, w in zip(got, want):
            for k in w:
                np.testing.assert_array_equal(
                    np.asarray(g[k]), np.asarray(w[k])
                )


# ---------------------------------------------------------------------------
# parity: PA bitwise, sketch integer-exact
# ---------------------------------------------------------------------------


class TestParity:
    def test_pa_cluster_bitwise_vs_streaming_oracle(self):
        pa = create_workload("pa", SMALL)
        oracle = pa.oracle_values()
        driver = build_cluster_driver(
            pa,
            config=ClusterConfig(
                num_shards=2, num_workers=1, staleness_bound=0,
            ),
            registry=False,
        )
        with driver:
            result = driver.run(pa.batches())
        assert np.array_equal(result.values, oracle), (
            "BSP cluster PA table is not bitwise the streaming oracle"
        )
        v = pa.parity_verdict(result.values, oracle)
        assert v.ok and "bitwise" in v.detail

    def test_pa_oracle_anchored_to_streaming_driver(self):
        """The sequential streaming oracle is the literal
        StreamingDriver run modulo XLA fusion (the one-program jit may
        reassociate float sums by ulps at some shapes — see
        PAClassifierWorkload.oracle_values): pinned allclose tight."""
        pa = create_workload("pa", SMALL)
        np.testing.assert_allclose(
            pa.oracle_values(), pa.streaming_driver_values(),
            rtol=1e-5, atol=1e-6,
        )

    def test_pa_bitwise_holds_at_the_fusion_sensitive_shape(self):
        """The shape where transform_batched's fused program diverges
        by ulps from the standalone step (rounds=10, batch=64, F=48,
        seed=0 — found by the example smoke): the cluster must STILL
        be bitwise vs the streaming oracle, because both run the same
        compiled step artifact."""
        p = WorkloadParams(rounds=10, batch=64, num_items=48, seed=0)
        pa = create_workload("pa", p)
        oracle = pa.oracle_values()
        driver = build_cluster_driver(
            pa,
            config=ClusterConfig(
                num_shards=2, num_workers=1, staleness_bound=0,
            ),
            registry=False,
        )
        with driver:
            result = driver.run(pa.batches())
        assert np.array_equal(result.values, oracle)

    def test_sketch_integer_exact_two_workers_q8_requested(self):
        """Two interleaving workers + a REQUESTED q8 codec: counts
        must still be integer-exact because increment semantics
        bypass quantization (and integer adds commute)."""
        sk = create_workload("sketch", SMALL)
        oracle = sk.oracle_values()
        driver = build_cluster_driver(
            sk,
            config=ClusterConfig(
                num_shards=2, num_workers=2, staleness_bound=0,
                wire_format="q8",
            ),
            registry=False,
        )
        with driver:
            # the carve-out must have stripped the compressor from
            # every worker client (quantized increments would land
            # within-a-granule, i.e. wrong)
            assert all(
                c._compressor is None and c.wire_format == "b64"
                for c in driver._clients
            )
            result = driver.run(sk.batches())
        v = sk.parity_verdict(result.values, oracle)
        assert v.ok, v.detail
        assert np.array_equal(result.values, oracle)

    def test_dense_combine_preserves_masked_sums(self):
        """DenseCombineLogic unit: the dense per-round push equals the
        masked lane sums of the inner logic's request (numpy oracle),
        and untouched ids stay unmasked."""
        import jax

        pa = create_workload("pa", SMALL)
        logic = pa.make_logic()
        assert isinstance(logic, DenseCombineLogic)
        batch = pa.batches()[0]
        ids = np.asarray(logic.keys(batch))
        pulled = np.zeros(ids.shape, np.float32)
        state, req, _out = jax.jit(logic.step)(
            (), batch, pulled
        )
        dense = np.asarray(req.deltas)
        touched = np.asarray(req.mask)
        # inner-step oracle
        inner = logic.inner
        _, ireq, _ = jax.jit(inner.step)((), batch, pulled)
        m = np.asarray(ireq.mask).reshape(-1)
        flat_ids = np.asarray(ireq.ids).reshape(-1)[m]
        flat_d = np.asarray(ireq.deltas).reshape(-1)[m]
        want = np.zeros(pa.capacity, np.float64)
        np.add.at(want, flat_ids, flat_d.astype(np.float64))
        np.testing.assert_allclose(
            dense[touched], want[touched], rtol=1e-5, atol=1e-6
        )
        assert not touched[~np.isin(
            np.arange(pa.capacity), flat_ids
        )].any()


# ---------------------------------------------------------------------------
# the push-semantics seam + error feedback
# ---------------------------------------------------------------------------


class TestPushSemantics:
    def test_increment_downgrade_in_make_client(self):
        sk = create_workload("sketch", SMALL)
        driver = build_cluster_driver(
            sk,
            config=ClusterConfig(
                num_shards=1, num_workers=1, staleness_bound=2,
                wire_format="q8",
            ),
            registry=False,
        )
        with driver:
            client = driver._make_client(worker="probe")
            try:
                assert client.wire_format == "b64"
                assert client._compressor is None
            finally:
                client.close()

    def test_delta_workload_keeps_q8_under_ssp(self):
        pa = create_workload("pa", SMALL)
        driver = build_cluster_driver(
            pa,
            config=ClusterConfig(
                num_shards=1, num_workers=1, staleness_bound=2,
                wire_format="q8",
            ),
            registry=False,
        )
        with driver:
            client = driver._make_client(worker="probe")
            try:
                assert client.wire_format == "q8"
                assert client._compressor is not None
            finally:
                client.close()

    def test_error_feedback_is_pa_compatible(self):
        """The compression plane's ≤1-granule-per-id delivered-sum
        property holds on PA-shaped SCALAR rows (the PA weight vector
        is value_shape ()): error feedback re-injects each round's
        quantization error, so the delivered sum trails the fp32 sum
        by at most the last round's granule."""
        from flink_parameter_server_tpu.compression.quantizers import (
            DeltaCompressor,
        )

        rng = np.random.default_rng(0)
        F = 32
        comp = DeltaCompressor("q8")
        delivered = np.zeros(F, np.float64)
        exact = np.zeros(F, np.float64)
        granule = np.zeros(F, np.float64)
        ids = np.arange(F, dtype=np.int64)
        for _ in range(40):
            deltas = (
                rng.standard_normal(F).astype(np.float32)
                * (rng.random(F) < 0.4)
            )
            dq, q, scales = comp.compress(ids, deltas)
            assert q is not None and scales is not None
            delivered += np.asarray(dq, np.float64).reshape(F)
            exact += deltas.astype(np.float64)
            granule = np.maximum(
                granule, np.asarray(scales, np.float64).reshape(F)
            )
        err = np.abs(delivered - exact)
        assert (err <= granule + 1e-6).all(), (
            f"error feedback broke on scalar rows: "
            f"max err {err.max():.3e} vs granule {granule.max():.3e}"
        )

    def test_pa_q8_cluster_tracks_oracle_within_granules(self):
        """End to end: a PA cluster run with the q8 push codec under
        SSP stays within error-feedback distance of the exact fp32
        oracle — the compression plane is usable by the second delta
        workload, not just MF."""
        pa = create_workload("pa", SMALL)
        oracle = pa.oracle_values()
        driver = build_cluster_driver(
            pa,
            config=ClusterConfig(
                num_shards=2, num_workers=1, staleness_bound=None,
                wire_format="q8",
            ),
            registry=False,
        )
        with driver:
            result = driver.run(pa.batches())
        # PA-I updates are bounded by C=1 per feature per round; the
        # residual property bounds the tail at one granule per id, so
        # a loose absolute bound is the honest check here
        assert np.abs(result.values - oracle).max() < 0.05


# ---------------------------------------------------------------------------
# serving verbs over live TCP
# ---------------------------------------------------------------------------


class TestServing:
    def test_sketch_query_topk_tcp(self):
        from flink_parameter_server_tpu.telemetry.registry import (
            MetricsRegistry,
        )
        from flink_parameter_server_tpu.workloads import (
            WorkloadServingClient,
        )

        reg = MetricsRegistry()
        sk = create_workload("sketch", SMALL)
        driver = build_cluster_driver(
            sk,
            config=ClusterConfig(
                num_shards=2, num_workers=1, staleness_bound=0,
            ),
            registry=reg,
        )
        with driver:
            driver.run(sk.batches())
            client = driver._make_client(worker="serve")
            server = serve_workload(sk, client, registry=reg)
            try:
                sc = WorkloadServingClient(server.host, server.port)
                tokens = sk._tokens()
                true = np.bincount(tokens, minlength=sk.vocab)
                keys = [int(np.argmax(true)), 0]
                est = sc.query(keys)
                # count-min never underestimates; overestimate bounded
                for k, e in zip(keys, est):
                    assert e >= int(true[k])
                top = sc.topk(3)
                assert len(top) == 3
                assert top[0][0] == int(np.argmax(true))
                assert top[0][1] >= int(true.max())
                info = sc.info()
                assert info["name"] == "sketch"
                with pytest.raises(RuntimeError, match="bad-request"):
                    sc.query([])
                with pytest.raises(RuntimeError, match="bad-request"):
                    sc.predict([[(0, 1.0)]])
                table = workload_table(reg)
                assert table["sketch"]["queries_total"] >= 2
                assert table["sketch"]["topk_total"] == 1
                assert table["sketch"]["serving_errors_total"] == 2
                assert table["sketch"]["queries_observed"] >= 3
            finally:
                server.stop()
                client.close()

    def test_pa_predict_margins_match_table(self):
        from flink_parameter_server_tpu.workloads import (
            WorkloadServingClient,
        )

        pa = create_workload("pa", SMALL)
        driver = build_cluster_driver(
            pa,
            config=ClusterConfig(
                num_shards=2, num_workers=1, staleness_bound=0,
            ),
            registry=False,
        )
        with driver:
            result = driver.run(pa.batches())
            w = result.values
            client = driver._make_client(worker="serve")
            server = serve_workload(pa, client, registry=False)
            try:
                sc = WorkloadServingClient(server.host, server.port)
                ex = [[(0, 1.5), (3, -0.5)], [(7, 2.0)]]
                margins = sc.predict(ex)
                want = [
                    1.5 * w[0] - 0.5 * w[3],
                    2.0 * w[7],
                ]
                np.testing.assert_allclose(
                    margins, want, rtol=1e-4, atol=1e-5
                )
            finally:
                server.stop()
                client.close()


# ---------------------------------------------------------------------------
# chaos: the satellite — sketch increments under mid-frame RST +
# kill→promote replay integer-exact
# ---------------------------------------------------------------------------


class TestChaos:
    def test_sketch_rst_kill_promote_integer_exact(self, tmp_path):
        from flink_parameter_server_tpu.nemesis.runner import (
            run_scenario,
        )
        from flink_parameter_server_tpu.nemesis.scenarios import (
            NemesisOp,
            Scenario,
        )

        s = Scenario(
            "sketch_rst_promote_direct",
            (
                NemesisOp(2, "truncate_next", shard=0, mode="c2s",
                          keep_frac=0.4, cut="payload"),
                NemesisOp(4, "kill_shard", shard=0),
                NemesisOp(4, "promote_shard", shard=0),
            ),
            seed=207,
            rounds=8,
            batch=64,
            num_items=48,
            replicated=True,
            workload="sketch",
            wire_format="q8",
        )
        report = run_scenario(s, wal_root=str(tmp_path))
        bad = [v for v in report.verdicts if not v.ok]
        assert report.ok, bad
        parity = next(
            v for v in report.verdicts
            if v.name == "final_table_parity"
        )
        assert "integer-exact" in parity.detail
        assert "mismatched_cells=0" in parity.detail


# ---------------------------------------------------------------------------
# soak plumbing: workload-generic runner + q8/aggregation arms
# ---------------------------------------------------------------------------


@pytest.mark.soak
class TestSoakArms:
    def test_sketch_soak_q8_bypassed(self):
        from flink_parameter_server_tpu.loadgen.soak import (
            SoakConfig,
            run_soak,
        )

        rep = run_soak(SoakConfig(
            duration_s=2.0, offered_rps=60.0, generators=2,
            num_users=64, num_items=128, warmup_requests=16,
            link_delay_ms=0.0, workload="sketch", wire_format="q8",
        ))
        assert rep.ok, [v.detail for v in rep.verdicts if not v.ok]
        # increments bypass the codec: nothing saved, nothing lossy
        assert "compression_bytes_saved" not in rep.overload

    def test_mf_soak_q8_aggregation_arm(self):
        from flink_parameter_server_tpu.loadgen.soak import (
            SoakConfig,
            run_soak,
        )

        rep = run_soak(SoakConfig(
            duration_s=2.5, offered_rps=80.0, generators=3,
            num_users=64, num_items=128, warmup_requests=16,
            link_delay_ms=0.0, wire_format="q8", push_aggregate=True,
        ))
        assert rep.ok, [v.detail for v in rep.verdicts if not v.ok]
        assert rep.overload["push_aggregate"] is True
        assert rep.overload["combined_pushes"] > 0
        assert rep.overload.get("compression_bytes_saved", 0) > 0


# ---------------------------------------------------------------------------
# psctl workloads + telemetry path (live)
# ---------------------------------------------------------------------------


class TestPsctl:
    def test_psctl_workloads_live_smoke(self, capsys):
        from tools.psctl import main as psctl_main

        from flink_parameter_server_tpu.telemetry.exporter import (
            TelemetryServer,
        )
        from flink_parameter_server_tpu.telemetry.registry import (
            MetricsRegistry,
        )
        from flink_parameter_server_tpu.workloads import (
            WorkloadServingClient,
        )

        reg = MetricsRegistry()
        sk = create_workload("sketch", SMALL)
        driver = build_cluster_driver(
            sk,
            config=ClusterConfig(
                num_shards=2, num_workers=1, staleness_bound=0,
            ),
            registry=reg,
        )
        with driver:
            driver.run(sk.batches())
            client = driver._make_client(worker="serve")
            server = serve_workload(sk, client, registry=reg)
            tsrv = TelemetryServer(reg).start()
            try:
                sc = WorkloadServingClient(server.host, server.port)
                sc.query([0, 1])
                sc.topk(2)
                rc = psctl_main([
                    "workloads",
                    "--metrics", f"{tsrv.host}:{tsrv.port}",
                    "--json",
                ])
                assert rc == 0
                out = capsys.readouterr().out
                table = json.loads(out)
                assert "sketch" in table
                row = table["sketch"]
                assert row["updates_total"] == SMALL.rounds * SMALL.batch
                assert row["queries_total"] >= 2
                assert row["topk_total"] == 1
                assert "query_latency_p99_ms" in row
                # one rendered frame too (rates path)
                rc = psctl_main([
                    "workloads", "--raw", "--iterations", "1",
                    "--interval", "0.05",
                    "--metrics", f"{tsrv.host}:{tsrv.port}",
                ])
                assert rc == 0
                rendered = capsys.readouterr().out
                assert "workload" in rendered and "sketch" in rendered
            finally:
                tsrv.stop()
                server.stop()
                client.close()


# ---------------------------------------------------------------------------
# tooling gates
# ---------------------------------------------------------------------------


class TestTooling:
    def test_known_component_registered(self):
        from tools.check_metric_lines import KNOWN_COMPONENTS

        assert "workloads" in KNOWN_COMPONENTS
