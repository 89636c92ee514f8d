"""compression/ — quantized delta push path + aggregation tree tests.

The acceptance anchors (ISSUE 14, docs/compression.md):

  * the codec properties — per-row-scaled int8 and bf16 delta codecs
    with ERROR FEEDBACK converge to the fp32 oracle within one
    quantization granule per id (and measurably beat feedback-off);
    combine-then-quantize and quantize-then-combine-with-residuals
    both land inside the documented RMSE bound;
  * the wire e2e — a ``wire_format="q8"`` client negotiates the enc
    on the hello line, ships int8 + T_SCALE frames, and the table
    tracks the oracle; EVERY downgrade cell of the negotiation matrix
    (old binary server, pre-binary server, line-pinned client)
    delivers the IDENTICAL table, because the client always applies
    the dequantized rows;
  * the aggregation tree — one combined push per shard per round,
    frames ÷ num_workers, uplink ledger exactly-once;
  * the BSP carve-out — a bound-0 driver configured "q8" is BITWISE
    the "b64" run;
  * quantized replication — a q8 leg's follower tracks the primary
    within the granule bound and a promoted quantized log replays
    bitwise; the bf16 push round-trips through a repl ship bitwise;
  * the two mid-frame-RST corpus schedules replay green over a
    quantized-enc connection (a torn quantized frame dedupes exactly
    like f32);
  * the operator/tooling satellites — psctl ``bytes`` and the
    ``compression`` component lint.
"""
import dataclasses
import io
import json
import os
import sys
import threading
import time
from contextlib import redirect_stdout

import numpy as np
import pytest

from flink_parameter_server_tpu import telemetry as tm
from flink_parameter_server_tpu.cluster.client import ClusterClient
from flink_parameter_server_tpu.cluster.partition import RangePartitioner
from flink_parameter_server_tpu.cluster.shard import ParamShard, ShardServer
from flink_parameter_server_tpu.compression.quantizers import (
    DeltaCompressor,
    ResidualStore,
    bf16_roundtrip,
    compress_record_payload,
    dequantize_q8,
    q8_from_payload,
    q8_payload,
    quantize_q8,
    record_deltas,
)
from flink_parameter_server_tpu.ops.dedup import (
    aggregate_delta_batches,
    aggregate_deltas,
)
from flink_parameter_server_tpu.utils import frames as binf

pytestmark = pytest.mark.compression

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def fresh_registry():
    reg = tm.MetricsRegistry(run_id="test-compression")
    tm.set_registry(reg)
    yield reg
    tm.set_registry(None)


def _mini_cluster(n_shards=2, *, server_cls=ShardServer, dim=4,
                  capacity=64, wal_dir=None):
    part = RangePartitioner(capacity, n_shards)
    shards = [
        ParamShard(
            i, part, (dim,), registry=False,
            wal_dir=None if wal_dir is None else f"{wal_dir}/s{i}",
        )
        for i in range(n_shards)
    ]
    servers = [server_cls(s).start() for s in shards]
    addrs = [(srv.host, srv.port) for srv in servers]
    return part, shards, servers, addrs


# ---------------------------------------------------------------------------
# codec units
# ---------------------------------------------------------------------------


class TestQ8Codec:
    def test_round_trip_error_bound(self):
        rng = np.random.default_rng(0)
        rows = rng.normal(0, 0.01, (128, 16)).astype(np.float32)
        q, scales = quantize_q8(rows)
        dq = dequantize_q8(q, scales, (16,))
        # per-row error bounded by half a granule (scale/2)
        assert np.all(
            np.abs(dq - rows) <= scales[:, None] / 2 + 1e-9
        )
        # payload round trip is bitwise the dq rows
        p, sb = q8_payload(rows)
        assert np.array_equal(q8_from_payload(p, sb, (16,)), dq)
        # a quarter of the f32 bytes (+4 bytes/row of scale)
        assert len(p) == rows.size
        assert len(sb) == 4 * len(rows)

    def test_zero_rows_and_shapes(self):
        rows = np.zeros((4, 8), np.float32)
        q, scales = quantize_q8(rows)
        assert np.all(scales == 0)
        assert np.array_equal(
            dequantize_q8(q, scales, (8,)), rows
        )
        # scalar stores ((n,) deltas) survive the codec
        flat = np.asarray([0.5, -0.25, 0.0], np.float32)
        q, s = quantize_q8(flat)
        assert dequantize_q8(q, s, ()).shape == (3,)

    def test_non_finite_rejected(self):
        bad = np.asarray([[1.0, np.nan]], np.float32)
        with pytest.raises(ValueError, match="non-finite"):
            quantize_q8(bad)

    def test_oversized_frame_rejected(self):
        from flink_parameter_server_tpu.compression.quantizers import (
            MAX_Q8_ROWS,
        )

        with pytest.raises(ValueError, match="chunk"):
            q8_payload(np.zeros((MAX_Q8_ROWS + 1, 1), np.float32))

    def test_bad_payloads_rejected(self):
        with pytest.raises(ValueError, match="T_SCALE"):
            q8_from_payload(b"\x01\x02", None, (2,))
        with pytest.raises(ValueError, match="tile"):
            q8_from_payload(b"\x01\x02\x03", b"\x00" * 4, (2,))

    def test_bf16_roundtrip_matches_wire_codec(self):
        rng = np.random.default_rng(1)
        rows = rng.normal(0, 1, (32, 4)).astype(np.float32)
        host = bf16_roundtrip(rows)
        wire = binf.rows_from_payload(
            binf.rows_to_payload(rows, binf.ENC_BF16), (4,),
            binf.ENC_BF16,
        )
        assert np.array_equal(host, wire)
        # bf16 re-encode of the round-tripped rows is LOSSLESS — what
        # lets the client compute residuals before the bytes leave
        assert np.array_equal(bf16_roundtrip(host), host)


# ---------------------------------------------------------------------------
# error-feedback residual properties (the convergence contract)
# ---------------------------------------------------------------------------


class TestErrorFeedback:
    def _stream(self, rounds, n, dim, seed):
        rng = np.random.default_rng(seed)
        return [
            rng.normal(0, 0.01, (n, dim)).astype(np.float32)
            for _ in range(rounds)
        ]

    @pytest.mark.parametrize("enc", ["q8", "bf16"])
    def test_feedback_converges_to_fp32_oracle(self, enc):
        """The residual rule: after any number of rounds, the
        delivered sum trails the true fp32 sum by at most ONE granule
        per id (the residual still in flight) — the quantization error
        does not accumulate."""
        n, dim = 40, 8
        ids = np.arange(n)
        comp = DeltaCompressor(enc)
        oracle = np.zeros((n, dim), np.float32)
        table = np.zeros((n, dim), np.float32)
        granule = 0.0
        for d in self._stream(300, n, dim, seed=7):
            oracle += d
            delivered, q, scales = comp.compress(ids, d)
            table += delivered
            if scales is not None:
                granule = max(granule, float(scales.max()))
        err = float(np.abs(table - oracle).max())
        if enc == "q8":
            assert err <= granule + 1e-6
        # and absolutely small relative to the accumulated signal
        rel = err / float(np.sqrt(np.mean(oracle ** 2)))
        assert rel < 0.02

    def test_feedback_beats_no_feedback(self):
        """Feedback-off truncation accumulates bias; the residual rule
        does not — the property that makes q8 usable for training."""
        n, dim = 32, 4
        ids = np.arange(n)
        comp = DeltaCompressor("q8")
        oracle = np.zeros((n, dim), np.float32)
        with_fb = np.zeros((n, dim), np.float32)
        without = np.zeros((n, dim), np.float32)
        # biased small deltas: the adversarial case for truncation
        rng = np.random.default_rng(11)
        for _ in range(300):
            d = np.abs(rng.normal(0, 0.004, (n, dim))).astype(
                np.float32
            )
            d[0] = 1.0  # a big row pins the per-row scale... per row,
            # so only row 0; others quantize at their own scale
            oracle += d
            delivered, _, _ = comp.compress(ids, d)
            with_fb += delivered
            q, s = quantize_q8(d)
            without += dequantize_q8(q, s, (dim,))
        err_fb = np.abs(with_fb - oracle).max()
        err_raw = np.abs(without - oracle).max()
        assert err_fb < err_raw

    def test_combine_orders_both_converge(self):
        """Satellite 3: combine-then-quantize (the aggregation tree in
        front of a quantized uplink) vs quantize-then-combine-with-
        residuals (independently quantizing workers) both land within
        the documented bound of the fp32 oracle."""
        n, dim, workers = 24, 4, 3
        ids = np.arange(n)
        rng = np.random.default_rng(13)
        oracle = np.zeros((n, dim), np.float32)
        combined_then_q = np.zeros((n, dim), np.float32)
        q_then_combined = np.zeros((n, dim), np.float32)
        uplink = DeltaCompressor("q8")
        per_worker = [DeltaCompressor("q8") for _ in range(workers)]
        granule = 0.0
        for _ in range(200):
            ds = [
                rng.normal(0, 0.01, (n, dim)).astype(np.float32)
                for _ in range(workers)
            ]
            total = np.sum(ds, axis=0, dtype=np.float32)
            oracle += total
            # combine → quantize (one residual store at the uplink)
            uq, summed = aggregate_delta_batches(
                [(ids, d) for d in ds]
            )
            assert np.array_equal(uq, ids)
            delivered, _, s = uplink.compress(uq, summed.astype(
                np.float32
            ))
            combined_then_q += delivered
            if s is not None:
                granule = max(granule, float(s.max()))
            # quantize per worker (own residuals) → combine
            for w, d in enumerate(ds):
                dlv, _, s = per_worker[w].compress(ids, d)
                q_then_combined += dlv
                if s is not None:
                    granule = max(granule, float(s.max()))
        # combined: one granule per id; per-worker: one per worker
        assert np.abs(combined_then_q - oracle).max() <= (
            granule + 1e-6
        )
        assert np.abs(q_then_combined - oracle).max() <= (
            workers * granule + 1e-6
        )

    def test_residual_store_take_put_norm(self):
        rs = ResidualStore()
        ids = np.asarray([3, 5])
        rs.put(ids, np.asarray([[1.0, 0.0], [0.5, 0.5]], np.float32))
        assert len(rs) == 2 and rs.norm() > 0
        taken = rs.take(np.asarray([5, 9]), 2)
        assert np.array_equal(
            taken, np.asarray([[0.5, 0.5], [0.0, 0.0]], np.float32)
        )
        assert len(rs) == 1  # 5 consumed, 3 still stored
        rs.clear()
        assert len(rs) == 0 and rs.norm() == 0.0


# ---------------------------------------------------------------------------
# ops/dedup.aggregate_delta_batches (the combiner's merge step)
# ---------------------------------------------------------------------------


class TestAggregateBatches:
    def test_equals_concatenated_aggregate(self):
        rng = np.random.default_rng(3)
        batches = []
        all_ids, all_d = [], []
        for _ in range(4):
            ids = rng.integers(0, 32, 50).astype(np.int64)
            d = rng.normal(0, 1, (50, 3)).astype(np.float32)
            batches.append((ids, d))
            all_ids.append(ids)
            all_d.append(d)
        uq, summed = aggregate_delta_batches(batches)
        uq2, summed2 = aggregate_deltas(
            np.concatenate(all_ids), np.concatenate(all_d)
        )
        assert np.array_equal(uq, uq2)
        assert np.array_equal(summed, summed2)

    def test_masks_and_empties(self):
        ids = np.asarray([1, 2, 3])
        d = np.ones((3, 2), np.float32)
        mask = np.asarray([True, False, True])
        uq, summed = aggregate_delta_batches([
            (ids, d, mask),
            None,
            (np.empty(0, np.int64), np.empty((0, 2), np.float32)),
            (ids, d, np.zeros(3, bool)),
        ])
        assert uq.tolist() == [1, 3]
        assert np.array_equal(summed, np.ones((2, 2), np.float32))
        uq, summed = aggregate_delta_batches([])
        assert uq.size == 0


# ---------------------------------------------------------------------------
# the wire: q8 e2e + the negotiation matrix
# ---------------------------------------------------------------------------


class _OldBinServer(ShardServer):
    """A PR-13-era binary server: answers the hello WITHOUT the enc
    token — a new client must assume bf16-only and ship q8 as f32."""

    def _execute(self, line: str) -> str:
        toks = line.split()
        if toks and toks[0].lower() == "hello":
            return binf.HELLO_OK
        return super()._execute(line)


class _OldLineServer(ShardServer):
    """A pre-binary server: no hello at all."""

    def _execute(self, line: str) -> str:
        if line.split()[0].lower() == "hello":
            raise ValueError("unknown command 'hello'")
        return super()._execute(line)

    def respond_frame(self, data):  # pragma: no cover — must not run
        raise AssertionError("old server must never see binary frames")


def _push_stream(client, capacity, dim, rounds=20, seed=2):
    ids = np.arange(capacity, dtype=np.int64)
    rng = np.random.default_rng(seed)
    oracle = np.zeros((capacity, dim), np.float32)
    for _ in range(rounds):
        d = rng.normal(0, 0.01, (capacity, dim)).astype(np.float32)
        oracle += d
        client.push_batch(ids, d)
    return oracle


class TestQuantizedWire:
    def test_q8_e2e_bytes_saved_and_rmse(self, fresh_registry):
        part, shards, servers, addrs = _mini_cluster(dim=8)
        try:
            c = ClusterClient(
                addrs, part, (8,), registry=fresh_registry,
                wire_format="q8", worker="w0",
            )
            oracle = _push_stream(c, 64, 8)
            got = c.pull_batch(np.arange(64, dtype=np.int64))
            assert np.abs(got - oracle).max() < 5e-4
            conn = next(iter(c._conns.values()))
            assert conn.proto == "bin" and "q8" in conn.encs
            # one more push so the server conn ledger's LAST frame is
            # a q8 push — the rollout-visibility column
            c.push_batch(
                np.arange(64, dtype=np.int64),
                np.full((64, 8), 1e-3, np.float32),
            )
            table = servers[0].conn_table()
            assert table and table[0]["enc"] == "q8"
            # the compression plane counted real savings + a live
            # residual-norm probe
            snap = fresh_registry.snapshot()
            saved = sum(
                int(i["value"] or 0)
                for i in snap.get("compression_bytes_saved_total", [])
            )
            assert saved > 0
            norms = snap.get("compression_residual_norm", [])
            assert norms and norms[0]["value"] is not None
            c.close()
        finally:
            for s in servers:
                s.stop()

    def test_negotiation_matrix_identical_tables(self):
        """Every downgrade cell delivers the SAME table: the client
        applies dequantized rows whatever the framing, so a mixed
        fleet mid-rollout cannot fork the model."""
        tables = {}
        for label, cls, wire_proto in (
            ("new", ShardServer, "auto"),
            ("old-bin", _OldBinServer, "auto"),
            ("old-line", _OldLineServer, "auto"),
            ("line-pinned", ShardServer, "line"),
        ):
            part, shards, servers, addrs = _mini_cluster(
                dim=4, server_cls=cls
            )
            try:
                c = ClusterClient(
                    addrs, part, (4,), registry=False,
                    wire_format="q8", wire_proto=wire_proto,
                )
                _push_stream(c, 64, 4, rounds=8)
                tables[label] = c.pull_batch(
                    np.arange(64, dtype=np.int64)
                )
                conn = next(iter(c._conns.values()))
                if label == "new":
                    assert "q8" in conn.encs
                elif label == "old-bin":
                    assert conn.proto == "bin"
                    assert conn.encs == binf.LEGACY_BIN_ENCS
                else:
                    assert conn.proto == "line"
                c.close()
            finally:
                for s in servers:
                    s.stop()
        base = tables.pop("new")
        for label, t in tables.items():
            assert np.array_equal(t, base), label

    def test_q8_frame_missing_scales_is_bad_request(self):
        part, shards, servers, addrs = _mini_cluster(dim=4)
        try:
            from flink_parameter_server_tpu.cluster.client import (
                ShardConnection,
            )

            conn = ShardConnection(*addrs[0], negotiate=True)
            req = binf.encode_request(
                binf.VERB_IDS["push"],
                ids=np.arange(4, dtype=np.int64),
                payload=b"\x00" * 16,
                enc=binf.ENC_Q8,
            )
            resp = conn.request_many([req])[0]
            assert resp.flag == binf.STATUS_BAD_REQUEST
            assert "T_SCALE" in (resp.tlv_str(binf.T_ERR) or "")
            conn.close()
        finally:
            for s in servers:
                s.stop()

    def test_bf16_push_round_trip_and_repl_ship(self, tmp_path):
        """Satellite 1: a bf16 push round-trips end to end AND the
        resulting WAL records (exact post-truncation f32) ship to a
        follower bitwise."""
        from flink_parameter_server_tpu.replication.follower import (
            ReplicaShard,
        )
        from flink_parameter_server_tpu.replication.shipper import (
            ReplHub,
            WALShipper,
        )

        part, shards, servers, addrs = _mini_cluster(
            n_shards=1, dim=4, wal_dir=str(tmp_path / "wal")
        )
        try:
            c = ClusterClient(
                addrs, part, (4,), registry=False, wire_format="bf16"
            )
            oracle = _push_stream(c, 64, 4, rounds=12, seed=9)
            got = c.pull_batch(np.arange(64, dtype=np.int64))
            # bf16 + residuals: within a couple of granules of fp32
            assert np.abs(got - oracle).max() < 1e-3
            conn = next(iter(c._conns.values()))
            assert conn.proto == "bin" and "bf16" in conn.encs
            # ship the primary's log to a follower — bitwise (the log
            # holds the exact post-dq rows; shipping is f32)
            follower = ReplicaShard(
                0, part, (4,), wal_dir=str(tmp_path / "fwal"),
                registry=False,
            )
            fsrv = ShardServer(follower).start()
            hub = ReplHub()
            ship = WALShipper(
                shards[0], (fsrv.host, fsrv.port), hub.subscribe(),
                registry=False,
            ).start()
            head = shards[0].head_seq()
            deadline = time.time() + 30
            while ship.acked_seq < head and time.time() < deadline:
                time.sleep(0.01)
            while follower.apply_lag() > 0 and time.time() < deadline:
                time.sleep(0.01)
            assert np.array_equal(
                follower.values(), shards[0].values()
            )
            ship.stop()
            fsrv.stop()
            follower.close()
            c.close()
        finally:
            for s in servers:
                s.stop()


# ---------------------------------------------------------------------------
# quantized replication legs
# ---------------------------------------------------------------------------


class TestQuantizedReplication:
    def test_q8_leg_tracks_within_granule_and_replays_bitwise(
        self, tmp_path
    ):
        from flink_parameter_server_tpu.replication.follower import (
            ReplicaShard,
        )
        from flink_parameter_server_tpu.replication.shipper import (
            ReplHub,
            WALShipper,
        )

        part = RangePartitioner(64, 1)
        primary = ParamShard(
            0, part, (8,), wal_dir=str(tmp_path / "p"), registry=False
        )
        rng = np.random.default_rng(3)
        ids = np.arange(64, dtype=np.int64)
        for _ in range(30):
            primary.push(
                ids, rng.normal(0, 0.01, (64, 8)).astype(np.float32)
            )
        follower = ReplicaShard(
            0, part, (8,), wal_dir=str(tmp_path / "f"), registry=False
        )
        srv = ShardServer(follower).start()
        hub = ReplHub()
        ship = WALShipper(
            primary, (srv.host, srv.port), hub.subscribe(),
            registry=False, enc="q8",
        ).start()
        try:
            head = primary.head_seq()
            deadline = time.time() + 30
            while ship.acked_seq < head and time.time() < deadline:
                time.sleep(0.01)
            while (
                follower.apply_lag() > 0 and time.time() < deadline
            ):
                time.sleep(0.01)
            err = float(np.abs(
                follower.values() - primary.values()
            ).max())
            assert 0 < err < 5e-3  # tracks, NOT bitwise (documented)
            assert ship.repl_bytes_saved > 0
            # promotion path: catch up, promote, then a restart
            # REPLAYS the quantized log bitwise (record_deltas is
            # deterministic) — the promoted-log durability story
            follower.catch_up()
            follower.promote_to_primary(1)
            before = follower.values().copy()
            follower.restart()
            assert np.array_equal(follower.values(), before)
            # verify-against-log audits a quantized log bitwise too
            from flink_parameter_server_tpu.replication.failover import (
                verify_against_log,
            )

            assert verify_against_log(follower)
        finally:
            ship.stop()
            srv.stop()
            follower.close()
            primary.close()

    def test_invalid_enc_rejected(self):
        from flink_parameter_server_tpu.replication.shipper import (
            WALShipper,
            _FollowerQueue,
        )

        with pytest.raises(ValueError, match="enc"):
            WALShipper(
                None, ("127.0.0.1", 1), _FollowerQueue(),
                registry=False, enc="zstd",
            )


# ---------------------------------------------------------------------------
# driver integration: aggregation tree + BSP carve-out
# ---------------------------------------------------------------------------


def _mf_driver(wire_format, push_aggregate, num_workers, registry=False):
    from flink_parameter_server_tpu.cluster.driver import (
        ClusterConfig,
        ClusterDriver,
    )
    from flink_parameter_server_tpu.data.movielens import (
        synthetic_ratings,
    )
    from flink_parameter_server_tpu.data.streams import microbatches
    from flink_parameter_server_tpu.models.matrix_factorization import (
        OnlineMatrixFactorization,
        SGDUpdater,
    )
    from flink_parameter_server_tpu.utils.initializers import (
        ranged_random_factor,
    )

    cols = synthetic_ratings(48, 64, 6 * 64, seed=3)
    batches = list(microbatches(cols, 64))
    logic = OnlineMatrixFactorization(
        48, 4, updater=SGDUpdater(0.05), seed=1
    )
    driver = ClusterDriver(
        logic, capacity=64, value_shape=(4,),
        init_fn=ranged_random_factor(7, (4,)),
        config=ClusterConfig(
            num_shards=2, num_workers=num_workers, staleness_bound=0,
            wire_format=wire_format, push_aggregate=push_aggregate,
        ),
        registry=registry,
    )
    return driver, batches


class TestDriverIntegration:
    def test_aggregation_tree_one_push_per_shard_per_round(
        self, fresh_registry
    ):
        """The tree: push frames ÷ num_workers, parity allclose with
        the flat run, and the exactly-once ledger balances on the
        uplink (satellite 3's ledger audit)."""
        results = {}
        for label, agg in (("flat", False), ("tree", True)):
            reg = tm.MetricsRegistry(run_id=f"agg-{label}")
            tm.set_registry(reg)
            driver, batches = _mf_driver("b64", agg, 4, registry=reg)
            with driver:
                values = driver.run(batches).values
                acked = sum(
                    c.rows_pushed for c in driver._clients
                )
                pa = driver.last_push_aggregator
                if pa is not None:
                    acked += pa.client.rows_pushed
                applied = sum(
                    sh.rows_applied for sh in driver.shards
                )
            frames = 0
            for inst in reg.snapshot().get("net_frames_total", []):
                lb = inst["labels"]
                if (
                    lb.get("verb") == "push"
                    and lb.get("direction") == "out"
                    and lb.get("role") == "client"
                ):
                    frames += int(inst["value"] or 0)
            results[label] = {
                "values": values, "frames": frames,
                "acked": acked, "applied": applied,
                "fanin": (
                    None if pa is None else pa.last_fanin
                ),
            }
        flat, tree = results["flat"], results["tree"]
        assert tree["frames"] * 4 == flat["frames"]
        assert tree["acked"] == tree["applied"] > 0
        assert flat["acked"] == flat["applied"]
        assert np.allclose(
            flat["values"], tree["values"], atol=1e-4, rtol=1e-4
        )
        assert results["tree"]["fanin"] >= 1
        # the combine fan-in gauge is on the plane
        tm.set_registry(None)

    def test_bsp_carveout_bitwise(self):
        """Acceptance: the bound-0 arm configured "q8" lands BITWISE
        identical to "b64" — worker clients are downgraded to exact
        fp32 (single worker: deterministic fp32 scatter order)."""
        tables = {}
        for wf in ("q8", "b64"):
            driver, batches = _mf_driver(wf, False, 1)
            with driver:
                tables[wf] = driver.run(batches).values
                # the carve-out actually fired: no compressor on the
                # worker client
                assert driver._clients[0]._compressor is None
        assert np.array_equal(tables["q8"], tables["b64"])

    def test_non_bsp_driver_keeps_quantization(self):
        from flink_parameter_server_tpu.cluster.driver import (
            ClusterConfig,
            ClusterDriver,
        )
        from flink_parameter_server_tpu.models.matrix_factorization import (
            OnlineMatrixFactorization,
            SGDUpdater,
        )

        driver = ClusterDriver(
            OnlineMatrixFactorization(8, 4, updater=SGDUpdater(0.05)),
            capacity=64, value_shape=(4,),
            config=ClusterConfig(
                num_shards=1, num_workers=1, staleness_bound=2,
                wire_format="q8",
            ),
            registry=False,
        )
        with driver:
            assert driver._clients[0]._compressor is not None


# ---------------------------------------------------------------------------
# the mid-frame-RST corpus schedules over a quantized-enc connection
# ---------------------------------------------------------------------------


class TestTornQuantizedFrames:
    @pytest.mark.parametrize(
        "name", ["mid_frame_rst_pull", "mid_frame_rst_push"]
    )
    def test_corpus_schedule_replays_green_over_q8(
        self, name, tmp_path
    ):
        """Satellite 1: the committed mid-frame-RST schedules replayed
        with a QUANTIZED enc negotiated — a torn quantized frame (cut
        inside the header or the int8 payload) must dedupe exactly
        like f32: exactly-once ledger balanced, zero run errors.
        Parity is off because the quantized arm needs a non-zero bound
        (the BSP carve-out would downgrade it to fp32)."""
        from flink_parameter_server_tpu.nemesis import (
            load_corpus,
            run_scenario,
        )

        corpus = {s.name: s for s in load_corpus()}
        s = dataclasses.replace(
            corpus[name],
            name=f"{name}-q8",
            wire_format="q8",
            staleness_bound=2,
            parity=False,
        )
        report = run_scenario(s, wal_root=str(tmp_path))
        bad = [v for v in report.verdicts if not v.ok]
        assert report.ok, bad
        names = {v.name for v in report.verdicts}
        assert "exactly_once_ledger" in names


# ---------------------------------------------------------------------------
# tooling satellites: psctl bytes, lints
# ---------------------------------------------------------------------------


class TestTooling:
    def test_psctl_bytes_live_smoke(self, fresh_registry):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import psctl

        part, shards, servers, addrs = _mini_cluster(dim=4)
        try:
            c = ClusterClient(
                addrs, part, (4,), registry=fresh_registry,
                wire_format="q8", worker="w0",
            )
            _push_stream(c, 64, 4, rounds=6)
            with tm.TelemetryServer(fresh_registry) as tsrv:
                addr = f"{tsrv.host}:{tsrv.port}"
                buf = io.StringIO()
                with redirect_stdout(buf):
                    rc = psctl.main([
                        "bytes", "--metrics", addr,
                        "--interval", "0.2", "--iterations", "2",
                        "--raw",
                    ])
                assert rc == 0
                out = buf.getvalue()
                assert "psctl bytes" in out
                assert "compression: push saved" in out
                assert "push" in out
                # --json emits the machine payload once
                buf = io.StringIO()
                with redirect_stdout(buf):
                    rc = psctl.main(["bytes", "--metrics", addr,
                                     "--json"])
                assert rc == 0
                doc = json.loads(buf.getvalue())
                assert doc["compression_bytes_saved"] > 0
                assert "push" in doc["verbs"]
                assert doc["push_ratio"] is None or (
                    doc["push_ratio"] > 1.0
                )
            c.close()
        finally:
            for s in servers:
                s.stop()

    def test_compression_component_lints(self, fresh_registry):
        from tools.check_metric_lines import (
            KNOWN_COMPONENTS,
            check_lines,
        )

        assert "compression" in KNOWN_COMPONENTS
        fresh_registry.counter(
            "compression_bytes_saved_total", component="compression"
        ).inc(5)
        line = fresh_registry.emit(sink=io.StringIO())
        assert check_lines([line]) == []
        # a typo'd component still fails
        bad = tm.MetricsRegistry(run_id="x")
        bad.counter("foo_total", component="compresion").inc()
        assert check_lines([bad.emit(sink=io.StringIO())])

    def test_fpsanalyze_catalogs_compression_docs(self):
        from tools.fpsanalyze.rules_drift import default_drift_config

        cfg = default_drift_config(REPO)
        assert "docs/compression.md" in cfg.catalog_doc_files
        assert "compression" in cfg.known_components
