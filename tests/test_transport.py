"""Binary framed transport (utils/frames.py) + multiprocess shards.

Covers ISSUE 13's tentpole end to end:

  * the frame codec — round trips, zero-copy views, bf16, malformed
    frames rejected;
  * per-connection negotiation + cross-version compat — new client vs
    old server downgrades on the first ``err bad-request``, old client
    vs new server is served unchanged, and BSP parity is BITWISE
    across both framings;
  * everything that must ride the new frames: trace tokens, lease
    grants + piggybacked invalidations, priority shedding decided on
    the header alone, NetMeter byte accounting, the
    ``conns``/ConnStats proto+enc rollout surface;
  * the selectors event loop — mixed-framing pipelining in order,
    overflow discipline, clean stop;
  * mid-frame RST inside a binary HEADER and inside a PAYLOAD, both
    directions, with the (pid, id) ledger auditing the replay;
  * shard worker processes — bitwise proc-vs-thread parity, WAL
    rebuild across a kill, and the spawn-grace dial window;
  * the budget-phase lint lockstep.
"""
from __future__ import annotations

import json
import socket
import threading
import time

import numpy as np
import pytest

from flink_parameter_server_tpu.cluster.client import (
    ClusterClient,
    ShardConnection,
)
from flink_parameter_server_tpu.cluster.partition import RangePartitioner
from flink_parameter_server_tpu.cluster.shard import ParamShard, ShardServer
from flink_parameter_server_tpu.utils import frames as binf
from flink_parameter_server_tpu.utils.net import PeerHalfClosed

pytestmark = pytest.mark.cluster


# ---------------------------------------------------------------------------
# codec
# ---------------------------------------------------------------------------


class TestFrameCodec:
    def test_request_round_trip_all_fields(self):
        ids = np.arange(7, dtype=np.int64) * 3
        rows = np.arange(7 * 4, dtype=np.float32).reshape(7, 4)
        buf = binf.encode_request(
            binf.VERB_IDS["push"],
            ids=ids,
            payload=binf.rows_to_payload(rows, binf.ENC_F32),
            enc=binf.ENC_F32,
            epoch=5,
            priority=2,
            tlvs=[(binf.T_PID, b"p.1"), (binf.T_SESS, b"s.9")],
        )
        f = binf.decode(buf, kind="request")
        assert f.verb_name == "push"
        assert f.aux == 5 and f.flag == 2
        assert np.array_equal(np.asarray(f.ids), ids)
        assert f.tlv_str(binf.T_PID) == "p.1"
        assert f.tlv_str(binf.T_SESS) == "s.9"
        got = binf.rows_from_payload(f.payload, (4,), f.enc)
        assert np.array_equal(got, rows)

    def test_zero_copy_views(self):
        """ids/payload decode as VIEWS over the receive buffer — the
        no-b64, no-repr() receive path the rework exists for."""
        ids = np.arange(64, dtype=np.int64)
        rows = np.ones((64, 2), np.float32)
        buf = binf.encode_request(
            binf.VERB_IDS["push"], ids=ids,
            payload=binf.rows_to_payload(rows, binf.ENC_F32),
        )
        f = binf.decode(buf, kind="request")
        assert f.ids.base is not None  # a view, not a copy
        vals = binf.rows_from_payload(f.payload, (2,), f.enc)
        assert vals.base is not None
        assert not vals.flags.writeable  # read-only by contract

    def test_response_round_trip_and_error(self):
        buf = binf.encode_response(
            binf.VERB_IDS["pull"], aux=9, n=3,
            payload=b"\x00" * 12, enc=binf.ENC_F32,
            tlvs=[(binf.T_INV, b"1,2")],
        )
        f = binf.decode(buf, kind="response")
        assert f.flag == binf.STATUS_OK and f.aux == 9 and f.n == 3
        assert f.tlv_str(binf.T_INV) == "1,2"
        err = binf.decode(
            binf.error_response(
                binf.VERB_IDS["push"], binf.STATUS_STALE_EPOCH, "old",
                tlvs=[(binf.T_EPOCH, b"4")],
            ),
            kind="response",
        )
        assert err.status_name == "stale-epoch"
        assert err.tlv_str(binf.T_ERR) == "old"
        assert err.tlv_int(binf.T_EPOCH) == 4

    def test_decode_split_equivalent(self):
        buf = binf.encode_response(
            binf.VERB_IDS["pull"], n=1, payload=b"abcd",
            enc=binf.ENC_RAW,
        )
        a = binf.decode(buf, kind="response")
        b = binf.decode_split(
            buf[: binf.HEADER_SIZE], buf[binf.HEADER_SIZE:],
            kind="response",
        )
        assert bytes(a.payload) == bytes(b.payload) == b"abcd"
        assert a.n == b.n and a.flag == b.flag

    def test_bf16_round_trip_truncation(self):
        rows = np.linspace(-3, 3, 64, dtype=np.float32).reshape(16, 4)
        got = binf.rows_from_payload(
            binf.rows_to_payload(rows, binf.ENC_BF16), (4,),
            binf.ENC_BF16,
        )
        # bf16 keeps 7 explicit mantissa bits and the encode
        # TRUNCATES: relative error bounded by 2^-7
        nz = rows != 0
        rel = np.abs(got[nz] - rows[nz]) / np.abs(rows[nz])
        assert float(rel.max()) < 2 ** -7
        # half the bytes of fp32
        assert len(binf.rows_to_payload(rows, binf.ENC_BF16)) == (
            len(binf.rows_to_payload(rows, binf.ENC_F32)) // 2
        )

    def test_malformed_frames_rejected(self):
        good = binf.encode_request(
            binf.VERB_IDS["pull"], ids=np.arange(4)
        )
        with pytest.raises(binf.FrameError):
            binf.decode(b"\x00" + good[1:], kind="request")  # magic
        with pytest.raises(binf.FrameError):
            binf.decode(good[:10], kind="request")  # short
        bad_ver = bytearray(good)
        bad_ver[2] = 9
        with pytest.raises(binf.FrameError):
            binf.decode(bytes(bad_ver), kind="request")
        # id section longer than the body
        hdr = bytearray(good)
        hdr[16:20] = (1 << 20).to_bytes(4, "little")  # n field
        with pytest.raises(binf.FrameError):
            binf.decode(bytes(hdr), kind="request")
        # length prefix disagrees with the buffer
        with pytest.raises(binf.FrameError):
            binf.decode(good + b"x", kind="request")

    def test_link_helpers(self):
        buf = binf.encode_request(binf.VERB_IDS["lease"], ids=np.arange(2))
        assert binf.peek_is_binary(buf)
        assert not binf.peek_is_binary(b"pull 1,2 b64\n")
        assert binf.frame_length(buf[:10]) is None
        assert binf.frame_length(buf) == len(buf)
        assert binf.peek_verb_name(buf) == "lease"
        verb, enc, flag, total = binf.peek_header(buf)
        assert verb == binf.VERB_IDS["lease"] and total == len(buf)


# ---------------------------------------------------------------------------
# negotiation + cross-version compat
# ---------------------------------------------------------------------------


class _OldShardServer(ShardServer):
    """A PRE-BINARY server: no hello handler, no binary dispatch —
    what a not-yet-upgraded shard answers mid-rollout."""

    def _execute(self, line: str) -> str:
        if line.split()[0].lower() == "hello":
            raise ValueError("unknown command 'hello'")
        return super()._execute(line)

    def respond_frame(self, data):  # pragma: no cover — must not run
        raise AssertionError("old server must never see binary frames")


def _mini_cluster(n_shards=2, *, server_cls=ShardServer, dim=4,
                  capacity=64):
    part = RangePartitioner(capacity, n_shards)
    shards = [
        ParamShard(i, part, (dim,), registry=False)
        for i in range(n_shards)
    ]
    servers = [server_cls(s).start() for s in shards]
    addrs = [(srv.host, srv.port) for srv in servers]
    return part, shards, servers, addrs


class TestNegotiationCompat:
    def test_new_client_new_server_negotiates_binary(self):
        part, shards, servers, addrs = _mini_cluster()
        try:
            c = ClusterClient(addrs, part, (4,), registry=False)
            ids = np.arange(64, dtype=np.int64)
            base = c.pull_batch(ids)
            c.push_batch(ids, np.ones((64, 4), np.float32))
            after = c.pull_batch(ids)
            assert np.array_equal(after, base + 1)
            assert all(cc.proto == "bin" for cc in c._conns.values())
            # the rollout surface: ConnStats reports proto + enc
            table = servers[0].conn_table()
            assert table and table[0]["proto"] == "bin"
            assert table[0]["enc"] == "f32"
            # ... and the conns wire verb carries the same ledger
            resp = c._conns[addrs[0]].request("conns")
            doc = json.loads(resp[3:])
            assert doc[0]["proto"] == "bin"
            c.close()
        finally:
            for s in servers:
                s.stop()

    def test_new_client_old_server_downgrades_to_line(self):
        part, shards, servers, addrs = _mini_cluster(
            server_cls=_OldShardServer
        )
        try:
            c = ClusterClient(addrs, part, (4,), registry=False)
            ids = np.arange(64, dtype=np.int64)
            c.push_batch(ids, np.full((64, 4), 2.0, np.float32))
            got = c.pull_batch(ids)
            assert np.array_equal(
                got, np.full((64, 4), 2.0, np.float32)
            )
            assert all(cc.proto == "line" for cc in c._conns.values())
            c.close()
        finally:
            for s in servers:
                s.stop()

    def test_old_client_new_server_unchanged(self):
        part, shards, servers, addrs = _mini_cluster()
        try:
            c = ClusterClient(
                addrs, part, (4,), registry=False, wire_proto="line"
            )
            ids = np.arange(64, dtype=np.int64)
            c.push_batch(ids, np.full((64, 4), 3.0, np.float32))
            assert np.array_equal(
                c.pull_batch(ids), np.full((64, 4), 3.0, np.float32)
            )
            table = servers[0].conn_table()
            assert all(t["proto"] == "line" for t in table)
            c.close()
        finally:
            for s in servers:
                s.stop()

    def test_bitwise_parity_line_vs_binary(self):
        """The same pushed deltas land BITWISE identically over both
        framings — the cross-version parity pin."""
        rng = np.random.default_rng(3)
        deltas = rng.normal(0, 1, (64, 4)).astype(np.float32)
        tables = {}
        for proto in ("line", "auto"):
            part, shards, servers, addrs = _mini_cluster()
            try:
                c = ClusterClient(
                    addrs, part, (4,), registry=False, wire_proto=proto
                )
                ids = np.arange(64, dtype=np.int64)
                for _ in range(3):
                    c.push_batch(ids, deltas)
                tables[proto] = c.pull_batch(ids)
                c.close()
            finally:
                for s in servers:
                    s.stop()
        assert np.array_equal(tables["line"], tables["auto"])


# ---------------------------------------------------------------------------
# everything riding the new frames
# ---------------------------------------------------------------------------


class TestBinaryDataPlane:
    def test_lease_and_inv_piggyback_over_binary(self):
        from flink_parameter_server_tpu.hotcache import (
            HotRowCache,
            StaticHotSet,
        )

        part, shards, servers, addrs = _mini_cluster(n_shards=1)
        try:
            reader = ClusterClient(addrs, part, (4,), registry=False)
            reader.attach_hotcache(
                HotRowCache(8, registry=False), StaticHotSet([1, 2, 3])
            )
            writer = ClusterClient(addrs, part, (4,), registry=False)
            ids = np.asarray([1, 2, 3], np.int64)
            reader.pull_batch(ids)  # leases granted, cache filled
            assert reader.leases_acquired == 3
            assert shards[0].leases.active_leases() == 3
            # another session writes the keys: the next binary response
            # to the reader must carry the T_INV piggyback
            writer.push_batch(ids, np.ones((3, 4), np.float32))
            reader.pull_batch(np.asarray([40], np.int64))
            assert reader.hotcache.lookup(ids) == {}  # invalidated
            reader.close()
            writer.close()
        finally:
            for s in servers:
                s.stop()

    def test_trace_tokens_ride_binary_frames(self):
        from flink_parameter_server_tpu.telemetry.spans import SpanTracer

        part, shards, servers, addrs = _mini_cluster(n_shards=1)
        srv_tracer = SpanTracer(process="shard-0")
        servers[0].tracer = srv_tracer
        try:
            client_tracer = SpanTracer(process="client")
            c = ClusterClient(
                addrs, part, (4,), registry=False, tracer=client_tracer
            )
            c.pull_batch(np.arange(8, dtype=np.int64))
            assert all(cc.proto == "bin" for cc in c._conns.values())
            client_ids = {
                s["trace_id"] for s in client_tracer.spans()
                if s["name"] == "pull_batch"
            }
            server_spans = [
                s for s in srv_tracer.spans()
                if s["name"] == "shard.pull"
            ]
            assert server_spans
            assert {s["trace_id"] for s in server_spans} <= client_ids
            c.close()
        finally:
            for s in servers:
                s.stop()

    def test_netmeter_counts_binary_frames(self):
        from flink_parameter_server_tpu.telemetry.registry import (
            MetricsRegistry,
        )

        reg = MetricsRegistry()
        part = RangePartitioner(32, 1)
        shard = ParamShard(0, part, (2,), registry=False)
        srv = ShardServer(shard)
        srv.meter._registry = reg  # server-role ledger into this reg
        srv.start()
        try:
            c = ClusterClient(
                [(srv.host, srv.port)], part, (2,), registry=False
            )
            c.pull_batch(np.arange(32, dtype=np.int64))
            got = {
                (i.labels.get("direction"), i.labels.get("verb")): i.value
                for i in reg.instruments()
                if i.name == "net_bytes_total"
            }
            assert got.get(("in", "pull"), 0) > 0
            assert got.get(("out", "pull"), 0) > 0
            c.close()
        finally:
            srv.stop()

    def test_priority_shed_on_header_alone(self):
        from flink_parameter_server_tpu.loadgen.overload import (
            OverloadGuard,
        )

        part = RangePartitioner(32, 1)
        shard = ParamShard(0, part, (2,), registry=False)
        srv = ShardServer(
            shard, overload=OverloadGuard(
                sheddable_depth=1, read_depth=2, registry=False
            ),
        )
        ids = np.arange(4, dtype=np.int64)
        pull2 = binf.encode_request(
            binf.VERB_IDS["pull"], ids=ids, priority=2
        )
        push0 = binf.encode_request(
            binf.VERB_IDS["push"], ids=ids,
            payload=binf.rows_to_payload(np.ones((4, 2), np.float32)),
            priority=0,
        )
        # inflate the live depth so the guard's thresholds bite
        with shard._depth_lock:
            shard._active_requests = 5
        try:
            shed = binf.decode(
                srv.respond_frame(pull2), kind="response"
            )
            assert shed.flag == binf.STATUS_OVERLOADED
            ok = binf.decode(srv.respond_frame(push0), kind="response")
            assert ok.flag == binf.STATUS_OK  # writes never shed
        finally:
            with shard._depth_lock:
                shard._active_requests = 0

    def test_binary_error_mapping(self):
        part = RangePartitioner(32, 1)
        shard = ParamShard(0, part, (2,), registry=False)
        srv = ShardServer(shard)
        shard.retire(7)  # epoch bumped; everything frozen
        push = binf.encode_request(
            binf.VERB_IDS["push"], ids=np.arange(2),
            payload=binf.rows_to_payload(np.ones((2, 2), np.float32)),
            epoch=0,
        )
        resp = binf.decode(srv.respond_frame(push), kind="response")
        assert resp.status_name == "stale-epoch"
        assert resp.tlv_int(binf.T_EPOCH) == 7
        bad = binf.decode(
            srv.respond_frame(b"\xb1\xf5garbage-header-bytes...."),
            kind="response",
        )
        assert bad.status_name == "bad-request"

    def test_repl_frame_rides_raw_bytes(self):
        from flink_parameter_server_tpu.resilience.wal import (
            decode_frame_bytes,
            encode_frame_bytes,
        )

        payload = {"ids": np.arange(3), "deltas": np.ones((3, 2))}
        raw = encode_frame_bytes(4, 1, payload)
        rec = decode_frame_bytes(raw)
        assert rec.start_step == 4 and rec.n_steps == 1
        assert np.array_equal(rec.payload["ids"], np.arange(3))
        with pytest.raises(ValueError):
            decode_frame_bytes(raw[:-2])  # CRC must catch truncation


# ---------------------------------------------------------------------------
# the selectors event loop
# ---------------------------------------------------------------------------


class TestEventLoop:
    def test_mixed_framing_pipelined_in_order(self):
        part, shards, servers, addrs = _mini_cluster(n_shards=1)
        try:
            conn = ShardConnection(*addrs[0], negotiate=True)
            assert conn.proto == "bin"
            ids = np.arange(4, dtype=np.int64)
            reqs = [
                binf.encode_request(binf.VERB_IDS["pull"], ids=ids),
                "stats",
                binf.encode_request(binf.VERB_IDS["pull"], ids=ids),
                "flush",
            ]
            resps = conn.request_many(reqs)
            assert isinstance(resps[0], binf.Frame) and resps[0].n == 4
            assert isinstance(resps[1], str) and resps[1].startswith(
                "ok {"
            )
            assert isinstance(resps[2], binf.Frame)
            assert resps[3].startswith("ok pushes=")
            conn.close()
        finally:
            for s in servers:
                s.stop()

    def test_line_overflow_still_answered_and_closed(self):
        part, shards, servers, addrs = _mini_cluster(n_shards=1)
        servers[0].max_line_bytes = 1 << 10
        try:
            with socket.create_connection(addrs[0], timeout=5) as s:
                s.sendall(b"pull " + b"1," * 2000)  # no newline, 4KB+
                s.settimeout(5)
                data = s.recv(1 << 16)
                assert b"err bad-request: line too long" in data
                assert s.recv(1 << 16) == b""  # closed after
        finally:
            for s in servers:
                s.stop()

    def test_binary_overflow_rejected(self):
        part, shards, servers, addrs = _mini_cluster(n_shards=1)
        servers[0].max_line_bytes = 1 << 10
        try:
            huge = binf.encode_request(
                binf.VERB_IDS["push"], ids=np.arange(4),
                payload=b"\x00" * (1 << 11),
            )
            with socket.create_connection(addrs[0], timeout=5) as s:
                s.sendall(huge)
                s.settimeout(5)
                buf = b""
                while len(buf) < binf.HEADER_SIZE:
                    d = s.recv(1 << 16)
                    if not d:
                        break
                    buf += d
                f = binf.decode(
                    buf[: binf.frame_length(buf)], kind="response"
                )
                assert f.status_name == "bad-request"
        finally:
            for s in servers:
                s.stop()

    def test_stop_joins_dispatchers_and_clears_conns(self):
        part, shards, servers, addrs = _mini_cluster(n_shards=1)
        conns = [
            ShardConnection(*addrs[0], negotiate=True) for _ in range(4)
        ]
        for c in conns:
            c.request_many([binf.encode_request(
                binf.VERB_IDS["pull"], ids=np.arange(2)
            )])
        srv = servers[0]
        assert srv.live_connections() == 4
        srv.stop()
        assert srv.live_connections() == 0
        deadline = time.time() + 5
        while time.time() < deadline and any(
            t.is_alive() for t in srv._handlers
        ):
            time.sleep(0.01)
        assert not any(t.is_alive() for t in srv._handlers)
        for c in conns:
            c.close()

    def test_idle_connection_parks_then_resumes(self):
        """A connection idle past the linger window hands back to the
        selector and must still answer the next request."""
        part, shards, servers, addrs = _mini_cluster(n_shards=1)
        servers[0].LINGER_S = 0.05
        try:
            conn = ShardConnection(*addrs[0], negotiate=True)
            req = binf.encode_request(
                binf.VERB_IDS["pull"], ids=np.arange(2)
            )
            assert conn.request_many([req])[0].flag == binf.STATUS_OK
            time.sleep(0.3)  # well past the linger: parked in selector
            assert conn.request_many([req])[0].flag == binf.STATUS_OK
            conn.close()
        finally:
            for s in servers:
                s.stop()


# ---------------------------------------------------------------------------
# mid-frame RST inside binary header / payload (the nemesis satellite)
# ---------------------------------------------------------------------------


class TestBinaryMidFrameRST:
    def _proxied(self, shard_dim=2, wal_dir=None):
        from flink_parameter_server_tpu.nemesis.proxy import ChaosProxy

        part = RangePartitioner(32, 1)
        shard = ParamShard(
            0, part, (shard_dim,), registry=False, wal_dir=wal_dir
        )
        srv = ShardServer(shard).start()
        proxy = ChaosProxy(srv.host, srv.port, registry=False).start()
        return part, shard, srv, proxy

    @pytest.mark.parametrize("cut", ["header", "payload"])
    def test_response_torn_inside_binary_frame(self, cut):
        part, shard, srv, proxy = self._proxied()
        try:
            conn = ShardConnection(
                proxy.host, proxy.port, negotiate=True, timeout=5
            )
            assert conn.proto == "bin"
            proxy.inject_once("truncate_rst", "s2c", cut=cut)
            with pytest.raises((PeerHalfClosed, OSError)):
                conn.request_many([binf.encode_request(
                    binf.VERB_IDS["pull"], ids=np.arange(8)
                )])
            assert proxy.faults.get("truncate_rst") == 1
            conn.close()
        finally:
            proxy.stop()
            srv.stop()

    @pytest.mark.parametrize("cut", ["header", "payload"])
    def test_push_torn_request_replays_exactly_once(self, cut, tmp_path):
        """The dedupe audit: a binary push torn mid-frame (header or
        payload) and replayed with the same pid applies EXACTLY once —
        the (pid, id) ledger absorbs the ambiguity either way."""
        part, shard, srv, proxy = self._proxied(
            wal_dir=str(tmp_path / f"wal-{cut}")
        )
        try:
            ids = np.arange(8, dtype=np.int64)
            deltas = np.ones((8, 2), np.float32)
            frame = binf.encode_request(
                binf.VERB_IDS["push"], ids=ids,
                payload=binf.rows_to_payload(deltas),
                tlvs=[(binf.T_PID, b"pid.42")],
            )
            conn = ShardConnection(
                proxy.host, proxy.port, negotiate=True, timeout=5
            )
            proxy.inject_once("truncate_rst", "c2s", cut=cut)
            with pytest.raises((PeerHalfClosed, OSError)):
                conn.request_many([frame])
            conn.close()
            # the replay (fresh connection, same pid)
            conn2 = ShardConnection(
                proxy.host, proxy.port, negotiate=True, timeout=5
            )
            resp = conn2.request_many([frame])[0]
            assert resp.flag == binf.STATUS_OK
            # and a duplicate retry after the ack: acked, not re-applied
            resp2 = conn2.request_many([frame])[0]
            assert resp2.flag == binf.STATUS_OK
            vals = shard.pull(ids)
            assert np.array_equal(vals, deltas)  # exactly once
            conn2.close()
        finally:
            proxy.stop()
            srv.stop()

    def test_proxy_reassembles_binary_frames(self):
        """Binary frames (which may contain 0x0A bytes and end without
        a newline) relay through the byte-level proxy intact."""
        part, shard, srv, proxy = self._proxied()
        try:
            conn = ShardConnection(
                proxy.host, proxy.port, negotiate=True, timeout=5
            )
            # 10 == ord("\n"): the id section embeds newline bytes
            ids = np.asarray([10, 26, 10], np.int64)
            resp = conn.request_many([binf.encode_request(
                binf.VERB_IDS["pull"], ids=ids
            )])[0]
            assert resp.flag == binf.STATUS_OK and resp.n == 3
            conn.close()
        finally:
            proxy.stop()
            srv.stop()


# ---------------------------------------------------------------------------
# shard worker processes
# ---------------------------------------------------------------------------


class TestShardProcesses:
    def test_proc_vs_thread_bitwise_parity(self):
        from flink_parameter_server_tpu.cluster.driver import (
            ClusterConfig,
            ClusterDriver,
        )
        from flink_parameter_server_tpu.models.matrix_factorization import (
            OnlineMatrixFactorization,
            SGDUpdater,
        )

        rng = np.random.default_rng(0)
        batches = [{
            "user": rng.integers(0, 16, 32).astype(np.int32),
            "item": rng.integers(0, 32, 32).astype(np.int32),
            "rating": rng.normal(0, 1, 32).astype(np.float32),
        } for _ in range(3)]
        init = {"kind": "hashed_uniform", "scale": 0.1, "seed": 7}
        tables = {}
        for procs in (True, False):
            logic = OnlineMatrixFactorization(
                16, 4, updater=SGDUpdater(0.05), seed=1
            )
            driver = ClusterDriver(
                logic, capacity=32, value_shape=(4,),
                config=ClusterConfig(
                    num_shards=2, num_workers=1, shard_procs=procs,
                    proc_init=init, profile=False,
                ),
                registry=False,
            )
            with driver:
                r = driver.run(batches)
            tables[procs] = r.values
            if procs:
                # stats crossed the wire from the child process
                assert r.shard_stats[0]["pushes"] == 3
        assert np.array_equal(tables[True], tables[False])

    def test_kill_and_respawn_rebuilds_from_wal(self, tmp_path):
        from flink_parameter_server_tpu.cluster.procs import (
            ShardProcSpec,
            ShardProcess,
        )

        spec = ShardProcSpec(
            shard_id=0, partition="range", capacity=16, num_shards=1,
            value_shape=(2,), wal_dir=str(tmp_path / "wal"),
        )
        proc = ShardProcess(spec).wait_ready()
        part = RangePartitioner(16, 1)
        c = ClusterClient(
            [(proc.host, proc.port)], part, (2,), registry=False
        )
        ids = np.arange(16, dtype=np.int64)
        c.push_batch(ids, np.full((16, 2), 5.0, np.float32))
        before = c.pull_batch(ids)
        c.flush()  # the explicit durability point: fsync the WAL
        c.close()
        proc.kill()  # SIGKILL — no drain; the WAL is the durable half
        assert not proc.running
        proc2 = ShardProcess(spec).wait_ready()
        try:
            c2 = ClusterClient(
                [(proc2.host, proc2.port)], part, (2,),
                registry=False, spawn_grace_s=5.0,
            )
            after = c2.pull_batch(ids)
            assert np.array_equal(after, before)  # bitwise rebuild
            c2.close()
        finally:
            proc2.stop()

    def test_spawn_grace_dial_retries_refused(self):
        """The _await_retry interaction fix: a dial racing a child's
        bind retries inside the grace window instead of failing with
        the conn-class reject that spends storm retry budget."""
        # reserve a port, release it, and bring the server up LATE
        probe = socket.socket()
        probe.bind(("127.0.0.1", 0))
        host, port = probe.getsockname()
        probe.close()
        part = RangePartitioner(8, 1)
        state = {}

        def late_start():
            time.sleep(0.4)
            shard = ParamShard(0, part, (2,), registry=False)
            state["srv"] = ShardServer(shard, host, port).start()

        t = threading.Thread(target=late_start, daemon=True)
        t.start()
        c = ClusterClient(
            [(host, port)], part, (2,), registry=False,
            spawn_grace_s=5.0,
        )
        try:
            got = c.pull_batch(np.arange(8, dtype=np.int64))
            assert got.shape == (8, 2)
        finally:
            c.close()
            t.join()
            state["srv"].stop()

    def test_no_grace_fails_fast(self):
        probe = socket.socket()
        probe.bind(("127.0.0.1", 0))
        addr = probe.getsockname()
        probe.close()
        part = RangePartitioner(8, 1)
        c = ClusterClient([addr], part, (2,), registry=False)
        with pytest.raises(OSError):
            c.pull_batch(np.arange(8, dtype=np.int64))
        c.close()

    def test_elastic_rejects_shard_procs(self):
        from flink_parameter_server_tpu.elastic.controller import (
            ElasticClusterConfig,
            ElasticClusterDriver,
        )
        from flink_parameter_server_tpu.models.matrix_factorization import (
            OnlineMatrixFactorization,
            SGDUpdater,
        )

        driver = ElasticClusterDriver(
            OnlineMatrixFactorization(8, 2, updater=SGDUpdater(0.05)),
            capacity=16, value_shape=(2,),
            config=ElasticClusterConfig(
                num_shards=1, num_workers=1, shard_procs=True,
            ),
            registry=False,
        )
        with pytest.raises(NotImplementedError):
            driver.start()
        driver.stop()


# ---------------------------------------------------------------------------
# tools
# ---------------------------------------------------------------------------


class TestToolsAndArtifacts:
    def test_budget_phase_vocabulary_lockstep(self):
        from flink_parameter_server_tpu.telemetry.profiler import PHASES
        from tools.check_metric_lines import KNOWN_BUDGET_PHASES

        assert KNOWN_BUDGET_PHASES == frozenset(PHASES)

    def test_budget_lint_rejects_unknown_phase(self):
        from tools.check_metric_lines import check_budget

        doc = {
            "ts": 1.0, "run_id": "r", "budgets": {
                "pull": {"phases": [
                    {"phase": "warp_drive", "p50_ms": 1.0, "pct": 100.0}
                ]},
            },
        }
        bad = check_budget(doc)
        assert any("warp_drive" in b for b in bad)

    def test_psctl_conns_renders_proto_column(self, capsys):
        import argparse

        from tools.psctl import cmd_conns

        part, shards, servers, addrs = _mini_cluster(n_shards=1)
        try:
            c = ClusterClient(addrs, part, (4,), registry=False)
            c.pull_batch(np.arange(8, dtype=np.int64))
            args = argparse.Namespace(
                shards=f"{addrs[0][0]}:{addrs[0][1]}", metrics=None
            )
            assert cmd_conns(args) == 0
            out = capsys.readouterr().out
            assert "proto" in out and "bin" in out
            c.close()
        finally:
            for s in servers:
                s.stop()
