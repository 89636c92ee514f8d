"""shmem/ — the shared-memory transport (docs/shmem.md).

What is pinned here, and why it is the right oracle:

  * **ring edge cases** — wraparound straddle (K_WRAP + the implicit
    skip rule), full-ring backpressure, borrowed-views-pin-the-
    producer, the seeded torn-commit recovery (a reader must never
    adopt a torn 8-byte index), scribble → RingCorruption;
  * **the bell** — the process-local wakeup goes shared exactly when
    both ring ends live in one process, and publishes ring it only
    for a PARKED peer (the hot-path elision);
  * **negotiation** — ``hello shm v=1`` lands proto=shm end to end
    (client attr, server ConnStats, psctl column), and every refusal
    path (server opt-out, chaos-proxy splice point, non-local peer)
    falls back to binary TCP on the SAME connection, counted;
  * **reader-crash-while-borrowing** — a stale-heartbeat client with
    the response ring full is RECLAIMED after ``SHM_RECLAIM_S``, not
    waited on forever;
  * **BSP parity** — MF and PA cluster runs through ``wire_proto=
    "shm"`` equal the TCP runs BITWISE: the rings carry the same
    frames, so any divergence is a transport bug, not float noise;
  * **no segment leaks** — a full connect/pull/close cycle in a fresh
    interpreter leaves /dev/shm clean and the resource tracker quiet.

Everything here stands down automatically where /dev/shm is missing
(conftest.py skips the ``shmem`` marker).
"""
import argparse
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from flink_parameter_server_tpu import telemetry as tm
from flink_parameter_server_tpu.cluster.client import (
    ClusterClient,
    ShardConnection,
)
from flink_parameter_server_tpu.cluster.partition import RangePartitioner
from flink_parameter_server_tpu.cluster.shard import ParamShard, ShardServer
from flink_parameter_server_tpu.shmem.channel import (
    ShmShardConnection,
    shm_usable,
)
from flink_parameter_server_tpu.shmem.doorbell import Doorbell
from flink_parameter_server_tpu.shmem.ring import (
    HDR_SIZE,
    K_FRAME,
    K_LINE,
    RingClosed,
    RingCorruption,
    RingTimeout,
    ShmRing,
    _OFF_HEAD,
    _U64,
)
from flink_parameter_server_tpu.utils import frames as binf

pytestmark = pytest.mark.shmem

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def fresh_registry():
    reg = tm.MetricsRegistry(run_id="test-shmem")
    tm.set_registry(reg)
    yield reg
    tm.set_registry(None)


def _mini_cluster(n_shards=2, *, dim=4, capacity=64, **server_kw):
    part = RangePartitioner(capacity, n_shards)
    shards = [
        ParamShard(i, part, (dim,), registry=False)
        for i in range(n_shards)
    ]
    servers = [ShardServer(s, **server_kw).start() for s in shards]
    addrs = [(srv.host, srv.port) for srv in servers]
    return part, shards, servers, addrs


# ---------------------------------------------------------------------------
# ring edge cases
# ---------------------------------------------------------------------------


class TestRing:
    def test_round_trip_both_kinds_and_depth(self):
        r = ShmRing.create(4096)
        try:
            assert r.depth() == 0
            r.produce(K_LINE, b"stats")
            r.produce(K_FRAME, b"\x01\x02\x03")
            assert r.depth() > 0
            kind, view = r.consume(timeout=1.0)
            assert (kind, bytes(view)) == (K_LINE, b"stats")
            assert r.borrowed() > 0
            kind, view = r.consume(timeout=1.0)
            assert (kind, bytes(view)) == (K_FRAME, b"\x01\x02\x03")
            view = None
            r.release()
            assert r.borrowed() == 0
            assert r.depth() == 0
        finally:
            r.close()
            r.unlink()

    def test_wraparound_straddle_preserves_every_byte(self):
        """300 seeded variable-size records through a 256-byte ring:
        the write position laps the ring dozens of times, exercising
        both the K_WRAP marker (record would straddle the edge) and
        the implicit skip (less than a header left at the edge) —
        every payload must come back byte for byte, in order."""
        rng = np.random.default_rng(0)
        r = ShmRing.create(256)
        try:
            for i, size in enumerate(rng.integers(1, 121, 300)):
                payload = bytes([i % 251]) * int(size)
                kind = K_FRAME if i % 2 else K_LINE
                r.produce(kind, payload, timeout=1.0)
                got_kind, view = r.consume(timeout=1.0)
                assert got_kind == kind
                assert bytes(view) == payload, f"record {i}"
                view = None
                r.release()
            # the loop really wrapped: 300 records x >=9 bytes >> 256
            assert r._wpos > 10 * 256
        finally:
            r.close()
            r.unlink()

    def test_full_ring_backpressure_and_borrow_pin(self):
        """A full ring times the producer out; consuming WITHOUT
        releasing must keep it blocked (the borrowed view pins those
        bytes); release frees it."""
        r = ShmRing.create(128)
        try:
            p1, p2 = b"a" * 56, b"b" * 56  # 64-byte records: 2 fill it
            r.produce(K_FRAME, p1)
            r.produce(K_FRAME, p2)
            with pytest.raises(RingTimeout):
                r.produce(K_FRAME, b"c" * 56, timeout=0.05)
            _, view = r.consume(timeout=1.0)
            assert bytes(view) == p1
            # consumed but NOT released: the producer stays off
            assert r.borrowed() == 64
            with pytest.raises(RingTimeout):
                r.produce(K_FRAME, b"c" * 56, timeout=0.05)
            view = None
            r.release()
            r.produce(K_FRAME, b"c" * 56, timeout=1.0)
            _, v2 = r.consume(timeout=1.0)
            _, v3 = r.consume(timeout=1.0)
            assert bytes(v2) == p2 and bytes(v3) == b"c" * 56
            v2 = v3 = None
            r.release()
        finally:
            r.close()
            r.unlink()

    def test_torn_commit_recovery_seeded(self):
        """The seqlock pin: a reader NEVER adopts a torn index.  The
        head's sequence byte is forced odd (writer mid-publish) with a
        garbage value underneath; the reader must spin straight past
        the garbage and return only the value published with the even
        sequence byte."""
        r = ShmRing.create(1024)
        try:
            r._write_idx(_OFF_HEAD, 42)
            buf = r.buf
            s = buf[_OFF_HEAD]
            buf[_OFF_HEAD] = (s + 1) & 0xFF       # odd: mid-publish
            _U64.pack_into(buf, _OFF_HEAD + 8, 0xDEAD)  # the torn value
            got = []
            t = threading.Thread(
                target=lambda: got.append(r._read_idx(_OFF_HEAD)),
                daemon=True,
            )
            t.start()
            time.sleep(0.05)
            assert not got, "reader adopted a mid-publish value"
            _U64.pack_into(buf, _OFF_HEAD + 8, 43)
            buf[_OFF_HEAD] = (s + 2) & 0xFF       # even: committed
            t.join(timeout=2.0)
            assert got == [43]
        finally:
            r.close()
            r.unlink()

    def test_scribbled_record_header_raises_corruption(self):
        r = ShmRing.create(1024)
        try:
            r.produce(K_FRAME, b"payload")
            r.buf[HDR_SIZE + 4] = 9  # kind byte: not LINE/FRAME/WRAP
            with pytest.raises(RingCorruption):
                r.consume(timeout=0.5)
        finally:
            r.close()
            r.unlink()

    def test_closed_ring_raises_and_oversize_rejected(self):
        r = ShmRing.create(256)
        try:
            with pytest.raises(ValueError):
                r.produce(K_FRAME, b"x" * 512)  # can never fit
            r.mark_closed()
            with pytest.raises(RingClosed):
                r.consume(timeout=0.5)
            with pytest.raises(RingClosed):
                r.produce(K_FRAME, b"x")
        finally:
            r.close()
            r.unlink()

    def test_half_ring_record_rejected_not_deadlocked(self):
        """The wrap-slack bound: a record over capacity//2 has
        alignments at which its K_WRAP skip + body exceed the ring,
        so the room() wait can NEVER be satisfied — it must raise
        ValueError up front, not block an EMPTY ring until timeout
        (an 892-byte payload at offset 200 of a 1024-byte ring needs
        824 skip + 900 record = 1724 > 1024 contiguous-equivalent)."""
        r = ShmRing.create(1024)
        try:
            # walk the write position to offset 200
            r.produce(K_FRAME, b"a" * 192, timeout=1.0)
            _, view = r.consume(timeout=1.0)
            view = None
            r.release()
            assert r._wpos % r.capacity == 200
            t0 = time.monotonic()
            with pytest.raises(ValueError):
                r.produce(K_FRAME, b"x" * 892, timeout=5.0)
            assert time.monotonic() - t0 < 1.0, (
                "oversize record waited instead of raising"
            )
            # the ring is still healthy for legal records
            assert r.max_record == 1024 // 2 - 8
            r.produce(K_FRAME, b"y" * r.max_record, timeout=1.0)
            _, view = r.consume(timeout=1.0)
            assert bytes(view) == b"y" * r.max_record
            view = None
            r.release()
        finally:
            r.close()
            r.unlink()

    def test_max_record_fits_at_every_alignment(self):
        """A max_record payload must ALWAYS fit an empty ring, at any
        write offset: alternating 1-byte and max-size records walks
        the offset 9+136 bytes per round through a 256-byte ring, so
        every wrap alignment (marker and implicit skip) is crossed
        without a single produce blocking."""
        r = ShmRing.create(256)
        try:
            big = r.max_record  # 120
            for i in range(60):
                for payload in (bytes([i % 251]), b"z" * big):
                    r.produce(K_FRAME, payload, timeout=1.0)
                    _, view = r.consume(timeout=1.0)
                    assert bytes(view) == payload, f"round {i}"
                    view = None
                    r.release()
            assert r._wpos > 4 * 256  # really lapped the ring
        finally:
            r.close()
            r.unlink()


# ---------------------------------------------------------------------------
# the bell
# ---------------------------------------------------------------------------


class TestBell:
    def test_shared_flag_flips_on_second_in_process_attach(self):
        r = ShmRing.create(1024)
        try:
            assert r.bell.shared is False
            r2 = ShmRing.attach(r.name)
            try:
                # same object, now marked shared on BOTH handles
                assert r2.bell is r.bell
                assert r.bell.shared is True
            finally:
                r2.close()
        finally:
            r.close()
            r.unlink()

    def test_publish_rings_only_a_parked_peer(self):
        """The hot-path elision: produce/release ring the bell only
        while the parked byte is up — an unparked consumer costs the
        producer nothing per record."""
        r = ShmRing.create(1024)
        try:
            bell = r.bell
            bell.clear()
            r.produce(K_LINE, b"quiet")
            assert bell.wait(0) is False  # nobody parked: elided
            r.set_parked(True)
            r.produce(K_LINE, b"rung")
            assert bell.wait(0) is True
            r.set_parked(False)
        finally:
            r.close()
            r.unlink()

    def test_parked_consumer_woken_by_produce(self):
        """End to end through the Doorbell: a waiter parked on an
        empty shared-bell ring wakes promptly when the peer thread
        publishes."""
        r = ShmRing.create(4096)
        r2 = ShmRing.attach(r.name)
        try:
            db = Doorbell("test", ring=r2, registry=False)
            got = []

            def waiter():
                kind, view = r2.consume(timeout=5.0, waiter=db.wait)
                got.append(bytes(view))
                view = None
                r2.release()

            t = threading.Thread(target=waiter, daemon=True)
            t.start()
            time.sleep(0.05)  # let it park
            r.produce(K_FRAME, b"wake")
            t.join(timeout=2.0)
            assert got == [b"wake"]
            assert db.parks >= 1 and db.wakes >= 1
        finally:
            r2.close()
            r.close()
            r.unlink()

    def test_doorbell_timeout_and_counters(self):
        db = Doorbell("test", spin=10, registry=False)
        assert db.wait(lambda: False, timeout=0.05) is False
        assert db.parks == 1 and db.wakes == 0
        assert db.wait(lambda: True) is True


# ---------------------------------------------------------------------------
# negotiation, fallback, e2e data plane
# ---------------------------------------------------------------------------


class TestNegotiation:
    def test_shm_hello_lands_end_to_end(self):
        part, shards, servers, addrs = _mini_cluster()
        try:
            c = ClusterClient(
                addrs, part, (4,), registry=False, wire_proto="shm"
            )
            ids = np.arange(64, dtype=np.int64)
            base = c.pull_batch(ids)
            c.push_batch(ids, np.ones((64, 4), np.float32))
            after = c.pull_batch(ids)
            assert np.array_equal(after, base + 1)
            assert all(
                cc.proto == "shm" and cc.wire == "shm"
                for cc in c._conns.values()
            )
            # text verbs ride the same rings
            resp = c._conns[addrs[0]].request("conns")
            doc = json.loads(resp[3:])
            assert doc[0]["proto"] == "shm" and doc[0]["wire"] == "shm"
            # ... and the server-side ledger shows the substrate
            table = servers[0].conn_table()
            assert table and table[0]["wire"] == "shm"
            c.close()
        finally:
            for s in servers:
                s.stop()

    def test_server_opt_out_falls_back_to_binary(self, fresh_registry):
        part, shards, servers, addrs = _mini_cluster(
            n_shards=1, enable_shm=False
        )
        try:
            conn = ShmShardConnection(
                addrs[0][0], addrs[0][1], registry=fresh_registry
            )
            assert conn.proto == "bin" and conn.wire == "tcp"
            req = binf.encode_request(
                binf.VERB_IDS["pull"],
                ids=np.arange(8, dtype=np.int64),
            )
            frame = conn.request_many([req])[0]
            assert frame.verb_name == "pull"
            assert fresh_registry.counter(
                "shmem_fallbacks_total", component="shmem",
                reason="hello-refused",
            ).value >= 1
            conn.close()
        finally:
            for s in servers:
                s.stop()

    def test_non_loopback_peer_never_attempts_shm(self):
        assert shm_usable("10.1.2.3") is False
        assert shm_usable("127.0.0.1") in (True, False)  # host-dependent

    def test_chaos_proxy_splice_point_downgrades(self):
        """Through a ChaosProxy the shm hello is refused AT THE SPLICE
        POINT (segments are not routable through a TCP relay): the
        client lands on binary over the proxied link and traffic
        flows; the refusal is counted on the proxy."""
        from flink_parameter_server_tpu.nemesis.proxy import ChaosProxy

        part, shards, servers, addrs = _mini_cluster(n_shards=1)
        proxy = ChaosProxy(
            addrs[0][0], addrs[0][1], registry=False
        ).start()
        try:
            c = ClusterClient(
                [(proxy.host, proxy.port)], part, (4,),
                registry=False, wire_proto="shm",
            )
            ids = np.arange(16, dtype=np.int64)
            c.push_batch(ids, np.full((16, 4), 2.0, np.float32))
            assert np.array_equal(
                c.pull_batch(ids), np.full((16, 4), 2.0, np.float32)
            )
            assert all(cc.proto == "bin" for cc in c._conns.values())
            assert proxy.shm_downgrades == 1
            c.close()
        finally:
            proxy.stop()
            for s in servers:
                s.stop()


class TestBorrowReclaim:
    def test_reader_crash_while_borrowing_reclaimed(self, fresh_registry):
        """The lease: a client whose heartbeat went stale while the
        pump is write-blocked on a full response ring is reclaimed —
        counted, rings closed, TCP anchor dropped — instead of
        wedging the server forever."""
        part, shards, servers, addrs = _mini_cluster(n_shards=1)
        servers[0].SHM_RECLAIM_S = 0.3
        conn = None
        try:
            conn = ShmShardConnection(
                addrs[0][0], addrs[0][1],
                capacity=64 * 1024, registry=False,
            )
            assert conn.proto == "shm"
            # simulate the crash: heartbeat dies, responses are never
            # consumed (and never released)
            conn._hb_stop.set()
            conn._hb_thread.join(timeout=2.0)
            req = binf.encode_request(
                binf.VERB_IDS["pull"],
                ids=np.arange(64, dtype=np.int64),
            )
            for _ in range(120):  # ~1 KiB per response: s2c fills
                try:
                    conn._c2s.produce(K_FRAME, req, timeout=1.0)
                except (RingTimeout, RingClosed):
                    break
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline and not conn._s2c.closed:
                time.sleep(0.05)
            assert conn._s2c.closed, "pump never reclaimed the channel"
            assert fresh_registry.counter(
                "shmem_borrow_reclaims_total", component="shmem",
                role="server",
            ).value >= 1
        finally:
            if conn is not None:
                conn.close()
            for s in servers:
                s.stop()


class TestSizing:
    """Frames legal over TCP but bigger than the ring (or a batch of
    responses bigger than the ring) must NEVER wedge or silently fold
    a channel — the detour/spill/protocol-error escape hatches."""

    def _pull(self, n, start=0):
        return binf.encode_request(
            binf.VERB_IDS["pull"],
            ids=np.arange(start, start + n, dtype=np.int64),
        )

    def _rows(self, frame, dim=4):
        return binf.rows_from_payload(frame.payload, (dim,), frame.enc)

    def test_oversize_request_detours_over_tcp(self, fresh_registry):
        """A request over ring.max_record rides the TCP anchor —
        strictly ordered with the ring pipeline around it — and the
        channel stays on shm for everything that fits."""
        part, shards, servers, addrs = _mini_cluster(
            n_shards=1, capacity=512
        )
        conn = None
        try:
            conn = ShmShardConnection(
                addrs[0][0], addrs[0][1],
                capacity=4096, registry=fresh_registry,
            )
            assert conn.proto == "shm"
            big = self._pull(300)  # 2424-byte frame > max_record 2040
            assert len(big) > conn._max_payload
            small_before, oversize, small_after = conn.request_many(
                [self._pull(8), big, self._pull(8, start=292)]
            )
            assert oversize.n == 300
            rows = self._rows(oversize)
            assert np.array_equal(rows[:8], self._rows(small_before))
            assert np.array_equal(rows[292:], self._rows(small_after))
            assert conn.proto == "shm" and conn.wire == "shm"
            assert fresh_registry.counter(
                "shmem_fallbacks_total", component="shmem",
                reason="oversize",
            ).value == 1
            conn.close()
            conn = None
        finally:
            if conn is not None:
                conn.close()
            for s in servers:
                s.stop()

    def test_batch_spill_when_responses_outgrow_ring(self):
        """One batch whose responses total ~2x the response ring:
        the client spills (copies borrows off the ring and releases
        mid-batch) instead of wedging the pump until the 30s client
        timeout — and every row still comes back correct."""
        part, shards, servers, addrs = _mini_cluster(
            n_shards=1, capacity=512
        )
        conn = ref = None
        try:
            conn = ShmShardConnection(
                addrs[0][0], addrs[0][1],
                capacity=4096, registry=False,
            )
            assert conn.proto == "shm"
            # 8 pulls x 64 ids -> ~1 KiB per response, ~8.4 KiB total
            reqs = [self._pull(64, start=64 * i) for i in range(8)]
            t0 = time.monotonic()
            frames = conn.request_many(reqs)
            assert time.monotonic() - t0 < 10.0, "batch wedged"
            assert conn.spills >= 1, "batch this size must have spilled"
            ref = ShardConnection(
                addrs[0][0], addrs[0][1], negotiate=True
            )
            for i, frame in enumerate(frames):
                want = self._rows(ref.request_many(
                    [self._pull(64, start=64 * i)]
                )[0])
                assert np.array_equal(self._rows(frame), want), f"chunk {i}"
            # the channel survives and the next batch is zero-copy again
            again = conn.request_many([self._pull(8)])[0]
            assert again.n == 8
            conn.close()
            conn = None
        finally:
            if conn is not None:
                conn.close()
            if ref is not None:
                ref.close()
            for s in servers:
                s.stop()

    def test_oversize_response_is_protocol_error_not_teardown(self):
        """A response too big for a ring record answers a clear err
        line (the client can re-chunk) — the channel stays up; before
        this was pinned, the pump's produce raised into its catch-all
        and the fold looked like a dead peer."""
        part, shards, servers, addrs = _mini_cluster(
            n_shards=1, capacity=512
        )
        conn = None
        try:
            conn = ShmShardConnection(
                addrs[0][0], addrs[0][1],
                capacity=4096, registry=False,
            )
            assert conn.proto == "shm"
            # request fits (1624 B) but its response (3224 B) does not
            resp = conn.request_many([self._pull(200)])[0]
            assert isinstance(resp, str)
            assert resp.startswith("err bad-request")
            assert "exceeds shm ring record limit" in resp
            # channel still alive and serving
            frame = conn.request_many([self._pull(8)])[0]
            assert frame.n == 8
            conn.close()
            conn = None
        finally:
            if conn is not None:
                conn.close()
            for s in servers:
                s.stop()

    def test_pump_error_teardown_is_counted(self, fresh_registry):
        """The catch-all keeps its no-raise guarantee but loses its
        silence: an unexpected respond_frame error folds the channel
        AND increments shmem_pump_teardowns_total{reason=error}."""
        part, shards, servers, addrs = _mini_cluster(n_shards=1)
        conn = None
        try:
            conn = ShmShardConnection(
                addrs[0][0], addrs[0][1], registry=False,
            )
            assert conn.proto == "shm"

            def boom(data):
                raise RuntimeError("poisoned record")

            servers[0].respond_frame = boom
            conn._c2s.produce(K_FRAME, self._pull(8), timeout=1.0)
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline and not conn._s2c.closed:
                time.sleep(0.02)
            assert conn._s2c.closed, "pump never folded the channel"
            assert fresh_registry.counter(
                "shmem_pump_teardowns_total", component="shmem",
                reason="error",
            ).value >= 1
        finally:
            if conn is not None:
                conn.close()
            for s in servers:
                s.stop()


# ---------------------------------------------------------------------------
# BSP parity through the shm wire
# ---------------------------------------------------------------------------


class TestParity:
    @pytest.mark.parametrize("workload", ["mf", "pa"])
    def test_bsp_bitwise_parity_shm_vs_tcp(self, workload):
        """ACCEPTANCE: the same BSP run through ``wire_proto="shm"``
        equals the binary-TCP run BIT FOR BIT — the rings carry the
        identical frames, so the tables cannot differ by even a ulp."""
        from flink_parameter_server_tpu.cluster.driver import (
            ClusterConfig,
        )
        from flink_parameter_server_tpu.workloads import (
            WorkloadParams,
            build_cluster_driver,
            create_workload,
        )

        params = WorkloadParams(
            rounds=4, batch=48, num_users=24, num_items=32, dim=4,
            seed=3,
        )
        tables = {}
        for proto in ("auto", "shm"):
            w = create_workload(workload, params)
            driver = build_cluster_driver(
                w,
                config=ClusterConfig(
                    num_shards=2, num_workers=1, staleness_bound=0,
                    wire_proto=proto,
                ),
                registry=False,
            )
            with driver:
                result = driver.run(w.batches())
                if proto == "shm":
                    conns = [
                        cc for c in driver._clients
                        for cc in c._conns.values()
                    ]
                    assert conns and all(
                        cc.wire == "shm" for cc in conns
                    ), "shm arm did not actually ride shared memory"
            tables[proto] = np.asarray(result.values)
        assert np.array_equal(tables["auto"], tables["shm"]), (
            f"{workload}: shm table diverges from the TCP table"
        )


# ---------------------------------------------------------------------------
# hygiene: leaks, ledger, tooling
# ---------------------------------------------------------------------------


_LEAK_SCRIPT = """
import numpy as np
from flink_parameter_server_tpu.cluster.partition import RangePartitioner
from flink_parameter_server_tpu.cluster.shard import ParamShard, ShardServer
from flink_parameter_server_tpu.shmem.channel import ShmShardConnection
from flink_parameter_server_tpu.utils import frames as binf

part = RangePartitioner(32, 1)
shard = ParamShard(0, part, (4,), registry=False)
srv = ShardServer(shard).start()
conn = ShmShardConnection(srv.host, srv.port, registry=False)
assert conn.proto == "shm", conn.proto
req = binf.encode_request(
    binf.VERB_IDS["pull"], ids=np.arange(8, dtype=np.int64)
)
frame = conn.request_many([req])[0]
assert frame.verb_name == "pull"
conn.close()
srv.stop()
print("LEAKCHECK-OK")
"""


@pytest.mark.slow
class TestHygiene:
    def test_no_segment_leak_and_quiet_tracker(self):
        """A full connect/pull/close cycle in a fresh interpreter: no
        fps-ring-* segment survives in /dev/shm, and the stdlib
        resource tracker prints NOTHING (a warning there means a
        segment was leaked or double-unlinked)."""
        before = set(os.listdir("/dev/shm"))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, "-c", _LEAK_SCRIPT],
            capture_output=True, text=True, timeout=120,
            cwd=REPO, env=env,
        )
        assert proc.returncode == 0, proc.stderr
        assert "LEAKCHECK-OK" in proc.stdout
        assert "resource_tracker" not in proc.stderr, proc.stderr
        leaked = {
            n for n in set(os.listdir("/dev/shm")) - before
            if n.startswith("fps-ring-")
        }
        assert not leaked, leaked


class TestTooling:
    def test_psctl_conns_renders_wire_column(self, capsys):
        from tools.psctl import cmd_conns

        part, shards, servers, addrs = _mini_cluster(n_shards=1)
        try:
            c = ClusterClient(
                addrs, part, (4,), registry=False, wire_proto="shm"
            )
            c.pull_batch(np.arange(8, dtype=np.int64))
            args = argparse.Namespace(
                shards=f"{addrs[0][0]}:{addrs[0][1]}", metrics=None
            )
            assert cmd_conns(args) == 0
            out = capsys.readouterr().out
            assert "wire" in out and "shm" in out
            c.close()
        finally:
            for s in servers:
                s.stop()

    def test_shmem_is_a_known_component(self):
        from tools.check_metric_lines import KNOWN_COMPONENTS

        assert "shmem" in KNOWN_COMPONENTS
