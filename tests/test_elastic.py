"""elastic/ — live shard membership tests.

Thread-backed shards over real TCP (the cluster/ test discipline), so
the epoch protocol, the migration wire verbs, and the hedging race run
for real while staying tier-1.  The acceptance anchors:

  * live-resize parity — start 1 shard, scale out to 2 MID-STREAM
    (from a control thread, against concurrent 2-worker traffic),
    train to completion: the final MF table is allclose-equal fp32 to
    an uninterrupted static 2-shard run on the same stream, migrated
    rows land bitwise (the migration verify), and the shard WAL ledger
    audit balances — zero updates lost or double-applied;
  * a killed shard is replaced by the controller with the client
    seeing latency, not errors;
  * hedged pulls win against a straggling primary and never
    double-apply anything.
"""
import json
import tempfile
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from flink_parameter_server_tpu.cluster import (
    ClusterConfig,
    ClusterDriver,
    ConsistentHashPartitioner,
    ParamShard,
    RangePartitioner,
    ShardServer,
)
from flink_parameter_server_tpu.cluster.client import ClusterClient
from flink_parameter_server_tpu.cluster.shard import (
    format_rows,
    parse_rows,
)
from flink_parameter_server_tpu.data.movielens import synthetic_ratings
from flink_parameter_server_tpu.data.streams import microbatches
from flink_parameter_server_tpu.elastic import (
    ElasticClusterConfig,
    ElasticClusterDriver,
    ElasticController,
    HedgeBudget,
    Hedger,
    MembershipService,
    ScalePolicy,
    execute_moves,
    plan_moves,
)
from flink_parameter_server_tpu.models.matrix_factorization import (
    OnlineMatrixFactorization,
    SGDUpdater,
)
from flink_parameter_server_tpu.telemetry.registry import MetricsRegistry
from flink_parameter_server_tpu.utils.initializers import (
    ranged_random_factor,
)
from flink_parameter_server_tpu.utils.net import LineServer, request_lines

pytestmark = pytest.mark.elastic


# ---------------------------------------------------------------------------
# membership epochs
# ---------------------------------------------------------------------------


class TestMembership:
    def test_epochs_are_monotone_and_immutable(self):
        p1 = ConsistentHashPartitioner(64, 1)
        m = MembershipService(p1, [("h", 1)], registry=False)
        assert m.current().epoch == 0
        p2 = p1.grown(2)
        v = m.publish(p2, [("h", 1), ("h", 2)])
        assert v.epoch == 1
        assert m.current().partitioner is p2
        with pytest.raises(Exception):
            v.epoch = 5  # frozen dataclass

    def test_publish_validates_address_count(self):
        p1 = ConsistentHashPartitioner(64, 2)
        m = MembershipService(p1, [("h", 1), ("h", 2)], registry=False)
        with pytest.raises(ValueError):
            m.publish(p1.grown(3), [("h", 1), ("h", 2)])

    def test_subscribe_fires_and_unsubscribes(self):
        p1 = ConsistentHashPartitioner(64, 1)
        m = MembershipService(p1, [("h", 1)], registry=False)
        seen = []
        unsub = m.subscribe(lambda v: seen.append(v.epoch))
        m.publish(p1.grown(2), [("h", 1), ("h", 2)])
        unsub()
        m.publish(p1.grown(3), [("h", 1), ("h", 2), ("h", 3)])
        assert seen == [1]

    def test_registry_instruments(self):
        reg = MetricsRegistry()
        p1 = ConsistentHashPartitioner(64, 1)
        m = MembershipService(p1, [("h", 1)], registry=reg)
        m.publish(p1.grown(2), [("h", 1), ("h", 2)])
        snap = {i.name: i.value for i in reg.instruments()}
        assert snap["elastic_epoch"] == 1
        assert snap["elastic_epoch_flips_total"] == 1


# ---------------------------------------------------------------------------
# migration planning
# ---------------------------------------------------------------------------


class TestPlanMoves:
    def test_growth_moves_only_to_new_shards(self):
        old = ConsistentHashPartitioner(512, 2, seed=3)
        new = old.grown(4)
        moves = plan_moves(old, new)
        assert moves  # growth takes a real share
        for mv in moves:
            assert mv.dst >= 2  # only ONTO new shards
            assert (old.shard_of(mv.ids) == mv.src).all()
            assert (new.shard_of(mv.ids) == mv.dst).all()

    def test_shrink_moves_only_off_retired_shards(self):
        old = ConsistentHashPartitioner(512, 4, seed=3)
        new = old.shrunk(2)
        moves = plan_moves(old, new)
        assert moves
        for mv in moves:
            assert mv.src >= 2  # only OFF the retired shards
            assert mv.dst < 2

    def test_moves_cover_exactly_the_ownership_diff(self):
        old = ConsistentHashPartitioner(1024, 3, seed=9)
        new = old.grown(5)
        moves = plan_moves(old, new)
        moved = (
            np.concatenate([mv.ids for mv in moves])
            if moves else np.empty(0, np.int64)
        )
        assert len(np.unique(moved)) == len(moved)  # no key twice
        ids = np.arange(1024)
        expect = ids[old.shard_of(ids) != new.shard_of(ids)]
        assert np.array_equal(np.sort(moved), expect)

    def test_capacity_mismatch_rejected(self):
        with pytest.raises(ValueError):
            plan_moves(
                ConsistentHashPartitioner(64, 2),
                ConsistentHashPartitioner(128, 2),
            )


# ---------------------------------------------------------------------------
# the epoch-fenced wire protocol
# ---------------------------------------------------------------------------


class TestEpochWire:
    @pytest.fixture()
    def served(self, tmp_path):
        part = ConsistentHashPartitioner(64, 1, seed=5)
        init = ranged_random_factor(3, (4,))
        shard = ParamShard(
            0, part, (4,), init_fn=init,
            wal_dir=str(tmp_path / "wal"), registry=False,
        )
        server = ShardServer(shard, supervised=False).start()
        yield part, shard, server
        server.stop()
        shard.close()

    def test_stale_epoch_write_rejected(self, served):
        part, shard, server = served
        (ok,) = request_lines(
            server.host, server.port,
            ["push 1 " + format_rows(np.ones((1, 4), np.float32))
             + " e=0"],
        )
        assert ok.startswith("ok")
        new = part.grown(2)
        moving = np.arange(64)[new.shard_of(np.arange(64)) == 1]
        shard.freeze(moving)
        shard.install_epoch(1, new)
        kept = int(shard.owned[0])
        (r,) = request_lines(
            server.host, server.port,
            [f"push {kept} "
             + format_rows(np.ones((1, 4), np.float32)) + " e=0"],
        )
        assert r.startswith("err stale-epoch"), r
        assert "epoch=1" in r
        # current-epoch write goes through
        (r2,) = request_lines(
            server.host, server.port,
            [f"push {kept} "
             + format_rows(np.ones((1, 4), np.float32)) + " e=1"],
        )
        assert r2.startswith("ok"), r2

    def test_future_epoch_frame_accepted_when_routable(self, served):
        """Mid-flip, a client on the NEWER map may reach a shard that
        has not flipped yet; if the ids route here under both maps the
        write is correctly placed and must not bounce."""
        part, shard, server = served
        kept = int(shard.owned[0])
        (r,) = request_lines(
            server.host, server.port,
            [f"push {kept} "
             + format_rows(np.ones((1, 4), np.float32)) + " e=7"],
        )
        assert r.startswith("ok"), r

    def test_frozen_range_rejects_push_but_serves_pull(self, served):
        part, shard, server = served
        frozen_id = 5
        shard.freeze([frozen_id])
        r_push, r_pull, r_other = request_lines(
            server.host, server.port,
            [
                f"push {frozen_id} "
                + format_rows(np.ones((1, 4), np.float32)),
                f"pull {frozen_id} b64",
                "push 6 " + format_rows(np.ones((1, 4), np.float32)),
            ],
        )
        assert r_push == "err frozen"
        assert r_pull.startswith("ok")  # reads never block
        assert r_other.startswith("ok")  # non-moving keys never block
        shard.unfreeze()

    def test_xfer_load_roundtrip_bitwise(self, served):
        part, shard, server = served
        ids = shard.owned[:8]
        rng = np.random.default_rng(0)
        shard.push(ids, rng.normal(size=(8, 4)).astype(np.float32))
        (resp,) = request_lines(
            server.host, server.port,
            ["xfer " + ",".join(str(int(i)) for i in ids)],
        )
        assert resp.startswith("ok")
        _ok, _n, seq_tok, payload = resp.split(" ", 3)
        assert int(seq_tok.partition("=")[2]) == shard._push_seq
        rows = parse_rows(payload, (4,))
        assert np.array_equal(rows, shard.values()[:8])  # BITWISE
        # load assigns bitwise (no delta arithmetic)
        target = rng.normal(size=(8, 4)).astype(np.float32)
        (r2,) = request_lines(
            server.host, server.port,
            ["load " + ",".join(str(int(i)) for i in ids) + " "
             + format_rows(target, "b64")],
        )
        assert r2.startswith("ok loaded=8")
        assert np.array_equal(shard.values()[:8], target)

    def test_pid_dedupe_exactly_once(self, served):
        """A retried push frame (lost ack) is acked but applied once —
        including after a crash + WAL rebuild."""
        part, shard, server = served
        gid = int(shard.owned[0])
        line = (
            f"push {gid} "
            + format_rows(np.ones((1, 4), np.float32))
            + " pid=w0.1 e=0"
        )
        (r1,) = request_lines(server.host, server.port, [line])
        after_first = shard.values().copy()
        (r2,) = request_lines(server.host, server.port, [line])  # retry
        assert r1.startswith("ok") and r2.startswith("ok")
        assert np.array_equal(shard.values(), after_first)
        assert shard.rows_applied == 1
        # the dedupe window survives a crash (pairs ride the WAL)
        shard.crash()
        shard.restart()
        (r3,) = request_lines(server.host, server.port, [line])
        assert r3.startswith("ok")
        assert np.array_equal(shard.values(), after_first)


# ---------------------------------------------------------------------------
# migration execution
# ---------------------------------------------------------------------------


class TestMigration:
    def _topology(self, tmp_path, *, wal=True):
        old = ConsistentHashPartitioner(256, 1, seed=2)
        new = old.grown(2)
        init = ranged_random_factor(3, (4,))
        src = ParamShard(
            0, old, (4,), init_fn=init,
            wal_dir=str(tmp_path / "wal0") if wal else None,
            registry=False,
        )
        dst = ParamShard(
            1, new, (4,), init_fn=init,
            wal_dir=str(tmp_path / "wal1") if wal else None,
            registry=False,
        )
        servers = [
            ShardServer(src, supervised=False).start(),
            ShardServer(dst, supervised=False).start(),
        ]
        return old, new, src, dst, servers

    def test_migrated_rows_bitwise_equal_at_handoff(self, tmp_path):
        old, new, src, dst, servers = self._topology(tmp_path)
        try:
            rng = np.random.default_rng(1)
            ids = rng.integers(0, 256, 64)
            src.push(
                np.unique(ids),
                rng.normal(size=(len(np.unique(ids)), 4)).astype(
                    np.float32
                ),
            )
            moves = plan_moves(old, new)
            pre = {
                mv.dst: src.snapshot_rows(mv.ids)[0] for mv in moves
            }
            report = execute_moves(
                moves, {0: src, 1: dst},
                {0: (servers[0].host, servers[0].port),
                 1: (servers[1].host, servers[1].port)},
                (4,), verify=True, registry=False,
            )
            assert report.verified and report.mismatches == 0
            assert report.rows_moved == sum(len(m.ids) for m in moves)
            for mv in moves:
                got = dst.peek_rows(mv.ids)
                assert np.array_equal(got, pre[mv.dst])  # BITWISE
            assert 0 in report.freeze_started
        finally:
            for s in servers:
                s.stop()
            src.close()
            dst.close()

    def test_wal_tail_catches_up_writes_racing_the_snapshot(
        self, tmp_path
    ):
        """A push landing between the bulk snapshot and the freeze is
        caught up from the WAL tail — and the caught-up rows are
        bitwise the source's."""
        old, new, src, dst, servers = self._topology(tmp_path)
        try:
            moves = plan_moves(old, new)
            racing_id = int(moves[0].ids[0])
            orig_freeze = src.freeze
            raced = []

            def freeze_with_race(ids):
                if not raced:  # one race, at the real freeze point
                    raced.append(True)
                    src.push(
                        np.array([racing_id]),
                        np.full((1, 4), 0.125, np.float32),
                    )
                orig_freeze(ids)

            src.freeze = freeze_with_race
            report = execute_moves(
                moves, {0: src, 1: dst},
                {0: (servers[0].host, servers[0].port),
                 1: (servers[1].host, servers[1].port)},
                (4,), verify=True, registry=False,
            )
            assert raced
            assert report.tail_rows >= 1
            assert report.verified and report.mismatches == 0
            src_row, _ = src.snapshot_rows(np.array([racing_id]))
            dst_row = dst.peek_rows(np.array([racing_id]))
            assert np.array_equal(src_row, dst_row)  # BITWISE
        finally:
            for s in servers:
                s.stop()
            src.close()
            dst.close()

    def test_no_wal_falls_back_to_freeze_first(self, tmp_path):
        old, new, src, dst, servers = self._topology(tmp_path, wal=False)
        try:
            moves = plan_moves(old, new)
            report = execute_moves(
                moves, {0: src, 1: dst},
                {0: (servers[0].host, servers[0].port),
                 1: (servers[1].host, servers[1].port)},
                (4,), verify=True, registry=False,
            )
            assert report.verified and report.tail_rows == 0
        finally:
            for s in servers:
                s.stop()
            src.close()
            dst.close()

    def test_install_epoch_snapshot_survives_fresh_process(
        self, tmp_path
    ):
        """After a flip, a brand-new ParamShard over the same WAL dir
        rebuilds the post-flip slice bitwise (the snapshot barrier) —
        the dead-shard replacement path across a resharding."""
        part = ConsistentHashPartitioner(64, 1, seed=4)
        init = ranged_random_factor(3, (4,))
        wal = str(tmp_path / "wal")
        sh = ParamShard(0, part, (4,), init_fn=init, wal_dir=wal,
                        registry=False)
        sh.push(np.arange(10), np.ones((10, 4), np.float32), pid="a.0")
        p2 = part.grown(2)
        sh.install_epoch(1, p2)
        before = sh.values().copy()
        pairs = list(sh._applied_pairs)
        sh.close()
        reborn = ParamShard(0, p2, (4,), init_fn=init, wal_dir=wal,
                            registry=False)
        assert np.array_equal(reborn.values(), before)  # BITWISE
        assert list(reborn._applied_pairs) == pairs  # dedupe survives
        reborn.close()


# ---------------------------------------------------------------------------
# hedging
# ---------------------------------------------------------------------------


class _SlowOnceServer(ShardServer):
    """Delays exactly one pull frame (the straggler injection) —
    hooked on BOTH framings (clients negotiate binary by default)."""

    def __init__(self, *a, **k):
        super().__init__(*a, **k)
        self.slow = threading.Event()
        self.delay_s = 0.5

    def _maybe_stall(self, verb: str) -> None:
        if verb == "pull" and self.slow.is_set():
            self.slow.clear()
            time.sleep(self.delay_s)

    def respond(self, line):
        self._maybe_stall(line.split(None, 1)[0].lower() if line else "")
        return super().respond(line)

    def respond_frame(self, data):
        from flink_parameter_server_tpu.utils import frames as wire

        self._maybe_stall(wire.peek_verb_name(data))
        return super().respond_frame(data)


class TestHedging:
    @pytest.fixture()
    def slow_topology(self):
        part = RangePartitioner(64, 1)
        init = ranged_random_factor(3, (4,))
        shard = ParamShard(0, part, (4,), init_fn=init, registry=False)
        server = _SlowOnceServer(shard, supervised=False).start()
        yield part, shard, server
        server.stop()

    def test_budget_caps_hedges(self):
        b = HedgeBudget(max_fraction=0.5, burst=1)
        b.note_requests(2)
        assert b.allow(1)  # 1 <= 2*0.5 + 1
        assert b.allow(1)  # 2 <= 2
        assert not b.allow(1)
        b.refund(1)
        assert b.allow(1)
        with pytest.raises(ValueError):
            HedgeBudget(max_fraction=1.5)

    def test_hedge_beats_straggler_and_never_double_applies(
        self, slow_topology
    ):
        part, shard, server = slow_topology
        reg = MetricsRegistry()
        hedger = Hedger(
            0.05, budget=HedgeBudget(1.0, burst=16), registry=reg
        )
        mem = MembershipService(
            part, [(server.host, server.port)], registry=False
        )
        client = ClusterClient(
            value_shape=(4,), membership=mem, hedge=hedger,
            registry=False, chunk=64,
        )
        try:
            client.pull_batch(np.arange(4))  # warm the primary conn
            server.slow.set()
            t0 = time.perf_counter()
            vals = client.pull_batch(np.arange(8))
            wall = time.perf_counter() - t0
            assert wall < server.delay_s / 2, wall  # the hedge won
            assert hedger.hedges_won >= 1
            expect = np.asarray(
                ranged_random_factor(3, (4,))(
                    jnp.asarray(np.arange(8), jnp.int32)
                )
            )
            assert np.array_equal(vals, expect)  # delivered ONCE, exact
            # pushes are never hedged; state advances exactly once
            before = client.pull_batch(np.array([3]))[0]
            client.push_batch(
                np.array([3]), np.ones((1, 4), np.float32)
            )
            after = client.pull_batch(np.array([3]))[0]
            assert np.allclose(after - before, 1.0)
            assert shard.rows_applied == 1
            counters = {i.name: i.value for i in reg.instruments()}
            assert counters["elastic_hedged_pulls_total"] >= 1
            assert counters["elastic_hedges_won_total"] >= 1
        finally:
            client.close()

    def test_zero_budget_never_hedges(self, slow_topology):
        part, shard, server = slow_topology
        server.delay_s = 0.2
        hedger = Hedger(
            0.02, budget=HedgeBudget(0.0, burst=0), registry=False
        )
        mem = MembershipService(
            part, [(server.host, server.port)], registry=False
        )
        client = ClusterClient(
            value_shape=(4,), membership=mem, hedge=hedger,
            registry=False, chunk=64,
        )
        try:
            client.pull_batch(np.arange(4))
            server.slow.set()
            t0 = time.perf_counter()
            client.pull_batch(np.arange(4))
            assert time.perf_counter() - t0 >= server.delay_s * 0.9
            assert hedger.hedges_issued == 0
        finally:
            client.close()


# ---------------------------------------------------------------------------
# the acceptance anchors
# ---------------------------------------------------------------------------


def _mf_fixture(num_users=64, num_items=96, dim=8, batch=128, rounds=16):
    cols = synthetic_ratings(num_users, num_items, rounds * batch, seed=3)
    batches = list(microbatches(cols, batch))
    init = ranged_random_factor(7, (dim,))
    return batches, init, num_users, num_items, dim


def _static_table(batches, init, nu, ni, dim, *, num_shards, workers=2):
    logic = OnlineMatrixFactorization(
        nu, dim, updater=SGDUpdater(0.05), seed=1
    )
    driver = ClusterDriver(
        logic, capacity=ni, value_shape=(dim,), init_fn=init,
        config=ClusterConfig(
            num_shards=num_shards, num_workers=workers,
            partition="hash",
        ),
        registry=False,
    )
    with driver:
        return driver.run(batches).values


class TestLiveResize:
    def test_live_resize_parity_e2e(self, tmp_path):
        """ACCEPTANCE: 1 shard → scale out to 2 mid-stream against
        concurrent 2-worker traffic → train to completion.  Final
        table allclose-equal fp32 to an uninterrupted static 2-shard
        run; migrated rows bitwise at handoff (migration verify); the
        WAL ledger audit balances (zero updates lost or
        double-applied)."""
        batches, init, nu, ni, dim = _mf_fixture()
        base = _static_table(batches, init, nu, ni, dim, num_shards=2)
        reg = MetricsRegistry()
        logic = OnlineMatrixFactorization(
            nu, dim, updater=SGDUpdater(0.05), seed=1
        )
        driver = ElasticClusterDriver(
            logic, capacity=ni, value_shape=(dim,), init_fn=init,
            config=ElasticClusterConfig(
                num_shards=1, num_workers=2,
                wal_dir=str(tmp_path / "wal"),
            ),
            registry=reg,
        )
        driver.start()
        rounds_c = reg.counter(
            "cluster_worker_rounds_total", component="cluster"
        )
        scaled = []
        errors = []

        def control():
            try:
                deadline = time.monotonic() + 60
                while rounds_c.value < 8 and time.monotonic() < deadline:
                    time.sleep(0.002)
                scaled.append(driver.scale_out())
            except BaseException as e:  # pragma: no cover
                errors.append(e)

        t = threading.Thread(target=control, daemon=True)
        t.start()
        try:
            result = driver.run(batches, timeout=120)
            t.join(timeout=60)
            assert not errors, errors
            assert scaled, "scale_out never fired"
            report = scaled[0]
            # migrated rows were verified bitwise before the flip
            assert report.verified and report.mismatches == 0
            assert report.rows_moved > 0
            # final table == uninterrupted static 2-shard run
            np.testing.assert_allclose(
                result.values, base, rtol=1e-4, atol=1e-6
            )
            # the ledger audit: every unique delta row acked by a
            # worker client was applied on exactly one shard
            acked = sum(c.rows_pushed for c in driver._clients)
            applied = sum(sh.rows_applied for sh in driver.all_shards)
            assert acked == applied
            assert acked > 0
            # topology really flipped
            assert driver.partitioner.num_shards == 2
            assert driver.membership.current().epoch == 1
        finally:
            driver.stop()

    def test_scale_in_parity_e2e(self, tmp_path):
        """Drain-and-retire: 3 shards → 2 mid-stream; parity against a
        static 2-shard run, retired shard fully drained."""
        batches, init, nu, ni, dim = _mf_fixture(rounds=12)
        base = _static_table(batches, init, nu, ni, dim, num_shards=2)
        reg = MetricsRegistry()
        logic = OnlineMatrixFactorization(
            nu, dim, updater=SGDUpdater(0.05), seed=1
        )
        driver = ElasticClusterDriver(
            logic, capacity=ni, value_shape=(dim,), init_fn=init,
            config=ElasticClusterConfig(
                num_shards=3, num_workers=2,
                wal_dir=str(tmp_path / "wal"),
            ),
            registry=reg,
        )
        driver.start()
        rounds_c = reg.counter(
            "cluster_worker_rounds_total", component="cluster"
        )
        done = []

        def control():
            deadline = time.monotonic() + 60
            while rounds_c.value < 6 and time.monotonic() < deadline:
                time.sleep(0.002)
            done.append(driver.scale_in())

        t = threading.Thread(target=control, daemon=True)
        t.start()
        try:
            result = driver.run(batches, timeout=120)
            t.join(timeout=60)
            assert done and done[0].verified
            assert driver.partitioner.num_shards == 2
            np.testing.assert_allclose(
                result.values, base, rtol=1e-4, atol=1e-6
            )
            acked = sum(c.rows_pushed for c in driver._clients)
            applied = sum(sh.rows_applied for sh in driver.all_shards)
            assert acked == applied
        finally:
            driver.stop()

    def test_killed_shard_replaced_latency_not_errors(self, tmp_path):
        """ACCEPTANCE: kill a shard mid-stream (server down + slice
        gone), replace it from its WAL — the run completes with no
        errors, parity holds, and the replacement is counted."""
        batches, init, nu, ni, dim = _mf_fixture(rounds=12)
        base = _static_table(batches, init, nu, ni, dim, num_shards=2)
        reg = MetricsRegistry()
        logic = OnlineMatrixFactorization(
            nu, dim, updater=SGDUpdater(0.05), seed=1
        )
        driver = ElasticClusterDriver(
            logic, capacity=ni, value_shape=(dim,), init_fn=init,
            config=ElasticClusterConfig(
                num_shards=2, num_workers=2,
                wal_dir=str(tmp_path / "wal"),
            ),
            registry=reg,
        )
        driver.start()
        rounds_c = reg.counter(
            "cluster_worker_rounds_total", component="cluster"
        )
        acted = []

        def control():
            deadline = time.monotonic() + 60
            while rounds_c.value < 6 and time.monotonic() < deadline:
                time.sleep(0.002)
            driver.kill_shard(1)
            time.sleep(0.02)  # the window where clients retry
            acted.append(driver.replace_shard(1))

        t = threading.Thread(target=control, daemon=True)
        t.start()
        try:
            result = driver.run(batches, timeout=120)
            t.join(timeout=60)
            assert acted, "replacement never ran"
            np.testing.assert_allclose(
                result.values, base, rtol=1e-4, atol=1e-6
            )
            counters = {
                i.name: i.value for i in reg.instruments()
                if i.labels.get("component") == "elastic"
            }
            assert counters["elastic_shard_replacements_total"] == 1
            # the epoch bumped so clients re-resolved the address
            assert driver.membership.current().epoch == 1
        finally:
            driver.stop()

    def test_epoch_refresh_counter_counts_replays(self, tmp_path):
        """cluster/client satellite: a stale-epoch rejection refreshes
        the membership view and replays the frame instead of raising —
        visible on elastic_epoch_refreshes_total."""
        reg = MetricsRegistry()
        part = ConsistentHashPartitioner(64, 1, seed=5)
        init = ranged_random_factor(3, (4,))
        shard0 = ParamShard(
            0, part, (4,), init_fn=init,
            wal_dir=str(tmp_path / "w0"), registry=False,
        )
        srv0 = ShardServer(shard0, supervised=False).start()
        mem = MembershipService(
            part, [(srv0.host, srv0.port)], registry=False
        )
        client = ClusterClient(
            value_shape=(4,), membership=mem, registry=reg,
            worker="0", chunk=64,
        )
        try:
            # resize happens while the client holds the old view
            new = part.grown(2)
            shard1 = ParamShard(
                1, new, (4,), init_fn=init,
                wal_dir=str(tmp_path / "w1"), registry=False,
            )
            srv1 = ShardServer(shard1, supervised=False).start()
            moves = plan_moves(part, new)
            execute_moves(
                moves, {0: shard0, 1: shard1},
                {0: (srv0.host, srv0.port), 1: (srv1.host, srv1.port)},
                (4,), verify=True, registry=False,
            )
            shard1.install_epoch(1, new)
            shard0.install_epoch(1, new)
            mem.publish(new, [(srv0.host, srv0.port),
                              (srv1.host, srv1.port)])
            # client still routes by the OLD map; a moved key's push is
            # rejected, refreshed, replayed — not raised
            moved_id = int(moves[0].ids[0])
            before = client.pull_batch(np.array([moved_id]))[0]
            n = client.push_batch(
                np.array([moved_id]), np.ones((1, 4), np.float32)
            )
            assert n == 1
            after = client.pull_batch(np.array([moved_id]))[0]
            assert np.allclose(after - before, 1.0)  # applied ONCE
            refreshes = [
                i.value for i in reg.instruments()
                if i.name == "elastic_epoch_refreshes_total"
            ]
            assert refreshes and refreshes[0] >= 1
            assert client.partitioner.num_shards == 2
            srv1.stop()
            shard1.close()
        finally:
            client.close()
            srv0.stop()
            shard0.close()


# ---------------------------------------------------------------------------
# the controller policy
# ---------------------------------------------------------------------------


class TestController:
    def _driver(self, tmp_path, reg):
        logic = OnlineMatrixFactorization(
            32, 4, updater=SGDUpdater(0.05), seed=1
        )
        d = ElasticClusterDriver(
            logic, capacity=64, value_shape=(4,),
            init_fn=ranged_random_factor(3, (4,)),
            config=ElasticClusterConfig(
                num_shards=1, num_workers=1,
                wal_dir=str(tmp_path / "wal"),
            ),
            registry=reg,
        )
        d.start()
        return d

    def test_pressure_scales_out_idle_scales_in(self, tmp_path):
        reg = MetricsRegistry()
        d = self._driver(tmp_path, reg)
        try:
            ctl = ElasticController(
                d,
                policy=ScalePolicy(
                    max_shards=4, min_window_frames=5, cooldown_s=0.0
                ),
                registry=reg,
            )
            assert ctl.step() is None  # no signal, no action
            h = [
                i for i in reg.instruments()
                if i.name == "cluster_pull_rtt_seconds"
            ][0]
            for _ in range(50):
                h.observe(0.2)  # fat tail → pressure
            act = ctl.step()
            assert act and act["action"] == "scale_out" and act["ok"]
            assert d.partitioner.num_shards == 2
            for _ in range(50):
                h.observe(0.0001)  # idle tail → drain
            act = ctl.step()
            assert act and act["action"] == "scale_in" and act["ok"]
            assert d.partitioner.num_shards == 1
        finally:
            d.stop()

    def test_dead_shard_replaced_first(self, tmp_path):
        reg = MetricsRegistry()
        d = self._driver(tmp_path, reg)
        try:
            ctl = ElasticController(
                d, policy=ScalePolicy(cooldown_s=100.0), registry=reg
            )
            d.kill_shard(0)
            act = ctl.step()  # replace ignores cooldown
            assert act and act["action"] == "replace" and act["ok"]
            assert d.shard_alive(0)
        finally:
            d.stop()

    def test_cooldown_gates_resizes(self, tmp_path):
        reg = MetricsRegistry()
        d = self._driver(tmp_path, reg)
        try:
            ctl = ElasticController(
                d,
                policy=ScalePolicy(
                    max_shards=4, min_window_frames=5, cooldown_s=100.0
                ),
                registry=reg,
            )
            h = [
                i for i in reg.instruments()
                if i.name == "cluster_pull_rtt_seconds"
            ][0]
            for _ in range(50):
                h.observe(0.2)
            assert ctl.step()["action"] == "scale_out"
            for _ in range(50):
                h.observe(0.2)
            assert ctl.step() is None  # cooling down
        finally:
            d.stop()


# ---------------------------------------------------------------------------
# satellites: LineServer thread hygiene, lint, report
# ---------------------------------------------------------------------------


class _Echo(LineServer):
    def respond(self, line):
        return "ok " + line


def test_lineserver_stop_joins_handler_threads():
    """utils/net satellite: stop() joins the per-connection dispatcher
    threads — including one still BLOCKED in its linger-recv on an
    open client connection (the event-loop fast path) — so repeated
    scale-in/out cycles in one process don't leak a thread (and its
    socket buffers) per connection ever accepted."""
    import socket as socket_mod

    for _ in range(5):
        srv = _Echo().start()
        for _ in range(3):
            assert request_lines(
                srv.host, srv.port, ["ping"]
            ) == ["ok ping"]
        # one ACTIVE connection left open: after answering, its
        # dispatcher lingers in recv() when stop() runs — exactly the
        # blocked-thread case (a never-written connection costs no
        # thread at all under the selectors loop — that's the point)
        idle = socket_mod.create_connection((srv.host, srv.port))
        idle.sendall(b"ping\n")
        assert idle.recv(1 << 12) == b"ok ping\n"
        deadline = time.monotonic() + 5
        live = []
        while not live and time.monotonic() < deadline:
            live = [t for t in srv._handlers if t.is_alive()]
            time.sleep(0.002)
        assert live, "dispatcher thread never spawned"
        srv.stop()
        # stop() joined what it saw; a handler registered concurrently
        # with the shutdown exits on the stop flag — grace-wait, then
        # nothing may still be running
        deadline = time.monotonic() + 5
        while (
            any(t.is_alive() for t in live + srv._handlers)
            and time.monotonic() < deadline
        ):
            time.sleep(0.01)
        assert not any(t.is_alive() for t in live)  # joined, not leaked
        assert not any(t.is_alive() for t in srv._handlers)
        idle.close()


def test_elastic_component_lints_clean():
    """tools satellite: component=elastic registry lines pass the
    metric-line lint; a typo'd variant fails it."""
    import tools.check_metric_lines as lint

    reg = MetricsRegistry()
    reg.counter("elastic_epoch_flips_total", component="elastic").inc()
    line = reg.emit()
    assert lint.check_lines([line]) == []
    bad = line.replace('"component": "elastic"', '"component": "elastik"')
    problems = lint.check_lines([bad])
    assert problems and "elastik" in problems[0][1]


def test_run_report_carries_elastic_section():
    from flink_parameter_server_tpu.telemetry import (
        build_run_report,
        render_markdown,
    )

    reg = MetricsRegistry()
    reg.gauge("elastic_epoch", component="elastic").set(3)
    reg.counter(
        "elastic_rows_migrated_total", component="elastic"
    ).inc(42)
    reg.counter(
        "elastic_hedged_pulls_total", component="elastic"
    ).inc(5)
    reg.counter(
        "elastic_hedges_won_total", component="elastic"
    ).inc(2)
    report = build_run_report(reg)
    assert report["elastic"]["epoch"] == 3
    assert report["elastic"]["rows_migrated"] == 42
    assert report["elastic"]["hedged_pulls"] == 5
    md = render_markdown(report)
    assert "rows migrated" in md and "hedged pulls" in md
    assert json.loads(json.dumps(report))  # json-clean
