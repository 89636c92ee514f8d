"""loadgen/: open-loop arrivals, Zipf populations, the overload-control
plane (shed / retry budget / breaker / brownout), its wiring through
the shard + serving edges and the cluster client, ``psctl slo``, the
elastic-controller flapping regression, and an end-to-end soak smoke
(marker ``soak``)."""
import io
import json
import time
from contextlib import redirect_stdout

import numpy as np
import pytest

from flink_parameter_server_tpu.loadgen.arrivals import (
    constant_rate,
    diurnal_rate,
    flash_crowds,
    poisson_arrivals,
    ramp_rate,
    split_slots,
)
from flink_parameter_server_tpu.loadgen.overload import (
    BreakerBoard,
    BrownoutController,
    CircuitBreaker,
    LoadShedder,
    OverloadGuard,
    OverloadedError,
    RetryBudget,
    RetryBudgetExhausted,
)
from flink_parameter_server_tpu.loadgen.population import (
    Region,
    UserPopulation,
)
from flink_parameter_server_tpu.telemetry.registry import MetricsRegistry

pytestmark = pytest.mark.loadgen


# ---------------------------------------------------------------------------
# arrivals.py — seeded open-loop schedules
# ---------------------------------------------------------------------------


class TestArrivals:
    def test_poisson_seeded_and_rate_tracking(self):
        fn, mx = constant_rate(200.0)
        a = poisson_arrivals(fn, mx, 10.0, seed=7)
        b = poisson_arrivals(fn, mx, 10.0, seed=7)
        np.testing.assert_array_equal(a, b)  # the schedule IS the seed
        assert poisson_arrivals(fn, mx, 10.0, seed=8).size != 0
        # mean rate within 4 sigma of a Poisson(2000) draw
        assert abs(len(a) - 2000) < 4 * np.sqrt(2000)
        assert np.all(np.diff(a) >= 0) and a[0] >= 0 and a[-1] < 10.0

    def test_diurnal_shape(self):
        fn, mx = diurnal_rate(50.0, 250.0, period_s=100.0)
        assert fn(0.0) == pytest.approx(50.0)
        assert fn(50.0) == pytest.approx(250.0)  # peak half a period in
        assert mx == 250.0
        a = poisson_arrivals(fn, mx, 100.0, seed=1)
        # the peak half carries more traffic than the trough half
        first = ((a >= 25.0) & (a < 75.0)).sum()  # around the peak
        rest = len(a) - first
        assert first > 1.4 * rest

    def test_flash_crowds_multiply(self):
        base, mx = constant_rate(100.0)
        fn, worst = flash_crowds(base, mx, [(5.0, 2.0, 4.0)])
        assert fn(4.9) == 100.0 and fn(5.5) == 400.0 and fn(7.1) == 100.0
        assert worst == 400.0
        a = poisson_arrivals(fn, worst, 10.0, seed=2)
        spike = ((a >= 5.0) & (a < 7.0)).sum()
        calm = ((a >= 0.0) & (a < 2.0)).sum()
        assert spike > 2.5 * calm

    def test_ramp_and_thinning_bound(self):
        fn, mx = ramp_rate(10.0, 100.0, 10.0)
        assert fn(0) == 10.0 and fn(10.0) == 100.0 and fn(99.0) == 100.0
        assert mx == 100.0
        with pytest.raises(ValueError, match="exceeds rate_max"):
            poisson_arrivals(lambda t: 50.0, 10.0, 5.0, seed=0)

    def test_split_slots_preserves_absolute_times(self):
        a = np.arange(10, dtype=np.float64)
        slots = split_slots(a, 3)
        assert sorted(np.concatenate(slots).tolist()) == a.tolist()
        np.testing.assert_array_equal(slots[1], [1.0, 4.0, 7.0])


# ---------------------------------------------------------------------------
# population.py — Zipf users/items, regional mixes
# ---------------------------------------------------------------------------


class TestPopulation:
    def test_regional_serve_train_mix(self):
        pop = UserPopulation(
            64, 256,
            regions=[Region("r1", weight=1.0, serve_frac=0.8)],
            seed=3,
        )
        reqs = pop.request_stream(1000, seed=4)
        serve = sum(1 for r in reqs if r.kind == "serve")
        assert 740 <= serve <= 860  # 0.8 ± sampling noise
        assert {r.region for r in reqs} == {"r1"}

    def test_zipf_head_concentration_and_secret_head(self):
        pop = UserPopulation(128, 2048, zipf_s=1.1, seed=5)
        share = pop.head_share(20)
        assert 0.15 < share < 0.9
        hot = pop.hot_items(20)
        # the hot head is a seeded permutation, not [0..20)
        assert set(hot.tolist()) != set(range(20))
        reqs = pop.request_stream(600, seed=6)
        ids = np.concatenate([r.ids for r in reqs])
        observed = np.isin(ids, hot).mean()
        assert observed > 0.6 * share  # the head actually dominates

    def test_deterministic_streams(self):
        pop = UserPopulation(32, 64, seed=9)
        a = pop.request_stream(50, seed=1)
        b = pop.request_stream(50, seed=1)
        for x, y in zip(a, b):
            assert x.kind == y.kind and x.user == y.user
            np.testing.assert_array_equal(x.ids, y.ids)


# ---------------------------------------------------------------------------
# overload.py — budget, breaker, shedders, brownout
# ---------------------------------------------------------------------------


class TestRetryBudget:
    def test_spend_exhaust_refill(self):
        reg = MetricsRegistry()
        b = RetryBudget(
            2.0, refill_per_success=0.5, registry=reg, worker="w0"
        )
        assert b.try_spend() and b.try_spend()
        assert not b.try_spend()  # dry
        assert b.exhausted == 1
        for _ in range(2):
            b.on_success()
        assert b.tokens() == pytest.approx(1.0)
        assert b.try_spend() and not b.try_spend()
        gauges = [
            i for i in reg.instruments()
            if i.name == "retry_budget_tokens"
        ]
        assert gauges and gauges[0].value == pytest.approx(0.0)
        counters = [
            i for i in reg.instruments()
            if i.name == "retry_budget_exhausted_total"
        ]
        assert counters[0].value == 2.0

    def test_refill_caps_at_capacity(self):
        b = RetryBudget(1.5, refill_per_success=10.0, registry=False)
        b.on_success()
        assert b.tokens() == pytest.approx(1.5)


class TestCircuitBreaker:
    def test_full_cycle(self):
        clock = [0.0]
        br = CircuitBreaker(
            window_s=1.0, min_failures=3, failure_rate=0.5,
            cooldown_s=0.5, clock=lambda: clock[0],
        )
        assert br.allow() and br.state == "closed"
        for _ in range(3):
            br.fail()
        assert br.state == "open" and not br.allow()
        clock[0] = 0.6  # cooldown elapsed → one half-open probe
        assert br.allow() and br.state == "half_open"
        assert not br.allow()  # only one probe at a time
        br.ok()
        assert br.state == "closed" and br.allow()

    def test_half_open_probe_failure_reopens(self):
        clock = [0.0]
        br = CircuitBreaker(
            min_failures=2, cooldown_s=0.5, clock=lambda: clock[0]
        )
        br.fail()
        br.fail()
        assert br.state == "open"
        clock[0] = 0.6
        assert br.allow()
        br.fail()  # the probe failed
        assert br.state == "open" and not br.allow()
        clock[0] = 1.2  # another cooldown, another probe
        assert br.allow()

    def test_failure_rate_gate(self):
        """Plenty of successes in the window keep the breaker closed
        even past min_failures — it is a RATE breaker, not a count."""
        clock = [0.0]
        br = CircuitBreaker(
            min_failures=3, failure_rate=0.5, clock=lambda: clock[0]
        )
        for _ in range(10):
            br.ok()
        for _ in range(4):
            br.fail()
        assert br.state == "closed"  # 4/14 < 0.5

    def test_board_keys_and_gauges(self):
        clock = [0.0]
        reg = MetricsRegistry()
        board = BreakerBoard(
            min_failures=2, cooldown_s=0.5, registry=reg,
            clock=lambda: clock[0],
        )
        assert board.allow(0) and board.allow(1)
        board.fail(0)
        board.fail(0)
        assert not board.allow(0) and board.allow(1)  # per-shard
        assert board.open_count() == 1
        g = [
            i for i in reg.instruments()
            if i.name == "overload_breaker_open"
        ][0]
        assert g.value == 1.0
        trans = [
            i for i in reg.instruments()
            if i.name == "overload_breaker_transitions_total"
            and i.labels.get("state") == "open"
        ][0]
        assert trans.value == 1.0


class TestShedders:
    def test_guard_priority_matrix(self):
        reg = MetricsRegistry()
        g = OverloadGuard(
            sheddable_depth=2, read_depth=8, write_depth=None,
            registry=reg, shard=0,
        )
        # lease + pr=2 reads shed first; plain reads hold to
        # read_depth; pushes never shed
        assert g.admit("pull", None, depth=8)
        assert not g.admit("pull", None, depth=9)
        assert g.admit("pull", 2, depth=2)
        assert not g.admit("pull", 2, depth=3)
        assert not g.admit("lease", None, depth=3)
        assert g.admit("push", 2, depth=1000)  # write class wins
        assert g.admit("pull", 0, depth=1000)  # pr=0 = critical
        assert g.sheds == 3
        shed_counters = {
            i.labels.get("verb"): i.value
            for i in reg.instruments()
            if i.name == "overload_shed_total"
        }
        assert shed_counters["pull"] == 2.0
        assert shed_counters["lease"] == 1.0

    def test_load_shedder_fractions(self):
        s = LoadShedder(shed_at=0.5, normal_at=0.75, registry=False)
        assert s.admit(1, 10)                       # 10% — everyone in
        assert not s.admit(5, 10)                   # sheddable out at 50%
        assert s.admit(5, 10, priority=1)           # normal rides to 75%
        assert not s.admit(8, 10, priority=1)
        assert s.admit(10, 10, priority=0)          # critical never shed
        assert s.sheds == 2


class TestBrownout:
    def test_enter_widen_exit(self):
        from flink_parameter_server_tpu.hotcache.cache import HotRowCache

        clock = [0.0]
        cache = HotRowCache(4, registry=False)
        ctl = BrownoutController(
            [cache], widen_factor=3.0, enter_sheds=3, window_s=1.0,
            exit_quiet_s=0.5, registry=False, clock=lambda: clock[0],
        )
        for _ in range(3):
            ctl.note_shed()
        assert ctl.active and cache.widen_mult == 3.0
        assert ctl.entries == 1
        clock[0] = 0.3
        ctl.note_ok()
        assert ctl.active  # not quiet long enough
        clock[0] = 0.9
        ctl.note_ok()
        assert not ctl.active and cache.widen_mult == 1.0

    def test_widen_serves_stale_within_widened_bound(self):
        from flink_parameter_server_tpu.hotcache.cache import HotRowCache

        cache = HotRowCache(2, jitter_frac=0.0, registry=False)
        cache.fill([7], np.ones((1, 2), np.float32))
        for _ in range(3):
            cache.tick()
        # age 3 > bound 2: normally a stale reject
        assert cache.lookup([7]) == {}
        assert cache.stats()["stale_rejects"] == 1
        cache.fill([7], np.ones((1, 2), np.float32))
        for _ in range(3):
            cache.tick()
        cache.set_widen(2.0)  # brownout: bound 2 → 4
        hits = cache.lookup([7])
        assert 7 in hits
        st = cache.stats()
        assert st["max_served_age"] == 3  # the audit still tracks
        assert st["widen_mult"] == 2.0
        assert st["effective_bound"] == 4
        # age 5 > widened bound 4: even brownout has a real bound
        cache.tick()
        cache.tick()
        assert cache.lookup([7]) == {}

    def test_attach_during_brownout_widens_immediately(self):
        from flink_parameter_server_tpu.hotcache.cache import HotRowCache

        ctl = BrownoutController(
            [], widen_factor=2.0, enter_sheds=1, registry=False
        )
        ctl.note_shed()
        assert ctl.active
        cache = HotRowCache(4, registry=False)
        ctl.attach(cache)
        assert cache.widen_mult == 2.0


# ---------------------------------------------------------------------------
# the shard edge: err overloaded + pr= priority over the real protocol
# ---------------------------------------------------------------------------


class TestShardEdge:
    def _shard_server(self, guard):
        from flink_parameter_server_tpu.cluster.partition import (
            RangePartitioner,
        )
        from flink_parameter_server_tpu.cluster.shard import (
            ParamShard,
            ShardServer,
        )

        part = RangePartitioner(16, 1)
        shard = ParamShard(0, part, (2,), registry=False)
        return ShardServer(shard, supervised=False, overload=guard)

    def test_sheds_reads_before_writes(self):
        guard = OverloadGuard(
            sheddable_depth=2, read_depth=4, registry=False
        )
        srv = self._shard_server(guard)
        # fake a deep queue: respond() reads the live depth, which
        # includes concurrent handler threads in production
        with srv.shard._depth_lock:
            srv.shard._active_requests = 10
        try:
            assert srv.respond("pull 0,1 b64 pr=2") == "err overloaded"
            assert srv.respond("lease 0 b64 sess=s1") == "err overloaded"
            assert srv.respond("pull 0,1 b64") == "err overloaded"
            # training pushes go through at any depth
            resp = srv.respond(
                "push 0,1 b64:"
                + __import__("base64").b64encode(
                    np.ones((2, 2), "<f4").tobytes()
                ).decode()
            )
            assert resp.startswith("ok applied=2")
        finally:
            with srv.shard._depth_lock:
                srv.shard._active_requests = 0
        # depth back to normal: reads admitted again
        assert srv.respond("pull 0 b64 pr=2").startswith("ok n=1")
        assert guard.sheds == 3

    def test_client_raises_typed_overloaded(self):
        from flink_parameter_server_tpu.cluster.client import (
            ClusterClient,
        )

        guard = OverloadGuard(sheddable_depth=1, registry=False)
        srv = self._shard_server(guard).start()
        try:
            client = ClusterClient(
                [(srv.host, srv.port)], srv.shard.partitioner, (2,),
                registry=False, priority=2,
            )
            # priority rides the frame
            assert " pr=2" in client._frame_suffix()
            client.pull_batch(np.arange(2))  # healthy: served
            with srv.shard._depth_lock:
                srv.shard._active_requests = 10
            try:
                with pytest.raises(OverloadedError):
                    client.pull_batch(np.arange(2))
            finally:
                with srv.shard._depth_lock:
                    srv.shard._active_requests = 0
            client.close()
        finally:
            srv.stop()
            srv.shard.close()

    def test_pre_overload_server_ignores_pr(self):
        """Old servers parse-and-ignore pr= (the trailing-token
        contract): no guard attached, any priority is served."""
        srv = self._shard_server(None).start()
        try:
            from flink_parameter_server_tpu.cluster.client import (
                ClusterClient,
            )

            client = ClusterClient(
                [(srv.host, srv.port)], srv.shard.partitioner, (2,),
                registry=False, priority=2,
            )
            out = client.pull_batch(np.arange(4))
            assert out.shape == (4, 2)
            client.close()
        finally:
            srv.stop()
            srv.shard.close()


# ---------------------------------------------------------------------------
# the client: retry budget + retries counter + breaker wiring
# ---------------------------------------------------------------------------


class _StubView:
    def __init__(self, part, addrs):
        self.epoch = 1
        self.partitioner = part
        self.addresses = addrs
        self.replicas = []


class _StubMembership:
    def __init__(self, part, addrs):
        self._view = _StubView(part, addrs)

    def current(self):
        return self._view


class TestClientBudget:
    def _client(self, reg, budget):
        from flink_parameter_server_tpu.cluster.client import (
            ClusterClient,
        )
        from flink_parameter_server_tpu.cluster.partition import (
            ConsistentHashPartitioner,
        )

        part = ConsistentHashPartitioner(16, 1)
        return ClusterClient(
            value_shape=(2,),
            membership=_StubMembership(part, [("127.0.0.1", 1)]),
            registry=reg,
            worker="budget-test",
            retry_budget=budget,
            retry_sleep_s=1e-4,
            retry_sleep_cap_s=1e-3,
        )

    def test_storm_retries_spend_budget_and_fail_fast(self):
        reg = MetricsRegistry()
        budget = RetryBudget(2.0, registry=False)
        client = self._client(reg, budget)
        deadline = time.monotonic() + 60
        client._await_retry(deadline, 1, "pull", reason="conn")
        client._await_retry(deadline, 2, "pull", reason="conn")
        with pytest.raises(RetryBudgetExhausted):
            client._await_retry(deadline, 3, "pull", reason="conn")
        retries = [
            i for i in reg.instruments()
            if i.name == "client_retries_total"
        ]
        assert retries, "retry volume is visible on /metrics now"
        labels = {(i.labels["verb"], i.labels["reason"]): i.value
                  for i in retries}
        assert labels[("pull", "conn")] == 3.0

    def test_control_plane_retries_do_not_spend(self):
        """stale-epoch/frozen replays are the elastic control plane
        working, not a storm — an exhausted budget must not shed
        them."""
        reg = MetricsRegistry()
        budget = RetryBudget(1.0, registry=False)
        client = self._client(reg, budget)
        budget.try_spend()  # dry
        deadline = time.monotonic() + 60
        client._await_retry(deadline, 1, "push", reason="stale-epoch")
        client._await_retry(deadline, 2, "push", reason="frozen")
        with pytest.raises(RetryBudgetExhausted):
            client._await_retry(deadline, 3, "push", reason="conn")

    def test_breaker_open_short_circuits_before_the_wire(self):
        from flink_parameter_server_tpu.cluster.client import _Rejected

        reg = MetricsRegistry()
        board = BreakerBoard(
            min_failures=1, failure_rate=0.1, cooldown_s=60.0,
            registry=False,
        )
        client = self._client(reg, None)
        client.breakers = board
        board.fail(0)
        assert board.state(0) == "open"
        with pytest.raises(_Rejected) as e:
            client._request_frames(
                0, np.arange(2), ["pull 0,1 b64"], hedgeable=False
            )
        assert e.value.reason == "breaker_open"


# ---------------------------------------------------------------------------
# the serving admission edge: reject reasons, shed, deadline
# ---------------------------------------------------------------------------


class TestServingAdmission:
    def _service(self, reg, **kw):
        from flink_parameter_server_tpu.core.store import (
            ShardedParamStore,
        )
        from flink_parameter_server_tpu.serving.batcher import (
            RequestBatcher,
        )
        from flink_parameter_server_tpu.serving.engine import QueryEngine
        from flink_parameter_server_tpu.serving.server import (
            ServingService,
        )
        from flink_parameter_server_tpu.serving.snapshot import (
            SnapshotManager,
        )
        from flink_parameter_server_tpu.utils.initializers import (
            normal_factor,
        )

        store = ShardedParamStore.create(
            16, (2,), init_fn=normal_factor(0, (2,))
        )
        mgr = SnapshotManager(store.spec)
        mgr.publish(store.table, step=0)
        batcher = RequestBatcher(
            max_batch=4, max_queue=kw.pop("max_queue", 4),
            deadline_ms=kw.pop("deadline_ms", None),
        )
        return ServingService(
            QueryEngine(mgr), batcher=batcher, registry=reg, **kw
        )

    def _reason_counts(self, reg):
        return {
            i.labels.get("reason"): i.value
            for i in reg.instruments()
            if i.name == "serving_rejected_total"
            and "reason" in i.labels
        }

    def test_queue_full_reason(self):
        from flink_parameter_server_tpu.serving.batcher import QueueFull

        reg = MetricsRegistry()
        svc = self._service(reg, max_queue=2)
        svc.submit_lookup([1])
        svc.submit_lookup([2])
        with pytest.raises(QueueFull):
            svc.submit_lookup([3])
        counts = self._reason_counts(reg)
        assert counts["queue_full"] == 1.0
        assert counts["shed"] == 0.0 and counts["deadline"] == 0.0
        svc.batcher.close()

    def test_shed_reason_below_hard_line(self):
        from flink_parameter_server_tpu.serving.batcher import QueueFull

        reg = MetricsRegistry()
        svc = self._service(
            reg, max_queue=4,
            shedder=LoadShedder(
                shed_at=0.25, normal_at=0.5, registry=False
            ),
        )
        svc.submit_lookup([1])  # depth 0 → admitted
        with pytest.raises(QueueFull):  # depth 1/4 = 0.25 → shed
            svc.submit_lookup([2])
        assert self._reason_counts(reg)["shed"] == 1.0
        assert svc.metrics.total_rejected == 1
        svc.batcher.close()

    def test_deadline_reason_and_wire_answer(self):
        from flink_parameter_server_tpu.serving.batcher import (
            DeadlineExceeded,
        )
        from flink_parameter_server_tpu.serving.server import (
            format_response,
        )

        reg = MetricsRegistry()
        svc = self._service(reg, deadline_ms=10.0)
        fut = svc.submit_lookup([1])
        time.sleep(0.05)  # blow the queue-wait deadline pre-dispatch
        svc.start()
        with pytest.raises(DeadlineExceeded):
            fut.result(5.0)
        assert self._reason_counts(reg)["deadline"] == 1.0
        # a fresh request is served normally afterwards
        res = svc.submit_lookup([1]).result(5.0)
        assert format_response(res).startswith("ok ")
        svc.stop()

    def test_tcp_maps_deadline_to_err(self):
        from flink_parameter_server_tpu.serving.server import ServingServer

        reg = MetricsRegistry()
        svc = self._service(reg, deadline_ms=1.0)
        # stall dispatch so the queue wait always blows the deadline
        srv = ServingServer(svc, request_timeout=5.0)
        fut = svc.submit_lookup([1])
        time.sleep(0.01)
        svc.start()
        with pytest.raises(Exception):
            fut.result(5.0)
        # respond() path: admitted, then expired in dispatch
        line = srv.respond("pull 1")
        assert line in ("err deadline",) or line.startswith("ok "), line
        svc.stop()


# ---------------------------------------------------------------------------
# psctl slo — the operator view
# ---------------------------------------------------------------------------


class TestPsctlSlo:
    def test_live_table_and_json(self):
        from flink_parameter_server_tpu.telemetry.exporter import (
            TelemetryServer,
        )
        from flink_parameter_server_tpu.telemetry.slo import (
            SLOEngine,
            serving_latency_slo,
        )
        from tools import psctl

        reg = MetricsRegistry()
        h = reg.histogram("serving_latency_seconds", component="serving")
        for _ in range(40):
            h.observe(0.001)
        engine = SLOEngine(
            [serving_latency_slo(0.05)], registry=reg,
            windows=(0.5, 1.0),
        )
        engine.sample()
        # overload-plane state on the same endpoint
        shed = LoadShedder(shed_at=0.1, normal_at=0.2, registry=reg)
        assert not shed.admit(5, 10)
        BreakerBoard(registry=reg).allow(0)
        tel = TelemetryServer(reg, port=0).start()
        try:
            buf = io.StringIO()
            with redirect_stdout(buf):
                rc = psctl.main([
                    "slo", "--metrics", f"{tel.host}:{tel.port}",
                    "--iterations", "1", "--raw",
                ])
            out = buf.getvalue()
            assert rc == 0
            assert "psctl slo" in out
            assert "serving_p99" in out and "ok" in out
            assert "serving/submit=1" in out
            assert "breakers open 0" in out
            buf = io.StringIO()
            with redirect_stdout(buf):
                rc = psctl.main([
                    "slo", "--metrics", f"{tel.host}:{tel.port}",
                    "--json",
                ])
            doc = json.loads(buf.getvalue())
            assert rc == 0
            assert doc["slos"][0]["slo"] == "serving_p99"
            assert doc["slos"][0]["verdict"] == "ok"
            assert doc["sheds"] == {"serving/submit": 1}
        finally:
            tel.stop()


# ---------------------------------------------------------------------------
# elastic-controller flapping regression (satellite)
# ---------------------------------------------------------------------------


class _Report:
    rows_moved = 0


class _StubDriver:
    """Just enough driver for the controller: a mutable shard count,
    recorded resize calls, everything alive."""

    class _Part:
        def __init__(self):
            self.num_shards = 2

    def __init__(self):
        self.partitioner = self._Part()
        self.actions = []

    def shard_alive(self, s):
        return True

    def scale_out(self, add=1):
        self.partitioner.num_shards += add
        self.actions.append(("out", time.monotonic()))
        return _Report()

    def scale_in(self, remove=1):
        self.partitioner.num_shards -= remove
        self.actions.append(("in", time.monotonic()))
        return _Report()


class TestControllerFlapping:
    def _drive(self, policy, steps=60, step_sleep=0.01):
        from flink_parameter_server_tpu.elastic.controller import (
            ElasticController,
        )

        reg = MetricsRegistry()
        h = reg.histogram("cluster_pull_rtt_seconds", component="cluster")
        driver = _StubDriver()
        ctl = ElasticController(driver, policy=policy, registry=reg)
        for i in range(steps):
            # oscillating load exactly at the scale boundary: fat-tail
            # window, then idle window, alternating every evaluation
            v = 0.2 if i % 2 == 0 else 0.0001
            for _ in range(60):
                h.observe(v)
            ctl.step()
            time.sleep(step_sleep)
        return driver

    def test_cooldown_and_hysteresis_bound_thrash(self):
        from flink_parameter_server_tpu.elastic.controller import (
            ScalePolicy,
        )

        policy = ScalePolicy(
            min_shards=1, max_shards=4, min_window_frames=5,
            cooldown_s=0.15, scale_in_consecutive=2,
        )
        driver = self._drive(policy, steps=40, step_sleep=0.01)
        # 40 steps × 10 ms = ~0.4 s of oscillation: cooldown 0.15 s
        # bounds actions to ~ duration/cooldown (+1 for the first)
        assert len(driver.actions) <= 4, driver.actions
        # hysteresis: a single idle window between two pressured ones
        # must never shrink — no "in" can directly follow an "out"
        # within one cooldown period
        for (kind_a, t_a), (kind_b, t_b) in zip(
            driver.actions, driver.actions[1:]
        ):
            if kind_a == "out" and kind_b == "in":
                assert t_b - t_a >= policy.cooldown_s

    def test_single_idle_window_does_not_scale_in(self):
        from flink_parameter_server_tpu.elastic.controller import (
            ElasticController,
            ScalePolicy,
        )

        reg = MetricsRegistry()
        h = reg.histogram("cluster_pull_rtt_seconds", component="cluster")
        driver = _StubDriver()
        ctl = ElasticController(
            driver,
            policy=ScalePolicy(
                min_shards=1, max_shards=4, min_window_frames=5,
                cooldown_s=0.0, scale_in_consecutive=2,
            ),
            registry=reg,
        )
        for _ in range(60):
            h.observe(0.0001)
        assert ctl.step() is None  # first idle window: a data point
        for _ in range(60):
            h.observe(0.0001)
        act = ctl.step()  # second consecutive: the decision
        assert act and act["action"] == "scale_in"
        assert act["idle_streak"] == 2
        # pressure resets the streak
        for _ in range(60):
            h.observe(0.0001)
        assert ctl.step() is None  # streak restarted after the shrink


# ---------------------------------------------------------------------------
# the end-to-end soak smoke (marker: soak)
# ---------------------------------------------------------------------------


@pytest.mark.soak
class TestSoakSmoke:
    def test_short_soak_with_fault_holds_invariants(self):
        from flink_parameter_server_tpu.loadgen.soak import (
            SoakConfig,
            run_soak,
        )
        from flink_parameter_server_tpu.nemesis.scenarios import NemesisOp

        cfg = SoakConfig(
            duration_s=2.5,
            offered_rps=80.0,
            generators=2,
            train_workers=1,
            num_users=64,
            num_items=256,
            dim=4,
            num_shards=2,
            link_delay_ms=0.2,
            slo_ms=200.0,
            overload_control=True,
            warmup_requests=16,
            nemesis=(
                (0.8, NemesisOp(0, "partition", shard=0, mode="both",
                                ms=250.0)),
            ),
            seed=11,
        )
        rep = run_soak(cfg)
        s = rep.summary
        # every arrival classified exactly once
        assert s["arrivals"] == (
            s["ok"] + s["late"] + s["shed"] + s["error"]
        )
        assert s["latency_anchor"] == "arrival"
        assert s["ok"] > 0
        for v in rep.verdicts:
            assert v.ok, f"{v.name}: {v.detail}"
        assert rep.faults.get("partition_both", 0) >= 1
        # the report round-trips to JSON (the artifact path)
        json.dumps(rep.as_dict())

    def test_overload_arm_sheds_instead_of_erroring(self):
        """A heavily oversubscribed mini-soak with control ON: badput
        is typed sheds, not errors, and the ledger still balances."""
        from flink_parameter_server_tpu.loadgen.soak import (
            SoakConfig,
            run_soak,
        )

        cfg = SoakConfig(
            duration_s=2.0,
            offered_rps=400.0,  # far past a 1-shard mini-topology
            generators=2,
            train_workers=1,
            num_users=32,
            num_items=128,
            dim=4,
            num_shards=1,
            link_delay_ms=0.5,
            slo_ms=60.0,
            overload_control=True,
            warmup_requests=16,
            seed=13,
        )
        rep = run_soak(cfg)
        s = rep.summary
        assert s["shed"] > 0, "overload must surface as typed sheds"
        assert s["error"] == 0
        ledger = next(
            v for v in rep.verdicts if v.name == "exactly_once_ledger"
        )
        assert ledger.ok, ledger.detail
