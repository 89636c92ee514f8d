"""Latency-budget profiler suite (telemetry/profiler.py, the wire
byte ledger in utils/net.py, and the psctl CLI — docs/observability.md).

The load-bearing acceptance tests:

  * phase decomposition sums to within 10% of the measured pull p50
    against a SPAN-TRACE ORACLE (the client's per-shard round spans,
    timed independently of the phase timers);
  * `psctl` smoke against a LIVE 2-shard cluster mid-training (top /
    stats / conns / budget verbs over real sockets);
  * wire bytes/frames counted per (direction, verb, role) and exposed
    on /metrics as fps_net_bytes_total / fps_net_frames_total;
  * the stack sampler samples a busy function and exports folded
    stacks + a TraceCollector-mergeable ring;
  * the budget artifact lints via check_metric_lines --budget.
"""
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
from flink_parameter_server_tpu.telemetry.profiler import (
    NULL_PROFILER,
    PhaseProfiler,
    StackSampler,
    resolve_profiler,
)
from flink_parameter_server_tpu.utils.net import (
    LineServer,
    request_lines,
)

pytestmark = pytest.mark.profile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def fresh_registry():
    reg = tm.MetricsRegistry(run_id="test-profiler")
    tm.set_registry(reg)
    tm.set_profiler(None)  # auto default follows the registry swap
    yield reg
    tm.set_registry(None)
    tm.set_profiler(None)


# -- PhaseProfiler unit behaviour --------------------------------------------


def test_phase_observations_land_in_registry_and_reservoir(fresh_registry):
    prof = PhaseProfiler(fresh_registry)
    for v in (0.001, 0.002, 0.003):
        prof.observe("pull", "client_parse", v)
    with prof.timer("pull", "rtt"):
        time.sleep(0.002)
    st = prof.stat("pull", "client_parse")
    assert st["count"] == 3
    assert st["p50"] == pytest.approx(0.002)
    assert st["mean"] == pytest.approx(0.002)
    assert prof.stat("pull", "rtt")["p50"] >= 0.002
    # the same observations are live on the prometheus surface
    text = tm.prometheus_text(fresh_registry)
    assert 'fps_phase_seconds_count{component="profiler"' in text
    assert 'phase="client_parse"' in text


def test_budget_residuals_close_the_books(fresh_registry):
    prof = PhaseProfiler(fresh_registry)
    # a synthetic round: 1 ms RTT of which the server accounts 0.6 ms
    # (0.1 queue + 0.2 parse + 0.2 apply + 0.05 serialize + 0.05 other)
    for _ in range(50):
        prof.observe("pull", "client_serialize", 0.0001)
        prof.observe("pull", "rtt", 0.001)
        prof.observe("pull", "client_parse", 0.0002)
        prof.observe("pull", "server_total", 0.0006)
        prof.observe("pull", "server_queue_wait", 0.0001)
        prof.observe("pull", "server_parse", 0.0002)
        prof.observe("pull", "scatter_apply", 0.0002)
        prof.observe("pull", "response_serialize", 0.00005)
    b = prof.budget("pull")
    assert b["coverage"] == "full"
    assert b["round_ms"] == pytest.approx(1.3, rel=0.01)
    by = {p["phase"]: p for p in b["phases"]}
    assert by["wire"]["p50_ms"] == pytest.approx(0.4, rel=0.01)
    assert by["server_other"]["p50_ms"] == pytest.approx(0.05, rel=0.05)
    # phases sum to the round (the additivity contract)
    total = sum(p["p50_ms"] for p in b["phases"])
    assert total == pytest.approx(b["round_ms"], rel=0.01)
    assert sum(p["pct"] for p in b["phases"]) == pytest.approx(
        100.0, abs=1.0
    )
    assert b["top_phase"] == "wire"


def test_null_profiler_and_resolution(fresh_registry):
    assert resolve_profiler(False) is NULL_PROFILER
    with NULL_PROFILER.timer("pull", "rtt"):
        pass
    NULL_PROFILER.observe("pull", "rtt", 1.0)  # no-op, no instrument
    assert "phase_seconds" not in fresh_registry.snapshot()
    prof = PhaseProfiler(fresh_registry)
    assert resolve_profiler(prof) is prof
    # the auto default follows the process registry
    assert tm.get_profiler().registry is fresh_registry


# -- wire byte accounting (utils/net.py) -------------------------------------


class _EchoServer(LineServer):
    def respond(self, line):
        return "ok " + line


def test_line_server_counts_bytes_frames_and_conns(fresh_registry):
    with _EchoServer(name="echo") as srv:
        reqs = ["pull 1,2,3", "pull 9", "push 4 0.5"]
        resps = request_lines(srv.host, srv.port, reqs)
        assert resps == ["ok " + r for r in reqs]
        snap = fresh_registry.snapshot()

        def val(name, **want):
            total = 0.0
            for s in snap.get(name, ()):
                if all(s["labels"].get(k) == v for k, v in want.items()):
                    total += s["value"] or 0
            return total

        # server-side: request bytes in, response bytes out, per verb
        pull_in = sum(len(r) + 1 for r in reqs if r.startswith("pull"))
        assert val("net_bytes_total", direction="in", verb="pull",
                   role="server") == pull_in
        assert val("net_frames_total", direction="in", verb="pull",
                   role="server") == 2
        assert val("net_frames_total", direction="out", verb="push",
                   role="server") == 1
        # client-side helper counts the same frames under role=client
        assert val("net_frames_total", direction="out", verb="pull",
                   role="client") == 2
        assert val("net_bytes_total", direction="in", verb="push",
                   role="client") == len("ok push 4 0.5") + 1
        # the exposition carries the fps_-prefixed family
        text = tm.prometheus_text(fresh_registry)
        assert 'fps_net_bytes_total{' in text


def test_conn_table_live_ledger(fresh_registry):
    import socket as socketlib

    with _EchoServer(name="echo") as srv:
        with socketlib.create_connection((srv.host, srv.port)) as s:
            s.sendall(b"pull 1,2\n")
            buf = b""
            while b"\n" not in buf:
                buf += s.recv(1 << 16)
            deadline = time.time() + 2
            table = srv.conn_table()
            while not table and time.time() < deadline:
                time.sleep(0.01)
                table = srv.conn_table()
            assert len(table) == 1
            c = table[0]
            assert c["frames_in"] == 1 and c["frames_out"] == 1
            assert c["bytes_in"] == len(b"pull 1,2\n")
            assert c["bytes_out"] == len(b"ok pull 1,2\n")
            assert c["last_verb"] == "pull"
            assert ":" in c["peer"]
        deadline = time.time() + 2
        while srv.conn_table() and time.time() < deadline:
            time.sleep(0.01)
        assert srv.conn_table() == []  # closed conns leave the table


# -- stack sampler ------------------------------------------------------------


def _busy(stop: threading.Event) -> None:
    while not stop.is_set():
        sum(i * i for i in range(500))


def test_stack_sampler_folded_and_ring():
    stop = threading.Event()
    t = threading.Thread(target=_busy, args=(stop,), name="busy-worker",
                         daemon=True)
    t.start()
    sampler = StackSampler(0.002)
    with sampler:
        time.sleep(0.25)
    stop.set()
    t.join(timeout=2)
    assert sampler.samples > 10
    folded = sampler.folded()
    assert any("_busy" in stack and "busy-worker" in stack
               for stack in folded)
    text = sampler.export_folded()
    line = next(ln for ln in text.splitlines() if "_busy" in ln)
    stack, count = line.rsplit(" ", 1)
    assert int(count) >= 1 and ";" in stack
    # top() redistributes every folded sample onto leaf frames — the
    # busy thread's leaf is wherever the loop was caught (`_busy`
    # itself or the genexpr inside it), and totals must balance
    tops = sampler.top(10_000)
    assert sum(n for _leaf, n in tops) == sum(folded.values())
    assert any(
        "_busy" in leaf or "<genexpr>" in leaf for leaf, _n in tops
    )
    # the sample ring rides the TraceCollector lanes
    ring = sampler.to_tracer()
    assert len(ring) > 0
    col = tm.TraceCollector()
    col.add(ring)
    doc = json.loads(col.export())
    stacks = [e for e in doc if e.get("cat") == "stack"]
    assert stacks and all("pid" in e for e in stacks)
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        from check_metric_lines import check_trace_events

        assert check_trace_events(doc) == []
    finally:
        sys.path.remove(os.path.join(REPO, "tools"))


def test_stack_sampler_bounds_distinct_stacks():
    sampler = StackSampler(0.001, max_stacks=1)
    with sampler:
        time.sleep(0.05)
    folded = sampler.folded()
    # at most the single allowed stack plus the overflow bucket
    assert len(folded) <= 2


# -- the acceptance pair: phase-sum oracle + live-cluster psctl smoke --------


@pytest.fixture()
def budget_cluster(fresh_registry, tmp_path):
    """A profiled+traced 2-shard cluster run (WAL on, so wal_append
    phases are real), yielding the budget and the span-trace oracle's
    verdict on it.  Phases accumulate in the CURRENT process
    registry/profiler (the run-report test reads them from there)."""
    from flink_parameter_server_tpu.cluster.driver import (
        ClusterConfig,
        ClusterDriver,
    )
    from flink_parameter_server_tpu.models.matrix_factorization import (
        OnlineMatrixFactorization,
        SGDUpdater,
    )
    from flink_parameter_server_tpu.utils.initializers import normal_factor

    rounds, batch, num_items, num_users, dim = 25, 192, 768, 192, 8
    rng = np.random.default_rng(0)
    batches = [
        {
            "user": rng.integers(0, num_users, batch).astype(np.int32),
            "item": ((rng.zipf(1.2, batch) - 1) % num_items).astype(
                np.int32
            ),
            "rating": rng.normal(0, 1, batch).astype(np.float32),
        }
        for _ in range(rounds)
    ]
    logic = OnlineMatrixFactorization(
        num_users, dim, updater=SGDUpdater(0.01)
    )
    cfg = ClusterConfig(
        num_shards=2, num_workers=1, staleness_bound=0,
        trace=True, profile=True, wal_dir=str(tmp_path / "wal"),
    )
    driver = ClusterDriver(
        logic, capacity=num_items, value_shape=(dim,),
        init_fn=normal_factor(1, (dim,)), config=cfg,
    )
    with driver:
        # warmup: the first rounds pay jit compiles (client step fn,
        # shard scatter buckets) that belong to no steady-state phase
        driver.run(batches[:5])
        driver.run(batches)
        budget = tm.get_profiler().budget_report()
        # the span-trace oracle: p50 of the client's per-shard
        # `pull.shard<k>` spans — one wall measurement covering
        # serialize → wire → parse, timed by the tracer, completely
        # independent of the phase timers the budget sums.  (batch ≤
        # chunk keeps one frame per span, so per-frame phases and
        # per-span walls describe the same window.)
        pulls = sorted(
            s["dur"] for s in driver.client_tracer.spans()
            if s["name"].startswith("pull.shard")
        )
    oracle_p50_ms = (
        round(pulls[len(pulls) // 2] * 1e3, 4) if pulls else None
    )
    pull_budget = budget.get("pull", {})
    round_ms = pull_budget.get("round_ms")
    return {
        "budget": budget,
        "oracle_pull_p50_ms": oracle_p50_ms,
        "budget_round_ms": round_ms,
        "coverage_error": (
            round(abs(round_ms - oracle_p50_ms) / oracle_p50_ms, 4)
            if round_ms and oracle_p50_ms else None
        ),
        "top_phase": pull_budget.get("top_phase"),
        "top_pct": pull_budget.get("top_pct"),
    }


def test_budget_phases_sum_to_pull_p50_against_span_oracle(budget_cluster):
    r = budget_cluster
    assert r["oracle_pull_p50_ms"] is not None
    assert r["budget_round_ms"] is not None
    # THE acceptance bar: phases sum within 10% of the span-traced
    # pull round p50 (independent wall measurement of the same window)
    assert r["coverage_error"] <= 0.10, r
    pull = r["budget"]["pull"]
    assert pull["coverage"] == "full"
    total = sum(p["p50_ms"] for p in pull["phases"])
    assert total == pytest.approx(pull["round_ms"], rel=0.02)
    by = {p["phase"]: p for p in pull["phases"]}
    for phase in ("client_serialize", "server_queue_wait",
                  "server_parse", "scatter_apply",
                  "response_serialize", "client_parse"):
        assert by[phase]["count"] > 0, phase
    # WAL was on: the push budget attributes append cost
    push = r["budget"]["push"]
    push_by = {p["phase"]: p for p in push["phases"]}
    assert push_by["wal_append"]["count"] > 0
    assert push_by["scatter_apply"]["count"] > 0
    assert r["top_phase"] is not None and r["top_pct"] > 0


def test_budget_artifact_lints(budget_cluster, fresh_registry, tmp_path):
    path = tmp_path / "budget.json"
    tm.get_profiler().write_budget_artifact(str(path))
    doc = json.loads(path.read_text())
    assert doc["budgets"]["pull"]["phases"]
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        from check_metric_lines import check_budget, main as lint_main

        assert check_budget(doc) == []
        assert lint_main(["--budget", str(path)]) == 0
        # a mutilated artifact fails: pcts that cannot sum to a round
        doc["budgets"]["pull"]["phases"] = [
            {"phase": "wire", "p50_ms": 1.0, "pct": 5.0}
        ]
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps(doc))
        assert lint_main(["--budget", str(bad)]) == 1
    finally:
        sys.path.remove(os.path.join(REPO, "tools"))


def test_run_report_carries_latency_budget(budget_cluster, fresh_registry):
    report = tm.build_run_report(fresh_registry)
    assert "latency_budget" in report
    pull = report["latency_budget"]["pull"]
    assert pull["top_phase"] is not None
    assert report["net"]["server_bytes_in"] > 0
    assert report["net"]["server_bytes_out"] > 0
    md = tm.render_markdown(report)
    assert "## Latency budget" in md
    assert "top cost center" in md
    assert "wire bytes (server in / out)" in md


def test_psctl_against_live_two_shard_cluster(fresh_registry):
    """The psctl smoke: top/stats/conns/budget answered by a LIVE
    2-shard cluster while training traffic flows."""
    from flink_parameter_server_tpu.cluster.driver import (
        ClusterConfig,
        ClusterDriver,
    )
    from flink_parameter_server_tpu.models.matrix_factorization import (
        OnlineMatrixFactorization,
        SGDUpdater,
    )
    from flink_parameter_server_tpu.utils.initializers import normal_factor

    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import psctl

        rng = np.random.default_rng(0)
        batches = [
            {
                "user": rng.integers(0, 64, 96).astype(np.int32),
                "item": rng.integers(0, 256, 96).astype(np.int32),
                "rating": rng.normal(0, 1, 96).astype(np.float32),
            }
            for _ in range(200)
        ]
        logic = OnlineMatrixFactorization(64, 8, updater=SGDUpdater(0.01))
        driver = ClusterDriver(
            logic, capacity=256, value_shape=(8,),
            init_fn=normal_factor(1, (8,)),
            config=ClusterConfig(num_shards=2, num_workers=1),
        )
        with driver, tm.TelemetryServer(fresh_registry) as tsrv:
            done = threading.Event()

            def train():
                try:
                    driver.run(batches)
                finally:
                    done.set()

            t = threading.Thread(target=train, daemon=True)
            t.start()
            shard_addrs = ",".join(
                f"{s.host}:{s.port}" for s in driver.servers
            )
            metrics_addr = f"{tsrv.host}:{tsrv.port}"

            # wait for the first rounds' phases to land (jit compile
            # precedes the first pull), then introspect MID-training
            deadline = time.time() + 60
            while time.time() < deadline:
                doc = json.loads(
                    psctl.scrape(tsrv.host, tsrv.port, "budget")
                )
                if "pull" in doc.get("budgets", {}):
                    break
                time.sleep(0.05)
            assert "pull" in doc["budgets"]

            # psctl top: two frames mid-training, rates derived
            buf = io.StringIO()
            with redirect_stdout(buf):
                rc = psctl.main([
                    "top", "--metrics", metrics_addr,
                    "--interval", "0.2", "--iterations", "2", "--raw",
                ])
            assert rc == 0
            out = buf.getvalue()
            assert "psctl top" in out and "updates/sec" in out
            assert "wire in/sec" in out

            # psctl budget, also mid-training: phases accumulate live
            buf = io.StringIO()
            with redirect_stdout(buf):
                rc = psctl.main(["budget", "--metrics", metrics_addr])
            assert rc == 0
            assert "top cost center" in buf.getvalue()

            t.join(timeout=120)
            assert done.is_set()

            # psctl stats: one row per LIVE shard with depth figures
            buf = io.StringIO()
            with redirect_stdout(buf):
                rc = psctl.main(["stats", "--shards", shard_addrs])
            assert rc == 0
            out = buf.getvalue()
            assert "wal" in out and "dedupe" in out
            assert out.count("yes") == 2  # both shards alive

            # psctl conns: the client's pooled connections are visible
            buf = io.StringIO()
            with redirect_stdout(buf):
                rc = psctl.main(["conns", "--shards", shard_addrs])
            assert rc == 0
            out = buf.getvalue()
            assert "connection(s)" in out and "pull" in out

            # psctl budget: phase table with a named top cost center
            buf = io.StringIO()
            with redirect_stdout(buf):
                rc = psctl.main([
                    "budget", "--metrics", metrics_addr, "--verb", "pull",
                ])
            assert rc == 0
            out = buf.getvalue()
            assert "top cost center" in out
            for phase in ("wire", "scatter_apply", "client_parse"):
                assert phase in out
            # and the raw JSON form round-trips
            buf = io.StringIO()
            with redirect_stdout(buf):
                rc = psctl.main(
                    ["budget", "--metrics", metrics_addr, "--json"]
                )
            assert rc == 0
            doc = json.loads(buf.getvalue())
            assert "pull" in doc["budgets"]
    finally:
        sys.path.remove(os.path.join(REPO, "tools"))


def test_shard_conns_verb_and_stats_depths(fresh_registry):
    from flink_parameter_server_tpu.cluster.partition import (
        RangePartitioner,
    )
    from flink_parameter_server_tpu.cluster.shard import (
        ParamShard,
        ShardServer,
    )

    part = RangePartitioner(64, 1)
    shard = ParamShard(0, part, (4,))
    with ShardServer(shard) as srv:
        resps = request_lines(
            srv.host, srv.port,
            ["push 1,2 b64:" + _b64_rows(2, 4), "stats", "conns"],
        )
        assert resps[0].startswith("ok applied=2")
        stats = json.loads(resps[1][3:])
        assert stats["wal_records"] == 0  # no WAL configured
        assert "dedupe_pairs" in stats
        conns = json.loads(resps[2][3:])
        assert len(conns) == 1
        assert conns[0]["frames_in"] == 3
        assert conns[0]["last_verb"] == "conns"


def _b64_rows(n, width):
    import base64

    return base64.b64encode(
        np.zeros((n, width), "<f4").tobytes()
    ).decode("ascii")
