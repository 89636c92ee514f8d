"""Unified telemetry plane (telemetry/, docs/observability.md):
registry thread-safety and bucket math, span-trace export and nesting,
the live TCP ``/metrics`` endpoint mid-training, the JSON-lines
contract shared by every emitter, and the overhead guard.
"""
import json
import socket
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from flink_parameter_server_tpu import telemetry as tm
from flink_parameter_server_tpu.core.store import ShardedParamStore
from flink_parameter_server_tpu.data.movielens import synthetic_ratings
from flink_parameter_server_tpu.data.streams import microbatches
from flink_parameter_server_tpu.models.matrix_factorization import (
    OnlineMatrixFactorization,
    SGDUpdater,
)
from flink_parameter_server_tpu.training.driver import (
    DriverConfig,
    StreamingDriver,
)
from flink_parameter_server_tpu.utils.initializers import (
    ranged_random_factor,
)

pytestmark = pytest.mark.telemetry


@pytest.fixture()
def registry():
    """Isolated registry installed as the process default for the test
    (driver/serving wiring resolves the default lazily)."""
    reg = tm.MetricsRegistry(run_id="test-run")
    old = tm.get_registry()
    tm.set_registry(reg)
    yield reg
    tm.set_registry(old)


@pytest.fixture()
def tracer():
    tr = tm.SpanTracer()
    old = tm.get_tracer()
    tm.set_tracer(tr)
    yield tr
    tm.set_tracer(old)


def _mf_driver(num_users, num_items, dim, seed=0, **cfg):
    logic = OnlineMatrixFactorization(
        num_users, dim, updater=SGDUpdater(0.05)
    )
    store = ShardedParamStore.create(
        num_items, (dim,),
        init_fn=ranged_random_factor(seed + 1, (dim,)),
    )
    return StreamingDriver(
        logic, store, config=DriverConfig(dump_model=False, **cfg)
    )


# ---------------------------------------------------------------------------
# registry: typing, identity, thread-safety
# ---------------------------------------------------------------------------


def test_instrument_identity_and_type_conflicts(registry):
    c1 = registry.counter("x_total", component="train")
    c2 = registry.counter("x_total", component="train")
    assert c1 is c2
    # same name, different labels = a different instrument
    c3 = registry.counter("x_total", component="serving")
    assert c3 is not c1
    with pytest.raises(ValueError):
        registry.gauge("x_total", component="train")
    registry.histogram("h", component="train", buckets=[1.0, 2.0])
    with pytest.raises(ValueError):  # boundary mismatch on re-request
        registry.histogram("h", component="train", buckets=[1.0, 3.0])


def test_counter_rejects_negative(registry):
    c = registry.counter("n_total")
    with pytest.raises(ValueError):
        c.inc(-1)


def test_registry_thread_safety_under_concurrent_writers(registry):
    """N threads hammering the same counter + histogram lose nothing:
    totals are exact, histogram count equals observations made."""
    c = registry.counter("hits_total", component="train")
    h = registry.histogram(
        "lat_seconds", component="train", buckets=[0.25, 0.5, 0.75]
    )
    g = registry.gauge("level", component="train")
    n_threads, per_thread = 8, 2_000
    rngs = [np.random.default_rng(i) for i in range(n_threads)]

    def writer(i):
        for v in rngs[i].uniform(0, 1, per_thread):
            c.inc()
            h.observe(float(v))
            g.set(float(v))

    threads = [
        threading.Thread(target=writer, args=(i,))
        for i in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = n_threads * per_thread
    assert c.value == total
    assert h.count == total
    assert sum(h.bucket_counts()) == total
    assert g.value is not None and 0 <= g.value <= 1


def test_histogram_bucket_math_vs_numpy_oracle(registry):
    bounds = [0.001, 0.01, 0.1, 1.0, 10.0]
    h = registry.histogram("oracle_seconds", buckets=bounds)
    rng = np.random.default_rng(42)
    vals = rng.lognormal(mean=-3.0, sigma=2.0, size=5_000)
    for v in vals:
        h.observe(float(v))
    # numpy oracle: same bin edges ((-inf, b0], (b0, b1], ..., (bn, inf))
    edges = np.concatenate([[-np.inf], np.array(bounds), [np.inf]])
    oracle, _ = np.histogram(vals, bins=edges)
    assert h.bucket_counts() == oracle.tolist()
    assert h.count == len(vals)
    np.testing.assert_allclose(h.sum, vals.sum(), rtol=1e-9)
    # percentiles: the interpolated estimate must land in the same
    # bucket as the exact value (that is the precision the fixed
    # boundaries promise — no more, no less)
    for q in (50, 90, 99):
        exact = float(np.percentile(vals, q))
        est = h.percentile(q)
        assert np.searchsorted(bounds, est) == np.searchsorted(
            bounds, min(exact, bounds[-1])
        ), (q, exact, est)


def test_gauge_probe_failure_reads_none(registry):
    g = registry.gauge("flaky", fn=lambda: 1 / 0)
    assert g.value is None  # dead probe: visible as null, not a crash
    snap = registry.snapshot()
    assert snap["flaky"][0]["value"] is None


# ---------------------------------------------------------------------------
# JSON-lines contract: every emitter round-trips with shared ts/run_id
# ---------------------------------------------------------------------------


def _assert_metric_line(line):
    assert "\n" not in line
    d = json.loads(line)
    assert isinstance(d["ts"], float) and d["ts"] > 0
    assert isinstance(d["run_id"], str) and d["run_id"]
    return d


def test_all_emitters_round_trip_json(registry):
    import io

    from flink_parameter_server_tpu.resilience.health import (
        HealthMonitor,
        StallWatchdog,
    )
    from flink_parameter_server_tpu.serving.metrics import ServingMetrics
    from flink_parameter_server_tpu.training.metrics import StepMetrics

    # StepMetrics
    m = StepMetrics(events_per_step=10, registry=registry)
    m.step_start()
    m.step_end()
    d = _assert_metric_line(m.emit())
    assert d["run_id"] == "test-run" and d["steps"] == 1

    # ServingMetrics
    sm = ServingMetrics(registry=registry)
    sm.record_batch(3, 4, [0.001, 0.002, 0.004])
    d = _assert_metric_line(sm.emit())
    assert d["serving_requests"] == 3

    # StallWatchdog event line
    clock = [0.0]
    mon = HealthMonitor(clock=lambda: clock[0], registry=registry)
    sink = io.StringIO()
    wd = StallWatchdog(mon, 1.0, sink=sink, registry=registry)
    mon.beat("train")
    clock[0] = 5.0
    events = wd.check_once()
    assert [e["stall"] for e in events] == ["train"]
    d = _assert_metric_line(sink.getvalue().splitlines()[0])
    assert d["stall"] == "train"
    assert (
        registry.counter(
            "stall_episodes_total", component="train"
        ).value == 1
    )

    # registry emit itself
    d = _assert_metric_line(registry.emit())
    assert d["kind"] == "registry"

    # and the lint agrees with all of the above
    import tools.check_metric_lines as lint

    lines = [m.emit(), sm.emit(), sink.getvalue().splitlines()[0],
             registry.emit()]
    assert lint.check_lines(lines) == []


def test_json_line_sanitizes_non_finite(registry):
    line = tm.json_line({"a": float("nan"), "b": float("inf"),
                         "nested": {"c": float("-inf")}})
    d = json.loads(line)  # strict parser: would reject NaN/Infinity
    assert d["a"] is None and d["b"] is None and d["nested"]["c"] is None


def test_heartbeat_age_gauge_visible_before_watchdog(registry):
    from flink_parameter_server_tpu.resilience.health import HealthMonitor

    clock = [100.0]
    mon = HealthMonitor(clock=lambda: clock[0], registry=registry)
    mon.beat("ingest")
    clock[0] = 103.5
    txt = tm.prometheus_text(registry)
    assert 'fps_last_heartbeat_age_s{component="ingest"} 3.5' in txt


# ---------------------------------------------------------------------------
# spans: nesting, ring buffer, Chrome trace export
# ---------------------------------------------------------------------------


def test_span_nesting_and_chrome_export(tmp_path):
    tr = tm.SpanTracer()
    with tr.span("outer", component="train"):
        time.sleep(0.002)
        with tr.span("inner", component="ingest"):
            time.sleep(0.002)
    path = str(tmp_path / "trace.json")
    doc = json.loads(tr.export_chrome_trace(path))
    with open(path) as f:
        assert json.load(f) == doc  # file and return value agree
    by_name = {e["name"]: e for e in doc}
    outer, inner = by_name["outer"], by_name["inner"]
    assert outer["ph"] == inner["ph"] == "X"
    assert outer["args"]["depth"] == 0 and inner["args"]["depth"] == 1
    # proper nesting: inner's [ts, ts+dur] within outer's
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert inner["cat"] == "ingest"


def test_span_ring_buffer_bounds_memory():
    tr = tm.SpanTracer(capacity=16)
    for i in range(100):
        with tr.span(f"s{i}"):
            pass
    assert len(tr) == 16
    names = [s["name"] for s in tr.spans()]
    assert names == [f"s{i}" for i in range(84, 100)]  # newest survive


def test_disabled_tracer_records_nothing():
    tr = tm.SpanTracer(enabled=False)
    with tr.span("x"):
        pass
    tr.record("y", 0.0, 1.0)
    assert len(tr) == 0


# ---------------------------------------------------------------------------
# exporter: prometheus text + TCP endpoint
# ---------------------------------------------------------------------------


def test_prometheus_text_shapes(registry):
    registry.counter("steps_total", component="train").inc(7)
    h = registry.histogram("lat_seconds", component="train",
                           buckets=[0.1, 1.0])
    h.observe(0.05)
    h.observe(5.0)
    txt = tm.prometheus_text(registry)
    assert '# TYPE fps_steps_total counter' in txt
    assert 'fps_steps_total{component="train"} 7' in txt
    assert 'fps_lat_seconds_bucket{component="train",le="0.1"} 1' in txt
    assert 'fps_lat_seconds_bucket{component="train",le="+Inf"} 2' in txt
    assert 'fps_lat_seconds_count{component="train"} 2' in txt


def test_tcp_endpoint_http_and_line_protocol(registry):
    registry.counter("steps_total", component="train").inc(3)
    with tm.TelemetryServer(registry) as srv:
        # bare line protocol
        body = tm.scrape(srv.host, srv.port, "metrics")
        assert "fps_steps_total" in body
        # HTTP GET (what curl / a Prometheus scrape job sends)
        with socket.create_connection((srv.host, srv.port)) as s:
            s.sendall(b"GET /metrics HTTP/1.1\r\nHost: x\r\n\r\n")
            data = b""
            while True:
                chunk = s.recv(1 << 16)
                if not chunk:
                    break
                data += chunk
        head, _, payload = data.partition(b"\r\n\r\n")
        assert b"200 OK" in head and b"text/plain" in head
        assert b"fps_steps_total" in payload
        # /healthz + 404
        health = json.loads(tm.scrape(srv.host, srv.port, "healthz"))
        assert health["status"] == "ok"
        assert "unknown path" in tm.scrape(srv.host, srv.port, "nope")


# ---------------------------------------------------------------------------
# e2e: live /metrics mid-training (train-while-serve), span trace out
# ---------------------------------------------------------------------------


def test_metrics_endpoint_live_mid_training(registry, tracer):
    """The acceptance-criteria run: train-while-serve with the TCP
    endpoint up; a scrape taken MID-RUN (from a group hook, so it
    provably overlaps training) sees live train + serving families,
    and the span trace exports pull/compute/push + ingest + publish."""
    num_users, num_items, dim = 100, 150, 8
    driver = _mf_driver(num_users, num_items, dim)
    service = driver.serve_with(
        publish_every=2, max_batch=16, max_delay_ms=1.0
    )
    client = service.client()
    data = synthetic_ratings(num_users, num_items, 50_000, rank=4, seed=0)
    batches = list(microbatches(data, 512, epochs=1, shuffle_seed=0))
    assert len(batches) >= 90  # "a span trace of a ~100-step run"

    mid_scrapes = []
    with tm.TelemetryServer(registry) as srv:
        c_req = registry.counter(
            "serving_requests_total", component="serving"
        )

        def scrape_hook(step, n_steps, table, state, outs):
            if step == 20:
                # one mid-training query so the serving counters move;
                # record_batch runs on the dispatch thread AFTER the
                # future resolves — wait for the counter, then scrape
                client.top_k(3, k=5)
                deadline = time.monotonic() + 10
                while c_req.value < 1 and time.monotonic() < deadline:
                    time.sleep(0.002)
                mid_scrapes.append(
                    tm.scrape(srv.host, srv.port, "metrics")
                )

        driver.add_group_hook(scrape_hook)
        driver.run(batches)
    service.stop()

    assert len(mid_scrapes) == 1
    txt = mid_scrapes[0]
    # live counter value: exactly the 20 dispatches completed so far
    assert 'fps_train_steps_total{component="train"} 20' in txt
    assert "fps_dispatch_interval_seconds_bucket" in txt
    assert 'fps_serving_requests_total{component="serving"} 1' in txt
    assert "fps_snapshot_staleness_steps" in txt
    assert "fps_ingest_batches_total" in txt

    # span trace: valid Chrome trace JSON with the required phases
    doc = json.loads(tracer.export_chrome_trace())
    names = {e["name"] for e in doc}
    assert {"pull_compute_push", "ingest", "publish"} <= names
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in doc)
    n_dispatch = sum(1 for e in doc if e["name"] == "pull_compute_push")
    assert n_dispatch == len(batches)

    # end-of-run report rolls the same registry up
    report = tm.build_run_report(registry)
    assert report["train"]["steps"] == len(batches)
    assert report["serving"]["requests"] >= 1
    assert report["ingest"]["batches"] == len(batches)


def test_driver_checkpoint_span_and_counter(registry, tracer, tmp_path):
    pytest.importorskip("orbax.checkpoint")
    driver = _mf_driver(
        60, 80, 4,
        checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every=10,
    )
    data = synthetic_ratings(60, 80, 10_000, rank=4, seed=1)
    driver.run(microbatches(data, 512, epochs=1, shuffle_seed=0))
    assert registry.counter(
        "checkpoints_total", component="train"
    ).value >= 1
    assert "checkpoint" in {s["name"] for s in tracer.spans()}


def test_wal_append_span(registry, tracer, tmp_path):
    driver = _mf_driver(60, 80, 4, wal_dir=str(tmp_path / "wal"))
    data = synthetic_ratings(60, 80, 5_000, rank=4, seed=1)
    driver.run(microbatches(data, 512, epochs=1, shuffle_seed=0))
    names = {s["name"] for s in tracer.spans()}
    assert "wal_append" in names
    assert registry.counter(
        "wal_appends_total", component="ingest"
    ).value >= 1


def test_telemetry_off_touches_nothing(registry, tracer):
    driver = _mf_driver(60, 80, 4, telemetry=False)
    data = synthetic_ratings(60, 80, 5_000, rank=4, seed=1)
    driver.run(microbatches(data, 512, epochs=1, shuffle_seed=0))
    assert registry.counter(
        "train_steps_total", component="train"
    ).value == 0
    assert len(tracer) == 0


# ---------------------------------------------------------------------------
# report + overhead guard
# ---------------------------------------------------------------------------


def test_run_report_writes_md_and_json(registry, tmp_path):
    registry.counter("train_steps_total", component="train").inc(10)
    report = tm.build_run_report(
        registry, wall_s=2.0, extra={"telemetry_overhead_pct": 0.5}
    )
    assert report["train"]["steps_per_sec"] == 5.0
    paths = tm.write_run_report(report, results_dir=str(tmp_path))
    with open(paths["json"]) as f:
        assert json.load(f)["train"]["steps"] == 10
    with open(paths["md"]) as f:
        md = f.read()
    assert "| steps/sec | 5.0 |" in md
    assert "telemetry_overhead_pct" in md


# ---------------------------------------------------------------------------
# satellite: device_memory_stats uniform keys + gauges
# ---------------------------------------------------------------------------


def test_device_memory_stats_uniform_keys(registry):
    from flink_parameter_server_tpu.training import tracing

    stats = tracing.device_memory_stats()
    for entry in stats.values():
        assert set(entry) == {"bytes_in_use", "peak_bytes"}
        assert all(isinstance(v, int) for v in entry.values())
    wired = tracing.register_device_memory_gauges(registry)
    assert wired == len(stats)
    if wired:  # CPU backends may expose no memory_stats at all
        txt = tm.prometheus_text(registry)
        assert "fps_device_bytes_in_use" in txt


def test_device_memory_stats_warns_once_on_unknown_error(monkeypatch):
    from flink_parameter_server_tpu.training import tracing

    class Weird:
        def memory_stats(self):
            raise KeyError("boom")

        def __str__(self):
            return "weird:0"

    monkeypatch.setattr(
        tracing.jax, "devices", lambda: [Weird(), Weird()]
    )
    tracing._mem_stats_warned.clear()
    assert tracing.device_memory_stats() == {}
    assert tracing._mem_stats_warned == {"weird:0"}
    # second call: no growth, no raise (warned once per device)
    assert tracing.device_memory_stats() == {}
    assert tracing._mem_stats_warned == {"weird:0"}


# ---------------------------------------------------------------------------
# satellite: the metric-line lint over a real example run
# ---------------------------------------------------------------------------


def test_check_metric_lines_lint_over_live_run(registry, tmp_path):
    """Capture a real driver run's metrics_sink stream and hand it to
    tools/check_metric_lines.py — the CI-shaped invocation."""
    import io
    import subprocess
    import sys

    sink = io.StringIO()
    driver = _mf_driver(60, 80, 4, metrics_every=5)
    driver.metrics_sink = sink
    service = driver.serve_with(publish_every=4, max_batch=8)
    data = synthetic_ratings(60, 80, 20_000, rank=4, seed=3)
    driver.run(microbatches(data, 256, epochs=1, shuffle_seed=0))
    service.stop()
    assert sink.getvalue().strip(), "no metric lines emitted"

    log = tmp_path / "metrics.log"
    log.write_text(sink.getvalue())
    import os

    import tools.check_metric_lines as lint

    assert lint.check_lines(sink.getvalue().splitlines()) == []
    repo = os.path.dirname(os.path.dirname(os.path.abspath(
        lint.__file__
    )))
    proc = subprocess.run(
        [sys.executable, "tools/check_metric_lines.py", str(log)],
        capture_output=True, text=True, cwd=repo,
    )
    assert proc.returncode == 0, proc.stderr
    assert "0 malformed" in proc.stdout

    # and the lint actually catches rot
    bad = tmp_path / "bad.log"
    bad.write_text('{"ts": 1.0, "run_id": "x"}\nnot json at all\n')
    proc = subprocess.run(
        [sys.executable, "tools/check_metric_lines.py", str(bad)],
        capture_output=True, text=True, cwd=repo,
    )
    assert proc.returncode == 1
    assert "not valid JSON" in proc.stderr
