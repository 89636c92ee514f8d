"""Timeline plane tests (telemetry/timeline.py + detectors.py).

What is pinned here, and why it is the right oracle:

  * **detector oracles vs numpy** — the EWMA drift and rolling-MAD
    scores are recomputed from closed-form numpy expressions (weighted
    sums for the EW mean/variance, ``np.median`` for the robust z),
    NOT by re-running the detector's own recursion, so a math bug in
    the incremental update cannot hide behind itself.  Firing index
    and firing score must both match the reference.
  * **zero false positives on stationary noise** — the documented
    scale-floor contract: seeded gaussian jitter through both
    detectors at default thresholds produces NO episodes.
  * **edge-triggered episodes** — a sustained level shift is ONE
    anomaly record (fired at the leading edge), and the detector
    re-arms after the shift becomes the new normal.
  * **bucket-delta percentiles** — the recorder's windowed p99 is
    checked against ``np.percentile`` of the exact observations in the
    same delta window (agreement to within the enclosing bucket), and
    shown to be WINDOWED: a quiet second window is not dragged by a
    loud first one the way the cumulative histogram percentile is.
  * **skew attribution** — entities are each other's control group:
    a 10× entity is named with no pre-fault baseline; warmup_evals
    suppresses cold-start flags without suppressing ratios.
  * **elastic pressure** — a real detector firing, recorded through a
    real registry poll, drives ``ElasticController.step()`` to a
    scale_out whose decision record names the anomaly; the cursor
    advances so the same firing never pressures twice.
  * **psctl watch / timeline** — smoke over a live 2-shard cluster
    and a real TelemetryServer scrape, both render paths.
"""
import json
import math
import time

import numpy as np
import pytest

from flink_parameter_server_tpu.telemetry.detectors import (
    EWMADriftDetector,
    RollingMADDetector,
)
from flink_parameter_server_tpu.telemetry.registry import MetricsRegistry
from flink_parameter_server_tpu.telemetry.timeline import (
    SkewTracker,
    TimelineRecorder,
    get_timeline,
    percentile_from_counts,
    set_timeline,
)

pytestmark = pytest.mark.timeline


def _feed(det, xs, *, name="m", field="value", labels=None):
    """Run a series through a detector point-by-point; ts = index so a
    record's ``ts`` IS the firing index."""
    records = []
    for i, x in enumerate(xs):
        rec = det.observe(name, labels or {}, field, float(x), float(i))
        if rec is not None:
            records.append(rec)
    return records


# ---------------------------------------------------------------------------
# percentile_from_counts
# ---------------------------------------------------------------------------


class TestPercentileFromCounts:
    def test_exact_interpolation(self):
        bounds = [1.0, 2.0, 4.0]
        counts = [0, 10, 0, 0]  # all mass in (1, 2]
        # rank 5 of 10 → halfway through the (1, 2] bin
        assert percentile_from_counts(bounds, counts, 50.0) == pytest.approx(2.0 - 0.5)

    def test_overflow_clamps_to_last_bound(self):
        bounds = [1.0, 2.0]
        counts = [0, 0, 7]  # everything overflowed
        assert percentile_from_counts(bounds, counts, 99.0) == 2.0

    def test_empty_window_is_zero(self):
        assert percentile_from_counts([1.0, 2.0], [0, 0, 0], 99.0) == 0.0

    def test_matches_registry_histogram_on_full_window(self):
        """On a first window (delta == cumulative) the hoisted function
        and Histogram.percentile are the same math."""
        reg = MetricsRegistry()
        h = reg.histogram("x_seconds", component="test",
                          buckets=(0.01, 0.05, 0.1, 0.5, 1.0))
        rng = np.random.default_rng(7)
        for v in rng.uniform(0.0, 1.2, 200):
            h.observe(float(v))
        counts = h.bucket_counts()
        for q in (50.0, 90.0, 99.0):
            assert percentile_from_counts(h.bounds, counts, q) == pytest.approx(
                h.percentile(q)
            )


# ---------------------------------------------------------------------------
# detector oracles vs numpy
# ---------------------------------------------------------------------------


def _ewma_reference_scores(xs, *, alpha, warmup,
                           rel_floor=0.05, abs_floor=1e-9):
    """Closed-form EW mean/variance (weighted sums, not the detector's
    recursion): m_j = (1-a)^j x_0 + a Σ_{i=1..j} (1-a)^{j-i} x_i and
    v_j = Σ_{i=1..j} a (1-a)^{j-i+1} d_i² with d_i = x_i - m_{i-1}.
    Score at point j (j >= warmup) uses the state BEFORE absorbing it."""
    xs = np.asarray(xs, dtype=float)
    n = len(xs)
    means = np.empty(n)
    means[0] = xs[0]
    for j in range(1, n):
        w = alpha * (1.0 - alpha) ** (j - np.arange(1, j + 1))
        means[j] = (1.0 - alpha) ** j * xs[0] + float(w @ xs[1:j + 1])
    d = xs[1:] - means[:-1]
    variances = np.zeros(n)
    for j in range(1, n):
        w = alpha * (1.0 - alpha) ** (j - np.arange(1, j + 1) + 1)
        variances[j] = float(w @ (d[:j] ** 2))
    scores = np.full(n, np.nan)
    for j in range(warmup, n):
        m, v = means[j - 1], variances[j - 1]
        sigma = max(math.sqrt(max(0.0, v)), rel_floor * abs(m), abs_floor)
        scores[j] = abs(xs[j] - m) / sigma
    return scores


def _mad_reference_scores(xs, *, window, warmup,
                          rel_floor=0.05, abs_floor=1e-9):
    """Robust z of each point vs the np.median/MAD of the (up to
    ``window``) points BEFORE it — the detector appends after scoring."""
    xs = np.asarray(xs, dtype=float)
    scores = np.full(len(xs), np.nan)
    for j in range(len(xs)):
        win = xs[max(0, j - window):j]
        if len(win) >= warmup:
            med = float(np.median(win))
            mad = float(np.median(np.abs(win - med)))
            scale = max(1.4826 * mad, rel_floor * abs(med), abs_floor)
            scores[j] = abs(xs[j] - med) / scale
    return scores


class TestDetectorOracles:
    def test_ewma_firing_index_and_score_match_numpy(self):
        rng = np.random.default_rng(11)
        xs = list(rng.normal(1.0, 0.02, 30)) + list(rng.normal(1.6, 0.02, 10))
        alpha, k, warmup = 0.2, 4.0, 10
        ref = _ewma_reference_scores(xs, alpha=alpha, warmup=warmup)
        expected_idx = int(np.argmax(np.nan_to_num(ref) > k))
        assert ref[expected_idx] > k  # the shift IS detectable
        det = EWMADriftDetector("m", field="value", alpha=alpha,
                                k=k, warmup=warmup)
        records = _feed(det, xs)
        assert records, "level shift never fired"
        first = records[0]
        assert first["ts"] == float(expected_idx)
        assert first["kind"] == "ewma_drift"
        assert first["score"] == pytest.approx(ref[expected_idx], rel=1e-3)

    def test_mad_spike_index_and_score_match_numpy(self):
        rng = np.random.default_rng(13)
        xs = list(rng.normal(1.0, 0.02, 80))
        xs[40] = 2.0  # one wild point
        window, k, warmup = 24, 6.0, 12
        ref = _mad_reference_scores(xs, window=window, warmup=warmup)
        det = RollingMADDetector("m", field="value", window=window,
                                 k=k, warmup=warmup)
        records = _feed(det, xs)
        assert len(records) == 1
        assert records[0]["ts"] == 40.0
        assert records[0]["kind"] == "mad_outlier"
        assert records[0]["score"] == pytest.approx(ref[40], rel=1e-3)

    def test_zero_false_positives_on_stationary_noise(self):
        """The scale-floor contract: float jitter on a flat series
        cannot manufacture episodes at default thresholds."""
        rng = np.random.default_rng(17)
        xs = rng.normal(1.0, 0.02, 600)
        ewma = EWMADriftDetector("m", field="value")
        mad = RollingMADDetector("m", field="value")
        assert _feed(ewma, xs) == []
        assert _feed(mad, xs) == []

    def test_sustained_shift_is_one_episode_then_rearms(self):
        """Edge-trigger semantics: the plateau fires at its leading
        edge only; after the detector adapts (re-arm), a SECOND shift
        fires a second episode."""
        xs = ([1.0] * 10) + ([10.0] * 37) + ([30.0] * 5)
        det = EWMADriftDetector("m", field="value", alpha=0.2,
                                k=4.0, warmup=5)
        records = _feed(det, xs)
        assert [r["ts"] for r in records] == [10.0, 47.0]
        # the ledger mirrors the records (episode count, not samples)
        assert len(det.episodes) == 2

    def test_label_sets_keep_independent_state(self):
        """One detector instance watches every labelled series of its
        metric; a shift on shard 1 must not fire (or warm up) shard 0."""
        det = EWMADriftDetector("m", field="value", k=4.0, warmup=5)
        for i in range(8):
            det.observe("m", {"shard": "0"}, "value", 1.0, float(i))
            det.observe("m", {"shard": "1"}, "value", 1.0, float(i))
        rec = det.observe("m", {"shard": "1"}, "value", 9.0, 8.0)
        assert rec is not None and rec["labels"] == {"shard": "1"}
        assert det.observe("m", {"shard": "0"}, "value", 1.0, 8.0) is None

    def test_metric_and_field_scoping(self):
        det = RollingMADDetector("m", field="p99", window=8, k=6.0,
                                 warmup=4)
        for i in range(8):
            assert det.observe("other", {}, "p99", 1.0, float(i)) is None
            assert det.observe("m", {}, "rate", 1.0, float(i)) is None
        # nothing scoped-in was ever absorbed
        assert det.observe("m", {}, "p99", 100.0, 9.0) is None  # warming

    def test_ctor_validation(self):
        with pytest.raises(ValueError, match="warmup"):
            EWMADriftDetector("m", warmup=1)
        with pytest.raises(ValueError, match="alpha"):
            EWMADriftDetector("m", alpha=1.5)
        with pytest.raises(ValueError, match="window"):
            RollingMADDetector("m", window=2)
        with pytest.raises(ValueError, match="could never be met"):
            RollingMADDetector("m", window=8, warmup=9)
        with pytest.raises(ValueError, match="rearm_fraction"):
            EWMADriftDetector("m", rearm_fraction=0.0)


# ---------------------------------------------------------------------------
# the recorder
# ---------------------------------------------------------------------------


class TestTimelineRecorder:
    def test_counter_becomes_rate(self):
        reg = MetricsRegistry()
        c = reg.counter("events_total", component="test")
        rec = TimelineRecorder(reg, interval_s=0.01)
        a = time.monotonic()
        rec.sample()  # primes the counter window
        b = time.monotonic()
        c.inc(100)
        time.sleep(0.03)
        inner = time.monotonic()
        rec.sample()
        outer = time.monotonic()
        series = rec.series("events_total")
        assert len(series) == 1 and series[0]["field"] == "rate"
        (_, rate), = series[0]["points"]
        # the sample's dt is bracketed by our own monotonic reads
        assert 100.0 / (outer - a) <= rate <= 100.0 / (inner - b)

    def test_gauge_value_and_none_gap(self):
        reg = MetricsRegistry()
        g = reg.gauge("level", component="test")
        probe = reg.gauge("probe", component="test")
        probe.set_fn(lambda: None)  # unreadable probe
        rec = TimelineRecorder(reg, interval_s=0.01)
        g.set(3.5)
        rec.sample()
        g.set(4.5)
        rec.sample()
        series = {s["metric"]: s for s in rec.series()}
        assert [v for _, v in series["level"]["points"]] == [3.5, 4.5]
        assert "probe" not in series  # a gap, not a zero

    def test_histogram_windowed_p99_vs_exact_reservoir(self):
        """Bucket-delta p99 agrees with np.percentile of the exact
        delta-window observations to within the enclosing bucket, and
        is genuinely WINDOWED (a quiet window after a loud one)."""
        reg = MetricsRegistry()
        bounds = (0.01, 0.02, 0.05, 0.1, 0.2, 0.5, 1.0)
        h = reg.histogram("lat_seconds", component="test", buckets=bounds)
        rec = TimelineRecorder(reg, interval_s=0.01)
        rng = np.random.default_rng(23)

        def bucket_of(v):
            lo = 0.0
            for b in bounds:
                if v <= b:
                    return lo, b
                lo = b
            return lo, bounds[-1]

        loud = rng.uniform(0.2, 0.9, 400)
        for v in loud:
            h.observe(float(v))
        rec.sample()
        quiet = rng.uniform(0.001, 0.03, 300)
        for v in quiet:
            h.observe(float(v))
        rec.sample()
        p99 = [s for s in rec.series("lat_seconds")
               if s["field"] == "p99"][0]["points"]
        assert len(p99) == 2
        for (_, got), window in zip(p99, (loud, quiet)):
            exact = float(np.percentile(window, 99))
            lo, hi = bucket_of(exact)
            assert lo <= got <= hi, (got, exact)
        # windowed, not cumulative: window 2's p99 is small while the
        # cumulative histogram is still dominated by the loud window
        assert p99[1][1] < 0.1 < h.percentile(99.0)

    def test_capacity_bounds_ring(self):
        reg = MetricsRegistry()
        g = reg.gauge("level", component="test")
        rec = TimelineRecorder(reg, interval_s=0.01, capacity=4)
        for i in range(10):
            g.set(float(i))
            rec.sample()
        pts = rec.series("level")[0]["points"]
        assert [v for _, v in pts] == [6.0, 7.0, 8.0, 9.0]

    def test_max_series_drops_are_counted(self):
        reg = MetricsRegistry()
        reg.gauge("a", component="test").set(1.0)
        reg.gauge("b", component="test").set(2.0)
        rec = TimelineRecorder(reg, interval_s=0.01, max_series=1)
        rec.sample()
        assert len(rec.series()) == 1
        assert rec.payload()["dropped_series"] >= 1

    def test_marks_and_payload_are_json(self):
        reg = MetricsRegistry()
        reg.gauge("level", component="test").set(1.0)
        rec = TimelineRecorder(reg, interval_s=0.01)
        rec.mark("fault_injected", shard=0, op="delay")
        rec.sample()
        payload = json.loads(json.dumps(rec.payload()))
        assert payload["kind"] == "timeline"
        assert payload["samples"] == 1
        assert payload["marks"][0]["label"] == "fault_injected"
        assert payload["marks"][0]["shard"] == 0
        names = {s["metric"] for s in payload["series"]}
        assert "level" in names

    def test_anomaly_bumps_counter_and_ledger(self):
        reg = MetricsRegistry()
        g = reg.gauge("probe_value", component="test")
        det = EWMADriftDetector("probe_value", field="value",
                                k=4.0, warmup=5)
        rec = TimelineRecorder(reg, interval_s=0.01, detectors=[det])
        for _ in range(8):
            g.set(1.0)
            rec.sample()
        assert rec.anomalies() == []
        g.set(10.0)
        rec.sample()
        anoms = rec.anomalies()
        assert len(anoms) == 1 and anoms[0]["metric"] == "probe_value"
        bumped = [
            i for i in reg.instruments()
            if i.name == "timeline_anomalies_total"
        ]
        assert len(bumped) == 1 and bumped[0].value == 1
        assert bumped[0].labels["kind"] == "ewma_drift"

    def test_background_loop_samples_and_stops(self):
        reg = MetricsRegistry()
        reg.gauge("level", component="test").set(1.0)
        rec = TimelineRecorder(reg, interval_s=0.01)
        with rec:
            deadline = time.time() + 5.0
            while rec.payload()["samples"] < 3 and time.time() < deadline:
                time.sleep(0.01)
        assert rec.payload()["samples"] >= 3
        settled = rec.payload()["samples"]
        time.sleep(0.05)
        assert rec.payload()["samples"] == settled  # loop really stopped

    def test_ctor_validation(self):
        with pytest.raises(ValueError, match="interval_s"):
            TimelineRecorder(MetricsRegistry(), interval_s=0.0)
        with pytest.raises(ValueError, match="capacity"):
            TimelineRecorder(MetricsRegistry(), capacity=1)


# ---------------------------------------------------------------------------
# skew attribution
# ---------------------------------------------------------------------------


class TestSkewTracker:
    def _feed_entities(self, tracker, per_entity, n=8):
        for i in range(n):
            for entity, value in per_entity.items():
                tracker.observe(
                    tracker.metric, {"shard": entity},
                    "p99", value, float(i),
                )

    def test_straggler_named_with_no_baseline(self):
        reg = MetricsRegistry()
        t = SkewTracker("cluster_shard_rtt_seconds", entity_label="shard",
                        field="p99", window=8, min_points=3,
                        ratio_threshold=2.0, registry=reg)
        self._feed_entities(t, {"0": 0.01, "1": 0.011, "2": 0.1})
        verdict = t.evaluate(now=1.0)
        assert verdict is not None
        assert verdict["entity"] == "2" and verdict["flagged"]
        assert verdict["ratio"] == pytest.approx(0.1 / 0.011, rel=1e-3)
        # ratios published as gauges
        gauges = {
            i.labels["entity"]: i.value for i in reg.instruments()
            if i.name == "skew_ratio"
        }
        assert set(gauges) == {"0", "1", "2"}
        assert gauges["2"] == pytest.approx(0.1 / 0.011, rel=1e-3)

    def test_balanced_fleet_not_flagged(self):
        t = SkewTracker("m", entity_label="shard", window=8,
                        min_points=3, ratio_threshold=2.0)
        self._feed_entities(t, {"0": 0.01, "1": 0.0105, "2": 0.0098})
        verdict = t.evaluate(now=1.0)
        assert verdict is not None and not verdict["flagged"]

    def test_warmup_evals_suppresses_flag_not_ratio(self):
        t = SkewTracker("m", entity_label="shard", window=8,
                        min_points=3, ratio_threshold=2.0,
                        warmup_evals=2)
        # 3 entities: with only 2, the median-of-medians baseline
        # averages the straggler in and bounds the ratio below 2
        self._feed_entities(t, {"0": 0.01, "1": 0.011, "2": 0.1})
        v1 = t.evaluate(now=1.0)
        v2 = t.evaluate(now=2.0)
        v3 = t.evaluate(now=3.0)
        assert v1["ratio"] > 2.0 and not v1["flagged"]  # cold start
        assert not v2["flagged"]
        assert v3["flagged"]  # past warmup, same signal
        assert t.snapshot()["warmup_evals"] == 2

    def test_needs_two_entities(self):
        t = SkewTracker("m", entity_label="shard", min_points=1)
        t.observe("m", {"shard": "0"}, "p99", 0.01, 0.0)
        assert t.evaluate(now=1.0) is None

    def test_threshold_validation(self):
        with pytest.raises(ValueError, match="ratio_threshold"):
            SkewTracker("m", entity_label="shard", ratio_threshold=1.0)


# ---------------------------------------------------------------------------
# elastic pressure from anomaly firings
# ---------------------------------------------------------------------------


class TestElasticPressure:
    def test_anomaly_firing_drives_scale_out_once(self, tmp_path):
        from flink_parameter_server_tpu.elastic import (
            ElasticClusterConfig,
            ElasticClusterDriver,
            ElasticController,
            ScalePolicy,
        )
        from flink_parameter_server_tpu.models.matrix_factorization import (
            OnlineMatrixFactorization,
            SGDUpdater,
        )
        from flink_parameter_server_tpu.utils.initializers import (
            ranged_random_factor,
        )

        reg = MetricsRegistry()
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
        try:
            g = reg.gauge("probe_value", component="test")
            det = EWMADriftDetector("probe_value", field="value",
                                    k=4.0, warmup=5)
            rec = TimelineRecorder(reg, interval_s=0.01, detectors=[det])
            ctl = ElasticController(
                d,
                policy=ScalePolicy(
                    max_shards=4, min_window_frames=5, cooldown_s=0.0
                ),
                registry=reg,
                timeline=rec,
            )
            for _ in range(8):
                g.set(1.0)
                rec.sample()
            assert ctl.step() is None  # flat series, no pressure
            g.set(10.0)
            rec.sample()  # the drift fires here
            act = ctl.step()
            assert act and act["action"] == "scale_out" and act["ok"]
            assert act["timeline_anomalies"] == ["probe_value/ewma_drift"]
            assert d.partitioner.num_shards == 2
            # cursor advanced: the SAME firing never pressures twice
            assert ctl.step() is None
        finally:
            d.stop()


# ---------------------------------------------------------------------------
# surfaces: telemetry endpoint + psctl watch/timeline (live)
# ---------------------------------------------------------------------------


class TestSurfaces:
    def test_timeline_endpoint_null_without_recorder(self):
        from flink_parameter_server_tpu.telemetry.exporter import (
            TelemetryServer,
        )
        from tools.psctl import scrape

        reg = MetricsRegistry()
        prev = get_timeline()
        set_timeline(None)  # the opt-in contract: nothing lazy-creates one
        tsrv = TelemetryServer(reg).start()
        try:
            doc = json.loads(scrape(tsrv.host, tsrv.port, "timeline"))
            assert doc["timeline"] is None
            assert get_timeline() is None  # the scrape installed nothing
        finally:
            tsrv.stop()
            set_timeline(prev)

    def test_psctl_watch_and_timeline_live_smoke(self, capsys):
        from tools.psctl import main as psctl_main

        from flink_parameter_server_tpu.cluster.driver import ClusterConfig
        from flink_parameter_server_tpu.telemetry.exporter import (
            TelemetryServer,
        )
        from flink_parameter_server_tpu.workloads import (
            WorkloadParams,
            build_cluster_driver,
            create_workload,
        )

        reg = MetricsRegistry()
        wl = create_workload("sketch", WorkloadParams(
            rounds=4, batch=32, num_users=24, num_items=32, dim=4, seed=3,
        ))
        driver = build_cluster_driver(
            wl,
            config=ClusterConfig(
                num_shards=2, num_workers=1, staleness_bound=0,
            ),
            registry=reg,
        )
        rec = TimelineRecorder(reg, interval_s=0.02)
        tsrv = None
        try:
            with driver:
                rec.sample()
                driver.run(wl.batches())
                time.sleep(0.03)
                rec.sample()  # second tick: rates + RTT window
            set_timeline(rec)
            tsrv = TelemetryServer(reg).start()
            addr = f"{tsrv.host}:{tsrv.port}"

            rc = psctl_main([
                "watch", "--metrics", addr, "--raw",
                "--iterations", "2", "--interval", "0.05",
            ])
            assert rc == 0
            out = capsys.readouterr().out
            assert "psctl watch" in out
            # second frame carries rate rows over real counters
            assert "fps_" in out and "trend" in out

            # the per-shard attribution series, by registry name...
            rc = psctl_main([
                "timeline", "cluster_shard_rtt_seconds",
                "--metrics", addr, "--json",
            ])
            assert rc == 0
            doc = json.loads(capsys.readouterr().out)
            assert doc["metric"] == "cluster_shard_rtt_seconds"
            shards = {
                s["labels"].get("shard") for s in doc["series"]
                if s["field"] == "p99"
            }
            assert shards == {"0", "1"}  # one series per shard
            # ...and by exported (fps_-prefixed) name, rendered path
            rc = psctl_main([
                "timeline", "fps_cluster_shard_rtt_seconds",
                "--metrics", addr,
            ])
            assert rc == 0
            rendered = capsys.readouterr().out
            assert "psctl timeline" in rendered
            assert "shard=0" in rendered and "shard=1" in rendered

            # unknown metric is a loud rc=1 listing what IS recorded
            rc = psctl_main([
                "timeline", "no_such_metric", "--metrics", addr,
            ])
            assert rc == 1
        finally:
            set_timeline(None)
            if tsrv is not None:
                tsrv.stop()


# ---------------------------------------------------------------------------
# tooling gates
# ---------------------------------------------------------------------------


class TestTooling:
    def test_known_component_registered(self):
        from tools.check_metric_lines import KNOWN_COMPONENTS

        assert "timeline" in KNOWN_COMPONENTS

    def test_lint_catches_broken_payloads(self):
        from tools.check_metric_lines import check_timeline

        good = {
            "interval_s": 0.05,
            "series": [{
                "metric": "m", "labels": {}, "field": "value",
                "points": [[1.0, 2.0], [1.05, 2.1]],
            }],
            "marks": [{"ts": 1.0, "label": "start"}],
            "anomalies": [{"ts": 1.05, "metric": "m", "kind": "x"}],
        }
        assert check_timeline(good) == []
        bad = json.loads(json.dumps(good))
        bad["series"][0]["points"] = [[2.0, 1.0], [1.0, 1.0]]  # time warp
        bad["anomalies"][0]["metric"] = "ghost"  # evidence-free anomaly
        problems = check_timeline(bad)
        assert any("regress" in p for p in problems)
        assert any("ghost" in p for p in problems)
        assert check_timeline({"no": "payload"})  # nothing to lint is loud
