"""The arithmetic behind the numbers, against numpy; the result line's keys."""
import json

import numpy as np
import pytest

from chipbench import run, stats


@pytest.mark.parametrize("n", [1, 2, 3, 10, 101, 1000])
@pytest.mark.parametrize("q", [0, 50, 95, 99, 100])
def test_percentile_is_numpys(n, q):
    xs = np.random.default_rng(n * 101 + q).lognormal(size=n)
    assert stats.percentile(list(xs), q) == pytest.approx(
        float(np.percentile(xs, q)), rel=1e-12
    )


def test_percentile_of_nothing_is_nothing():
    assert stats.percentile([], 95) is None
    assert stats.median([]) is None


def test_rate_is_all_records_over_all_seconds_per_chip():
    assert stats.rate_per_chip(65536 * 100, 2.0, 1) == 65536 * 50
    assert stats.rate_per_chip(32768 * 10, 2.0, 4) == 32768 * 10 / 2.0 / 4
    with pytest.raises(ValueError):
        stats.rate_per_chip(1, 0.0, 1)


def test_result_line_has_exactly_the_contracts_keys():
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 123}
    line = json.loads(run.result_line(
        True, 400, 0, {"setup_s": (17.25, "s"), "query_p95_ms": (38.0, "ms")},
        device,
    ))
    assert tuple(line) == run.RESULT_KEYS
    assert line["metrics"]["setup_s"] == {"value": 17.25, "unit": "s"}
    assert line["device"] == device and line["correct"] is True
    traced = json.loads(run.result_line(
        False, 1, 1, {}, device,
        {"device_ops": [["fusion", 0.5]], "idle_gaps": []},
    ))
    assert tuple(traced) == run.RESULT_KEYS + ("breakdown",)
    assert "\n" not in run.result_line(True, 1, 0, {}, device)
