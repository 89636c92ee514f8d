"""The open-loop clock: latency runs from the instant a query was DUE, so a
stalled server raises the tail of the queries behind the stall; the generator
reports how late it sent; the schedule is the seed's."""
from concurrent.futures import Future

import numpy as np
import pytest

from chipbench import loadgen


class FakeTime:
    """A clock the test owns: sleeping advances it, nothing else does."""

    def __init__(self):
        self.now = 0.0

    def clock(self):
        return self.now

    def sleep(self, s):
        self.now += s


def _run(arrivals, serve):
    t = FakeTime()
    loop = loadgen.OpenLoop(
        lambda user: serve(t, user), np.asarray(arrivals, float),
        np.arange(len(arrivals)), threads=1, clock=t.clock, sleep=t.sleep,
    )
    loop._run(0.0, *loop._slots[0])  # in this thread: the fake clock is not shared
    return loop


def _answered(t, cost):
    t.now += cost  # a synchronous server: the generator waits for it
    f = Future()
    f.set_result("ok")
    return f


def test_latency_runs_from_due_time_not_send_time():
    # ten queries 1 ms apart; the server takes 1 ms for each but the third,
    # which stalls 50 ms: later queries were DUE during the stall
    def serve(t, user):
        return _answered(t, 0.050 if user == 2 else 0.001)

    loop = _run([i * 0.001 for i in range(10)], serve)
    lat = loop.latency_ms
    assert len(lat) == 10 and loop.failed == 0
    assert lat[0] == 1.0 and lat[1] == 1.0
    assert abs(lat[2] - 50.0) < 1e-9
    # query 3 was due at 3 ms, sent at 52 ms, answered at 53 ms: 50 ms, not 1
    assert abs(lat[3] - 50.0) < 1e-9
    # the server keeps pace but never catches up: every later query carries
    # the stall, which timing from the send would have hidden as 1 ms
    assert all(abs(x - 50.0) < 1e-6 for x in lat[3:])
    assert abs(loop.late_ms[3] - 49.0) < 1e-9 and loop.late_ms[0] == 0.0


def test_rejected_and_failed_queries_count_as_failed():
    def serve(t, user):
        if user == 1:
            raise RuntimeError("queue full")
        f = Future()
        if user == 2:
            f.set_exception(TimeoutError("deadline"))
        else:
            f.set_result("ok")
        return f

    loop = _run([0.0, 0.001, 0.002, 0.003], serve)
    assert loop.attempted == 4 and loop.failed == 2
    assert len(loop.latency_ms) == 2 and len(loop.errors) == 2


def test_unanswered_queries_are_failed_at_join():
    loop = loadgen.OpenLoop(
        lambda user: Future(), np.array([0.0, 0.0]), np.array([0, 1]), threads=2,
    )
    loop.start(loadgen.time.perf_counter())
    assert loop.join(timeout_s=0.2)
    assert loop.failed == 2 and loop.latency_ms == []


def test_schedule_is_the_seeds_and_poisson():
    a = loadgen.poisson_arrivals(500.0, 20.0, seed=2**31 + 11)
    b = loadgen.poisson_arrivals(500.0, 20.0, seed=2**31 + 11)
    c = loadgen.poisson_arrivals(500.0, 20.0, seed=5)
    assert np.array_equal(a, b) and not np.array_equal(a[:10], c[:10])
    assert abs(len(a) - 10_000) < 400 and a[-1] < 20.0 and (np.diff(a) > 0).all()
    gaps = np.diff(a)
    assert abs(gaps.mean() - 0.002) < 1e-4 and abs(gaps.std() - 0.002) < 2e-4
    slots = loadgen.split_slots(a, 4)
    assert sorted(np.concatenate(slots)) == sorted(a)


def test_zipf_users_are_skewed_in_range_and_seeded():
    u = loadgen.zipf_users(20_000, 100_000, 1.1, seed=3)
    assert u.min() >= 0 and u.max() < 100_000
    assert np.array_equal(u, loadgen.zipf_users(20_000, 100_000, 1.1, seed=3))
    _, counts = np.unique(u, return_counts=True)
    assert counts.max() > 500  # a hot head ...
    assert len(counts) > 5_000  # ... on a long tail


@pytest.mark.parametrize("rate, seconds", [(0.0, 1.0), (-5.0, 1.0), (10.0, 0.0)])
def test_a_schedule_needs_a_rate_and_a_length(rate, seconds):
    with pytest.raises(ValueError):
        loadgen.poisson_arrivals(rate, seconds, seed=0)
