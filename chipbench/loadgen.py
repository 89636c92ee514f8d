"""Open-loop query load — the benchmark's copy of the sound parts of
``loadgen/arrivals.py`` and ``loadgen/population.py``.

Arrival times are drawn up front from the seed, independent of any answer,
and every query is timed **from the instant it was due**, so a stall shows
up as tail latency of the queries behind it instead of thinning the load.
How late the generator itself sent is reported too: a starved generator
must not read as a fast server.
"""
from __future__ import annotations

import math
import threading
import time
from typing import Callable, List

import numpy as np


def poisson_arrivals(rate: float, duration_s: float, *, seed: int) -> np.ndarray:
    """Poisson arrival offsets in ``[0, duration_s)`` at a mean of ``rate``."""
    if rate <= 0 or duration_s <= 0:
        raise ValueError("rate and duration_s must be > 0")
    rng = np.random.default_rng(seed)
    n = int(rate * duration_s + 10 * math.sqrt(rate * duration_s) + 16)
    t = np.cumsum(rng.exponential(1.0 / rate, n))
    while t[-1] < duration_s:  # vanishingly rare: extend the draw
        t = np.concatenate(
            [t, t[-1] + np.cumsum(rng.exponential(1.0 / rate, n))]
        )
    return t[t < duration_s]


def split_slots(arrivals: np.ndarray, n: int) -> List[np.ndarray]:
    """Deal one global schedule to ``n`` generator threads round-robin;
    every query keeps its absolute arrival offset."""
    if n < 1:
        raise ValueError(f"n={n}: must be >= 1")
    return [np.asarray(arrivals[t::n], np.float64) for t in range(n)]


def zipf_users(n: int, num_users: int, s: float, *, seed: int) -> np.ndarray:
    """``n`` user ids, Zipf(``s``)-ranked over the whole population; rank ->
    id through a seeded affine bijection so the hot head is not ``[0..k)``."""
    rng = np.random.default_rng(seed)
    cdf = np.cumsum(np.arange(1, num_users + 1, dtype=np.float64) ** -float(s))
    ranks = np.searchsorted(cdf, rng.random(n) * cdf[-1]).astype(np.int64)
    mult = int(rng.integers(1, num_users))
    while math.gcd(mult, num_users) != 1:
        mult += 1
    shift = int(rng.integers(0, num_users))
    return ((ranks * mult + shift) % num_users).astype(np.int64)


class OpenLoop:
    """Send ``submit(user)`` at ``t0 + arrivals[i]`` from a few threads.

    ``submit`` returns a future (``add_done_callback``/``exception``/
    ``result``) or raises (a rejected query: counted failed).  After
    :meth:`join`: ``latency_ms`` (due -> answered), ``late_ms`` (due ->
    sent), ``answers`` (``(user, result)``), ``failed``, ``attempted``.
    """

    def __init__(
        self, submit: Callable, arrivals: np.ndarray, users: np.ndarray, *,
        threads: int, clock: Callable[[], float] = time.perf_counter,
        sleep: Callable[[float], None] = time.sleep,
    ):
        if len(arrivals) != len(users):
            raise ValueError("one user per arrival")
        self._submit, self._clock, self._sleep = submit, clock, sleep
        self._slots = list(zip(
            split_slots(arrivals, threads), split_slots(users, threads)
        ))
        self._lock = threading.Lock()
        self._threads: List[threading.Thread] = []
        self.attempted = len(arrivals)
        self.failed = 0
        self.latency_ms: List[float] = []
        self.late_ms: List[float] = []
        self.answers: list = []
        self.errors: List[str] = []

    def _fail(self, why: str) -> None:
        with self._lock:
            self.failed += 1
            if len(self.errors) < 5:
                self.errors.append(why)

    def _one(self, due: float, user: int) -> None:
        try:
            fut = self._submit(user)
        except Exception as e:  # rejected at admission: a failed query
            self._fail(f"{type(e).__name__}: {e}")
            return

        def done(f):
            t = self._clock()
            err = f.exception()
            if err is not None:
                self._fail(f"{type(err).__name__}: {err}")
                return
            with self._lock:
                self.latency_ms.append((t - due) * 1e3)
                self.answers.append((user, f.result()))

        fut.add_done_callback(done)

    def _run(self, t0: float, offsets: np.ndarray, users: np.ndarray) -> None:
        late = []
        for off, user in zip(offsets.tolist(), users.tolist()):
            due = t0 + off
            wait = due - self._clock()
            if wait > 0:
                self._sleep(wait)
            late.append((self._clock() - due) * 1e3)
            self._one(due, user)
        with self._lock:
            self.late_ms.extend(late)

    def start(self, t0: float) -> None:
        for offsets, users in self._slots:
            th = threading.Thread(
                target=self._run, args=(t0, offsets, users),
                name="chipbench-loadgen", daemon=True,
            )
            th.start()
            self._threads.append(th)

    def join(self, timeout_s: float) -> bool:
        """Wait for every generator thread and every answer; whatever has
        not come by then is counted failed.  False if a thread is alive."""
        deadline = time.monotonic() + timeout_s
        for th in self._threads:
            th.join(max(0.0, deadline - time.monotonic()))
        alive = any(th.is_alive() for th in self._threads)
        while time.monotonic() < deadline:
            with self._lock:
                if len(self.latency_ms) + self.failed >= self.attempted:
                    break
            time.sleep(0.005)
        with self._lock:
            missing = self.attempted - len(self.latency_ms) - self.failed
            if missing > 0:
                self.failed += missing
                self.errors.append(f"{missing} queries unanswered at join")
        return not alive
