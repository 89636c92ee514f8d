"""Supervised restart — the ``RecoveringDriver`` wrapper.

The reference's operational story ended at "a lost worker is a lost
job" (SURVEY.md §5).  This module is the supervisor that story was
missing, layered on what the rebuild already has: durable checkpoints
(``training/checkpoint``), the update WAL (:mod:`.wal`), and the
driver's resume-with-cursor-fast-forward contract.

Failure model (the recovery-semantics table in docs/resilience.md):

  ===============  ===========================================  ==========
  class            examples                                     recovery
  ===============  ===========================================  ==========
  SOURCE           ConnectionError, socket timeouts, OSError    restore + WAL replay,
                                                                then reconnect/re-feed
  DIVERGED         TrainingDiverged (NaN guard)                 restore, DROP the WAL
                                                                tail (it is the
                                                                poison), skip the
                                                                window's input
  DEVICE           XlaRuntimeError, injected ChaosError         restore + WAL replay
  UNKNOWN          anything else                                restore + WAL replay
                                                                (retry gated by
                                                                ``retry_unknown``)
  ===============  ===========================================  ==========

Restart discipline: capped exponential backoff with full jitter
(``sleep = uniform(0, min(cap, base * 2**attempt))`` — the AWS
architecture-blog shape, which decorrelates a herd of restarting
workers), bounded by ``max_restarts`` per run; the budget refills on
success (a job that hits a flaky hour and then runs clean for a week
has not "used up" its restarts).
"""
from __future__ import annotations

import dataclasses
import enum
import time
from typing import Any, Callable, Iterable, List, Optional

import numpy as np

from ..telemetry.registry import get_registry, json_line
from ..telemetry.spans import get_tracer
from ..training.driver import StreamingDriver, TrainingDiverged


class FailureClass(enum.Enum):
    SOURCE = "source"
    DIVERGED = "diverged"
    DEVICE = "device"
    UNKNOWN = "unknown"


def classify_failure(exc: BaseException) -> FailureClass:
    """Map an exception from the train loop onto the failure classes.

    Explicit tags win (:class:`~.chaos.ChaosError` carries
    ``failure_class`` so tests steer each branch deterministically);
    then the NaN guard, source/I-O errors, and device-runtime errors by
    type; everything else is UNKNOWN."""
    tag = getattr(exc, "failure_class", None)
    if isinstance(tag, str):
        try:
            return FailureClass(tag)
        except ValueError:
            pass
    if isinstance(exc, TrainingDiverged):
        return FailureClass.DIVERGED
    if isinstance(exc, (ConnectionError, TimeoutError, EOFError, OSError)):
        return FailureClass.SOURCE
    # jax's XlaRuntimeError moves between modules across versions —
    # match by name so classification does not pin a jax version
    if type(exc).__name__ in ("XlaRuntimeError", "JaxRuntimeError"):
        return FailureClass.DEVICE
    return FailureClass.UNKNOWN


@dataclasses.dataclass(frozen=True)
class RestartPolicy:
    """Backoff + budget knobs for :class:`RecoveringDriver`.

    ``max_restarts`` bounds consecutive failed attempts of one logical
    run.  ``backoff_base_s``/``backoff_cap_s`` shape the capped
    exponential; ``jitter`` in [0, 1] blends full jitter (1.0, the
    default — restarting fleets decorrelate) toward deterministic
    backoff (0.0 — reproducible tests).  ``seed`` makes the jitter
    stream deterministic either way."""

    max_restarts: int = 5
    backoff_base_s: float = 0.05
    backoff_cap_s: float = 30.0
    jitter: float = 1.0
    seed: int = 0
    retry_unknown: bool = True

    def __post_init__(self):
        if self.max_restarts < 0:
            raise ValueError(f"max_restarts={self.max_restarts}: must be >= 0")
        if not 0.0 <= self.jitter <= 1.0:
            raise ValueError(f"jitter={self.jitter}: must be in [0, 1]")

    def retryable(self, fc: FailureClass) -> bool:
        if fc is FailureClass.UNKNOWN:
            return self.retry_unknown
        return True

    def backoff_s(self, attempt: int, rng: np.random.Generator) -> float:
        """Sleep before restart ``attempt`` (1-based)."""
        ceiling = min(
            self.backoff_cap_s, self.backoff_base_s * (2 ** (attempt - 1))
        )
        jittered = float(rng.uniform(0.0, ceiling))
        return (1.0 - self.jitter) * ceiling + self.jitter * jittered


class RecoveryFailed(RuntimeError):
    """Restart budget exhausted (or non-retryable class); carries the
    last underlying failure as ``__cause__`` and the per-attempt event
    log as ``events``."""

    def __init__(self, message: str, events: List[dict]):
        super().__init__(message)
        self.events = events


class RecoveringDriver:
    """Supervised-restart wrapper: ``RecoveringDriver(driver,
    data_factory).run()`` is ``driver.run(data_factory())`` that
    survives crashes.

    ``data_factory`` must return a FRESH iterator over the SAME logical
    stream on each call (re-open the file, re-connect the socket —
    exactly the driver's documented resume contract); the wrapper
    handles the cursor so re-fed input is never double-applied:

      * restore the latest durable checkpoint (step S),
      * replay the WAL tail (steps S+1..T) through the normal driver
        loop — the recovered table is then *bitwise* what an
        uninterrupted run would hold at T, not approximately so,
      * fast-forward the fresh source past everything consumed
        (T batches, plus any window a divergence forced us to drop).

    On :class:`~..training.driver.TrainingDiverged` the WAL tail is
    dropped instead of replayed — it *contains* the poison and would
    re-diverge deterministically — and the input window since the last
    checkpoint is skipped (documented loss; every other class loses
    nothing).

    ``metrics_sink`` receives one JSON line per restart (same contract
    as the driver's metrics): ``{"restart": n, "failure": "device",
    "restored_step": S, "replayed_steps": k, "backoff_s": ...}``.
    """

    def __init__(
        self,
        driver: StreamingDriver,
        data_factory: Callable[[], Iterable],
        *,
        policy: Optional[RestartPolicy] = None,
        metrics_sink=None,
        registry=None,
        flightrec=None,
    ):
        self.driver = driver
        self.data_factory = data_factory
        self.policy = policy if policy is not None else RestartPolicy()
        self.metrics_sink = metrics_sink
        # flight recorder: blackbox-dump on every crash BEFORE the
        # restart overwrites the evidence (None = process-wide
        # recorder, no-op when none installed; False = never)
        self._flightrec = flightrec
        self.events: List[dict] = []
        self.restarts = 0
        self.steps_replayed = 0
        self.steps_dropped = 0
        self._extra_skip = 0  # input batches dropped forever (divergence)
        self._rng = np.random.default_rng(self.policy.seed)
        # unified plane: restart/backoff/replay episodes publish under
        # component=recovery (counters here, spans around the recover
        # path) alongside the per-restart JSON event line
        self._registry = (
            registry if registry is not None else get_registry()
        )

    # -- the supervision loop ----------------------------------------------
    def run(self, collect_outputs: bool = False, **run_kwargs) -> Any:
        """Run to completion under supervision; returns the final
        :class:`~..core.transform.TransformResult`.  ``collect_outputs``
        spans restarts only for the surviving run (outputs of a crashed
        attempt died with it — collecting across attempts would
        duplicate replayed steps)."""
        attempt = 0
        while True:
            try:
                return self.driver.run(
                    self.data_factory(),
                    collect_outputs=collect_outputs,
                    fast_forward=True,
                    **run_kwargs,
                )
            except BaseException as exc:
                fc = classify_failure(exc)
                attempt += 1
                event = {
                    "restart": attempt,
                    "failure": fc.value,
                    "error": f"{type(exc).__name__}: {exc}",
                }
                if not self.policy.retryable(fc):
                    event["gave_up"] = "non-retryable"
                    self._record(event)
                    raise
                if attempt > self.policy.max_restarts:
                    event["gave_up"] = "restart budget exhausted"
                    self._record(event)
                    raise RecoveryFailed(
                        f"giving up after {attempt - 1} restarts "
                        f"(max_restarts={self.policy.max_restarts}); "
                        f"last failure: {type(exc).__name__}: {exc}",
                        self.events,
                    ) from exc
                backoff = self.policy.backoff_s(attempt, self._rng)
                event["backoff_s"] = round(backoff, 4)
                if self._flightrec is not False:
                    rec = self._flightrec
                    if rec is None:
                        from ..telemetry.flightrec import get_recorder

                        rec = get_recorder()
                    if rec is not None:
                        rec.note(
                            "crash", failure=fc.value, restart=attempt,
                            error=event["error"],
                        )
                        rec.dump(f"crash_{fc.value}")
                tracer = get_tracer()
                if backoff > 0:
                    with tracer.span("backoff", component="recovery"):
                        time.sleep(backoff)
                t_rec = time.monotonic()
                with tracer.span("recover", component="recovery"):
                    self._recover(fc, exc, event)
                self._registry.histogram(
                    "recovery_duration_seconds", component="recovery"
                ).observe(time.monotonic() - t_rec)
                self.restarts += 1
                self._record(event)

    # -- recovery mechanics ------------------------------------------------
    def _recover(
        self, fc: FailureClass, exc: BaseException, event: dict
    ) -> None:
        driver = self.driver
        # Roll back to the latest durable checkpoint.  driver.run's own
        # except-path already resumed once (to keep the driver usable);
        # resuming again is idempotent and covers failures raised before
        # that path (e.g. out of the source on the first batch).
        restored = driver.resume()
        if restored:
            restored_step = driver.step_idx
        else:
            # No durable checkpoint: restart from the driver's pre-run
            # state — transform_batched copies (table, state) at entry,
            # so the store/state the driver holds are the ones from
            # before the crashed run; rewinding the step counter re-runs
            # the whole stream.  WAL replay needs a checkpoint anchor,
            # so it is skipped (idempotent appends absorb the re-feed).
            driver.step_idx = 0
            restored_step = 0
        event["restored_step"] = restored_step
        wal = driver.wal if restored else None
        if fc is FailureClass.DIVERGED and wal is not None:
            # the tail caused the divergence; replaying it re-diverges
            # deterministically — drop it and skip the window's input
            tail_end = wal.last_step_logged
            dropped = wal.drop_after(restored_step)
            window = max(
                0,
                (tail_end if tail_end is not None else restored_step)
                - restored_step,
            )
            self._extra_skip += window
            self.steps_dropped += window
            event["dropped_steps"] = window
            event["dropped_records"] = dropped
        elif fc is FailureClass.DIVERGED:
            # no WAL: best effort — skip input through the diverged step
            # (TrainingDiverged carries it); prefetched-but-unapplied
            # batches beyond it are re-fed, which is correct (they were
            # never applied, and are in no recovery log to replay)
            failed_step = getattr(exc, "step", restored_step)
            window = max(0, failed_step - restored_step)
            self._extra_skip += window
            self.steps_dropped += window
            event["dropped_steps"] = window
        elif wal is not None:
            replayed = self._replay_wal_tail(restored_step)
            self.steps_replayed += replayed
            event["replayed_steps"] = replayed
        # Cursor fast-forward for the re-fed source: everything applied
        # (step_idx) plus everything dropped must be skipped — without
        # this the next run would double-apply the replayed window.
        driver._pending_skip = driver.step_idx + self._extra_skip

    def _replay_wal_tail(self, restored_step: int) -> int:
        """Feed the WAL tail back through the normal driver loop (same
        jitted step, same cadences — replay is just training on logged
        batches; WAL idempotence skips re-logging them)."""
        driver = self.driver
        records = driver.wal.replay(after_step=restored_step)
        if not records:
            return 0
        batches = []
        for rec in records:
            if rec.n_steps == 1:
                batches.append(rec.payload)
            else:  # grouped record: one payload per step, in order
                batches.extend(rec.payload)
        driver.run(batches, collect_outputs=False, fast_forward=False)
        return driver.step_idx - restored_step

    def _record(self, event: dict) -> None:
        self.events.append(event)
        reg = self._registry
        if reg is not False:
            if "gave_up" not in event:  # a gave-up attempt never restarted
                reg.counter(
                    "recovery_restarts_total", component="recovery",
                    failure=event["failure"],
                ).inc()
            if event.get("replayed_steps"):
                reg.counter(
                    "recovery_replayed_steps_total", component="recovery"
                ).inc(event["replayed_steps"])
            if event.get("dropped_steps"):
                reg.counter(
                    "recovery_dropped_steps_total", component="recovery"
                ).inc(event["dropped_steps"])
        if self.metrics_sink is not None:
            # one JSON line per restart, now stamped with the shared
            # ts/run_id (same contract as every other emitter)
            json_line(event, self.metrics_sink)


__all__ = [
    "FailureClass",
    "classify_failure",
    "RestartPolicy",
    "RecoveringDriver",
    "RecoveryFailed",
]
