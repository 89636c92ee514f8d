"""The keyed shuffle — records to the worker that owns their key.

Reference parity: the reference keys its training stream before the worker
operator (Flink's keyed shuffle, SURVEY.md §2 "Data parallelism"): a user's
ratings all reach the one worker subtask that holds that user's vector.  The
TPU rebuild holds worker state row-sharded over the ``dp`` mesh axis in
contiguous blocks of ``rows_per_worker`` rows, and a microbatch is ``dp``
lane blocks of equal length, block ``w`` going to worker ``w``.  The router
is what stands between a flat stream and that step: microbatches in,
microbatches out in which lane block ``w`` holds only keys of worker ``w``
(``owner = key // rows_per_worker``).

Every record leaves exactly once, and the records of one worker leave in the
order they came (across workers the stream's order is given up, as a keyed
shuffle gives it up).  A batch is emitted when every worker has a full block;
what is left waits in a buffer, which is bounded: once some worker holds
``max_buffered_blocks`` blocks the router emits with the starved workers'
blocks padded (``mask`` false), and at the end of the stream it flushes the
same way.  Host-side numpy, at the ingestion edge; a batch that already lies
on the device is taken as keyed and handed on as it is (whoever stages
batches on the device routes them first), and so is a host batch that
arrives keyed while nothing waits: routing twice costs one comparison.
"""
from __future__ import annotations

from typing import Dict, Iterable, Iterator, List, Optional

import numpy as np

from ..telemetry.registry import get_registry
from ..telemetry.spans import NULL_TRACER, SpanTracer

Batch = Dict[str, np.ndarray]


class KeyedRouter:
    """Routes microbatches (dicts of columns with one leading axis, an
    optional ``"mask"``) to ``num_workers`` owners of contiguous key blocks.

    The lane count of a worker's block is the first batch's lanes over
    ``num_workers`` (or ``block``).  Counters, on ``registry`` (the
    process's by default): ``keyed_records`` (live records emitted),
    ``keyed_padded_lanes`` (lanes emitted with ``mask`` false) and the gauge
    ``keyed_buffered_max`` (the most records that waited after an emit);
    ``tracer`` gets one span ``ingest.key_route`` a batch routed.
    """

    def __init__(
        self,
        num_workers: int,
        rows_per_worker: int,
        *,
        key: str = "user",
        block: Optional[int] = None,
        max_buffered_blocks: int = 8,
        registry=None,
        tracer: Optional[SpanTracer] = None,
    ):
        if num_workers < 1 or rows_per_worker < 1:
            raise ValueError((num_workers, rows_per_worker))
        self.num_workers = num_workers
        self.rows_per_worker = rows_per_worker
        self.key = key
        self.block = block
        self.max_buffered_blocks = max(1, max_buffered_blocks)
        self.tracer = tracer if tracer is not None else NULL_TRACER
        registry = registry if registry is not None else get_registry()
        self._records = registry.counter("keyed_records", component="ingest")
        self._padded = registry.counter(
            "keyed_padded_lanes", component="ingest"
        )
        self._buffered_max = registry.gauge(
            "keyed_buffered_max", component="ingest"
        )
        self._high_water = 0
        # per worker: its waiting records, one array a column, stream order
        self._waiting: List[Optional[Batch]] = [None] * num_workers
        self._counts = np.zeros(num_workers, np.int64)

    # -- what is keyed -----------------------------------------------------
    def owners(self, keys: np.ndarray) -> np.ndarray:
        return np.clip(
            keys.astype(np.int64) // self.rows_per_worker,
            0, self.num_workers - 1,
        )

    def is_keyed(self, batch: Batch) -> bool:
        """Every live lane of block ``w`` names a key of worker ``w``."""
        keys = np.asarray(batch[self.key])
        if keys.ndim != 1 or len(keys) % self.num_workers or (
            self.block is not None
            and len(keys) != self.block * self.num_workers
        ):
            return False
        want = np.repeat(
            np.arange(self.num_workers), len(keys) // self.num_workers
        )
        ok = self.owners(keys) == want
        if "mask" in batch:
            ok |= ~np.asarray(batch["mask"], bool)
        return bool(ok.all())

    # -- the shuffle -------------------------------------------------------
    def route(self, batches: Iterable[Batch]) -> Iterator[Batch]:
        """Keyed microbatches from ``batches``; flushes when they end."""
        import jax

        for batch in batches:
            on_device = isinstance(batch[self.key], jax.Array)
            if on_device or (not self._counts.any() and self.is_keyed(batch)):
                if on_device and self._counts.any():
                    raise ValueError(
                        "a batch staged on the device reached the keyed "
                        "router while host records wait in it: their order "
                        "within a worker would be lost"
                    )
                yield batch
                continue
            out = []
            with self.tracer.span("key_route", component="ingest"):
                self._take(batch)
                while self._counts.min() >= self.block or (
                    self._counts.max()
                    >= self.max_buffered_blocks * self.block
                ):
                    out.append(self._emit())
                self._note_buffered()
            yield from out
        while self._counts.any():
            yield self._emit()

    def _take(self, batch: Batch) -> None:
        cols = {k: np.asarray(v) for k, v in batch.items() if k != "mask"}
        lanes = len(cols[self.key])
        if self.block is None:
            if lanes % self.num_workers:
                raise ValueError(
                    f"a microbatch of {lanes} lanes does not split over "
                    f"{self.num_workers} keyed workers"
                )
            self.block = lanes // self.num_workers
        if "mask" in batch:
            live = np.asarray(batch["mask"], bool)
            if not live.all():
                cols = {k: v[live] for k, v in cols.items()}
        owner = self.owners(cols[self.key])
        if self.num_workers <= 256:
            owner = owner.astype(np.uint8)  # numpy sorts bytes by counting
        order = np.argsort(owner, kind="stable")
        ends = np.cumsum(np.bincount(owner, minlength=self.num_workers))
        for w, (lo, hi) in enumerate(zip(np.r_[0, ends[:-1]], ends)):
            if hi == lo:
                continue
            mine = {k: v[order[lo:hi]] for k, v in cols.items()}
            held = self._waiting[w]
            self._waiting[w] = mine if held is None else {
                k: np.concatenate([held[k], mine[k]]) for k in mine
            }
            self._counts[w] += hi - lo

    def _emit(self) -> Batch:
        """One microbatch: each worker's oldest ``block`` records, a short
        block padded with lanes whose ``mask`` is false and whose key is the
        worker's first row."""
        block = self.block
        blocks, live = [], np.zeros((self.num_workers, block), bool)
        template = next(h for h in self._waiting if h is not None)
        for w, held in enumerate(self._waiting):
            n = int(min(self._counts[w], block))
            live[w, :n] = True
            part = {}
            for k, like in template.items():
                col = np.zeros((block,) + like.shape[1:], like.dtype)
                if k == self.key:
                    col[:] = w * self.rows_per_worker
                if n:
                    col[:n] = held[k][:n]
                part[k] = col
            blocks.append(part)
            if n:
                rest = int(self._counts[w]) - n
                self._waiting[w] = (
                    {k: v[n:] for k, v in held.items()} if rest else None
                )
                self._counts[w] = rest
        out = {k: np.concatenate([b[k] for b in blocks]) for k in template}
        out["mask"] = live.reshape(-1)
        sent = int(live.sum())
        self._records.inc(sent)
        self._padded.inc(live.size - sent)
        return out

    def _note_buffered(self) -> None:
        waiting = int(self._counts.sum())
        if waiting > self._high_water:
            self._high_water = waiting
            self._buffered_max.set(waiting)


__all__ = ["KeyedRouter"]
