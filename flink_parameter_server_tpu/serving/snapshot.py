"""Versioned table snapshots — the read path's isolation boundary.

The training loop donates its table buffer into every jitted step
(``transform_batched`` jits with ``donate_argnums``), so a reader
holding the live array would race the scatter-update — or worse, read a
deleted buffer.  The snapshot discipline (the straggler-study split:
serving must never block the update loop): at a configurable
``publish_every`` dispatch cadence the trainer *copies* the live table
(donated-buffer copy-on-publish — ``jnp.copy`` preserves sharding) and
swaps an immutable, versioned :class:`TableSnapshot` behind a lock.
Readers grab the latest snapshot pointer once per query and see a
bit-identical table until the next publish; staleness (trainer steps
behind) is carried as metadata on every answer instead of being hidden.

All publishes happen on the TRAINING thread (the driver's dispatch
callback), so the copy is sequenced before the next donation without
any cross-thread buffer juggling; readers only ever swap pointers.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..core.store import ShardedParamStore, StoreSpec
from ..telemetry.spans import NULL_TRACER

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class TableSnapshot:
    """An immutable published view of the parameter table.

    ``aux`` carries whatever the trainer published alongside the table —
    the driver publishes the worker state (e.g. MF user factors), which
    is what the query engine scores with.  ``train_step`` is the trainer
    step the snapshot was taken at; staleness at read time is computed
    against the manager's live step counter."""

    spec: StoreSpec
    table: Array
    aux: Any
    version: int
    train_step: int
    published_at: float

    def store(self) -> ShardedParamStore:
        """The snapshot as a read-only store (pull/top-K compose)."""
        return ShardedParamStore(self.spec, self.table)


class SnapshotManager:
    """Publish-side cadence + read-side pointer swap, thread-safe.

    ``publish_every`` is measured in trainer steps: ``maybe_publish``
    republishes only once the trainer has advanced that far past the
    last published snapshot (the first offer always publishes).  Every
    ``note_step``/``maybe_publish`` call also advances the live step
    counter that :meth:`staleness` measures against.

    ``tracer`` (``ServingService.attach_tracer`` hands the driver's; the
    default records nothing) gets one ``train.publish`` span a publish
    that copies — an offer the cadence declines records none — with
    children ``train.publish_enqueue`` (the copies handed to the device)
    and ``train.publish_sync`` (the wait until they are complete).
    """

    def __init__(self, spec: StoreSpec, *, publish_every: int = 1):
        if publish_every < 1:
            raise ValueError(f"publish_every={publish_every}: must be >= 1")
        self.spec = spec
        self.publish_every = int(publish_every)
        self._lock = threading.Lock()
        self._latest: Optional[TableSnapshot] = None
        self._current_step = 0
        self._published = threading.Event()
        self.tracer = NULL_TRACER

    # -- publish side (training thread) -----------------------------------
    def publish(self, table: Array, step: int, aux: Any = None) -> TableSnapshot:
        """Copy-on-publish: snapshot the live (donated-next-dispatch)
        buffers and swap the latest pointer.  Blocks until the copy is
        device-complete so the source buffer is free to be donated the
        moment this returns."""
        tracer = self.tracer
        with tracer.span("publish", component="train"):
            with tracer.span("publish_enqueue", component="train"):
                copied = jnp.copy(table)
                aux_copied = jax.tree.map(
                    lambda x: jnp.copy(x) if isinstance(x, jax.Array) else x,
                    aux,
                )
            with tracer.span("publish_sync", component="train"):
                jax.block_until_ready(copied)
                if aux_copied is not None:
                    jax.block_until_ready(aux_copied)
            with self._lock:
                version = (self._latest.version + 1) if self._latest else 1
                snap = TableSnapshot(
                    spec=self.spec,
                    table=copied,
                    aux=aux_copied,
                    version=version,
                    train_step=int(step),
                    published_at=time.time(),
                )
                self._latest = snap
                self._current_step = max(self._current_step, int(step))
            self._published.set()
        return snap

    def maybe_publish(
        self, table: Array, step: int, aux: Any = None
    ) -> Optional[TableSnapshot]:
        """Publish iff the cadence is due; always advances the live step
        counter (so staleness keeps ticking between publishes)."""
        self.note_step(step)
        with self._lock:
            due = (
                self._latest is None
                or int(step) - self._latest.train_step >= self.publish_every
            )
        if due:
            return self.publish(table, step, aux)
        return None

    def note_step(self, step: int) -> None:
        """Record trainer progress without publishing (staleness input)."""
        with self._lock:
            self._current_step = max(self._current_step, int(step))

    # -- read side (serving threads) ---------------------------------------
    def latest(self) -> Optional[TableSnapshot]:
        with self._lock:
            return self._latest

    @property
    def current_step(self) -> int:
        with self._lock:
            return self._current_step

    def staleness_of(self, snap: TableSnapshot) -> int:
        """Trainer steps the snapshot lags the live table (>= 0)."""
        return max(0, self.current_step - snap.train_step)

    def staleness(self) -> Optional[int]:
        snap = self.latest()
        return None if snap is None else self.staleness_of(snap)

    def wait_for_snapshot(self, timeout: Optional[float] = None) -> bool:
        """Block until the first publish (serving warm-up gate)."""
        return self._published.wait(timeout)


__all__ = ["TableSnapshot", "SnapshotManager"]
