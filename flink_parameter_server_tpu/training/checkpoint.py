"""Checkpoint / resume for PS jobs.

Reference parity (SURVEY.md §5 "Checkpoint / resume"): the reference has
NO PS-aware checkpointing — Flink's own checkpointing does not cover
iterative streams (in-flight feedback records are lost), so the repo lives
with close()-time model dumps and a ``transformWithModelLoad`` overload.

The rebuild does strictly better by design: pulls/pushes are synchronous
within a step, so there is no in-flight-message problem — a checkpoint is
just (sharded param table, worker state, data cursor), saved with orbax.
``restore`` reproduces the exact training state; ``load_model`` covers the
reference's model-load overload from a saved table.
"""
from __future__ import annotations

import os
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from ..core.store import ShardedParamStore, StoreSpec


def _ocp():
    import orbax.checkpoint as ocp

    return ocp


def _make_payload(store, worker_state, step, extra):
    # The payload table is in LOGICAL row order, as the store itself hands
    # it out (`ShardedParamStore.portable`; a `StoreGroup`'s is a table a
    # name, and its `capacity` a count a name).
    return {
        "table": store.portable(),
        "worker_state": worker_state if worker_state is not None else (),
        "meta": {
            "step": step,
            "capacity": store.spec.capacity,
            **(extra or {}),
        },
    }


def save(
    path: str,
    store: ShardedParamStore,
    worker_state: Any = None,
    *,
    step: int = 0,
    extra: Optional[Dict[str, Any]] = None,
) -> None:
    """Save (param table, worker state, cursor) atomically under ``path``."""
    ocp = _ocp()
    path = os.path.abspath(path)
    with ocp.PyTreeCheckpointer() as ckptr:
        ckptr.save(path, _make_payload(store, worker_state, step, extra), force=True)


def restore(
    path: str,
    spec: StoreSpec,
    worker_state_shardings: Any = None,
) -> Tuple[ShardedParamStore, Any, Dict[str, Any]]:
    """Restore a checkpoint onto (possibly different) shardings.

    ``spec`` supplies the target mesh/layout — elasticity the reference
    lacks: a job checkpointed at ps_parallelism=M restores onto M' shards.
    The saved table (padded for M shards) is sliced back to its logical
    capacity and re-padded for the target layout.

    ``worker_state_shardings``: optional pytree of shardings (matching the
    saved worker state) to place the restored worker state onto.
    """
    ocp = _ocp()
    path = os.path.abspath(path)
    import warnings

    with ocp.PyTreeCheckpointer() as ckptr:
        with warnings.catch_warnings():
            # orbax warns that restoring without target shardings reads the
            # sharding file — intentional here: elasticity means we restore
            # to host then re-place onto the *target* spec below.
            warnings.filterwarnings(
                "ignore", message="Sharding info not provided"
            )
            payload = ckptr.restore(path)
    return _payload_to_state(payload, spec, worker_state_shardings)


def _payload_to_state(
    payload, spec: StoreSpec, worker_state_shardings: Any = None
) -> Tuple[ShardedParamStore, Any, Dict[str, Any]]:
    """Re-place a restored payload onto the target spec (elastic)."""
    meta = payload.get("meta", {})
    # Rebuilt on the *target* spec by the spec itself, so nothing is dropped
    # in the round-trip (update rule, mesh, layout) and a group of stores
    # comes back through the same call (`StoreSpec.restored`).
    store = spec.restored(payload["table"], meta.get("capacity"))
    worker_state = payload.get("worker_state")
    if worker_state_shardings is not None and worker_state is not None:
        worker_state = jax.tree.map(
            lambda x, s: jax.device_put(np.asarray(x), s),
            worker_state,
            worker_state_shardings,
        )
    return store, worker_state, meta


class JobCheckpointManager:
    """Step-directory checkpoint manager for the StreamingDriver, backed
    by ``orbax.CheckpointManager``: atomic per-step commits (a crash mid
    -write can never destroy the previous durable checkpoint — unlike a
    single force-overwritten path), retention of the last ``max_to_keep``
    steps, and optional async writes (``save()`` snapshots device buffers
    to host — donation-safe — and the disk write overlaps training).
    """

    def __init__(
        self,
        directory: str,
        *,
        use_async: bool = False,
        max_to_keep: int = 2,
    ):
        ocp = _ocp()
        self._directory = os.path.abspath(directory)
        self._mgr = ocp.CheckpointManager(
            self._directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep,
                enable_async_checkpointing=use_async,
            ),
        )

    def save(
        self,
        step: int,
        store: ShardedParamStore,
        worker_state: Any = None,
        *,
        extra: Optional[Dict[str, Any]] = None,
        force: bool = False,
    ) -> bool:
        """Returns whether the save was accepted.  Duplicate steps are
        skipped by orbax unless ``force=True`` (the explicit-save path
        uses force so "save now" always lands).

        Donation safety: orbax's (a)sync save snapshots device buffers
        before returning (verified empirically — a jitted step may donate
        the buffers immediately after this call), and its per-shard
        serialization avoids a full host gather, so arrays pass straight
        through (multi-host-safe)."""
        import shutil

        ocp = _ocp()
        trash = None
        if force and step in self._mgr.all_steps():
            # orbax raises on duplicate steps.  Replace without a
            # durability gap: move the old step aside (atomic rename on
            # the same filesystem — a crash between here and the new
            # commit leaves the renamed copy on disk, never zero
            # checkpoints), then drop it only after the new save has
            # committed.
            self.wait()
            old_dir = os.path.join(self._directory, str(step))
            trash = os.path.join(self._directory, f".replacing.{step}")
            if os.path.isdir(old_dir):
                shutil.rmtree(trash, ignore_errors=True)
                os.rename(old_dir, trash)
                self._mgr.reload()
            else:  # non-default step-dir layout: fall back to delete
                trash = None
                self._mgr.delete(step)
        accepted = False
        committed = False
        try:
            accepted = bool(
                self._mgr.save(
                    step,
                    args=ocp.args.StandardSave(
                        _make_payload(store, worker_state, step, extra)
                    ),
                    # orbax's save-interval policy rejects steps <=
                    # latest; replacing a non-latest step must bypass it
                    force=force,
                )
            )
            if accepted and trash is not None:
                # Block until the replacement is durable (force saves are
                # rare explicit "save now" calls, so the wait is
                # acceptable even under async checkpointing — and an
                # async-write failure surfaces HERE, while the old copy
                # is still restorable, not after we pruned it).
                self.wait()
                committed = True
        finally:
            if trash is not None:
                if committed:
                    shutil.rmtree(trash, ignore_errors=True)
                else:
                    self._restore_replaced(step, trash)
        return accepted

    def _restore_replaced(self, step: int, trash: str) -> None:
        """Put a renamed-aside step back after a failed replacement.

        Runs in a ``finally`` — it must not raise (it would mask the
        original save error), and it must clear any partial new step dir
        that would make the rename fail with ENOTEMPTY.  If the restore
        itself fails, the old copy stays intact under ``trash`` and we
        warn with the path so it is recoverable by hand."""
        import shutil
        import warnings

        old_dir = os.path.join(self._directory, str(step))
        try:
            if os.path.exists(old_dir):
                # failed/uncommitted replacement remnants — remove so the
                # known-good copy can take the slot back
                shutil.rmtree(old_dir, ignore_errors=True)
            os.rename(trash, old_dir)
            self._mgr.reload()
        except OSError as e:  # pragma: no cover - disk-level failures
            warnings.warn(
                f"checkpoint step {step}: replacement failed and the "
                f"previous copy could not be moved back ({e}); it is "
                f"preserved at {trash}",
                RuntimeWarning,
            )

    def latest_step(self) -> Optional[int]:
        self.wait()
        return self._mgr.latest_step()

    def all_steps(self) -> list:
        """Durable (retained) checkpoint steps, ascending."""
        self.wait()
        return sorted(self._mgr.all_steps())

    def restore_latest(
        self, spec: StoreSpec, worker_state_shardings: Any = None
    ) -> Optional[Tuple[ShardedParamStore, Any, Dict[str, Any]]]:
        """Restore the newest RESTORABLE retained step.

        A corrupt/partial latest checkpoint (crash mid-write outside
        orbax's atomic-commit path, bit rot, a chaos test's garbling)
        must not kill the recovery it exists to serve: on a restore
        failure we warn and fall back to the next older retained step —
        losing one checkpoint interval beats losing the job (the WAL, if
        configured, still replays the difference).  Only when every
        retained step fails does the error propagate."""
        import warnings

        steps = self.all_steps()
        if not steps:
            return None
        last_exc: Optional[BaseException] = None
        for step in reversed(steps):
            try:
                # explicit StandardRestore: a FRESH manager (the resume
                # path — a new driver on an existing directory) has no
                # handler registered for the saved "default" item and
                # raises KeyError on an argless restore
                payload = self._mgr.restore(
                    step, args=_ocp().args.StandardRestore()
                )
                state = _payload_to_state(
                    payload, spec, worker_state_shardings
                )
            except BaseException as e:  # orbax raises a zoo of types
                # (ValueError, KeyError, FileNotFoundError, proto/zarr
                # decode errors) for a bad step dir — all mean the same
                # thing here: this step is not a usable recovery point
                last_exc = e
                warnings.warn(
                    f"checkpoint step {step} failed to restore "
                    f"({type(e).__name__}: {e}); falling back to the "
                    f"previous retained step",
                    RuntimeWarning,
                )
                continue
            return state
        raise RuntimeError(
            f"no retained checkpoint step under {self._directory!r} is "
            f"restorable (tried {list(reversed(steps))})"
        ) from last_exc

    def wait(self) -> None:
        self._mgr.wait_until_finished()

    def close(self) -> None:
        self.wait()
        self._mgr.close()


def load_model(path: str, **from_values_kwargs) -> ShardedParamStore:
    """The ``transformWithModelLoad`` analogue from a checkpoint:
    seed a fresh store from a saved table (SURVEY.md §2 #1).

    ``path`` may be a direct orbax checkpoint (written by :func:`save`) or
    a :class:`JobCheckpointManager` directory (the latest step is used)."""
    import warnings

    ocp = _ocp()
    path = os.path.abspath(path)
    with warnings.catch_warnings():
        # intentional: load to host, re-place via from_values below
        warnings.filterwarnings("ignore", message="Sharding info not provided")
        try:
            with ocp.PyTreeCheckpointer() as ckptr:
                payload = ckptr.restore(path)
        except (FileNotFoundError, ValueError):
            with ocp.CheckpointManager(path) as mgr:
                step = mgr.latest_step()
                if step is None:
                    raise FileNotFoundError(
                        f"no checkpoint under {path!r}"
                    ) from None
                # fresh manager: see restore_latest — an argless
                # restore has no handler for the saved item
                payload = mgr.restore(step, args=ocp.args.StandardRestore())
    values = np.asarray(payload["table"])[: payload["meta"]["capacity"]]
    return ShardedParamStore.from_values(
        jax.numpy.asarray(values), **from_values_kwargs
    )


__all__ = ["save", "restore", "load_model", "JobCheckpointManager"]
