"""MeshParamStore — the parameter table as ONE mesh-sharded array.

The paper's stated graft target, finally literal: "server-side
parameter shards live in TPU HBM as a pjit-sharded array …
``ps.pull(id)`` / ``ps.push(id, delta)`` become on-device gather /
scatter-add over ICI".  Where the socket backend fronts N
:class:`~..cluster.shard.ParamShard` slices with TCP servers, this
store holds the WHOLE table as a single
``jax.NamedSharding(mesh, P("shard"))`` global array and lowers the
batch surface to two jitted programs:

* **pull** — :func:`~..core.store.pull`: clip + sharded ``jnp.take``.
  XLA routes each id lane to the device block that owns its row (the
  collective gather); duplicate ids cost one routed row, so the host
  never dedupes.  The result stays on device — the worker's jitted
  step consumes it without a host copy.
* **push** — :func:`~..core.store.push`: masked dynamic scatter-add
  with the table buffer DONATED, so the update is in-place on device.
  Duplicate-id lanes combine inside the one scatter — the same
  single-sited-sum property :class:`~..workloads.base.
  DenseCombineLogic` pins for the socket path, which is what keeps
  exactly-once structural here: an in-process push either applies or
  raises; there is no retry path that could double-apply, so the
  socket backend's ``(pid, id)`` dedupe window has nothing to dedupe.

Durability lives at the HOST boundary (the only place bytes touch the
host in the push path): with ``wal_dir`` set, every push's raw
``(ids, deltas, mask)`` — exactly the device program's inputs — is
journaled to an :class:`~..resilience.wal.UpdateWAL` record BEFORE the
scatter dispatches.  Recovery replays the records through the same
jitted push, so a rebuilt table is bitwise the logged one
(:meth:`MeshParamStore.verify_against_log`, the mesh analogue of
:func:`~..replication.failover.verify_against_log`).

ZeRO-1 fold-in (arXiv 2004.13336 via :mod:`..core.dense`, evidence
``tests/test_zero1_memory.py``: 1/dp of replicated memory, counted in
bytes): with ``momentum > 0`` the store keeps a velocity buffer — the
optimizer state of its dense momentum update — created with
``zeros_like(table)`` (so it inherits the table's row-block sharding)
and pinned there every step via
:func:`~..core.dense.shard_opt_state_constraint`.  Each device holds
1/``n_devices`` of the optimizer state, never a replica; the constraint
makes that structural rather than conventional.  ``momentum=0`` (the
cluster driver's setting) is the plain scatter-add — bitwise the socket
backend's apply, which is what the BSP parity bar requires.
"""
from __future__ import annotations

import threading
import time
from typing import Optional, Sequence

import numpy as np

from .layout import SHARD_AXIS, check_alignment, make_store_mesh


class MeshParamStore:
    """One global device table + the host-boundary services around it.

    Thread-safe: one lock serializes device dispatch (pull, push,
    values) — donation makes the table buffer single-owner, so a pull
    must never race a push's donated reuse of the buffer it is
    reading.  Workers' SSP interleaving is the
    :class:`~..cluster.clock.StalenessClock`'s job, not this lock's.
    """

    def __init__(
        self,
        capacity: int,
        value_shape: Sequence[int] = (),
        *,
        init_fn=None,
        mesh=None,
        devices=None,
        partitioner=None,
        wal_dir: Optional[str] = None,
        wal_fsync_every: int = 0,
        momentum: float = 0.0,
        registry=None,
    ):
        import jax
        import jax.numpy as jnp

        from ..core.store import StoreSpec
        from ..core.store import pull as device_pull
        from ..core.store import push as device_push

        self.capacity = int(capacity)
        self.value_shape = tuple(int(s) for s in value_shape)
        self.mesh = mesh if mesh is not None else make_store_mesh(devices)
        if SHARD_AXIS not in self.mesh.axis_names:
            raise ValueError(
                f"mesh axes {self.mesh.axis_names} lack the store axis "
                f"{SHARD_AXIS!r} (build the mesh with make_store_mesh)"
            )
        self.n_devices = int(self.mesh.shape[SHARD_AXIS])
        if partitioner is not None:
            # the alignment rule is a precondition, not a convention:
            # misaligned shard boundaries straddle device blocks and
            # every pull pays a resharding gather
            check_alignment(partitioner, self.capacity, self.n_devices)
        self.partitioner = partitioner
        self.spec = StoreSpec(
            self.capacity, self.value_shape,
            mesh=self.mesh, ps_axis=SHARD_AXIS,
        )
        self.momentum = float(momentum)
        if self.momentum and wal_dir is not None:
            raise ValueError(
                "momentum>0 with a WAL is unsupported: the journal "
                "records plain scatter-add inputs, and replaying them "
                "through a momentum update would not rebuild the table "
                "(verify_against_log must stay bitwise)"
            )
        self._init_fn = init_fn
        self._lock = threading.RLock()
        self._push_seq = 0
        self.pulls_served = 0
        self.pushes_applied = 0
        self.rows_pulled = 0
        self.rows_applied = 0

        # jitted entry points, spec closed over (static); the push
        # donates the table so the scatter updates HBM in place
        self._pull_jit = jax.jit(
            lambda table, ids: device_pull(self.spec, table, ids)
        )
        self._push_jit = jax.jit(
            lambda table, ids, deltas, mask: device_push(
                self.spec, table, ids, deltas, mask
            ),
            donate_argnums=0,
        )
        if self.momentum:
            from ..core.dense import shard_opt_state_constraint

            mu = self.momentum

            def momentum_step(table, vel, ids, deltas, mask):
                dense = device_push(
                    self.spec, jnp.zeros_like(table), ids, deltas, mask
                )
                vel = mu * vel + dense
                # ZeRO-1: the optimizer state may never silently
                # replicate — each device keeps 1/n of it
                vel = shard_opt_state_constraint(
                    vel, self.mesh, dp_axis=SHARD_AXIS
                )
                return table + vel, vel

            self._momentum_jit = jax.jit(
                momentum_step, donate_argnums=(0, 1)
            )

        self.table = self._create_table()
        self.opt_state = (
            jnp.zeros_like(self.table) if self.momentum else None
        )

        self._wal = None
        if wal_dir is not None:
            from ..resilience.wal import UpdateWAL

            self._wal = UpdateWAL(wal_dir, fsync_every=wal_fsync_every)
            if self._wal.last_step_logged is not None:
                self._replay()

        self._register_instruments(registry)

    # -- construction / recovery ------------------------------------------
    def _create_table(self):
        """Materialise the padded global table under the mesh sharding.

        ``init_fn`` is the per-id deterministic init contract
        (:func:`~..core.store.create_table`); padding rows past
        ``capacity`` are zeroed so the init never sees an
        out-of-domain id — they are addressable but never routed."""
        import jax.numpy as jnp

        from ..core.store import create_table

        init_fn = self._init_fn
        capacity = self.capacity
        value_rank = len(self.value_shape)

        def padded_init(ids):
            if init_fn is None:
                return jnp.zeros(
                    ids.shape + self.value_shape, jnp.float32
                )
            rows = jnp.asarray(
                init_fn(jnp.minimum(ids, capacity - 1)), jnp.float32
            )
            live = (ids < capacity).reshape(
                ids.shape + (1,) * value_rank
            )
            return jnp.where(live, rows, jnp.zeros_like(rows))

        return create_table(self.spec, padded_init)

    def _apply(self, ids, deltas, mask) -> None:
        """One journaled-or-live record through the jitted scatter —
        construction replay and the live push share this seam, which
        is what makes the rebuilt table bitwise the logged one."""
        import jax.numpy as jnp

        ids_j = jnp.asarray(np.asarray(ids), jnp.int32)
        deltas_j = jnp.asarray(np.asarray(deltas, np.float32))
        mask_j = None if mask is None else jnp.asarray(np.asarray(mask))
        if self.momentum:
            self.table, self.opt_state = self._momentum_jit(
                self.table, self.opt_state, ids_j, deltas_j, mask_j
            )
        else:
            self.table = self._push_jit(
                self.table, ids_j, deltas_j, mask_j
            )
        self.table.block_until_ready()

    def _replay(self) -> int:
        """Recovery: re-apply every intact WAL record in sequence order
        through the same device scatter the live path uses."""
        n = 0
        for rec in self._wal.replay():
            p = rec.payload
            self._apply(p["ids"], p["deltas"], p.get("mask"))
            self._push_seq = max(self._push_seq, int(rec.end_step))
            self.pushes_applied += 1
            n += 1
        return n

    # -- the batch surface -------------------------------------------------
    def pull(self, ids) -> "np.ndarray":
        """Gather ``table[ids]`` (any leading shape; out-of-range ids
        clip — callers carry a validity mask).  Returns the DEVICE
        array: the worker's jitted step consumes it directly, so the
        inner loop never copies rows to the host."""
        import jax.numpy as jnp

        ids_np = np.asarray(ids)
        ids_j = jnp.asarray(ids_np, jnp.int32)
        with self._lock:
            t0 = time.perf_counter()
            out = self._pull_jit(self.table, ids_j)
            out.block_until_ready()
            dt = time.perf_counter() - t0
            self.pulls_served += 1
            self.rows_pulled += int(ids_np.size)
            if self._h_gather is not None:
                self._h_gather.observe(dt)
                self._c_pulls.inc()
                self._c_rows_pulled.inc(int(ids_np.size))
                self._c_gather_ops.inc()
        return out

    def push(self, ids, deltas, mask=None) -> int:
        """WRITE-AHEAD (when durable) then scatter-add; returns the
        push sequence number after this push.  ``ids``/``deltas``/
        ``mask`` are the raw device-program inputs — journaled as-is,
        so replay is bitwise (duplicate lanes recombine inside the
        same scatter)."""
        ids_np = np.asarray(ids)
        with self._lock:
            if self._wal is not None:
                payload = {
                    "ids": ids_np,
                    "deltas": np.asarray(deltas, np.float32),
                }
                if mask is not None:
                    payload["mask"] = np.asarray(mask)
                self._wal.append(self._push_seq, 1, payload)
                if self._c_wal is not None:
                    self._c_wal.inc()
            self._push_seq += 1
            t0 = time.perf_counter()
            self._apply(ids_np, deltas, mask)
            dt = time.perf_counter() - t0
            self.pushes_applied += 1
            rows = int(
                ids_np.size if mask is None
                else np.asarray(mask).astype(bool).sum()
            )
            self.rows_applied += rows
            if self._h_scatter is not None:
                self._h_scatter.observe(dt)
                self._c_pushes.inc()
                self._c_rows_pushed.inc(rows)
                self._c_scatter_ops.inc()
            return self._push_seq

    def values(self) -> np.ndarray:
        """The logical table (host copy) — rows ``[0, capacity)`` in
        global-id order; the dump/checkpoint surface, NOT the inner
        loop."""
        with self._lock:
            return np.asarray(self.table[: self.capacity])

    def flush(self) -> dict:
        """Make the journal durable (fsync) — the explicit durability
        point, outside the device lock (fpsanalyze B001: the WAL
        serializes its own appends/syncs)."""
        if self._wal is not None:
            self._wal.sync()
        return {"push_seq": self._push_seq, "durable": self._wal is not None}

    # -- audits ------------------------------------------------------------
    def verify_against_log(self) -> bool:
        """Rebuild deterministic-init + journal into a scratch table
        and compare bitwise with the live rows — the mesh analogue of
        :func:`~..replication.failover.verify_against_log`.  Safe under
        live traffic: ``(values, seq)`` are captured atomically and
        only records ``<= seq`` replay."""
        import jax.numpy as jnp

        if self._wal is None:
            raise ValueError("verify_against_log needs wal_dir")
        with self._lock:
            live = self.values()
            seq = self._push_seq
        self._wal.sync()
        scratch = self._create_table()
        for rec in self._wal.replay():
            if rec.end_step > seq:
                continue
            p = rec.payload
            ids_j = jnp.asarray(np.asarray(p["ids"]), jnp.int32)
            deltas_j = jnp.asarray(np.asarray(p["deltas"], np.float32))
            m = p.get("mask")
            mask_j = None if m is None else jnp.asarray(np.asarray(m))
            scratch = self._push_jit(scratch, ids_j, deltas_j, mask_j)
        rebuilt = np.asarray(scratch[: self.capacity])
        return bool(np.array_equal(rebuilt, live))

    # -- observability -----------------------------------------------------
    def _register_instruments(self, registry) -> None:
        if registry is False:
            self._h_gather = self._h_scatter = None
            self._c_pulls = self._c_pushes = self._c_wal = None
            self._c_rows_pulled = self._c_rows_pushed = None
            self._c_gather_ops = self._c_scatter_ops = None
            return
        from ..telemetry.registry import get_registry

        reg = registry if registry is not None else get_registry()
        self._h_gather = reg.histogram(
            "meshstore_gather_seconds", component="meshstore"
        )
        self._h_scatter = reg.histogram(
            "meshstore_scatter_seconds", component="meshstore"
        )
        self._c_pulls = reg.counter(
            "meshstore_pulls_total", component="meshstore"
        )
        self._c_pushes = reg.counter(
            "meshstore_pushes_total", component="meshstore"
        )
        self._c_rows_pulled = reg.counter(
            "meshstore_rows_pulled_total", component="meshstore"
        )
        self._c_rows_pushed = reg.counter(
            "meshstore_rows_pushed_total", component="meshstore"
        )
        self._c_wal = reg.counter(
            "meshstore_wal_appends_total", component="meshstore"
        )
        # per-round collective ledger: one routed gather / one routed
        # scatter per worker round (kind= keeps them on one instrument)
        self._c_gather_ops = reg.counter(
            "meshstore_collective_ops_total", component="meshstore",
            kind="gather",
        )
        self._c_scatter_ops = reg.counter(
            "meshstore_collective_ops_total", component="meshstore",
            kind="scatter",
        )
        reg.gauge(
            "meshstore_table_bytes", component="meshstore",
            fn=lambda: (
                int(self.table.nbytes) if self.table is not None else None
            ),
        )
        reg.gauge(
            "meshstore_device_bytes", component="meshstore",
            fn=self._bytes_per_device,
        )
        reg.gauge(
            "meshstore_opt_state_bytes", component="meshstore",
            fn=lambda: (
                int(self.opt_state.nbytes)
                if self.opt_state is not None else 0
            ),
        )

    def _bytes_per_device(self) -> Optional[int]:
        """Largest per-device resident slice of the table (+ optimizer
        state): the HBM figure capacity planning reads.  With the
        row-block layout this is ``nbytes / n_devices`` — the gauge
        measures it from the placed buffers rather than asserting it."""
        if self.table is None:
            return None
        per = {}
        for s in self.table.addressable_shards:
            per[s.device] = per.get(s.device, 0) + s.data.nbytes
        if self.opt_state is not None:
            for s in self.opt_state.addressable_shards:
                per[s.device] = per.get(s.device, 0) + s.data.nbytes
        return max(per.values()) if per else None

    def stats(self) -> dict:
        with self._lock:
            return {
                "backend": "mesh",
                "devices": self.n_devices,
                "rows": self.capacity,
                "padded_rows": int(self.spec.padded_capacity),
                "row_block": int(self.spec.rows_per_shard),
                "pulls": self.pulls_served,
                "pushes": self.pushes_applied,
                "push_seq": self._push_seq,
                "rows_pulled": self.rows_pulled,
                "rows_applied": self.rows_applied,
                "wal_records": (
                    0 if self._wal is None
                    else self._wal.records_appended
                ),
                "table_bytes": int(self.table.nbytes),
                "bytes_per_device": self._bytes_per_device(),
                "opt_state_bytes": (
                    int(self.opt_state.nbytes)
                    if self.opt_state is not None else 0
                ),
                "momentum": self.momentum,
                "alive": self.table is not None,
            }

    def close(self) -> None:
        if self._wal is not None:
            self._wal.close()
            self._wal = None
        self.table = None
        self.opt_state = None


__all__ = ["MeshParamStore"]
