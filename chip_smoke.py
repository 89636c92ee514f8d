#!/usr/bin/env python3
"""Does the system still start on the chip?  One process, a few minutes.

Drives the main path once through the entry points a user calls — online
matrix factorisation: ``OnlineMatrixFactorization`` +
``ShardedParamStore.create`` + ``StreamingDriver.run`` — at one fixed,
chip-sized shape (100,000 users x 131,072 items, dim 64,
bfloat16 table, batch 65,536, Zipf(1.2) items, seed 0), then compiles every
Pallas kernel the public API can reach and compares each with its XLA
reference.  With four or more devices stage 1 runs on a dp=2 x ps=2 mesh,
placement is asserted and a float32 leg compares the mesh with one device.

It measures nothing: set-up seconds and step milliseconds are printed so a
reader can see that programs compiled and ran, under no metric's name.

Exit codes: 0 every stage passed; 2 no TPU and no ``--cpu-dry-run``; 1 (a
traceback) a stage failed.  The last stdout line of a chip run is exactly
``{"ok": <bool>, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device as JAX reports it; the line before it is the fuller
summary (stages, wall seconds, ``"claim": null``).

``--cpu-dry-run`` is the only way to run it off the chip: every size
shrinks, kernels run with ``interpret=True``, and the last line is the
summary, which says the run proves control flow only — a dry run never
prints the chip run's result line.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import io
import itertools
import json
import os
import re
import shutil
import sys
import time
import traceback
import warnings

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
DRY_RUN_NOTE = "cpu dry run: proves control flow only, nothing about the chip"


class SmokeFailure(AssertionError):
    """A stage's check did not hold."""


def result_line(ok: bool, device: dict) -> str:
    """The chip run's last stdout line: these keys and no others."""
    return json.dumps({
        "ok": bool(ok),
        "device": {
            "platform": str(device["platform"]),
            "kind": str(device["kind"]),
            "count": int(device["count"]),
        },
    })


def require(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


@dataclasses.dataclass(frozen=True)
class Sizes:
    num_users: int
    num_items: int
    dim: int
    batch: int
    distinct_batches: int  # the stream cycles through this many
    single_steps: int  # dispatches at steps_per_call=1
    scan_k: int  # steps per scanned dispatch
    parity_steps: int
    kernel_lanes: int
    flash_seq: int


FULL = Sizes(
    num_users=100_000, num_items=131_072, dim=64, batch=65_536,
    distinct_batches=4, single_steps=20, scan_k=4, parity_steps=5,
    kernel_lanes=4096, flash_seq=512,
)
DRY = Sizes(
    num_users=512, num_items=1024, dim=64, batch=1024,
    distinct_batches=2, single_steps=16, scan_k=2, parity_steps=2,
    kernel_lanes=64, flash_seq=128,
)
# lr and init scale chosen so plain SGD neither stalls at the saddle nor
# diverges on the Zipf-hot items at batch 65,536 (item 0 takes ~18% of a
# batch, all summed from one snapshot)
LEARNING_RATE = 0.02
INIT_SCALE = 0.1
# the first scanned dispatch compiles the program, the second still
# compiles host-side slices of its stacked outputs; the third is steady
SCAN_DISPATCHES = 3


class Smoke:
    def __init__(self, dry_run: bool, out_dir: str):
        import jax

        self.jax = jax
        self.dry_run = dry_run
        self.sizes = DRY if dry_run else FULL
        self.out_dir = out_dir
        # kernels choose interpret mode themselves off the chip; the dry
        # run says so explicitly, the chip run takes the normal path
        self.interpret = True if dry_run else None
        devices = jax.devices()
        self.device = {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        }
        self.mesh = None
        if len(devices) >= 4:
            from flink_parameter_server_tpu.parallel.mesh import make_mesh

            self.mesh = make_mesh(
                worker_parallelism=2, ps_parallelism=2, devices=devices[:4]
            )
        self.stages = {}

    def report(self, stage: str, **fields) -> None:
        """One line per result, each naming the device it ran on."""
        self.stages[stage] = fields
        d = self.device
        body = " ".join(f"{k}={json.dumps(v)}" for k, v in fields.items())
        print(
            f"[{stage}] ok platform: {d['platform']} "
            f"device_kind: {d['kind']!r} devices: {d['count']} {body}",
            flush=True,
        )

    # -- stage 1: the main path -------------------------------------------
    def _mf_parts(self, dtype, mesh):
        from flink_parameter_server_tpu import ShardedParamStore
        from flink_parameter_server_tpu.models.matrix_factorization import (
            OnlineMatrixFactorization,
            SGDUpdater,
        )
        from flink_parameter_server_tpu.utils.initializers import normal_factor

        s = self.sizes
        logic = OnlineMatrixFactorization(
            s.num_users, s.dim, updater=SGDUpdater(LEARNING_RATE),
            dtype=dtype, mesh=mesh,
            init_low=-INIT_SCALE, init_high=INIT_SCALE,
        )
        # dim 64 is narrower than the row-write kernel takes: on one chip
        # the logic says so once (a "falling back" warning, an error here)
        # and keeps the XLA scatter.  Hear it now; main_path names the arm
        with warnings.catch_warnings(record=True) as heard:
            warnings.simplefilter("always")
            arm = logic.state_update_arm(
                self.jax.ShapeDtypeStruct((s.num_users, s.dim), dtype)
            )
        self.state_update = {
            "arm": arm, "refused": [str(w.message) for w in heard],
        }
        store = ShardedParamStore.create(
            s.num_items, (s.dim,), dtype=dtype, mesh=mesh,
            init_fn=normal_factor(
                1, (s.dim,), stddev=INIT_SCALE, dtype=dtype
            ),
        )
        return logic, store

    @functools.cached_property
    def _ratings(self):
        from flink_parameter_server_tpu.data.movielens import synthetic_ratings

        s = self.sizes
        return synthetic_ratings(
            s.num_users, s.num_items, s.distinct_batches * s.batch,
            zipf_a=1.2, seed=0,
        )

    def _stream(self, n_batches: int):
        """The first ``n_batches`` of one logical stream (re-fed from the
        start after a resume: the driver's cursor skips what it consumed).
        Under the mesh the user factors lie with keyed workers, and the
        stream is keyed HERE (the driver's own router then hands it on
        untouched), so that a dispatch is a batch of this stream whatever a
        router holds back: one epoch more goes in than comes out."""
        from flink_parameter_server_tpu.data.keyed import KeyedRouter
        from flink_parameter_server_tpu.data.streams import microbatches
        from flink_parameter_server_tpu.models.matrix_factorization import (
            worker_block_rows,
        )

        s = self.sizes
        epochs = -(-n_batches // s.distinct_batches)
        if self.mesh is None:
            stream = microbatches(self._ratings, s.batch, epochs=epochs)
        else:
            dp = self.mesh.shape["dp"]
            stream = KeyedRouter(
                dp, worker_block_rows(s.num_users, dp)
            ).route(microbatches(self._ratings, s.batch, epochs=epochs + 1))
        return itertools.islice(stream, n_batches)

    def _assert_placement(self, table, state, spec) -> dict:
        """Table rows split over ``ps`` on four devices, worker state over
        ``dp`` — not four whole copies."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self.mesh
        shards = table.addressable_shards
        devices = {sh.device for sh in shards}
        require(len(devices) == 4, f"table on {len(devices)} devices, not 4")
        rows = {sh.data.shape[0] for sh in shards}
        require(
            rows == {spec.rows_per_shard},
            f"table shards hold {rows} rows, expected "
            f"{{{spec.rows_per_shard}}} of {table.shape[0]}",
        )
        require(
            table.sharding.is_equivalent_to(
                NamedSharding(mesh, P("ps", None)), table.ndim
            ),
            f"table sharding {table.sharding}",
        )
        require(
            state.sharding.is_equivalent_to(
                NamedSharding(mesh, P("dp", None)), state.ndim
            ),
            f"worker state sharding {state.sharding}",
        )
        state_rows = {sh.data.shape[0] for sh in state.addressable_shards}
        require(
            state_rows == {state.shape[0] // 2},
            f"worker state shards hold {state_rows} rows",
        )
        return {
            "table_devices": sorted(str(d) for d in devices),
            "rows_per_shard": spec.rows_per_shard,
            "table_rows": table.shape[0],
            "state_rows_per_shard": state.shape[0] // 2,
        }

    def _hlo_collectives(self, logic, store, out_name: str) -> dict:
        """Read the compiled single step's HLO once: which collectives the
        GSPMD-partitioned pull/push became, and whether any all-gather
        rebuilds the whole table.  A finding, not a check."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from flink_parameter_server_tpu.core.transform import make_train_step

        jax, s = self.jax, self.sizes
        sh = NamedSharding(self.mesh, P("dp"))
        batch = {
            "user": jax.ShapeDtypeStruct((s.batch,), np.int32, sharding=sh),
            "item": jax.ShapeDtypeStruct((s.batch,), np.int32, sharding=sh),
            "rating": jax.ShapeDtypeStruct(
                (s.batch,), np.float32, sharding=sh
            ),
            "mask": jax.ShapeDtypeStruct((s.batch,), np.bool_, sharding=sh),
        }
        state = logic.init_state(jax.random.PRNGKey(0))
        text = (
            jax.jit(make_train_step(logic, store.spec), donate_argnums=(0, 1))
            .lower(store.table, state, batch)
            .compile()
            .as_text()
        )
        pattern = re.compile(
            r"= (?P<shape>.+?) (?P<op>all-gather|all-reduce|all-to-all|"
            r"reduce-scatter|collective-permute)(?:-start)?\("
        )
        ops = []
        for line in text.splitlines():
            m = pattern.search(line)
            if m:
                name = re.search(r'op_name="([^"]*)"', line)
                ops.append((
                    m["op"], m["shape"], name.group(1) if name else ""
                ))
        with open(os.path.join(self.out_dir, out_name), "w") as f:
            f.write(text)
        full = f"[{store.table.shape[0]},{store.table.shape[1]}]"
        shard = f"[{store.spec.rows_per_shard},{store.table.shape[1]}]"
        return {
            "collectives": sorted(
                {f"{op} {shape} <- {name}" for op, shape, name in ops}
            ),
            "all_gathers_full_table": any(
                op == "all-gather" and full in shape for op, shape, _ in ops
            ),
            # the push as GSPMD partitions it: every dp replica scatters
            # into its copy of the ps shard, then the copies are summed
            "all_reduces_table_shard": any(
                op == "all-reduce" and shard in shape and "scatter" in name
                for op, shape, name in ops
            ),
            "hlo_file": out_name,
        }

    def stage_main_path(self) -> None:
        import jax.numpy as jnp

        from flink_parameter_server_tpu import DriverConfig, StreamingDriver

        jax, s, mesh = self.jax, self.sizes, self.mesh
        dtype = jnp.bfloat16
        ckpt_dir = os.path.join(self.out_dir, "ckpt")
        shutil.rmtree(ckpt_dir, ignore_errors=True)  # this run's own scratch
        n_total = s.single_steps + SCAN_DISPATCHES * s.scan_k
        metric_sink = io.StringIO()

        def run(driver, stream):
            """driver.run with per-dispatch wall times from a group hook
            (outs are ready when it fires: metrics_every syncs dispatches)."""
            marks, out_sharding = [], []

            def hook(global_step, n_steps, table, state, outs):
                marks.append((time.perf_counter(), n_steps))
                out_sharding[:] = [outs["prediction"].sharding]

            driver.add_group_hook(hook)
            t0 = time.perf_counter()
            result = driver.run(stream, collect_outputs=True)
            times = [t for t, _ in marks]
            setup_s = times[0] - t0
            per_step_ms = [
                (b - a) * 1e3 / n
                for a, b, (_, n) in zip(times, times[1:], marks[1:])
            ]
            return result, marks, setup_s, per_step_ms, out_sharding[0]

        def rmse_per_step(result):
            outs = result.worker_outputs[:-1]  # last entry: finish() dump
            return [
                float(np.sqrt(np.mean(
                    np.square(np.asarray(o["error"], np.float32))
                )))
                for o in outs
            ]

        # ~twenty steps, one dispatch each, close-time checkpoint
        logic, store = self._mf_parts(dtype, mesh)
        table0 = np.asarray(store.values())
        first = StreamingDriver(
            logic, store,
            config=DriverConfig(
                checkpoint_dir=ckpt_dir, metrics_every=5, nan_check_every=5,
                steps_per_call=1,
            ),
            metrics_sink=metric_sink,
        )
        res1, marks1, setup1, ms1, pred_sharding = run(
            first, self._stream(s.single_steps)
        )
        require(
            [n for _, n in marks1] == [1] * s.single_steps,
            f"single-step dispatches: {[n for _, n in marks1]}",
        )
        require(first.step_idx == s.single_steps, f"step {first.step_idx}")
        table1 = np.asarray(first.store.table)
        state1 = np.asarray(res1.worker_state)
        require(
            np.isfinite(table1.astype(np.float32)).all(), "table not finite"
        )
        require(
            not np.array_equal(
                np.asarray(first.store.values()), table0
            ),
            "table unchanged by training",
        )
        placement = None
        if mesh is not None:
            placement = self._assert_placement(
                first.store.table, res1.worker_state, store.spec
            )
            from jax.sharding import NamedSharding, PartitionSpec as P

            require(
                pred_sharding.is_equivalent_to(
                    NamedSharding(mesh, P("dp")), 1
                ),
                f"per-record outputs not dp-sharded: {pred_sharding}",
            )

        # a fresh driver resumes: same step, bitwise-equal table and state
        logic2, store2 = self._mf_parts(dtype, mesh)
        second = StreamingDriver(
            logic2, store2,
            config=DriverConfig(
                checkpoint_dir=ckpt_dir, metrics_every=1, nan_check_every=1,
                steps_per_call=s.scan_k,
            ),
            metrics_sink=metric_sink,
        )
        require(second.resume(), "no checkpoint to resume from")
        require(
            second.step_idx == s.single_steps,
            f"resumed at step {second.step_idx}, saved {s.single_steps}",
        )
        require(
            np.asarray(second.store.table).tobytes() == table1.tobytes(),
            "resumed table differs bitwise",
        )
        require(
            np.asarray(second._state).tobytes() == state1.tobytes(),
            "resumed worker state differs bitwise",
        )
        if mesh is not None:
            self._assert_placement(
                second.store.table, second._state, store2.spec
            )

        # the same logical stream again: the cursor skips what was
        # consumed, then the scanned program — serving attached, so each
        # dispatch publishes a snapshot
        service = second.serve_with(publish_every=1)
        try:
            res2, marks2, setup2, ms2, _ = run(second, self._stream(n_total))
            require(
                [n for _, n in marks2] == [s.scan_k] * SCAN_DISPATCHES,
                f"scanned dispatches: {[n for _, n in marks2]}",
            )
            require(second.step_idx == n_total, f"step {second.step_idx}")
            if mesh is not None:
                self._assert_placement(
                    second.store.table, res2.worker_state, store2.spec
                )
            table2 = np.asarray(second.store.values()).astype(np.float32)
            users2 = np.asarray(res2.worker_state).astype(np.float32)
            require(np.isfinite(table2).all(), "table not finite after scan")
            require(
                not np.array_equal(
                    table2, table1[: s.num_items].astype(np.float32)
                ),
                "table unchanged by the scanned dispatches",
            )
            rmse = rmse_per_step(res1) + rmse_per_step(res2)
            require(len(rmse) == n_total, f"{len(rmse)} step outputs")
            head, tail = np.mean(rmse[:5]), np.mean(rmse[-5:])
            require(
                tail < 0.9 * head,
                f"training RMSE did not fall: first5 {head:.4f} "
                f"last5 {tail:.4f}",
            )

            # three top-K answers from the final snapshot
            client = service.client()
            k = 10
            answers = []
            for user in (0, 1, s.num_users - 1):
                ans = client.top_k(user, k=k, timeout=600.0)
                ids = np.asarray(ans.item_ids)
                scores = np.asarray(ans.scores, np.float32)
                require(ids.shape == (k,), f"top-k shape {ids.shape}")
                require(
                    ((ids >= 0) & (ids < s.num_items)).all()
                    and len(set(ids.tolist())) == k,
                    f"top-k ids out of range or repeated: {ids}",
                )
                require(np.isfinite(scores).all(), f"top-k scores {scores}")
                require(ans.train_step == n_total, f"answer from step "
                        f"{ans.train_step}")
                ref = np.sort(table2 @ users2[user])[::-1][:k]
                require(
                    np.allclose(scores, ref, rtol=2e-2, atol=2e-3),
                    f"top-k scores {scores} vs reference {ref}",
                )
                answers.append(ids.tolist())
        finally:
            service.stop()
        shutil.rmtree(ckpt_dir, ignore_errors=True)  # ~30 MB; checked above

        self.report(
            "main_path",
            users=s.num_users, items=s.num_items, dim=s.dim, batch=s.batch,
            table_dtype="bfloat16",
            mesh=None if mesh is None else dict(mesh.shape),
            single_step_setup_s=round(setup1, 3),
            single_step_ms=round(float(np.median(ms1)), 3),
            scan_k=s.scan_k,
            scan_setup_s=round(setup2, 3),
            scan_step_ms=round(ms2[-1], 3),
            steps=n_total, resumed_at=s.single_steps,
            rmse_first5=round(float(head), 4),
            rmse_last5=round(float(tail), 4),
            metric_lines=len(metric_sink.getvalue().splitlines()),
            topk=answers,
            placement=placement,
            state_update=self.state_update,
        )

    # -- stage 2: four chips ------------------------------------------------
    def stage_mesh(self) -> None:
        """float32: the same batches on one device and on the dp x ps
        mesh agree (compared as __graft_entry__.dryrun_multichip does);
        then the HLO finding."""
        import jax.numpy as jnp

        from flink_parameter_server_tpu import DriverConfig, StreamingDriver

        s = self.sizes

        # one device is given the mesh's keyed microbatches
        keyed = list(self._stream(s.parity_steps))

        def train(mesh):
            logic, store = self._mf_parts(jnp.float32, mesh)
            driver = StreamingDriver(
                logic, store, config=DriverConfig(nan_check_every=1)
            )
            res = driver.run(iter(keyed), collect_outputs=True)
            preds = np.stack([
                np.asarray(o["prediction"]) for o in res.worker_outputs[:-1]
            ])
            return (
                np.asarray(driver.store.values()),
                np.asarray(res.worker_state)[: s.num_users], preds,
            )

        t0 = time.perf_counter()
        got = train(self.mesh)
        want = train(None)
        for name, a, b in zip(("table", "worker state", "predictions"),
                              got, want):
            np.testing.assert_allclose(
                a, b, rtol=2e-4, atol=2e-5,
                err_msg=f"mesh {name} diverges from one device",
            )
        logic, store = self._mf_parts(jnp.bfloat16, self.mesh)
        hlo = self._hlo_collectives(logic, store, "mf_step_mesh.hlo.txt")
        self.report(
            "mesh_parity",
            mesh=dict(self.mesh.shape), steps=s.parity_steps,
            dtype="float32", rtol=2e-4, atol=2e-5,
            wall_s=round(time.perf_counter() - t0, 3), **hlo,
        )

    # -- stage 3: kernels ---------------------------------------------------
    def _kernel_case(
        self, name, fn, args, check, *, mosaic=True, stage="kernel",
    ) -> None:
        """Compile ``fn`` once, require a Mosaic call in its lowering (on
        the chip), run it, and hand the result to ``check``.  The row
        kernel's refusal counter must not move: a pass on the XLA scatter
        would prove nothing about the kernel."""
        from flink_parameter_server_tpu.ops.row_update import refusal_count

        jax = self.jax
        before = refusal_count()
        jitted = jax.jit(fn)
        t0 = time.perf_counter()
        if mosaic and not self.dry_run:
            require(
                "tpu_custom_call" in jitted.lower(*args).as_text(),
                f"{name}: no Mosaic call in the lowered module",
            )
        got = jax.block_until_ready(jitted(*args))
        setup_s = time.perf_counter() - t0
        err = check(got)
        require(
            refusal_count() == before,
            f"{name}: fell back to the XLA scatter",
        )
        self.report(
            f"{stage}:{name}", max_abs_err=float(f"{err:.3e}"),
            setup_s=round(setup_s, 3),
            mosaic=bool(mosaic and not self.dry_run),
        )

    @staticmethod
    def _close(want, tol):
        """check(got): max |got - want| over the pytree, required <= tol."""
        import jax

        def check(got):
            errs = jax.tree.leaves(jax.tree.map(
                lambda g, w: float(np.max(np.abs(
                    np.asarray(g, np.float32) - np.asarray(w, np.float32)
                ))),
                got, want,
            ))
            err = max(errs)
            require(err <= tol, f"max abs err {err:.3e} > {tol}")
            return err

        return check

    def stage_kernels(self) -> None:
        import jax.numpy as jnp

        from flink_parameter_server_tpu import ShardedParamStore
        from flink_parameter_server_tpu.models.transformer import (
            TransformerConfig,
            init_params,
            lm_loss,
        )
        from flink_parameter_server_tpu.core.transform import make_train_step
        from flink_parameter_server_tpu.models.matrix_factorization import (
            OnlineMatrixFactorization,
            SGDUpdater,
        )
        from flink_parameter_server_tpu.ops import dedup, row_update
        from flink_parameter_server_tpu.ops.flash_attention import (
            flash_mha,
            flash_mha_dp,
        )
        from flink_parameter_server_tpu.parallel.mesh import make_mesh
        from flink_parameter_server_tpu.parallel.ring_attention import (
            reference_attention,
        )

        jax, interpret, close = self.jax, self.interpret, self._close
        rng = np.random.default_rng(0)
        n = self.sizes.kernel_lanes

        def zipf_ids(cap):
            return jnp.asarray(rng.zipf(1.3, size=n) % cap, jnp.int32)

        def normal(shape, scale=1.0, dtype=jnp.float32):
            return jnp.asarray(rng.normal(size=shape) * scale, dtype)

        cap, d = 1024, 128
        table, deltas = normal((cap, d)), normal((n, d))

        # ops/row_update at the shape class of the MF cells' user state:
        # f32 rows of 128 lanes, a row count that no 8 divides, uniform
        # ids with a few planted duplicates (one run longer than a block)
        rows_u = 100_004 if not self.dry_run else 1_028
        table_u = normal((rows_u, d))
        ids_u = np.array(rng.integers(0, rows_u, n), np.int32)
        ids_u[: n // 8] = ids_u[0]
        ids_u[-3:] = rows_u - 1
        ids_u = jnp.asarray(rng.permutation(ids_u))
        self._kernel_case(
            "row_update_d128_f32_rows_mod8",
            lambda t, i, dl: row_update.row_add(
                t, i, jnp.take(t, i, axis=0), dl, interpret=interpret),
            (table_u, ids_u, deltas),
            close(table_u.at[ids_u].add(deltas), 1e-3),
        )

        # the same kernel under a mesh, as the keyed MF workers run it: every
        # chip's own block of the rows, its own lane block of ids (local to
        # the block), inside a shard_map; against np.add.at on the whole
        if self.mesh is not None:
            from jax.sharding import PartitionSpec as P

            w = len(jax.devices()) if not self.dry_run else 4
            workers = make_mesh(w, 1, devices=jax.devices()[:w])
            block = rows_u // w // 8 * 8
            ids_k = np.array(rng.integers(0, block, n // w * w), np.int32)
            ids_k[: n // 16] = ids_k[0]
            table_k, deltas_k = normal((w * block, d)), normal((len(ids_k), d))
            want_k = np.array(table_k)
            np.add.at(
                want_k,
                ids_k + np.repeat(np.arange(w) * block, len(ids_k) // w),
                np.asarray(deltas_k),
            )
            self._kernel_case(
                f"row_update_d128_f32_shard_map_dp{w}",
                jax.shard_map(
                    lambda t, i, dl: row_update.row_add(
                        t, i, jnp.take(t, i, axis=0), dl,
                        interpret=interpret),
                    mesh=workers,
                    in_specs=(P("dp", None), P("dp"), P("dp", None)),
                    out_specs=P("dp", None), check_vma=False,
                ),
                (table_k, jnp.asarray(ids_k), deltas_k), close(want_k, 1e-3),
            )

        # ops/row_update's tile kernel at the shape class of cell 5's push:
        # f32 rows of 640 lanes (five registers), Zipf ids with long runs;
        # it adds a row's lanes one by one as XLA does
        rows_w, w = (100_000 if not self.dry_run else 1_024), 640
        table_w, deltas_w = normal((rows_w, w)), normal((n, w))
        ids_w = zipf_ids(rows_w)
        self._kernel_case(
            "row_update_tiles_d640_f32",
            lambda t, i, dl: row_update.scatter_add(
                t, i, dl, interpret=interpret),
            (table_w, ids_w, deltas_w),
            close(table_w.at[ids_w].add(deltas_w), 1e-6),
        )

        # ... and handed rows narrower than the table's (cell 5's 600 lanes
        # of 640): the kernel adds into lanes [0, 600) and pads nothing
        self._kernel_case(
            "row_update_tiles_d640_w600_f32",
            lambda t, i, dl: row_update.scatter_add(
                t, i, dl, interpret=interpret),
            (table_w, ids_w, deltas_w[:, :600]),
            close(table_w.at[ids_w, :600].add(deltas_w[:, :600]), 1e-6),
        )

        # a rule's narrow rows at cell 6's row (three lanes held at four,
        # the table rows-minor): every touched tile of 128 rows read, set
        # and written back, the bits of XLA's row set
        rows_s = 128 * (4_000 if not self.dry_run else 8)
        ids_s = jnp.sort(jnp.asarray(
            rng.choice(rows_s, n - 40, replace=False), jnp.int32))
        ids_s = jnp.concatenate([ids_s, jnp.full((40,), rows_s, jnp.int32)])
        table_s = jnp.pad(normal((rows_s, 3)), ((0, 0), (0, 1)))
        new_s = normal((n, 3))
        self._kernel_case(
            "row_set_tiles_d3_f32",
            lambda t, i, r: row_update.sorted_tile_set(
                t, i, r, interpret=interpret)[0],
            (table_s, ids_s, new_s),
            close(table_s.at[ids_s].set(
                jnp.pad(new_s, ((0, 0), (0, 1))), mode="drop"), 0.0),
        )

        # a rule's wide rows at cell 9's width (36 lanes; Zipf ids with long
        # runs): permuted once at 128 lanes and summed run by run by the row
        # kernel, against the one scatter-add in stream order that every
        # other backend keeps (the same ids, other roundings)
        ids_c, rows_c = zipf_ids(rows_w), normal((n, 36))
        self._kernel_case(
            "combine_runs_d36_f32",
            lambda i, r: dedup.combine_runs(
                i, r, rows_w, "row_kernel", interpret=interpret)[:2],
            (ids_c, rows_c),
            close(dedup.combine_runs(
                ids_c, rows_c, rows_w, "scatter_add")[:2], 1e-3),
        )

        # the MF step's DEFAULT arm at that shape class, against the XLA
        # arm: table, state and both per-record outputs in stream order
        # (the dry run pins the arm: off the chip the default is XLA's)
        def mf_step(arm):
            logic = OnlineMatrixFactorization(
                rows_u, d, updater=SGDUpdater(0.05), state_scatter=arm,
                init_low=-INIT_SCALE, init_high=INIT_SCALE,
            )
            spec = ShardedParamStore.from_values(table).spec
            return logic, jax.jit(make_train_step(logic, spec))

        batch_u = {
            "user": ids_u, "item": zipf_ids(cap), "rating": normal((n,)),
            "mask": jnp.asarray(rng.random(n) > 0.05),
        }
        logic_x, step_x = mf_step("xla")
        logic_d, step_d = mf_step("sorted_rows" if self.dry_run else None)
        state_u = logic_x.init_state(jax.random.PRNGKey(0))
        arm_d = logic_d.state_update_arm(state_u)
        require(arm_d == "sorted_rows", f"default MF state update is {arm_d}")
        self._kernel_case(
            "mf_step_default_arm_d128_f32",
            step_d, (table, state_u, batch_u),
            close(step_x(table, state_u, batch_u), 1e-3),
        )

        # the packed store (ops/packed.py) at MF's dim 64, two rows to a
        # 128-lane row, through ShardedParamStore.push: XLA ops only
        def store_case(name, mesh):
            cap_s, dim_s = 4096, 64
            init = normal((cap_s, dim_s), 0.1)
            ids_s, deltas_s = zipf_ids(cap_s), normal((n, dim_s))
            store = ShardedParamStore.from_values(
                init, layout="packed", mesh=mesh
            )
            require(store.spec.layout == "packed", store.spec.layout)
            self._kernel_case(
                name,
                lambda t, i, dl: ShardedParamStore(store.spec, t)
                .push(i, dl).values(),
                (store.table, ids_s, deltas_s),
                close(init.at[ids_s].add(deltas_s), 1e-3), mosaic=False,
            )

        store_case("store_push_packed_d64", None)
        if self.mesh is not None:
            store_case("store_push_packed_d64_dp2xps2", self.mesh)

        # the packed pull's lane slice (ops/packed's one kernel) at FM's 17
        # lanes, seven rows to a physical row: whole blocks and a ragged
        # rest, against the select arm bit for bit
        from flink_parameter_server_tpu.ops import packed

        block_p = packed.SLICE_BLOCK if not self.dry_run else 256
        rows_p = normal((2 * block_p + 300, 128))
        ids_p = jnp.asarray(rng.integers(0, 10 ** 6, len(rows_p)), jnp.int32)
        self._kernel_case(
            "packed_lane_slice_d17_f32",
            lambda r, i: packed.sub_row_slice_kernel(
                r, i, 17, block=block_p, interpret=interpret),
            (rows_p, ids_p),
            close(packed._sub_row_slice(rows_p, ids_p, 17), 0.0),
        )

        # the same kernel at DiFacto's 36 lanes, three rows to a physical row
        # (windows of 36 sublanes that start at 0, 36, 72: no multiple of 8)
        self._kernel_case(
            "packed_lane_slice_k3_d36",
            lambda r, i: packed.sub_row_slice_kernel(
                r, i, 36, block=block_p, interpret=interpret),
            (rows_p, ids_p),
            close(packed._sub_row_slice(rows_p, ids_p, 36), 0.0),
        )

        # the packed push's lane shift, the slice's mirror: 17-lane rows fed
        # feature-major, a masked lane in every few, against the select arm
        # over deltas zeroed first, bit for bit
        deltas_p = rows_p[:, :17]
        mask_p = jnp.asarray(rng.random(len(rows_p)) < 0.9)
        self._kernel_case(
            "packed_lane_shift_d17_f32",
            lambda dl, i, m: packed.lane_shift_kernel(
                dl, i, 17, m, block=block_p, interpret=interpret),
            (deltas_p.T, ids_p, mask_p),
            close(packed.lane_shift_deltas(
                jnp.where(mask_p[:, None], deltas_p, 0), ids_p, 17), 0.0),
        )

        # both kernels a FIELD at a time (the FM family's step since PR 63,
        # DLRM's since PR 65): a key block of (B, K) gathered example-major
        # and sliced TURNED, (K, B, d); deltas read (d, K, B); against the
        # flat arms' bits.  39 fields of 17 lanes, seven rows to a physical
        # row, and 26 of 64, two to one (8 trips of 3 fields and 2 odd ones)
        for fields_p, d_p in ((39, 17), (26, 64)):
            batch_p = 2 * packed.TURN_BLOCK
            rows_f = normal((batch_p * fields_p, 128))
            ids_f = jnp.asarray(
                rng.integers(0, 10 ** 6, (batch_p, fields_p)), jnp.int32)
            self._kernel_case(
                f"packed_lane_slice_turned_d{d_p}_f32",
                lambda r, i, d_p=d_p: packed.turned_slice_kernel(
                    r, i, d_p, interpret=interpret),
                (rows_f, ids_f),
                close(jnp.swapaxes(packed._sub_row_slice(
                    rows_f, ids_f.reshape(-1), d_p
                ).reshape(batch_p, fields_p, d_p), 0, 1), 0.0),
            )
            deltas_f = normal((fields_p, batch_p, d_p))
            mask_f = jnp.asarray(rng.random((fields_p, batch_p)) < 0.9)
            self._kernel_case(
                f"packed_lane_shift_fielded_d{d_p}_f32",
                lambda dl, i, m, d_p=d_p: packed.lane_shift_kernel(
                    jnp.moveaxis(dl, -1, 0), i, d_p, m, interpret=interpret),
                (deltas_f, ids_f.T, mask_f),
                close(packed.lane_shift_deltas(
                    jnp.where(mask_f[..., None], deltas_f, 0).reshape(-1, d_p),
                    ids_f.T.reshape(-1), d_p), 0.0),
            )

        # a PACKED rule store at cell 9's row (36 lanes, three to a physical
        # row) inside one jitted step, pull and push: the lane slice, the
        # combine's row kernel and the write-back's row set on the chip,
        # against numpy on the host, bit for bit (small whole numbers and a
        # rule of halves and sums: exact in any order of a run's sum)
        from flink_parameter_server_tpu.core import store as store_mod

        def halve_and_add(current, combined):
            return 0.5 * current + combined

        cap_r = 30_000 if not self.dry_run else 300
        init_r = rng.integers(-8, 9, (cap_r, 36)).astype(np.float32) * 2
        init_r[8], init_r[7, ::2] = -0.0, np.inf  # untouched neighbours of 6
        ids_r = np.array(rng.zipf(1.3, size=n) % cap_r, np.int32)
        ids_r[np.isin(ids_r, (7, 8))] = 6
        ids_r[:3] = [6, 9, 10]  # row 6 alone in its physical row, 9-10 two
        deltas_r = rng.integers(-4, 5, (n, 36)).astype(np.float32)
        store_r = ShardedParamStore.from_values(
            jnp.asarray(init_r), update=halve_and_add, layout="auto")
        require(store_r.spec.layout == "packed" and store_r.spec.pack == 3,
                f"rule rows of 36 lanes lie {store_r.spec.layout}")
        want_r = init_r.copy()
        sums_r = np.zeros_like(init_r)
        np.add.at(sums_r, ids_r, deltas_r)
        hit_r = np.unique(ids_r)
        want_r[hit_r] = 0.5 * init_r[hit_r] + sums_r[hit_r]

        def rule_step(t, i, dl):
            pulled = store_mod.pull(store_r.spec, t, i)
            t, counted = store_mod.push_counted(store_r.spec, t, i, dl)
            return (ShardedParamStore(store_r.spec, t).values(), pulled,
                    counted["ps_rule_packed_rows"])

        def check_rule(got):
            values, pulled, wrote = (np.asarray(g) for g in got)
            require(values.tobytes() == want_r.tobytes(),
                    "packed rule push differs from numpy's bits")
            require(pulled.tobytes() == init_r[ids_r].tobytes(),
                    "packed rule pull differs from the rows")
            require(int(wrote) == len(np.unique(hit_r // 3)),
                    f"{int(wrote)} physical rows written")
            return 0.0

        self._kernel_case(
            "packed_rule_push_d36", rule_step,
            (store_r.table, jnp.asarray(ids_r), jnp.asarray(deltas_r)),
            check_rule, stage="store",
        )

        # splash flash attention: forward, gradient, and under shard_map
        B, T, H, D = 2, self.sizes.flash_seq, 4, 64
        q, k, v = (normal((B, T, H, D), 0.5, jnp.bfloat16) for _ in range(3))
        self._kernel_case(
            "flash_mha_fwd_bf16",
            lambda a, b, c: flash_mha(a, b, c, interpret=interpret),
            (q, k, v), close(reference_attention(q, k, v), 0.03),
        )

        def grad_of(fn):
            return jax.grad(
                lambda a, b, c: fn(a, b, c).astype(jnp.float32).sum(),
                argnums=(0, 1, 2),
            )

        self._kernel_case(
            "flash_mha_grad_bf16",
            grad_of(lambda a, b, c: flash_mha(a, b, c, interpret=interpret)),
            (q, k, v),
            close(jax.jit(grad_of(reference_attention))(q, k, v), 0.05),
        )
        n_dp = 2 if self.mesh is not None else 1
        dp_mesh = make_mesh(n_dp, 1, devices=jax.devices()[:n_dp])
        self._kernel_case(
            "flash_mha_dp_fwd_bf16",
            lambda a, b, c: flash_mha_dp(
                a, b, c, mesh=dp_mesh, interpret=interpret),
            (q, k, v), close(reference_attention(q, k, v), 0.03),
        )

        # the LM's normal path: flash_attention defaults to "auto", which
        # on the chip lands on the splash kernel
        def lm_cfg(flash):
            return TransformerConfig(
                vocab_size=512, d_model=256, n_heads=4, n_layers=1,
                d_ff=512, max_seq=T, flash_attention=flash,
            )

        params = init_params(jax.random.PRNGKey(0), lm_cfg("auto"))
        tokens = jnp.asarray(rng.integers(0, 512, (2, T)), jnp.int32)
        want_loss = jax.jit(
            lambda p, t: lm_loss(p, {"tokens": t}, lm_cfg("off"))
        )(params, tokens)
        self._kernel_case(
            "transformer_lm_loss_flash_auto",
            lambda p, t: lm_loss(p, {"tokens": t}, lm_cfg("auto")),
            (params, tokens), close(want_loss, 0.05),
        )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--cpu-dry-run", action="store_true",
        help="tiny sizes on the CPU, kernels interpreted: control flow only",
    )
    parser.add_argument(
        "--out", default=os.path.join(REPO, "chiprun_out", "chip_smoke"),
        help="output directory (checkpoint scratch, report, HLO text)",
    )
    args = parser.parse_args(argv)

    sys.path.insert(0, REPO)
    import jax

    if args.cpu_dry_run:
        jax.config.update("jax_platforms", "cpu")
    platform = jax.devices()[0].platform
    if platform != ("cpu" if args.cpu_dry_run else "tpu"):
        print(
            f"chip_smoke: platform is {platform!r}, not 'tpu'; nothing run "
            f"(--cpu-dry-run exercises the control flow off the chip)",
            file=sys.stderr,
        )
        return 2

    from flink_parameter_server_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    cache_dir = enable_compile_cache()
    # a kernel that quietly took another path is a failed stage
    warnings.filterwarnings("error", message=".*falling back.*")
    os.makedirs(args.out, exist_ok=True)
    t_start = time.perf_counter()
    smoke = Smoke(args.cpu_dry_run, args.out)
    d = smoke.device
    print(
        f"chip_smoke: platform: {d['platform']} device_kind: {d['kind']!r} "
        f"devices: {d['count']} jax {jax.__version__} "
        f"compile_cache: {cache_dir}",
        flush=True,
    )
    try:
        smoke.stage_main_path()
        if smoke.mesh is not None:
            smoke.stage_mesh()
        smoke.stage_kernels()
    except Exception:
        # a failed stage is the script's failure: say so and stop
        traceback.print_exc()
        if not args.cpu_dry_run:
            print(result_line(False, d), flush=True)
        return 1

    summary = {
        "ok": True,
        "device": d,
        "dry_run": DRY_RUN_NOTE if args.cpu_dry_run else False,
        "mesh": None if smoke.mesh is None else dict(smoke.mesh.shape),
        "stages": list(smoke.stages),
        "wall_s": round(time.perf_counter() - t_start, 1),
        "claim": None,
    }
    with open(os.path.join(args.out, "chip_smoke.json"), "w") as f:
        json.dump({**summary, "results": smoke.stages}, f, indent=1)
    print(json.dumps(summary), flush=True)
    if not args.cpu_dry_run:
        print(result_line(True, d), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
