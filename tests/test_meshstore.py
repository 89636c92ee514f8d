"""meshstore/ — the device-mesh store backend (docs/meshstore.md).

What is pinned here, and why it is the right bar:

  * **layout algebra** — ``mesh_row_block`` / ``block_aligned`` keep
    every shard boundary on a device row-block multiple (property-
    tested: totality + disjointness survive the rounding), and
    ``check_alignment`` makes misalignment LOUD instead of a silent
    per-pull resharding gather;
  * **store oracle** — pull is ``table[ids]``, push is ``np.add.at``
    with duplicates combined in ONE scatter (integer-valued fp32
    deltas make the check exact regardless of combine order);
  * **durability at the host boundary** — the WAL journals the raw
    device-program inputs, so crash-recovery and the live audit
    (``verify_against_log``) are BITWISE, exactly the replication
    plane's bar;
  * **driver parity through ``store_backend="mesh"``** — the same
    envelope the socket backend pins: PA bitwise at one worker
    (including the fusion-sensitive shape), MF allclose at two,
    sketch integer-exact at two;
  * **SSP/async/BSP on the mesh path** — the StalenessClock is store-
    independent and the mesh run must prove it: held worker plateaus
    at the bound with the staleness gauge live on /metrics, async
    never blocks, BSP barriers;
  * **ZeRO-1 fold-in** — optimizer state is sharded (per-device bytes
    = (table + opt state) / n_devices) and the momentum update
    matches a numpy oracle exactly on integer-valued inputs;
  * **tooling** — meshstore instruments lint as a known component.
"""
import threading
import time

import numpy as np
import pytest

from flink_parameter_server_tpu.cluster.driver import (
    ClusterConfig,
    ClusterDriver,
)
from flink_parameter_server_tpu.cluster.partition import (
    ConsistentHashPartitioner,
    RangePartitioner,
    mesh_row_block,
)
from flink_parameter_server_tpu.meshstore import (
    MeshClient,
    MeshParamStore,
    MisalignedTable,
    aligned_partitioner,
    check_alignment,
)
from flink_parameter_server_tpu.telemetry.registry import MetricsRegistry
from flink_parameter_server_tpu.workloads import (
    WorkloadParams,
    build_cluster_driver,
    create_workload,
)

pytestmark = pytest.mark.meshstore

SMALL = WorkloadParams(
    rounds=6, batch=48, num_users=24, num_items=32, dim=4, seed=3
)


def _mesh_config(**kw):
    kw.setdefault("num_shards", 2)
    kw.setdefault("num_workers", 1)
    kw.setdefault("staleness_bound", 0)
    return ClusterConfig(store_backend="mesh", **kw)


# ---------------------------------------------------------------------------
# layout: the alignment rule
# ---------------------------------------------------------------------------


class TestLayout:
    def test_mesh_row_block_matches_store_spec(self, mesh_devices):
        """The block the partitioner aligns to IS the rows-per-device
        split the device table actually uses — one arithmetic, pinned
        against the live StoreSpec rather than re-derived."""
        from flink_parameter_server_tpu.core.store import StoreSpec
        from flink_parameter_server_tpu.meshstore.layout import (
            SHARD_AXIS,
            make_store_mesh,
        )

        mesh = make_store_mesh()
        n = len(mesh_devices)
        for capacity in (8, 97, 256, 1000):
            spec = StoreSpec(capacity, (), mesh=mesh, ps_axis=SHARD_AXIS)
            assert mesh_row_block(capacity, n) == spec.rows_per_shard

    def test_block_aligned_property(self):
        """block_aligned keeps the map total and disjoint while every
        boundary lands on a row-block multiple (satellite 6)."""
        pytest.importorskip("hypothesis")
        from hypothesis import given, settings, strategies as st

        @settings(max_examples=60, deadline=None)
        @given(
            capacity=st.integers(1, 4096),
            num_shards=st.integers(1, 16),
            n_devices=st.integers(1, 16),
        )
        def check(capacity, num_shards, n_devices):
            if num_shards > capacity:
                num_shards = capacity
            part = RangePartitioner(capacity, num_shards)
            aligned = part.block_aligned(n_devices)
            block = mesh_row_block(capacity, n_devices)
            assert aligned.aligned_block == block
            assert aligned.rows_per_shard % block == 0
            assert aligned.rows_per_shard >= part.rows_per_shard
            # the padded extent stays whole row-blocks (no extra
            # padding needed when the store builds over this map)
            assert (aligned.rows_per_shard * num_shards) % block == 0
            # total + disjoint: every id owned exactly once
            owned = [aligned.owned_ids(s) for s in range(num_shards)]
            allids = np.concatenate(owned) if owned else np.array([])
            assert len(allids) == capacity
            assert np.array_equal(np.sort(allids), np.arange(capacity))
            # shard_of agrees with ownership
            for s, ids in enumerate(owned):
                if len(ids):
                    assert (aligned.shard_of(ids) == s).all()
            check_alignment(aligned, capacity, n_devices)

        check()

    def test_block_aligned_grid_sweep(self):
        """The same invariants over a deterministic grid — runs even
        where hypothesis is absent (the image's tier-1 floor)."""
        for capacity in (1, 7, 8, 9, 100, 255, 256, 1000):
            for num_shards in (1, 2, 3, 5, 8):
                if num_shards > capacity:
                    continue
                for n_devices in (1, 2, 7, 8, 16):
                    part = RangePartitioner(capacity, num_shards)
                    aligned = part.block_aligned(n_devices)
                    block = mesh_row_block(capacity, n_devices)
                    assert aligned.rows_per_shard % block == 0
                    assert aligned.rows_per_shard >= part.rows_per_shard
                    owned = [
                        aligned.owned_ids(s) for s in range(num_shards)
                    ]
                    allids = np.concatenate(owned)
                    assert np.array_equal(
                        np.sort(allids), np.arange(capacity)
                    )
                    check_alignment(aligned, capacity, n_devices)

    def test_check_alignment_rejects_misaligned_range(self):
        # 100 rows over 8 devices: block = ceil(ceil(100/8)/8)*8 = 16;
        # a 3-shard split (34 rows) straddles device blocks
        part = RangePartitioner(100, 3)
        assert part.rows_per_shard % mesh_row_block(100, 8) != 0
        with pytest.raises(MisalignedTable, match="block_aligned"):
            check_alignment(part, 100, 8)
        check_alignment(part.block_aligned(8), 100, 8)

    def test_check_alignment_rejects_hash_maps(self):
        with pytest.raises(MisalignedTable, match="RangePartitioner"):
            check_alignment(ConsistentHashPartitioner(64, 4), 64, 8)

    def test_aligned_partitioner_helper(self):
        part = aligned_partitioner(100, 3, 8)
        assert part.rows_per_shard % mesh_row_block(100, 8) == 0
        check_alignment(part, 100, 8)


# ---------------------------------------------------------------------------
# the store: gather/scatter oracle, durability, ZeRO-1
# ---------------------------------------------------------------------------


def _int_deltas(rng, shape):
    """Integer-valued fp32: adds are exact, so the device scatter's
    combine order cannot blur the oracle comparison."""
    return rng.integers(-8, 9, shape).astype(np.float32)


class TestMeshParamStore:
    def test_pull_push_matches_numpy_oracle(self, mesh_devices, rng):
        store = MeshParamStore(100, (4,), registry=False)
        want = np.zeros((100, 4), np.float32)
        for _ in range(5):
            ids = rng.integers(0, 100, 64)  # duplicates likely
            deltas = _int_deltas(rng, (64, 4))
            mask = rng.random(64) < 0.8
            store.push(ids, deltas, mask)
            np.add.at(want, ids[mask], deltas[mask])
        assert np.array_equal(store.values(), want)
        probe = rng.integers(0, 100, 32)
        assert np.array_equal(np.asarray(store.pull(probe)), want[probe])
        store.close()

    def test_pull_returns_device_array_sharded_over_mesh(
        self, mesh_devices
    ):
        """The no-host-copy contract: pull's result is a jax array (the
        worker's jitted step consumes it directly), and the table
        itself is genuinely split over all the devices."""
        import jax

        store = MeshParamStore(128, (2,), registry=False)
        out = store.pull(np.arange(16))
        assert isinstance(out, jax.Array)
        assert {
            s.device for s in store.table.addressable_shards
        } == set(mesh_devices)
        store.close()

    def test_push_without_mask_and_clip(self, mesh_devices, rng):
        store = MeshParamStore(32, (), registry=False)
        ids = np.array([0, 5, 5, 31])
        deltas = np.array([1.0, 2.0, 3.0, 4.0], np.float32)
        store.push(ids, deltas)
        want = np.zeros(32, np.float32)
        np.add.at(want, ids, deltas)
        assert np.array_equal(store.values(), want)
        store.close()

    def test_wal_recovery_is_bitwise(self, mesh_devices, rng, tmp_path):
        wal = str(tmp_path / "wal")
        store = MeshParamStore(64, (3,), wal_dir=wal, registry=False)
        for _ in range(4):
            ids = rng.integers(0, 64, 48)
            store.push(ids, rng.normal(0, 1, (48, 3)).astype(np.float32),
                       rng.random(48) < 0.9)
        live = store.values()
        seq = store._push_seq
        store.close()
        # crash-recover: a fresh store over the same journal replays
        # the raw device-program inputs through the same jitted scatter
        again = MeshParamStore(64, (3,), wal_dir=wal, registry=False)
        assert again._push_seq == seq
        assert np.array_equal(again.values(), live)
        again.close()

    def test_verify_against_log(self, mesh_devices, rng, tmp_path):
        store = MeshParamStore(
            64, (), wal_dir=str(tmp_path / "wal"), registry=False
        )
        for _ in range(3):
            store.push(rng.integers(0, 64, 32),
                       rng.normal(0, 1, 32).astype(np.float32))
        assert store.verify_against_log()
        # an unjournaled write is exactly what the audit must catch
        store._apply(np.array([1]), np.array([5.0], np.float32), None)
        assert not store.verify_against_log()
        store.close()

    def test_momentum_with_wal_is_rejected(self, mesh_devices, tmp_path):
        with pytest.raises(ValueError, match="momentum"):
            MeshParamStore(
                64, (), momentum=0.9, wal_dir=str(tmp_path / "w"),
                registry=False,
            )

    def test_zero1_opt_state_is_sharded_not_replicated(
        self, mesh_devices, rng
    ):
        """The ZeRO-1 bar (tests/test_zero1_memory.py): per-device
        bytes = (table + optimizer state) / n_devices — each device
        holds 1/n of the velocity buffer, never a replica."""
        store = MeshParamStore(256, (4,), momentum=0.5, registry=False)
        store.push(rng.integers(0, 256, 64),
                   _int_deltas(rng, (64, 4)))
        s = store.stats()
        n = s["devices"]
        assert s["opt_state_bytes"] == s["table_bytes"]
        assert s["bytes_per_device"] * n == (
            s["table_bytes"] + s["opt_state_bytes"]
        )
        store.close()
        # momentum=0 (the driver's setting): no optimizer state at all
        plain = MeshParamStore(256, (4,), registry=False)
        sp = plain.stats()
        assert sp["opt_state_bytes"] == 0
        assert sp["bytes_per_device"] * n == sp["table_bytes"]
        plain.close()

    def test_momentum_update_matches_numpy_oracle(
        self, mesh_devices, rng
    ):
        """The sharding constraint must not change the arithmetic:
        vel = mu*vel + dense; table += vel — exact on integer-valued
        fp32 inputs with mu=0.5 (halves are exact in fp32)."""
        store = MeshParamStore(40, (2,), momentum=0.5, registry=False)
        table = np.zeros((40, 2), np.float32)
        vel = np.zeros((40, 2), np.float32)
        for _ in range(3):
            ids = rng.integers(0, 40, 24)
            deltas = _int_deltas(rng, (24, 2))
            store.push(ids, deltas)
            dense = np.zeros((40, 2), np.float32)
            np.add.at(dense, ids, deltas)
            vel = 0.5 * vel + dense
            table = table + vel
        assert np.array_equal(store.values(), table)
        store.close()

    def test_misaligned_partitioner_rejected_at_construction(
        self, mesh_devices
    ):
        with pytest.raises(MisalignedTable):
            MeshParamStore(
                100, (), partitioner=RangePartitioner(100, 3),
                registry=False,
            )


# ---------------------------------------------------------------------------
# the client: ClusterClient batch surface + the event ABC
# ---------------------------------------------------------------------------


class TestMeshClient:
    def test_batch_surface_and_counters(self, mesh_devices, rng):
        store = MeshParamStore(64, (), registry=False)
        client = MeshClient(store, worker="0")
        ids = np.array([1, 1, 2, 9])
        deltas = np.array([1.0, 1.0, 2.0, 3.0], np.float32)
        mask = np.array([True, True, True, False])
        assert client.push_batch(ids, deltas, mask) == 3
        assert client.rows_pushed == 3
        got = np.asarray(client.pull_batch(np.array([1, 2, 9])))
        assert np.array_equal(got, np.array([2.0, 2.0, 0.0], np.float32))
        # structurally wire-free: nothing ever retries or caches
        assert client.frames_retried == 0
        assert client.hotcache is None
        assert client.shard_stats()[0]["backend"] == "mesh"
        store.close()

    def test_event_api_drain(self, mesh_devices):
        store = MeshParamStore(16, (), registry=False)
        client = MeshClient(store)
        client.push(3, 2.0)
        client.push(3, 1.0)
        client.pull(3)
        got = {}
        n = client.drain(
            on_pull_recv=lambda pid, v, c: got.__setitem__(pid, float(v))
        )
        assert n == 1 and got == {3: 3.0}
        store.close()


# ---------------------------------------------------------------------------
# the driver: parity + consistency semantics through store_backend="mesh"
# ---------------------------------------------------------------------------


def _streaming_mf_oracle(mf):
    from flink_parameter_server_tpu.core.store import ShardedParamStore
    from flink_parameter_server_tpu.training.driver import (
        DriverConfig,
        StreamingDriver,
    )

    store = ShardedParamStore.create(
        mf.capacity, mf.value_shape, init_fn=mf.init_fn()
    )
    driver = StreamingDriver(
        mf.make_logic(), store, config=DriverConfig(dump_model=False)
    )
    res = driver.run(iter(mf.batches()), collect_outputs=False)
    return np.asarray(res.store.values())


class TestMeshDriverParity:
    def test_pa_bsp_bitwise_vs_streaming_oracle(self, mesh_devices):
        """The PA bitwise bar, same envelope the socket backend pins
        (one worker: one fp32 add per id per round on both arms)."""
        pa = create_workload("pa", SMALL)
        oracle = pa.oracle_values()
        driver = build_cluster_driver(
            pa, config=_mesh_config(), registry=False
        )
        with driver:
            result = driver.run(pa.batches())
        assert np.array_equal(result.values, oracle), (
            "mesh-backend BSP PA table is not bitwise the streaming "
            "oracle"
        )
        v = pa.parity_verdict(result.values, oracle)
        assert v.ok and "bitwise" in v.detail
        assert result.shard_stats[0]["backend"] == "mesh"
        assert result.shard_stats[0]["pushes"] > 0

    def test_pa_bitwise_at_the_fusion_sensitive_shape(self, mesh_devices):
        p = WorkloadParams(rounds=10, batch=64, num_items=48, seed=0)
        pa = create_workload("pa", p)
        driver = build_cluster_driver(
            pa, config=_mesh_config(), registry=False
        )
        with driver:
            result = driver.run(pa.batches())
        assert np.array_equal(result.values, pa.oracle_values())

    def test_mf_bsp_parity_two_workers(self, mesh_devices):
        """MF's parity mode is allclose (fp32 two-worker interleaving
        reassociates sums on EVERY backend — the socket test
        test_bsp_parity_4_shards_2_workers pins the same bar)."""
        mf = create_workload("mf", SMALL)
        base = _streaming_mf_oracle(mf)
        driver = build_cluster_driver(
            mf, config=_mesh_config(num_workers=2), registry=False
        )
        with driver:
            result = driver.run(mf.batches())
        np.testing.assert_allclose(result.values, base,
                                   rtol=1e-4, atol=1e-6)
        assert result.clock["staleness"] == 0
        assert result.clock["clocks"] == [len(mf.batches())] * 2

    def test_sketch_integer_exact_two_workers(self, mesh_devices):
        """Counts are integers and integer adds commute: two
        interleaving workers through the mesh scatter must still land
        the exact bincount — NO tolerance."""
        sk = create_workload("sketch", SMALL)
        driver = build_cluster_driver(
            sk, config=_mesh_config(num_workers=2), registry=False
        )
        with driver:
            result = driver.run(sk.batches())
        oracle = sk.oracle_values()
        assert np.array_equal(result.values, oracle)
        v = sk.parity_verdict(result.values, oracle)
        assert v.ok, v.detail

    def test_final_values_is_host_ndarray(self, mesh_devices):
        pa = create_workload("pa", SMALL)
        driver = build_cluster_driver(
            pa, config=_mesh_config(), registry=False
        )
        with driver:
            driver.run(pa.batches())
            vals = driver.final_values()
        assert type(vals) is np.ndarray
        assert vals.shape == (pa.capacity,)

    def test_wal_dir_flows_to_mesh_store(self, mesh_devices, tmp_path):
        pa = create_workload("pa", SMALL)
        driver = build_cluster_driver(
            pa, config=_mesh_config(wal_dir=str(tmp_path)),
            registry=False,
        )
        with driver:
            driver.run(pa.batches())
            assert driver.mesh_store.verify_against_log()
            assert driver.mesh_store.stats()["wal_records"] > 0


class TestMeshStalenessSemantics:
    def test_ssp_bound_enforced_and_staleness_scrapeable(
        self, mesh_devices
    ):
        """Mirror of the socket SSP acceptance: worker 0 held at its
        round-1 gate, worker 1 plateaus at clock0 + bound + 1 and the
        staleness gauge is live on /metrics MID-RUN — the clock is
        store-independent and the mesh path must not bypass it."""
        from flink_parameter_server_tpu.telemetry import (
            TelemetryServer,
            scrape,
        )

        bound = 2
        mf = create_workload(
            "mf",
            WorkloadParams(rounds=10, batch=48, num_users=24,
                           num_items=32, dim=4, seed=3),
        )
        reg = MetricsRegistry()
        driver = build_cluster_driver(
            mf,
            config=_mesh_config(num_workers=2, staleness_bound=bound),
            registry=reg,
        )
        release = threading.Event()

        def hold_worker_0(worker, rnd):
            if worker == 0 and rnd == 1:
                assert release.wait(60), "test hung: release never set"

        result = {}
        errors = []

        def run():
            try:
                with driver:
                    result["r"] = driver.run(
                        mf.batches(), round_hook=hold_worker_0
                    )
            except BaseException as e:  # pragma: no cover
                errors.append(e)
                release.set()

        t = threading.Thread(target=run, daemon=True)
        t.start()
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            clocks = driver.clock.clocks() if driver.clock else [0, 0]
            if clocks[1] >= 1 + bound + 1 and driver.clock.block_counts[1]:
                break
            time.sleep(0.005)
        assert not errors, errors
        clocks = driver.clock.clocks()
        assert clocks[0] == 1
        assert clocks[1] == 1 + bound + 1
        assert driver.clock.staleness() == bound + 1
        with TelemetryServer(reg) as srv:
            body = scrape(srv.host, srv.port, "metrics")
        line = [
            ln for ln in body.splitlines()
            if ln.startswith("fps_cluster_staleness_steps")
        ]
        assert line and line[0].split()[-1] == str(bound + 1), line
        time.sleep(0.05)
        assert driver.clock.clocks()[1] == 1 + bound + 1
        release.set()
        t.join(timeout=120)
        assert not errors, errors
        r = result["r"]
        assert r.clock["clocks"] == [len(mf.batches())] * 2
        assert r.clock["block_counts"][1] >= 1

    def test_async_mode_never_blocks(self, mesh_devices):
        mf = create_workload("mf", SMALL)
        driver = build_cluster_driver(
            mf,
            config=_mesh_config(num_workers=2, staleness_bound=None),
            registry=False,
        )
        with driver:
            r = driver.run(mf.batches())
        assert r.clock["block_counts"] == [0, 0]
        assert r.clock["clocks"] == [len(mf.batches())] * 2
        assert np.isfinite(r.values).all()


# ---------------------------------------------------------------------------
# guards: the carve-outs that keep the contracts honest
# ---------------------------------------------------------------------------


class TestMeshConfigGuards:
    def _pa_driver(self, config):
        pa = create_workload("pa", SMALL)
        return build_cluster_driver(pa, config=config, registry=False)

    def test_unknown_backend_is_loud(self):
        with pytest.raises(ValueError, match="store_backend"):
            self._pa_driver(ClusterConfig(store_backend="rdma"))

    def test_elastic_driver_rejects_mesh(self):
        from flink_parameter_server_tpu.elastic.controller import (
            ElasticClusterDriver,
        )

        pa = create_workload("pa", SMALL)
        with pytest.raises(NotImplementedError, match="mesh"):
            build_cluster_driver(
                pa, config=_mesh_config(),
                driver_cls=ElasticClusterDriver, registry=False,
            )

    def test_shard_procs_rejected(self):
        with pytest.raises(ValueError, match="shard_procs"):
            self._pa_driver(_mesh_config(shard_procs=True))

    def test_hot_cache_rejected(self):
        with pytest.raises(ValueError, match="hot_cache"):
            self._pa_driver(_mesh_config(hot_cache=True))

    def test_hash_partition_rejected(self):
        with pytest.raises(ValueError, match="range"):
            self._pa_driver(_mesh_config(partition="hash"))


# ---------------------------------------------------------------------------
# telemetry + artifact lint (the tools satellites)
# ---------------------------------------------------------------------------


class TestMeshTelemetry:
    def test_instruments_land_and_lint(self, mesh_devices):
        import tools.check_metric_lines as lint

        pa = create_workload("pa", SMALL)
        reg = MetricsRegistry()
        driver = build_cluster_driver(
            pa, config=_mesh_config(), registry=reg
        )
        with driver:
            driver.run(pa.batches())
        by_name = {}
        for inst in reg.instruments():
            if inst.labels.get("component") == "meshstore":
                by_name.setdefault(inst.name, []).append(inst)
        for name in (
            "meshstore_gather_seconds",
            "meshstore_scatter_seconds",
            "meshstore_pulls_total",
            "meshstore_pushes_total",
            "meshstore_rows_pulled_total",
            "meshstore_rows_pushed_total",
            "meshstore_collective_ops_total",
            "meshstore_table_bytes",
            "meshstore_device_bytes",
            "meshstore_opt_state_bytes",
        ):
            assert name in by_name, f"missing {name}"
        # one routed gather + one routed scatter per worker round
        kinds = {
            i.labels["kind"]
            for i in by_name["meshstore_collective_ops_total"]
        }
        assert kinds == {"gather", "scatter"}
        line = reg.emit()
        assert lint.check_lines([line]) == []
        bad = line.replace(
            '"component": "meshstore"', '"component": "meshstor"'
        )
        problems = lint.check_lines([bad])
        assert problems and "meshstor" in problems[0][1]
