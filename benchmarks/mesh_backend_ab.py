"""Mesh backend A/B: store_backend="mesh" vs the proc-shard socket path.

PR 16's transport work ended with the honest finding that this host
has no more WIRE to squeeze — shm tied binary TCP because the residual
is serialized server work, not the kernel.  The mesh backend
(meshstore/, docs/meshstore.md) removes the wire entirely: the table
is ONE mesh-sharded device array and pull/push lower to jitted
gather / scatter-add.  This benchmark prices that swap against the
STRONGEST socket baseline — shard processes (``shard_procs=True``,
cluster/procs.py), each shard server in its own spawned process — at
EQUAL worker count, on the same PA workload, and records whether the
two backends agree on the final model (the parity verdict the
``--mesh-ab`` lint requires; a one-armed or verdict-free A/B does not
lint).

Measured per arm:

  * **updates/sec** — valid example lanes through ``driver.run`` per
    wall second (the workload-level rate, both arms over the
    identical seeded stream);
  * **pull/push p50/p99** — host-observed latency of one client's
    ``pull_batch``/``push_batch`` over a fixed 256-id batch
    (duplicates included — the mesh gather routes them, the socket
    client coalesces them; both are that backend's honest cost).

The verdict paragraph is REPORTED, not gated: on this CPU host the
"mesh" is 8 virtual XLA host-platform devices
(``--xla_force_host_platform_device_count=8``) sharing one memory
system — collective routing is a memcpy, not an ICI hop — while the
socket arm pays real process boundaries.  The number that transfers
to TPU is the SHAPE of the win (no serialize/parse/frame in the inner
loop), not its magnitude; the real thing has not been measured on a
chip (ROADMAP S7).

Artifacts: ``results/cpu/mesh_backend_ab.{md,json}`` — the JSON
carries ``ts``/``run_id``, the ``mesh_ab`` section
``tools/check_metric_lines.py --mesh-ab`` lints (both arms + parity
verdict, self-linted before anything is written), and a ``payloads``
list ``tools/bench_history.py`` folds into the perf ledger.

Usage::

    JAX_PLATFORMS=cpu python benchmarks/mesh_backend_ab.py \
        [--rounds 30] [--items 256] [--batch 256] [--workers 2] \
        [--out results/cpu]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# the mesh arm needs >1 device; force the 8-way virtual CPU split
# BEFORE any jax backend initializes (same dance as tests/conftest.py)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

if os.environ.get("FPS_TPU_TESTS") != "1":
    jax.config.update("jax_platforms", "cpu")

LAT_BATCH = 256
LAT_REPS = 150


def _pctl(samples, q) -> float:
    return round(float(np.percentile(np.asarray(samples), q)) * 1e3, 4)


def run_arm(
    backend: str,
    *,
    rounds: int,
    items: int,
    batch: int,
    num_workers: int,
    num_shards: int = 2,
    seed: int = 0,
) -> dict:
    """One arm: the full PA workload through ``driver.run`` (the
    updates/sec number), then a client-surface latency microbench on
    the still-started driver.  ``backend="socket"`` runs the shard
    servers as SPAWNED PROCESSES — the strongest socket baseline, and
    the deployment shape the mesh backend replaces."""
    from flink_parameter_server_tpu.cluster.driver import ClusterConfig
    from flink_parameter_server_tpu.workloads import (
        WorkloadParams,
        build_cluster_driver,
        create_workload,
    )

    wl = create_workload(
        "pa",
        WorkloadParams(rounds=rounds, batch=batch, num_items=items,
                       seed=seed),
    )
    cfg = ClusterConfig(
        store_backend="mesh" if backend == "mesh" else "socket",
        num_shards=num_shards, num_workers=num_workers,
        staleness_bound=0,
        shard_procs=(backend == "socket"),
    )
    driver = build_cluster_driver(wl, config=cfg, registry=False)
    batches = wl.batches()
    lanes = int(sum(np.asarray(b["mask"]).sum() for b in batches))
    rng = np.random.default_rng(7)
    lat_ids = rng.integers(0, wl.capacity, LAT_BATCH).astype(np.int64)
    zero_deltas = np.zeros(LAT_BATCH, np.float32)
    ones_mask = np.ones(LAT_BATCH, bool)
    with driver:
        t0 = time.perf_counter()
        result = driver.run(batches)
        wall = time.perf_counter() - t0
        values = np.asarray(result.values).copy()
        # latency microbench on one worker's client (zero deltas: the
        # parity snapshot above is already taken, and a no-op push
        # prices the same code path)
        client = driver._clients[0]
        for _ in range(10):
            client.pull_batch(lat_ids)
            client.push_batch(lat_ids, zero_deltas, ones_mask)
        pulls, pushes = [], []
        for _ in range(LAT_REPS):
            t = time.perf_counter()
            client.pull_batch(lat_ids)
            pulls.append(time.perf_counter() - t)
            t = time.perf_counter()
            client.push_batch(lat_ids, zero_deltas, ones_mask)
            pushes.append(time.perf_counter() - t)
        stats = result.shard_stats
    return {
        "backend": backend,
        "shard_procs": bool(cfg.shard_procs),
        "updates_per_sec": round(lanes / wall, 1),
        "run_wall_s": round(wall, 4),
        "lanes": lanes,
        "rounds": len(batches),
        "pull_p50_ms": _pctl(pulls, 50),
        "pull_p99_ms": _pctl(pulls, 99),
        "push_p50_ms": _pctl(pushes, 50),
        "push_p99_ms": _pctl(pushes, 99),
        "lat_batch": LAT_BATCH,
        "shard_stats": stats,
        "_values": values,
    }


def _parity(mesh_vals: np.ndarray, socket_vals: np.ndarray) -> dict:
    err = float(np.max(np.abs(mesh_vals - socket_vals))) if (
        mesh_vals.shape == socket_vals.shape
    ) else float("inf")
    if np.array_equal(mesh_vals, socket_vals):
        verdict = "bitwise"
    elif np.allclose(mesh_vals, socket_vals, rtol=1e-4, atol=1e-6):
        verdict = "allclose"
    else:
        verdict = "diverged"
    return {"verdict": verdict, "max_abs_err": err}


def run_mesh_backend_ab(
    *, rounds: int = 30, items: int = 256, batch: int = 256,
    num_workers: int = 2, num_shards: int = 2,
) -> dict:
    if jax.device_count() < 2:
        raise RuntimeError(
            f"mesh_backend_ab needs >1 device for a real mesh arm "
            f"(got {jax.device_count()}: jax initialized before "
            f"--xla_force_host_platform_device_count could apply)"
        )
    common = dict(rounds=rounds, items=items, batch=batch,
                  num_workers=num_workers, num_shards=num_shards)
    socket = run_arm("socket", **common)
    mesh = run_arm("mesh", **common)
    parity = _parity(mesh.pop("_values"), socket.pop("_values"))
    speedup = (
        round(mesh["updates_per_sec"] / socket["updates_per_sec"], 2)
        if socket["updates_per_sec"] else None
    )
    pull_speedup = (
        round(socket["pull_p50_ms"] / mesh["pull_p50_ms"], 2)
        if mesh["pull_p50_ms"] else None
    )
    return {
        "arms": {"mesh": mesh, "socket": socket},
        "parity": parity["verdict"],
        "max_abs_err": parity["max_abs_err"],
        "updates_speedup": speedup,
        "pull_p50_speedup": pull_speedup,
        "workload": "pa",
        "rounds": rounds, "items": items, "batch": batch,
        "num_workers": num_workers, "num_shards": num_shards,
        "devices": jax.device_count(),
        "platform": jax.devices()[0].platform,
    }


def write_artifacts(r: dict, out_dir: str) -> None:
    from flink_parameter_server_tpu.telemetry.registry import (
        default_run_id,
    )
    from tools.check_metric_lines import check_mesh_ab

    mesh, socket = r["arms"]["mesh"], r["arms"]["socket"]
    arm_fields = (
        "backend", "shard_procs", "updates_per_sec", "run_wall_s",
        "lanes", "rounds", "pull_p50_ms", "pull_p99_ms",
        "push_p50_ms", "push_p99_ms", "lat_batch",
    )
    doc = {
        "ts": round(time.time(), 3),
        "run_id": default_run_id(),
        "kind": "mesh_backend_ab",
        "mesh_ab": {
            "arms": {
                k: {f: r["arms"][k][f] for f in arm_fields}
                for k in ("mesh", "socket")
            },
            "parity": r["parity"],
            "max_abs_err": r["max_abs_err"],
            "updates_speedup": r["updates_speedup"],
            "pull_p50_speedup": r["pull_p50_speedup"],
        },
        "payloads": [
            {"metric": "mesh backend updates (mesh)",
             "value": mesh["updates_per_sec"], "unit": "updates/sec"},
            {"metric": "mesh backend updates (proc socket)",
             "value": socket["updates_per_sec"], "unit": "updates/sec"},
            {"metric": "mesh backend pull p50 (mesh)",
             "value": mesh["pull_p50_ms"], "unit": "ms"},
            {"metric": "mesh backend pull p50 (proc socket)",
             "value": socket["pull_p50_ms"], "unit": "ms"},
            {"metric": "mesh backend push p50 (mesh)",
             "value": mesh["push_p50_ms"], "unit": "ms"},
            {"metric": "mesh backend push p50 (proc socket)",
             "value": socket["push_p50_ms"], "unit": "ms"},
        ],
        "workload": {
            "name": r["workload"], "rounds": r["rounds"],
            "items": r["items"], "batch": r["batch"],
            "num_workers": r["num_workers"],
            "num_shards": r["num_shards"],
        },
        "host": {
            "cpus": os.cpu_count(),
            "devices": r["devices"],
            "platform": r["platform"],
        },
    }
    bad = check_mesh_ab(doc)
    if bad:
        raise SystemExit(
            f"mesh_backend_ab: artifact failed its own lint: {bad}"
        )
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "mesh_backend_ab.json"), "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    md = f"""# Mesh backend A/B — store_backend="mesh" vs proc-shard sockets

Same PA workload ({r['rounds']} rounds x {r['batch']} lanes over a
{r['items']}-row table), same {r['num_workers']} workers and BSP
clock, one store backend per arm: the socket arm runs
{r['num_shards']} shard servers in SPAWNED PROCESSES
(`shard_procs=True` — the strongest socket baseline); the mesh arm
holds the whole table as ONE array sharded over {r['devices']}
virtual CPU devices, pull/push lowered to jitted gather/scatter-add
(meshstore/, docs/meshstore.md).  Latency is host-observed on a fixed
{mesh['lat_batch']}-id client batch.

| arm | updates/sec | pull p50 | pull p99 | push p50 | push p99 |
|---|---|---|---|---|---|
| mesh | {mesh['updates_per_sec']} | {mesh['pull_p50_ms']} ms | \
{mesh['pull_p99_ms']} ms | {mesh['push_p50_ms']} ms | \
{mesh['push_p99_ms']} ms |
| proc socket | {socket['updates_per_sec']} | \
{socket['pull_p50_ms']} ms | {socket['pull_p99_ms']} ms | \
{socket['push_p50_ms']} ms | {socket['push_p99_ms']} ms |

**Parity: {r['parity']}** (max abs err {r['max_abs_err']:.3g}) — the
two backends trained the same model on the same stream; the mesh
path's two-worker fp32 interleaving reassociates sums exactly as the
socket path's does, so `allclose` here is the same bar the socket
backend's own two-worker parity test pins (bitwise holds at one
worker on both backends, pinned in tests/test_meshstore.py).

**Verdict (reported, not gated):** mesh ran at
**{r['updates_speedup']}x** the socket arm's update rate and
**{r['pull_p50_speedup']}x** its pull p50 on this host —
{"a win the host flatters" if (r['updates_speedup'] or 0) >= 1
 else "SLOWER here, and that is the expected CPU result"}.  The
{r['devices']} "devices" are XLA host-platform virtual devices
sharing this machine's {os.cpu_count()} CPU core(s) and one memory
system: every jitted gather/scatter is partitioned {r['devices']}
ways and then executed on the SAME cores, all dispatch overhead and
no parallel hardware, while the proc-shard socket arm gets real
OS-process parallelism.  Neither distortion exists on TPU, where the
per-device slices live in separate HBM stacks, the collective rides
ICI, and the costs this backend deletes — frame encode/parse, host
copies, the per-row codec — are exactly the residual PR 16 measured
as unremovable from the socket path.  So the number that transfers
is the parity column and the SHAPE of the cost model, not the
multiple; the real thing (HBM table, ICI collectives) has not been
measured on a chip (ROADMAP S7).

Produced by `benchmarks/mesh_backend_ab.py` on a {os.cpu_count()}-CPU
host; linted by `tools/check_metric_lines.py --mesh-ab`; folded into
the perf ledger by `tools/bench_history.py` (payloads list); pinned
by tests/test_meshstore.py (committed-artifact lint).
"""
    with open(os.path.join(out_dir, "mesh_backend_ab.md"), "w") as f:
        f.write(md)


def main() -> int:
    from flink_parameter_server_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--rounds", type=int, default=30)
    p.add_argument("--items", type=int, default=256)
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--workers", type=int, default=2)
    p.add_argument("--shards", type=int, default=2)
    p.add_argument("--out", default=os.path.join(REPO, "results", "cpu"))
    args = p.parse_args()
    r = run_mesh_backend_ab(
        rounds=args.rounds, items=args.items, batch=args.batch,
        num_workers=args.workers, num_shards=args.shards,
    )
    write_artifacts(r, args.out)
    print(json.dumps({
        "metric": "mesh backend A/B (on-device vs proc-shard sockets)",
        "value": r["updates_speedup"],
        "unit": "x updates/sec speedup",
        "extra": {
            "parity": r["parity"],
            "max_abs_err": r["max_abs_err"],
            "pull_p50_speedup": r["pull_p50_speedup"],
            "mesh_updates_per_sec":
                r["arms"]["mesh"]["updates_per_sec"],
            "socket_updates_per_sec":
                r["arms"]["socket"]["updates_per_sec"],
            "mesh_pull_p50_ms": r["arms"]["mesh"]["pull_p50_ms"],
            "socket_pull_p50_ms": r["arms"]["socket"]["pull_p50_ms"],
            "devices": r["devices"],
            "platform": r["platform"],
        },
    }))
    return 0 if r["parity"] in ("bitwise", "allclose") else 1


if __name__ == "__main__":
    sys.exit(main())
