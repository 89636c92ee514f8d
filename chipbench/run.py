"""Run one cell of BENCHMARK.json on the chip this process is started on.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process each time: build the cell's store and logic through the
program's public entry points from ``--seed``, stage a pool of seeded
batches, then ONE ``StreamingDriver.run`` as users run it, free-running.  Its
first dispatches are set-up (they compile, feed the reference comparison and,
with serving, wait for the first snapshot and warm the query buckets); the
measured window starts at a synced dispatch boundary and lasts ``--seconds``.
With ``--trace 0`` the last stdout line carries the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics from a short profiler trace
inside the window.  Without a TPU (or with fewer chips than the cell asks
for) it exits 2 and prints no result.  ``--cpu-dry-run`` walks the same
control flow at the configuration's ``dry_run`` sizes and prints counts only,
never the result line.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()  # process start, as near as Python lets us see it

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import queue  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

import numpy as np  # noqa: E402

from chipbench import loadgen, peaks, spec, stats  # noqa: E402
from chipbench import trace as trace_mod  # noqa: E402

OUT_DIR = os.path.join(spec.BENCH_DIR, "out")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device")


class RunFailed(RuntimeError):
    """The run cannot stand as a measurement: no result line, exit 1."""


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for: no result line, exit 2."""


def log(*parts) -> None:
    print("[chipbench]", *parts, file=sys.stderr, flush=True)


def result_line(
    correct: bool, attempted: int, failed: int, metrics: dict, device: dict,
    breakdown=None,
) -> str:
    """The last stdout line: the contract's keys and no others."""
    line = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {
            name: {"value": float(value), "unit": unit}
            for name, (value, unit) in metrics.items()
        },
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    return json.dumps(line)


class CompileCounter:
    """Counts XLA compilations (cache loads included) as JAX reports them."""

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.count += 1


class Window:
    """The driver-side instruments: the batch source, the per-dispatch group
    hook and the thread that watches each dispatch's outputs become ready.

    The stream goes on at batch ``first`` of the pool.  ``on_warm(table,
    state)`` runs once at the last set-up dispatch (device idle);
    ``on_start(t)`` at the window's first instant.
    """

    def __init__(
        self, pool, *, first: int, warmup: int, seconds: float, on_warm,
        on_start, trace_plan, compiles: CompileCounter,
    ):
        import jax

        self.jax = jax
        self.pool, self.first, self.warmup = pool, first, warmup
        self.seconds = seconds
        self.on_warm, self.on_start = on_warm, on_start
        self.trace_plan, self.compiles = trace_plan, compiles
        self.dispatches = 0
        self.t_start = self.deadline = self.t_end = None
        self.compiles_at_start = self.compiles_at_deadline = None
        self.handover = None
        self.samples = []  # (handed over, outputs ready, steps) per dispatch
        self.ingest_ms = []
        self.trace_started = None  # perf_counter when the profiler started
        self.traced = False  # a trace was recorded and stopped
        self._ready = queue.SimpleQueue()
        self._watcher = threading.Thread(
            target=self._watch, name="chipbench-watcher", daemon=True
        )
        self._watcher.start()
        self._note = None  # open TraceAnnotation between two hooks
        self._window_note = None

    # -- ingest: runs on the driver's prefetch thread ----------------------
    def source(self):
        note = self.jax.profiler.TraceAnnotation
        i = self.first  # the stream goes on where the checked batches ended
        while True:
            t0 = time.perf_counter()
            with note("chipbench.ingest"):
                if self.deadline is not None and t0 >= self.deadline:
                    self.compiles_at_deadline = self.compiles.count
                    return
                batch = self.pool[i % len(self.pool)]
                i += 1
            self.ingest_ms.append((time.perf_counter() - t0) * 1e3)
            yield batch

    # -- outputs ready: its own thread, so the trainer never syncs ---------
    def _watch(self):
        while True:
            item = self._ready.get()
            if item is None:
                return
            handover, steps, outs = item
            self.jax.block_until_ready(outs)
            self.samples.append((handover, time.perf_counter(), steps))

    # -- once per dispatch, on the training thread -------------------------
    def hook(self, global_step, n_steps, table, state, outs):
        jax, note = self.jax, self.jax.profiler.TraceAnnotation
        if self._note is not None:
            self._note.__exit__(None, None, None)
            self._note = None
        if self._window_note is not None and (
            time.perf_counter() >= self.trace_started + self.trace_plan["seconds"]
        ):
            self._stop_trace(outs)
        with note("chipbench.hook"):
            self.dispatches += 1
            if self.t_start is not None:
                self._ready.put((self.handover, n_steps, outs))
            elif self.dispatches >= self.warmup:
                jax.block_until_ready((table, state, outs))
                self.on_warm(table, state)
                self.compiles_at_start = self.compiles.count
                self.t_start = time.perf_counter()
                self.deadline = self.t_start + self.seconds
                self.on_start(self.t_start)
        if self.trace_plan and self.trace_started is None and self.t_start and (
            time.perf_counter() >= self.t_start + self.trace_plan["after"]
        ):
            jax.profiler.start_trace(self.trace_plan["dir"])
            self.trace_started = time.perf_counter()
            self._window_note = note(trace_mod.WINDOW)
            self._window_note.__enter__()
        # from here to the next hook the driver takes a batch from its
        # queue, dispatches the step and (with serving) offers a publish
        self._note = note("chipbench.driver_dispatch")
        self._note.__enter__()
        self.handover = time.perf_counter()

    def _stop_trace(self, outs=None):
        """End the traced window at this instant; what the device still has
        queued finishes before the profiler stops, so its events are whole."""
        self._window_note.__exit__(None, None, None)
        self._window_note = None
        if outs is not None:
            self.jax.block_until_ready(outs)
        self.jax.profiler.stop_trace()
        self.traced = True

    def close(self):
        if self._note is not None:
            self._note.__exit__(None, None, None)
            self._note = None
        if self._window_note is not None:  # the stream ended first
            self._stop_trace()
        self._ready.put(None)
        self._watcher.join()
        if self.samples:
            self.t_end = self.samples[-1][1]


def _stage(batches, mesh, device):
    """Host batches -> device-ready ones, replicated over the cell's mesh
    (the program reshards over ``dp`` itself where it has one)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    where = NamedSharding(mesh, PartitionSpec()) if mesh is not None else device
    staged = [jax.device_put(b, where) for b in batches]
    jax.block_until_ready(staged)
    return staged


def _answers_well_formed(answers, num_items: int, k: int) -> int:
    """How many answers are NOT k distinct in-range ids with finite scores."""
    if not answers:
        return 0
    ids = np.stack([np.asarray(a.item_ids) for _, a in answers])
    scores = np.stack([np.asarray(a.scores, np.float32) for _, a in answers])
    srt = np.sort(ids, axis=1)
    ok = (
        (ids.shape[1] == k)
        & (ids >= 0).all(axis=1) & (ids < num_items).all(axis=1)
        & (srt[:, 1:] != srt[:, :-1]).all(axis=1)
        & np.isfinite(scores).all(axis=1)
    )
    return int((~ok).sum())


def _devices(chips: int, dry: bool, workload: str, mark):
    """The devices JAX finds; no result without the chips the cell asks for."""
    if dry:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={chips}"
            ).strip()

    import jax

    mark("import_jax")
    if dry:
        jax.config.update("jax_platforms", "cpu")
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no device: {e}") from None
    mark("device_start")
    if not dry and (devices[0].platform != "tpu" or len(devices) < chips):
        raise NoChip(
            f"{workload} needs {chips} TPU chip(s); JAX reports "
            f"{len(devices)} x {devices[0].platform}"
        )
    return devices


def _query_load(cfg, queries, driver, seed, seconds):
    """Serving attached as users attach it, and the open loop's schedule."""
    service = driver.serve_with(**cfg["serving"])
    rate = float(queries["rate_per_s"])
    arrivals = loadgen.poisson_arrivals(rate, seconds, seed=seed + 1)
    users = loadgen.zipf_users(
        len(arrivals), cfg[queries["population"]], float(queries["zipf_s"]),
        seed=seed + 2,
    )
    k = int(queries["k"])
    gen = loadgen.OpenLoop(
        lambda u: service.submit_topk(u, k), arrivals, users,
        threads=int(queries["threads"]),
    )

    def warm(table, state):
        # the traffic's own set-up: a snapshot that carries worker state,
        # and every bucket shape the batcher can hand the query kernel
        if not service.wait_for_snapshot(60.0, min_version=2):
            raise RunFailed("no mid-training snapshot within 60 s")
        for bucket in service.batcher.buckets:
            service.engine.top_k(np.zeros(bucket, np.int32), k)

    return service, gen, users, rate, warm


def _check_queries(cfg, fam, queries, service, gen, users) -> list:
    """Every answer of the window well formed; then, training stopped, fresh
    answers against numpy on the final snapshot."""
    failures = []
    k = int(queries["k"])
    if not gen.join(timeout_s=60.0):
        failures.append("a load generator thread did not end")
    bad = _answers_well_formed(gen.answers, cfg["num_items"], k)
    if bad:
        failures.append(f"{bad} answers out of range, repeated or not finite")
    client = service.client()
    sample = [
        (int(u), client.top_k(int(u), k=k, timeout=60.0))
        for u in users[: int(queries["check_answers"])]
    ]
    wrong = fam.topk_check(
        cfg, service.snapshots.latest(), sample,
        rtol=float(queries["rtol"]), atol=float(queries["atol"]),
    )
    if wrong:
        failures.append(f"{wrong} of {len(sample)} top-K answers wrong")
    return failures


def _check_rows(check, reference, got, before) -> tuple:
    """The rows the checked batches touched, against the plain reference
    (its rows after the batches and how far it moved each element in all):
    ``|got - want|`` may reach ``delta_rtol`` of that movement, plus
    ``delta_atol`` (what rounding inside an example's sums leaves in a delta
    that cancellation made small), plus ``row_ulps`` float32 roundings of the
    row value the deltas land in.
    Returns the failures and the element that came nearest its allowance
    (``share`` of it, and which term gave the room)."""
    want, moved = reference
    failures, worst = [], {"share": 0.0}
    ulp = float(check["row_ulps"]) * float(np.finfo(np.float32).eps)
    for name, expected in want.items():
        for_deltas = float(check["delta_rtol"]) * moved[name] + float(
            check["delta_atol"]
        )
        for_row = ulp * np.maximum(np.abs(expected), np.abs(before[name]))
        off = np.abs(got[name] - expected)
        share = off / np.maximum(for_deltas + for_row, 1e-30)
        share = np.where(np.isfinite(share), share, np.inf)
        at = np.unravel_index(np.argmax(share), share.shape)
        if not share[at] <= worst["share"]:
            worst = {
                "share": float(share[at]), "rows": name, "off": float(off[at]),
                "allowed_for_deltas": float(for_deltas[at]),
                "allowed_for_row": float(for_row[at]),
            }
        if not share[at] <= 1.0:
            failures.append(
                f"{name} rows changed otherwise than the plain reference's "
                f"({share[at]:.3g} x the allowance)"
            )
        if np.array_equal(got[name], before[name]):
            failures.append(f"{name} rows unchanged by training")
    return failures, worst


def _all_finite(tree) -> bool:
    import jax
    import jax.numpy as jnp

    leaves = [
        x for x in jax.tree.leaves(tree) if jnp.issubdtype(x.dtype, jnp.floating)
    ]
    return bool(jax.jit(
        lambda *xs: jnp.stack([jnp.isfinite(x).all() for x in xs]).all()
    )(*leaves))


def _layer_metrics(bench, workload, ctx) -> dict:
    """Each per-layer metric of the cell from its own reader; a reader that
    finds nothing to read leaves its metric out of the line."""
    out = {}
    for m in spec.metrics_of(bench, "per_layer", workload):
        reader = spec.metric_reader(m["name"])
        value = reader.read(ctx) if reader else None
        if value is not None:
            out[m["name"]] = (value, m["unit"])
    return out


def run_cell(bench: dict, args) -> dict:
    dry = args.cpu_dry_run
    cell = spec.resolve(bench, args.workload, dry_run=dry)
    cfg, traffic, chips = cell["cfg"], cell["traffic_spec"], int(cell["chips"])
    marks = [("start", T0)]

    def mark(name):
        marks.append((name, time.perf_counter()))

    devices = _devices(chips, dry, args.workload, mark)

    import jax

    from flink_parameter_server_tpu import DriverConfig, StreamingDriver
    from flink_parameter_server_tpu.parallel.mesh import make_mesh
    from flink_parameter_server_tpu.telemetry.spans import get_tracer
    from flink_parameter_server_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    cache_dir = enable_compile_cache()
    compiles = CompileCounter()
    used = devices[:chips]
    mesh = None
    if cfg.get("mesh"):
        mesh = make_mesh(
            worker_parallelism=cfg["mesh"]["dp"],
            ps_parallelism=cfg["mesh"]["ps"], devices=used,
        )
    seed, seconds = int(args.seed), float(args.seconds)
    fam = spec.family(cfg["family"])
    ref = spec.reference(cfg)
    check = cfg["reference"]
    mark("import_program")

    # -- set-up: tables on the device from the seed, the pool, the rows the
    # reference comparison will follow (PRNGKey takes 32 signed bits)
    logic, store = fam.build(cfg, seed % (2**31 - 1), mesh)
    host_pool = fam.host_batches(cfg, traffic, seed, int(cfg["pool_batches"]))
    mark("tables_and_batches")
    pool = _stage(host_pool, mesh, used[0])
    check_batches = host_pool[: int(check["batches"])]
    touched = ref.touched(check_batches)
    state0 = logic.init_state(jax.random.PRNGKey(0))
    rows_before = fam.rows(store, state0, touched)
    del state0
    mark("staged_rows_before")

    driver = StreamingDriver(logic, store, config=DriverConfig(**cfg["driver"]))
    del store  # the driver owns it now: no second table stays alive for us
    queries = traffic.get("queries")
    service = gen = users = rate = None
    warm = start = lambda *_: None  # noqa: E731
    warmup = int(traffic["warmup_dispatches"])
    if queries:
        service, gen, users, rate, warm = _query_load(
            cfg, queries, driver, seed, seconds
        )
        start = gen.start
        warmup = max(warmup, int(cfg["serving"]["publish_every"]) + 4)
    trace_plan = None
    if args.trace:
        trace_plan = {
            "dir": os.path.join(OUT_DIR, "trace", args.workload),
            "after": min(float(traffic["trace_after_s"]), seconds / 4),
            "seconds": min(float(traffic["trace_seconds"]), seconds / 2),
        }
        shutil.rmtree(trace_plan["dir"], ignore_errors=True)

    # the first batches of the stream go through the system on their own
    # (this compiles the step): the rows they touch are held against the
    # plain reference after the window
    checked = driver.run(iter(pool[: len(check_batches)]))
    rows_after = fam.rows(checked.store, checked.worker_state, touched)
    del checked
    mark("checked_batches")
    window = Window(
        pool, first=len(check_batches), warmup=warmup, seconds=seconds,
        on_warm=warm, on_start=start, trace_plan=trace_plan, compiles=compiles,
    )
    driver.add_group_hook(window.hook)
    get_tracer().clear()
    try:
        # ... and the stream goes on, on the same driver
        result = driver.run(window.source())
        jax.block_until_ready((result.store.table, result.worker_state))
    finally:
        window.close()
    if window.t_start is None or not window.samples:
        raise RunFailed("the stream ended before the window began")
    # the close-time publish runs after the last dispatch: count what
    # compiled from the window's start until the source stopped feeding it
    compiled_inside = window.compiles_at_deadline - window.compiles_at_start
    if compiled_inside:
        raise RunFailed(
            f"{compiled_inside} compilation(s) inside the measured window"
        )
    window_s = window.t_end - window.t_start
    samples = window.samples
    records = sum(n for _, _, n in samples) * int(cfg["batch"])

    # -- after the window: correctness -------------------------------------
    failures, worst = _check_rows(
        check, ref.apply(cfg, rows_before, touched, check_batches),
        rows_after, rows_before,
    )
    finite = _all_finite((result.store.table, result.worker_state))
    if not finite:
        failures.append("table or worker state not finite after the window")
    attempted, failed = len(samples), 0 if finite else 1
    fill = None
    if gen:
        failures += _check_queries(cfg, fam, queries, service, gen, users)
        attempted += gen.attempted
        failed += gen.failed
        fill = service.metrics.batch_fill()
        service.stop()

    # -- numbers -----------------------------------------------------------
    peak = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used
    )
    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "memory_peak_bytes": int(peak),
    }
    end_to_end = {
        "updates_per_s_chip": stats.rate_per_chip(records, window_s, chips),
        "pull_push_p50_ms": stats.median(
            [(ready - handed) * 1e3 for handed, ready, _ in samples]
        ),
        "query_p95_ms": stats.percentile(gen.latency_ms, 95) if gen else None,
        "setup_s": window.t_start - T0,
    }
    info = {
        "workload": args.workload, "seed": seed, "window_s": window_s,
        "mesh": dict(mesh.shape) if mesh is not None else None,
        "dispatches": len(samples), "records": records,
        "warmup_dispatches": window.dispatches - len(samples),
        "reference_worst": worst, "failures": failures,
        "cache_dir": cache_dir, "compiles_total": compiles.count,
        "setup_phases_s": {
            name: round(t - before, 3) for (_, before), (name, t)
            in zip(marks, marks[1:] + [("warmup", window.t_start)])
        },
        "queries": None if not gen else {
            "rate_per_s": rate, "attempted": gen.attempted,
            "failed": gen.failed, "errors": gen.errors,
            "p50_ms": stats.median(gen.latency_ms),
            "p95_ms": stats.percentile(gen.latency_ms, 95),
            "p99_ms": stats.percentile(gen.latency_ms, 99),
            "late_p99_ms": stats.percentile(gen.late_ms, 99),
            "batch_fill": fill,
        },
        "end_to_end": end_to_end,
    }
    breakdown = None
    if not args.trace:
        metrics = {}
        for m in spec.metrics_of(bench, "end_to_end", args.workload):
            if end_to_end.get(m["name"]) is None:
                raise RunFailed(f"no value for end-to-end {m['name']}")
            metrics[m["name"]] = (end_to_end[m["name"]], m["unit"])
    else:
        reduced = None
        if window.traced:
            planes = trace_mod.read_xplane(trace_mod.find_xplane(trace_plan["dir"]))
            if args.dump_events:
                with open(args.dump_events, "w") as f:
                    json.dump(planes, f)
            log(trace_mod.summary(planes))
            reduced = trace_mod.reduce(planes, fam.STEP_PROGRAM)
        if reduced is None and not dry:
            raise RunFailed("the traced window holds no device operation")
        metrics = _layer_metrics(bench, args.workload, {
            "cfg": cfg, "traffic": traffic, "chips": chips, "trace": reduced,
            "peaks": None if dry else peaks.peaks_for(device["kind"]),
            "spans": [
                s for s in get_tracer().spans()
                if window.t_start <= s["start"] < window.t_end
            ],
            "counters": {
                "ingest_ms": window.ingest_ms[-len(samples):],
                "query_latency_ms": gen.latency_ms if gen else [],
                "late_ms": gen.late_ms if gen else [],
                "batch_fill": fill,
                "peak_hbm_bytes": peak,
                "hbm_bytes_per_step": fam.hbm_bytes_per_step(cfg),
            },
        })
        if reduced:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            breakdown = reduced["breakdown"]
            info["trace"] = {k: v for k, v in reduced.items() if k != "breakdown"}
    info["metrics"] = {k: v for k, (v, _) in metrics.items()}
    return {
        "correct": not failures, "attempted": attempted, "failed": failed,
        "metrics": metrics, "device": device, "breakdown": breakdown,
        "info": info,
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument(
        "--cpu-dry-run", action="store_true",
        help="tiny sizes on the CPU: control flow only, no result line",
    )
    parser.add_argument(
        "--dump-events", default=None,
        help="with --trace 1: write the trace's plain events to this file",
    )
    args = parser.parse_args(argv)
    bench = spec.load_benchmark()
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])
    os.makedirs(OUT_DIR, exist_ok=True)
    try:
        out = run_cell(bench, args)
    except NoChip as e:
        log(f"{e} (--cpu-dry-run walks the control flow off the chip)")
        return 2
    except RunFailed as e:
        log(f"run failed: {e}")
        return 1
    log(json.dumps(out["info"]))
    if args.cpu_dry_run:
        print(json.dumps({
            "dry_run": "cpu: proves control flow only, nothing about the chip",
            "correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metric_names": sorted(out["metrics"]),
            "failures": out["info"]["failures"],
        }))
        return 0 if out["correct"] else 1
    print(result_line(
        out["correct"], out["attempted"], out["failed"], out["metrics"],
        out["device"], out["breakdown"],
    ), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
